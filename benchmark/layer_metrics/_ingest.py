"""What the set-up readers share: the program's own ``Timed`` phases
(``photon_tpu/utils/timing.py``; recorded with telemetry on or off, on
``time.perf_counter``, the clock of the kind's ``fits`` samples), summed by
prefix over the process. A phase times what the HOST spent in a call and
waits for no device; one estimator is prepared a run, so the sum is that
job's. A program from before it had phases records none."""


def phase_seconds(prefix):
    from photon_tpu.utils import timing

    found = [seconds for label, seconds in timing.timing_records()
             if label.startswith(prefix)]
    return sum(found) if found else None
