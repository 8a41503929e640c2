"""Seconds from the start of the process to the program's first import: the
interpreter, ``import jax`` and reaching the chip (``jax.devices()``), none
of it code of the program. ``obs/spans`` reads its process epoch on
``time.perf_counter`` when it is first imported, which ``run.py`` does right
after ``jax.devices()``; ``run.py`` reads ``_PROCESS_START`` on the same
clock as its first statement. ``setup_s`` less this is what the program and
the kind's set-up took. ``None`` where ``__main__`` has no
``_PROCESS_START`` (the harness imported, not run)."""

import sys

LAYER = "device"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run):
    from photon_tpu.obs import spans

    started = getattr(sys.modules.get("__main__"), "_PROCESS_START", None)
    epoch = getattr(spans, "_EPOCH_PERF", None)
    if started is None or epoch is None:
        return None
    return epoch - started
