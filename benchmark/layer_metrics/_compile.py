"""What the compile-account readers share: the program's always-on counter
``compile.seconds{stage, during}`` and its buffer of the newest outermost
stage events (``photon_tpu/utils/compile_cache.py``), fed by JAX's own
``jax.monitoring`` events where JAX traces, lowers, loads or compiles a
program. ``stage`` is ``trace`` | ``lower`` | ``cache_load`` | ``backend``;
``during`` is the ``Timed`` phase the host stood in (``ingest/prepare``,
``ingest/h2d``, ``ingest/stats``), else ``none``. Seconds are an event's OWN
(nested trace events are not counted twice), so a stage's sum is wall time.
A program from before it kept the account has neither, and every reader
returns ``None``."""


def seconds(stage):
    """``compile.seconds{stage}`` summed over ``during`` for the process;
    0.0 where the account is kept and nothing matched, ``None`` where the
    program keeps no account."""
    from photon_tpu.obs.metrics import registry
    from photon_tpu.utils import compile_cache

    if not hasattr(compile_cache, "account_compiles"):
        return None
    return sum((value for labels, value
                in registry.series("compile.seconds")
                if labels["stage"] == stage), 0.0)


def traces_between(windows):
    """How many of the buffer's ``trace`` events began inside one of
    ``windows``, ``(start, end)`` pairs on ``time.perf_counter``: an event's
    ``start_unix`` is moved onto that clock through ``obs/spans``' epoch
    pair, read at one instant. ``None`` where the program keeps no buffer."""
    from photon_tpu.obs import spans
    from photon_tpu.utils import compile_cache

    if not hasattr(compile_cache, "programs"):
        return None
    shift = spans._EPOCH_PERF - spans._EPOCH_UNIX
    return sum(1 for event in compile_cache.programs()
               if event["stage"] == "trace"
               and any(start <= event["start_unix"] + shift <= end
                       for start, end in windows))
