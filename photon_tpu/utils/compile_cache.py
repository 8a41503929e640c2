"""Persistent XLA compilation cache across processes.

The in-process jitcache (utils/jitcache.py) removes re-traces within one
run; this module removes re-COMPILES across runs. A GAME fit's cold start
is compile-dominated (the CD loop jits one solve per coordinate x config
shape), so the first run of a driver on a fresh host pays tens of seconds
that every later run can skip by loading serialized XLA executables from
disk.

The reference has no analog (JVM/Spark JITs incrementally); on TPU this is
the standard deployment answer: ``jax.config.jax_compilation_cache_dir``.
"""

from __future__ import annotations

import logging
import os

from photon_tpu.obs.metrics import registry as _metrics

_logger = logging.getLogger("photon_tpu.compile_cache")

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
ENV_OPT_OUT = "PHOTON_TPU_NO_XLA_CACHE"

# a cache that moves between runs never hits, so the default is one fixed
# place per checkout: resolved from the package location, never from
# $HOME, a pid, a temp name or the time (gitignored; tests/conftest.py
# points the test suite at the same directory)
_CHECKOUT_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    ".jax_compile_cache"))


def cache_dir() -> str:
    """Where this run's compiled programs are kept: exactly the directory
    ``JAX_COMPILATION_CACHE_DIR`` names when it is set (the machine's
    owner placed the cache), else ``<checkout>/.jax_compile_cache``."""
    return os.environ.get(ENV_CACHE_DIR) or _CHECKOUT_DIR


def enable_persistent_cache() -> str:
    """Enable JAX's on-disk compilation cache (idempotent) and return the
    directory in use. With ``JAX_COMPILATION_CACHE_DIR`` set JAX itself
    reads the directory from the environment and this function sets only
    thresholds; no directory is ever set in code over the variable.

    Call before the first jit compilation for maximum effect; later
    calls still help future jits.
    """
    import jax

    path = cache_dir()
    os.makedirs(path, exist_ok=True)
    if not os.environ.get(ENV_CACHE_DIR):
        jax.config.update("jax_compilation_cache_dir", path)
    # cache everything: a GAME run is many small executables (one solve
    # per coordinate x block-shape set, one scorer per bucket). On a v5e
    # 128 of the smoke's 146 programs compiled in under 0.2 s each and
    # together were the 11 s a warm cache still paid at that threshold;
    # tracing/lowering is NOT covered by this cache, so every skipped
    # compile just adds to the uncacheable floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # activation is observable: the gauge says whether the persistent cache
    # is on, and the log line says where it lives (debuggability contract —
    # "was the cache even active for this run?")
    _metrics.gauge("compile_cache.enabled").set(1)
    _metrics.counter("compile_cache.activations").inc()
    _logger.info("persistent XLA compilation cache enabled at %s", path)
    return path


def _held_to_cpu() -> bool:
    """``JAX_PLATFORMS=cpu`` (or the config's equal): read without
    starting a backend."""
    import jax

    return jax.config.jax_platforms == "cpu"


def maybe_enable() -> str | None:
    """Entry-point hook every driver goes through: enable the cache
    unless the user opted out via ``PHOTON_TPU_NO_XLA_CACHE``. On a CPU
    run a cache that cannot be enabled (unwritable directory) is a
    warning; on any other backend it is an error — a chip run that
    silently recompiles everything pays minutes per process. Where the
    process is not held to the CPU it also starts the kernels' toolchain
    importing in the background (``pallas_glm.prefetch_toolchain``): a
    driver calls this first and reads its data next."""
    import jax

    if not _held_to_cpu():
        from photon_tpu.ops import pallas_glm
        pallas_glm.prefetch_toolchain()
    if os.environ.get(ENV_OPT_OUT):
        _metrics.counter("compile_cache.disabled", reason="env_opt_out").inc()
        _metrics.gauge("compile_cache.enabled").set(0)
        _logger.info("persistent XLA cache disabled via %s", ENV_OPT_OUT)
        return None
    try:
        return enable_persistent_cache()
    except OSError as e:
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                f"persistent XLA cache at {cache_dir()!r} cannot be enabled "
                f"on backend {jax.default_backend()!r}: {e!r} (set "
                f"{ENV_CACHE_DIR} to a writable directory, or {ENV_OPT_OUT}=1 "
                f"to run without one)") from e
        _metrics.counter("compile_cache.disabled", reason="error").inc()
        _metrics.gauge("compile_cache.enabled").set(0)
        _logger.warning("persistent XLA cache unavailable: %r", e)
        return None


# ---------------------------------------------------------------------------
# warmup accounting (serving contract: zero steady-state compiles)
# ---------------------------------------------------------------------------

# compile-phase flag: builds that happen inside warmup() are expected and
# budgeted at model-load time; any build outside is a steady-state compile
# — for a serving process that is an SLO violation, and
# scripts/check_serving_no_recompile.py fails on it.
_warmup_depth = 0


def in_warmup() -> bool:
    return _warmup_depth > 0


def record_compile(what: str = "program") -> None:
    """Count one program build under the current phase. Called by the
    jitcache on every build; serving asserts
    ``compiles{phase="steady_state"}`` stays zero after warmup."""
    phase = "warmup" if in_warmup() else "steady_state"
    _metrics.counter("compile_cache.compiles", phase=phase, what=what).inc()


def compile_counts() -> dict:
    """{"warmup": n, "steady_state": m} across all ``what`` labels."""
    out = {"warmup": 0.0, "steady_state": 0.0}
    for key, val in _metrics.snapshot()["counters"].items():
        if key.startswith("compile_cache.compiles{"):
            for phase in out:
                if f'phase="{phase}"' in key:
                    out[phase] += val
    return out


def warmup(buckets, compile_fn) -> int:
    """Pre-compile one program per bucket at model-load time.

    ``compile_fn(bucket)`` must actually execute the jitted program for
    that bucket (a dispatch on dummy inputs of the bucket's padded shape),
    not just lower it — only a real call populates jit's executable cache
    so steady-state traffic reuses it. Builds inside this call are counted
    as ``compile_cache.compiles{phase="warmup"}``; everything after is
    steady-state. Returns the number of buckets warmed. Reentrant (an
    engine warming several coordinates nests safely).
    """
    global _warmup_depth
    _warmup_depth += 1
    try:
        n = 0
        for b in buckets:
            compile_fn(b)
            n += 1
        return n
    finally:
        _warmup_depth -= 1
