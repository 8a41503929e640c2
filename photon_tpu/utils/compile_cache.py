"""Persistent XLA compilation cache across processes.

The in-process jitcache (utils/jitcache.py) removes re-traces within one
run; this module removes re-COMPILES across runs. A GAME fit's cold start
is compile-dominated (the CD loop jits one solve per coordinate x config
shape), so the first run of a driver on a fresh host pays tens of seconds
that every later run can skip by loading serialized XLA executables from
disk.

The reference has no analog (JVM/Spark JITs incrementally); on TPU this is
the standard deployment answer: ``jax.config.jax_compilation_cache_dir``.

It also keeps the job's COMPILE ACCOUNT (PERF.md §3, layer ``compile``):
what JAX spent tracing, lowering, loading from the cache and compiling,
counted where JAX reports it (``jax.monitoring``) and booked under the
``Timed`` phase the host stood in. Always on, like ``ingest.h2d_bytes``;
the listeners run only when JAX traces, lowers or compiles, never on jit's
fast path. Read it with ``obs.metrics.snapshot()["counters"]``,
:func:`programs` or :func:`report_section` (a RunReport's ``compile``).
"""

from __future__ import annotations

import collections
import contextlib
import logging
import os
import threading
from typing import Any, Deque, Dict, List, Tuple

from photon_tpu.obs import spans as _spans
from photon_tpu.obs.metrics import registry as _metrics
from photon_tpu.utils import timing as _timing

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


@contextlib.contextmanager
def compiled_in_this_process():
    """Programs compiled inside are never written to the persistent cache,
    so no later process is served them from it: each compiles its own.

    For a program whose OUTPUT layout is stated. On this stack (JAX 0.9.0,
    the TPU's PJRT plugin) an executable the persistent cache serves hands
    out such an output in the stated layout and LABELS it with the
    device's default one (``x.format`` reads column-major over rows-major
    bytes), and the next program, compiled for the label, is refused its
    argument (``INVALID_ARGUMENT: expected parameter 1 of size 4240384000
    ... but got buffer with incompatible size 4341760000``; PERF.md §6,
    PR 37). A program compiled in the process labels its output rightly.

    The switch is JAX's process-wide write threshold
    (``jax_persistent_cache_min_compile_time_secs``), not a per-thread
    one: a program another thread compiles meanwhile is not written either
    (it is compiled again by the next process, nothing worse), and reads of
    the cache go on. Keep the body to the one small program; what it
    returns is checked by its caller (``game/dataset.store_rows_major``
    reads the label back and drops a mislabelled array).
    """
    import jax

    name = "jax_persistent_cache_min_compile_time_secs"
    was = getattr(jax.config, name)
    jax.config.update(name, float("inf"))
    try:
        yield
    finally:
        jax.config.update(name, was)


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

    account_compiles()
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
    account_compiles()      # a library user never calls maybe_enable()
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


# ---------------------------------------------------------------------------
# the compile account: JAX's own stages, booked under the job's phases
# ---------------------------------------------------------------------------

# what JAX 0.9.0 sends (jax/_src/dispatch.py:60-62, 184-215;
# compiler.py:435-452). A stage event sends a scalar (its start) when the
# stage BEGINS and, when it ends, a duration and a time span (start and end
# on ``time.time``); the span carries everything the duration does, so the
# span listener books it and no duration listener is registered.
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_OUTCOMES = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}

MAX_PROGRAMS = 256
# (fun_name, stage, seconds, start_unix, during), newest last
_PROGRAMS: Deque[Tuple[str, str, float, float, str]] = collections.deque(
    maxlen=MAX_PROGRAMS)
_ACCOUNT_LOCK = threading.Lock()
_accounting = False


# per thread. ``open``: one entry a stage event that has begun and not
# ended, innermost last, holding the seconds of the events that ended
# inside it. Stage events NEST: tracing a solve traces every jitted
# function it calls (5,921 stage events for the 86 programs of
# ``glmix-ml20m``, 12,077 for ``glmix-ml20m-lbfgs``: PR 35's count), and
# each reports its whole span, so a sum of durations counts an inner trace
# once for itself and once for every trace around it. What is booked is an
# event's OWN seconds, its span less the spans that ended inside it: the
# stages then sum to the wall time the pipeline took. ``own``: the own
# seconds of the nested events, by stage, until the outermost event around
# them closes and books them with its own, so a nested event touches no
# lock, no counter and no phase. ``hit``: the persistent cache served the
# backend event that is open.
class _OpenStages(threading.local):
    def __init__(self):
        self.open: List[float] = []
        self.own: Dict[str, float] = {}
        self.hit = False


_THREAD = _OpenStages()


def _on_scalar(event: str, value: float, **_: Any) -> None:
    if event in _STAGES:
        _THREAD.open.append(0.0)


def _on_event(event: str, **_: Any) -> None:
    outcome = _CACHE_OUTCOMES.get(event)
    if outcome is None:
        return
    _metrics.counter("compile.cache", outcome=outcome).inc()
    # JAX reports the outcome INSIDE the backend-compile event of the same
    # program on the same thread (pxla.py: compile_or_get_cached under
    # BACKEND_COMPILE_EVENT); remember it for the span that follows
    _THREAD.hit = outcome == "hit"


def _on_time_span(event: str, start: float, end: float, fun_name: str = "",
                  **_: Any) -> None:
    stage = _STAGES.get(event)
    if stage is None:
        return
    thread = _THREAD
    stack, own = thread.open, thread.own
    seconds = end - start
    # empty where the event began before the listeners did
    inside = stack.pop() if stack else 0.0
    if stage == "backend" and thread.hit:
        # the persistent cache served this program: the event held the
        # key's hashing, the read and the deserialisation, and no compile
        thread.hit = False
        stage = "cache_load"
    own[stage] = own.get(stage, 0.0) + seconds - inside
    if stack:                       # nested: its span is the outer's too
        stack[-1] += seconds
        return
    # an outermost event is a program's: book what ended with it, count
    # it and keep its whole span
    during = _timing.current_phase()
    for ended, ended_seconds in own.items():
        # ``time.time`` may step back, and a counter refuses a negative
        _metrics.counter("compile.seconds", stage=ended,
                         during=during).inc(max(ended_seconds, 0.0))
    own.clear()
    _metrics.counter("compile.programs", stage=stage, during=during).inc()
    _PROGRAMS.append((fun_name, stage, seconds, start, during))
    _spans.record(f"compile/{stage}", start, seconds, fun=fun_name,
                  during=during)


def account_compiles() -> None:
    """Register the three ``jax.monitoring`` listeners, once a process
    however often it is called (``maybe_enable`` and the jitcache's
    builds call it). They feed ``compile.seconds{stage, during}``, an
    event's own seconds, and ``compile.programs{stage, during}``, the
    outermost events, with ``stage`` one of ``trace`` (to a jaxpr),
    ``lower`` (jaxpr to MLIR), ``cache_load`` (a backend event the
    persistent cache served) and ``backend`` (one that compiled) and
    ``during`` = ``utils/timing.current_phase()``; ``compile.cache
    {outcome=hit|miss}``; and the newest ``MAX_PROGRAMS`` outermost events
    in :func:`programs`. With telemetry on each of those is also a span
    ``compile/<stage>`` (attributes ``fun``, ``during``). JAX calls a
    listener only where it traces, lowers or compiles: a call on jit's
    fast path reaches none."""
    global _accounting
    if _accounting:
        return
    with _ACCOUNT_LOCK:
        if _accounting:
            return
        import jax.monitoring as monitoring

        monitoring.register_scalar_listener(_on_scalar)
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_time_span_listener(_on_time_span)
        _accounting = True


def programs() -> List[Dict[str, Any]]:
    """The newest ``MAX_PROGRAMS`` outermost stage events, oldest first;
    ``seconds`` is the event's whole span and ``start_unix`` its start on
    ``time.time`` (``obs/spans``' epoch pair moves it onto
    ``time.perf_counter``)."""
    return [{"fun": fun, "stage": stage, "seconds": seconds,
             "start_unix": start, "during": during}
            for fun, stage, seconds, start, during in list(_PROGRAMS)]


def clear_programs() -> None:
    _PROGRAMS.clear()


def report_section() -> Dict[str, Any]:
    """A RunReport's ``compile``: seconds and programs by stage and, under
    each, by the phase they were booked to (``during``), the cache's
    outcomes, and the ten slowest stage records of the buffer by name."""
    section: Dict[str, Any] = {"seconds": {}, "programs": {}}
    for what, by_stage in section.items():
        for labels, value in _metrics.series(f"compile.{what}"):
            by_stage.setdefault(labels["stage"], {})[labels["during"]] = value
    section["cache"] = {labels["outcome"]: value for labels, value
                        in _metrics.series("compile.cache")}
    section["slowest"] = sorted(
        programs(), key=lambda r: -r["seconds"])[:10]
    return section
