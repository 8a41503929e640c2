"""Wall-clock phase timing.

Reference: photon-lib util/Timed.scala:33-69 — every pipeline phase runs
inside a `Timed("msg") { ... }` block that logs "msg (duration)"; the
reference uses it pervasively (GameTrainingDriver.run,
CoordinateDescent.scala:178-185).

Used as either a context manager or a decorator; durations are also
recorded in a process-wide registry so drivers can dump a timing summary
(the Spark-UI stage-view stand-in).

``Timed`` is now a shim over the telemetry span system (photon_tpu/obs/
spans.py): when telemetry is enabled, every Timed block additionally
records a nested trace span (Perfetto-exportable, aligned with device
traces via jax.profiler.TraceAnnotation) and lands in the RunReport's
phase list. The legacy ``_TIMINGS`` registry keeps its exact behavior —
and is now thread-safe, so concurrent RE solves can't corrupt or
interleave the summary.

It is the one primitive that records with telemetry OFF, which is why
the set-up phases of a fit are ``Timed`` (``ingest/prepare/<coordinate>
/<step>``, ``ingest/h2d/<coordinate>``, ``ingest/stats``,
``ingest/feature_stats/<shard>``: PERF.md §3): a job's set-up seconds
have to be readable from a run that was measured with telemetry off. A
phase times what the HOST spends in the block on ``time.perf_counter``;
nothing in it waits for the device, but for ``ingest/feature_stats``,
whose statistics have to be done (and its copy of the matrix gone) before
the estimator places its own (``cli/train.py``). The registry
keeps the newest ``_MAX_TIMINGS`` records, so a long-lived process that
prepares datasets round after round (nearline) cannot grow it.

A phase also says WHERE host work beneath the spans was done: while a
``Timed`` whose label is a scoped name (``ingest/prepare/per_user/pad``: it
has a ``/`` and no space; a driver's free-text log line has not) is open
on a thread, :func:`current_phase` returns the label's first two segments
(``ingest/prepare``), and ``none`` outside every such phase.
``utils/compile_cache`` books JAX's trace / lower / compile seconds under
it (``compile.seconds{stage, during}``). A ``Timed`` wraps phases of a job,
never an update or an evaluation, so the stack costs a split a phase and
nothing a fit.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import logging
import threading
import time
from typing import Callable, Deque, List, Optional, Tuple

from photon_tpu.obs.spans import span as _obs_span

_default_logger = logging.getLogger("photon_tpu.timing")

# (label, seconds) in completion order; guarded by _TIMINGS_LOCK
_MAX_TIMINGS = 4096
_TIMINGS: Deque[Tuple[str, float]] = collections.deque(maxlen=_MAX_TIMINGS)
_TIMINGS_LOCK = threading.Lock()


# per thread: the open scoped phases, innermost last
_PHASES = threading.local()


def _phase_of(label: str) -> Optional[str]:
    """The first two segments of a scoped label; ``None`` for free text."""
    if "/" not in label or " " in label:
        return None
    return "/".join(label.split("/", 2)[:2])


def current_phase() -> str:
    """Where this thread stands: the innermost open scoped ``Timed``
    (its first two segments), else ``none``."""
    stack = getattr(_PHASES, "stack", None)
    return stack[-1] if stack else "none"


def timing_records() -> List[Tuple[str, float]]:
    with _TIMINGS_LOCK:
        return list(_TIMINGS)


def clear_timings() -> None:
    with _TIMINGS_LOCK:
        _TIMINGS.clear()


def timing_summary() -> str:
    records = timing_records()
    lines = [f"  {label}: {secs:.3f}s" for label, secs in records]
    return "timing summary:\n" + "\n".join(lines) if lines else "no timings"


class Timed(contextlib.AbstractContextManager):
    """``with Timed("phase", logger): ...`` logs 'phase (1.234 s)'."""

    def __init__(self, label: str, logger: Optional[logging.Logger] = None,
                 level: int = logging.INFO):
        self.label = label
        self.logger = logger or _default_logger
        self.level = level
        self.seconds: Optional[float] = None
        self._phase = _phase_of(label)

    def __enter__(self) -> "Timed":
        # span shim: no-op (two attribute writes) when telemetry is off
        self._span = _obs_span(self.label)
        self._span.__enter__()
        if self._phase is not None:
            stack = getattr(_PHASES, "stack", None)
            if stack is None:
                stack = _PHASES.stack = []
            stack.append(self._phase)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.seconds = time.perf_counter() - self._t0
        if self._phase is not None:
            _PHASES.stack.pop()
        self._span.__exit__(exc_type, exc, tb)
        with _TIMINGS_LOCK:
            _TIMINGS.append((self.label, self.seconds))
        status = "" if exc_type is None else " [FAILED]"
        self.logger.log(self.level, "%s (%.3f s)%s", self.label,
                        self.seconds, status)


def timed(label: Optional[str] = None,
          logger: Optional[logging.Logger] = None) -> Callable:
    """Decorator form: ``@timed("phase")``."""

    def wrap(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with Timed(label or fn.__qualname__, logger):
                return fn(*args, **kwargs)

        return inner

    return wrap
