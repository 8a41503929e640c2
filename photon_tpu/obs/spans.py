"""Nested trace spans with Chrome-trace-event / Perfetto JSON export.

Subsumes ``utils/timing.Timed`` (which is now a shim over this module):
every span records wall-clock start/end, the host seconds between them,
thread and nesting parent, and — when JAX is already loaded — wraps the
body in a ``jax.profiler.TraceAnnotation`` carrying the span's attributes,
so host spans line up with device activity in a captured device trace
(``--profile-dir``) and a gap under ``cd/update`` names its coordinate.

What a span times is the HOST: JAX dispatches asynchronously, so a span
around a jitted call that nothing blocks on ends when the call is
enqueued, not when the device is done (``dur_us`` is enqueue time there).
Device seconds come from the device trace, by ``jax.named_scope``
(PERF.md §3); the annotations are what put the two on one clock.

Zero-overhead-when-disabled: :class:`span` checks ``_config.enabled()``
once on ``__enter__`` and becomes two attribute writes when telemetry is
off — no clock reads, no list append, no profiler import.

Export: :func:`write_trace` emits ``{"traceEvents": [...]}`` with ``ph:
"X"`` complete events (ts/dur in microseconds), which chrome://tracing
and https://ui.perfetto.dev load directly.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Any, Dict, List

from photon_tpu.obs import _config

_LOCK = threading.Lock()
_RECORDS: List[Dict[str, Any]] = []
_TLS = threading.local()  # per-thread span stack for nesting

# one trace epoch per process so ts values are comparable across threads
_EPOCH_PERF = time.perf_counter()
_EPOCH_UNIX = time.time()


def _stack() -> List["span"]:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def _jax_annotation(name: str, attrs: Dict[str, Any]):
    """A jax.profiler.TraceAnnotation when jax is ALREADY imported (a
    telemetry span must never be the thing that pulls in the backend).
    ``attrs`` become the event's arguments in the trace; its name stays
    ``name``, which is what readers group by."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        return jax.profiler.TraceAnnotation(name, **attrs)
    except Exception:  # pragma: no cover - profiler unavailable
        return None


def _append(name: str, ts_us: float, seconds: float, start_unix: float,
            parent, depth: int, attrs: Dict[str, Any],
            error: bool = False) -> None:
    rec = {
        "name": name,
        "ts_us": ts_us,
        "dur_us": seconds * 1e6,
        "start_unix": start_unix,
        "end_unix": start_unix + seconds,
        "tid": threading.get_ident(),
        "parent": parent,
        "depth": depth,
    }
    if attrs:
        rec["args"] = dict(attrs)
    if error:
        rec["error"] = True
    with _LOCK:
        _RECORDS.append(rec)


class span:
    """``with span("phase", key=value): ...`` — records one trace event.

    Nested use is encouraged: the enclosing span (same thread) becomes
    ``parent`` in the record, and Perfetto renders containment from the
    ts/dur intervals. Exceptions mark the record ``"error": true`` and
    propagate.
    """

    __slots__ = ("name", "attrs", "_on", "_t0", "_wall0", "_parent",
                 "_depth", "_ann")

    def __init__(self, name: str, **attrs: Any):
        self.name = name
        self.attrs = attrs
        self._on = False

    def __enter__(self) -> "span":
        if not _config.enabled():
            return self
        self._on = True
        st = _stack()
        self._parent = st[-1].name if st else None
        self._depth = len(st)
        st.append(self)
        self._ann = _jax_annotation(self.name, self.attrs)
        if self._ann is not None:
            self._ann.__enter__()
        self._wall0 = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if not self._on:
            return
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        _append(self.name, (self._t0 - _EPOCH_PERF) * 1e6, t1 - self._t0,
                self._wall0, self._parent, self._depth, self.attrs,
                error=exc_type is not None)


def record(name: str, start_unix: float, seconds: float,
           **attrs: Any) -> None:
    """One span that something else timed, put on this module's clock:
    machinery that reports itself only through a callback when it is DONE
    (JAX's trace / lower / compile stages, ``utils/compile_cache``) hands
    in its start on ``time.time`` and its seconds, and the record lands
    where a ``span`` closed at that moment would have (``ts_us`` from
    ``_EPOCH_UNIX``; parent = the innermost span open on the thread). A
    no-op with telemetry off."""
    if not _config.enabled():
        return
    st = _stack()
    _append(name, (start_unix - _EPOCH_UNIX) * 1e6, seconds, start_unix,
            st[-1].name if st else None, len(st), attrs)


def annotate(name: str, **attrs: Any):
    """Device-trace-only annotation for hot call sites: aligns a named
    region with device activity under ``jax.profiler`` without recording
    a host span (no lock, no list growth when called per CD update).
    Returns a no-op context when telemetry is off."""
    if not _config.enabled():
        return _NULL_CONTEXT
    ann = _jax_annotation(name, attrs)
    return ann if ann is not None else _NULL_CONTEXT


class _NullContext:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_CONTEXT = _NullContext()


def records() -> List[Dict[str, Any]]:
    """Snapshot of raw span records (report form: unix start/end + parent)."""
    with _LOCK:
        return [dict(r) for r in _RECORDS]


def clear() -> None:
    with _LOCK:
        _RECORDS.clear()


def chrome_trace_events() -> List[Dict[str, Any]]:
    """Chrome-trace ``ph: "X"`` complete events, Perfetto-loadable."""
    pid = os.getpid()
    events = []
    for r in records():
        ev = {
            "name": r["name"],
            "ph": "X",
            "ts": r["ts_us"],
            "dur": r["dur_us"],
            "pid": pid,
            "tid": r["tid"],
            "cat": "photon_tpu",
        }
        args = dict(r.get("args", {}))
        if r.get("parent"):
            args["parent"] = r["parent"]
        if args:
            ev["args"] = args
        events.append(ev)
    return events


def write_trace(path: str) -> str:
    """Write the span buffer as a Chrome-trace JSON file; returns path."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    payload = {
        "displayTimeUnit": "ms",
        "metadata": {"trace_epoch_unix": _EPOCH_UNIX},
        "traceEvents": chrome_trace_events(),
    }
    with open(path, "w") as f:
        json.dump(payload, f)
    return path
