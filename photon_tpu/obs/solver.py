"""Device-resident solver telemetry, drained only at phase boundaries.

Extends the lazy-transfer pattern of ``optim/tracking.py``: coordinate
descent pushes each update's tracker here as a bare reference — the
per-iteration loss/||g||/step ring buffers and per-entity RE outcome
arrays stay DEVICE arrays, so recording costs one list append and zero
syncs. :func:`drain` (called at RunReport build time, i.e. a phase
boundary) pays the host transfers in one batch, converts every tracker
to a JSON-safe dict, and empties the buffer.

Multi-process runs keep this per-process; the RunReport aggregation
(obs/aggregate.py) ships the drained host dicts to process 0 — no
collectives ride in the recording path.

The buffer is bounded by the shape of a fit, not by how long the process
lives: one entry a (coordinate, sweep), a later fit's update replacing an
earlier fit's. A process that fits again and again with telemetry on and
never builds a report (a traced benchmark window, a tuning loop) would
otherwise pin every update's device arrays.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Tuple

from photon_tpu.obs import _config

_LOCK = threading.Lock()
# (coordinate, sweep) -> {"kind", "coordinate", "tracker", "unix", **meta};
# tracker is a live OptimizationStatesTracker / RandomEffectOptimization
# Tracker whose arrays may still be device-resident
_BUFFER: Dict[Tuple[str, Any], Dict[str, Any]] = {}


def record(coordinate: str, tracker, **meta: Any) -> None:
    """Push one update's tracker (no-op when telemetry is off, no host
    sync ever — the tracker's arrays are adopted as-is)."""
    if tracker is None or not _config.enabled():
        return
    kind = ("random_effect" if hasattr(tracker, "reason_counts")
            else "states")
    key = (coordinate, meta.get("sweep"))
    with _LOCK:
        _BUFFER.pop(key, None)     # re-inserted last: drain keeps time order
        _BUFFER[key] = {"kind": kind, "coordinate": coordinate,
                        "tracker": tracker, "unix": time.time(), **meta}


def lane_counts() -> Dict[str, Dict[str, int]]:
    """``{coordinate: {"sum", "capacity", "trips"}}``: the buffered
    updates' ``lane_counts()`` added up, which is ONE fit's (a later fit's
    updates replace an earlier fit's): what its vmapped loops ran against
    what their lanes needed — a random effect's per-entity loops, a bucket
    each, and a fixed effect's lambda lanes (``GameEstimator.fit_swept``),
    one loop. Pays the host transfers and leaves the buffer as it is — ask
    after a fit, not inside a sweep. Empty with telemetry off."""
    with _LOCK:
        entries = [e for e in _BUFFER.values()
                   if e["kind"] == "random_effect"]
    out: Dict[str, Dict[str, int]] = {}
    for e in entries:
        total = out.setdefault(e["coordinate"], {})
        for stat, n in e["tracker"].lane_counts().items():
            total[stat] = total.get(stat, 0) + n
    return out


def pending() -> int:
    with _LOCK:
        return len(_BUFFER)


def clear() -> None:
    with _LOCK:
        _BUFFER.clear()


def drain() -> Dict[str, List[Dict[str, Any]]]:
    """Convert + clear: {"trajectories": [...], "random_effects": [...]}.

    This is where device->host transfers happen — call it at phase
    boundaries only (RunReport build, end of fit), never inside a sweep.
    """
    with _LOCK:
        entries = list(_BUFFER.values())
        _BUFFER.clear()
    out: Dict[str, List[Dict[str, Any]]] = {
        "trajectories": [], "random_effects": []}
    for e in entries:
        base = {k: v for k, v in e.items() if k not in ("tracker", "kind")}
        try:
            base.update(e["tracker"].to_dict())
        except Exception as exc:  # a broken tracker must not kill a report
            base["error"] = repr(exc)
        if e["kind"] == "random_effect":
            out["random_effects"].append(base)
        else:
            out["trajectories"].append(base)
    return out
