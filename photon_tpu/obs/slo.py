"""Declarative SLO specs evaluated per window over the timeseries.

An SLO here is a frozen rule object evaluated against a windowed
snapshot (``obs.timeseries.WindowedRegistry.snapshot()`` or the merged
multi-process dict from ``merge_snapshots``). Evaluation emits TYPED
verdict records — PASS / WARN / BREACH with the exact offending windows
— rather than a boolean, so a gate can assert not just "p99 was
fine" but "the breach was localized to the shard-kill windows and every
survivor window stayed PASS".

Rules:

  * :class:`P99Ceiling` — per-window p99 of a quantile series must stay
    under a ceiling, evaluated only in windows whose qps (a counter
    series over the same interval) meets a floor — idle windows with two
    stragglers don't count against the SLO.
  * :class:`MaxDegradationRate` — typed-degradation counter divided by a
    request counter per window must stay under a rate.
  * :class:`ZeroSteadyStateCompiles` — the post-warmup compile delta
    (from the existing three compile monitors) must be exactly zero;
    window-free, the whole run is one observation.

Verdict status: 0 offending windows → PASS; at most ``warn_windows``
offending → WARN (transients tolerated, e.g. the probation window right
after a live swap); more → BREACH.

``evaluate()`` also records every verdict in a module-level sink so the
RunReport's ``slo`` section picks them up; ``write_verdicts`` emits the
machine-readable verdict file CI reads.
"""

from __future__ import annotations

import dataclasses
import json
import threading
from typing import Dict, List, Optional, Sequence, Tuple

SCHEMA = "photon_tpu.slo.v1"

PASS = "PASS"
WARN = "WARN"
BREACH = "BREACH"


def _series_key(name: str, labels: Optional[Dict[str, str]]) -> str:
    from photon_tpu.obs.metrics import _label_items, _label_suffix
    return name + _label_suffix(_label_items(dict(labels or {})))


def _lookup(snapshot: dict, name: str,
            labels: Optional[Dict[str, str]]) -> Optional[dict]:
    return snapshot.get("timeseries", {}).get(_series_key(name, labels))


@dataclasses.dataclass(frozen=True)
class P99Ceiling:
    """Per-window p99 of ``series`` must stay <= ``ceiling_s`` in every
    window where ``qps_series`` (a windowed counter of requests) divided
    by the interval reaches ``qps_floor``."""

    rule_id: str
    series: str
    ceiling_s: float
    labels: Optional[Dict[str, str]] = None
    qps_series: Optional[str] = None
    qps_labels: Optional[Dict[str, str]] = None
    qps_floor: float = 0.0
    warn_windows: int = 0

    kind = "p99_ceiling"

    def evaluate(self, snapshot: dict, compile_delta=None) -> "Verdict":
        s = _lookup(snapshot, self.series, self.labels)
        qs = (_lookup(snapshot, self.qps_series, self.qps_labels or
                      self.labels) if self.qps_series else None)
        qps_by_idx: Dict[int, float] = {}
        if qs is not None:
            dt = float(qs.get("interval_s", 1.0)) or 1.0
            for w in qs.get("windows", []):
                qps_by_idx[int(w["idx"])] = float(w["value"]) / dt
        offending: List[dict] = []
        evaluated = 0
        for w in (s or {}).get("windows", []):
            idx = int(w["idx"])
            if self.qps_series is not None:
                if qps_by_idx.get(idx, 0.0) < self.qps_floor:
                    continue  # under the qps floor: window not judged
            p99 = w.get("p99")
            if p99 is None:
                continue
            evaluated += 1
            if float(p99) > self.ceiling_s:
                offending.append({"idx": idx, "value": float(p99),
                                  "limit": self.ceiling_s})
        return _verdict(self, evaluated, offending,
                        detail=f"p99 <= {self.ceiling_s:g}s"
                               + (f" @ qps >= {self.qps_floor:g}"
                                  if self.qps_series else ""))


@dataclasses.dataclass(frozen=True)
class MaxDegradationRate:
    """Per-window ``degraded_series / total_series`` must stay <=
    ``max_rate`` (windows with no traffic are skipped)."""

    rule_id: str
    degraded_series: str
    total_series: str
    max_rate: float
    degraded_labels: Optional[Dict[str, str]] = None
    total_labels: Optional[Dict[str, str]] = None
    warn_windows: int = 0

    kind = "max_degradation_rate"

    def evaluate(self, snapshot: dict, compile_delta=None) -> "Verdict":
        deg = _lookup(snapshot, self.degraded_series, self.degraded_labels)
        tot = _lookup(snapshot, self.total_series, self.total_labels)
        deg_by_idx = {int(w["idx"]): float(w["value"])
                      for w in (deg or {}).get("windows", [])}
        offending: List[dict] = []
        evaluated = 0
        for w in (tot or {}).get("windows", []):
            idx, total = int(w["idx"]), float(w["value"])
            if total <= 0:
                continue
            evaluated += 1
            rate = deg_by_idx.get(idx, 0.0) / total
            if rate > self.max_rate:
                offending.append({"idx": idx, "value": rate,
                                  "limit": self.max_rate})
        return _verdict(self, evaluated, offending,
                        detail=f"degradation rate <= {self.max_rate:g}")


@dataclasses.dataclass(frozen=True)
class ZeroSteadyStateCompiles:
    """The post-warmup compile delta must be exactly zero. Window-free:
    the caller passes ``compile_delta`` — the summed delta from the three
    existing compile monitors (steady-state compile events, jitcache
    misses, per-program ``_cache_size`` growth)."""

    rule_id: str
    warn_windows: int = 0  # always 0-tolerance; kept for shape uniformity

    kind = "zero_steady_state_compiles"

    def evaluate(self, snapshot: dict,
                 compile_delta: Optional[float] = None) -> "Verdict":
        if compile_delta is None:
            return Verdict(rule_id=self.rule_id, kind=self.kind,
                           status=WARN, windows_evaluated=0,
                           offending_windows=[],
                           detail="compile_delta not provided")
        offending = ([] if compile_delta == 0 else
                     [{"idx": -1, "value": float(compile_delta),
                       "limit": 0.0}])
        return _verdict(self, 1, offending,
                        detail="steady-state compile delta == 0")


SLORule = (P99Ceiling, MaxDegradationRate, ZeroSteadyStateCompiles)


@dataclasses.dataclass(frozen=True)
class Verdict:
    rule_id: str
    kind: str
    status: str
    windows_evaluated: int
    offending_windows: List[dict]
    detail: str = ""

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def _verdict(rule, evaluated: int, offending: List[dict],
             detail: str) -> Verdict:
    if not offending:
        status = PASS
    elif len(offending) <= rule.warn_windows:
        status = WARN
    else:
        status = BREACH
    return Verdict(rule_id=rule.rule_id, kind=rule.kind, status=status,
                   windows_evaluated=evaluated,
                   offending_windows=offending, detail=detail)


@dataclasses.dataclass(frozen=True)
class SLOSpec:
    rules: Tuple[object, ...]

    def __init__(self, rules: Sequence[object]):
        object.__setattr__(self, "rules", tuple(rules))


_lock = threading.Lock()
_verdicts: List[Verdict] = []


def evaluate(spec: SLOSpec, snapshot: dict,
             compile_delta: Optional[float] = None,
             record: bool = True) -> List[Verdict]:
    """Evaluate every rule against a windowed snapshot. ``record=True``
    (default) also appends the verdicts to the module sink the RunReport
    ``slo`` section reads."""
    out = [rule.evaluate(snapshot, compile_delta=compile_delta)
           for rule in spec.rules]
    if record:
        with _lock:
            _verdicts.extend(out)
    return out


def recorded_verdicts() -> List[Verdict]:
    with _lock:
        return list(_verdicts)


def clear() -> None:
    with _lock:
        _verdicts.clear()


def worst_status(verdicts: Sequence[Verdict]) -> str:
    order = {PASS: 0, WARN: 1, BREACH: 2}
    worst = PASS
    for v in verdicts:
        if order.get(v.status, 2) > order[worst]:
            worst = v.status
    return worst


def write_verdicts(path, verdicts: Sequence[Verdict]) -> dict:
    """Machine-readable verdict file: schema id, worst status, one typed
    record per rule. Written atomically when resilience.io is available."""
    doc = {"schema": SCHEMA,
           "status": worst_status(verdicts),
           "verdicts": [v.to_json() for v in verdicts]}
    blob = json.dumps(doc, indent=1, sort_keys=True).encode() + b"\n"
    try:
        from photon_tpu.resilience import io as rio
        rio.atomic_write_bytes(str(path), blob)
    except Exception:
        with open(path, "wb") as f:
            f.write(blob)
    return doc


def report_section() -> Optional[dict]:
    """The RunReport ``slo`` section; None while nothing was evaluated."""
    with _lock:
        if not _verdicts:
            return None
        return {"status": worst_status(_verdicts),
                "verdicts": [v.to_json() for v in _verdicts]}
