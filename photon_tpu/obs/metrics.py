"""Process-wide metrics registry: counters, gauges, histograms, labels.

The Spark-UI-counters replacement (reference: Photon ML leans on Spark's
stage/task metrics for pipeline accounting). One process-wide
:data:`registry` instance backs every subsystem — jit/compile caches,
coordinate descent, the drivers — and exports two ways:

  * ``to_json()``   — nested snapshot for the RunReport manifest;
  * ``to_prometheus_text()`` — the Prometheus text exposition format, so
    a sidecar can scrape a dumped file without any client library.

All operations take one lock; increments are host-side and happen at
cache-lookup/phase granularity, never inside jitted code.
"""

from __future__ import annotations

import bisect
import json
import threading
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# Prometheus-style default buckets, extended upward for compile times
DEFAULT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
                   2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0)

LabelItems = Tuple[Tuple[str, str], ...]


def _label_items(labels: Dict[str, str]) -> LabelItems:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _label_suffix(items: LabelItems) -> str:
    if not items:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in items)
    return "{" + inner + "}"


class Counter:
    """Monotone sum. ``inc`` only (negative deltas rejected)."""

    __slots__ = ("_lock", "value")

    def __init__(self, lock: threading.RLock):
        self._lock = lock
        self.value = 0.0

    def inc(self, delta: float = 1.0) -> None:
        if delta < 0:
            raise ValueError(f"counter increments must be >= 0, got {delta}")
        with self._lock:
            self.value += delta


class Gauge:
    """Last-write-wins scalar, with a convenience ``max`` for watermarks."""

    __slots__ = ("_lock", "value")

    def __init__(self, lock: threading.RLock):
        self._lock = lock
        self.value = 0.0

    def set(self, value: float) -> None:
        with self._lock:
            self.value = float(value)

    def max(self, value: float) -> None:
        with self._lock:
            self.value = max(self.value, float(value))


class Histogram:
    """Cumulative-bucket histogram (Prometheus semantics: le upper bounds
    plus an implicit +Inf bucket; ``sum``/``count`` ride along)."""

    __slots__ = ("_lock", "buckets", "counts", "sum", "count")

    def __init__(self, lock: threading.RLock,
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        self._lock = lock
        self.buckets: Tuple[float, ...] = tuple(sorted(buckets))
        self.counts: List[int] = [0] * (len(self.buckets) + 1)  # +Inf last
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        with self._lock:
            self.counts[bisect.bisect_left(self.buckets, value)] += 1
            self.sum += value
            self.count += 1

    def quantile(self, q: float) -> Optional[float]:
        with self._lock:
            return bucket_quantile(self.buckets, self.counts, q)


def bucket_quantile(buckets: Sequence[float], counts: Sequence[int],
                    q: float) -> Optional[float]:
    """Prometheus-style estimated quantile: find the bucket holding rank
    q*count, interpolate linearly inside it (lower bound 0 for the first
    bucket; the +Inf bucket clamps to the last finite bound). None when
    empty. Estimation error is bounded by bucket width — pick latency
    buckets accordingly (serving uses ~1.3x geometric steps)."""
    total = sum(counts)
    if total == 0:
        return None
    target = q * total
    cumulative = 0
    for i, c in enumerate(counts):
        prev = cumulative
        cumulative += c
        if cumulative >= target and c > 0:
            if i >= len(buckets):            # +Inf bucket
                return float(buckets[-1]) if buckets else None
            lo = buckets[i - 1] if i > 0 else 0.0
            hi = buckets[i]
            frac = (target - prev) / c
            return float(lo + (hi - lo) * frac)
    return float(buckets[-1]) if buckets else None


class MetricsRegistry:
    """Thread-safe name+labels -> metric registry.

    The first registration of a name fixes its kind; re-registering the
    same (name, labels) returns the same instance, so call sites can do
    ``registry.counter("jitcache.hits").inc()`` on every event.
    """

    def __init__(self):
        self._lock = threading.RLock()
        self._metrics: Dict[Tuple[str, LabelItems], object] = {}
        self._kinds: Dict[str, str] = {}

    def _get(self, kind: str, name: str, labels: Dict[str, str], factory):
        key = (name, _label_items(labels))
        with self._lock:
            existing_kind = self._kinds.get(name)
            if existing_kind is not None and existing_kind != kind:
                raise ValueError(
                    f"metric {name!r} already registered as {existing_kind}, "
                    f"requested {kind}")
            metric = self._metrics.get(key)
            if metric is None:
                metric = self._metrics[key] = factory()
                self._kinds[name] = kind
            return metric

    def counter(self, name: str, **labels: str) -> Counter:
        return self._get("counter", name, labels,
                         lambda: Counter(self._lock))

    def gauge(self, name: str, **labels: str) -> Gauge:
        return self._get("gauge", name, labels, lambda: Gauge(self._lock))

    def histogram(self, name: str,
                  buckets: Sequence[float] = DEFAULT_BUCKETS,
                  **labels: str) -> Histogram:
        return self._get("histogram", name, labels,
                         lambda: Histogram(self._lock, buckets))

    def clear(self) -> None:
        with self._lock:
            self._metrics.clear()
            self._kinds.clear()

    # -- export ----------------------------------------------------------

    def snapshot(self) -> Dict[str, Dict[str, object]]:
        """{"counters": {...}, "gauges": {...}, "histograms": {...}} with
        ``name{label="v"}`` keys — the RunReport's ``metrics`` section."""
        out: Dict[str, Dict[str, object]] = {
            "counters": {}, "gauges": {}, "histograms": {}}
        with self._lock:
            items = list(self._metrics.items())
        for (name, labels), metric in sorted(items, key=lambda kv: kv[0]):
            key = name + _label_suffix(labels)
            if isinstance(metric, Counter):
                out["counters"][key] = metric.value
            elif isinstance(metric, Gauge):
                out["gauges"][key] = metric.value
            else:
                assert isinstance(metric, Histogram)
                h = {
                    "buckets": list(metric.buckets),
                    "counts": list(metric.counts),
                    "sum": metric.sum,
                    "count": metric.count,
                }
                if metric.count:
                    for name_q, q in (("p50", 0.5), ("p95", 0.95),
                                      ("p99", 0.99)):
                        h[name_q] = bucket_quantile(h["buckets"],
                                                    h["counts"], q)
                out["histograms"][key] = h
        return out

    def series(self, name: str) -> List[Tuple[Dict[str, str], float]]:
        """``[(labels, value)]`` of every counter or gauge called ``name``:
        a family by its labels, without parsing snapshot keys."""
        with self._lock:
            return [(dict(labels), metric.value)
                    for (n, labels), metric in self._metrics.items()
                    if n == name and isinstance(metric, (Counter, Gauge))]

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.snapshot(), indent=indent, sort_keys=True)

    def to_prometheus_text(self) -> str:
        """Prometheus text exposition format (one ``# TYPE`` per family)."""
        with self._lock:
            items = sorted(self._metrics.items(), key=lambda kv: kv[0])
            kinds = dict(self._kinds)
        lines: List[str] = []
        seen_type: set = set()

        def prom_name(name: str) -> str:
            return name.replace(".", "_").replace("-", "_").replace("/", "_")

        for (name, labels), metric in items:
            pname = prom_name(name)
            if name not in seen_type:
                seen_type.add(name)
                lines.append(f"# TYPE {pname} {kinds[name]}")
            suffix = _label_suffix(labels)
            if isinstance(metric, (Counter, Gauge)):
                lines.append(f"{pname}{suffix} {metric.value}")
            else:
                assert isinstance(metric, Histogram)
                cumulative = 0
                for le, c in zip(metric.buckets, metric.counts):
                    cumulative += c
                    le_items = labels + (("le", repr(float(le))),)
                    lines.append(
                        f"{pname}_bucket{_label_suffix(le_items)} {cumulative}")
                cumulative += metric.counts[-1]
                inf_items = labels + (("le", "+Inf"),)
                lines.append(
                    f"{pname}_bucket{_label_suffix(inf_items)} {cumulative}")
                lines.append(f"{pname}_sum{suffix} {metric.sum}")
                lines.append(f"{pname}_count{suffix} {metric.count}")
        return "\n".join(lines) + ("\n" if lines else "")


# process-wide default registry: every subsystem records here
registry = MetricsRegistry()


def merge_snapshots(snapshots: Iterable[Dict[str, Dict[str, object]]]
                    ) -> Dict[str, Dict[str, object]]:
    """Merge per-process ``snapshot()`` dicts into one cluster view:
    counters sum, gauges take the max (they are used as watermarks/flags),
    histograms sum bucket-wise when bucket layouts agree (first layout
    wins otherwise). Snapshots carrying a ``timeseries`` section
    (obs/timeseries.py WindowedRegistry.snapshot()) merge those series
    window-by-window too, and the output gains a ``timeseries`` section
    only in that case — plain MetricsRegistry merges keep the old shape.
    Used by the RunReport's process-0 aggregation — runs once at report
    time, never in a hot path."""
    out: Dict[str, Dict[str, object]] = {
        "counters": {}, "gauges": {}, "histograms": {}}
    ts_groups: Dict[str, list] = {}
    for snap in snapshots:
        for k, s in snap.get("timeseries", {}).items():
            ts_groups.setdefault(k, []).append(s)
        for k, v in snap.get("counters", {}).items():
            out["counters"][k] = out["counters"].get(k, 0.0) + v
        for k, v in snap.get("gauges", {}).items():
            out["gauges"][k] = max(out["gauges"].get(k, float("-inf")), v)
        for k, h in snap.get("histograms", {}).items():
            cur = out["histograms"].get(k)
            if cur is None:
                out["histograms"][k] = {
                    "buckets": list(h["buckets"]), "counts": list(h["counts"]),
                    "sum": h["sum"], "count": h["count"]}
            elif list(cur["buckets"]) == list(h["buckets"]):
                cur["counts"] = [a + b for a, b in
                                 zip(cur["counts"], h["counts"])]
                cur["sum"] += h["sum"]
                cur["count"] += h["count"]
    for h in out["histograms"].values():
        if h["count"]:  # cluster-level quantiles over the merged buckets
            for name_q, q in (("p50", 0.5), ("p95", 0.95), ("p99", 0.99)):
                h[name_q] = bucket_quantile(h["buckets"], h["counts"], q)
    if ts_groups:
        from photon_tpu.obs import timeseries as _ts  # lazy: avoid cycle
        out["timeseries"] = {k: _ts.merge_series(v)
                             for k, v in sorted(ts_groups.items())}
    return out
