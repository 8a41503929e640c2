"""Chip peaks, and the utilization gauges of the streamed and blocked
pipelines that are read against them.

``device_peaks`` is the one table of per-chip figures, keyed by device
kind; a CPU has none, so every share derived from it is ``None`` there.
``stream_overlap_utilization`` and ``re_block_overlap`` turn a pipeline's
reader/consumer clocks into an overlap efficiency and a host->device
bandwidth share; ``re_peak_hbm`` publishes the blocked random-effect
planner's predicted peak beside the measured one.
"""

from __future__ import annotations

from typing import NamedTuple, Optional


class Peaks(NamedTuple):
    flops: float      # bf16/native-matmul FLOP/s per chip
    hbm_bw: float     # HBM bytes/s per chip
    h2d_bw: float     # host->device bytes/s per chip


# The one peaks table, keyed by `device_kind` substring; first match wins,
# so "v5p" precedes "v5" (a v5e reports "TPU v5 lite"). FLOP/s and HBM
# figures are the public per-chip numbers of each generation's Google
# Cloud TPU page (v5e: 197 TFLOP/s bf16, 819 GB/s). The host->device
# column normalizes the streaming pipeline's transfer gauge, where what
# matters is the ORDER — is the pipeline within a small factor of the
# interconnect — not the digit.
_PEAKS_BY_KIND = (
    ("v6", Peaks(918e12, 1640e9, 64e9)),      # Trillium / v6e
    ("v5p", Peaks(459e12, 2765e9, 64e9)),
    ("v5", Peaks(197e12, 819e9, 32e9)),       # v5e / v5 lite
    ("v4", Peaks(275e12, 1228e9, 32e9)),
    ("v3", Peaks(123e12, 900e9, 16e9)),
    ("v2", Peaks(45e12, 700e9, 16e9)),
)


def device_peaks(device) -> tuple:
    """(Peaks | None, device_kind) for a jax device. A CPU has no peak —
    ``None``, and every utilization derived from it is ``None`` too: a
    CPU run never prints a figure under a device metric's name. Any
    other device must be in the table; a peak is never assumed."""
    kind = getattr(device, "device_kind", "") or ""
    platform = getattr(device, "platform", "")
    if platform == "cpu":
        return None, kind or "cpu"
    low = kind.lower()
    for marker, peaks in _PEAKS_BY_KIND:
        if marker in low:
            return peaks, kind
    raise ValueError(
        f"no peak figures for device kind {kind!r} (platform "
        f"{platform!r}): add it to utils/flops._PEAKS_BY_KIND with its "
        f"source")


def peak_h2d_bw(device) -> tuple:
    """(peak host->device bytes/s | None on CPU, device_kind)."""
    peaks, kind = device_peaks(device)
    return (None if peaks is None else peaks.h2d_bw), kind


def _share(rate: float, peak: Optional[float]) -> Optional[float]:
    """``rate / peak``, or ``None`` where there is no peak (CPU)."""
    return None if peak is None else float(rate / peak)


def stream_overlap_utilization(reader_busy_s: float, consumer_stall_s: float,
                               wall_s: float, bytes_h2d: int,
                               device=None, phase: str = "stream") -> dict:
    """Transfer-vs-compute overlap efficiency of a streamed pass.

    The double-buffered pipeline's whole point is that chunk k+1's
    read+pack+transfer happens WHILE chunk k computes. The reader thread
    was busy ``reader_busy_s``; of that, the only part the consumer ever
    saw was its own stalls waiting on the queue (``consumer_stall_s``) —
    everything else was hidden behind compute:

        hidden_s             = max(reader_busy_s - consumer_stall_s, 0)
        overlap_efficiency   = hidden_s / reader_busy_s    (1.0 = fully
                               hidden; 0.0 = fully serialized)

    ``h2d_bw_util`` is the achieved host->device byte rate over the pass
    against the chip's nominal transfer peak (``None``, gauge unset, on a
    CPU). Both land as gauges (``perf.stream_overlap`` /
    ``perf.h2d_bw_util``) so every RunReport snapshot carries them; the
    same numbers come back as a dict.
    """
    import jax

    from photon_tpu.obs.metrics import registry

    if device is None:
        device = jax.devices()[0]
    peak_bw, kind = peak_h2d_bw(device)
    wall_s = max(float(wall_s), 1e-12)
    reader_busy_s = max(float(reader_busy_s), 0.0)
    hidden_s = max(reader_busy_s - max(float(consumer_stall_s), 0.0), 0.0)
    # a reader that was never meaningfully busy hid everything there was
    overlap = hidden_s / reader_busy_s if reader_busy_s > 1e-9 else 1.0
    h2d_util = _share(bytes_h2d / wall_s, peak_bw)
    registry.gauge("perf.stream_overlap", phase=phase).set(overlap)
    if h2d_util is not None:
        registry.gauge("perf.h2d_bw_util", phase=phase).set(h2d_util)
    return {
        "phase": phase,
        "device_kind": kind,
        "reader_busy_s": float(reader_busy_s),
        "consumer_stall_s": float(consumer_stall_s),
        "hidden_s": float(hidden_s),
        "wall_s": float(wall_s),
        "bytes_h2d": int(bytes_h2d),
        "overlap_efficiency": float(overlap),
        "h2d_bw_utilization": h2d_util,
        "peak_h2d_bw": peak_bw,
    }


def re_block_overlap(reader_busy_s: float, consumer_stall_s: float,
                     wall_s: float, bytes_staged: int,
                     device=None, coordinate: str = "re") -> dict:
    """Stage-vs-solve overlap efficiency of a blocked random-effect pass
    — ``stream_overlap_utilization``'s sibling for the entity-bucket
    pipeline (game/block_stream.BlockPrefetcher): the prefetch thread
    stages bucket b+1 while bucket b solves; the only staging time the
    solver ever saw was its own stalls waiting on the queue. Lands as
    ``perf.re_block_overlap{coordinate}`` / ``perf.re_h2d_bw_util
    {coordinate}`` gauges and comes back as a dict."""
    import jax

    from photon_tpu.obs.metrics import registry

    if device is None:
        device = jax.devices()[0]
    peak_bw, kind = peak_h2d_bw(device)
    wall_s = max(float(wall_s), 1e-12)
    reader_busy_s = max(float(reader_busy_s), 0.0)
    hidden_s = max(reader_busy_s - max(float(consumer_stall_s), 0.0), 0.0)
    overlap = hidden_s / reader_busy_s if reader_busy_s > 1e-9 else 1.0
    h2d_util = _share(bytes_staged / wall_s, peak_bw)
    registry.gauge("perf.re_block_overlap", coordinate=coordinate).set(overlap)
    if h2d_util is not None:
        registry.gauge("perf.re_h2d_bw_util",
                       coordinate=coordinate).set(h2d_util)
    return {
        "coordinate": coordinate,
        "device_kind": kind,
        "reader_busy_s": float(reader_busy_s),
        "consumer_stall_s": float(consumer_stall_s),
        "hidden_s": float(hidden_s),
        "wall_s": float(wall_s),
        "bytes_staged": int(bytes_staged),
        "overlap_efficiency": float(overlap),
        "h2d_bw_utilization": h2d_util,
        "peak_h2d_bw": peak_bw,
    }


def re_peak_hbm(coordinate: str, planned_bytes: int,
                measured_bytes: int) -> dict:
    """Publish a blocked/swept random-effect pass's peak device
    footprint: the ``parallel/memory`` planner's prediction next to the
    measured peak (on CPU backends the measurement is an array-bytes
    proxy). Both land as
    ``perf.re_peak_hbm_bytes{coordinate, kind}`` gauges so every
    RunReport snapshot carries the planned-vs-measured pair; the
    acceptance contract is planned >= measured on every bucket."""
    from photon_tpu.obs.metrics import registry

    registry.gauge("perf.re_peak_hbm_bytes", coordinate=coordinate,
                   kind="planned").set(int(planned_bytes))
    registry.gauge("perf.re_peak_hbm_bytes", coordinate=coordinate,
                   kind="measured").set(int(measured_bytes))
    return {
        "coordinate": coordinate,
        "planned_peak_bytes": int(planned_bytes),
        "measured_peak_bytes": int(measured_bytes),
        "within_plan": bool(int(measured_bytes) <= int(planned_bytes)),
    }
