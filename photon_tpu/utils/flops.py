"""Model-flop accounting for MFU reporting.

"Model flops" are the algorithmically-required floating point operations of
the GLM solves (the useful work), NOT hardware flops: we count the
aggregator passes the optimizer actually executed, using each solver's
reported objective-evaluation count. MFU = model_flops / wall_clock /
chip_peak_flops — a deliberate lower bound, because ancillary work
(line-search vector ops, convergence checks, scatter/gathers, Hessian-vector
products inside TRON's CG loop) is not counted.

Per objective evaluation on a batch with NNZ feature slots:
  * forward margins (matvec / gather-dot):   2 * NNZ
  * backward gradient (rmatvec / scatter):   2 * NNZ
so one value-and-gradient pass = 4 * NNZ flops
(reference hot loop being replaced: ValueAndGradientAggregator.scala:240-255).

For vmapped random-effect solves the per-entity evaluation count is not
individually tracked; we use 2 evaluations per L-BFGS iteration (one
accepted step + ~one line-search probe), again a deliberate estimate that
is labelled as such in the bench output.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

from photon_tpu.ops import features as F

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


def peak_flops(device) -> tuple:
    """(peak FLOP/s | None on CPU, device_kind)."""
    peaks, kind = device_peaks(device)
    return (None if peaks is None else peaks.flops), kind


def peak_hbm_bw(device) -> tuple:
    """(peak HBM bytes/s | None on CPU, device_kind)."""
    peaks, kind = device_peaks(device)
    return (None if peaks is None else peaks.hbm_bw), kind


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
    ``perf.h2d_bw_util``) so every RunReport snapshot carries them, and
    the returned dict goes into bench records.
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
    {coordinate}`` gauges and a dict for bench records."""
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
    measured peak (on CPU backends the measurement is an array-bytes /
    RSS proxy — see bench.py --mode re_sweep). Both land as
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


def _nnz_slots(features) -> int:
    """Feature slots touched per objective pass (dense: n*d; ELL: n*K)."""
    if isinstance(features, F.SparseFeatures):
        return int(np.prod(features.values.shape))
    return int(np.prod(features.shape))


def value_grad_pass_bytes(features, dim: int, fused: bool = False) -> int:
    """HBM bytes one value+gradient evaluation must move, from shapes:
    the feature stream (dense f32 tile or ELL int32 index + f32 value
    slots), the per-sample vectors (labels, offsets, weights), and the
    coefficient/gradient vectors. The XLA two-contraction path streams
    the features TWICE (margins, then the transposed contraction);
    ``fused=True`` models the single-HBM-pass Pallas kernels
    (ops/pallas_glm.py). A deliberate lower bound — intermediates that
    XLA may spill are not counted."""
    nnz = _nnz_slots(features)
    if isinstance(features, F.SparseFeatures):
        n = int(features.values.shape[0])
        stream = nnz * (4 + 4)            # int32 index + f32 value
    else:
        n = int(features.shape[0])
        stream = nnz * int(np.dtype(features.dtype).itemsize)
    passes = 1 if fused else 2
    return passes * stream + 3 * n * 4 + 2 * int(dim) * 4


def phase_utilization(model_flops: int, bytes_moved: int, seconds: float,
                      device=None, phase: str = "solve") -> dict:
    """MFU and HBM-bandwidth-utilization estimate for one solve phase.

    Both are model-work ratios against chip peaks — deliberate lower
    bounds computed from shapes, not hardware counters; on a CPU both
    are ``None`` and the gauges stay unset. The dict lands in bench
    records, and the two gauges (``perf.mfu`` / ``perf.hbm_bw_util``
    with a ``phase`` label) put the same numbers in every RunReport via
    the metrics-registry snapshot."""
    import jax

    from photon_tpu.obs.metrics import registry

    if device is None:
        device = jax.devices()[0]
    peaks, kind = device_peaks(device)
    seconds = max(float(seconds), 1e-12)
    mfu = _share(model_flops / seconds, peaks and peaks.flops)
    bw_util = _share(bytes_moved / seconds, peaks and peaks.hbm_bw)
    if peaks is not None:
        registry.gauge("perf.mfu", phase=phase).set(mfu)
        registry.gauge("perf.hbm_bw_util", phase=phase).set(bw_util)
    return {
        "phase": phase,
        "device_kind": kind,
        "model_flops": int(model_flops),
        "bytes_moved": int(bytes_moved),
        "seconds": float(seconds),
        "mfu": mfu,
        "hbm_bw_utilization": bw_util,
        "peak_flops": peaks and peaks.flops,
        "peak_hbm_bw": peaks and peaks.hbm_bw,
    }


def fixed_effect_flops(coord) -> int:
    """Model flops of a FixedEffectCoordinate's last solve."""
    result = getattr(coord, "last_result", None)
    if result is None:
        return 0
    evals = int(np.asarray(result.num_fun_evals))
    return evals * 4 * _nnz_slots(coord.batch.features)


def random_effect_flops(coord) -> int:
    """Estimated model flops of a RandomEffectCoordinate's last solve:
    sum over entities of (2 evals/iter * iters) * 4 * S_b * K_b."""
    tracker = getattr(coord, "last_tracker", None)
    if tracker is None:
        return 0
    iters = np.maximum(np.asarray(tracker.iterations), 0)
    total = 0
    for blk in coord.dataset.blocks:
        ents = np.asarray(blk.entity_rows)
        valid = ents < iters.shape[0]
        it_b = int(iters[ents[valid]].sum())
        per_eval = 4 * blk.max_samples * blk.features.values.shape[-1]
        total += 2 * it_b * per_eval
    return total


def estimator_sweep_flops(estimator) -> int:
    """Model flops of the LAST coordinate-descent sweep of a fitted
    GameEstimator (each coordinate's trackers reflect its final update)."""
    from photon_tpu.game.coordinate import (
        FixedEffectCoordinate,
        RandomEffectCoordinate,
    )

    coords = getattr(estimator, "_coordinates", None) or {}
    total = 0
    for coord in coords.values():
        if isinstance(coord, FixedEffectCoordinate):
            total += fixed_effect_flops(coord)
        elif isinstance(coord, RandomEffectCoordinate):
            total += random_effect_flops(coord)
    return total
