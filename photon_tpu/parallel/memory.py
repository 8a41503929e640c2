"""HBM footprint planning for λ-lane random-effect sweeps.

Lane-batching the random-effect sweep axis K-folds the per-bucket device
footprint: every λ lane carries its own ``[E_b, d]`` theta stack, solver
history, and working vectors on top of the (shared) entity-block data.
Discovering that multiplication as a runtime OOM mid-sweep would waste
the whole run, so the planner sizes every bucket of the ladder AGAINST
AN EXPLICIT BYTE BUDGET *before* anything is staged, and degrades
per bucket in typed steps:

  * ``full_k``        — all K lanes fit alongside a double-buffered
                        block: one data pass for the whole grid;
  * ``chunked``       — K splits into ⌈K/c⌉ passes of c lanes each (the
                        staged block is reused across passes, so the
                        storage→device traffic stays one pass);
  * ``single_lambda`` — lanes degrade all the way to one λ per pass —
                        the sequential sweep's footprint, still planned
                        and still recorded.

A bucket that cannot fit even one lane inside the budget is marked
``over_budget`` (the plan is still emitted — a refused shape is data,
not a crash; callers decide whether to proceed on a host with slack).

The budget defaults from the backend (``Device.memory_stats()``'s
``bytes_limit`` with a safety margin) exactly like the serving two-tier
store's ``hbm_budget_bytes``, is overridable per call, and can be pinned
fleet-wide via ``PHOTON_TPU_RE_HBM_BUDGET``. Every plan is recorded for
the RunReport ``re_plan`` section (obs/report.py reads this module via
``sys.modules`` so runs that never sweep pay nothing).

Byte model (pinned by tests/test_re_sweep.py — change them together):

  data_bytes(E, S, W)  = E*S*W*(4 + itemsize)        ELL indices + values
                       + E*S*(3*itemsize + 4)        labels/offsets/weights
                                                     + sample_rows
                       + E*4                         entity_rows
  lane_bytes(E, d)     = E*d*itemsize*(2 + 2*m + 6)  x0 + result
                                                     + L-BFGS (S,Y) pairs
                                                     + working vectors
  resident(n)          = n*4                         the dataset's flat-order
                                                     map (one int32 a flat
                                                     row), beside every bucket
  peak(c)              = resident + copies*data + c*(data + lane_bytes)

where ``m`` is the solver history (``SolverConfig.num_corrections``) and
the 6 working vectors bound the gradient/direction/line-search temps.
Each lane is charged ``data + lane_bytes``: the swept program flattens
its c lanes into the entity axis by tiling the staged block c× on
device (game/coordinate._make_bucket_solver, lanes mode — the price of bitwise
lane-vs-scalar parity), so the tiled batch scales with the chunk, while
the staging (``copies`` = 2 when double-buffered) does not. All terms
are deliberate over-estimates of steady state (at c=1 the block is
consumed in place, untiled) — the acceptance contract is
planned >= measured on every bucket, never the reverse.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence, Tuple

ENV_BUDGET = "PHOTON_TPU_RE_HBM_BUDGET"

# CPU-only fallback (a CPU backend reports no bytes_limit): big enough
# that tests and CPU benches only degrade when they *force* a budget
_FALLBACK_BUDGET_BYTES = 1 << 30            # 1 GiB
# fraction of the backend's bytes_limit the sweep may claim — the rest
# stays for the programs themselves, XLA temps, and the residual vector
_BACKEND_BUDGET_FRACTION = 0.8

# solver working set per lane, in units of [E, d] vectors: gradient,
# direction, trial coef, trial gradient + two history-matvec temps
_WORK_VECTORS = 6

STRATEGY_FULL = "full_k"
STRATEGY_CHUNKED = "chunked"
STRATEGY_SINGLE = "single_lambda"


def default_hbm_budget_bytes(device=None) -> Tuple[int, str]:
    """(budget bytes, source) — source is ``env`` | ``backend`` |
    ``fallback``. Reads ``PHOTON_TPU_RE_HBM_BUDGET`` first, then the
    backend's ``memory_stats()['bytes_limit']`` (scaled by the safety
    fraction). Only a CPU, which reports no limit, gets the nominal host
    figure; an accelerator that reports none is an error — planning a
    16 GB chip against 1 GiB would be a silent 16x under-use."""
    env = os.environ.get(ENV_BUDGET)
    if env:
        return max(1, int(env)), "env"
    if device is None:
        import jax
        device = jax.local_devices()[0]
    stats = device.memory_stats() or {}
    limit = int(stats.get("bytes_limit", 0))
    if limit > 0:
        return int(limit * _BACKEND_BUDGET_FRACTION), "backend"
    if device.platform != "cpu":
        raise RuntimeError(
            f"{device.platform} device {device.device_kind!r} reports no "
            f"memory_stats()['bytes_limit'] to plan random-effect blocks "
            f"against; set {ENV_BUDGET} to its HBM bytes")
    return _FALLBACK_BUDGET_BYTES, "fallback"


def block_data_bytes(entity_rows: int, max_samples: int, ell_width: int,
                     itemsize: int) -> int:
    """Device bytes of one staged EntityBlock (ELL indices int32 + values,
    labels/offsets/weights, sample_rows int32, entity_rows int32)."""
    e, s, w = int(entity_rows), int(max_samples), int(ell_width)
    return (e * s * w * (4 + itemsize)
            + e * s * (3 * itemsize + 4)
            + e * 4)


def lane_state_bytes(entity_rows: int, dim: int, itemsize: int,
                     history: int) -> int:
    """Device bytes ONE λ lane adds on top of the shared block data:
    theta stack + result + L-BFGS (S, Y) history + working vectors."""
    e, d = int(entity_rows), int(dim)
    return e * d * itemsize * (2 + 2 * int(history) + _WORK_VECTORS)


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """One size bucket's lane decision."""

    bucket: int
    entity_rows: int
    max_samples: int
    ell_width: int
    data_bytes: int          # one staged copy of the block
    lane_bytes: int          # per-λ solver state
    lane_chunk: int          # c lanes solved per pass
    passes: int              # ceil(K / c) compute passes over the block
    strategy: str            # full_k | chunked | single_lambda
    peak_bytes: int          # planned peak: double-buffered data + c lanes
    over_budget: bool        # even c=1 exceeds the budget

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """The whole ladder's plan for a K-lane sweep."""

    coordinate: str
    lanes: int
    dim: int
    dtype: str
    history: int
    budget_bytes: int
    budget_source: str       # env | backend | fallback | override
    resident_bytes: int      # held beside every bucket: the flat-order map
    buckets: Tuple[BucketPlan, ...]

    @property
    def lane_chunk(self) -> int:
        """The ladder-wide chunk: the tightest bucket's c. The
        all-at-once swept program solves every bucket in one trace, so
        it must run at the chunk the worst bucket tolerates."""
        return min((b.lane_chunk for b in self.buckets), default=self.lanes)

    @property
    def passes(self) -> int:
        return max((b.passes for b in self.buckets), default=1)

    @property
    def peak_bytes(self) -> int:
        return max((b.peak_bytes for b in self.buckets), default=0)

    @property
    def degraded(self) -> bool:
        return any(b.strategy != STRATEGY_FULL for b in self.buckets)

    @property
    def over_budget(self) -> bool:
        return any(b.over_budget for b in self.buckets)

    def to_dict(self) -> dict:
        return {
            "coordinate": self.coordinate,
            "lanes": self.lanes,
            "dim": self.dim,
            "dtype": self.dtype,
            "history": self.history,
            "budget_bytes": self.budget_bytes,
            "budget_source": self.budget_source,
            "resident_bytes": self.resident_bytes,
            "lane_chunk": self.lane_chunk,
            "passes": self.passes,
            "peak_bytes": self.peak_bytes,
            "degraded": self.degraded,
            "over_budget": self.over_budget,
            "buckets": [b.to_dict() for b in self.buckets],
        }


def plan_block_ladder(
    bucket_shapes: Sequence[Tuple[int, int, int]],
    *,
    lanes: int,
    dim: int,
    itemsize: int,
    history: int = 10,
    hbm_budget_bytes: Optional[int] = None,
    coordinate: str = "re",
    dtype: str = "",
    double_buffer: bool = True,
    resident_bytes: int = 0,
) -> BlockPlan:
    """Plan a K-lane sweep over a bucket ladder of ``(E_b, S_b, K_b)``
    shapes, ``resident_bytes`` of the budget being held beside every
    bucket. Pure byte arithmetic — nothing is staged, nothing traced."""
    if lanes < 1:
        raise ValueError(f"lanes must be >= 1, got {lanes}")
    if hbm_budget_bytes is None:
        budget, source = default_hbm_budget_bytes()
    else:
        budget, source = int(hbm_budget_bytes), "override"
    if budget < 1:
        raise ValueError(f"hbm budget must be positive, got {budget}")
    data_copies = 2 if double_buffer else 1
    buckets = []
    for bi, (e, s, w) in enumerate(bucket_shapes):
        data = block_data_bytes(e, s, w, itemsize)
        lane = lane_state_bytes(e, dim, itemsize, history)
        base = int(resident_bytes) + data_copies * data
        headroom = budget - base
        # each lane costs a tiled copy of the block plus its solver
        # state (the flattened-lane program; module docstring)
        per_lane = data + lane
        c = max(1, min(lanes, headroom // per_lane if per_lane > 0
                       else lanes))
        over = base + c * per_lane > budget
        passes = -(-lanes // c)
        strategy = (STRATEGY_FULL if c >= lanes
                    else STRATEGY_CHUNKED if c > 1
                    else STRATEGY_SINGLE)
        buckets.append(BucketPlan(
            bucket=bi, entity_rows=int(e), max_samples=int(s),
            ell_width=int(w), data_bytes=data, lane_bytes=lane,
            lane_chunk=int(c), passes=int(passes), strategy=strategy,
            peak_bytes=base + c * per_lane, over_budget=bool(over)))
    return BlockPlan(coordinate=coordinate, lanes=int(lanes), dim=int(dim),
                     dtype=str(dtype), history=int(history),
                     budget_bytes=int(budget), budget_source=source,
                     resident_bytes=int(resident_bytes),
                     buckets=tuple(buckets))


def plan_for_dataset(dataset, *, lanes: int, history: int = 10,
                     hbm_budget_bytes: Optional[int] = None,
                     coordinate: str = "re",
                     double_buffer: bool = True) -> BlockPlan:
    """Plan from a ``RandomEffectDataset``'s actual bucket ladder."""
    import numpy as np

    shapes = [(b.num_rows, b.max_samples, b.features.values.shape[-1])
              for b in dataset.blocks]
    dt = (np.dtype(dataset.blocks[0].labels.dtype) if dataset.blocks
          else np.dtype(np.float32))
    return plan_block_ladder(
        shapes, lanes=lanes, dim=dataset.projected_dim,
        itemsize=dt.itemsize, history=history,
        hbm_budget_bytes=hbm_budget_bytes, coordinate=coordinate,
        dtype=str(dt), double_buffer=double_buffer,
        resident_bytes=4 * dataset.num_flat_samples)


# -- plan accounting for the RunReport `re_plan` section ---------------------

_PLAN_STATS = {
    "plans": 0,                 # plans recorded this process
    "buckets_degraded": 0,      # buckets planned below full-K lanes
    "buckets_over_budget": 0,   # buckets that exceed the budget even at c=1
    "last_plan": None,          # most recent plan, as a dict
}


def record_plan(plan: BlockPlan) -> None:
    """Account one emitted plan (host-side bookkeeping only)."""
    _PLAN_STATS["plans"] += 1
    _PLAN_STATS["buckets_degraded"] += sum(
        1 for b in plan.buckets if b.strategy != STRATEGY_FULL)
    _PLAN_STATS["buckets_over_budget"] += sum(
        1 for b in plan.buckets if b.over_budget)
    _PLAN_STATS["last_plan"] = plan.to_dict()


def reset_plan_stats() -> None:
    _PLAN_STATS.update(plans=0, buckets_degraded=0, buckets_over_budget=0,
                       last_plan=None)


def report_section() -> Optional[dict]:
    """The RunReport ``re_plan`` section; ``None`` while no sweep has
    been planned (obs/report.py reads this via ``sys.modules`` so
    non-sweeping runs pay nothing)."""
    if not _PLAN_STATS["plans"]:
        return None
    return {
        "plans": _PLAN_STATS["plans"],
        "buckets_degraded": _PLAN_STATS["buckets_degraded"],
        "buckets_over_budget": _PLAN_STATS["buckets_over_budget"],
        "last_plan": _PLAN_STATS["last_plan"],
    }
