"""Lane-batched solvers: K hyperparameter configurations in ONE program.

Photon ML's tuner treats every regularization setting as a separate full
training run, so a K-point sweep costs K data passes. Stacking the K
coefficient vectors into a ``[K, d]`` array turns the per-example margin
into an ``[n, K]`` matmul the MXU executes at near-constant cost for
small K — the shared-data-pass economics of hierarchical GLM training
(Snap ML, arXiv:1803.06333).

The mechanism is ``jax.vmap`` over the existing lax-level L-BFGS /
OWL-QN solvers, which the batching rules turn into exactly the program
we want:

- the dense data term ``x @ theta`` vmapped over ``theta`` becomes one
  ``X Θᵀ`` dot_general; the sparse-ELL gather ``theta[x.indices]``
  becomes one stacked gather over the shared plan — the batch itself is
  closed over inside the trace, never copied per lane;
- each lane gets an *independent* line search (the inner while_loop is
  vmapped like the outer one);
- the outer ``lax.while_loop`` cond becomes "any lane still active" and
  every carry update is ``where``-selected per lane, so converged lanes
  freeze bitwise (their ``it``/``reason`` stop advancing) while the
  rest continue — the loop exits when all lanes converge, with no
  recompiles as lanes finish and no host syncs;
- a lane that hits a typed ``FailureMode`` (e.g. NaN-poisoned data)
  freezes the same way without sinking its siblings;
- with K=1 the "any over one lane" cond is the scalar cond, so the
  singleton-lane program takes exactly the scalar solver's iteration
  count.

Precision is stated, not defaulted: with two matrix operands the lane
contractions are real dots to XLA, and on a TPU a float32 dot at default
precision is ONE bfloat16 pass on the MXU (the scalar solver's
matrix-vector products are not: the compiler runs them on the vector unit
in float32). The lane solvers are therefore traced under
``LANE_MATMUL_PRECISION``, so every ``dot_general`` of a lane program
carries it and a lane reads what its scalar fit reads.

On a mesh the whole vmapped solve runs inside ONE outer shard_map over
the sample axes; the per-evaluation reduction is a single staged
ICI→DCN psum of the packed ``[K, d+1]`` value/gradient block (the
collective batching rule keeps it one psum eqn regardless of K).
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from photon_tpu.function.objective import GLMObjective, Hyper
from photon_tpu.optim import lbfgs, owlqn
from photon_tpu.optim.base import SolverConfig, SolverResult

Array = jax.Array

# what every dot of a lane program is traced at (module docstring)
LANE_MATMUL_PRECISION = "highest"


def _vmap_lanes(one_lane: Callable, *lane_args: Array):
    """``vmap(one_lane)`` over the leading lane axis, traced so that every
    ``dot_general`` in it states ``LANE_MATMUL_PRECISION``."""
    with jax.default_matmul_precision(LANE_MATMUL_PRECISION):
        return jax.vmap(one_lane)(*lane_args)

# value_and_gradient(coef [d], hyper) -> (value, grad [d]) for ONE lane;
# the data batch is closed over so every lane shares it.
LaneValueAndGradient = Callable[[Array, Hyper], Tuple[Array, Array]]


class SweepWeightError(ValueError):
    """A sweep/tuning regularization weight is refused at config time.

    Raised for empty grids and negative / non-finite weights — before
    anything is traced, so a bad grid can never poison a compiled solve.
    """


def validate_lane_weights(weights: Sequence[float],
                          name: str = "regularization weight") -> np.ndarray:
    """Validate a sweep grid; returns the weights as a float64 1-D array.

    The single chokepoint for every path that accepts sweep weights
    (``solve_swept``, ``CoordinateConfiguration.with_regularization_weight``,
    ``cli/train --sweep-l2``): negative and non-finite values raise a
    typed :class:`SweepWeightError` here, at config time, never inside
    the compiled program.
    """
    arr = np.atleast_1d(np.asarray(weights, dtype=np.float64))
    if arr.ndim != 1 or arr.size == 0:
        raise SweepWeightError(
            f"{name} grid must be a non-empty 1-D sequence, got shape "
            f"{arr.shape}")
    if not np.all(np.isfinite(arr)):
        bad = arr[~np.isfinite(arr)]
        raise SweepWeightError(
            f"{name} grid contains non-finite values {bad.tolist()}")
    if np.any(arr < 0):
        bad = arr[arr < 0]
        raise SweepWeightError(
            f"{name} grid contains negative values {bad.tolist()}")
    return arr


def pad_lane_grid(weights: Sequence[float],
                  chunk: int) -> List[Tuple[np.ndarray, int]]:
    """Split a K-point λ grid into ⌈K/c⌉ fixed-shape lane chunks for the
    planner's chunked-lanes degradation (parallel/memory.BlockPlan).

    Returns ``[(lane_indices [c], n_real), ...]`` where ``lane_indices``
    index into the validated grid. Every chunk has EXACTLY ``c`` lanes —
    the tail is padded by repeating its last index, so one compiled
    program per (bucket, c) shape serves the whole grid; callers write
    back only the first ``n_real`` lanes of each chunk's results (the
    padded duplicates are dropped, never published).
    """
    arr = validate_lane_weights(weights)
    k = int(arr.size)
    c = max(1, min(int(chunk), k))
    out: List[Tuple[np.ndarray, int]] = []
    for lo in range(0, k, c):
        idx = np.arange(lo, min(lo + c, k), dtype=np.int64)
        n_real = int(idx.size)
        if n_real < c:
            idx = np.concatenate(
                [idx, np.full((c - n_real,), idx[-1], np.int64)])
        out.append((idx, n_real))
    return out


def minimize_lanes(value_and_gradient: LaneValueAndGradient,
                   x0_lanes: Array,
                   *,
                   l2: Array,
                   l1: Optional[Array] = None,
                   config: SolverConfig = SolverConfig(),
                   use_owlqn: bool = False) -> SolverResult:
    """Fit K lanes — stacked ``x0_lanes [K, d]``, per-lane ``l2``/``l1``
    ``[K]`` — in one vmapped L-BFGS / OWL-QN solve.

    Returns a stacked :class:`SolverResult` whose every array field has
    a leading lane axis (``coef [K, d]``, ``iterations [K]``, ...).
    Must be called under an enclosing ``jit`` with the data batch bound
    as an argument of that jit (the repo's data-as-arguments rule).
    """
    if use_owlqn:
        l1_lanes = l1 if l1 is not None else jnp.zeros_like(l2)

        def one_lane(x0, l2k, l1k):
            vg = lambda c: value_and_gradient(c, Hyper(l2_weight=l2k))
            return owlqn.minimize(vg, x0, l1_weight=l1k, config=config)

        return _vmap_lanes(one_lane, x0_lanes, l2, l1_lanes)

    def one_lane(x0, l2k):
        vg = lambda c: value_and_gradient(c, Hyper(l2_weight=l2k))
        return lbfgs.minimize(vg, x0, config=config)

    return _vmap_lanes(one_lane, x0_lanes, l2)


def minimize_lanes_meshed(objective: GLMObjective,
                          sharded_batch,
                          x0_lanes: Array,
                          *,
                          l2: Array,
                          l1: Optional[Array] = None,
                          mesh,
                          config: SolverConfig = SolverConfig(),
                          use_owlqn: bool = False) -> SolverResult:
    """Data-parallel lane batch: the entire vmapped solve runs inside
    ONE shard_map over the mesh's sample axes.

    Each lane's objective evaluates the data term over this shard's
    rows (with ``1/num_shards`` of the L2 quadratic, so shard-sums
    recover the global objective exactly — the hier invariant), then
    reduces the packed ``[grad | value]`` block with a single staged
    ICI→DCN psum. Under vmap the collective batches to one psum of the
    ``[K, d+1]`` stack, so the per-iteration DCN reduction count is
    independent of K — ``parallel/mesh.count_axis_psums`` sees the same
    count as the scalar solver.
    """
    from photon_tpu.optim import hier
    from photon_tpu.parallel import mesh as M

    sample_axes = hier._sample_axes(mesh)
    p_shards = hier._num_shards(mesh, sample_axes)

    def lanes_body(x0_l, l2_l, l1_l, batch):
        def lane_vg(c, hyper):
            f, g = objective.local_value_and_gradient(c, batch, hyper,
                                                      p_shards)
            packed = hier._staged_all_psum(
                jnp.concatenate([g, f[None]]), mesh)
            return packed[-1], packed[:-1]

        if use_owlqn:
            def one_lane(x0, l2k, l1k):
                vg = lambda c: lane_vg(c, Hyper(l2_weight=l2k))
                return owlqn.minimize(vg, x0, l1_weight=l1k, config=config)
            return _vmap_lanes(one_lane, x0_l, l2_l, l1_l)

        def one_lane(x0, l2k):
            vg = lambda c: lane_vg(c, Hyper(l2_weight=l2k))
            return lbfgs.minimize(vg, x0, config=config)
        return _vmap_lanes(one_lane, x0_l, l2_l)

    specs = hier._batch_specs(sharded_batch, sample_axes)
    l1_lanes = l1 if l1 is not None else jnp.zeros_like(l2)
    # check_vma=False: the vmapped solver while_loop mixes replicated
    # and shard-varying carries, which the varying-axes checker refuses;
    # the staged psum establishes the P() output replication it would
    # verify (hier precedent).
    return M.shard_map(lanes_body, mesh=mesh,
                       in_specs=(P(), P(), P(), specs),
                       out_specs=P(),
                       check_vma=False)(x0_lanes, l2, l1_lanes,
                                        sharded_batch)


def split_lanes(stacked: SolverResult) -> List[SolverResult]:
    """Split a stacked lane result into per-lane :class:`SolverResult`s.

    A host-boundary helper: the per-lane views are lazy indexes into the
    stacked device arrays (optional fields stay ``None``).
    """
    k = int(stacked.iterations.shape[0])
    return [
        SolverResult(*(None if f is None else f[i] for f in stacked))
        for i in range(k)
    ]


# -- sweep accounting for the RunReport `sweep` section ---------------------

_SWEEP_STATS = {
    "runs": 0,            # batched solves executed
    "lanes_total": 0,     # sum of K over runs
    "lane_records": [],   # per-run: lanes' weight/loss/iterations/reason
    "tuner": None,        # filled in by GameEstimator.tune()
}
_MAX_LANE_RECORDS = 64


def record_sweep_run(lane_records: List[dict]) -> None:
    """Account one batched solve (called at the host boundary where the
    caller already materialized per-lane scalars — no device syncs of
    its own)."""
    _SWEEP_STATS["runs"] += 1
    _SWEEP_STATS["lanes_total"] += len(lane_records)
    if len(_SWEEP_STATS["lane_records"]) < _MAX_LANE_RECORDS:
        _SWEEP_STATS["lane_records"].append(lane_records)


def record_tuner_summary(summary: dict) -> None:
    """Attach the tuner's round/selection summary to the sweep section."""
    _SWEEP_STATS["tuner"] = dict(summary)


def reset_sweep_stats() -> None:
    _SWEEP_STATS.update(runs=0, lanes_total=0, lane_records=[], tuner=None)


def report_section() -> dict:
    """The RunReport ``sweep`` section (obs/report.py reads this via
    ``sys.modules`` so runs that never sweep pay nothing)."""
    return {
        "runs": _SWEEP_STATS["runs"],
        "lanes_total": _SWEEP_STATS["lanes_total"],
        "lane_records": list(_SWEEP_STATS["lane_records"]),
        "tuner": _SWEEP_STATS["tuner"],
    }
