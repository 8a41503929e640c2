"""Host-side views over solver state tracking.

Reference: photon-lib optimization/OptimizationStatesTracker.scala:31
(ring buffer of up to 100 (coefficients, loss, ||g||, time) states with a
convergence reason) and photon-api optimization/
RandomEffectOptimizationTracker.scala (aggregates per-entity trackers
into count/convergence-reason summaries logged after each coordinate
update, CoordinateDescent.scala:242-249).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from photon_tpu.optim.base import ConvergenceReason, SolverResult


@dataclasses.dataclass
class OptimizationStatesTracker:
    """Ordered per-iteration (loss, ||g||) trajectory for one solve."""

    losses: np.ndarray      # [k] in iteration order
    gnorms: np.ndarray      # [k]
    iterations: int
    reason: ConvergenceReason
    steps: Optional[np.ndarray] = None   # [k] accepted step sizes (NaN
    #                                      where the solver has no step)

    @staticmethod
    def from_result(result: SolverResult) -> Optional["OptimizationStatesTracker"]:
        if result.loss_history is None:
            return None
        loss = np.asarray(result.loss_history)
        gn = np.asarray(result.gnorm_history)
        it = int(result.iterations)
        size = loss.shape[0]
        if it <= size:
            order = np.arange(it)
        else:  # un-rotate the ring buffer
            order = np.arange(it - size, it) % size
        losses, gnorms = loss[order], gn[order]
        valid = np.isfinite(losses)
        steps = None
        if result.step_history is not None:
            steps = np.asarray(result.step_history)[order][valid]
        return OptimizationStatesTracker(
            losses=losses[valid], gnorms=gnorms[valid],
            iterations=it,
            reason=ConvergenceReason(int(result.reason)),
            steps=steps)

    def summary(self) -> str:
        if not len(self.losses):
            return f"converged at start ({self.reason.name})"
        return (f"{self.iterations} iters, loss {self.losses[0]:.6g} -> "
                f"{self.losses[-1]:.6g}, ||g|| {self.gnorms[-1]:.3g}, "
                f"{self.reason.name}")

    def to_dict(self) -> Dict[str, object]:
        """JSON-ready trajectory for the RunReport (pays the host
        transfer if the arrays are still on device)."""
        out: Dict[str, object] = {
            "kind": "states",
            "iterations": int(self.iterations),
            "reason": self.reason.name,
            "loss": [float(v) for v in np.asarray(self.losses)],
            "gnorm": [float(v) for v in np.asarray(self.gnorms)],
        }
        if self.steps is not None:
            out["step"] = [float(v) for v in np.asarray(self.steps)]
        return out


@dataclasses.dataclass
class RandomEffectOptimizationTracker:
    """Aggregate of per-entity solver outcomes for one coordinate update
    (or of per-lambda-lane outcomes: a fixed effect's swept update is ONE
    vmapped loop whose K lanes are its only bucket).

    ``iterations``/``reasons`` may be DEVICE arrays — the producing solve
    hands them over without a host sync, and the first summary accessor
    pays the (lazy) transfer. A blocking transfer at update time would
    serialize every coordinate-descent sweep on the solver's completion.
    """

    iterations: np.ndarray   # [E] int (numpy or jax.Array)
    reasons: np.ndarray      # [E] int (ConvergenceReason; numpy or jax.Array)
    # the size buckets' ``entity_rows`` (one [E_b] array a bucket, pad rows
    # out of range): which entities one vmapped loop solved together
    bucket_rows: Tuple[np.ndarray, ...] = ()

    @property
    def num_entities(self) -> int:
        return len(self.iterations)

    def lane_counts(self) -> Dict[str, int]:
        """What the vmapped loops ran against what the entities needed. A
        bucket's loop trips until its SLOWEST entity is done, every lane
        riding along: ``sum`` = the entities' iterations, ``trips`` = the
        buckets' largest counts, summed, ``capacity`` = entities of a
        bucket x its largest count, summed (``sum / capacity`` is the
        lanes' occupancy). Pays the host transfers, like every accessor
        here."""
        iters, _ = self._host()
        out = {"sum": 0, "capacity": 0, "trips": 0}
        for rows in self.bucket_rows:
            # a jax.Array keeps its host copy: a dataset's rows cross once
            rows = np.asarray(rows)
            its = iters[rows[(rows >= 0) & (rows < len(iters))]]
            its = its[its >= 0]
            if not len(its):
                continue
            out["sum"] += int(its.sum())
            out["trips"] += int(its.max())
            out["capacity"] += len(its) * int(its.max())
        return out

    def _host(self) -> Tuple[np.ndarray, np.ndarray]:
        if not isinstance(self.iterations, np.ndarray):
            object.__setattr__(self, "iterations", np.asarray(self.iterations))
            object.__setattr__(self, "reasons", np.asarray(self.reasons))
        return self.iterations, self.reasons

    def reason_counts(self) -> Dict[str, int]:
        _, reasons = self._host()
        out: Dict[str, int] = {}
        for r in ConvergenceReason:
            c = int(np.sum(reasons == int(r)))
            if c:
                out[r.name] = c
        return out

    def iteration_stats(self) -> Tuple[float, int, int]:
        """(mean, min, max) iterations across entities."""
        iters, _ = self._host()
        if not len(iters):
            return 0.0, 0, 0
        return (float(np.mean(iters)),
                int(np.min(iters)), int(np.max(iters)))

    def summary(self) -> str:
        mean_it, lo, hi = self.iteration_stats()
        return (f"{self.num_entities} entities, iterations "
                f"mean {mean_it:.1f} [{lo}, {hi}], reasons "
                f"{self.reason_counts()}")

    def to_dict(self) -> Dict[str, object]:
        """JSON-ready per-entity outcome aggregate for the RunReport
        (this is the drain point: the lazy device->host transfer in
        ``_host`` happens here, at a phase boundary, not in the sweep)."""
        mean_it, lo, hi = self.iteration_stats()
        out = {
            "kind": "random_effect",
            "num_entities": int(self.num_entities),
            "iterations": {"mean": mean_it, "min": lo, "max": hi},
            "reason_counts": self.reason_counts(),
        }
        if self.bucket_rows:
            out["lanes"] = self.lane_counts()
        return out
