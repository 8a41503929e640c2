"""Solver contracts: state, convergence reasons, tolerance semantics.

Reference: photon-lib optimization/Optimizer.scala:36-190 (template method:
absolute tolerances derived from the initial state, convergence reasons at
:135-149), OptimizerState.scala, OptimizationStatesTracker.scala:31.

TPU re-design: a solver is a pure jittable function
``minimize(obj, x0, data, hyper, config) -> SolverResult``; the optimize
loop is a ``lax.while_loop`` carry rather than a driver-side iteration, so
the whole solve (including every "treeAggregate") is ONE XLA program.
Because all control flow is lax-level, the same solver can be ``vmap``-ed
over entity blocks for the random-effect path — per-entity convergence
masking falls out of the while_loop batching rule.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


class ConvergenceReason(enum.IntEnum):
    """Reference: Optimizer.getConvergenceReason (Optimizer.scala:135-149)."""

    NOT_CONVERGED = 0
    MAX_ITERATIONS = 1
    FUNCTION_VALUES_CONVERGED = 2
    GRADIENT_CONVERGED = 3
    OBJECTIVE_NOT_IMPROVING = 4
    # TPU-native extension (no reference analog): the stochastic dual
    # solver (optim/sdca.py) terminates on a duality-gap certificate
    # rather than value/gradient deltas — the gap bounds the primal
    # suboptimality directly, so this is a stronger typed stop.
    DUALITY_GAP_CONVERGED = 5


class FailureMode(enum.IntEnum):
    """Typed device-side failure detected inside a solver while_loop.

    The reference has no analog — a NaN objective poisons the Breeze
    history silently and the model that comes out is garbage. Here every
    solver guards its carry: a non-finite loss/gradient/step rejects the
    step and terminates the solve with one of these codes on
    ``SolverResult.failure``, leaving the last finite iterate as the
    result. Coordinate descent (game/descent.py) reads the code at the
    coordinate boundary and rolls back."""

    NONE = 0
    NON_FINITE_LOSS = 1
    NON_FINITE_GRADIENT = 2
    NON_FINITE_STEP = 3


@dataclasses.dataclass(frozen=True)
class SolverConfig:
    """Reference: OptimizerConfig.scala:28 + per-solver defaults
    (LBFGS.scala:152-157, TRON.scala:256-262)."""

    max_iterations: int = 100
    tolerance: float = 1e-7
    # L-BFGS
    num_corrections: int = 10
    # TRON
    max_cg_iterations: int = 20
    max_improvement_failures: int = 5
    # Line search
    linesearch_max_iterations: int = 25
    # Box constraints (reference: constraintMap / LBFGSB bounds) — arrays [d]
    lower_bounds: Optional[jax.Array] = None
    upper_bounds: Optional[jax.Array] = None
    # L1 (OWL-QN): per-index weight mask multiplying the l1 weight from hyper;
    # None means regularize every index.
    l1_mask: Optional[jax.Array] = None
    # Per-iteration (loss, ||g||) ring buffer size; 0 disables tracking
    # (reference: OptimizationStatesTracker.scala:31 keeps up to 100 states)
    track_states: int = 0


class SolverResult(NamedTuple):
    """Final state, mirroring OptimizerState + convergence bookkeeping."""

    coef: Array
    value: Array
    gradient: Array
    iterations: Array          # int32
    reason: Array              # int32 ConvergenceReason
    num_fun_evals: Array       # int32 — objective evaluations (profiling)
    # ring buffers of the last `track_states` iterations (None when off)
    loss_history: Optional[Array] = None    # [T]
    gnorm_history: Optional[Array] = None   # [T]
    step_history: Optional[Array] = None    # [T] accepted step sizes (NaN
    #                                         where the solver has no step)
    # int32 FailureMode; None only for legacy constructions that predate
    # the non-finite guards (treated as NONE by consumers)
    failure: Optional[Array] = None
    # TRON's curvature work, int32 scalars; None (no leaves: no other
    # solver's program carries them) everywhere else. ``cg_steps``: one
    # operator product each; ``hessian_builds``: operators taken at a new
    # point (one at the start, one after each accepted step that is not the
    # last); ``rejected_steps``: trial points the trust region refused
    cg_steps: Optional[Array] = None
    hessian_builds: Optional[Array] = None
    rejected_steps: Optional[Array] = None


class StateTracking(NamedTuple):
    """While-loop carry fragment for the per-iteration ring buffer.

    Device-resident by design: the series accumulate inside the jitted
    while-loop carry and only cross to the host when a tracker/report
    actually reads them — never via callbacks staged into the loop.
    """

    loss: Array    # [T]
    gnorm: Array   # [T]
    step: Array    # [T] accepted step size (NaN for steps the solver
    #                doesn't parameterize, e.g. TRON's trust region)

    @staticmethod
    def init(size: int, dtype) -> Optional["StateTracking"]:
        if size <= 0:
            return None
        nan = jnp.full((size,), jnp.nan, dtype)
        return StateTracking(loss=nan, gnorm=nan, step=nan)

    def record(self, it: Array, f: Array, g: Array,
               step: Optional[Array] = None) -> "StateTracking":
        slot = it % self.loss.shape[0]
        return StateTracking(
            loss=self.loss.at[slot].set(f),
            gnorm=self.gnorm.at[slot].set(jnp.linalg.norm(g)),
            step=self.step.at[slot].set(
                jnp.nan if step is None else step),
        )


class Tolerances(NamedTuple):
    """Absolute tolerances set from the initial state
    (reference: Optimizer.setAbsTolerances)."""

    value_tol: Array
    gradient_tol: Array


def absolute_tolerances(f0: Array, g0: Array, rel_tol: float) -> Tolerances:
    eps = jnp.asarray(jnp.finfo(g0.dtype).tiny, dtype=g0.dtype)
    return Tolerances(
        value_tol=rel_tol * jnp.maximum(jnp.abs(f0), eps),
        gradient_tol=rel_tol * jnp.maximum(jnp.linalg.norm(g0), eps),
    )


def convergence_reason(
    it: Array,
    f_prev: Array,
    f: Array,
    g: Array,
    tols: Tolerances,
    max_iterations: int,
    improved: Optional[Array] = None,
    gnorm: Optional[Array] = None,
) -> Array:
    """Priority-ordered convergence decision, matching the reference order
    MaxIterations -> FunctionValuesConverged -> GradientConverged
    (Optimizer.scala:135-149). OBJECTIVE_NOT_IMPROVING is emitted by
    solvers that track improvement failures (TRON), not here.

    ``improved`` (bool) says the iterate actually changed this iteration:
    a rejected step leaves f == f_prev, and |delta f| = 0 must NOT read as
    FUNCTION_VALUES_CONVERGED — the reference classifies an unchanged
    iterate as ObjectiveNotImproving before checking function values
    (Optimizer.scala:140-142); here the solver's own failure counting
    handles that, so the function-values check is simply gated off.

    ``gnorm`` lets a solver that already holds g . g (e.g. the Gram-based
    directional L-BFGS) pass ||g|| in instead of paying one more full pass
    over a sharded 10^7-dim gradient here.
    """
    if gnorm is None:
        gnorm = jnp.linalg.norm(g)
    f_conv = jnp.abs(f_prev - f) <= tols.value_tol
    if improved is not None:
        f_conv = f_conv & improved
    reason = jnp.where(
        it >= max_iterations,
        ConvergenceReason.MAX_ITERATIONS,
        jnp.where(
            f_conv,
            ConvergenceReason.FUNCTION_VALUES_CONVERGED,
            jnp.where(
                gnorm <= tols.gradient_tol,
                ConvergenceReason.GRADIENT_CONVERGED,
                ConvergenceReason.NOT_CONVERGED,
            ),
        ),
    )
    return reason.astype(jnp.int32)


def nonfinite_code(f: Array, g_finite: Array) -> Array:
    """int32 FailureMode from a scalar loss and a scalar gradient-finite
    flag (callers pick the cheapest finite witness they have — e.g. the
    directional L-BFGS uses its already-computed g.g instead of paying a
    full pass over a sharded gradient)."""
    return jnp.where(
        jnp.isfinite(f),
        jnp.where(g_finite, FailureMode.NONE, FailureMode.NON_FINITE_GRADIENT),
        FailureMode.NON_FINITE_LOSS,
    ).astype(jnp.int32)


# Objective closures the solvers consume: fg(x, data, hyper) -> (f, g) and
# (second order) hv(x, v, data, hyper) -> Hv.
ValueAndGrad = Callable[..., Tuple[Array, Array]]
HessVec = Callable[..., Array]


def jit_donating(fn, donate_argnums=(0,)):
    """``jax.jit`` with solver-state buffers donated on accelerator backends.

    Donating x0 lets XLA alias the initial coefficients straight into the
    while-loop carry instead of round-tripping a fresh HBM buffer per
    solve — at model-sharded scale that buffer is the full per-device θ
    shard. The CPU backend ignores donation (and warns about it), so the
    gate keeps host runs quiet; callers must still never hand a donated
    position a caller-owned array they intend to reuse (see
    GlmOptimizationProblem.run's defensive copy for warm starts)."""
    if jax.default_backend() == "cpu":
        return jax.jit(fn)
    return jax.jit(fn, donate_argnums=donate_argnums)


def project_box(x: Array, config: SolverConfig) -> Array:
    """Box projection after each step (reference: LBFGS.scala box-constraint
    projection; OptimizerConfig.constraintMap)."""
    if config.lower_bounds is not None:
        x = jnp.maximum(x, config.lower_bounds)
    if config.upper_bounds is not None:
        x = jnp.minimum(x, config.upper_bounds)
    return x
