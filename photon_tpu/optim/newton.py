"""Damped Newton (IRLS) with explicit Hessian factorization.

TPU-native extension of DIRECT (optim/direct.py) past quadratic losses:
for twice-differentiable GLM losses (logistic, Poisson, squared) the
minimizer is reached by a handful of Newton steps, each one

    H(x) s = -g(x);   x <- x + t s      (t from Armijo backtracking)

where H is the explicit [d, d] GLM Hessian — one curvature-weighted Gram
contraction (MXU) — and the solve is a Cholesky factorization
(``optim/spd.py::spd_solve``). A logistic GLMix per-entity solve costs ~5
factorizations total, versus TRON's nested outer x CG sequential
while_loop steps (the reference runs full iterative TRON/L-BFGS per
entity: SingleNodeOptimizationProblem.scala:40, TRON.scala:278-338).

This is classic IRLS re-shaped for the hardware: all sequential depth
that XLA cannot batch is collapsed into the one place it is algorithmically
irreducible (the outer Newton iteration). What the chip showed about the
solve (PERF.md §5-§6, PR 22-25): vmapped over entities, ``cho_factor`` /
``cho_solve`` become a batched [E, K, K] custom call that the TPU walks
matrix by matrix, 2.2 us a 20 x 20 system and 56% of a GLMix fit. So for
K <= ``spd.LANES_MAX_DIM`` the batched solve now runs with the ENTITY axis
on the lanes, K elementwise steps over [K, K + 1, E], 85 times less for a
bucket of 10,360 (3% of the fit); a larger system (a fixed effect's
unbatched K = 128) stays on XLA's factorization.

Safeguards:
  * non-PD / singular curvature (lambda = 0 with rank-deficient data)
    produces a non-finite Cholesky step -> fall back to steepest descent
    for that iteration (never silently stop at the start point);
  * Armijo backtracking rejects divergent steps (Poisson's exp margins
    can overflow on an overconfident Newton step: a non-finite trial
    value fails the acceptance test and the step halves);
  * tolerance semantics match the other solvers (absolute-from-relative
    at the initial state, Optimizer.scala:36-190 convention), so NEWTON
    drops into any config where LBFGS/TRON run today.

Each step of an iteration runs under a ``jax.named_scope``
``optim/newton/<step>``: ``init``, ``hessian`` (the call into ``agg/``),
``factor_solve`` (``spd_solve``: the Cholesky factorisation and its two
substitutions), ``direction`` (the descent safeguard), ``linesearch``, ``update``,
``converged``, and ``loop`` around the ``while_loop`` itself. The names are
what a device trace's seconds are grouped by (PERF.md §3) and are an
interface.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from photon_tpu.optim.base import (
    ConvergenceReason,
    FailureMode,
    SolverConfig,
    SolverResult,
    StateTracking,
    absolute_tolerances,
    convergence_reason,
    nonfinite_code,
)
from photon_tpu.optim.spd import spd_solve

Array = jax.Array

_ARMIJO_C1 = 1e-4


class _Carry(NamedTuple):
    x: Array
    f: Array
    g: Array
    it: Array
    n_evals: Array
    reason: Array
    failure: Array    # int32 FailureMode (non-zero terminates the loop)
    tracking: Optional[StateTracking]


def minimize(
    value_and_grad,
    hess_matrix,
    x0: Array,
    config: SolverConfig = SolverConfig(max_iterations=25, tolerance=1e-7),
) -> SolverResult:
    """``value_and_grad(x) -> (f, g)``; ``hess_matrix(x) -> [d, d]`` full
    (regularized) Hessian at x. Both are re-evaluated every outer
    iteration — unlike DIRECT, no quadratic assumption is made."""
    with jax.named_scope("optim/newton/init"):
        f0, g0 = value_and_grad(x0)
        tols = absolute_tolerances(f0, g0, config.tolerance)

    def linesearch(x, f, g, direction):
        """Armijo backtracking from t=1 (the Newton-natural step). The
        acceptance test carries a machine-epsilon slack (approximate-Wolfe
        style): near the optimum the true decrease underflows f's ulp, and
        a strict test would burn linesearch_max_iterations full data
        passes rejecting a perfectly converged step."""
        gdot = jnp.dot(g, direction)
        slack = 4.0 * jnp.finfo(x.dtype).eps * jnp.abs(f)

        def cond(c):
            t, f_new, _, k, done = c
            return (~done) & (k < config.linesearch_max_iterations)

        def body(c):
            t, _, _, k, _ = c
            f_t, g_t = value_and_grad(x + t * direction)
            ok = jnp.isfinite(f_t) & (f_t <= f + _ARMIJO_C1 * t * gdot + slack)
            return (jnp.where(ok, t, 0.5 * t), f_t, g_t, k + 1, ok)

        t0 = jnp.asarray(1.0, x.dtype)
        t, f_new, g_new, k, ok = jax.lax.while_loop(
            cond, body, (t0, f, g, jnp.asarray(0, jnp.int32),
                         jnp.asarray(False)))
        return t, f_new, g_new, k, ok

    def cond(c: _Carry):
        return ((c.reason == ConvergenceReason.NOT_CONVERGED)
                & (c.failure == FailureMode.NONE))

    def body(c: _Carry):
        with jax.named_scope("optim/newton/hessian"):
            h = hess_matrix(c.x)
        with jax.named_scope("optim/newton/factor_solve"):
            step = -spd_solve(h, c.g)
        with jax.named_scope("optim/newton/direction"):
            # descent safeguard: a non-PD factorization yields NaN/inf (both
            # paths of spd_solve) or an ascent direction; steepest descent
            # keeps the iteration alive
            newton_ok = (jnp.all(jnp.isfinite(step))
                         & (jnp.dot(c.g, step) < 0.0))
            direction = jnp.where(newton_ok, step, -c.g)
        with jax.named_scope("optim/newton/linesearch"):
            t, f_new, g_new, ls_evals, accepted = linesearch(
                c.x, c.f, c.g, direction)
        with jax.named_scope("optim/newton/update"):
            # the slack is a CLASSIFICATION device only: a step it admits with
            # f_new > f is a rounding-level ascent — keep `accepted` (the solve
            # is converged to the dtype's resolution and classifies as
            # FUNCTION_VALUES_CONVERGED below) but never move the iterate
            # uphill (same contract as linesearch.LineSearchResult)
            # non-finite guard: the Armijo test already screens f_t, but a
            # finite trial value can still carry a NaN/Inf gradient (saturated
            # margins) — never admit one into the carry, and terminate with a
            # typed failure (retrying the same step cannot help)
            g_fin = jnp.all(jnp.isfinite(g_new))
            take = accepted & (f_new <= c.f) & g_fin
            failure = jnp.where(
                accepted & ~g_fin,
                jnp.asarray(FailureMode.NON_FINITE_GRADIENT, jnp.int32),
                jnp.asarray(FailureMode.NONE, jnp.int32))
            x_new = jnp.where(take, c.x + t * direction, c.x)
            f_new = jnp.where(take, f_new, c.f)
            g_new = jnp.where(take, g_new, c.g)
            tracking = (None if c.tracking is None
                        else c.tracking.record(c.it, f_new, g_new))
        with jax.named_scope("optim/newton/converged"):
            it = c.it + 1
            reason = convergence_reason(it, c.f, f_new, g_new, tols,
                                        config.max_iterations, improved=accepted)
            # an exhausted line search means no further progress is possible
            # (TRON reports the analogous state as OBJECTIVE_NOT_IMPROVING)
            reason = jnp.where(
                (reason == ConvergenceReason.NOT_CONVERGED) & ~accepted,
                jnp.asarray(ConvergenceReason.OBJECTIVE_NOT_IMPROVING, jnp.int32),
                reason)
            reason = jnp.where(
                failure != FailureMode.NONE,
                jnp.asarray(ConvergenceReason.OBJECTIVE_NOT_IMPROVING, jnp.int32),
                reason)
        return _Carry(x_new, f_new, g_new, it,
                      c.n_evals + ls_evals, reason, failure, tracking)

    with jax.named_scope("optim/newton/init"):
        # sentinel f_prev far from f0 so the initial check can only fire on
        # the gradient (an already-stationary start) or max_iterations=0
        f_far = f0 + 2.0 * tols.value_tol + 1.0
        init = _Carry(
            x=x0, f=f0, g=g0,
            it=jnp.asarray(0, jnp.int32),
            n_evals=jnp.asarray(1, jnp.int32),
            reason=jnp.asarray(
                convergence_reason(jnp.asarray(0, jnp.int32), f_far, f0, g0,
                                   tols, config.max_iterations), jnp.int32),
            failure=nonfinite_code(f0, jnp.all(jnp.isfinite(g0))),
            tracking=StateTracking.init(config.track_states, x0.dtype))
    with jax.named_scope("optim/newton/loop"):
        out = jax.lax.while_loop(cond, body, init)
    return SolverResult(
        coef=out.x, value=out.f, gradient=out.g,
        iterations=out.it, reason=out.reason, num_fun_evals=out.n_evals,
        loss_history=None if out.tracking is None else out.tracking.loss,
        gnorm_history=None if out.tracking is None else out.tracking.gnorm,
        step_history=None if out.tracking is None else out.tracking.step,
        failure=out.failure,
    )
