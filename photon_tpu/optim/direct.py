"""Exact normal-equations solve for quadratic (squared-loss) objectives.

TPU-native extension with no reference analog: the reference runs Breeze
L-BFGS / TRON to convergence on per-entity ridge problems
(SingleNodeOptimizationProblem.scala:40); for squared loss the objective
is exactly quadratic, so the minimizer is one linear solve:

    x* = x0 - H^{-1} g(x0)      (exact from ANY starting point)

H is the weighted Gram matrix + lambda*I (one MXU contraction via
aggregators.hessian_matrix) and the solve is a Cholesky factorization
(``optim/spd.py::spd_solve``) instead of thousands of sequential while_loop
iterations. Batched over entities under vmap, K <= ``spd.LANES_MAX_DIM``
runs with the entity axis on the TPU's lanes (XLA's batched [E, K, K]
potrf/trsm custom call walks the batch matrix by matrix, 2.2 us a 20 x 20
system on a v5e: PERF.md §5, PR 25); a larger K stays on ``cho_factor``.
sklearn Ridge's own `cholesky` solver is the CPU-world equivalent, which
makes bench comparisons apples-to-apples.

Requires positive-definite H: lambda > 0, or full-rank (weighted)
features. Entities with no data keep their starting coefficients (the
iterative solvers' behavior at a zero gradient).

The steps run under ``jax.named_scope`` ``optim/direct/<step>``: ``init``
(value and gradient at the start), ``hessian`` (the call into ``agg/``),
``factor_solve`` and ``update`` (PERF.md §3; the names are an interface).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from photon_tpu.optim.base import (
    ConvergenceReason,
    FailureMode,
    SolverResult,
    nonfinite_code,
)
from photon_tpu.optim.spd import spd_solve

Array = jax.Array


def _newton_step(x0: Array, f0: Array, g: Array, h: Array) -> SolverResult:
    """One exact Newton step on a quadratic with value f0 / gradient g /
    Hessian h at x0. The solution-point value and gradient follow from
    already-materialized quantities — no second data pass:
    g(x) = g + H step;  f(x) = f0 + g.step + 0.5 step.H.step.

    Singular/degenerate curvature (rank-deficient features at lambda=0,
    or an empty vmap lane) keeps the start point and SAYS SO — a failed
    entity must not read as converged in the per-entity trackers. The
    ``failure`` code distinguishes a bad input (non-finite f0/g, e.g. a
    poisoned residual) from a non-finite Cholesky step."""
    with jax.named_scope("optim/direct/factor_solve"):
        step = -spd_solve(h, g)
    with jax.named_scope("optim/direct/update"):
        ok = jnp.all(jnp.isfinite(step))
        step = jnp.where(ok, step, 0.0)
        hs = h @ step
        init_fail = nonfinite_code(f0, jnp.all(jnp.isfinite(g)))
        failure = jnp.where(
            init_fail != FailureMode.NONE,
            init_fail,
            jnp.where(ok,
                      jnp.asarray(FailureMode.NONE, jnp.int32),
                      jnp.asarray(FailureMode.NON_FINITE_STEP, jnp.int32)))
        return SolverResult(
            coef=x0 + step,
            value=f0 + jnp.dot(g, step) + 0.5 * jnp.dot(step, hs),
            gradient=g + hs,
            iterations=jnp.asarray(1, jnp.int32),
            reason=jnp.where(
                ok,
                jnp.asarray(ConvergenceReason.GRADIENT_CONVERGED, jnp.int32),
                jnp.asarray(ConvergenceReason.NOT_CONVERGED, jnp.int32)),
            num_fun_evals=jnp.asarray(1, jnp.int32),
            loss_history=None, gnorm_history=None,
            failure=failure,
        )


def minimize_path(value_and_grad_noreg, hessian_matrix_noreg, x0: Array,
                  lambdas: Array) -> SolverResult:
    """Solve the ENTIRE L2 regularization path in one data pass.

    ``value_and_grad_noreg`` / ``hessian_matrix_noreg`` evaluate the
    UN-regularized data objective; the Gram matrix G and the data
    gradient are computed once, then each lambda is one Cholesky of
    (G + lambda I) — vmapped, so an L-point ridge path costs one pass
    over the samples plus L [d, d] factorizations in one batch. (The
    iterative reference pays a full warm-started solve per lambda:
    ModelTraining.scala:134-147.) Returns a SolverResult whose leaves
    are stacked on a leading [L] axis.
    """
    with jax.named_scope("optim/direct/init"):
        f0, g0 = value_and_grad_noreg(x0)
    with jax.named_scope("optim/direct/hessian"):
        gram = hessian_matrix_noreg(x0)
        eye = jnp.eye(x0.shape[0], dtype=x0.dtype)

    def one(lam):
        # full-objective value/gradient at x0 for this lambda
        return _newton_step(x0, f0 + 0.5 * lam * jnp.dot(x0, x0),
                            g0 + lam * x0, gram + lam * eye)

    return jax.vmap(one)(lambdas)


def minimize(value_and_grad, hessian_matrix, x0: Array) -> SolverResult:
    """``value_and_grad(x) -> (f, g)``; ``hessian_matrix(x) -> [d, d]``
    constant in ``x`` for a quadratic objective (evaluated at ``x0``)."""
    with jax.named_scope("optim/direct/init"):
        f0, g0 = value_and_grad(x0)
    with jax.named_scope("optim/direct/hessian"):
        h = hessian_matrix(x0)
    return _newton_step(x0, f0, g0, h)
