"""OWL-QN: orthant-wise limited-memory quasi-Newton for L1/elastic-net.

The reference delegates to breeze.optimize.OWLQN with a per-index L1 weight
function (optimization/OWLQN.scala:40,80); this is a fresh JAX
implementation of the Andrew & Gao (2007) algorithm: pseudo-gradient,
two-loop direction on smooth-gradient history, sign-aligned direction,
orthant-projected backtracking line search. The L1 weight is a traced
argument so regularization-path sweeps reuse one compiled solve, and a
static ``config.l1_mask`` exempts indices (e.g. the intercept) from the
penalty.

Each step of an iteration runs under a ``jax.named_scope``
``optim/owlqn/<step>``: ``init``, ``direction``, ``linesearch`` (the
orthant-projected backtracking, its evaluations included), ``update``,
``converged``, and ``loop`` around the ``while_loop`` itself (PERF.md §3;
the names are an interface).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from photon_tpu.optim.base import (
    ConvergenceReason,
    FailureMode,
    StateTracking,
    SolverConfig,
    SolverResult,
    absolute_tolerances,
    convergence_reason,
    nonfinite_code,
)
from photon_tpu.optim.lbfgs import push_pair, two_loop_direction

Array = jax.Array


def _pseudo_gradient(x: Array, g: Array, l1: Array) -> Array:
    right = g + l1   # derivative moving positive
    left = g - l1    # derivative moving negative
    pg_zero = jnp.where(right < 0, right, jnp.where(left > 0, left, 0.0))
    return jnp.where(x > 0, right, jnp.where(x < 0, left, pg_zero))


def _project_orthant(x: Array, orthant: Array) -> Array:
    return jnp.where(x * orthant > 0, x, 0.0)


class _Carry(NamedTuple):
    x: Array
    f: Array          # full objective: smooth + l1
    g: Array          # smooth gradient
    pg: Array         # pseudo-gradient
    f_prev: Array
    s_hist: Array     # age order (lbfgs.push_pair): slot 0 the newest pair
    y_hist: Array
    rho: Array
    n_pairs: Array
    it: Array
    reason: Array
    n_evals: Array
    failure: Array    # int32 FailureMode (non-zero terminates the loop)
    trk: Optional[StateTracking]  # per-iteration ring buffer (None = off)


def minimize(
    value_and_grad,
    x0: Array,
    *args,
    l1_weight,
    config: SolverConfig = SolverConfig(),
    c1: float = 1e-4,
) -> SolverResult:
    """Minimize ``f(x) + sum(l1 * |x|)`` where ``value_and_grad`` computes
    the smooth part. ``l1_weight`` is a scalar or [d] array (traced)."""
    m = config.num_corrections
    d = x0.shape[0]
    dtype = x0.dtype

    with jax.named_scope("optim/owlqn/init"):
        l1 = jnp.broadcast_to(jnp.asarray(l1_weight, dtype), (d,))
        if config.l1_mask is not None:
            l1 = l1 * config.l1_mask

    def full_value(x, fx):
        return fx + jnp.sum(l1 * jnp.abs(x))

    with jax.named_scope("optim/owlqn/init"):
        f0s, g0 = value_and_grad(x0, *args)
        f0 = full_value(x0, f0s)
        pg0 = _pseudo_gradient(x0, g0, l1)
        tols = absolute_tolerances(f0, pg0, config.tolerance)

    def cond(c: _Carry):
        return ((c.reason == ConvergenceReason.NOT_CONVERGED)
                & (c.failure == FailureMode.NONE))

    def body(c: _Carry) -> _Carry:
        with jax.named_scope("optim/owlqn/direction"):
            direction = two_loop_direction(c.pg, c.s_hist, c.y_hist, c.rho,
                                           c.n_pairs, m)
            # sign alignment: d must agree with -pg componentwise
            direction = jnp.where(direction * (-c.pg) > 0, direction, 0.0)
            descent = jnp.dot(direction, c.pg) < 0
            direction = jnp.where(descent, direction, -c.pg)

            orthant = jnp.where(c.x != 0, jnp.sign(c.x), jnp.sign(-c.pg))

            first = c.n_pairs == 0
            pgnorm = jnp.linalg.norm(c.pg)
            step0 = jnp.where(first, jnp.minimum(1.0, 1.0 / jnp.maximum(pgnorm, 1e-12)), 1.0)

        with jax.named_scope("optim/owlqn/linesearch"):
            # orthant-projected backtracking Armijo line search. Flat-exit
            # guard (same floor problem linesearch.wolfe solves with
            # approximate-Wolfe acceptance): when a trial lands within
            # machine rounding of f after at least one halving, further
            # halvings can only get flatter — stop probing instead of
            # burning linesearch_max_iterations full data passes. The exit
            # keeps ok=False, so the improvement gate below still classifies
            # the iterate as not-improving (the honest terminal state).
            slack = 8.0 * jnp.finfo(dtype).eps * jnp.abs(c.f)

            def ls_cond(s):
                alpha, f_new, _x, _g, k, ok, stop = s
                return (~stop) & (k < config.linesearch_max_iterations)

            def ls_body(s):
                alpha, _f, _x, _g, k, _ok, _stop = s
                alpha = jnp.where(k == 0, alpha, alpha * 0.5)
                x_new = _project_orthant(c.x + alpha * direction, orthant)
                f_s, g_new = value_and_grad(x_new, *args)
                f_new = full_value(x_new, f_s)
                ok = f_new <= c.f + c1 * jnp.dot(c.pg, x_new - c.x)
                flat = (~ok) & (k >= 1) & (jnp.abs(f_new - c.f) <= slack)
                return alpha, f_new, x_new, g_new, k + 1, ok, ok | flat

            init_ls = (step0.astype(dtype), c.f, c.x, c.g,
                       jnp.asarray(0, jnp.int32), jnp.asarray(False),
                       jnp.asarray(False))
            _alpha, f_new, x_new, g_new, k, ok, _ = lax.while_loop(
                ls_cond, ls_body, init_ls)

        with jax.named_scope("optim/owlqn/update"):
            # Non-finite guard: a NaN/Inf trial must never be kept, and unlike
            # a merely flat trial it cannot be retried (the next probe would be
            # identical), so it terminates with a typed failure code. NaN fails
            # `<` on its own but -Inf passes it — gate on full finiteness.
            g_fin = jnp.all(jnp.isfinite(g_new))
            fin = jnp.isfinite(f_new) & g_fin
            failure = jnp.where(fin, jnp.asarray(FailureMode.NONE, jnp.int32),
                                nonfinite_code(f_new, g_fin))
            decreased = ok & (f_new < c.f) & fin
            x_kept = jnp.where(decreased, x_new, c.x)
            f_kept = jnp.where(decreased, f_new, c.f)
            g_kept = jnp.where(decreased, g_new, c.g)
            pg_new = _pseudo_gradient(x_kept, g_kept, l1)

            # curvature pairs from the smooth gradient (Andrew & Gao)
            s = x_kept - c.x
            yv = g_kept - c.g
            sy = jnp.dot(s, yv)
            store = decreased & (sy > 1e-10 * jnp.maximum(jnp.dot(yv, yv), 1e-30))
            s_hist, y_hist, rho = push_pair(store, c.s_hist, c.y_hist, c.rho,
                                            s, yv, sy)
            n_pairs = jnp.where(store, jnp.minimum(c.n_pairs + 1, m), c.n_pairs)
            trk = None if c.trk is None else c.trk.record(c.it, f_kept, pg_new)

        with jax.named_scope("optim/owlqn/converged"):
            it = c.it + 1
            reason = convergence_reason(it, c.f, f_kept, pg_new, tols,
                                        config.max_iterations, improved=decreased)
            reason = jnp.where(
                (reason == ConvergenceReason.NOT_CONVERGED) & ~decreased,
                jnp.asarray(ConvergenceReason.OBJECTIVE_NOT_IMPROVING, jnp.int32),
                reason,
            )
            reason = jnp.where(
                failure != FailureMode.NONE,
                jnp.asarray(ConvergenceReason.OBJECTIVE_NOT_IMPROVING, jnp.int32),
                reason,
            )

        return _Carry(x=x_kept, f=f_kept, g=g_kept, pg=pg_new, f_prev=c.f,
                      s_hist=s_hist, y_hist=y_hist, rho=rho,
                      n_pairs=n_pairs, it=it, reason=reason,
                      n_evals=c.n_evals + k, failure=failure, trk=trk)

    with jax.named_scope("optim/owlqn/init"):
        init = _Carry(
            x=x0, f=f0, g=g0, pg=pg0, f_prev=f0,
            s_hist=jnp.zeros((m, d), dtype), y_hist=jnp.zeros((m, d), dtype),
            rho=jnp.zeros((m,), dtype),
            n_pairs=jnp.asarray(0, jnp.int32),
            it=jnp.asarray(0, jnp.int32),
            reason=jnp.where(
                jnp.linalg.norm(pg0) <= tols.gradient_tol,
                jnp.asarray(ConvergenceReason.GRADIENT_CONVERGED, jnp.int32),
                jnp.asarray(ConvergenceReason.NOT_CONVERGED, jnp.int32),
            ),
            n_evals=jnp.asarray(1, jnp.int32),
            failure=nonfinite_code(f0, jnp.all(jnp.isfinite(g0))),
            trk=StateTracking.init(config.track_states, dtype),
        )

    with jax.named_scope("optim/owlqn/loop"):
        out = lax.while_loop(cond, body, init)
    return SolverResult(
        coef=out.x, value=out.f, gradient=out.pg,
        iterations=out.it, reason=out.reason, num_fun_evals=out.n_evals,
        loss_history=None if out.trk is None else out.trk.loss,
        gnorm_history=None if out.trk is None else out.trk.gnorm,
        step_history=None if out.trk is None else out.trk.step,
        failure=out.failure,
    )
