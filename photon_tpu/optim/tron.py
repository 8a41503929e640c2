"""TRON: trust-region Newton with truncated conjugate-gradient.

A fresh JAX implementation of the algorithm the reference hand-ports from
LIBLINEAR (optimization/TRON.scala:80, runOneIteration :152,
truncatedConjugateGradientMethod :278): outer trust-region loop with
(eta0, eta1, eta2) = (1e-4, 0.25, 0.75) and (sigma1, sigma2, sigma3) =
(0.25, 0.5, 4.0), inner Steihaug CG on Hessian-vector products, retry on
non-improvement capped at ``max_improvement_failures`` (5). Defaults
maxIter=15, tol=1e-5, CG cap 20 (TRON.scala:256-262).

The GLM Hessian at a point is fixed by its per-sample curvature weights
``w_i l''(m_i)``, and the evaluation at that point has the margins they are
taken from: the objective hands the weights back beside value and gradient
(``GLMObjective.value_gradient_and_weights``), the solver carries those of
the point it stands at (the trial point's, once accepted), and no operator
reads X of its own. What a Hessian-vector product costs depends on the
operator the caller builds from them (``optim/problem.py`` chooses it):
matrix-free, the weights themselves and one product ``X^T (d2 * Xv)`` under
``agg/hessian_vector`` where the reference pays a treeAggregate (ONE read of
X through the fused kernel where ``pallas_glm.dense_route`` admits the
matrix, XLA's two passes elsewhere); explicit, NO pass over X (one
``[d, d] @ [d]`` product under ``optim/tron/direction``) after one
``X^T D X`` contraction an operator build (``agg/hessian_matrix``). The
operator belongs to a POINT: it is taken at the start and again only after
an accepted step, never after a rejected one (the point did not move), and
``SolverResult`` counts the operators taken, the CG steps and the rejected
steps of a solve.

Each step of an iteration runs under a ``jax.named_scope``
``optim/tron/<step>``: ``init``, ``hessian`` (taking the operator at the
point: the explicit matrix built from the carried weights, and the weights
carried on from an accepted trial), ``direction`` (the truncated CG,
Hessian-vector products included), ``trial`` (the evaluation at the trial
point), ``update`` (trust radius and acceptance), ``converged``, and
``loop`` around the outer ``while_loop`` itself (PERF.md §3; the names are
an interface).
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

Array = jax.Array

_ETA0, _ETA1, _ETA2 = 1e-4, 0.25, 0.75
_SIGMA1, _SIGMA2, _SIGMA3 = 0.25, 0.5, 4.0


class _CGCarry(NamedTuple):
    s: Array
    r: Array
    d: Array
    rr: Array
    it: Array
    done: Array


def _trcg(hess_vec, g, delta, max_cg, cg_tol_factor, *args):
    """Steihaug truncated CG: approximately solve H s = -g within ||s||<=delta.

    Returns (s, r, steps) with r the final residual -g - Hs (used in
    prered) and steps the CG steps taken (one operator product each).
    """
    dtype = g.dtype
    r0 = -g
    cg_tol = cg_tol_factor * jnp.linalg.norm(g)

    def cond(c: _CGCarry):
        return (~c.done) & (c.it < max_cg) & (jnp.sqrt(c.rr) > cg_tol)

    def body(c: _CGCarry) -> _CGCarry:
        hd = hess_vec(c.d, *args)
        dhd = jnp.dot(c.d, hd)
        alpha = c.rr / jnp.where(dhd > 0, dhd, 1.0)
        # non-positive curvature: jump to the trust-region boundary
        npc = dhd <= 0

        s_try = c.s + alpha * c.d
        outside = jnp.linalg.norm(s_try) > delta

        # boundary step: find tau >= 0 with ||s + tau d|| = delta
        sd = jnp.dot(c.s, c.d)
        dd = jnp.dot(c.d, c.d)
        ss = jnp.dot(c.s, c.s)
        rad = jnp.sqrt(jnp.maximum(sd * sd + dd * (delta * delta - ss), 0.0))
        tau = (rad - sd) / jnp.where(dd > 0, dd, 1.0)

        hit_boundary = npc | outside
        step = jnp.where(hit_boundary, tau, alpha)
        s_new = c.s + step * c.d
        r_new = c.r - step * hd
        rr_new = jnp.dot(r_new, r_new)
        beta = rr_new / jnp.where(c.rr > 0, c.rr, 1.0)
        d_new = r_new + beta * c.d

        return _CGCarry(
            s=s_new, r=r_new, d=d_new, rr=rr_new,
            it=c.it + 1, done=hit_boundary,
        )

    init = _CGCarry(
        s=jnp.zeros_like(g), r=r0, d=r0, rr=jnp.dot(r0, r0),
        it=jnp.asarray(0, jnp.int32), done=jnp.asarray(False),
    )
    out = lax.while_loop(cond, body, init)
    return out.s, out.r, out.it


class _Carry(NamedTuple):
    x: Array
    f: Array
    g: Array
    f_prev: Array
    delta: Array
    it: Array
    failures: Array
    reason: Array
    n_evals: Array
    nf_count: Array   # consecutive non-finite trial steps
    failure: Array    # int32 FailureMode (non-zero terminates the loop)
    trk: "Optional[StateTracking]"  # per-iteration ring buffer (None = off)
    hstate: object    # hess_setup's operator at x (None without hess_setup)
    d2: object        # the curvature weights at x (None without hess_apply)
    stale: Array      # bool: x has moved since its operator was taken
    cg_steps: Array   # int32, summed over the outer iterations
    builds: Array     # int32 operators taken at a new point
    rejected: Array   # int32 trial steps refused


def minimize(
    value_and_grad,
    hess_vec,
    x0: Array,
    *args,
    config: SolverConfig = SolverConfig(max_iterations=15, tolerance=1e-5),
    cg_tol_factor: float = 0.1,
    hess_setup=None,
    hess_apply=None,
) -> SolverResult:
    """Minimize with ``value_and_grad(x, *args)`` and
    ``hess_vec(x, v, *args)`` (Hessian at x applied to v).

    When ``hess_apply`` is given, the Hessian comes from curvature weights
    instead: ``value_and_grad`` returns a third result, the per-sample
    weights at x, and a CG step applies ``hess_apply(op, v, *args)`` to
    the operator of the point the solver stands at. That operator is the
    weights themselves (matrix-free: no product re-derives the margins, as
    the reference's does, HessianVectorAggregator.scala:37), or,
    with ``hess_setup``, ``hess_setup(d2, *args)`` (e.g. the explicit
    d x d matrix for small dims), built under a ``lax.cond`` on "x moved
    since the last build": a rejected step keeps the operator it was
    computed with. Either way no operator evaluates anything at x: the
    weights are those of the evaluation that took x, the first or an
    accepted trial."""
    curvature = hess_apply is not None
    with jax.named_scope("optim/tron/init"):
        f0, g0, d0 = _evaluate(value_and_grad, x0, args, curvature)
        tols = absolute_tolerances(f0, g0, config.tolerance)
    dtype = x0.dtype

    def cond(c: _Carry):
        return ((c.reason == ConvergenceReason.NOT_CONVERGED)
                & (c.failure == FailureMode.NONE))

    def body(c: _Carry) -> _Carry:
        hstate = None
        if hess_setup is not None:
            with jax.named_scope("optim/tron/hessian"):
                hstate = lax.cond(c.stale,
                                  lambda: hess_setup(c.d2, *args),
                                  lambda: c.hstate)
            hv = lambda v: hess_apply(hstate, v, *args)
        elif curvature:
            hv = lambda v: hess_apply(c.d2, v, *args)
        else:
            hv = lambda v: hess_vec(c.x, v, *args)
        with jax.named_scope("optim/tron/direction"):
            s, r, cg = _trcg(lambda v, *_: hv(v), c.g, c.delta,
                             config.max_cg_iterations, cg_tol_factor)

        with jax.named_scope("optim/tron/trial"):
            gs = jnp.dot(c.g, s)
            prered = -0.5 * (gs - jnp.dot(s, r))
            x_try = c.x + s
            f_try, g_try, d_try = _evaluate(value_and_grad, x_try, args,
                                            curvature)
            actred = c.f - f_try
            snorm = jnp.linalg.norm(s)

        with jax.named_scope("optim/tron/update"):
            # trust-radius update (LIBLINEAR/TRON.scala constants); the
            # first step's length caps the initial radius ||g0||
            # (tron.cpp: ``if (iter == 1) delta = min(delta, snorm)``)
            delta_in = jnp.where((c.it == 0) & jnp.isfinite(snorm),
                                 jnp.minimum(c.delta, snorm), c.delta)
            denom = f_try - c.f - gs
            alpha = jnp.where(denom <= 0, _SIGMA3,
                              jnp.maximum(_SIGMA1, -0.5 * (gs / jnp.where(denom != 0, denom, 1.0))))
            asn = alpha * snorm
            delta = jnp.where(
                actred < _ETA0 * prered,
                jnp.minimum(jnp.maximum(asn, _SIGMA1 * snorm), _SIGMA2 * delta_in),
                jnp.where(
                    actred < _ETA1 * prered,
                    jnp.maximum(_SIGMA1 * delta_in, jnp.minimum(asn, _SIGMA2 * delta_in)),
                    jnp.where(
                        actred < _ETA2 * prered,
                        jnp.maximum(_SIGMA1 * delta_in, jnp.minimum(asn, _SIGMA3 * delta_in)),
                        jnp.maximum(delta_in, jnp.minimum(asn, _SIGMA3 * delta_in)),
                    ),
                ),
            )

            # Non-finite guard: a NaN actred fails `>` on its own, but a -Inf
            # f_try makes actred = +Inf and would be accepted — gate acceptance
            # on full finiteness of the trial, and keep the trust radius finite
            # (a NaN prered/asn poisons delta even on a rejected step) so the
            # shrunken region can recover from transient overflow.
            g_fin = jnp.all(jnp.isfinite(g_try))
            fin = jnp.isfinite(f_try) & g_fin
            accept = fin & (actred > _ETA0 * prered)
            delta = jnp.where(jnp.isfinite(delta), delta, 0.5 * delta_in)
            x_new = jnp.where(accept, x_try, c.x)
            f_new = jnp.where(accept, f_try, c.f)
            g_new = jnp.where(accept, g_try, c.g)
            failures = jnp.where(accept, 0, c.failures + 1).astype(jnp.int32)
            nf_count = jnp.where(fin, 0, c.nf_count + 1).astype(jnp.int32)
            failure = jnp.where(
                nf_count >= 2,
                nonfinite_code(f_try, g_fin),
                jnp.asarray(FailureMode.NONE, jnp.int32),
            )
            trk = None if c.trk is None else c.trk.record(c.it, f_new, g_new)
        with jax.named_scope("optim/tron/hessian"):
            # the next operator's input: the trial point's weights if the
            # solver moved there
            d2 = None if d_try is None else jnp.where(accept, d_try, c.d2)

        with jax.named_scope("optim/tron/converged"):
            it = c.it + 1
            reason = convergence_reason(it, c.f, f_new, g_new, tols,
                                        config.max_iterations, improved=accept)
            reason = jnp.where(
                (reason == ConvergenceReason.NOT_CONVERGED)
                & (failures >= config.max_improvement_failures),
                jnp.asarray(ConvergenceReason.OBJECTIVE_NOT_IMPROVING, jnp.int32),
                reason,
            )
            reason = jnp.where(
                failure != FailureMode.NONE,
                jnp.asarray(ConvergenceReason.OBJECTIVE_NOT_IMPROVING, jnp.int32),
                reason,
            )

        return _Carry(x=x_new, f=f_new, g=g_new, f_prev=c.f, delta=delta,
                      it=it, failures=failures, reason=reason,
                      n_evals=c.n_evals + 1, nf_count=nf_count,
                      failure=failure, trk=trk, hstate=hstate, d2=d2,
                      stale=accept,
                      cg_steps=c.cg_steps + cg,
                      builds=c.builds + c.stale.astype(jnp.int32),
                      rejected=c.rejected + (~accept).astype(jnp.int32))

    with jax.named_scope("optim/tron/init"):
        zero = jnp.asarray(0, jnp.int32)
        init = _Carry(
            x=x0, f=f0, g=g0, f_prev=f0,
            delta=jnp.linalg.norm(g0).astype(dtype),
            it=jnp.asarray(0, jnp.int32),
            failures=jnp.asarray(0, jnp.int32),
            reason=jnp.where(
                jnp.linalg.norm(g0) <= tols.gradient_tol,
                jnp.asarray(ConvergenceReason.GRADIENT_CONVERGED, jnp.int32),
                jnp.asarray(ConvergenceReason.NOT_CONVERGED, jnp.int32),
            ),
            n_evals=jnp.asarray(1, jnp.int32),
            nf_count=jnp.asarray(0, jnp.int32),
            failure=nonfinite_code(f0, jnp.all(jnp.isfinite(g0))),
            trk=StateTracking.init(config.track_states, dtype),
            # a placeholder of the operator's shape: the first trip builds
            hstate=None if hess_setup is None else jax.tree.map(
                lambda a: jnp.zeros(a.shape, a.dtype),
                jax.eval_shape(hess_setup, d0, *args)),
            d2=d0,
            stale=jnp.asarray(curvature),
            cg_steps=zero, builds=zero, rejected=zero,
        )

    with jax.named_scope("optim/tron/loop"):
        out = lax.while_loop(cond, body, init)
    return SolverResult(
        coef=out.x, value=out.f, gradient=out.g,
        iterations=out.it, reason=out.reason, num_fun_evals=out.n_evals,
        loss_history=None if out.trk is None else out.trk.loss,
        gnorm_history=None if out.trk is None else out.trk.gnorm,
        step_history=None if out.trk is None else out.trk.step,
        failure=out.failure,
        cg_steps=out.cg_steps, hessian_builds=out.builds,
        rejected_steps=out.rejected,
    )


def _evaluate(value_and_grad, x, args, curvature: bool):
    """``(f, g, d2)`` at x: the curvature weights where the evaluation hands
    them back (``curvature``), else None."""
    out = value_and_grad(x, *args)
    return out if curvature else (*out, None)
