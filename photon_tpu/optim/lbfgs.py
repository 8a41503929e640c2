"""L-BFGS as one jittable lax.while_loop (replaces breeze.optimize.LBFGS
behind the reference's LBFGS adapter, optimization/LBFGS.scala:39).

Two-loop recursion over a fixed-size (S, Y) history kept in age order (slot 0
the newest pair; a ring buffer's write position would be a per-lane value
under ``vmap`` and make every history read a gather), strong-Wolfe
line search (optim/linesearch.py), optional box projection after each step
(the reference projects into the constraint box after each Breeze step —
LBFGS.scala; LBFGSB.scala:40 gets the same treatment here).

Defaults mirror the reference: maxIter=100, numCorrections=10, tol=1e-7
(LBFGS.scala:152-157).

Because every branch is lax-level, this function serves both roles the
reference splits into DistributedOptimizationProblem (one big solve over a
sharded batch) and SingleNodeOptimizationProblem (vmap-ed over entity
blocks with per-entity convergence masking).

Each step of an iteration runs under a ``jax.named_scope``
``optim/lbfgs/<step>``: ``init`` (the evaluation at the start point),
``direction``, ``linesearch``, ``update`` (acceptance, curvature history,
carry), ``converged``, and ``loop`` around the ``while_loop`` itself (its
condition and control; a step's operations keep the step's name, the
innermost). The names are what a device trace's seconds are
grouped by (PERF.md §3) and are an interface.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from photon_tpu.optim.base import (
    ConvergenceReason,
    FailureMode,
    SolverConfig,
    SolverResult,
    StateTracking,
    absolute_tolerances,
    convergence_reason,
    nonfinite_code,
    project_box,
)
from photon_tpu.optim.linesearch import (
    wolfe_linesearch,
    wolfe_linesearch_directional,
)

Array = jax.Array


class _Carry(NamedTuple):
    x: Array
    f: Array
    g: Array
    f_prev: Array
    s_hist: Array      # [m, d], age order: slot 0 the newest pair
    y_hist: Array      # [m, d]
    rho: Array         # [m]
    n_pairs: Array     # int32: number of valid pairs (<= m)
    it: Array
    reason: Array
    n_evals: Array
    ls_failed: Array   # bool: last line search failed to decrease
    nf_count: Array    # int32: consecutive non-finite evaluations
    failure: Array     # int32 FailureMode (non-zero terminates the loop)
    trk: Optional[StateTracking]  # per-iteration ring buffer (None = off)


def two_loop_direction(g, s_hist, y_hist, rho, n_pairs, m):
    """Standard two-loop recursion over a history kept in AGE order: slot 0
    holds the newest pair, slot ``n_pairs - 1`` the oldest, the rest zeros
    (``push_pair`` keeps it so). Step ``j`` reads slot ``j`` whatever the
    solve's state, a static slice; only the mask ``j < n_pairs`` is the
    solve's own. A ring buffer's write position would be a per-lane value
    under ``vmap`` and turn every read into a gather."""
    alphas = []
    q = g
    for age in range(m):                      # newest to oldest
        valid = age < n_pairs
        a = rho[age] * jnp.dot(s_hist[age], q)
        a = jnp.where(valid, a, 0.0)
        q = q - a * y_hist[age]
        alphas.append(a)

    # initial Hessian scaling from the most recent pair
    sy = jnp.dot(s_hist[0], y_hist[0])
    yy = jnp.dot(y_hist[0], y_hist[0])
    gamma = jnp.where((n_pairs > 0) & (yy > 0), sy / jnp.where(yy > 0, yy, 1.0), 1.0)
    r = gamma * q

    for age in reversed(range(m)):            # oldest to newest
        valid = age < n_pairs
        beta = rho[age] * jnp.dot(y_hist[age], r)
        upd = s_hist[age] * (alphas[age] - beta)
        r = r + jnp.where(valid, upd, 0.0)
    return -r


def push_pair(store, s_hist, y_hist, rho, s, y, sy):
    """The history with the pair ``(s, y)`` (``sy = s . y``) in slot 0 and
    every older pair one slot further, the oldest dropped, where ``store``
    holds; unchanged elsewhere. One select over the history, no indexed
    write: the layout ``two_loop_direction`` reads."""
    def pushed(hist, new):
        return jnp.where(store, jnp.concatenate([new[None], hist[:-1]]), hist)

    return (pushed(s_hist, s), pushed(y_hist, y),
            pushed(rho, 1.0 / jnp.where(sy != 0, sy, 1.0)))


def minimize(
    value_and_grad,
    x0: Array,
    *args,
    config: SolverConfig = SolverConfig(),
    init_fg=None,
) -> SolverResult:
    """Minimize ``value_and_grad(x, *args) -> (f, g)`` from ``x0``.

    ``init_fg``, when given, is ``(f0, g0)`` already evaluated at the
    PROJECTED start point — the caller saves the solver's first full
    evaluation (the hierarchical round body computes F_k(c) anyway for
    the safeguard; optim/hier.py). Only valid when the caller guarantees
    the pair really is ``value_and_grad(project_box(x0), *args)``; with
    box constraints the projection may move x0, so callers without
    box bounds are the intended users.
    """
    m = config.num_corrections
    d = x0.shape[0]
    dtype = x0.dtype
    has_box = config.lower_bounds is not None or config.upper_bounds is not None

    with jax.named_scope("optim/lbfgs/init"):
        x0 = project_box(x0, config)
        if init_fg is None:
            f0, g0 = value_and_grad(x0, *args)
        else:
            f0, g0 = init_fg
        tols = absolute_tolerances(f0, g0, config.tolerance)

    def cond(c: _Carry):
        return ((c.reason == ConvergenceReason.NOT_CONVERGED)
                & (c.failure == FailureMode.NONE))

    def body(c: _Carry) -> _Carry:
        with jax.named_scope("optim/lbfgs/direction"):
            direction = two_loop_direction(c.g, c.s_hist, c.y_hist, c.rho,
                                           c.n_pairs, m)
            # safeguard: fall back to steepest descent on non-descent directions
            descent = jnp.dot(direction, c.g) < 0
            direction = jnp.where(descent, direction, -c.g)

            gnorm = jnp.linalg.norm(c.g)
            first = c.n_pairs == 0
            init_step = jnp.where(first, jnp.minimum(1.0, 1.0 / jnp.maximum(gnorm, 1e-12)), 1.0)

        with jax.named_scope("optim/lbfgs/linesearch"):
            ls = wolfe_linesearch(
                value_and_grad, c.x, direction, c.f, c.g, *args,
                initial_step=init_step.astype(dtype),
                max_evals=config.linesearch_max_iterations,
            )

            x_new = c.x + ls.step * direction
            f_new, g_new = ls.f, ls.g
            if has_box:
                # Project and re-evaluate at the projected point (reference
                # projects coefficients into the box after each step).
                x_proj = project_box(x_new, config)
                changed = jnp.any(x_proj != x_new)
                f_proj, g_proj = value_and_grad(x_proj, *args)
                x_new = x_proj
                f_new = jnp.where(changed, f_proj, f_new)
                g_new = jnp.where(changed, g_proj[...], g_new)

        with jax.named_scope("optim/lbfgs/update"):
            # Non-finite guard: a NaN f fails `<` on its own, but a -inf loss
            # would sail through, and a finite f with a NaN gradient would
            # poison the curvature history — gate acceptance on full
            # finiteness. Rejection leaves the carry at the last finite
            # iterate; the failure code below terminates after the retry
            # (same direction, ls shrinks) also comes back non-finite.
            g_finite = jnp.all(jnp.isfinite(g_new))
            finite = jnp.isfinite(f_new) & g_finite
            decreased = finite & (f_new < c.f)
            # reject non-decreasing steps entirely
            x_new = jnp.where(decreased, x_new, c.x)
            f_kept = jnp.where(decreased, f_new, c.f)
            g_kept = jnp.where(decreased, g_new, c.g)

            # curvature update
            s = x_new - c.x
            yv = g_kept - c.g
            sy = jnp.dot(s, yv)
            store = decreased & (sy > 1e-10 * jnp.maximum(jnp.dot(yv, yv), 1e-30))
            s_hist, y_hist, rho = push_pair(store, c.s_hist, c.y_hist, c.rho,
                                            s, yv, sy)
            n_pairs = jnp.where(store, jnp.minimum(c.n_pairs + 1, m), c.n_pairs)
            trk = None if c.trk is None else c.trk.record(
                c.it, f_kept, g_kept,
                step=jnp.where(decreased, ls.step, 0.0))

        with jax.named_scope("optim/lbfgs/converged"):
            it = c.it + 1
            reason = convergence_reason(it, c.f, f_kept, g_kept, tols,
                                        config.max_iterations, improved=decreased)
            # two consecutive failed line searches -> objective not improving
            both_failed = (~decreased) & c.ls_failed
            reason = jnp.where(
                (reason == ConvergenceReason.NOT_CONVERGED) & both_failed,
                jnp.asarray(ConvergenceReason.OBJECTIVE_NOT_IMPROVING, jnp.int32),
                reason,
            )
            # two consecutive non-finite evaluations: the NaN-aware line
            # search already shrank away once and the region is still bad —
            # terminate with a typed failure at the last finite iterate
            nf_count = jnp.where(finite, 0, c.nf_count + 1).astype(jnp.int32)
            failure = jnp.where(nf_count >= 2, nonfinite_code(f_new, g_finite),
                                jnp.asarray(FailureMode.NONE, jnp.int32))
            reason = jnp.where(
                failure != FailureMode.NONE,
                jnp.asarray(ConvergenceReason.OBJECTIVE_NOT_IMPROVING, jnp.int32),
                reason,
            )

        return _Carry(
            x=x_new, f=f_kept, g=g_kept, f_prev=c.f,
            s_hist=s_hist, y_hist=y_hist, rho=rho,
            n_pairs=n_pairs,
            it=it, reason=reason,
            n_evals=c.n_evals + ls.num_evals + (1 if has_box else 0),
            ls_failed=~decreased,
            nf_count=nf_count, failure=failure, trk=trk,
        )

    with jax.named_scope("optim/lbfgs/init"):
        init = _Carry(
            x=x0, f=f0, g=g0, f_prev=f0 + jnp.asarray(jnp.inf, dtype),
            s_hist=jnp.zeros((m, d), dtype), y_hist=jnp.zeros((m, d), dtype),
            rho=jnp.zeros((m,), dtype),
            n_pairs=jnp.asarray(0, jnp.int32),
            it=jnp.asarray(0, jnp.int32),
            # handle an already-converged start (zero gradient)
            reason=jnp.where(
                jnp.linalg.norm(g0) <= tols.gradient_tol,
                jnp.asarray(ConvergenceReason.GRADIENT_CONVERGED, jnp.int32),
                jnp.asarray(ConvergenceReason.NOT_CONVERGED, jnp.int32),
            ),
            n_evals=jnp.asarray(1, jnp.int32),
            ls_failed=jnp.asarray(False),
            nf_count=jnp.asarray(0, jnp.int32),
            # a non-finite start (poisoned data) exits before the first step
            failure=nonfinite_code(f0, jnp.all(jnp.isfinite(g0))),
            trk=StateTracking.init(config.track_states, dtype),
        )

    with jax.named_scope("optim/lbfgs/loop"):
        out = lax.while_loop(cond, body, init)
    return SolverResult(
        coef=out.x, value=out.f, gradient=out.g,
        iterations=out.it, reason=out.reason, num_fun_evals=out.n_evals,
        loss_history=None if out.trk is None else out.trk.loss,
        gnorm_history=None if out.trk is None else out.trk.gnorm,
        step_history=None if out.trk is None else out.trk.step,
        failure=out.failure,
    )


class _DirCarry(NamedTuple):
    x: Array
    f: Array
    g: Array
    f_prev: Array
    margins: Array     # [n] resident margins at x (affinely updated)
    xx: Array          # x . x (L2 term's quadratic, refreshed each accept)
    s_hist: Array      # [m, d]
    y_hist: Array      # [m, d]
    rho: Array         # [m]
    sy_gram: Array     # [m, m]: sy_gram[i, j] = s_i . y_j
    yy_gram: Array     # [m, m]: yy_gram[i, j] = y_i . y_j
    sg: Array          # [m]: s_i . g
    yg: Array          # [m]: y_i . g
    gg: Array          # g . g
    n_pairs: Array
    head: Array
    it: Array
    reason: Array
    n_evals: Array
    ls_failed: Array
    failure: Array     # int32 FailureMode (non-zero terminates the loop)
    trk: Optional[StateTracking]


def _compact_direction(sg, yg, gg, sy_gram, yy_gram, rho, n_pairs, head, m):
    """Two-loop recursion in the span of {g} ∪ S ∪ Y by Gram algebra alone
    (the VL-BFGS observation, arXiv:1409.2442): because the backward loop
    only ever subtracts Y components from q, every inner product it needs
    is an entry of S·Yᵀ, Y·Yᵀ, S·g or Y·g — O(m²) scalar work instead of
    4m passes over d-vectors. Returns coefficients ``(c_g, c_s, c_y)`` with

        direction = -(c_g * g + c_s @ S + c_y @ Y)

    so the caller materializes the direction with ONE [m, d] combination.
    This path keeps a ring buffer (``head`` is the next write slot: it is
    never vmapped, and at d = 10^7 a store must stay one row's write), so
    step ``j`` visits slot ``(head - 1 - j) % m``; steps past ``n_pairs``
    are masked as in ``two_loop_direction``: their alphas/r_s entries stay
    zero, so garbage Gram entries at dead slots never contribute."""
    dtype = sg.dtype

    def bwd(j, alphas):
        idx = (head - 1 - j) % m
        valid = j < n_pairs
        # s_idx . q where q = g - alphas @ Y
        a = rho[idx] * (sg[idx] - jnp.dot(sy_gram[idx], alphas))
        return alphas.at[idx].set(jnp.where(valid, a, 0.0))

    alphas = lax.fori_loop(0, m, bwd, jnp.zeros((m,), dtype))

    last = (head - 1) % m
    sy = sy_gram[last, last]
    yy = yy_gram[last, last]
    gamma = jnp.where((n_pairs > 0) & (yy > 0),
                      sy / jnp.where(yy > 0, yy, 1.0), 1.0)
    # r = gamma * q = gamma * g - gamma * alphas @ Y
    r_y = -gamma * alphas

    def fwd(j, r_s):
        idx = (head - n_pairs + j) % m
        valid = j < n_pairs
        yr = (gamma * yg[idx] + jnp.dot(r_s, sy_gram[:, idx])
              + jnp.dot(r_y, yy_gram[:, idx]))
        beta = rho[idx] * yr
        return r_s.at[idx].add(jnp.where(valid, alphas[idx] - beta, 0.0))

    r_s = lax.fori_loop(0, m, fwd, jnp.zeros((m,), dtype))
    return gamma, r_s, r_y


def minimize_directional(
    problem,
    x0: Array,
    *,
    config: SolverConfig = SolverConfig(),
) -> SolverResult:
    """L-BFGS over a margin-resident ``DirectionalProblem``
    (function/objective.directional_problem).

    Built for the model-sharded sparse path, where every pass over the
    feature nnz is the wallclock. Per iteration exactly TWO such passes
    happen: one matvec for the direction's margin increment and one
    rmatvec for the gradient at the accepted point — every line-search
    trial is O(n_samples) on resident margins, and the search direction
    itself comes from ``_compact_direction``'s O(m²) Gram algebra plus a
    single [m, d] combination (the classic two-loop re-reads the whole
    history twice per iteration).

    Semantics mirror ``minimize``: same init-step rule, non-decreasing
    steps rejected, same curvature-pair store condition, same convergence
    classification. ``num_fun_evals`` counts FULL-data evaluations only
    (1 at init + 1 per iteration at the accepted point); the O(n) trial
    probes are excluded, keeping the count comparable to the classic
    path's value_and_grad calls.

    Box constraints are unsupported — projection would break margin
    residency; use ``minimize``.
    """
    if config.lower_bounds is not None or config.upper_bounds is not None:
        raise ValueError("minimize_directional does not support box "
                         "constraints; use minimize")
    m = config.num_corrections
    d = x0.shape[0]
    dtype = x0.dtype

    with jax.named_scope("optim/lbfgs/init"):
        f0, g0, margins0, xx0 = problem.init(x0)
        tols = absolute_tolerances(f0, g0, config.tolerance)

    def cond(c: _DirCarry):
        return ((c.reason == ConvergenceReason.NOT_CONVERGED)
                & (c.failure == FailureMode.NONE))

    def body(c: _DirCarry) -> _DirCarry:
        with jax.named_scope("optim/lbfgs/direction"):
            c_g, c_s, c_y = _compact_direction(
                c.sg, c.yg, c.gg, c.sy_gram, c.yy_gram, c.rho,
                c.n_pairs, c.head, m)
            d0 = -(c_g * c.gg + jnp.dot(c_s, c.sg) + jnp.dot(c_y, c.yg))
            # safeguard: fall back to steepest descent on non-descent directions
            descent = d0 < 0
            c_g = jnp.where(descent, c_g, 1.0)
            c_s = jnp.where(descent, c_s, jnp.zeros_like(c_s))
            c_y = jnp.where(descent, c_y, jnp.zeros_like(c_y))
            d0 = jnp.where(descent, d0, -c.gg)

            direction = -(c_g * c.g + c_s @ c.s_hist + c_y @ c.y_hist)
            m_dir = problem.dir_margins(direction)
            xd = jnp.dot(c.x, direction)
            dd = jnp.dot(direction, direction)

            first = c.n_pairs == 0
            gnorm = jnp.sqrt(c.gg)
            init_step = jnp.where(
                first, jnp.minimum(1.0, 1.0 / jnp.maximum(gnorm, 1e-12)), 1.0)

        with jax.named_scope("optim/lbfgs/linesearch"):
            ls = wolfe_linesearch_directional(
                lambda a: problem.trial(c.margins, m_dir, c.xx, xd, dd, a),
                c.f, d0,
                initial_step=init_step.astype(dtype),
                max_evals=config.linesearch_max_iterations,
            )

        with jax.named_scope("optim/lbfgs/update"):
            decreased = ls.f < c.f
            t = jnp.where(decreased, ls.step, 0.0).astype(dtype)
            x_new = c.x + t * direction
            margins_new = c.margins + t * m_dir
            # xx advanced by the L2 quadratic that is EXACT along the ray; the
            # drift of this scalar recurrence vs a fresh dot is O(iters * eps),
            # orders below the f32 progress floor the solve stalls at — and it
            # saves one full d-pass per iteration.
            xx_kept = c.xx + t * (2.0 * xd + t * dd)

            # ONE full-data evaluation at the accepted point. When the line
            # search fails t is exactly 0, x_new/margins_new/xx are bitwise
            # c.x/c.margins/c.xx, and this recomputation reproduces f/g
            # bit-for-bit — so no where(decreased) selects are needed on them
            # (each select over [d] is a full extra pass on a 10^7-dim solve).
            f_kept, g_kept = problem.at_point(x_new, margins_new, xx_kept)

            gng = jnp.dot(c.g, g_kept)
            gg_new = jnp.dot(g_kept, g_kept)

            # Non-finite guard priced for the sharded path: isfinite on two
            # scalars already in hand (f and g.g — any NaN/Inf component of g
            # makes g.g non-finite), NO extra d-pass. A bad full-data eval
            # withdraws the step — the carry reverts to the previous finite
            # point — and the failure code terminates the loop, so the
            # where-selects below are only ever live on the final iteration.
            ok = jnp.isfinite(f_kept) & jnp.isfinite(gg_new)
            failure = jnp.where(ok, jnp.asarray(FailureMode.NONE, jnp.int32),
                                nonfinite_code(f_kept, jnp.isfinite(gg_new)))
            x_new = jnp.where(ok, x_new, c.x)
            margins_new = jnp.where(ok, margins_new, c.margins)
            xx_kept = jnp.where(ok, xx_kept, c.xx)
            f_kept = jnp.where(ok, f_kept, c.f)
            g_kept = jnp.where(ok, g_kept, c.g)
            gng = jnp.where(ok, gng, c.gg)
            gg_new = jnp.where(ok, gg_new, c.gg)
            decreased = decreased & ok

            # direction . y_j via coefficients against the old grams;
            # direction . g_new comes straight from the line search: the trial
            # restriction's dphi at the accepted step IS direction . g(x_new)
            # by the adjoint identity (dphi = m_dir . dloss + l2*(xd + a*dd)),
            # so the store decision needs NO history matvec. On a failed
            # search t = 0 zeroes sy below, so a stale dphi is harmless.
            d_dot_y = -(c_g * c.yg + c_s @ c.sy_gram + c_y @ c.yy_gram)
            d_dot_gn = ls.dphi

            # curvature pair (s, y) = (t*direction, g_new - g) without touching
            # d-space: s.y = t*(d.g_new - d.g) and y.y = |g_new|^2 - 2 g.g_new
            # + |g|^2, all scalars already in hand. The cancellation noise this
            # admits (~eps*|g|^2) only matters when the true curvature is at
            # rounding level — exactly the pairs the threshold must reject
            # anyway — and it keeps sy consistent with the sy_gram row below,
            # which is built from the same coefficient form.
            sy = t * (d_dot_gn - d0)
            yy = jnp.maximum(gg_new - 2.0 * gng + c.gg, 0.0)
            store = decreased & (sy > 1e-10 * jnp.maximum(yy, 1e-30))
            write = c.head % m

            # conditional stores at ROW granularity: a where(store) over the
            # full [m, d] history materializes two extra history-sized buffers
            # per iteration (measured ~0.9 s/iter at d = 10^7, m = 10 — more
            # than the sparse kernels themselves); selecting the one written
            # row keeps the dynamic-update-slice in place. The y subtraction
            # fuses into the row write instead of materializing a [d] vector.
            # Writes come BEFORE the history matvecs: the old buffers' last
            # use is the update itself, so XLA aliases the carry in place.
            s_hist = c.s_hist.at[write].set(jnp.where(store, t * direction,
                                                      c.s_hist[write]))
            y_hist = c.y_hist.at[write].set(jnp.where(store, g_kept - c.g,
                                                      c.y_hist[write]))
            rho = jnp.where(
                store, c.rho.at[write].set(1.0 / jnp.where(sy != 0, sy, 1.0)),
                c.rho)

            # The ONLY O(m d) Gram work: two matvecs against the NEW history.
            # At the written slot the products are s_new . g_new and
            # y_new . g_new — exactly the values the next direction needs;
            # without a store the history is unchanged and these are plain
            # recomputations. Uniform either way — no conditional fixups.
            sg = s_hist @ g_kept
            yg = y_hist @ g_kept

            # off-diagonal column s_i . y_new = s_i . g_new - s_i . g (valid
            # for i != write; the evicted slot's entries are overwritten by the
            # row set and the diagonal set, applied last)
            sy_upd = (c.sy_gram
                      .at[write, :].set(t * d_dot_y)          # s_new . y_j
                      .at[:, write].set(sg - c.sg)            # s_i . y_new
                      .at[write, write].set(sy))
            yy_col = yg - c.yg                                # y_i . y_new
            yy_upd = (c.yy_gram
                      .at[write, :].set(yy_col)
                      .at[:, write].set(yy_col)
                      .at[write, write].set(yy))
            sy_gram = jnp.where(store, sy_upd, c.sy_gram)
            yy_gram = jnp.where(store, yy_upd, c.yy_gram)

            head = jnp.where(store, (c.head + 1) % m, c.head)
            n_pairs = jnp.where(store, jnp.minimum(c.n_pairs + 1, m), c.n_pairs)
            trk = None if c.trk is None else c.trk.record(
                c.it, f_kept, g_kept, step=t)

        with jax.named_scope("optim/lbfgs/converged"):
            it = c.it + 1
            reason = convergence_reason(it, c.f, f_kept, g_kept, tols,
                                        config.max_iterations, improved=decreased,
                                        gnorm=jnp.sqrt(gg_new))
            both_failed = (~decreased) & c.ls_failed
            reason = jnp.where(
                (reason == ConvergenceReason.NOT_CONVERGED) & both_failed,
                jnp.asarray(ConvergenceReason.OBJECTIVE_NOT_IMPROVING, jnp.int32),
                reason,
            )
            reason = jnp.where(
                failure != FailureMode.NONE,
                jnp.asarray(ConvergenceReason.OBJECTIVE_NOT_IMPROVING, jnp.int32),
                reason,
            )

        return _DirCarry(
            x=x_new, f=f_kept, g=g_kept, f_prev=c.f,
            margins=margins_new, xx=xx_kept,
            s_hist=s_hist, y_hist=y_hist, rho=rho,
            sy_gram=sy_gram, yy_gram=yy_gram, sg=sg, yg=yg, gg=gg_new,
            n_pairs=n_pairs, head=head.astype(jnp.int32),
            it=it, reason=reason,
            n_evals=c.n_evals + 1,
            ls_failed=~decreased,
            failure=failure, trk=trk,
        )

    with jax.named_scope("optim/lbfgs/init"):
        gg0 = jnp.dot(g0, g0)
        init = _DirCarry(
            x=x0, f=f0, g=g0, f_prev=f0 + jnp.asarray(jnp.inf, dtype),
            margins=margins0, xx=xx0,
            s_hist=jnp.zeros((m, d), dtype), y_hist=jnp.zeros((m, d), dtype),
            rho=jnp.zeros((m,), dtype),
            sy_gram=jnp.zeros((m, m), dtype), yy_gram=jnp.zeros((m, m), dtype),
            sg=jnp.zeros((m,), dtype), yg=jnp.zeros((m,), dtype),
            gg=gg0,
            n_pairs=jnp.asarray(0, jnp.int32), head=jnp.asarray(0, jnp.int32),
            it=jnp.asarray(0, jnp.int32),
            reason=jnp.where(
                jnp.sqrt(gg0) <= tols.gradient_tol,
                jnp.asarray(ConvergenceReason.GRADIENT_CONVERGED, jnp.int32),
                jnp.asarray(ConvergenceReason.NOT_CONVERGED, jnp.int32),
            ),
            n_evals=jnp.asarray(1, jnp.int32),
            ls_failed=jnp.asarray(False),
            # same scalar-witness trick as the loop guard: g.g covers g
            failure=nonfinite_code(f0, jnp.isfinite(gg0)),
            trk=StateTracking.init(config.track_states, dtype),
        )

    with jax.named_scope("optim/lbfgs/loop"):
        out = lax.while_loop(cond, body, init)
    return SolverResult(
        coef=out.x, value=out.f, gradient=out.g,
        iterations=out.it, reason=out.reason, num_fun_evals=out.n_evals,
        loss_history=None if out.trk is None else out.trk.loss,
        gnorm_history=None if out.trk is None else out.trk.gnorm,
        step_history=None if out.trk is None else out.trk.step,
        failure=out.failure,
    )
