"""Strong-Wolfe line search as a single lax.while_loop state machine.

Replaces the line search inside Breeze's LBFGS (the reference delegates to
breeze.optimize.LBFGS — optimization/LBFGS.scala:39; there is no JVM code to
port, so this is a fresh implementation of bracket+zoom, Nocedal & Wright
alg. 3.5/3.6, with quadratic interpolation and bisection safeguards).

Written entirely with lax control flow so it jits once and vmaps over
entity blocks (the random-effect path) with per-entity masking handled by
the while_loop batching rule.

Inside whichever solver's ``optim/<solver>/linesearch`` scope it runs, the
search names its own steps ``optim/linesearch/<step>``: ``init``, ``trial``
(one evaluation of the objective along the direction: the call into
``agg/``), ``zoom`` (the bracket-and-zoom bookkeeping) and ``loop`` around
the ``while_loop`` itself (PERF.md §3; the names are an interface).
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array

_BRACKET = 0
_ZOOM = 1
_DONE = 2


class LineSearchResult(NamedTuple):
    """Accepted point of a (strong or approximate) Wolfe search.

    Residual-slack contract: near the optimum the approximate-Wolfe test
    classifies a step as converged when the decrease underflows ``f0``'s
    ulp (``|f_a - f0| <= 8 * eps * |f0|``, the Hager-Zhang flatness
    window). That slack affects CLASSIFICATION only — ``success`` may be
    True for such a step — but the returned iterate never moves uphill:
    a candidate with ``f_a > f0`` is refused as the accepted point, so
    callers may rely on ``f <= f0`` whenever ``step > 0``."""

    step: Array       # accepted step length
    f: Array          # objective at accepted point
    g: Array          # full gradient at accepted point
    num_evals: Array  # objective evaluations used
    success: Array    # bool: strong or approximate Wolfe satisfied


class _Carry(NamedTuple):
    stage: Array
    i: Array
    a_next: Array
    # zoom bracket: lo carries its full gradient (it may be accepted)
    a_lo: Array
    f_lo: Array
    d_lo: Array
    g_lo: Array
    a_hi: Array
    f_hi: Array
    d_hi: Array
    # previous bracketing point
    a_prev: Array
    f_prev: Array
    d_prev: Array
    g_prev: Array
    # accepted / best-decrease-so-far result
    a_best: Array
    f_best: Array
    g_best: Array
    success: Array


def wolfe_linesearch(
    fg: Callable[..., Tuple[Array, Array]],
    x: Array,
    direction: Array,
    f0: Array,
    g0: Array,
    *fg_args,
    initial_step: Array | float = 1.0,
    c1: float = 1e-4,
    c2: float = 0.9,
    max_evals: int = 25,
    max_step: float = 1e10,
) -> LineSearchResult:
    """Find a step satisfying the strong Wolfe conditions along ``direction``.

    Falls back to the best strict-decrease point seen (success=False) if the
    Wolfe point isn't found within ``max_evals`` — the caller decides whether
    to reset curvature history.
    """
    dtype = x.dtype
    with jax.named_scope("optim/linesearch/init"):
        d0 = jnp.dot(g0, direction)

    def phi(a):
        f, g = fg(x + a * direction, *fg_args)
        return f, g, jnp.dot(g, direction)

    def zoom_candidate(a_lo, f_lo, d_lo, a_hi, f_hi):
        """Quadratic interpolation with bisection safeguard."""
        h = a_hi - a_lo
        denom = 2.0 * (f_hi - f_lo - d_lo * h)
        a_q = a_lo - d_lo * h * h / denom
        mid = a_lo + 0.5 * h
        lo, hi = jnp.minimum(a_lo, a_hi), jnp.maximum(a_lo, a_hi)
        pad = 0.1 * (hi - lo)
        bad = (~jnp.isfinite(a_q)) | (a_q <= lo + pad) | (a_q >= hi - pad)
        return jnp.where(bad, mid, a_q)

    def body(c: _Carry) -> _Carry:
        with jax.named_scope("optim/linesearch/trial"):
            f_a, g_a, d_a = phi(c.a_next)
        with jax.named_scope("optim/linesearch/zoom"):
            i = c.i + 1
            a = c.a_next

            # best strict-decrease tracker (failure fallback); a -inf "best"
            # would poison the caller's carry, so non-finite trials never win
            better = (f_a < c.f_best) & jnp.isfinite(f_a)
            a_best = jnp.where(better, a, c.a_best)
            f_best = jnp.where(better, f_a, c.f_best)
            g_best = jnp.where(better, g_a, c.g_best)

            # a non-finite trial classifies as an Armijo failure: the bracket
            # shrinks back toward the finite region instead of growing into it
            armijo_fail = (f_a > f0 + c1 * a * d0) | ~jnp.isfinite(f_a)
            wolfe_ok = jnp.abs(d_a) <= -c2 * d0
            # approximate-Wolfe acceptance (Hager-Zhang style): near the
            # optimum the true decrease underflows f0's ulp, strict Armijo
            # reads it as failure, and the zoom stage burns the whole eval
            # budget shrinking a bracket around machine noise (measured: 55
            # evals for a 6-iteration f32 Poisson solve). When f is flat to
            # within rounding AND the directional derivative satisfies the
            # two-sided slope test, the step is as converged as the dtype
            # can express — accept it.
            slack = 8.0 * jnp.finfo(dtype).eps * jnp.abs(f0)
            approx_conv = ((f_a <= f0 + slack)
                           & (d_a >= c2 * d0)
                           & (d_a <= (2.0 * c1 - 1.0) * d0)
                           & jnp.isfinite(f_a))
            # the slack is a CLASSIFICATION device only: a candidate inside the
            # flatness window but with f_a > f0 is a rounding-level ascent —
            # report converged (success) without moving the iterate off the
            # best point seen (see the LineSearchResult contract)
            approx_take = approx_conv & (f_a <= f0)
            approx_stop = approx_conv & ~approx_take

            in_bracket = c.stage == _BRACKET
            # --- bracket-stage classification ---
            br_to_zoom1 = armijo_fail | ((i > 1) & (f_a >= c.f_prev))
            br_accept = (~br_to_zoom1) & wolfe_ok
            br_to_zoom2 = (~br_to_zoom1) & (~wolfe_ok) & (d_a >= 0)
            br_grow = (~br_to_zoom1) & (~br_accept) & (~br_to_zoom2)

            # --- zoom-stage classification ---
            zm_shrink_hi = armijo_fail | (f_a >= c.f_lo)
            zm_accept = (~zm_shrink_hi) & wolfe_ok
            zm_flip = (~zm_shrink_hi) & (~wolfe_ok) & (d_a * (c.a_hi - c.a_lo) >= 0)

            accept = jnp.where(in_bracket, br_accept, zm_accept) | approx_take

            # new bracket for the zoom stage
            z1 = br_to_zoom1
            new_a_lo = jnp.where(
                in_bracket,
                jnp.where(z1, c.a_prev, a),
                jnp.where(zm_shrink_hi, c.a_lo, a),
            )
            new_f_lo = jnp.where(
                in_bracket,
                jnp.where(z1, c.f_prev, f_a),
                jnp.where(zm_shrink_hi, c.f_lo, f_a),
            )
            new_d_lo = jnp.where(
                in_bracket,
                jnp.where(z1, c.d_prev, d_a),
                jnp.where(zm_shrink_hi, c.d_lo, d_a),
            )
            new_g_lo = jnp.where(
                in_bracket,
                jnp.where(z1, c.g_prev, g_a),
                jnp.where(zm_shrink_hi, c.g_lo, g_a),
            )
            new_a_hi = jnp.where(
                in_bracket,
                jnp.where(z1, a, c.a_prev),
                jnp.where(zm_shrink_hi, a, jnp.where(zm_flip, c.a_lo, c.a_hi)),
            )
            new_f_hi = jnp.where(
                in_bracket,
                jnp.where(z1, f_a, c.f_prev),
                jnp.where(zm_shrink_hi, f_a, jnp.where(zm_flip, c.f_lo, c.f_hi)),
            )
            new_d_hi = jnp.where(
                in_bracket,
                jnp.where(z1, d_a, c.d_prev),
                jnp.where(zm_shrink_hi, d_a, jnp.where(zm_flip, c.d_lo, c.d_hi)),
            )

            # next stage
            entering_zoom = in_bracket & (br_to_zoom1 | br_to_zoom2)
            staying_zoom = (~in_bracket)
            interval = jnp.abs(new_a_hi - new_a_lo)
            interval_dead = (entering_zoom | staying_zoom) & (
                interval <= 1e-10 * jnp.maximum(jnp.abs(new_a_hi), 1.0)
            )
            # accept lo when the zoom interval collapses (best we have there)
            collapse_accept = interval_dead & ~accept

            stage = jnp.where(
                accept | collapse_accept | approx_stop | (i >= max_evals),
                _DONE,
                jnp.where(in_bracket & br_grow, _BRACKET, _ZOOM),
            ).astype(jnp.int32)

            # next candidate step
            grow_a = jnp.minimum(2.0 * a, max_step)
            zoom_a = zoom_candidate(new_a_lo, new_f_lo, new_d_lo, new_a_hi, new_f_hi)
            a_next = jnp.where(in_bracket & br_grow, grow_a, zoom_a)

            # accepted result
            acc_a = jnp.where(accept, a, new_a_lo)
            acc_f = jnp.where(accept, f_a, new_f_lo)
            acc_g = jnp.where(accept, g_a, new_g_lo)
            take = accept | collapse_accept
            a_best = jnp.where(take, acc_a, a_best)
            f_best = jnp.where(take, acc_f, f_best)
            g_best = jnp.where(take, acc_g, g_best)
            success = c.success | accept | approx_stop

        return _Carry(
            stage=stage, i=i, a_next=a_next,
            a_lo=new_a_lo, f_lo=new_f_lo, d_lo=new_d_lo, g_lo=new_g_lo,
            a_hi=new_a_hi, f_hi=new_f_hi, d_hi=new_d_hi,
            a_prev=a, f_prev=f_a, d_prev=d_a, g_prev=g_a,
            a_best=a_best, f_best=f_best, g_best=g_best, success=success,
        )

    with jax.named_scope("optim/linesearch/init"):
        zero = jnp.zeros((), dtype)
        init = _Carry(
            stage=jnp.asarray(_BRACKET, jnp.int32),
            i=jnp.asarray(0, jnp.int32),
            a_next=jnp.asarray(initial_step, dtype),
            a_lo=zero, f_lo=f0, d_lo=d0, g_lo=g0,
            a_hi=zero, f_hi=f0, d_hi=d0,
            a_prev=zero, f_prev=f0, d_prev=d0, g_prev=g0,
            a_best=zero, f_best=f0, g_best=g0,
            success=jnp.asarray(False),
        )

    with jax.named_scope("optim/linesearch/loop"):
        out = lax.while_loop(lambda c: c.stage != _DONE, body, init)
    return LineSearchResult(
        step=out.a_best, f=out.f_best, g=out.g_best,
        num_evals=out.i, success=out.success,
    )


class DirectionalLineSearchResult(NamedTuple):
    """Accepted point of a 1-D (margin-resident) Wolfe search. Same
    residual-slack contract as ``LineSearchResult``: classification may use
    the flatness window, the iterate never moves uphill (``f <= f0``
    whenever ``step > 0``)."""

    step: Array       # accepted step length
    f: Array          # phi(step)
    dphi: Array       # phi'(step) — the directional derivative at the
                      # accepted point; lets the caller reuse it as
                      # direction . g_new without re-deriving it from
                      # history inner products
    num_evals: Array  # phi evaluations used
    success: Array    # bool: strong or approximate Wolfe satisfied


class _DirCarry(NamedTuple):
    stage: Array
    i: Array
    a_next: Array
    a_lo: Array
    f_lo: Array
    d_lo: Array
    a_hi: Array
    f_hi: Array
    d_hi: Array
    a_prev: Array
    f_prev: Array
    d_prev: Array
    a_best: Array
    f_best: Array
    d_best: Array
    success: Array


def wolfe_linesearch_directional(
    phi: Callable[[Array], Tuple[Array, Array]],
    f0: Array,
    d0: Array,
    *,
    initial_step: Array | float = 1.0,
    c1: float = 1e-4,
    c2: float = 0.9,
    max_evals: int = 25,
    max_step: float = 1e10,
) -> DirectionalLineSearchResult:
    """``wolfe_linesearch`` over a scalar restriction ``phi(a) -> (f, dphi)``.

    Same bracket+zoom machine as ``wolfe_linesearch`` but with no gradient
    vectors in the carry: the caller holds margins resident and evaluates
    trial points in O(n_samples) (GLM: loss at ``margins + a * dir_margins``
    plus the L2 quadratic in precomputed dot products), so a whole search
    costs less than ONE classic evaluation's pass over the feature nnz.
    The full gradient is recovered by the caller only at the accepted point.
    """
    f0 = jnp.asarray(f0)
    dtype = f0.dtype

    def zoom_candidate(a_lo, f_lo, d_lo, a_hi, f_hi):
        h = a_hi - a_lo
        denom = 2.0 * (f_hi - f_lo - d_lo * h)
        a_q = a_lo - d_lo * h * h / denom
        mid = a_lo + 0.5 * h
        lo, hi = jnp.minimum(a_lo, a_hi), jnp.maximum(a_lo, a_hi)
        pad = 0.1 * (hi - lo)
        bad = (~jnp.isfinite(a_q)) | (a_q <= lo + pad) | (a_q >= hi - pad)
        return jnp.where(bad, mid, a_q)

    def body(c: _DirCarry) -> _DirCarry:
        with jax.named_scope("optim/linesearch/trial"):
            f_a, d_a = phi(c.a_next)
        with jax.named_scope("optim/linesearch/zoom"):
            i = c.i + 1
            a = c.a_next

            # same non-finite handling as wolfe_linesearch: bad trials never
            # become the fallback best, and they shrink the bracket
            better = (f_a < c.f_best) & jnp.isfinite(f_a)
            a_best = jnp.where(better, a, c.a_best)
            f_best = jnp.where(better, f_a, c.f_best)
            d_best = jnp.where(better, d_a, c.d_best)

            armijo_fail = (f_a > f0 + c1 * a * d0) | ~jnp.isfinite(f_a)
            wolfe_ok = jnp.abs(d_a) <= -c2 * d0
            slack = 8.0 * jnp.finfo(dtype).eps * jnp.abs(f0)
            approx_conv = ((f_a <= f0 + slack)
                           & (d_a >= c2 * d0)
                           & (d_a <= (2.0 * c1 - 1.0) * d0)
                           & jnp.isfinite(f_a))
            approx_take = approx_conv & (f_a <= f0)
            approx_stop = approx_conv & ~approx_take

            in_bracket = c.stage == _BRACKET
            br_to_zoom1 = armijo_fail | ((i > 1) & (f_a >= c.f_prev))
            br_accept = (~br_to_zoom1) & wolfe_ok
            br_to_zoom2 = (~br_to_zoom1) & (~wolfe_ok) & (d_a >= 0)
            br_grow = (~br_to_zoom1) & (~br_accept) & (~br_to_zoom2)

            zm_shrink_hi = armijo_fail | (f_a >= c.f_lo)
            zm_accept = (~zm_shrink_hi) & wolfe_ok
            zm_flip = (~zm_shrink_hi) & (~wolfe_ok) & (d_a * (c.a_hi - c.a_lo) >= 0)

            accept = jnp.where(in_bracket, br_accept, zm_accept) | approx_take

            z1 = br_to_zoom1
            new_a_lo = jnp.where(
                in_bracket,
                jnp.where(z1, c.a_prev, a),
                jnp.where(zm_shrink_hi, c.a_lo, a),
            )
            new_f_lo = jnp.where(
                in_bracket,
                jnp.where(z1, c.f_prev, f_a),
                jnp.where(zm_shrink_hi, c.f_lo, f_a),
            )
            new_d_lo = jnp.where(
                in_bracket,
                jnp.where(z1, c.d_prev, d_a),
                jnp.where(zm_shrink_hi, c.d_lo, d_a),
            )
            new_a_hi = jnp.where(
                in_bracket,
                jnp.where(z1, a, c.a_prev),
                jnp.where(zm_shrink_hi, a, jnp.where(zm_flip, c.a_lo, c.a_hi)),
            )
            new_f_hi = jnp.where(
                in_bracket,
                jnp.where(z1, f_a, c.f_prev),
                jnp.where(zm_shrink_hi, f_a, jnp.where(zm_flip, c.f_lo, c.f_hi)),
            )
            new_d_hi = jnp.where(
                in_bracket,
                jnp.where(z1, d_a, c.d_prev),
                jnp.where(zm_shrink_hi, d_a, jnp.where(zm_flip, c.d_lo, c.d_hi)),
            )

            entering_zoom = in_bracket & (br_to_zoom1 | br_to_zoom2)
            staying_zoom = (~in_bracket)
            interval = jnp.abs(new_a_hi - new_a_lo)
            interval_dead = (entering_zoom | staying_zoom) & (
                interval <= 1e-10 * jnp.maximum(jnp.abs(new_a_hi), 1.0)
            )
            collapse_accept = interval_dead & ~accept

            stage = jnp.where(
                accept | collapse_accept | approx_stop | (i >= max_evals),
                _DONE,
                jnp.where(in_bracket & br_grow, _BRACKET, _ZOOM),
            ).astype(jnp.int32)

            grow_a = jnp.minimum(2.0 * a, max_step)
            zoom_a = zoom_candidate(new_a_lo, new_f_lo, new_d_lo, new_a_hi, new_f_hi)
            a_next = jnp.where(in_bracket & br_grow, grow_a, zoom_a)

            acc_a = jnp.where(accept, a, new_a_lo)
            acc_f = jnp.where(accept, f_a, new_f_lo)
            acc_d = jnp.where(accept, d_a, new_d_lo)
            take = accept | collapse_accept
            a_best = jnp.where(take, acc_a, a_best)
            f_best = jnp.where(take, acc_f, f_best)
            d_best = jnp.where(take, acc_d, d_best)
            success = c.success | accept | approx_stop

        return _DirCarry(
            stage=stage, i=i, a_next=a_next,
            a_lo=new_a_lo, f_lo=new_f_lo, d_lo=new_d_lo,
            a_hi=new_a_hi, f_hi=new_f_hi, d_hi=new_d_hi,
            a_prev=a, f_prev=f_a, d_prev=d_a,
            a_best=a_best, f_best=f_best, d_best=d_best, success=success,
        )

    with jax.named_scope("optim/linesearch/init"):
        zero = jnp.zeros((), dtype)
        init = _DirCarry(
            stage=jnp.asarray(_BRACKET, jnp.int32),
            i=jnp.asarray(0, jnp.int32),
            a_next=jnp.asarray(initial_step, dtype),
            a_lo=zero, f_lo=f0, d_lo=d0,
            a_hi=zero, f_hi=f0, d_hi=d0,
            a_prev=zero, f_prev=f0, d_prev=d0,
            a_best=zero, f_best=f0, d_best=d0,
            success=jnp.asarray(False),
        )

    with jax.named_scope("optim/linesearch/loop"):
        out = lax.while_loop(lambda c: c.stage != _DONE, body, init)
    return DirectionalLineSearchResult(
        step=out.a_best, f=out.f_best, dphi=out.d_best,
        num_evals=out.i, success=out.success,
    )
