"""Hierarchical local-subproblem solver (Snap ML, arXiv 1803.06333).

Communication-avoiding distributed GLM training (arXiv 1811.01564) on
the existing two-level mesh: each device runs H inner second-order
L-BFGS steps against its LOCAL data shard with the global model frozen,
then ONE staged ICI-then-DCN ``psum`` per round aggregates the local
deltas into a globally-consistent averaged update. DCN reductions drop
from per-L-BFGS-evaluation (the reference data-parallel solve) to
per-round — the round's single collective is the entire cross-slice
traffic, regardless of how many inner iterations ran.

Local subproblem (gradient-corrected, DANE-style — Shamir et al.'s
communication-efficient distributed optimization, the same family as
arXiv 1811.01564): shard k minimizes

    F~_k(theta) = F_k(theta) + v_k . theta
                  + (mu/2) * ||theta - c||^2
    F_k(theta)  = sum_{i in shard k} w_i * loss_i(theta)
                  + (lambda / P) * 0.5 * ||theta||^2
    v_k         = grad F(c_prev) / P  -  grad F_k(c_prev)

(``GLMObjective.local_value_and_gradient`` supplies F_k; ``sum_k F_k ==
F`` exactly). The linear correction ``v_k`` cancels each shard's
gradient heterogeneity at the anchor: every local problem then has the
SAME (1/P-scaled) global gradient there, so the fixed points of the
round iteration are exactly the stationary points of F — naive
parameter averaging instead stalls at the one-shot-averaging bias
floor. The global gradient the correction needs is one round stale and
rides the SAME packed psum (``concat([delta_k, g_k, f_k])``), so each
round still issues exactly one DCN-stage reduction, and the global
objective value at every candidate comes along for free.

Safeguard (host-side, between rounds — the round boundary is therefore
a bitwise-reproducible checkpoint exactly like parallel CD's group
boundaries): a candidate is accepted only if the global loss decreased;
otherwise the round's deltas are discarded and ONE reference global
L-BFGS step is taken from the best-known iterate — a typed
``hier_fallback`` event plus counters, never an exception.

Scope: data-parallel (replicated theta) dense or ELL-sparse batches
sharded over ``(dcn?, data)``. ``ModelShardedSparse`` is refused by
construction — its margins need model-axis psums before the pointwise
dz, so a round's inner iterations could never be collective-free.

This module is scanned by ``scripts/check_no_host_sync.py``: host reads
of round scalars spell ``np.asarray`` and only happen at the round
boundary.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from photon_tpu.data.dataset import DataBatch
from photon_tpu.function.objective import GLMObjective, Hyper
from photon_tpu.obs.metrics import registry as _metrics
from photon_tpu.ops import features as F
from photon_tpu.ops import pallas_glm
from photon_tpu.optim import lbfgs
from photon_tpu.optim.base import SolverConfig
from photon_tpu.parallel import mesh as M
from photon_tpu.resilience.failures import record_failure

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class HierConfig:
    """Round structure of the hierarchical solve.

    ``local_iterations`` is H — the inner L-BFGS budget each shard
    spends per round against its frozen corrected local subproblem.
    ``prox`` seeds the damping weight mu of the proximity term anchoring
    the local solve to the incoming candidate (0 = undamped); the host
    loop adapts mu trust-region style — grown on safeguard trips, decayed
    on accepted rounds — as a TRACED round input, so adaptation never
    recompiles. ``tolerance`` stops the outer loop on the relative
    global-loss change between accepted rounds (and on a matching
    gradient norm).
    """

    rounds: int = 30
    local_iterations: int = 8
    prox: float = 0.0
    tolerance: float = 1e-8
    num_corrections: int = 10
    linesearch_max_iterations: int = 25
    # >1: each round's LOCAL solve reads only a 1/inner_chunks slice of
    # the shard's rows (round-robin over rounds; data term scaled by
    # inner_chunks to stay an unbiased estimate of the shard objective),
    # so one round streams a fraction of the local data through compute —
    # the mini-batch inner-step mode for out-of-core shards. The
    # correction anchor v, the packed psum (f, g) and the safeguard all
    # still use the FULL shard, so acceptance decisions are exact and the
    # communication structure (one staged DCN psum per round) is
    # unchanged.
    inner_chunks: int = 1


class HierResult(NamedTuple):
    coef: Array                  # best iterate (replicated)
    value: float                 # global objective at coef
    rounds: int                  # rounds executed
    accepted: int                # rounds whose candidate improved F
    fallbacks: int               # safeguard trips (reference steps taken)
    dcn_reductions: int          # DCN-stage reductions this solve issued
    history: Tuple[float, ...]   # global F at each evaluated candidate
    converged: bool


def _sample_axes(mesh) -> Tuple[str, ...]:
    if M.DCN_AXIS in mesh.axis_names:
        return (M.DCN_AXIS, M.DATA_AXIS)
    return (M.DATA_AXIS,)


def _check_features(batch: DataBatch) -> None:
    if isinstance(batch.features, F.ModelShardedSparse):
        raise ValueError(
            "hierarchical solver needs data-parallel (replicated-theta) "
            "batches; ModelShardedSparse margins require model-axis psums "
            "inside every evaluation, so collective-free local rounds are "
            "impossible by construction — use minimize_directional on the "
            "model-sharded path instead")


def _batch_specs(batch: DataBatch, sample_axes: Tuple[str, ...]):
    spec_axis = sample_axes if len(sample_axes) > 1 else sample_axes[0]
    return jax.tree.map(
        lambda a: P(spec_axis, *([None] * (a.ndim - 1))), batch)


def _staged_all_psum(x, mesh):
    """Sum ``x`` over the mesh's sample axes — the only axes a
    data-parallel shard-local value varies on; devices along any other
    axis already hold identical copies, and a psum over an axis the
    operand does not vary on is a type error under shard_map. The DCN
    hop is staged last so it is exactly one countable psum over
    ``DCN_AXIS``."""
    if M.DCN_AXIS in mesh.axis_names:
        return M.staged_psum(x)
    return jax.lax.psum(x, M.DATA_AXIS)


def _num_shards(mesh, sample_axes) -> int:
    """Number of data shards: the product of the sample-axis sizes."""
    p_shards = 1
    for a in sample_axes:
        p_shards *= M.axis_size(mesh, a)
    return p_shards


def build_round_fn(objective: GLMObjective, mesh,
                   config: HierConfig = HierConfig()):
    """The per-round SPMD program: ``round_fn(c, c_prev, g_prev, mu,
    hyper, batch) -> (avg_delta, g_global, f_global)`` where ``f_global
    = F(c)``, ``g_global = grad F(c)`` (the NEXT round's stale
    correction anchor), and ``avg_delta`` is the shard-averaged
    corrected local L-BFGS displacement. ``(c_prev, g_prev)`` anchor
    this round's gradient correction — the previous candidate and the
    global gradient there, both delivered by the previous round's psum.
    ``mu`` is the traced proximal damping weight.

    Exposed separately so tests and the bench can pin the communication
    structure statically: ``mesh.count_axis_psums(round_fn, DCN_AXIS,
    ...) == 1`` no matter how large ``local_iterations`` is.

    With ``config.inner_chunks > 1`` the returned function takes a
    LEADING traced ``chunk_idx`` argument selecting which local slice the
    round's inner solve reads (``round_fn(chunk_idx, c, c_prev, g_prev,
    mu, hyper, batch)``); the default keeps the classic arity.
    """
    sample_axes = _sample_axes(mesh)
    p_shards = _num_shards(mesh, sample_axes)
    inner = int(config.inner_chunks)
    if inner < 1:
        raise ValueError(f"inner_chunks must be >= 1, got {inner}")
    local_cfg = SolverConfig(
        max_iterations=config.local_iterations,
        tolerance=config.tolerance,
        num_corrections=config.num_corrections,
        linesearch_max_iterations=config.linesearch_max_iterations)

    def round_body(chunk_idx, c, c_prev, g_prev, mu, hyper, batch):
        d = c.shape[0]
        f0_raw, g0_raw = objective.local_value_and_gradient(
            c, batch, hyper, p_shards)
        # stale DANE correction anchored at the previous candidate:
        # v cancels this shard's gradient heterogeneity at c_prev
        _, gk_prev = objective.local_value_and_gradient(
            c_prev, batch, hyper, p_shards)
        v = g_prev / p_shards - gk_prev

        if inner > 1:
            n_local = batch.labels.shape[0]
            if n_local % inner != 0:
                raise ValueError(
                    f"inner_chunks={inner} must divide the per-shard row "
                    f"count {n_local} (shard_batch pads to the shard "
                    f"grid, not the chunk grid)")
            cl = n_local // inner
            sub = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(
                    a, chunk_idx * cl, cl, axis=0), batch)

            def local_vg(ci):
                # 1/inner of the rows at inner x weight: same expectation
                # as the full-shard term, with L2 still at l2/p_shards
                f, g = objective.local_value_and_gradient(
                    ci, sub, hyper, p_shards * inner)
                dc = ci - c
                f = inner * f + jnp.dot(v, ci) + 0.5 * mu * jnp.dot(dc, dc)
                g = inner * g + v + mu * dc
                return f, g

            # no init_fg: the chunk objective at the anchor is NOT the
            # full-shard f0_raw — let the solver evaluate its own start
            res = lbfgs.minimize(local_vg, c, config=local_cfg)
        else:
            def local_vg(ci):
                f, g = objective.local_value_and_gradient(
                    ci, batch, hyper, p_shards)
                dc = ci - c
                f = f + jnp.dot(v, ci) + 0.5 * mu * jnp.dot(dc, dc)
                g = g + v + mu * dc
                return f, g

            # F~_k(c) / grad F~_k(c) from the raw pair — the prox term
            # and its gradient vanish at the anchor
            res = lbfgs.minimize(
                local_vg, c, config=local_cfg,
                init_fg=(f0_raw + jnp.dot(v, c), g0_raw + v))
        delta = res.coef - c
        packed = _staged_all_psum(
            jnp.concatenate([delta, g0_raw, f0_raw[None]]), mesh)
        return packed[:d] / p_shards, packed[d:2 * d], packed[2 * d]

    def make(chunk_idx, c, c_prev, g_prev, mu, hyper, batch):
        specs = _batch_specs(batch, sample_axes)
        # check_vma=False: the inner L-BFGS while_loop starts from
        # replicated values and carries shard-varying ones, which the
        # varying-axes checker refuses; the staged psum above
        # establishes the P() output replication it would verify
        return M.shard_map(round_body, mesh=mesh,
                           in_specs=(P(), P(), P(), P(), P(),
                                     jax.tree.map(lambda _: P(), hyper),
                                     specs),
                           out_specs=(P(), P(), P()),
                           check_vma=False)(chunk_idx, c, c_prev, g_prev,
                                            mu, hyper, batch)

    jitted = jax.jit(make)
    if inner > 1:
        return jitted
    # classic arity: chunk_idx is meaningless at inner_chunks=1
    return jax.jit(lambda c, c_prev, g_prev, mu, hyper, batch: jitted(
        jnp.asarray(0, jnp.int32), c, c_prev, g_prev, mu, hyper, batch))


def build_global_vg(objective: GLMObjective, mesh):
    """Shard-map-explicit global ``(f, g)`` over the same layout, with
    the identical staged psum — the reference arm and the
    bootstrap/closing evaluation. Its jaxpr carries exactly ONE
    DCN-stage psum, so a reference L-BFGS solve issues one DCN
    reduction PER FUNCTION EVALUATION (vs per round for the
    hierarchical program)."""
    sample_axes = _sample_axes(mesh)
    p_shards = _num_shards(mesh, sample_axes)

    def vg_body(c, hyper, batch):
        f, g = objective.local_value_and_gradient(c, batch, hyper, p_shards)
        packed = _staged_all_psum(jnp.concatenate([g, f[None]]), mesh)
        return packed[-1], packed[:-1]

    def make(c, hyper, batch):
        specs = _batch_specs(batch, sample_axes)
        return M.shard_map(vg_body, mesh=mesh,
                           in_specs=(P(), jax.tree.map(lambda _: P(), hyper),
                                     specs),
                           out_specs=(P(), P()))(c, hyper, batch)

    return jax.jit(make)


def minimize_hier(objective: GLMObjective, batch: DataBatch, hyper: Hyper,
                  x0: Array, mesh, *,
                  config: HierConfig = HierConfig()) -> HierResult:
    """Run the hierarchical solve: shard ``batch`` over the mesh's
    ``(dcn?, data)`` axes, bootstrap the correction anchor with one
    global evaluation, then iterate rounds of corrected device-local
    L-BFGS + one staged psum each, safeguarded by the host-side
    accept/fallback loop.

    The Pallas fused kernel is disabled while tracing these programs:
    inside a shard_map body the operands are per-shard tracers and the
    kernel's dispatch gate cannot see the enclosing mesh, so routing
    stays on the (shard-safe) XLA aggregators.
    """
    _check_features(batch)
    sample_axes = _sample_axes(mesh)
    sharded = M.shard_batch(
        batch, mesh,
        axis=sample_axes if len(sample_axes) > 1 else sample_axes[0])
    c = M.replicate(jnp.asarray(x0), mesh)

    round_fn = build_round_fn(objective, mesh, config)
    global_vg = build_global_vg(objective, mesh)

    fb_cfg = SolverConfig(max_iterations=1,
                          tolerance=config.tolerance,
                          num_corrections=config.num_corrections,
                          linesearch_max_iterations=(
                              config.linesearch_max_iterations))

    def _fallback_step(c_best, hyper_, batch_):
        return lbfgs.minimize(
            lambda ci: global_vg(ci, hyper_, batch_), c_best, config=fb_cfg)

    fallback_fn = jax.jit(_fallback_step)
    hits = _metrics.counter("parallel.dcn_stage_reductions", path="hier")

    # bootstrap: one global evaluation seeds f_best AND the correction
    # anchor (c_prev, g_prev), so round 1 is already gradient-corrected
    with pallas_glm.disabled():
        f0, g0 = global_vg(c, hyper, sharded)
    dcn = 1
    hits.inc()
    f_best = float(np.asarray(f0))
    g0_norm = float(np.linalg.norm(np.asarray(g0)))
    gtol = config.tolerance * max(1.0, g0_norm)
    eps = float(jnp.finfo(jnp.asarray(x0).dtype).eps)
    x_best, c_prev, g_prev = c, c, g0
    rounds = accepted = fallbacks = stall = 0
    pending = False    # does c hold a not-yet-evaluated candidate?
    at_anchor = True   # is c a point whose loss IS f_best by construction?
    mu = float(config.prox)
    dtype = jnp.asarray(x0).dtype
    history = [f_best]
    converged = g0_norm <= gtol

    inner = int(config.inner_chunks)
    while rounds < config.rounds and not converged:
        with pallas_glm.disabled():
            if inner > 1:
                # round-robin chunk cursor: traced, so every round reuses
                # the one compiled program
                avg_delta, g_c, f_c = round_fn(
                    jnp.asarray(rounds % inner, jnp.int32), c, c_prev,
                    g_prev, jnp.asarray(mu, dtype), hyper, sharded)
            else:
                avg_delta, g_c, f_c = round_fn(
                    c, c_prev, g_prev, jnp.asarray(mu, dtype), hyper,
                    sharded)
        rounds += 1
        dcn += 1
        hits.inc()
        f_c_h = float(np.asarray(f_c))
        history.append(f_c_h)
        pending = False
        # ftol: material-progress threshold; slack: the dtype's own
        # round-off at this loss magnitude — a "regression" smaller than
        # float noise is a tie, not a safeguard trip
        ftol = max(config.tolerance, 4.0 * eps) * (abs(f_best) + 1.0)
        slack = 16.0 * eps * (abs(f_best) + 1.0)
        if np.isfinite(f_c_h) and (at_anchor or f_c_h <= f_best + slack):
            # accept: the delta that produced c held or improved the
            # global loss (or c IS the anchor — f_c equals f_best by
            # construction, nothing to judge yet); advance along this
            # round's averaged local displacement
            if f_c_h < f_best:
                improvement = f_best - f_c_h
                x_best, f_best = c, f_c_h
                accepted += 1
            else:
                improvement = 0.0
            if not at_anchor:
                stall = stall + 1 if improvement <= ftol else 0
                if improvement > ftol:
                    mu *= 0.25  # damping pays rent only while needed
                    if mu < 1e-12:
                        mu = 0.0
            gnorm = float(np.linalg.norm(np.asarray(g_c)))
            if gnorm <= gtol or stall >= 3:
                # stationary, or three straight advanced rounds below
                # material progress — converged to the dtype's
                # resolution of the optimum
                converged = True
                break
            c_prev, g_prev = c, g_c
            c = c + avg_delta
            pending = True
            at_anchor = False
        else:
            # safeguard: the previous round's delta regressed the GLOBAL
            # loss. Typed event, delta discarded, one reference global
            # step from the best-known iterate re-anchors the rounds,
            # and the proximal damping tightens so the next round's
            # local solves stay nearer the anchor (trust-region shrink).
            fallbacks += 1
            _metrics.counter("hier.fallbacks").inc()
            record_failure("hier_fallback", round=rounds,
                           f_candidate=f_c_h, f_best=f_best)
            delta_norm = float(np.linalg.norm(
                np.asarray(c) - np.asarray(x_best)))
            g_anchor_norm = float(np.linalg.norm(np.asarray(g_prev)))
            mu_floor = g_anchor_norm / max(delta_norm, 1e-30)
            mu = max(4.0 * mu, mu_floor)
            with pallas_glm.disabled():
                res = fallback_fn(x_best, hyper, sharded)
            n_evals = int(np.asarray(res.num_fun_evals))
            dcn += n_evals
            hits.inc(n_evals)
            prev_best = f_best
            x_best = res.coef
            f_best = float(np.asarray(res.value))
            history.append(f_best)
            # the fallback result carries the exact global gradient at
            # the new anchor — the next round's correction is fresh
            c, c_prev, g_prev = res.coef, res.coef, res.gradient
            at_anchor = True
            stall = 0
            if (float(np.linalg.norm(np.asarray(res.gradient))) <= gtol
                    or prev_best - f_best <= ftol):
                # even the reference step cannot make material progress
                converged = True
                break

    # closing global evaluation of the final (unevaluated) candidate —
    # the monotone best-of guarantee costs one more staged reduction
    if pending:
        with pallas_glm.disabled():
            f_final, _ = global_vg(c, hyper, sharded)
        dcn += 1
        hits.inc()
        f_final_h = float(np.asarray(f_final))
        history.append(f_final_h)
        if np.isfinite(f_final_h) and f_final_h < f_best:
            x_best, f_best = c, f_final_h

    _metrics.gauge("hier.rounds").set(rounds)
    _metrics.gauge("hier.dcn_reductions").set(dcn)
    return HierResult(coef=x_best, value=f_best, rounds=rounds,
                      accepted=accepted, fallbacks=fallbacks,
                      dcn_reductions=dcn, history=tuple(history),
                      converged=converged)


def minimize_reference(objective: GLMObjective, batch: DataBatch,
                       hyper: Hyper, x0: Array, mesh, *,
                       config: SolverConfig = SolverConfig()
                       ) -> Tuple[lbfgs.SolverResult, int]:
    """Reference data-parallel solve over the SAME shard-map-explicit
    global value-and-grad (one staged DCN psum per evaluation). Returns
    ``(result, dcn_reductions)`` where the reduction count is
    ``num_fun_evals`` — every evaluation crossed DCN once. This is the
    comparison arm for the >=5x fewer-DCN-reductions acceptance bar."""
    _check_features(batch)
    sample_axes = _sample_axes(mesh)
    sharded = M.shard_batch(
        batch, mesh,
        axis=sample_axes if len(sample_axes) > 1 else sample_axes[0])
    c = M.replicate(jnp.asarray(x0), mesh)
    global_vg = build_global_vg(objective, mesh)

    def _solve(ci, hyper_, batch_):
        return lbfgs.minimize(
            lambda cc: global_vg(cc, hyper_, batch_), ci, config=config)

    with pallas_glm.disabled():
        res = jax.jit(_solve)(c, hyper, sharded)
    n = int(np.asarray(res.num_fun_evals))
    _metrics.counter("parallel.dcn_stage_reductions", path="reference").inc(n)
    return res, n
