"""Chunk-local stochastic dual coordinate ascent over the streaming store.

Every other solver in this tree is a batch method: a fit on the
disk-native chunk store pays one full storage pass per line-search
evaluation, tens of passes per solve. SDCA (Snap ML, TPA-SCD — see
PAPERS.md) flips the loop: ONE storage pass per outer epoch, with each
device-resident chunk running a compiled inner program of randomized
dual-coordinate updates while the next chunk streams in behind it on the
double-buffered :class:`~photon_tpu.data.streaming.ChunkLoader`.

Duality setup (SUM + per-example-weight convention, matching
``GLMObjective``):

    P(w) = sum_i c_i phi(x_i . w + o_i) + (l2/2) |w|^2

with dual variables ``alpha_i`` (one per example, stored chunk-local in
a ``[C, R]`` device-resident table), the shared primal carry
``v = sum_i alpha_i x_i`` (so ``w = v / l2``), and

    D(alpha) = -sum_i [ c_i phi*(-alpha_i / c_i) + alpha_i o_i ]
               - |v|^2 / (2 l2)

Weak duality gives the typed stopping certificate for free: with
``z_i = x_i . w + o_i``,

    gap_i = c_i phi(z_i) + c_i phi*(-alpha_i / c_i) + alpha_i z_i >= 0

(Fenchel-Young, pointwise), and ``sum_i gap_i = P(w) - D(alpha)`` bounds
the primal suboptimality directly. The per-chunk program accumulates
these partials AT CHUNK ENTRY — the same numbers its update loop needs
anyway — so the gap costs no extra data pass. Between chunk visits a
row's ``alpha_i`` is frozen while ``v`` moves, so the per-epoch gap
estimate is one-visit lagged (Snap ML reports the same way); it is
nonnegative always and exact at convergence.

Cross-chunk consistency follows the papers' bounded-staleness recipe:
each chunk commits against the primal snapshot it entered with (on a
mesh, each sample shard additionally carries its own local ``v`` through
the whole epoch — the chunk program contains ZERO collectives, and the
epoch-end merge is exactly one staged ICI->DCN psum). The analytic dual
increase every update predicts,

    dD = cps(alpha) - cps(alpha + d) - d (o + m) - d^2 q / 2,
         cps(a) = c phi*(-a / c),  q = |x_i|^2 / l2,

is accumulated alongside, and the realized increase (the dual estimate
is exactly one epoch lagged, so realized lands one epoch later) is
checked against it — a shortfall is the staleness signature, answered by
halving the CoCoA-style step damping (applied to BOTH ``alpha`` and
``v`` inside the update, preserving ``v = sum alpha_i x_i`` exactly)
and a typed ``sdca_staleness_fallback`` record. Never an exception —
mirroring game/parallel_cd.py's predicted-vs-realized degradation.

Determinism is total: coordinate permutations are counter-derived
(``fold_in(key, epoch, chunk, inner[, shard])``), the chunk visit order
is :func:`~photon_tpu.data.streaming.epoch_chunk_order`, and the host
loop is straight-line numpy — two runs are bitwise identical, and the
crc-framed kill/resume checkpoint (dual table + primal carry + chunk
cursor) replays to the same bits.

Losses: logistic, squared, smoothed hinge have closed-form or safe
guarded-Newton conjugate steps; Poisson's dual step has neither (the
conjugate ``u log u - u`` step lands outside any box the weights
bound) and is refused typed (:class:`SdcaUnsupportedLossError`).
"""

from __future__ import annotations

import dataclasses
import io
import json
import math
import os
import struct
import threading
import zlib
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from photon_tpu.data.streaming import epoch_chunk_order
from photon_tpu.function.objective import GLMObjective
from photon_tpu.ops import features as F
from photon_tpu.optim.base import (
    ConvergenceReason,
    FailureMode,
    SolverResult,
    jit_donating,
)
from photon_tpu.resilience import chaos
from photon_tpu.resilience import failures
from photon_tpu.resilience import io as rio

Array = jax.Array


# =========================================================================
# Typed refusal surface
# =========================================================================

class SdcaUnsupportedLossError(ValueError):
    """The task's loss has no implemented conjugate (dual) step."""


class SdcaWeightError(ValueError):
    """Example weights are non-finite or negative — the dual step divides
    by ``c_i`` and boxes ``alpha_i`` by it, so a bad weight corrupts the
    solve silently. Validated on the host BEFORE anything compiles."""


def validate_example_weights(source, block_rows: int = 1 << 16) -> None:
    """Host block-scan of a chunk source's example weights. Sources
    without a weights column (implicit weight 1) pass trivially."""
    w = getattr(source, "weights", None)
    if w is None:
        return
    n = int(w.shape[0])
    for s in range(0, n, block_rows):
        blk = np.asarray(w[s:s + block_rows])
        if not bool(np.all(np.isfinite(blk))):
            raise SdcaWeightError(
                f"non-finite example weight in rows [{s}, "
                f"{min(n, s + block_rows)}) — SDCA's dual step divides by "
                f"the weight; clean the data or drop the rows")
        if bool(np.any(blk < 0)):
            raise SdcaWeightError(
                f"negative example weight in rows [{s}, "
                f"{min(n, s + block_rows)}) — a negative weight makes the "
                f"per-example dual problem unbounded")


# =========================================================================
# Config
# =========================================================================

@dataclasses.dataclass(frozen=True)
class SdcaConfig:
    """Knobs for :func:`minimize_sdca`.

    ``gap_tolerance`` is RELATIVE to the first epoch's gap estimate
    (with ``alpha = 0`` every conjugate term vanishes, so the initial
    gap is the initial primal data loss — the natural scale).
    ``inner_epochs`` repeats the randomized coordinate sweep within each
    resident chunk before the stream moves on (TPA-SCD's
    epochs-within-chunk; more local work per byte streamed).
    ``staleness_guard``: fallback triggers when the realized dual
    increase of an epoch falls below ``guard x predicted`` — on a single
    device realized == predicted to FP, so the default never fires
    there; meshed shard staleness is what it watches.
    """

    max_epochs: int = 20
    gap_tolerance: float = 1e-3
    inner_epochs: int = 1
    seed: int = 0
    newton_steps: int = 8
    staleness_guard: float = 0.5
    min_damping: float = 1.0 / 16.0


# =========================================================================
# Per-loss conjugate steps
# =========================================================================
#
# Each loss contributes two shape-polymorphic pure functions:
#   step(alpha, z, q, c, c_safe, y) -> d       the UNgated, UNdamped
#       coordinate-optimal dual increment solving
#       phi*'(-(alpha+d)/c) = z + d q (box-projected where the conjugate
#       has a box)
#   cps(alpha, c, c_safe, y) -> c phi*(-alpha/c)
# ``c_safe`` is ``where(c > 0, c, 1)`` — pad rows (weight 0) divide by 1
# and are gated to a zero update/partial by the caller.

def _dual_functions(loss_name: str, newton_steps: int
                    ) -> Tuple[Callable, Callable]:
    if loss_name == "squared":
        # phi(z) = (z-y)^2 / 2;  phi*(u) = u y + u^2 / 2
        def step(alpha, z, q, c, c_safe, y):
            return (c * (y - z) - alpha) / (1.0 + c * q)

        def cps(alpha, c, c_safe, y):
            return -alpha * y + alpha * alpha / (2.0 * c_safe)

        return step, cps

    if loss_name == "logistic":
        # phi(z) = log(1+e^z) - y z, y in {0,1};
        # phi*(u) = t log t + (1-t) log(1-t) with t = u + y in [0,1].
        # Coordinate optimum: t = y - (alpha+d)/c solves the monotone
        # g(t) = logit(t) - z - q (c (y - t) - alpha) = 0; g' =
        # 1/(t(1-t)) + q c > 0, so clipped Newton from t0 = sigmoid(z)
        # converges fast (8 steps lands at FP resolution in practice).
        def step(alpha, z, q, c, c_safe, y):
            lo = jnp.asarray(np.finfo(np.dtype(jnp.result_type(z))).eps,
                             jnp.result_type(z))
            t0 = jnp.clip(jax.nn.sigmoid(z), lo, 1.0 - lo)

            def newton(_, t):
                g = (jnp.log(t) - jnp.log1p(-t) - z
                     - q * (c * (y - t) - alpha))
                gp = 1.0 / (t * (1.0 - t)) + q * c
                return jnp.clip(t - g / gp, lo, 1.0 - lo)

            t = lax.fori_loop(0, newton_steps, newton, t0)
            return c * (y - t) - alpha

        def cps(alpha, c, c_safe, y):
            t = jnp.clip(y - alpha / c_safe, 0.0, 1.0)

            def xlogx(x):
                tiny = jnp.asarray(
                    np.finfo(np.dtype(jnp.result_type(x))).tiny,
                    jnp.result_type(x))
                return jnp.where(x > 0, x * jnp.log(jnp.maximum(x, tiny)),
                                 jnp.zeros_like(x))

            return c * (xlogx(t) + xlogx(1.0 - t))

        return step, cps

    if loss_name == "smoothed_hinge":
        # phi(z) = psi(s z), s = 2y-1; psi*(r) = r + r^2/2 on [-1, 0].
        # With a = s alpha / c in [0, 1]: unconstrained optimum
        # a* = a + (1 - s z - a)/(1 + q c), box-projected; d = c s (a*-a).
        def step(alpha, z, q, c, c_safe, y):
            s = 2.0 * y - 1.0
            a = s * alpha / c_safe
            a_new = jnp.clip(a + (1.0 - s * z - a) / (1.0 + q * c),
                             0.0, 1.0)
            return c * s * (a_new - a)

        def cps(alpha, c, c_safe, y):
            s = 2.0 * y - 1.0
            a = jnp.clip(s * alpha / c_safe, 0.0, 1.0)
            return c * (0.5 * a * a - a)

        return step, cps

    raise SdcaUnsupportedLossError(
        f"SDCA has no conjugate step for loss {loss_name!r} (supported: "
        f"logistic, squared, smoothed_hinge; Poisson's dual step has no "
        f"closed form or safely boxed Newton) — use the streamed "
        f"L-BFGS/OWL-QN path for this task")


def validate_loss(loss_name: str) -> None:
    """Config-time typed check that SDCA has a conjugate step for this
    loss (raises :class:`SdcaUnsupportedLossError` otherwise) — lets a
    coordinate refuse a Poisson+SDCA config at construction instead of
    mid-fit."""
    _dual_functions(loss_name, 1)


# =========================================================================
# Feature access (dense / padded-ELL; pads are (0, 0.0) => contribute 0)
# =========================================================================

def _check_features(feats) -> None:
    if isinstance(feats, F.ModelShardedSparse):
        raise ValueError(
            "SDCA keeps the full primal carry v per sample shard, which "
            "contradicts model-axis sharding of theta; use the streamed "
            "L-BFGS path for model-sharded coordinates")


def _margins(feats, v: Array) -> Array:
    if isinstance(feats, F.SparseFeatures):
        return jnp.sum(feats.values * v[feats.indices], axis=1)
    return feats @ v


def _row_sqnorms(feats) -> Array:
    if isinstance(feats, F.SparseFeatures):
        return jnp.sum(feats.values * feats.values, axis=1)
    return jnp.sum(feats * feats, axis=1)


def _row_dot(feats, i: Array, v: Array) -> Array:
    if isinstance(feats, F.SparseFeatures):
        return jnp.sum(feats.values[i] * v[feats.indices[i]])
    return jnp.dot(feats[i], v)


def _row_axpy(v: Array, feats, i: Array, scale: Array) -> Array:
    if isinstance(feats, F.SparseFeatures):
        return v.at[feats.indices[i]].add(scale * feats.values[i])
    return v + scale * feats[i]


# =========================================================================
# Module stats (RunReport `sdca` section — mirrors optim/batched's sweep)
# =========================================================================

_STATS_LOCK = threading.Lock()
_STATS = {"runs": 0, "epochs": 0, "fallbacks": 0, "converged": 0,
          "last": None}


def reset_sdca_stats() -> None:
    with _STATS_LOCK:
        _STATS.update(runs=0, epochs=0, fallbacks=0, converged=0, last=None)


def report_section() -> Optional[dict]:
    with _STATS_LOCK:
        if not _STATS["runs"]:
            return None
        return {"runs": _STATS["runs"], "epochs": _STATS["epochs"],
                "fallbacks": _STATS["fallbacks"],
                "converged": _STATS["converged"],
                "last": None if _STATS["last"] is None
                else dict(_STATS["last"])}


def _record_run(last: dict, converged: bool) -> None:
    # fallbacks are counted per-event in _record_fallback (survives a
    # mid-run kill); counting them again here would double the total
    with _STATS_LOCK:
        _STATS["runs"] += 1
        _STATS["converged"] += int(converged)
        _STATS["last"] = last


# =========================================================================
# Compiled programs (one per (mesh, batch structure) — shared across all
# chunks, epochs and damping values: everything varying is traced)
# =========================================================================

class _SdcaPrograms:
    """Compiled chunk/finalize programs + state plumbing for one solve.

    State dict (device-resident):
      unmeshed: {"alpha": [C, R], "v": [d]}
      meshed:   {"alpha": [C, R] sharded on R, "vloc": [p, d] shard-local,
                 "vg": [d] replicated epoch-start primal carry}
    ``acc`` is the per-epoch partials accumulator
    [primal_entry, gap_entry, dual_ps_entry, predicted_increase]
    ([4] unmeshed, [p, 4] shard-local meshed).
    """

    def __init__(self, objective: GLMObjective, loader, cfg: SdcaConfig,
                 l2_weight: float, dim: int, dtype, c_max: int):
        self.objective = objective
        self.loader = loader
        self.mesh = loader.mesh
        self.cfg = cfg
        self.dim = int(dim)
        self.dtype = np.dtype(dtype)
        self.c_max = int(c_max)
        self.chunk_rows = int(loader.chunk_rows)
        self._l2 = jnp.asarray(l2_weight, self.dtype)
        self._key = jax.random.PRNGKey(cfg.seed)
        self._step, self._cps = _dual_functions(objective.loss.name,
                                                cfg.newton_steps)
        if self.mesh is None:
            self._build_unmeshed()
        else:
            self._build_meshed()

    # -- shared chunk body (runs per device; shard-local on a mesh) ---------

    def _chunk_body(self, alpha_all, v, acc, batch, rows, epoch, chunk_id,
                    damping, row_base, sigma=1.0):
        """alpha_all [C, r], v [d], acc [4] -> updated triple. ``r`` is
        the (possibly shard-local) row count; ``row_base`` offsets local
        row positions into the global chunk so the pad mask and the
        permutation key stay correct per shard.

        ``sigma`` is the CoCoA+ safety factor (= number of sample shards
        on a mesh, 1.0 unmeshed): K shards taking full local steps and
        merging additively overshoot by up to K, so each local step
        solves the sigma-conservative subproblem instead — effective
        curvature ``sigma * q`` and a sigma-boosted carry ``u = v_global
        + sigma * dv_local`` (the caller converts vloc <-> u at the
        chunk boundary). With gamma=1, sigma=K the additive epoch-end
        merge is provably safe (Ma et al., CoCoA+), and the accumulated
        predicted gain is a certified LOWER bound on the realized global
        dual increase — which is exactly what the staleness guard
        watches. At sigma=1 every formula reduces to plain sequential
        SDCA."""
        cfg, loss = self.cfg, self.objective.loss
        step_fn, cps_fn = self._step, self._cps
        l2 = self._l2
        feats, y = batch.features, batch.labels
        r = y.shape[0]
        o = (batch.offsets if batch.offsets is not None
             else jnp.zeros_like(y))
        w = batch.weights if batch.weights is not None else jnp.ones_like(y)
        # weight-0 pad rows (and any stale staging tail): gate everything
        mask = (row_base + jnp.arange(r, dtype=jnp.int32)) < rows
        c = jnp.where(mask, w, jnp.zeros_like(w))
        c_safe = jnp.where(c > 0, c, jnp.ones_like(c))
        live = c > 0
        q = jnp.asarray(sigma, self.dtype) * _row_sqnorms(feats) / l2

        zero_i = jnp.zeros((), chunk_id.dtype)  # match index width (x64)
        alpha = lax.dynamic_slice(alpha_all, (chunk_id, zero_i), (1, r))[0]

        # entry partials: the SAME numbers the update loop consumes,
        # doubling as the (one-visit-lagged) gap/dual/primal estimators
        z_entry = _margins(feats, v) / l2 + o
        phi = loss.loss_and_dz(z_entry, y)[0]
        cps_entry = cps_fn(alpha, c, c_safe, y)
        zero = jnp.zeros_like(y)
        primal_entry = jnp.sum(jnp.where(live, c * phi, zero))
        gap_entry = jnp.sum(jnp.where(
            live, c * phi + cps_entry + alpha * z_entry, zero))
        dual_ps_entry = jnp.sum(jnp.where(live, cps_entry + alpha * o,
                                          zero))

        key_c = jax.random.fold_in(
            jax.random.fold_in(self._key, epoch), chunk_id)
        if self.mesh is not None:
            key_c = jax.random.fold_in(key_c, row_base)

        def inner(inner_idx, carry):
            v, alpha, pred = carry
            perm = jax.random.permutation(
                jax.random.fold_in(key_c, inner_idx), r)

            def body(t, st):
                v, alpha, pred = st
                i = perm[t]
                ci, csi, yi = c[i], c_safe[i], y[i]
                oi, qi, ai = o[i], q[i], alpha[i]
                m_loc = _row_dot(feats, i, v) / l2
                zi = m_loc + oi
                d_raw = step_fn(ai, zi, qi, ci, csi, yi)
                d = jnp.where(ci > 0, damping * d_raw,
                              jnp.zeros_like(d_raw))
                inc = jnp.where(
                    ci > 0,
                    cps_fn(ai, ci, csi, yi) - cps_fn(ai + d, ci, csi, yi)
                    - d * zi - 0.5 * d * d * qi,
                    jnp.zeros_like(d))
                # u-carry: alpha_i += d moves the boosted vector by
                # sigma * d * x_i (= d * x_i when unmeshed)
                v = _row_axpy(v, feats, i,
                              jnp.asarray(sigma, d.dtype) * d)
                alpha = alpha.at[i].set(ai + d)
                return v, alpha, pred + inc

            v, alpha, pred = lax.fori_loop(0, r, body, (v, alpha, pred))
            return v, alpha, pred

        v, alpha, pred = lax.fori_loop(
            0, cfg.inner_epochs, inner,
            (v, alpha, jnp.zeros((), v.dtype)))

        alpha_all = lax.dynamic_update_slice(alpha_all, alpha[None],
                                             (chunk_id, zero_i))
        acc = acc + jnp.stack([primal_entry, gap_entry, dual_ps_entry,
                               pred])
        return alpha_all, v, acc

    # -- unmeshed -----------------------------------------------------------

    def _build_unmeshed(self):
        def chunk(alpha_all, v, acc, batch, rows, epoch, chunk_id,
                  damping):
            return self._chunk_body(alpha_all, v, acc, batch, rows, epoch,
                                    chunk_id, damping,
                                    jnp.zeros((), jnp.int32))

        self._chunk = jit_donating(chunk, donate_argnums=(0, 1, 2))

        def finalize(v, v_start, acc, l2):
            primal = acc[0] + jnp.dot(v, v) / (2.0 * l2)
            dual = -acc[2] - jnp.dot(v_start, v_start) / (2.0 * l2)
            return jnp.stack([primal, dual, acc[1], acc[3]])

        self._finalize = jax.jit(finalize)

    # -- meshed: shard-local v, one staged psum per epoch -------------------

    def _build_meshed(self):
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from photon_tpu.optim.hier import (
            _num_shards,
            _sample_axes,
            _staged_all_psum,
        )
        from photon_tpu.parallel import mesh as M

        mesh = self.mesh
        sample_axes = _sample_axes(mesh)
        self._p_shards = _num_shards(mesh, sample_axes)
        spec_axis = (sample_axes if len(sample_axes) > 1
                     else sample_axes[0])
        if self.chunk_rows % self._p_shards:
            raise ValueError(
                f"chunk_rows={self.chunk_rows} not divisible by "
                f"{self._p_shards} sample shards")
        r_loc = self.chunk_rows // self._p_shards
        self._shardings = {
            "alpha": NamedSharding(mesh, P(None, spec_axis)),
            "vloc": NamedSharding(mesh, P(spec_axis, None)),
            "acc": NamedSharding(mesh, P(spec_axis, None)),
        }
        alpha_spec, vloc_spec, acc_spec = (P(None, spec_axis),
                                           P(spec_axis, None),
                                           P(spec_axis, None))

        def shard_pos():
            i = jnp.zeros((), jnp.int32)
            for a in sample_axes:
                i = i * M.axis_size(mesh, a) + lax.axis_index(a)
            return i

        sigma = float(self._p_shards)

        def chunk_body(alpha_all, vloc, vg, acc, batch, rows, epoch,
                       chunk_id, damping):
            row_base = shard_pos() * r_loc
            # sigma-boosted local carry (see _chunk_body): margins and
            # steps see this shard's own updates amplified K-fold, which
            # is what makes the additive epoch-end merge safe
            u = vg + sigma * (vloc[0] - vg)
            a, u2, ac = self._chunk_body(alpha_all, u, acc[0], batch,
                                         rows, epoch, chunk_id, damping,
                                         row_base, sigma=sigma)
            vloc_out = vg + (u2 - vg) / sigma
            return a, vloc_out[None], ac[None]

        def chunk(alpha_all, vloc, vg, acc, batch, rows, epoch, chunk_id,
                  damping):
            specs = jax.tree.map(
                lambda x: P(spec_axis, *([None] * (x.ndim - 1))), batch)
            return M.shard_map(
                chunk_body, mesh=mesh,
                in_specs=(alpha_spec, vloc_spec, P(), acc_spec, specs,
                          P(), P(), P(), P()),
                out_specs=(alpha_spec, vloc_spec, acc_spec),
                check_vma=False,
            )(alpha_all, vloc, vg, acc, batch, rows, epoch, chunk_id,
              damping)

        self._chunk_meshed = jit_donating(chunk, donate_argnums=(0, 1, 3))

        def merge_body(vloc, vg, acc):
            # the epoch's single reduction: [dv | partials] in one staged
            # ICI-then-DCN psum. Shards own DISJOINT rows, so the add
            # merge preserves v = sum alpha_i x_i exactly.
            packed = _staged_all_psum(
                jnp.concatenate([vloc[0] - vg, acc[0]]), mesh)
            return vg + packed[:-4], packed[-4:]

        def merge(vloc, vg, acc):
            return M.shard_map(
                merge_body, mesh=mesh,
                in_specs=(vloc_spec, P(), acc_spec),
                out_specs=(P(), P()),
                check_vma=False,
            )(vloc, vg, acc)

        self._merge = jax.jit(merge)

        def finalize(vg_new, v_start, acc_tot, l2):
            primal = acc_tot[0] + jnp.dot(vg_new, vg_new) / (2.0 * l2)
            dual = -acc_tot[2] - jnp.dot(v_start, v_start) / (2.0 * l2)
            return jnp.stack([primal, dual, acc_tot[1], acc_tot[3]])

        self._finalize = jax.jit(finalize)

    # -- state plumbing -----------------------------------------------------

    def init_state(self) -> dict:
        c, r, d, dt = self.c_max, self.chunk_rows, self.dim, self.dtype
        if self.mesh is None:
            return {"alpha": jnp.zeros((c, r), dt), "v": jnp.zeros((d,), dt)}
        from photon_tpu.parallel import mesh as M
        p = self._p_shards
        return {
            "alpha": jax.device_put(np.zeros((c, r), dt),
                                    self._shardings["alpha"]),
            "vloc": jax.device_put(np.zeros((p, d), dt),
                                   self._shardings["vloc"]),
            "vg": M.replicate(jnp.zeros((d,), dt), self.mesh),
        }

    def init_acc(self):
        if self.mesh is None:
            return jnp.zeros((4,), self.dtype)
        return jax.device_put(np.zeros((self._p_shards, 4), self.dtype),
                              self._shardings["acc"])

    def epoch_carry(self, state: dict) -> Array:
        """The epoch-start primal carry the dual estimate is anchored to
        (functional arrays: holding the reference keeps it valid)."""
        return state["v"] if self.mesh is None else state["vg"]

    def run_chunk(self, state: dict, acc, batch, rows: int, epoch: int,
                  chunk_id: int, damping: float):
        args = (acc, batch, jnp.int32(rows), jnp.int32(epoch),
                jnp.int32(chunk_id), jnp.asarray(damping, self.dtype))
        if self.mesh is None:
            a, v, acc = self._chunk(state["alpha"], state["v"], *args)
            return {"alpha": a, "v": v}, acc
        a, vloc, acc = self._chunk_meshed(state["alpha"], state["vloc"],
                                          state["vg"], *args)
        return {"alpha": a, "vloc": vloc, "vg": state["vg"]}, acc

    def finish_epoch(self, state: dict, acc, v_start):
        """Epoch-end merge + scalars. Returns (state', scalars[4]) where
        scalars = [primal, dual, gap, predicted]."""
        if self.mesh is None:
            return state, self._finalize(state["v"], v_start, acc,
                                         self._l2)
        vg_new, acc_tot = self._merge(state["vloc"], state["vg"], acc)
        scal = self._finalize(vg_new, v_start, acc_tot, self._l2)
        vloc = jax.device_put(
            jnp.broadcast_to(vg_new, (self._p_shards, self.dim)),
            self._shardings["vloc"])
        return {"alpha": state["alpha"], "vloc": vloc, "vg": vg_new}, scal

    def coef_host(self, state: dict) -> np.ndarray:
        v = state["v"] if self.mesh is None else state["vg"]
        return np.asarray(v) / float(np.asarray(self._l2))

    def state_to_host(self, state: dict, acc, v_start) -> dict:
        out = {f"st_{k}": np.asarray(a) for k, a in state.items()}
        out["acc"] = np.asarray(acc)
        out["v_start"] = np.asarray(v_start)
        return out

    def state_from_host(self, arrays: dict):
        if self.mesh is None:
            state = {"alpha": jnp.asarray(arrays["st_alpha"], self.dtype),
                     "v": jnp.asarray(arrays["st_v"], self.dtype)}
            acc = jnp.asarray(arrays["acc"], self.dtype)
            v_start = jnp.asarray(arrays["v_start"], self.dtype)
            return state, acc, v_start
        from photon_tpu.parallel import mesh as M
        state = {
            "alpha": jax.device_put(np.asarray(arrays["st_alpha"]),
                                    self._shardings["alpha"]),
            "vloc": jax.device_put(np.asarray(arrays["st_vloc"]),
                                   self._shardings["vloc"]),
            "vg": M.replicate(jnp.asarray(arrays["st_vg"], self.dtype),
                              self.mesh),
        }
        acc = jax.device_put(np.asarray(arrays["acc"]),
                             self._shardings["acc"])
        v_start = M.replicate(jnp.asarray(arrays["v_start"], self.dtype),
                              self.mesh)
        return state, acc, v_start


# =========================================================================
# Checkpoint (crc-framed npz, atomic publish — own magic, same framing
# discipline as optim/streaming's PTSTRMC1)
# =========================================================================

_MAGIC = b"PTSDCAC1"
_SCHEMA = 1


def _encode_checkpoint(meta: dict, arrays: dict) -> bytes:
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    body = buf.getvalue()
    meta_b = json.dumps(meta, sort_keys=True).encode()
    return (_MAGIC + struct.pack("<II", zlib.crc32(body), len(meta_b))
            + meta_b + body)


def _decode_checkpoint(blob: bytes) -> Tuple[dict, dict]:
    if blob[:8] != _MAGIC:
        raise ValueError("not an SDCA checkpoint (bad magic)")
    crc, mlen = struct.unpack("<II", blob[8:16])
    meta = json.loads(blob[16:16 + mlen].decode())
    body = blob[16 + mlen:]
    if zlib.crc32(body) != crc:
        raise ValueError("SDCA checkpoint payload crc mismatch")
    if meta.get("schema") != _SCHEMA:
        raise ValueError(
            f"SDCA checkpoint schema {meta.get('schema')} != {_SCHEMA}")
    with np.load(io.BytesIO(body), allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files}
    return meta, arrays


def load_sdca_checkpoint(path: str) -> Tuple[dict, dict]:
    """(meta, arrays) of an SDCA cursor checkpoint; raises ValueError on
    torn/corrupt files (crc framed)."""
    return _decode_checkpoint(rio.read_bytes(path, op="sdca.checkpoint"))


# =========================================================================
# Host epoch loop
# =========================================================================

def _record_fallback(epoch: int, predicted: float, realized: float,
                     damping: float) -> None:
    with _STATS_LOCK:
        _STATS["fallbacks"] += 1
    try:
        from photon_tpu.obs.metrics import registry
        registry.counter("sdca.fallbacks").inc()
    except Exception:   # hygiene-ok — telemetry is best-effort
        pass
    failures.record_failure("sdca_staleness_fallback", epoch=epoch,
                            predicted=predicted, realized=realized,
                            damping=damping)


def minimize_sdca(
    objective: GLMObjective,
    loader,
    *,
    l2_weight: float,
    config: SdcaConfig = SdcaConfig(),
    dim: Optional[int] = None,
    dtype=None,
    checkpoint_path: Optional[str] = None,
    checkpoint_every_chunks: int = 0,
    on_epoch: Optional[Callable[[int, dict], None]] = None,
) -> SolverResult:
    """Fit ``objective`` over a ChunkLoader's stream by chunk-local SDCA.

    One storage pass per outer epoch; duality-gap-typed stopping
    (``ConvergenceReason.DUALITY_GAP_CONVERGED``); bitwise run-to-run
    reproducible; crc-framed kill/resume via ``checkpoint_path``.
    ``on_epoch(epoch, info)`` fires after each epoch with the gap /
    primal / dual estimates and a host copy of the current coefficients
    (bench instrumentation; adds one host pull per epoch when set).

    Result mapping: ``coef = v / l2`` (the dual's primal iterate),
    ``gradient`` is all-zeros (SDCA never forms a primal gradient — the
    duality gap is the optimality certificate), ``iterations`` and
    ``num_fun_evals`` both count storage passes.
    """
    from photon_tpu.obs import spans as _obs_spans
    from photon_tpu.obs.metrics import registry

    if config.max_epochs < 1:
        raise ValueError("SdcaConfig.max_epochs must be >= 1")
    if not objective.norm.is_identity:
        raise ValueError(
            "SDCA runs in raw feature space (the dual step needs literal "
            "rows x_i); fold normalization into the store before "
            "streaming, or use the streamed L-BFGS path")
    if not l2_weight > 0.0:
        raise ValueError(
            "SDCA requires l2_weight > 0: the dual decomposition "
            "w = v / l2 does not exist for the unregularized problem")
    # typed refusal BEFORE any compile: unsupported conjugate, bad weights
    _dual_functions(objective.loss.name, config.newton_steps)
    validate_example_weights(loader.source)

    d = int(dim if dim is not None else loader.source.dim)
    dt = np.dtype(dtype if dtype is not None else loader.dtype)
    r = int(loader.chunk_rows)
    # unfiltered ceiling: with drop_invalid the true chunk count is only
    # known after pass 0, but it can never exceed this
    c_max = max(1, -(-int(loader.source.num_rows) // r))
    progs = _SdcaPrograms(objective, loader, config, float(l2_weight),
                          d, dt, c_max)

    state = progs.init_state()
    acc = progs.init_acc()
    v_start = progs.epoch_carry(state)
    damping = 1.0
    gap0: Optional[float] = None
    prev_dual: Optional[float] = None
    prev_pred: Optional[float] = None
    gap_history: list = []
    start_epoch, start_pos = 0, 0
    resumed_mid_epoch = False
    run_fallbacks = 0

    if checkpoint_path and os.path.exists(checkpoint_path):
        meta, arrays = load_sdca_checkpoint(checkpoint_path)
        if int(meta["dim"]) != d or int(meta["chunk_rows"]) != r:
            raise ValueError(
                f"SDCA checkpoint geometry (dim={meta['dim']}, "
                f"chunk_rows={meta['chunk_rows']}) does not match this "
                f"solve (dim={d}, chunk_rows={r})")
        state, acc, v_start = progs.state_from_host(arrays)
        damping = float(meta["damping"])
        gap0 = meta["gap0"]
        prev_dual = meta["prev_dual"]
        prev_pred = meta["prev_pred"]
        gap_history = list(arrays["gap_history"]) \
            if "gap_history" in arrays else []
        start_epoch = int(meta["epoch"])
        start_pos = int(meta["next_pos"])
        resumed_mid_epoch = True
        geom = None
        if meta.get("num_chunks") is not None:
            geom = {"num_chunks": int(meta["num_chunks"])}
            if "block_cum" in arrays:
                geom["block_cum"] = arrays["block_cum"]
        loader.restore_geometry(geom)

    def save_checkpoint(epoch: int, next_pos: int, state, acc,
                        v_start) -> None:
        arrays = progs.state_to_host(state, acc, v_start)
        arrays["gap_history"] = np.asarray(gap_history, np.float64)
        geom = loader.geometry()
        if geom is not None and geom.get("block_cum") is not None:
            arrays["block_cum"] = geom["block_cum"]
        meta = {
            "schema": _SCHEMA, "dim": d, "chunk_rows": r,
            "epoch": int(epoch), "next_pos": int(next_pos),
            "damping": float(damping), "gap0": gap0,
            "prev_dual": prev_dual, "prev_pred": prev_pred,
            "num_chunks": None if geom is None else geom["num_chunks"],
        }
        rio.atomic_write_bytes(checkpoint_path,
                               _encode_checkpoint(meta, arrays),
                               op="sdca.checkpoint")
        try:
            registry.counter("sdca.checkpoints").inc()
        except Exception:   # hygiene-ok — telemetry is best-effort
            pass

    ckpt_on = bool(checkpoint_path) and (checkpoint_every_chunks > 0
                                         or chaos.is_active())
    tiny = float(np.finfo(np.float64).tiny)
    reason = int(ConvergenceReason.MAX_ITERATIONS)
    failure = int(FailureMode.NONE)
    primal = float("nan")
    gap = float("nan")
    epochs_done = 0

    for e in range(start_epoch, config.max_epochs):
        if e == start_epoch and resumed_mid_epoch:
            pos0 = start_pos    # acc / v_start restored mid-epoch
        else:
            pos0 = 0
            acc = progs.init_acc()
            v_start = progs.epoch_carry(state)
        order = None
        if e > 0:
            n_chunks = loader.num_chunks
            if n_chunks is None:
                raise RuntimeError(
                    "chunk count unknown after a completed pass 0 — "
                    "loader geometry was not learned")
            order = epoch_chunk_order(config.seed, e, n_chunks)
        with _obs_spans.span("sdca/epoch", epoch=e):
            for chunk in loader.stream(start_chunk=pos0, order=order):
                cid = (chunk.chunk_id if chunk.chunk_id >= 0
                       else chunk.index)
                state, acc = progs.run_chunk(state, acc, chunk.batch,
                                             chunk.rows, e, cid, damping)
                # consumption token: acc's readiness implies the chunk's
                # reads are done, freeing its staging buffer
                loader.release(chunk, acc)
                if ckpt_on:
                    kill = chaos.should_kill_stream(e, chunk.index)
                    cadence = (checkpoint_every_chunks > 0
                               and (chunk.index + 1)
                               % checkpoint_every_chunks == 0)
                    if kill or cadence:
                        save_checkpoint(e, chunk.index + 1, state, acc,
                                        v_start)
                        if kill:
                            raise chaos.SimulatedKill(
                                f"chaos: killed SDCA at epoch {e}, chunk "
                                f"{chunk.index} (checkpoint written)")
            state, scal_dev = progs.finish_epoch(state, acc, v_start)
            # the ONE deliberate host crossing per epoch
            scal = np.asarray(scal_dev)
        primal, dual, gap, pred = (float(scal[0]), float(scal[1]),
                                   float(scal[2]), float(scal[3]))
        epochs_done = e + 1
        gap_history.append(gap)
        with _STATS_LOCK:
            _STATS["epochs"] += 1
        try:
            registry.gauge("sdca.duality_gap").set(gap)
            registry.counter("sdca.epochs").inc()
        except Exception:   # hygiene-ok — telemetry is best-effort
            pass
        if not (math.isfinite(primal) and math.isfinite(dual)
                and math.isfinite(gap)):
            failure = int(FailureMode.NON_FINITE_LOSS)
            reason = int(ConvergenceReason.OBJECTIVE_NOT_IMPROVING)
            break
        # bounded-staleness guard: the dual estimate is exactly one epoch
        # lagged, so epoch e's scalars realize epoch e-1's prediction
        if (prev_dual is not None and prev_pred is not None
                and math.isfinite(prev_pred) and prev_pred > tiny):
            realized = dual - prev_dual
            if realized < config.staleness_guard * prev_pred:
                damping = max(damping * 0.5, config.min_damping)
                run_fallbacks += 1
                _record_fallback(e, prev_pred, realized, damping)
        prev_dual, prev_pred = dual, pred
        if gap0 is None:
            gap0 = gap
        if on_epoch is not None:
            on_epoch(e, {"gap": gap, "primal": primal, "dual": dual,
                         "predicted": pred,
                         "coef": progs.coef_host(state)})
        if gap <= config.gap_tolerance * max(gap0, tiny):
            reason = int(ConvergenceReason.DUALITY_GAP_CONVERGED)
            break

    if checkpoint_path and os.path.exists(checkpoint_path):
        try:
            os.remove(checkpoint_path)
        except OSError:  # pragma: no cover — best-effort cleanup
            pass

    converged = reason == int(ConvergenceReason.DUALITY_GAP_CONVERGED)
    _record_run({"epochs": epochs_done, "gap": gap, "gap0": gap0,
                 "damping": damping, "reason": reason,
                 "converged": converged,
                 "fallbacks": run_fallbacks,
                 "loss": objective.loss.name}, converged)

    coef = progs.coef_host(state)
    return SolverResult(
        coef=jnp.asarray(coef, dt),
        value=jnp.asarray(primal, dt),
        gradient=jnp.zeros((d,), dt),
        iterations=jnp.asarray(epochs_done, jnp.int32),
        reason=jnp.asarray(reason, jnp.int32),
        num_fun_evals=jnp.asarray(epochs_done, jnp.int32),
        failure=jnp.asarray(failure, jnp.int32),
    )
