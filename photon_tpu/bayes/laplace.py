"""Post-fit diagonal-Hessian Laplace pass: posterior variances at the
optimum.

The Laplace approximation around a fitted GLM optimum gives a diagonal
Gaussian posterior ``theta_i ~ N(mu_i, 1 / (H_ii + lambda))`` where
``H_ii`` is the data term of the Hessian diagonal at the optimum (the
reference's SIMPLE variance semantics,
DistributedOptimizationProblem.computeVariances). The aggregator kernels
already form these diagonals (``ops/aggregators.hessian_diagonal``), so
the pass is pure reuse:

- **Fixed effect, streamed**: ``StreamedLaplace`` folds chunk after
  chunk from a ``data.streaming.ChunkLoader`` into a device-resident
  ``[dim]`` diagonal accumulator — the same carry/partial/finalize
  structure as ``optim/streaming.StreamedProblem``. On a mesh the carry
  stays SHARD-LOCAL ``[n_shards, dim]`` through the whole pass, the
  per-chunk partial contains NO collectives, and the finalize issues
  exactly one staged ICI-then-DCN psum. The single host crossing of the
  pass is the ``np.asarray`` pull of the finished variances.

- **Random effects, blocked**: ``entity_variances_blocked`` rides the
  PR 17 block-staging machinery — each size bucket's K entities are one
  staged device program (a vmap over the bucket's entity lanes, exactly
  the lane axis the flattened-lane solver batches over), with
  ``game/block_stream.BlockPrefetcher`` staging bucket b+1 while bucket
  b computes. Staging order and per-bucket programs are fixed, so two
  runs are bitwise identical.

Both entry points refuse losses without a Hessian (smoothed hinge is
first-order only in the reference too) with a typed ``ValueError``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.data.dataset import DataBatch
from photon_tpu.function.objective import GLMObjective, Hyper
from photon_tpu.ops import features as F
from photon_tpu.optim.base import jit_donating
from photon_tpu.utils import jitcache

Array = jax.Array

_NO_HESSIAN = ("Laplace variances need a twice-differentiable loss; "
               "{loss} has no Hessian (has_hessian=False) — the posterior "
               "is undefined under the reference's first-order treatment")


def _check_hessian(objective: GLMObjective) -> None:
    if not objective.loss.has_hessian:
        raise ValueError(_NO_HESSIAN.format(loss=type(objective.loss)))


def _variance_from_diag(diag: Array, l2: Array) -> Array:
    d = diag + l2
    return 1.0 / jnp.maximum(d, jnp.finfo(d.dtype).tiny)


class StreamedLaplace:
    """One streamed pass over a chunk store -> fixed-effect posterior
    variances ``1 / (H_ii + l2)`` at ``coef``.

    Mirrors ``optim/streaming.StreamedProblem``'s evaluation structure:
    a device-resident diagonal accumulator updated by one jitted partial
    per chunk (donated carry, zero host syncs, zero per-chunk
    collectives), finalized by a single program that — on a mesh —
    issues the pass's one staged ICI->DCN all-psum before adding the L2
    ridge and inverting.
    """

    def __init__(self, objective: GLMObjective, loader,
                 l2_weight: float = 0.0, dim: Optional[int] = None,
                 dtype=None):
        _check_hessian(objective)
        self.objective = objective
        self.loader = loader
        self.mesh = loader.mesh
        self.dim = int(dim if dim is not None else loader.source.dim)
        self.dtype = np.dtype(dtype if dtype is not None else loader.dtype)
        self.l2_weight = float(l2_weight)
        self._l2_dev = jnp.asarray(self.l2_weight, self.dtype)
        zero = Hyper(l2_weight=0.0)
        if self.mesh is None:
            self._partial = jit_donating(
                lambda carry, coef, batch: carry
                + objective.hessian_diagonal(coef, batch, zero),
                donate_argnums=(0,))
            self._finalize = jax.jit(_variance_from_diag)
        else:
            self._build_meshed()

    def _build_meshed(self):
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from photon_tpu.optim.hier import (
            _num_shards,
            _sample_axes,
            _staged_all_psum,
        )
        from photon_tpu.parallel import mesh as M

        mesh, obj = self.mesh, self.objective
        zero = Hyper(l2_weight=0.0)
        sample_axes = _sample_axes(mesh)
        self._n_shards = _num_shards(mesh, sample_axes)
        spec_axis = sample_axes if len(sample_axes) > 1 else sample_axes[0]
        carry_spec = P(spec_axis, None)
        self._carry_sharding = NamedSharding(mesh, carry_spec)

        def partial_body(cd, coef, batch):
            # shard-local accumulate: cd [1, dim] — NO collectives
            return (cd[0] + obj.hessian_diagonal(coef, batch, zero))[None]

        def finalize_body(cd, l2):
            # the pass's single reduction: one staged ICI-then-DCN psum
            diag = _staged_all_psum(cd[0], mesh)
            return _variance_from_diag(diag, l2)

        def partial(carry, coef, batch):
            specs = jax.tree.map(
                lambda a: P(spec_axis, *([None] * (a.ndim - 1))), batch)
            return M.shard_map(partial_body, mesh=mesh,
                               in_specs=(carry_spec, P(), specs),
                               out_specs=carry_spec,
                               check_vma=False)(carry, coef, batch)

        def finalize(carry, l2):
            return M.shard_map(finalize_body, mesh=mesh,
                               in_specs=(carry_spec, P()),
                               out_specs=P(),
                               check_vma=False)(carry, l2)

        self._partial = jit_donating(partial, donate_argnums=(0,))
        self._finalize = jax.jit(finalize)

    def init_carry(self):
        if self.mesh is None:
            return jnp.zeros((self.dim,), self.dtype)
        return jax.device_put(
            np.zeros((self._n_shards, self.dim), self.dtype),
            self._carry_sharding)

    def _put_coef(self, coef):
        if self.mesh is None:
            return jnp.asarray(coef, self.dtype)
        from photon_tpu.parallel import mesh as M
        return M.replicate(jnp.asarray(coef, self.dtype), self.mesh)

    def variances(self, coef) -> np.ndarray:
        """One full streamed pass -> host ``[dim]`` posterior variances.

        The chunk loop is pure async dispatch; the np.asarray pull of the
        finalized variances is the pass's single host crossing.
        """
        coef_dev = self._put_coef(coef)
        carry = self.init_carry()
        for chunk in self.loader.stream():
            carry = self._partial(carry, coef_dev, chunk.batch)
            # zero-copy consumption token: the new carry's readiness
            # implies this chunk's reads are done, freeing its buffer
            self.loader.release(chunk, carry)
        var_dev = self._finalize(carry, self._l2_dev)
        # pass boundary: the single deliberate sync — host-sync-ok
        return np.asarray(var_dev)


def fixed_effect_variances_streamed(objective: GLMObjective, loader, coef,
                                    l2_weight: float = 0.0,
                                    dim: Optional[int] = None,
                                    dtype=None) -> np.ndarray:
    """Convenience wrapper: build a :class:`StreamedLaplace` and run one
    pass at ``coef``."""
    return StreamedLaplace(objective, loader, l2_weight=l2_weight,
                           dim=dim, dtype=dtype).variances(coef)


# =========================================================================
# Random effects: blocked, lane-batched per-entity diagonals
# =========================================================================


def _block_variance_fn(coord):
    """The per-bucket diagonal program for one coordinate: a vmap over
    the bucket's K entity lanes of the SIMPLE per-entity variance,
    jitted once per bucket shape (the same compile economics as the
    bucket solvers). Cached on the coordinate's task like
    ``RandomEffectCoordinate._variance_fn``."""
    obj = coord.objective

    def build():
        def one(feat_idx, feat_val, labels, offsets, weights, coef, l2):
            batch = DataBatch(F.SparseFeatures(feat_idx, feat_val),
                              labels, offsets, weights)
            d = obj.hessian_diagonal(coef, batch, Hyper(l2_weight=0.0))
            var = _variance_from_diag(d, l2)
            has_data = jnp.sum(weights) > 0
            return jnp.where(has_data, var, 0.0)

        @jax.jit
        def var_block(blk, residual_rows, coefs_b, l2):
            offsets = blk.offsets
            if residual_rows is not None:
                offsets = offsets + residual_rows
            return jax.vmap(one, in_axes=(0, 0, 0, 0, 0, 0, None))(
                blk.features.indices, blk.features.values,
                blk.labels, offsets, blk.weights, coefs_b, l2)

        return var_block

    return jitcache.get_or_build(("bayes_re_var_block", coord.task), build)


def entity_variances_blocked(coord, coefficients,
                             residual_scores=None, *,
                             prefetch: bool = True) -> np.ndarray:
    """Blocked per-entity posterior variances for a
    ``RandomEffectCoordinate``: ``[E, K]`` with ``var[e, k] =
    1 / (H_kk(entity e) + l2)`` at the entity's fitted ``coefficients``
    row (zero rows for entities with no data — they have no posterior
    beyond the prior, matching ``_variance_fn``).

    Device memory holds ONE staged bucket at a time (+ one in flight
    when ``prefetch``): each size bucket's K entity lanes run as one
    vmapped program while ``BlockPrefetcher`` stages the next bucket,
    exactly the staging discipline of the blocked fits' one host loop
    (``RandomEffectCoordinate._solve_blocked``).
    Prefetching never changes bytes — staging order and per-bucket
    programs are fixed, so the result is bitwise run-to-run.
    """
    _check_hessian(coord.objective)
    ds = coord.dataset
    E_pad = ds.num_entities
    K = ds.projected_dim
    dtype = np.dtype(ds.blocks[0].labels.dtype) if ds.blocks \
        else np.dtype(np.float32)
    table = np.zeros((E_pad, K), dtype)
    w = np.asarray(coefficients, dtype)
    table[: min(E_pad, w.shape[0])] = w[:E_pad]
    lam = coord.config.regularization_weight
    l2 = jnp.asarray(coord.config.regularization.l2_weight(lam), dtype)
    out = np.zeros((E_pad, K), dtype)
    var_fn = _block_variance_fn(coord)
    res_flat = (None if residual_scores is None
                else jnp.asarray(residual_scores, dtype))
    n_blocks = len(ds.blocks)
    from photon_tpu.game.block_stream import BlockPrefetcher
    stream = None
    if prefetch and n_blocks > 1:
        stream = BlockPrefetcher(ds.blocks)
    try:
        for bi, blk in enumerate(ds.blocks):
            ents = np.asarray(blk.entity_rows)
            valid = (ents >= 0) & (ents < E_pad)
            x = np.zeros((ents.shape[0], K), dtype)
            x[valid] = table[ents[valid]]
            staged = stream.get(bi) if stream is not None else blk
            res_rows = None
            if res_flat is not None:
                res_rows = staged.rows_from_flat(res_flat)
            var_b = var_fn(staged, res_rows, jnp.asarray(x), l2)
            # the per-bucket host round-trip IS the design (cf.
            # RandomEffectCoordinate._solve_blocked): results land in
            # host RAM, device peak stays one bucket
            out[ents[valid]] = np.asarray(var_b)[valid]
            if stream is not None:
                stream.release()
    finally:
        if stream is not None:
            stream.close()
    return out[:coord._num_entities_orig]
