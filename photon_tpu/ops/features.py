"""Device-resident feature matrices: dense and padded-sparse (ELL) layouts.

The reference streams per-sample Breeze sparse vectors through Spark
closures. On TPU every batch is one static-shape array; sparse rows use a
padded ELL layout (``indices [n, k]``, ``values [n, k]``) with pad slots
pointing at column 0 with value 0, so no masking is ever needed:
pads contribute ``0 * theta[0]`` to margins and scatter ``+0`` into
gradients.

All four aggregator kernels (see ops/aggregators.py) reduce to three
primitives on this layout:

  * ``matvec(X, theta)        -> margins [n]``   (MXU-friendly when dense)
  * ``rmatvec(X, w, dim)      -> X^T w    [d]``  (segment-sum scatter when sparse)
  * ``sq_rmatvec(X, w, dim)   -> (X*X)^T w [d]`` (for Hessian diagonals)

plus ``weighted_gram`` for small-dimension full Hessians.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

Array = jax.Array


class SparseFeatures(NamedTuple):
    """Padded ELL rows: ``indices[i, j]`` / ``values[i, j]`` is the j-th
    nonzero of sample i; pad slots are ``(0, 0.0)``."""

    indices: Array  # [n, k] int32
    values: Array   # [n, k] float


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ModelShardedSparse:
    """Feature-range-partitioned ELL rows for model-parallel sparse theta.

    The TPU answer to the reference's partitioned PalDB feature indexes
    (PalDBIndexMap.scala:43) feeding "hundreds of billions of coefficients"
    (README.md:56): theta is range-sharded over the mesh's model axis, and
    each sample's nonzeros are pre-partitioned AT INGEST into one ELL block
    per range with LOCAL column ids. On device, margins are per-shard
    gather-dots psum-ed over the model axis, and gradients are per-shard
    local scatters psum-ed over the data axis — no nonzero ever crosses a
    chip boundary after ingest (SURVEY §5.7's "moral equivalent of sequence
    parallelism").

    ``indices``/``values`` are ``[P, n, kp]`` with ``indices[p, i, j]`` the
    LOCAL id (global id − p·shard_size) of the j-th in-range nonzero of
    sample i; pad slots are ``(0, 0.0)``. Placement: ``P(model, data)``.

    The ELL view serves ``matvec`` (contiguous gather-dot over rows). For
    the transposed products a second, column-sorted view of the SAME
    nonzeros is precomputed at ingest (``build_csc_plan``): per
    (model-shard, data-chunk) block, ``csc_rows``/``csc_vals`` hold the
    real nonzeros sorted by local column, and ``csc_ptr`` the column
    boundaries, so ``rmatvec``/``sq_rmatvec`` become contiguous segment
    reductions instead of serialized random scatter-adds (measured ~30x
    per-pass on the CPU backend at bench shapes). When the CSC view is
    absent (None) the kernels fall back to the original ``at[].add``
    scatter — tests pin the two paths against each other.

    ``dcn_axis`` (optional) names a cross-slice axis of a two-level
    (dcn, data, model) mesh: the sample dim is then sharded over
    ``(dcn, data)`` and gradient reductions are staged ICI-then-DCN
    (parallel/mesh.staged_psum as mesh layout).
    """

    indices: Array  # [P, n, kp] int32, local ids
    values: Array   # [P, n, kp]
    shard_size: int = dataclasses.field(metadata=dict(static=True))
    mesh: jax.sharding.Mesh = dataclasses.field(metadata=dict(static=True))
    data_axis: str = dataclasses.field(default="data",
                                       metadata=dict(static=True))
    model_axis: str = dataclasses.field(default="model",
                                        metadata=dict(static=True))
    # column-sorted view of the same nonzeros, per (shard, data-chunk)
    csc_rows: Optional[Array] = None   # [P, C, m] int32, chunk-local rows
    csc_vals: Optional[Array] = None   # [P, C, m]
    csc_ptr: Optional[Array] = None    # [P, C, shard_size + 1] int32
    dcn_axis: Optional[str] = dataclasses.field(
        default=None, metadata=dict(static=True))

    @property
    def padded_dim(self) -> int:
        return self.indices.shape[0] * self.shard_size

    @property
    def shape(self):  # (n, d_padded) by analogy with a dense matrix
        return (self.values.shape[1], self.padded_dim)


FeatureMatrix = Union[Array, SparseFeatures, ModelShardedSparse]


def num_samples(x: FeatureMatrix) -> int:
    if isinstance(x, ModelShardedSparse):
        return x.values.shape[1]
    return (x.values if isinstance(x, SparseFeatures) else x).shape[0]


def _ms_specs(x: ModelShardedSparse):
    # sample dims shard over (dcn, data) on a two-level mesh, data otherwise
    sample = ((x.dcn_axis, x.data_axis) if x.dcn_axis is not None
              else x.data_axis)
    ell = PartitionSpec(x.model_axis, sample, None)
    return ell, PartitionSpec(x.model_axis), PartitionSpec(sample)


def _ms_data_psum(x: ModelShardedSparse, g: Array) -> Array:
    """Gradient-shard reduction over the sample axes. On a two-level mesh
    this is the staged all-reduce (parallel/mesh.staged_psum, inlined to
    avoid the circular import): within-slice ICI first, one DCN crossing
    after — the reference's treeAggregateDepth>1 as collective structure."""
    g = jax.lax.psum(g, x.data_axis)
    if x.dcn_axis is not None:
        g = jax.lax.psum(g, x.dcn_axis)
    return g


def matvec(x: FeatureMatrix, theta: Array) -> Array:
    """Per-sample margins ``X @ theta`` -> [n]."""
    if isinstance(x, ModelShardedSparse):
        ell, model_vec, data_vec = _ms_specs(x)

        def f(idx, val, th):
            # idx/val [1, n_local, kp]; th [shard_size] = this chip's range.
            # Local ids are constructed in-range at ingest (pads point at
            # 0), so the gather plan is static and unchecked — no clamp or
            # fill lowering on the hot path.
            gathered = th.at[idx[0]].get(mode="promise_in_bounds")
            part = jnp.sum(val[0] * gathered, axis=-1)
            return jax.lax.psum(part, x.model_axis)

        return jax.shard_map(f, mesh=x.mesh,
                             in_specs=(ell, ell, model_vec),
                             out_specs=data_vec)(x.indices, x.values, theta)
    if isinstance(x, SparseFeatures):
        return jnp.sum(x.values * theta[x.indices], axis=-1)
    return x @ theta


def matvec_lanes(x: FeatureMatrix, thetas: Array) -> Array:
    """Stacked margins for K coefficient lanes: ``thetas [K, d] -> [K, n]``.

    The lane-batched data pass of the sweep path (optim/batched): dense
    rows contract as ONE ``Θ Xᵀ`` dot_general (contracting over d — no
    materialized transpose of the big matrix, same strided-path concern
    as ``rmatvec``'s ``w @ x``), and sparse ELL rows as ONE stacked
    gather over the shared ``x.indices`` plan — the batch is read once
    regardless of K. Model-sharded layouts train one model per mesh and
    are refused typed (sweep lanes would multiply the sharded theta
    footprint K-fold). The precision is stated: two matrix operands make
    this a real dot, which a TPU would otherwise run as ONE bfloat16 pass
    (optim/batched.LANE_MATMUL_PRECISION says the same of the lane solvers).
    """
    if isinstance(x, ModelShardedSparse):
        raise NotImplementedError(
            "matvec_lanes does not support ModelShardedSparse features — "
            "lane-batched sweeps hold K full coefficient vectors, which "
            "contradicts a theta range-sharded over the model axis")
    if isinstance(x, SparseFeatures):
        gathered = jnp.take(thetas, x.indices, axis=1)   # [K, n, k]
        return jnp.sum(x.values[None, :, :] * gathered, axis=-1)
    return jnp.einsum("kd,nd->kn", thetas, x,
                      precision=jax.lax.Precision.HIGHEST)


def _ms_scatter(x: ModelShardedSparse, w: Array, square: bool) -> Array:
    """Shared shard_map scatter for X^T w / (X*X)^T w on the model-sharded
    layout: local scatters into this chip's theta range, psum over data.

    Fallback path for structs ingested without a CSC plan; the packed
    ``_ms_segment_reduce`` below replaces it on the hot path."""
    ell, model_vec, data_vec = _ms_specs(x)
    shard_size = x.shard_size

    def f(idx, val, wl):
        if square:
            # promote BEFORE squaring: bf16 storage must not round the
            # squared Hessian terms at storage precision
            v0 = val[0].astype(wl.dtype)
            v = v0 * v0
        else:
            v = val[0]
        contrib = (v * wl[:, None]).ravel()
        g = jnp.zeros((shard_size,), dtype=contrib.dtype)
        g = g.at[idx[0].ravel()].add(contrib)
        return _ms_data_psum(x, g)

    return jax.shard_map(f, mesh=x.mesh,
                         in_specs=(ell, ell, data_vec),
                         out_specs=model_vec)(x.indices, x.values, w)


def _ms_segment_reduce(x: ModelShardedSparse, w: Array, square: bool) -> Array:
    """X^T w / (X*X)^T w as a contiguous segment reduction over the
    column-sorted CSC view: gather w by row, prefix-sum in sorted order,
    difference at the precomputed column boundaries. Equivalent to a
    sorted ``segment_sum`` but lowering to two contiguous passes instead
    of per-segment bookkeeping (measured ~5x over segment_sum and ~30x
    over the serialized scatter-add on the CPU backend at bench shapes).
    Pad entries carry value 0 at row 0 and sit past every column's end
    pointer, so they vanish from both the gather-product and the
    boundary differences."""
    sample = ((x.dcn_axis, x.data_axis) if x.dcn_axis is not None
              else x.data_axis)
    csc = PartitionSpec(x.model_axis, sample, None)
    model_vec = PartitionSpec(x.model_axis)
    data_vec = PartitionSpec(sample)

    def f(rows, vals, ptr, wl):
        # rows/vals [1, 1, m] (this chip's block), ptr [1, 1, S+1],
        # wl [n_local] = this chip's slice of the per-sample weights
        v = vals[0, 0]
        if square:
            v0 = v.astype(wl.dtype)  # promote BEFORE squaring (see above)
            v = v0 * v0
        wg = wl.at[rows[0, 0]].get(mode="promise_in_bounds")
        cs = jnp.cumsum((v * wg).astype(wl.dtype))
        z = jnp.concatenate([jnp.zeros((1,), cs.dtype), cs])
        p = ptr[0, 0]
        g = (z.at[p[1:]].get(mode="promise_in_bounds")
             - z.at[p[:-1]].get(mode="promise_in_bounds"))
        return _ms_data_psum(x, g)

    return jax.shard_map(f, mesh=x.mesh,
                         in_specs=(csc, csc, csc, data_vec),
                         out_specs=model_vec)(x.csc_rows, x.csc_vals,
                                              x.csc_ptr, w)


def rmatvec(x: FeatureMatrix, w: Array, dim: int) -> Array:
    """``X^T w`` -> [d]; ``w`` is a per-sample weight vector [n]."""
    if isinstance(x, ModelShardedSparse):
        if x.csc_ptr is not None:
            return _ms_segment_reduce(x, w, square=False)
        return _ms_scatter(x, w, square=False)
    if isinstance(x, SparseFeatures):
        contrib = (x.values * w[:, None]).ravel()
        return jnp.zeros((dim,), dtype=contrib.dtype).at[x.indices.ravel()].add(contrib)
    # w @ X, not X.T @ w: algebraically identical, but the explicit
    # transpose forces XLA-CPU through a strided 0.1 GFLOP/s path
    # (measured 33x slower at 200k x 512); on TPU both lower to the same
    # MXU contraction
    return w @ x


def sq_rmatvec(x: FeatureMatrix, w: Array, dim: int) -> Array:
    """``(X * X)^T w`` -> [d] (elementwise square), for Hessian diagonals.
    Values promote to the weight dtype BEFORE squaring so narrow feature
    storage (bf16) doesn't round the squared Hessian terms."""
    if isinstance(x, ModelShardedSparse):
        if x.csc_ptr is not None:
            return _ms_segment_reduce(x, w, square=True)
        return _ms_scatter(x, w, square=True)
    if isinstance(x, SparseFeatures):
        v = x.values.astype(w.dtype)
        contrib = (v * v * w[:, None]).ravel()
        return jnp.zeros((dim,), dtype=contrib.dtype).at[x.indices.ravel()].add(contrib)
    xf = x.astype(w.dtype)
    return w @ (xf * xf)  # see rmatvec: avoid XLA-CPU's strided .T path


def weighted_gram(x: FeatureMatrix, w: Array, dim: int,
                  precision=jax.lax.Precision.DEFAULT,
                  block_rows: Optional[int] = None) -> Array:
    """``X^T diag(w) X`` -> [d, d], for small-dim full Hessians
    (reference: HessianMatrixAggregator.scala:31). ``precision`` and
    ``block_rows`` are the dense contraction's: DEFAULT and ONE full product
    over all rows for the callers to whom the Hessian is a means (below);
    stated otherwise by the one whose RESULT it is (``optim/problem.py::
    coefficient_variances``: ``_upper_gram_in_row_blocks``, ``gram_route``)."""
    if isinstance(x, ModelShardedSparse):
        raise NotImplementedError(
            "model-sharded sparse theta is matrix-free by design: a d x d "
            "Hessian would defeat the point of sharding theta")
    if isinstance(x, SparseFeatures):
        n, k = x.indices.shape
        if k <= 64:
            # per-slot scatter of the outer product: k scatters whose
            # temporaries are [n, k] — the same footprint as the data —
            # never an [n, dim] densification nor [n, k, k] blow-up
            # (the explicit-Hessian TRON path calls this per entity
            # under vmap, where big temps would dwarf the block)
            wv = w[:, None] * x.values                           # [n, k]
            h = jnp.zeros((dim, dim), wv.dtype)
            for j in range(k):  # k is a static ELL width, loop unrolls
                h = h.at[x.indices[:, j][:, None], x.indices].add(
                    wv[:, j][:, None] * x.values)
            return h
        dense = to_dense(x, dim)
        return jnp.matmul(dense.T, dense * w[:, None], precision=precision)
    if gram_route(x, block_rows) == "dense_upper":
        return _upper_gram_in_row_blocks(x, w, precision, block_rows)
    # DEFAULT, stated: on a TPU ONE bfloat16 pass of the MXU. The Hessian's
    # callers (NEWTON, TRON) take their gradient exactly, so an inexact
    # Hessian moves iteration counts, not the optimum, and at DEFAULT it
    # moved none: epsilon's TRON fit is 5 iterations / 8 CG steps at
    # DEFAULT, HIGH and HIGHEST alike, for 25.9 / 69.0 / 141.1 ms a build
    # at 530,000 x 2,000 (PERF.md §5, my chip runs, PR 33)
    return jnp.matmul(x.T, x * w[:, None], precision=precision)


def to_dense(x: FeatureMatrix, dim: int) -> Array:
    if isinstance(x, ModelShardedSparse):
        raise NotImplementedError("refusing to densify model-sharded features")
    if isinstance(x, SparseFeatures):
        n, k = x.indices.shape
        out = jnp.zeros((n, dim), dtype=x.values.dtype)
        rows = jnp.broadcast_to(jnp.arange(n)[:, None], (n, k))
        return out.at[rows.ravel(), x.indices.ravel()].add(x.values.ravel())
    return x


def partition_by_feature_range(
    sf: SparseFeatures, dim: int, n_shards: int, pad_multiple: int = 1
) -> tuple:
    """Host-side ingest step for model-parallel sparse training: split each
    ELL row's nonzeros into ``n_shards`` contiguous feature ranges with
    LOCAL column ids (the reference's partitioned-PalDB layout,
    PalDBIndexMapBuilder.scala:27, re-done as static arrays).

    Returns ``(indices [P, n, kp], values [P, n, kp], shard_size)`` as
    numpy arrays; kp is the worst-case per-(row, range) nonzero count,
    padded to ``pad_multiple``. Index maps that hash feature names over the
    id space keep ranges load-balanced — partitioning is by id range, the
    hashing already happened at index build.
    """
    idx = np.asarray(sf.indices)
    val = np.asarray(sf.values)
    n, k = idx.shape
    shard_size = -(-dim // n_shards)  # ceil
    if k == 0 or n == 0:
        kp = max(pad_multiple, 1)
        return (np.zeros((n_shards, n, kp), np.int32),
                np.zeros((n_shards, n, kp), val.dtype), shard_size)
    shard_of = idx // shard_size                       # [n, k]
    # ELL pad slots (value 0) must not inflate kp: route them to a virtual
    # shard n_shards, which sorts last and is truncated after scatter
    shard_of = np.where(val == 0, n_shards, shard_of)
    order = np.argsort(shard_of, axis=1, kind="stable")
    shard_sorted = np.take_along_axis(shard_of, order, 1)
    idx_sorted = np.take_along_axis(idx, order, 1)
    val_sorted = np.take_along_axis(val, order, 1)
    js = np.broadcast_to(np.arange(k), (n, k))
    new_group = np.concatenate(
        [np.ones((n, 1), bool), shard_sorted[:, 1:] != shard_sorted[:, :-1]], 1)
    group_start = np.maximum.accumulate(np.where(new_group, js, 0), axis=1)
    pos = js - group_start                             # slot within (row, range)
    real = shard_sorted < n_shards
    kp = int(pos[real].max()) + 1 if real.any() else 1
    kp = -(-kp // pad_multiple) * pad_multiple
    out_idx = np.zeros((n_shards + 1, n, max(kp, int(pos.max()) + 1)), np.int32)
    out_val = np.zeros_like(out_idx, dtype=val.dtype)
    rows = np.broadcast_to(np.arange(n)[:, None], (n, k))
    out_idx[shard_sorted, rows, pos] = idx_sorted - shard_sorted * shard_size
    out_val[shard_sorted, rows, pos] = val_sorted
    # drop the virtual pad shard and the slots only it used
    return (np.ascontiguousarray(out_idx[:n_shards, :, :kp]),
            np.ascontiguousarray(out_val[:n_shards, :, :kp]), shard_size)


def build_csc_plan(
    sf: SparseFeatures, dim: int, n_shards: int, n_chunks: int
) -> tuple:
    """Host-side companion of ``partition_by_feature_range``: the SAME
    nonzeros re-laid-out column-sorted per (model-shard, data-chunk)
    block, so the transposed products run as contiguous segment
    reductions on device (``_ms_segment_reduce``).

    Chunk c covers rows [c·n/C, (c+1)·n/C) — the contiguous row block a
    (dcn, data) device slice owns. Returns numpy arrays
    ``(rows [P, C, m], vals [P, C, m], ptr [P, C, S+1])`` where ``m`` is
    the worst-case per-block real-nonzero count, ``rows`` are chunk-LOCAL
    sample ids sorted by shard-LOCAL column within each block, and
    ``ptr[p, c, j]`` is the first sorted slot of local column j (ptr[S] =
    the block's real count). Pad slots hold (row 0, value 0) past every
    column's end — inert in both the gather-product and the boundary
    differences. ELL pad slots (value 0) are excluded entirely."""
    idx = np.asarray(sf.indices)
    val = np.asarray(sf.values)
    n, k = idx.shape
    shard_size = -(-dim // n_shards)
    if n % n_chunks:
        raise ValueError(f"sample count {n} must divide into {n_chunks} "
                         "data chunks; pad the batch first")
    n_loc = n // n_chunks
    if n == 0 or k == 0:
        return (np.zeros((n_shards, n_chunks, 1), np.int32),
                np.zeros((n_shards, n_chunks, 1), val.dtype),
                np.zeros((n_shards, n_chunks, shard_size + 1), np.int32))
    real = val.ravel() != 0
    flat_idx = idx.ravel()[real].astype(np.int64)
    rows_g = np.broadcast_to(np.arange(n)[:, None], (n, k)).ravel()[real]
    vals_f = val.ravel()[real]
    shard_of = flat_idx // shard_size
    chunk_of = rows_g // n_loc
    local_col = flat_idx - shard_of * shard_size
    # single sort key: (shard, chunk, local column) — one lexsort pass
    key = (shard_of * n_chunks + chunk_of) * shard_size + local_col
    order = np.argsort(key, kind="stable")
    key_s = key[order]
    rows_s = (rows_g[order] - chunk_of[order] * n_loc).astype(np.int32)
    vals_s = vals_f[order]
    # column boundaries per block from one bincount over the full key
    # space; block sizes from its per-block reduction
    counts = np.bincount(key_s, minlength=n_shards * n_chunks * shard_size)
    counts = counts.reshape(n_shards, n_chunks, shard_size)
    block_sizes = counts.sum(axis=-1)
    m = max(int(block_sizes.max()), 1)
    ptr = np.zeros((n_shards, n_chunks, shard_size + 1), np.int32)
    np.cumsum(counts, axis=-1, out=ptr[:, :, 1:])
    # scatter sorted entries into fixed-width blocks
    block_of = key_s // shard_size            # flat (shard, chunk) id
    starts = np.zeros(n_shards * n_chunks + 1, np.int64)
    np.cumsum(block_sizes.ravel(), out=starts[1:])
    pos = np.arange(key_s.size) - starts[block_of]
    rows_out = np.zeros((n_shards, n_chunks, m), np.int32)
    vals_out = np.zeros((n_shards, n_chunks, m), val.dtype)
    p_i, c_i = block_of // n_chunks, block_of % n_chunks
    rows_out[p_i, c_i, pos] = rows_s
    vals_out[p_i, c_i, pos] = vals_s
    return rows_out, vals_out, ptr


def from_csr_arrays(indptr, cols, vals, max_nnz: int | None = None,
                    dtype=np.float32) -> SparseFeatures:
    """Host-side: raw CSR arrays -> padded ELL (vectorized; the zero-copy
    variant of from_scipy_csr for the native columnar ingest)."""
    indptr = np.asarray(indptr, np.int64)
    n = len(indptr) - 1
    row_nnz = np.diff(indptr)
    widest = int(row_nnz.max()) if n else 0
    k = int(max_nnz) if max_nnz is not None else widest
    if widest > k:
        raise ValueError(f"row has {widest} nonzeros > max_nnz={k}; "
                         "refusing to silently truncate features")
    indices = np.zeros((n, k), dtype=np.int32)
    values = np.zeros((n, k), dtype=dtype)
    if n and k:
        slot = np.arange(k)[None, :]
        mask = slot < row_nnz[:, None]
        src = indptr[:-1, None] + slot
        indices[mask] = np.asarray(cols)[src[mask]]
        values[mask] = np.asarray(vals)[src[mask]]
    return SparseFeatures(indices=jnp.asarray(indices),
                          values=jnp.asarray(values))


def from_scipy_csr(csr, max_nnz: int | None = None, dtype=np.float32) -> SparseFeatures:
    """Host-side: scipy CSR -> padded ELL arrays (vectorized).

    ``max_nnz`` pads/clips the row width; rows with more nonzeros than
    ``max_nnz`` are rejected — silent feature truncation would corrupt
    margins. Callers that want capping must subsample explicitly.
    """
    csr = csr.tocsr()
    n = csr.shape[0]
    row_nnz = np.diff(csr.indptr)
    widest = int(row_nnz.max()) if n else 0
    k = int(max_nnz) if max_nnz is not None else widest
    if widest > k:
        raise ValueError(f"row has {widest} nonzeros > max_nnz={k}; "
                         "refusing to silently truncate features")
    indices = np.zeros((n, k), dtype=np.int32)
    values = np.zeros((n, k), dtype=dtype)
    if n and k:
        cols = np.arange(k)[None, :]
        mask = cols < row_nnz[:, None]
        src = csr.indptr[:-1, None] + cols
        indices[mask] = csr.indices[src[mask]]
        values[mask] = csr.data[src[mask]]
    return SparseFeatures(indices=jnp.asarray(indices), values=jnp.asarray(values))


def from_rows(rows, dim: int, dtype=np.float32, max_nnz: int | None = None) -> SparseFeatures:
    """Host-side: list of (indices, values) pairs -> padded ELL arrays."""
    n = len(rows)
    widest = max((len(r[0]) for r in rows), default=0)
    k = max_nnz if max_nnz is not None else widest
    if widest > k:
        raise ValueError(f"row has {widest} nonzeros > max_nnz={k}; "
                         "refusing to silently truncate features")
    indices = np.zeros((n, k), dtype=np.int32)
    values = np.zeros((n, k), dtype=dtype)
    for i, (idx, val) in enumerate(rows):
        m = len(idx)
        indices[i, :m] = np.asarray(idx, dtype=np.int32)
        values[i, :m] = np.asarray(val, dtype=dtype)
    del dim  # shape is carried by coefficient vectors, not the ELL arrays
    return SparseFeatures(indices=jnp.asarray(indices), values=jnp.asarray(values))


# --------------------------------------------------------------------------
# the dense Gram summed in row blocks (PR 40), its upper triangle only (PR 41)
# --------------------------------------------------------------------------

# Columns a block of the upper triangle (a multiple of 128: X is stored
# rows-major at whole 128-lane tiles, so a block's edge is a tile's). Chosen
# from PERF.md §5's table (my chip runs, PR 41; ms a Gram at 530,000 x 2,000,
# seconds to compile the FULL variance program cold): 512 92.0 / 14.4-18.3,
# 256 91.0 / 15.4, 128 117.4 / 18.1, the full product 149.1 / 13.4), as
# ``optim/problem.py::VARIANCE_GRAM_BLOCK_ROWS`` was; no function takes it
# and no configuration sets it.
GRAM_COLUMN_BLOCK = 256


def gram_route(x: FeatureMatrix, block_rows: Optional[int]) -> str:
    """How ``weighted_gram(x, w, dim, precision, block_rows)`` takes the
    product, from what it can see: ``sparse`` (scatter-adds), ``dense_upper``
    (dense and summed in row blocks, that is ``block_rows`` set and exceeded:
    the upper triangle's column blocks, mirrored once), else ``dense`` (ONE
    full product over all rows: NEWTON, TRON, DIRECT, a mesh, ``vmap``'s
    per-entity widths). The Gram and the counter
    ``kernels.variance_gram{path}`` both ask here."""
    if isinstance(x, (SparseFeatures, ModelShardedSparse)):
        return "sparse"
    if block_rows is not None and x.shape[0] > block_rows:
        return "dense_upper"
    return "dense"


def _upper_gram_in_row_blocks(x: Array, w: Array, precision,
                              block_rows: int) -> Array:
    """The dense ``X^T diag(w) X`` as a float32 SUM over blocks of
    ``block_rows`` rows, its UPPER triangle only.

    Why row blocks: one contraction over n rows adds them into its float32
    accumulator one after another, which rounds the sum by some ``sqrt(n) x
    3.5e-8`` of itself whatever the products' precision: at 530,000 x 2,000
    the variances from a HIGHEST Gram read 2.6e-5 off a float64 oracle in
    one contraction, nearly what ONE bfloat16 pass costs (3.3e-5), and 5e-7
    in blocks of 8,192 rows, for 7% more time (PERF.md §5, my chip runs,
    PR 40). The variances' exactness rests on the row blocks and on the
    precision, so both stayed and the speed comes from the work.

    Why the upper triangle: the product is symmetric. The columns are cut
    into blocks of ``GRAM_COLUMN_BLOCK`` (the last one ragged) and a row
    block contributes, for column block I, ONE contraction ``xb[:, I].T @
    (xb[:, I's first column:] * wb)``: the block pairs (I, J), I <= J, side
    by side in one ``[c, width - start]`` strip with its own float32
    accumulator, the weights on one operand as ever. Entry (p, q), p <= q,
    is the very products the full contraction forms for it, summed over the
    same rows: ``(width^2 + sum c_i^2) / (2 width^2)`` of the
    multiply-adds, 56.3% at 2,000 columns. A matrix within one column block
    makes one strip, the full product. The lower triangle is filled ONCE,
    after the last row block, by mirroring, so the result is EXACTLY
    symmetric, which the full product's is not.

    The rows over the last whole block ride in one more trip of the SAME
    loop: its slice starts ``block_rows`` before the end and the rows the
    trip before summed weigh nothing (exact zeros into the sum). A second
    set of contractions for them outside the loop cost a cold first fit
    7.5 s of compiling where this costs 2.0, for 0.6 ms a Gram (PERF.md §5,
    my chip runs, PR 41)."""
    n, width = x.shape
    starts = range(0, width, GRAM_COLUMN_BLOCK)

    def add_block(i, strips):
        at = jnp.minimum(i * block_rows, n - block_rows)
        xb = jax.lax.dynamic_slice_in_dim(x, at, block_rows)
        wb = jax.lax.dynamic_slice_in_dim(w, at, block_rows)
        wb = jnp.where(at + jnp.arange(block_rows) >= i * block_rows, wb, 0)
        return [strip + jnp.matmul(xb[:, s:s + GRAM_COLUMN_BLOCK].T,
                                   xb[:, s:] * wb[:, None],
                                   precision=precision)
                for s, strip in zip(starts, strips)]

    dtype = jnp.result_type(x, w)
    strips = jax.lax.fori_loop(
        0, -(-n // block_rows), add_block,
        [jnp.zeros((min(GRAM_COLUMN_BLOCK, width - s), width - s), dtype)
         for s in starts])
    # each strip back at its columns: block upper triangular, the diagonal
    # blocks whole; below the diagonal the mirror image of what is above
    h = jnp.concatenate([jnp.pad(strip, ((0, 0), (s, 0)))
                         for s, strip in zip(starts, strips)])
    upper = (jax.lax.broadcasted_iota(jnp.int32, h.shape, 0)
             <= jax.lax.broadcasted_iota(jnp.int32, h.shape, 1))
    return jnp.where(upper, h, h.T)
