"""Pallas TPU kernels: fused GLM value+gradient in ONE pass over X.

The XLA path (ops/aggregators.py value_and_gradient) lowers to two
separate contractions over the feature matrix — ``margins = X @ coef``
and ``grad = X^T (w * dz)`` — so every objective evaluation streams X
from HBM twice. A GLM solve at fixed-effect shapes is HBM-bandwidth-
bound, which makes the second pass pure waste: dz depends only on each
row's own margin, so the gradient contraction can consume the SAME
VMEM-resident tile of X that just produced the margins.

Layout, the same in all three kernels: SAMPLES RUN ALONG THE LANES.
Per-sample vectors (labels, offsets, weights, margins) are lane-dense
``[1, T]`` rows, the coefficient vector and the gradient are ``[8, D]``
blocks of identical rows (a matmul is fed no fewer than one sublane tile
of LHS rows; row 0 is the answer), and the scalar loss accumulates in
SMEM. No block has a lane dimension of 1 and no contraction runs over a
transposed left operand.

``fused_dense_value_grad`` tiles X over rows; per grid step it computes
``m = coef . X_tile^T`` (MXU), the pointwise loss/dz (VPU), and
accumulates ``value += sum(w*l)`` and ``grad += (w*dz) . X_tile`` (MXU)
into carried output blocks — X is read from HBM exactly once.
Theoretical ceiling vs the XLA path on a bandwidth-bound solve: 2x.

``fused_sparse_value_grad`` extends the same structure to padded-ELL
sparse rows: each grid step reads one slot-major ``[K, T]`` tile of the
nnz stream (indices + values), expands it into a VMEM-resident dense
``[D, T]`` tile via a static-K unrolled one-hot accumulation (a row
iota compared against one sublane-broadcast slot row at a time — never
touches HBM), then runs the identical margins/loss/grad flow on that
tile. The XLA sparse arm instead gathers theta for margins and
scatter-adds the gradient — two passes over the nnz stream plus a
serialized scatter. The slot-major view is one XLA transpose of the nnz
stream outside the kernel. The VMEM tile bounds the supported
coefficient dimension (``_MAX_SPARSE_DIM``); larger models stay on the
CSC segment-sum path.

Scope: identity normalization, f32 coefficients, dense f32/bf16 or
ELL-sparse features. Callers opt in via ``PHOTON_TPU_PALLAS_GLM=1``
(see ops/aggregators.py). On a TPU the kernels are compiled by Mosaic
and a kernel that cannot compile raises; on every other backend they
run in interpret mode, which is what pins them to the XLA path in
tests/test_pallas_glm.py. ``chip_smoke.py`` compiles each of them on
the chip and holds it to a float64 oracle.

Reference semantics: ValueAndGradientAggregator.scala:36-80 (the same
fused margin/loss/grad algebra, minus the normalization prefactors).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

_LANES = 128                 # last-dim tile of every TPU vector layout
_MXU_ROWS = 8                # fewest LHS rows a matmul is fed (one f32 sublane tile)
_TILE_N = 1024
_TILE_N_SPARSE = 128
_TILE_B_SERVING = 128
# A v5e core's scoped VMEM is 16 MiB. The pipeline double-buffers the X
# tile and the two full-f32 contractions split it into bf16 pieces: the
# compiler's scoped allocation measured 6.3x the tile (25.1 MiB at a
# 4 MiB tile, refused), so one buffer is capped at 2 MiB. The row tile
# shrinks as d grows; past _MAX_DENSE_DIM even a 128-row tile overflows
# the cap.
_X_TILE_BYTES = 2 << 20
_MAX_DENSE_DIM = _X_TILE_BYTES // (_LANES * 4)        # 4096
# the sparse/serving kernels expand a [D, T] f32 tile in VMEM: at
# T = 128 that is 4096 x 128 x 4B = 2 MiB of scratch, built and consumed
# in _D_CHUNK-row pieces so the compare/select and contraction
# temporaries stay a fraction of it
_MAX_SPARSE_DIM = 4096
_D_CHUNK = 512
# Mosaic's default contraction rounds f32 operands to bf16 (one MXU pass):
# measured on a v5e, the fused gradient then sits 2e-3 (relative) from a
# float64 oracle where the XLA path sits at 7e-7. The kernels are bound by
# HBM, not the MXU, so they ask for full f32 contractions.
_F32 = jax.lax.Precision.HIGHEST

# trace-time kill switch: pallas_call carries no sharding annotations, so
# a mesh-sharded SPMD solve must never pick the kernel up (it would force
# replication of X or fail at lowering). GlmOptimizationProblem wraps
# mesh solves in ``disabled()``; the flag is a ContextVar so it binds at
# TRACE time, exactly like the env flag it refines.
_TRACE_DISABLED = contextvars.ContextVar("pallas_glm_disabled",
                                         default=False)


@contextlib.contextmanager
def disabled():
    token = _TRACE_DISABLED.set(True)
    try:
        yield
    finally:
        _TRACE_DISABLED.reset(token)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _batched(*arrays) -> bool:
    """True under vmap. jax exports no public name for the batching
    tracer, so the class is recognised by name."""
    return any(type(a).__name__ == "BatchTracer" for a in arrays)


def _row(a: Array, n_pad: int) -> Array:
    """[n] per-sample vector -> lane-dense [1, n_pad] row, zero-padded."""
    return jnp.pad(a, (0, n_pad - a.shape[0])).reshape(1, n_pad)


def _lhs_rows(v: Array) -> Array:
    """[1, w] row -> [_MXU_ROWS, w] of identical rows, the LHS of a
    row-vector matmul; row 0 of the product is the answer."""
    return jnp.broadcast_to(v, (_MXU_ROWS, v.shape[1]))


def _coef_lhs(coef: Array, limit: int, kernel: str) -> Array:
    """[d] coefficients -> the kernels' [_MXU_ROWS, d_pad] LHS block,
    zero-padded to a multiple of 128 lanes; refuses a dimension whose
    VMEM tile cannot fit."""
    d = coef.shape[0]
    d_pad = _round_up(d, _LANES)
    if d_pad > limit:
        raise ValueError(
            f"fused {kernel} kernel holds a tile of {d_pad} f32 "
            f"coefficient columns in VMEM and supports d <= {limit}")
    coef = jnp.pad(jnp.asarray(coef, jnp.float32), (0, d_pad - d))
    return _lhs_rows(coef.reshape(1, d_pad))


def _default_interpret() -> bool:
    # compiled by Mosaic on a TPU, and only there: a kernel that cannot
    # compile raises — it never interprets on the chip. Every other
    # backend gets exact interpret-mode semantics.
    return jax.default_backend() != "tpu"


def _supported(x, norm, coef) -> bool:
    """Dense 2D f32/bf16 features AND f32 coefficients, identity
    normalization, a feature dimension whose 128-row tile fits the VMEM
    cap, NOT under vmap, NOT inside a ``disabled()`` (mesh) region. The
    vmap exclusion: the kernel's sequential-grid accumulation (init on
    program_id 0, += into a revisited output block) assumes it owns the
    whole grid, which a batching transform breaks (the random-effect
    path vmaps the objective over dense-local entity blocks). The
    coef-dtype exclusion: an f64 solve over f32 features promotes in the
    XLA path, while the kernel would silently return f32 and break the
    while_loop carry dtype at trace time."""
    if _TRACE_DISABLED.get() or _batched(x, coef):
        return False
    return (isinstance(x, jax.Array) and x.ndim == 2
            and x.dtype in (jnp.float32, jnp.bfloat16)
            and x.shape[1] <= _MAX_DENSE_DIM
            and coef.dtype == jnp.float32
            and norm.is_identity)


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def _fused(loss_and_dz, x, labels, offsets, weights, tile_n: int,
           interpret: bool, coef):
    """x [n, d]; labels/offsets/weights [1, n] rows; coef [8, d] (equal
    rows); n % tile_n == 0, tile_n % 128 == 0, d % 128 == 0."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = x.shape

    def kernel(x_ref, y_ref, off_ref, w_ref, coef_ref, val_ref, grad_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            val_ref[0, 0] = jnp.float32(0.0)
            grad_ref[...] = jnp.zeros_like(grad_ref)

        # the tile of X stays in VMEM for both contractions — HBM reads
        # X exactly once. bf16 storage composes: the tile is read at
        # half the bytes and upcast once in VMEM. Samples run along the
        # LANES in everything below: margins are coef . X_tile^T (an
        # A.B^T contraction, like q.k^T), so every per-sample vector is
        # a lane-dense [1, T] row and no block has a lane dimension of 1.
        x_t = x_ref[...].astype(jnp.float32)
        m = jax.lax.dot_general(
            coef_ref[...], x_t,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_F32)   # [8, T]
        z = m[0:1, :] + off_ref[...]
        l, dz = loss_and_dz(z, y_ref[...])
        w = w_ref[...]
        val_ref[0, 0] += jnp.sum(l * w)
        grad_ref[...] += jnp.dot(
            _lhs_rows(w * dz), x_t,
            preferred_element_type=jnp.float32, precision=_F32)   # [8, D]

    row = pl.BlockSpec((1, tile_n), lambda i: (0, i))
    value, grad = pl.pallas_call(
        kernel,
        grid=(n // tile_n,),
        in_specs=[
            pl.BlockSpec((tile_n, d), lambda i: (i, 0)),
            row, row, row,
            pl.BlockSpec((_MXU_ROWS, d), lambda i: (0, 0)),
        ],
        out_specs=[
            # the scalar accumulator lives in scalar memory
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((_MXU_ROWS, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((_MXU_ROWS, d), jnp.float32),
        ],
        interpret=interpret,
    )(x, labels, offsets, weights, coef)
    return value[0, 0], grad[0]


def _sample_rows(n: int, labels, offsets, weights, n_pad: int):
    y = jnp.asarray(labels, jnp.float32)
    off = (jnp.zeros((n,), jnp.float32) if offsets is None
           else jnp.asarray(offsets, jnp.float32))
    w = (jnp.ones((n,), jnp.float32) if weights is None
         else jnp.asarray(weights, jnp.float32))
    # pad rows carry zero weight: no contribution to value or gradient
    return _row(y, n_pad), _row(off, n_pad), _row(w, n_pad)


def _row_tile(tile_n: int, n: int, cap: Optional[int] = None) -> int:
    """Rows per grid step: a multiple of 128 (they run along lanes), no
    larger than asked, than the data, or than ``cap``."""
    tile = max(_LANES, tile_n // _LANES * _LANES)
    if cap is not None:
        tile = min(tile, max(_LANES, cap // _LANES * _LANES))
    return min(tile, _round_up(n, _LANES))


def fused_dense_value_grad(
    loss,
    x: Array,
    labels: Array,
    offsets: Optional[Array],
    weights: Optional[Array],
    coef: Array,
    *,
    tile_n: int = _TILE_N,
    interpret: Optional[bool] = None,
) -> Tuple[Array, Array]:
    """Weighted loss value and gradient, X streamed from HBM once.

    Drop-in for the un-normalized dense case of
    ``aggregators.value_and_gradient`` (no L2 term — the objective adds
    it, as with the XLA path). Rows are padded to the tile size with
    zero-weight samples, which contribute nothing to either output; a
    feature dimension that is not a multiple of 128 is zero-padded too
    (a copy of X — callers that care keep d lane-aligned).
    """
    if interpret is None:
        interpret = _default_interpret()
    n, d = x.shape
    if n == 0:
        # grid=(0,) would skip the kernel entirely and return
        # uninitialized buffers; match the XLA path's empty-sum contract
        zero = jnp.zeros((), jnp.float32)
        return zero, jnp.zeros((d,), jnp.float32)
    coef = _coef_lhs(coef, _MAX_DENSE_DIM, "dense")
    d_pad = coef.shape[1]
    tile = _row_tile(tile_n, n, cap=_X_TILE_BYTES // (d_pad * 4))
    n_pad = _round_up(n, tile)
    if (n_pad, d_pad) != (n, d):
        x = jnp.pad(x, ((0, n_pad - n), (0, d_pad - d)))
    value, grad = _fused(loss.loss_and_dz, x,
                         *_sample_rows(n, labels, offsets, weights, n_pad),
                         tile, bool(interpret), coef)
    return value, grad[:d]


def _supported_sparse(x, norm, coef) -> bool:
    """ELL-sparse analogue of ``_supported``: padded-ELL features with
    f32/bf16 values AND f32 coefficients, identity normalization, a
    coefficient dimension the VMEM expansion tile can hold, NOT under
    vmap, NOT inside a ``disabled()`` (mesh) region. Larger dimensions
    stay on the CSC segment-sum XLA path — expanding a [D, T] tile that
    overflows VMEM would spill to HBM and forfeit the single pass."""
    from photon_tpu.ops.features import SparseFeatures
    if _TRACE_DISABLED.get():
        return False
    if not isinstance(x, SparseFeatures):
        return False
    idx, val = x.indices, x.values
    if _batched(idx, val, coef):
        return False
    return (isinstance(val, jax.Array) and val.ndim == 2
            and val.dtype in (jnp.float32, jnp.bfloat16)
            and coef.dtype == jnp.float32
            and coef.shape[0] <= _MAX_SPARSE_DIM
            and norm.is_identity)


def _d_chunks(d: int):
    """Static (start, size) pieces of the coefficient dimension (d is a
    multiple of 128): the largest of 512/256/128 rows that divides it."""
    size = next(c for c in (_D_CHUNK, 256, _LANES) if d % c == 0)
    return [(c0, size) for c0 in range(0, d, size)]


def _expand_chunk_t(idx_ref, val_ref, c0: int, size: int, t: int):
    """Rows ``[c0, c0 + size)`` of the tile's dense view, TRANSPOSED:
    ``[size, T]`` from the ``[K, T]`` slot rows, by a static-K unrolled
    one-hot accumulation. Slot j's indices are one sublane row, broadcast
    down the sublanes against a row iota — no width-1 lane slice, no
    [T, 1] column. ELL pad slots (index 0, value 0) contribute exactly
    zero, and duplicate column ids within a row accumulate — both match
    the XLA gather/scatter semantics."""
    rows = c0 + jax.lax.broadcasted_iota(jnp.int32, (size, t), 0)
    chunk = jnp.zeros((size, t), jnp.float32)
    for j in range(idx_ref.shape[0]):   # static ELL width, loop unrolls
        chunk = chunk + jnp.where(rows == idx_ref[j:j + 1, :],
                                  val_ref[j:j + 1, :], 0.0)
    return chunk


@functools.partial(jax.jit, static_argnums=(0, 6, 7))
def _fused_sparse(loss_and_dz, idx_t, val_t, labels, offsets, weights,
                  tile_n: int, interpret: bool, coef):
    """idx_t/val_t [K, n] (slot-major, f32 values); labels/offsets/
    weights [1, n]; coef [8, d] (equal rows); n % tile_n == 0."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k, n = idx_t.shape
    d = coef.shape[1]

    def kernel(idx_ref, val_ref, y_ref, off_ref, w_ref, coef_ref,
               val_out_ref, grad_ref, dense_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            val_out_ref[0, 0] = jnp.float32(0.0)
            grad_ref[...] = jnp.zeros_like(grad_ref)

        # expand the tile's nnz into the VMEM scratch chunk by chunk,
        # taking the margins on the way; the scratch then feeds the
        # gradient contraction, so the nnz stream was read from HBM
        # exactly once
        m = jnp.zeros((_MXU_ROWS, tile_n), jnp.float32)
        for c0, size in _d_chunks(d):
            chunk = _expand_chunk_t(idx_ref, val_ref, c0, size, tile_n)
            dense_ref[c0:c0 + size, :] = chunk
            m = m + jnp.dot(coef_ref[:, c0:c0 + size], chunk,
                            preferred_element_type=jnp.float32,
                            precision=_F32)                   # [8, T]
        z = m[0:1, :] + off_ref[...]
        l, dz = loss_and_dz(z, y_ref[...])
        w = w_ref[...]
        val_out_ref[0, 0] += jnp.sum(l * w)
        wdz = _lhs_rows(w * dz)
        for c0, size in _d_chunks(d):
            grad_ref[:, c0:c0 + size] += jax.lax.dot_general(
                wdz, dense_ref[c0:c0 + size, :],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_F32)                               # [8, size]

    slots = pl.BlockSpec((k, tile_n), lambda i: (0, i))
    row = pl.BlockSpec((1, tile_n), lambda i: (0, i))
    value, grad = pl.pallas_call(
        kernel,
        grid=(n // tile_n,),
        in_specs=[slots, slots, row, row, row,
                  pl.BlockSpec((_MXU_ROWS, d), lambda i: (0, 0))],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((_MXU_ROWS, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((_MXU_ROWS, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, tile_n), jnp.float32)],
        interpret=interpret,
    )(idx_t, val_t, labels, offsets, weights, coef)
    return value[0, 0], grad[0]


def _slot_rows(idx: Array, val: Array, n_pad: int):
    """[n, k] ELL slots -> slot-major [k, n_pad] (int32, f32). The
    transpose is one XLA pass over the nnz stream outside the kernel;
    pad samples and a width-zero ELL get inert (0, 0.0) slots."""
    n, k = idx.shape
    if k == 0:
        return (jnp.zeros((1, n_pad), jnp.int32),
                jnp.zeros((1, n_pad), jnp.float32))
    pad = ((0, 0), (0, n_pad - n))
    return (jnp.pad(idx.astype(jnp.int32).T, pad),
            jnp.pad(val.astype(jnp.float32).T, pad))


def fused_sparse_value_grad(
    loss,
    x,
    labels: Array,
    offsets: Optional[Array],
    weights: Optional[Array],
    coef: Array,
    *,
    tile_n: int = _TILE_N_SPARSE,
    interpret: Optional[bool] = None,
) -> Tuple[Array, Array]:
    """Weighted loss value and gradient over padded-ELL sparse rows,
    the nnz stream read by the kernel once.

    Drop-in for the un-normalized ELL case of
    ``aggregators.value_and_gradient`` (no L2 term — the objective adds
    it, as with the XLA path). Rows are padded to the tile size with
    zero-weight all-pad rows, which contribute nothing to either
    output; rows whose slots are ALL pads (empty segments) likewise
    contribute only their offset's loss, exactly like the XLA path.
    """
    if interpret is None:
        interpret = _default_interpret()
    idx, val = x.indices, x.values
    n = idx.shape[0]
    d = coef.shape[0]
    if n == 0:
        zero = jnp.zeros((), jnp.float32)
        return zero, jnp.zeros((d,), jnp.float32)
    coef = _coef_lhs(coef, _MAX_SPARSE_DIM, "sparse")
    tile = _row_tile(tile_n, n)
    n_pad = _round_up(n, tile)
    value, grad = _fused_sparse(
        loss.loss_and_dz, *_slot_rows(idx, val, n_pad),
        *_sample_rows(n, labels, offsets, weights, n_pad),
        tile, bool(interpret), coef)
    return value, grad[:d]


def _supported_serving(theta: Array, slot_width: int) -> bool:
    """Serving gather+margin gate: f32 coefficient vector small enough
    for the VMEM one-hot expansion tile, at least one gather slot, NOT
    inside a ``disabled()`` region. Evaluated once per scorer program at
    build time — the serving tables/batches are concrete by contract."""
    if _TRACE_DISABLED.get():
        return False
    return (slot_width >= 1
            and theta.ndim == 1
            and theta.dtype == jnp.float32
            and theta.shape[0] <= _MAX_SPARSE_DIM)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _fused_margin(idx_t, val_t, offsets, tile_b: int, interpret: bool,
                  theta):
    """idx_t/val_t [K, n] slot-major; offsets [1, n]; theta [8, d]."""
    from jax.experimental import pallas as pl

    k, n = idx_t.shape
    d = theta.shape[1]

    def kernel(idx_ref, val_ref, off_ref, theta_ref, out_ref):
        # same one-hot expansion as the sparse training kernel: the
        # request tile's (index, value) slots are read from HBM once and
        # expanded in VMEM chunk by chunk; each chunk's margin share is
        # one MXU contraction against the pinned coefficient vector. Pad
        # slots (0, 0.0) and pad rows contribute exactly zero.
        m = jnp.zeros((_MXU_ROWS, tile_b), jnp.float32)
        for c0, size in _d_chunks(d):
            m = m + jnp.dot(
                theta_ref[:, c0:c0 + size],
                _expand_chunk_t(idx_ref, val_ref, c0, size, tile_b),
                preferred_element_type=jnp.float32,
                precision=_F32)                               # [8, T]
        out_ref[...] = m[0:1, :] + off_ref[...]

    slots = pl.BlockSpec((k, tile_b), lambda i: (0, i))
    row = pl.BlockSpec((1, tile_b), lambda i: (0, i))
    out = pl.pallas_call(
        kernel,
        grid=(n // tile_b,),
        in_specs=[slots, slots, row,
                  pl.BlockSpec((_MXU_ROWS, d), lambda i: (0, 0))],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(idx_t, val_t, offsets, theta)
    return out[0]


def fused_gather_margin(
    idx: Array,
    val: Array,
    offsets: Optional[Array],
    theta: Array,
    *,
    tile_b: int = _TILE_B_SERVING,
    interpret: Optional[bool] = None,
) -> Array:
    """Fixed-effect serving margins ``offsets + sum_j val[:, j] *
    theta[idx[:, j]]`` with the request tile read by the kernel once.

    Drop-in for the serving scorer's per-shard gathered dot
    (serving/scorer.py): the caller concatenates every fixed shard's
    padded (index, value) slots with the shard's offset into one
    coefficient vector, so the whole fixed-effect margin is ONE kernel
    per batch instead of a gather + multiply + reduce per shard."""
    if interpret is None:
        interpret = _default_interpret()
    n = idx.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.float32)
    off = (jnp.zeros((n,), jnp.float32) if offsets is None
           else jnp.asarray(offsets, jnp.float32))
    tile = _row_tile(tile_b, n)
    n_pad = _round_up(n, tile)
    out = _fused_margin(*_slot_rows(idx, val, n_pad), _row(off, n_pad),
                        tile, bool(interpret),
                        _coef_lhs(theta, _MAX_SPARSE_DIM, "serving"))
    return out[:n]
