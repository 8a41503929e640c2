"""Pallas TPU kernels: fused GLM value+gradient in ONE pass over X.

The XLA path (ops/aggregators.py value_and_gradient) lowers to two
separate contractions over the feature matrix — ``margins = X @ coef``
and ``grad = X^T (w * dz)`` — so every objective evaluation streams X
from HBM twice. A GLM solve at fixed-effect shapes is HBM-bandwidth-
bound, which makes the second pass pure waste: dz depends only on each
row's own margin, so the gradient contraction can consume the SAME
VMEM-resident tile of X that just produced the margins.

``fused_dense_value_grad`` tiles X over rows and runs on the VECTOR
unit, as XLA's own matrix-vector fusions do (a matrix-vector product
uses 8 of the MXU's 128 rows; at full float32 precision the two
contractions a tile cost more than the read they ride on — PERF.md §5's
gate table): rows on the sublanes, features on the lanes, as X lies in
HBM, so a block spans X's full width whatever it is and nothing is
padded or copied; the kernel takes the whole row tiles and the rows left
over (fewer than a tile) are summed beside it as plain array operations. Per grid step and 128-row block: ``m = sum_lanes(X *
theta)``, the pointwise loss/dz on lane-dense ``[tile / 128, 128]``
vectors, then ``g += sum_sublane_groups(X * (w dz))`` from the same
VMEM tile; the value's and the gradient's partial sums accumulate in
revisited output blocks (the grid is sequential) and are summed outside.
X is read from HBM exactly once: 5.7 ms an evaluation at 530,000 x
2,000 against XLA's 11.5 (93% of the HBM peak). ``dense_route`` says
where ``aggregators.value_and_gradient`` takes it: on a TPU, dense
float32, not under vmap or a mesh, a width the kernel won at on the
chip; a normalised objective takes it too, at effective coefficients,
with the margin shift on the offsets and a third result ``sum(w dz)``.

The other two kernels keep SAMPLES ALONG THE LANES: per-sample vectors
(labels, offsets, weights, margins) are lane-dense ``[1, T]`` rows, the
coefficient vector and the gradient are ``[8, D]`` blocks of identical
rows (a matmul is fed no fewer than one sublane tile of LHS rows; row 0
is the answer), and the scalar loss accumulates in SMEM.

``fused_sparse_value_grad`` extends the single pass to padded-ELL
sparse rows: each grid step reads one slot-major ``[K, T]`` tile of the
nnz stream (indices + values), expands it into a VMEM-resident dense
``[D, T]`` tile via a static-K unrolled one-hot accumulation (a row
iota compared against one sublane-broadcast slot row at a time — never
touches HBM), then runs the margins/loss/grad flow on that tile with
full-float32 MXU contractions. The XLA sparse arm instead gathers theta
for margins and scatter-adds the gradient — two passes over the nnz
stream plus a serialized scatter. The slot-major view is one XLA
transpose of the nnz stream outside the kernel. The VMEM tile bounds
the supported coefficient dimension (``_MAX_SPARSE_DIM``); larger models
stay on the CSC segment-sum path. Nothing routes it (no cell runs sparse
features and the chip has not timed it): its callers call it.

On a TPU the kernels are compiled by Mosaic and a kernel that cannot
compile raises; on every other backend they run in interpret mode,
which is what pins them to the XLA path in tests/test_pallas_glm.py.
``chip_smoke.py`` compiles each of them on the chip and holds it to a
float64 oracle.

Reference semantics: ValueAndGradientAggregator.scala:36-80 (the same
fused margin/loss/grad algebra; the normalization prefactors are applied
by ``ops/aggregators.py`` around the kernel, which hands it effective
coefficients and takes ``sum(w dz)`` back).
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
_TILE_N_SPARSE = 128
_TILE_B_SERVING = 128
# The dense kernel's pipeline double-buffers its tile of X and holds
# nothing else of size, so the tile is set in bytes and the row count
# follows from d: 256 rows at epsilon's 2,000 (2,048 lanes a row in VMEM).
# The step overhead does not show: 256, 512, 1,024 and 2,048 rows a step
# cost 5.752 / 5.745 / 5.753 / 5.745 ms an evaluation at 530,000 x 2,000
# (my chip run, PR 32), so the tile stays inside the 16 MiB of scoped VMEM
# a v5e core hands a kernel by default and no limit is asked for.
_X_TILE_BYTES = 2 << 20
# past this width a 128-row block's per-feature partial sums (d / 128
# registers of the 64) no longer ride in registers through a tile
_MAX_DENSE_DIM = 4096
# The least width the routing admits: a 128-row block costs the kernel
# some 240 ns whatever its width (its per-row numbers change layout twice)
# and XLA's two passes nothing of the kind, so the one read of X wins only
# where a row is wide enough. Seconds an evaluation inside a solve-like
# loop, XLA's two passes / the kernel (my chip run, PR 32, TPU v5e):
#   5,000,000 x   128   6.897 / 9.282 ms   1.35x  (loses)
#   4,000,000 x   128   5.516 / 7.438 ms   1.35x  (loses)
#   4,000,000 x   256  10.929 / 7.469 ms   0.68x
#   2,000,000 x   512  10.883 / 5.482 ms   0.50x
#   1,000,000 x 1,024  10.856 / 5.434 ms   0.50x
#     530,000 x 2,000  11.226 / 5.745 ms   0.51x  (the floor: 5.30 ms)
# Nothing between 128 and 256 was measured, so 256 it is.
_DENSE_MIN_WIDTH = 256
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
# mesh and lambda-lane solves in ``disabled()``; the flag is a ContextVar
# so it binds at TRACE time, like the routing it refines.
_TRACE_DISABLED = contextvars.ContextVar("pallas_glm_disabled",
                                         default=False)


@contextlib.contextmanager
def disabled():
    token = _TRACE_DISABLED.set(True)
    try:
        yield
    finally:
        _TRACE_DISABLED.reset(token)


_PREFETCH = None


def prefetch_toolchain() -> None:
    """Start importing Pallas on a daemon thread (idempotent). The import
    is 1.2 s of Python on a chip's host, paid inside the first solve's
    trace unless it is already under way: a driver's entry hook
    (``utils.compile_cache.maybe_enable``) calls this before the data is
    read or generated, which takes longer and mostly runs outside the
    interpreter's lock. A kernel traced meanwhile waits on the import
    lock for the rest of it."""
    global _PREFETCH
    if _PREFETCH is None:
        import threading

        def load():
            from jax.experimental import pallas  # noqa: F401
            from jax.experimental.pallas import tpu  # noqa: F401

        _PREFETCH = threading.Thread(target=load, name="pallas-import",
                                     daemon=True)
        _PREFETCH.start()


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


def _on_tpu() -> bool:
    """The routing's one look at the backend (a test that drives the
    routing on the CPU, in interpret mode, patches this and nothing
    else)."""
    return jax.default_backend() == "tpu"


KERNEL = "kernel"


def dense_route(x, coef, *rest) -> Optional[str]:
    """Where ``aggregators`` sends an evaluation or a Hessian-vector product
    (``coef`` its vector; ``rest`` whatever else would reach the kernel:
    labels, offsets, weights, a context's factors and shifts, ``None``
    where there is none), by what it observes. ``None``: not the kernel's —
    not a TPU, not a dense rank-2 float32 matrix, coefficients that are
    not float32 (a float64 solve over float32 features promotes on the XLA
    path; the kernel would hand back float32 and break the ``while_loop``
    carry) — so XLA's two passes, uncounted. ``KERNEL``: the one fused
    pass, under an identity context and under a normalisation alike (the
    aggregator folds factors and shifts in around the kernel). Otherwise
    the reason such an evaluation was turned away, which
    ``kernels.xla_fallbacks`` carries as a label: ``"vmap"`` (the
    sequential grid's accumulation into a revisited block assumes it owns
    the grid; the per-entity ladders and the lambda lanes batch the
    objective, and a batch over offsets or contexts alone would reach the
    kernel through ``rest``), ``"mesh"`` (a ``disabled()`` region:
    ``pallas_call`` carries no sharding), ``"shape"`` (the width the
    kernel did not win at: ``_DENSE_MIN_WIDTH``)."""
    if not (_on_tpu() and isinstance(x, jax.Array) and x.ndim == 2
            and x.dtype == jnp.float32 and coef.dtype == jnp.float32):
        return None
    if _batched(x, coef, *rest):
        return "vmap"
    if _TRACE_DISABLED.get():
        return "mesh"
    if not _DENSE_MIN_WIDTH <= x.shape[1] <= _MAX_DENSE_DIM:
        return "shape"
    return KERNEL


def _lane_chunks(d: int):
    """Static (start, size) pieces of the feature dimension as it lies on
    the lanes: whole 128-lane tiles, then the ragged tail (80 lanes at
    d = 2,000)."""
    return [(c0, min(_LANES, d - c0)) for c0 in range(0, d, _LANES)]


@functools.partial(jax.jit, static_argnums=(0, 5, 6, 8, 9))
def _fused(loss_and_dz, x, labels, offsets, weights, tile_n: int,
           interpret: bool, coef, dz_sum: bool = False, d2z=None):
    """x [n, d] AS PLACED (no pad, no copy: any n >= tile_n, any d), of
    which the kernel reads the ``n // tile_n`` whole tiles; labels/
    offsets/weights [steps, tile_n / 128, 128] (row t of step i at
    [i, t // 128, t % 128]); coef [1, d]; tile_n % 128 == 0. Returns the
    value's per-lane partial sums [tile_n / 128, 128] and the gradient's
    per-sublane partial sums [8, d]; with ``dz_sum`` the per-lane partial
    sums [tile_n / 128, 128] of ``w * dz`` (what a normalisation's shifts
    multiply); with ``d2z`` (the loss's second derivative) every row's
    ``w * d2z`` [steps, tile_n / 128, 128], laid out as the labels are."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = x.shape
    steps = n // tile_n
    blocks = tile_n // _LANES
    chunks = _lane_chunks(d)
    f32 = jnp.float32

    def kernel(x_ref, y_ref, off_ref, w_ref, coef_ref, val_ref, grad_ref,
               *rest):
        # rest: the outputs asked for (sum, weights), then the two scratches
        m_ref, wdz_ref = rest[-2:]
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            val_ref[...] = jnp.zeros_like(val_ref)
            grad_ref[...] = jnp.zeros_like(grad_ref)
            if dz_sum:
                rest[0][...] = jnp.zeros_like(rest[0])

        # A 128-row block's per-row numbers change hands between two
        # layouts: one a SUBLANE ([128, 1], what a sum over the lanes of
        # X . theta leaves, and what a row of X is scaled by) and one a
        # LANE ([1, 128], what the loss is computed on and what comes from
        # HBM densely). The diagonal of a [128, 128] broadcast carries one
        # into the other exactly, on the vector unit alone.
        eye = (jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1))
        zero = f32(0.0)

        def x_block(b, c0, size):
            r0 = pl.multiple_of(b * _LANES, _LANES)
            return x_ref[pl.ds(r0, _LANES), c0:c0 + size].astype(f32)

        def margins(b, carry):
            whole = tail = None
            for c0, size in chunks:
                p = x_block(b, c0, size) * coef_ref[:, c0:c0 + size]
                if size == _LANES:
                    whole = p if whole is None else whole + p
                else:
                    tail = p
            col = sum(jnp.sum(p, axis=1, keepdims=True)
                      for p in (whole, tail) if p is not None)
            m_ref[pl.ds(b, 1), :] = jnp.sum(
                jnp.where(eye, col, zero), axis=0, keepdims=True)
            return carry

        jax.lax.fori_loop(0, blocks, margins, 0)
        # the whole tile's loss on dense [tile_n / 128, 128] vectors
        l, dz = loss_and_dz(m_ref[...] + off_ref[...], y_ref[...])
        w = w_ref[...]
        val_ref[...] += l * w
        wdz_ref[...] = w * dz
        if dz_sum:
            rest[0][...] += wdz_ref[...]
        _curvature_weights(d2z, m_ref, off_ref, y_ref, w, rest[dz_sum:-2])
        def gradient(b, acc):
            col = jnp.sum(jnp.where(eye, wdz_ref[pl.ds(b, 1), :], zero),
                          axis=1, keepdims=True)                # [128, 1]
            return tuple(
                a + jnp.sum((x_block(b, c0, size) * col).reshape(
                    _LANES // _MXU_ROWS, _MXU_ROWS, size), axis=0)
                for (c0, size), a in zip(chunks, acc))

        # the same tile of X, still in VMEM, a second time: HBM was read
        # once. Eight partial sums a feature (one a sublane) are carried
        # in registers through the tile.
        acc = jax.lax.fori_loop(
            0, blocks, gradient,
            tuple(jnp.zeros((_MXU_ROWS, size), f32) for _, size in chunks))
        for (c0, size), a in zip(chunks, acc):
            grad_ref[:, c0:c0 + size] += a

    rows = pl.BlockSpec((None, blocks, _LANES), lambda i: (i, 0, 0))
    lanes = pl.BlockSpec((blocks, _LANES), lambda i: (0, 0))
    lanes_shape = jax.ShapeDtypeStruct((blocks, _LANES), f32)
    rows_shape = jax.ShapeDtypeStruct(labels.shape, f32)
    # Mosaic lowers no 64-bit type, and under x64 a ``fori_loop`` counts in
    # int64: the kernel is traced with x64 off (its arrays are float32)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            grid=(steps,),
            in_specs=[
                # the block spans X's FULL last dimension, whatever it is
                pl.BlockSpec((tile_n, d), lambda i: (i, 0)),
                rows, rows, rows,
                pl.BlockSpec((1, d), lambda i: (0, 0)),
            ],
            out_specs=[
                lanes,
                pl.BlockSpec((_MXU_ROWS, d), lambda i: (0, 0)),
            ] + [lanes] * dz_sum + [rows] * (d2z is not None),
            out_shape=[
                lanes_shape,
                jax.ShapeDtypeStruct((_MXU_ROWS, d), f32),
            ] + [lanes_shape] * dz_sum + [rows_shape] * (d2z is not None),
            scratch_shapes=[pltpu.VMEM((blocks, _LANES), f32),
                            pltpu.VMEM((blocks, _LANES), f32)],
            compiler_params=pltpu.CompilerParams(
                # every step adds into the revisited output blocks: in
                # order
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(x, labels, offsets, weights, coef)
    return tuple(out)


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
    tile_n: Optional[int] = None, interpret: Optional[bool] = None,
    with_dz_sum: bool = False,
    with_weights: bool = False,
) -> Tuple[Array, ...]:
    """Weighted loss value and gradient, X streamed from HBM once.

    The dense case of ``aggregators.value_and_gradient`` on raw rows (no
    L2 term — the objective adds it, as with the XLA path). A normalised
    objective is this evaluation too: the aggregator hands in the
    EFFECTIVE coefficients and the margin shift on the offsets, asks
    ``with_dz_sum`` for a third result, ``sum_i w_i dz_i`` (what the
    shifts multiply in the gradient), and applies factors and shifts to
    what comes out. ``with_weights`` adds a last result, each row's
    curvature weight ``w_i l''(m_i)`` [n], from the margins the kernel
    holds anyway (``aggregators.value_gradient_and_weights``). X goes in
    as placed, for any ``n`` and ``d``: the kernel takes the whole tiles;
    the rows left over (fewer than a tile: 80 of epsilon's 530,000) are
    the same sums as array operations over a slice. No copy of X is made.
    The kernel is never shown a row past ``n``: a block past the end of
    X holds unspecified bits that zero weights would not silence, and
    where XLA has placed a small X in VMEM the block IS the operand
    (PERF.md §6, PR 32)."""
    if interpret is None:
        interpret = _default_interpret()
    n, d = x.shape
    if d > _MAX_DENSE_DIM:
        raise ValueError(
            f"fused dense kernel holds a 128-row tile of {d} feature "
            f"columns in VMEM and supports d <= {_MAX_DENSE_DIM}")
    f32 = jnp.float32
    coef = jnp.asarray(coef, f32)
    y = jnp.asarray(labels, f32)
    off = jnp.zeros((n,), f32) if offsets is None else jnp.asarray(offsets, f32)
    w = jnp.ones((n,), f32) if weights is None else jnp.asarray(weights, f32)
    cap = max(_LANES, _X_TILE_BYTES // (_round_up(d, _LANES) * 4))
    tile = min(cap if tile_n is None else tile_n, cap, n) // _LANES * _LANES
    whole = n // tile * tile if tile else 0
    # the rows past the last whole tile (all of them below 128 rows)
    xt = x[whole:].astype(f32)
    zt = jnp.sum(xt * coef, axis=1) + off[whole:]
    lt, dzt = loss.loss_and_dz(zt, y[whole:])
    value = jnp.sum(lt * w[whole:])
    grad = jnp.sum(xt * (dzt * w[whole:])[:, None], axis=0)
    sums = (jnp.sum(dzt * w[whole:]),) if with_dz_sum else ()
    curv = loss.d2z if with_weights else None
    if whole:
        shape = (whole // tile, tile // _LANES, _LANES)
        v, g, *rest = _fused(loss.loss_and_dz, x,
                          *(r[:whole].reshape(shape) for r in (y, off, w)),
                          tile, bool(interpret), coef.reshape(1, d),
                          with_dz_sum, curv)
        value, grad = value + jnp.sum(v), grad + jnp.sum(g, axis=0)
        sums = tuple(a + jnp.sum(b) for a, b in zip(sums, rest))
    d2 = () if curv is None else (curv(zt, y[whole:]) * w[whole:],)
    if whole and d2:        # the kernel's rows first, then the rest
        d2 = (jnp.concatenate([rest[-1].reshape(whole), d2[0]]),)
    return (value, grad) + sums + d2


def fused_dense_hessian_vector(
    x: Array,
    d2: Array,
    vector: Array,
    *,
    offsets: Optional[Array] = None,
    tile_n: Optional[int] = None, interpret: Optional[bool] = None,
    with_dz_sum: bool = False,
) -> Tuple[Array, ...]:
    """``(v . Hv / 2, Hv)`` for ``H = X^T diag(d2) X``, X streamed from HBM
    once: TRON's matrix-free CG step (``aggregators.
    hessian_vector_from_weights`` where ``dense_route`` admits the matrix).

    A product IS a value-and-gradient evaluation: of the squared loss at
    labels 0 and offsets 0, with the curvature weights for sample weights
    and the vector for coefficients. The per-row function is then ``t ->
    (t^2 / 2, t)`` at ``t = X v``, the kernel's ``w * dz`` is ``d2 * Xv``,
    its gradient ``X^T (d2 * Xv)`` and its value the quadratic form, free:
    ``fused_dense_value_grad``'s kernel body, tile and left-over rows,
    where XLA's path reads X twice. The zero labels and offsets are made
    once a solve, outside its loops (2 MB each at 530,000 rows). Under a
    normalisation the aggregator hands in the effective vector, ``offsets``
    = the margin shift, and asks ``with_dz_sum`` for ``sum_i d2_i t_i``."""
    from photon_tpu.ops.losses import SquaredLoss
    zeros = jnp.zeros((x.shape[0],), jnp.float32)
    return fused_dense_value_grad(
        SquaredLoss, x, zeros, zeros if offsets is None else offsets, d2,
        vector, tile_n=tile_n, interpret=interpret, with_dz_sum=with_dz_sum)


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


def _curvature_weights(d2z, m_ref, off_ref, y_ref, w, out) -> None:
    """In ``_fused``'s kernel, asked for the curvature weights (``d2z`` the
    loss's second derivative, ``out`` the one output block of them): each
    row's ``l''(m + offset) * w`` of the tile, from the margins it holds in
    VMEM, written once a grid step. Not asked for, nothing is traced. It
    stands here and not in the kernel's body because the body's line
    numbers are serialised with the kernel, and the compile cache of every
    cell that runs it keys on them (ROADMAP D13)."""
    if d2z is not None:
        out[0][...] = d2z(m_ref[...] + off_ref[...], y_ref[...]) * w
