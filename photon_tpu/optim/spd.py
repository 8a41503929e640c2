"""One solve of a small symmetric positive-definite system, batch on the lanes.

``spd_solve(h, g)`` returns ``x`` with ``h x = g`` for one ``[K, K]`` SPD
matrix. It is what NEWTON and DIRECT call for their step, and both are
vmapped over entities by the random-effect coordinates, so the batched form
is the one that runs: thousands of 8 x 8 and 20 x 20 systems a call.

Two paths, chosen at trace time by the static shape K:

* ``K <= LANES_MAX_DIM`` (path ``lanes``): a Cholesky factorisation and its
  two substitutions written as K sequential steps of elementwise work. Under
  ``jax.vmap`` a ``custom_vmap`` rule moves the batch axis LAST, so every step
  is a pass or two over ``[K, K + 1, E]`` with the entities on the TPU's lanes.
* above it (path ``lapack``): ``cho_factor`` / ``cho_solve`` as before. An
  unbatched system is better served by XLA's factorisation at any K, and a
  batch's ``[K, K + 1, E]`` block leaves VMEM past K = 32.

Why: XLA's batched Cholesky on the TPU is a custom call that tiles the LAST
two dimensions ``T(8,128)`` (a 20 x 20 matrix occupies 24 x 128 words) and
walks the batch matrix by matrix, 2.2 us a 20 x 20 system at any batch width;
that was 56% of a GLMix fit's device seconds (PERF.md §5, PR 23). The lanes
path takes 267 us for a bucket of 10,360 such systems, 85 times less. The
table behind ``LANES_MAX_DIM`` is in PERF.md §5 (PR 25).

The contract the solvers rely on, kept by both paths: the operands' dtype
throughout (float32 stays float32); a matrix that is not positive definite,
or singular, gives a non-finite ``x`` (never an exception, never a silent
finite guess from a failed pivot); and under ``vmap`` one entity's result does
not depend on which or how many entities share its batch (the tests hold both
paths to it on the CPU; on the chip the lanes path keeps it and XLA's
``cho_solve`` does not: PERF.md §6, PR 25).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from photon_tpu.obs.metrics import registry

Array = jax.Array

# the largest K the lanes path serves: PERF.md §5, "the gate" (PR 25)
LANES_MAX_DIM = 32


@jax.jit
def _cholesky_solve_lanes(h: Array, g: Array) -> Array:
    """``h``: [K, K, *B], ``g``: [K, *B] -> ``x``: [K, *B]. Every operation
    is elementwise along the trailing batch axes and NOTHING is reduced: an
    entry's value is one fixed sequence of multiply, subtract, divide and
    square root wherever it sits, so it is the same bit for bit in a batch of
    7, of 10,360 or alone (a version that summed over an axis was not, on the
    chip, and neither is XLA's ``cho_solve`` there: PERF.md §6, PR 25).

    Right-looking Cholesky on column slabs: ``a[k, i]`` is entry (i, k), so
    column k is one contiguous ``[K + 1, *B]`` slab on the major axis. ``g``
    rides as row K of every column, which makes the forward substitution part
    of the factorisation: row K ends as ``y = L^-1 g``. Step j scales slab j
    into L's column and takes its outer product off the slabs behind it. The
    back substitution then takes x[j] times L's row j off what is still to
    solve. Both loops are rolled (``fori_loop``): the steps are identical but
    for their index, and an instance compiles in under a second at any K.
    Jitted so that a shape is traced once a process: ``vmap`` of a
    ``while_loop`` runs the batching rule below several times a solve.
    """
    k_dim = h.shape[0]
    batch = (1,) * (g.ndim - 1)
    # h is symmetric, so h[j] (row j) is its column j
    a = jnp.concatenate([h, g[:, None]], axis=1)           # [K, K + 1, *B]
    slabs = jnp.arange(k_dim).reshape((k_dim, 1) + batch)
    rows = jnp.arange(k_dim + 1).reshape((1, k_dim + 1) + batch)

    def factor_step(j, a):
        col = jax.lax.dynamic_index_in_dim(a, j, axis=0, keepdims=False)
        pivot = jax.lax.dynamic_index_in_dim(col, j, axis=0, keepdims=True)
        # a pivot <= 0 (not PD, singular) gives NaN or inf here and in every
        # later step: the non-finite x the callers test for
        col = col / jnp.sqrt(pivot)                        # [K + 1, *B]
        behind = a - col[None] * col[:k_dim, None]
        return jnp.where((slabs > j) & (rows > j), behind,
                         jnp.where(slabs == j, col[None], a))

    lt = jax.lax.fori_loop(0, k_dim, factor_step, a)   # lt[k, i] = L[i, k], i >= k

    def back_step(t, carry):
        j = k_dim - 1 - t
        y, x = carry                                       # [K, *B] each
        l_row = jax.lax.dynamic_index_in_dim(lt, j, axis=1, keepdims=False)
        x_j = (jax.lax.dynamic_index_in_dim(y, j, axis=0, keepdims=True)
               / jax.lax.dynamic_index_in_dim(l_row, j, axis=0, keepdims=True))
        return (jnp.where(slabs[:, 0] < j, y - l_row * x_j, y),
                jnp.where(slabs[:, 0] == j, x_j, x))

    return jax.lax.fori_loop(0, k_dim, back_step,
                             (lt[:, k_dim], jnp.zeros_like(g)))[1]


@custom_vmap
def _solve_lanes(h: Array, g: Array) -> Array:
    return _cholesky_solve_lanes(h, g)


@_solve_lanes.def_vmap
def _solve_lanes_vmap(axis_size, in_batched, h, g):
    """The batch axis goes last ([K, K, E], [K, E]); an operand that is not
    batched is broadcast along it."""
    h_batched, g_batched = in_batched
    h = (jnp.moveaxis(h, 0, -1) if h_batched
         else jnp.broadcast_to(h[..., None], h.shape + (axis_size,)))
    g = (jnp.moveaxis(g, 0, -1) if g_batched
         else jnp.broadcast_to(g[..., None], g.shape + (axis_size,)))
    return jnp.moveaxis(_cholesky_solve_lanes(h, g), -1, 0), True


def spd_solve(h: Array, g: Array) -> Array:
    """``x`` with ``h x = g`` for a symmetric positive-definite ``h`` [K, K]
    and ``g`` [K]. Batch it with ``jax.vmap`` (either operand, or both).
    Not positive definite or singular: ``x`` is non-finite."""
    lanes = h.shape[-1] <= LANES_MAX_DIM
    # ticked at TRACE time: a compiled program's count, nothing on the device
    # (as ops/aggregators._kernel_counter)
    registry.counter("kernels.spd_solve",
                     path="lanes" if lanes else "lapack").inc()
    if lanes:
        return _solve_lanes(h, g)
    return jax.scipy.linalg.cho_solve(jax.scipy.linalg.cho_factor(h), g)
