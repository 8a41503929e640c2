"""The four fused GLM compute kernels.

These replace the reference's treeAggregate kernels — the hot loops of the
whole system (reference: photon-lib function/glm/ValueAndGradientAggregator
.scala:34, HessianVectorAggregator.scala:37, HessianDiagonalAggregator
.scala:33, HessianMatrixAggregator.scala:31). On Spark each is a per-sample
``seqOp`` plus a tree merge; here each is one fused XLA computation over a
batch: margins via matvec (MXU), pointwise loss, and a transposed matvec.
Under jit with batch-sharded inputs and replicated coefficients, the
``jnp.sum`` reductions lower to ``psum`` over the mesh's ICI — the
treeAggregate equivalent.

Normalization is folded in algebraically, exactly mirroring the reference's
effective-coefficient + prefactor trick (ValueAndGradientAggregator
.scala:36-80): with x' = (x - shift) * factor and e = coef * factor,

    margin_i = e . x_i - e . shift + offset_i
    d value / d coef_j = factor_j [ sum_i w_i l'_i x_ij ] - (sum_i w_i l'_i) factor_j shift_j

so the raw data is never rescaled on device.

Every public function runs under a ``jax.named_scope`` ``agg/<function>``
(``agg/margins`` for ``compute_margins``; the ``*_from_weights`` halves
share their whole's name), on the XLA path and the Pallas path alike: the
names are compile-time metadata, cost nothing at run time, and are what a
device trace's seconds are grouped by (PERF.md §3). They are an interface;
rename one and the per-layer metrics that read it go blind.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from photon_tpu.ops.features import (
    FeatureMatrix,
    SparseFeatures,
    matvec,
    rmatvec,
    sq_rmatvec,
    weighted_gram,
)
from photon_tpu.ops.losses import PointwiseLoss
from photon_tpu.ops.normalization import NormalizationContext

Array = jax.Array


def _kernel_counter(name: str, path: str, **labels: str) -> None:
    """Tick a kernel-activation counter. Runs at TRACE time (the routing
    decision is a Python branch), so the count is per compiled program,
    not per execution — exactly what "did this solve use the fused
    kernel" needs, with zero on-device cost."""
    from photon_tpu.obs.metrics import registry
    registry.counter(f"kernels.{name}", path=path, **labels).inc()


def effective_coefficients(coef: Array, norm: NormalizationContext) -> Tuple[Array, Array]:
    """(e, margin_shift) with e = coef*factor and margin_shift = -e.shift."""
    e = coef * norm.factors if norm.factors is not None else coef
    if norm.shifts is not None:
        shift = -jnp.dot(e, norm.shifts)
    else:
        shift = jnp.zeros((), dtype=coef.dtype)
    return e, shift


@jax.named_scope("agg/margins")
def compute_margins(
    x: FeatureMatrix,
    coef: Array,
    offsets: Optional[Array],
    norm: NormalizationContext,
) -> Array:
    e, margin_shift = effective_coefficients(coef, norm)
    m = matvec(x, e) + margin_shift
    if offsets is not None:
        m = m + offsets
    return m


def _apply_factor_and_shift(
    vec: Array, prefactor: Array, norm: NormalizationContext
) -> Array:
    """factor * vec - prefactor * factor * shift (identity when unnormalized)."""
    out = vec
    if norm.factors is not None:
        out = out * norm.factors
        if norm.shifts is not None:
            out = out - prefactor * norm.factors * norm.shifts
    elif norm.shifts is not None:
        out = out - prefactor * norm.shifts
    return out


@jax.named_scope("agg/value_and_gradient")
def value_and_gradient(
    loss: PointwiseLoss,
    x: FeatureMatrix,
    labels: Array,
    offsets: Optional[Array],
    weights: Optional[Array],
    coef: Array,
    norm: NormalizationContext,
) -> Tuple[Array, Array]:
    """Weighted loss value and gradient w.r.t. transformed-space coef.

    Reference: ValueAndGradientAggregator.calculateValueAndGradient
    (:240-255 RDD path, :266-279 local path) — here one fused kernel.

    On a TPU a dense, float32, unbatched design matrix of a width the
    kernel won at runs the Pallas single-HBM-pass kernel
    (``ops/pallas_glm.py``) instead of XLA's two contractions over X,
    under an identity context and under a normalisation alike
    (``_through_kernel``): ``pallas_glm.dense_route`` decides from what it
    can observe while tracing, and no flag or option has a say.
    ``kernels.pallas_hits{path}`` ticks once a traced program that took
    the kernel, ``kernels.xla_fallbacks{path, reason}`` once a traced
    program whose dense float32 evaluation on a TPU was turned away
    (``vmap``, ``mesh``, ``shape``); ``path`` is ``dense`` under an
    identity context and ``dense_norm`` under factors or shifts. The
    ELL-sparse kernel is routed by nothing: no cell runs sparse features
    and nothing has timed it.
    """
    from photon_tpu.ops import pallas_glm
    route = pallas_glm.dense_route(x, coef, labels, offsets, weights,
                                   norm.factors, norm.shifts)
    path = "dense" if norm.is_identity else "dense_norm"
    if route == pallas_glm.KERNEL:
        _kernel_counter("pallas_hits", path)
        return _through_kernel(
            lambda off, c, s: pallas_glm.fused_dense_value_grad(
                loss, x, labels, off, weights, c, with_dz_sum=s),
            x.shape[0], offsets, coef, norm)
    if route is not None:
        _kernel_counter("xla_fallbacks", path, reason=route)
    dim = coef.shape[0]
    margins = compute_margins(x, coef, offsets, norm)
    l, dz = loss.loss_and_dz(margins, labels)
    if weights is not None:
        l = l * weights
        dz = dz * weights
    value = jnp.sum(l)
    vector_sum = rmatvec(x, dz, dim)
    grad = _apply_factor_and_shift(vector_sum, jnp.sum(dz), norm)
    return value, grad


def _through_kernel(fused, rows: int, offsets: Optional[Array], coef: Array,
                    norm: NormalizationContext) -> Tuple[Array, Array]:
    """(value, gradient) in transformed space through the one-read kernel,
    ``fused(offsets, coefficients, with_dz_sum)`` being the kernel bound to
    its rows. The fold this module's header writes out: the kernel runs at
    the EFFECTIVE coefficients ``coef * factor`` with the margin shift
    ``-e . shift`` added to the offsets (one ``[rows]`` vector), and what
    comes out takes ``_apply_factor_and_shift``. The prefactor
    ``sum_i w_i dz_i`` is the kernel's third result, asked for only where
    there are shifts: under an identity context or factors alone the
    program is the two-result one, and under an identity context the
    trace is the call ``fused(offsets, coef, False)`` and nothing else.
    The third result and not the gradient's intercept slot (a column of
    ones makes ``(X^T w dz)[intercept]`` the same sum): the context
    carries no intercept index, the XLA path asks for none, and the kernel
    holds ``w dz`` in scratch anyway, two vector adds a tile."""
    e = coef if norm.factors is None else coef * norm.factors
    if norm.shifts is None:
        value, vector_sum = fused(offsets, e, False)
        return value, _apply_factor_and_shift(vector_sum, None, norm)
    margin_shift = -jnp.dot(e, norm.shifts)
    off = (jnp.broadcast_to(margin_shift, (rows,)) if offsets is None
           else offsets + margin_shift)
    value, vector_sum, prefactor = fused(off, e, True)
    return value, _apply_factor_and_shift(vector_sum, prefactor, norm)


def _weighted_loss_and_dz(
    loss: PointwiseLoss,
    labels: Array,
    weights: Optional[Array],
    margins: Array,
) -> Tuple[Array, Array]:
    l, dz = loss.loss_and_dz(margins, labels)
    if weights is not None:
        l = l * weights
        dz = dz * weights
    return jnp.sum(l), dz


@jax.named_scope("agg/margin_value_and_gradient")
def margin_value_and_gradient(
    loss: PointwiseLoss,
    x: FeatureMatrix,
    labels: Array,
    weights: Optional[Array],
    margins: Array,
    norm: NormalizationContext,
    dim: int,
) -> Tuple[Array, Array]:
    """``value_and_gradient`` at a point whose margins are already resident.

    Skips the matvec a classic evaluation would pay: the margin-resident
    L-BFGS path (optim/lbfgs.minimize_directional) keeps margins updated
    affinely across iterations, so a full evaluation at the accepted point
    is ONE rmatvec over the feature nnz instead of two passes."""
    value, dz = _weighted_loss_and_dz(loss, labels, weights, margins)
    grad = _apply_factor_and_shift(rmatvec(x, dz, dim), jnp.sum(dz), norm)
    return value, grad


@jax.named_scope("agg/margin_trial")
def margin_trial(
    loss: PointwiseLoss,
    labels: Array,
    weights: Optional[Array],
    margins: Array,
    dir_margins: Array,
    step: Array,
) -> Tuple[Array, Array]:
    """(phi(a), phi'(a)) of the data term's 1-D restriction along a
    direction whose margins are precomputed: margins are linear in coef,
    so a trial point is O(n_samples) pointwise work — no feature pass."""
    value, dz = _weighted_loss_and_dz(
        loss, labels, weights, margins + step * dir_margins)
    return value, jnp.dot(dz, dir_margins)


@jax.named_scope("agg/hessian_weights")
def hessian_weights(
    loss: PointwiseLoss,
    x: FeatureMatrix,
    labels: Array,
    offsets: Optional[Array],
    weights: Optional[Array],
    coef: Array,
    norm: NormalizationContext,
) -> Array:
    """Per-sample Gauss-Newton curvature weights ``w_i l''(margin_i)``.

    The Hessian at a fixed coefficient point is fully determined by these
    weights: NEWTON, DIRECT and the variances take them here, one pass of
    their own; TRON takes them from the evaluation that computed the same
    margins (``value_gradient_and_weights``), where the reference re-derives
    them in every Hv product (HessianVectorAggregator.scala:37)."""
    margins = compute_margins(x, coef, offsets, norm)
    d2 = loss.d2z(margins, labels)
    if weights is not None:
        d2 = d2 * weights
    return d2


@jax.named_scope("agg/hessian_vector")
def hessian_vector_from_weights(
    x: FeatureMatrix,
    d2: Array,
    vector: Array,
    norm: NormalizationContext,
    dim: int,
) -> Array:
    """Hv given precomputed curvature weights.

    Where ``pallas_glm.dense_route`` admits the matrix (the gate of
    ``value_and_gradient``: a TPU, dense float32, unbatched, a width the
    kernel won at) ONE read of X through the same fused kernel
    (``pallas_glm.fused_dense_hessian_vector``), a normalisation folded in
    around it as in ``value_and_gradient`` (``_through_kernel``: the
    product is linear in the vector as the margins are in the
    coefficients); elsewhere XLA's TWO passes (``X v``, then
    ``X^T (d2 * Xv)``: 11.5 ms a product at 530,000 x 2,000 float32 on a
    TPU v5e; PERF.md §5). ``kernels.pallas_hits{path}`` ticks once a
    traced program that took the kernel for a product,
    ``kernels.xla_fallbacks{path, reason}`` once a traced program turned
    away (``vmap``, ``mesh``, ``shape``); ``path`` is ``dense_hv`` under
    an identity context, ``dense_hv_norm`` under factors or shifts."""
    from photon_tpu.ops import pallas_glm
    route = pallas_glm.dense_route(x, vector, d2, norm.factors,
                                   norm.shifts)
    path = "dense_hv" if norm.is_identity else "dense_hv_norm"
    if route == pallas_glm.KERNEL:
        _kernel_counter("pallas_hits", path)
        return _through_kernel(
            lambda off, v, s: pallas_glm.fused_dense_hessian_vector(
                x, d2, v, offsets=off, with_dz_sum=s),
            x.shape[0], None, vector, norm)[1]
    if route is not None:
        _kernel_counter("xla_fallbacks", path, reason=route)
    v_eff = vector * norm.factors if norm.factors is not None else vector
    t = matvec(x, v_eff)
    if norm.shifts is not None:
        t = t - jnp.dot(v_eff, norm.shifts)
    coeffs = d2 * t
    vector_sum = rmatvec(x, coeffs, dim)
    return _apply_factor_and_shift(vector_sum, jnp.sum(coeffs), norm)


@jax.named_scope("agg/hessian_matrix")
def hessian_matrix_from_weights(
    x: FeatureMatrix,
    d2: Array,
    norm: NormalizationContext,
    dim: int,
    precision=jax.lax.Precision.DEFAULT,
    block_rows: Optional[int] = None,
) -> Array:
    """Full H from precomputed curvature weights, at the caller's
    ``precision`` and ``block_rows`` (``features.weighted_gram``): DEFAULT
    and ONE full GEMM over all rows for NEWTON and TRON, whose gradient is
    exact; the variances state HIGHEST in row blocks, and there only the
    UPPER triangle's column blocks are formed and mirrored once after the
    last row block (``features.gram_route``; the row blocks stayed, the
    float32 sum rests on them). A normalisation is folded in afterwards.

    For a CG solve this is one ``X^T diag(d2) X`` plus O(d^2) matvecs; on
    a TPU v5e, bound by two reads of X up to some 1,000 features and by
    the MXU above: ONE matrix-free product's cost under 256 features,
    1.5-2.3 one-read products at 256-1,024, 4.5 at 2,000 (PERF.md §5,
    PR 34): ``optim/problem.tron_explicit_hessian`` gates TRON by that."""
    h = weighted_gram(x, d2, dim, precision, block_rows)
    if norm.shifts is not None:
        lin = rmatvec(x, d2, dim)
        outer = jnp.outer(lin, norm.shifts)
        h = h - outer - outer.T + jnp.sum(d2) * jnp.outer(norm.shifts, norm.shifts)
    if norm.factors is not None:
        h = h * jnp.outer(norm.factors, norm.factors)
    return h


@jax.named_scope("agg/hessian_vector")
def hessian_vector(
    loss: PointwiseLoss,
    x: FeatureMatrix,
    labels: Array,
    offsets: Optional[Array],
    weights: Optional[Array],
    coef: Array,
    vector: Array,
    norm: NormalizationContext,
) -> Array:
    """Gauss-Newton Hessian-vector product (reference:
    HessianVectorAggregator.calcHessianVector :130/:158), used by TRON CG."""
    dim = coef.shape[0]
    d2 = hessian_weights(loss, x, labels, offsets, weights, coef, norm)
    return hessian_vector_from_weights(x, d2, vector, norm, dim)


@jax.named_scope("agg/hessian_diagonal")
def hessian_diagonal(
    loss: PointwiseLoss,
    x: FeatureMatrix,
    labels: Array,
    offsets: Optional[Array],
    weights: Optional[Array],
    coef: Array,
    norm: NormalizationContext,
) -> Array:
    """diag(H) = sum_i w_i l''_i x'_ij^2 (reference:
    HessianDiagonalAggregator.calcHessianDiagonal :92/:115); SIMPLE variance."""
    dim = coef.shape[0]
    margins = compute_margins(x, coef, offsets, norm)
    d2 = loss.d2z(margins, labels)
    if weights is not None:
        d2 = d2 * weights

    sq = sq_rmatvec(x, d2, dim)
    if norm.shifts is None:
        diag = sq
    else:
        lin = rmatvec(x, d2, dim)
        diag = sq - 2.0 * norm.shifts * lin + (norm.shifts ** 2) * jnp.sum(d2)
    if norm.factors is not None:
        diag = diag * norm.factors * norm.factors
    return diag


@jax.named_scope("agg/hessian_matrix")
def hessian_matrix(
    loss: PointwiseLoss,
    x: FeatureMatrix,
    labels: Array,
    offsets: Optional[Array],
    weights: Optional[Array],
    coef: Array,
    norm: NormalizationContext,
) -> Array:
    """Full H = sum_i w_i l''_i x'_i x'_i^T (reference:
    HessianMatrixAggregator.calcHessianMatrix :92/:116); FULL variance,
    small dims only."""
    dim = coef.shape[0]
    d2 = hessian_weights(loss, x, labels, offsets, weights, coef, norm)
    return hessian_matrix_from_weights(x, d2, norm, dim)


@jax.named_scope("agg/value_and_gradient")
def value_gradient_and_weights(
    loss: PointwiseLoss,
    x: FeatureMatrix,
    labels: Array,
    offsets: Optional[Array],
    weights: Optional[Array],
    coef: Array,
    norm: NormalizationContext,
) -> Tuple[Array, Array, Array]:
    """``value_and_gradient`` and, from the same margins, the curvature
    weights ``hessian_weights`` computes at ``coef``: what TRON builds its
    operator from at a point it has just evaluated, with no pass over X of
    its own (``optim/tron.py``).

    Routed as ``value_and_gradient`` is, under its scope: where
    ``pallas_glm.dense_route`` admits the matrix the kernel hands the
    weights out beside value and gradient (``with_weights``), a
    normalisation folded in by ``_through_kernel`` so that its margin shift
    is inside the margins the weights are taken at; elsewhere (``vmap``, a
    mesh, narrow or sparse features, no TPU) XLA's two passes, and the
    weights from the margins the first computed. Counted under
    ``kernels.pallas_hits{path}`` / ``kernels.xla_fallbacks{path,
    reason}``, ``path`` ``dense_curv`` under an identity context and
    ``dense_curv_norm`` under factors or shifts: once a traced evaluation,
    and nothing under ``dense`` / ``dense_norm``."""
    from photon_tpu.ops import pallas_glm
    route = pallas_glm.dense_route(x, coef, labels, offsets, weights,
                                   norm.factors, norm.shifts)
    path = "dense_curv" if norm.is_identity else "dense_curv_norm"
    if route == pallas_glm.KERNEL:
        _kernel_counter("pallas_hits", path)

        def fused(off, c, dz_sum):
            value, *rest, d2 = pallas_glm.fused_dense_value_grad(
                loss, x, labels, off, weights, c, with_dz_sum=dz_sum,
                with_weights=True)
            # the weights ride in the value's slot, which _through_kernel
            # hands back as it is
            return ((value, d2), *rest)

        (value, d2), grad = _through_kernel(fused, x.shape[0], offsets,
                                            coef, norm)
        return value, grad, d2
    if route is not None:
        _kernel_counter("xla_fallbacks", path, reason=route)
    margins = compute_margins(x, coef, offsets, norm)
    value, dz = _weighted_loss_and_dz(loss, labels, weights, margins)
    grad = _apply_factor_and_shift(rmatvec(x, dz, coef.shape[0]),
                                   jnp.sum(dz), norm)
    d2 = loss.d2z(margins, labels)
    return value, grad, d2 if weights is None else d2 * weights
