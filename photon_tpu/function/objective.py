"""Objective functions: the optimizer <-> model contract.

Reference hierarchy: function/ObjectiveFunction.scala:25, DiffFunction
.scala:25, TwiceDiffFunction.scala:25, the L2Regularization mixins
(function/L2Regularization.scala:26,77,140), and DistributedGLMLossFunction
/ SingleNodeGLMLossFunction (function/glm/*.scala), which delegate to the
four aggregators.

TPU re-design: an objective is a bundle of *pure functions* over
``(coef, batch, hyper)``. ``hyper`` carries dynamic hyperparameters —
currently the L2 weight — as traced values, so a regularization-path sweep
(reference: ModelTraining.scala:134-147) reuses ONE compiled optimizer
instead of recompiling per lambda. The same objective object drives the
distributed (batch-sharded pjit) and local (vmap-ed per-entity) paths, the
moral of the reference's abstract ``type Data`` trick.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from photon_tpu.data.dataset import DataBatch
from photon_tpu.ops import aggregators
from photon_tpu.ops.losses import PointwiseLoss
from photon_tpu.ops.normalization import NormalizationContext, no_normalization

Array = jax.Array


class RegularizationType(enum.Enum):
    """Reference: optimization/RegularizationContext.scala:38."""

    NONE = "NONE"
    L1 = "L1"
    L2 = "L2"
    ELASTIC_NET = "ELASTIC_NET"


@dataclasses.dataclass(frozen=True)
class RegularizationContext:
    """Splits a total regularization weight into L1/L2 parts
    (reference: RegularizationContext.scala:115-130; alpha is the elastic-net
    mixing weight: l1 = alpha * lambda, l2 = (1 - alpha) * lambda)."""

    reg_type: RegularizationType = RegularizationType.NONE
    elastic_net_alpha: Optional[float] = None

    def l1_weight(self, reg_weight: float) -> float:
        if self.reg_type == RegularizationType.L1:
            return reg_weight
        if self.reg_type == RegularizationType.ELASTIC_NET:
            return (self.elastic_net_alpha or 0.0) * reg_weight
        return 0.0

    def l2_weight(self, reg_weight: float) -> float:
        if self.reg_type == RegularizationType.L2:
            return reg_weight
        if self.reg_type == RegularizationType.ELASTIC_NET:
            return (1.0 - (self.elastic_net_alpha or 0.0)) * reg_weight
        return 0.0


NoRegularization = RegularizationContext(RegularizationType.NONE)
L1Regularization = RegularizationContext(RegularizationType.L1)
L2Regularization = RegularizationContext(RegularizationType.L2)


class Hyper(NamedTuple):
    """Dynamic (traced) objective hyperparameters."""

    l2_weight: Array  # scalar

    @staticmethod
    def of(l2_weight: float = 0.0, dtype=jnp.float32) -> "Hyper":
        return Hyper(l2_weight=jnp.asarray(l2_weight, dtype=dtype))


class DirectionalProblem(NamedTuple):
    """Margin-resident view of an objective for directional solvers.

    A GLM objective is pointwise loss over margins plus an L2 quadratic,
    and margins are LINEAR in the coefficients. A solver that keeps the
    current margins resident can therefore evaluate any line-search trial
    ``f(x + a*d)`` in O(n_samples) pointwise work — no pass over the
    feature nnz — once the direction's margin increment is known. On the
    model-sharded sparse path, where every feature pass is the wallclock,
    this collapses a whole Wolfe search to less than one classic
    evaluation (see optim/lbfgs.minimize_directional).

    Closures (all pure, jit-safe):
      init(coef) -> (f, g, margins, xx)      one matvec + one rmatvec
      dir_margins(d) -> margin increment     one matvec
      trial(margins, m_d, xx, xd, dd, a) -> (f_a, dphi_a)   O(n_samples)
      at_point(coef, margins, xx) -> (f, g)  one rmatvec
    where ``xx = coef . coef``, ``xd = coef . d``, ``dd = d . d`` feed the
    L2 term's exact 1-D quadratic. ``at_point`` takes xx from the caller
    (the solver advances it by the same exact quadratic,
    xx + a*(2*xd + a*dd)) so the evaluation never re-pays a full
    d-dimensional dot for a scalar it already knows.
    """

    init: Callable[[Array], Tuple[Array, Array, Array, Array]]
    dir_margins: Callable[[Array], Array]
    trial: Callable[..., Tuple[Array, Array]]
    at_point: Callable[[Array, Array, Array], Tuple[Array, Array]]


@dataclasses.dataclass(frozen=True)
class GLMObjective:
    """GLM loss objective with L2 folded in (L1 is the solver's job — OWL-QN,
    as in the reference where OWLQN owns the L1 term).

    All methods are pure and jit/vmap-safe. ``coef`` lives in
    transformed (normalized) space; ``norm`` folds the affine feature map
    into the kernels algebraically.
    """

    loss: PointwiseLoss
    norm: NormalizationContext = no_normalization()

    # -- first order --------------------------------------------------------

    def value(self, coef: Array, batch: DataBatch, hyper: Hyper) -> Array:
        v, _ = self.value_and_gradient(coef, batch, hyper)
        return v

    def gradient(self, coef: Array, batch: DataBatch, hyper: Hyper) -> Array:
        _, g = self.value_and_gradient(coef, batch, hyper)
        return g

    def value_and_gradient(
        self, coef: Array, batch: DataBatch, hyper: Hyper
    ) -> Tuple[Array, Array]:
        v, g = aggregators.value_and_gradient(
            self.loss, batch.features, batch.labels, batch.offsets, batch.weights,
            coef, self.norm,
        )
        # L2 mixin (reference: L2Regularization.scala:26,77) — the reference
        # regularizes the full vector, intercept included.
        v = v + 0.5 * hyper.l2_weight * jnp.dot(coef, coef)
        g = g + hyper.l2_weight * coef
        return v, g

    def local_value_and_gradient(
        self, coef: Array, batch: DataBatch, hyper: Hyper, num_shards: int
    ) -> Tuple[Array, Array]:
        """Local-subproblem view for the hierarchical solver (optim/hier):
        the data term over THIS shard's rows plus ``1/num_shards`` of the
        L2 quadratic, so summing F_k over all shards recovers the global
        objective exactly — the invariant the round safeguard's global-
        loss comparison rests on."""
        scaled = Hyper(l2_weight=hyper.l2_weight / num_shards)
        return self.value_and_gradient(coef, batch, scaled)

    # -- streamed (chunk-accumulated) evaluation ----------------------------

    @staticmethod
    def init_stream_carry(dim: int, dtype) -> Tuple[Array, Array]:
        """Device-resident accumulator for a chunked objective pass:
        (value_acc scalar, grad_acc [dim]), both zero."""
        return (jnp.zeros((), dtype=dtype), jnp.zeros((dim,), dtype=dtype))

    def chunk_value_and_gradient(
        self, carry: Tuple[Array, Array], coef: Array, batch: DataBatch
    ) -> Tuple[Array, Array]:
        """One streamed chunk's contribution to the DATA term, folded into
        the carry. Pad rows carry weight 0 and contribute exactly nothing,
        so the padded tail chunk needs no separate mask. The L2 term is
        deliberately absent — it is per-pass, not per-chunk — and is added
        once by ``finalize_streamed``. Summing this over a pass's chunks
        reproduces the resident data term up to FP summation order."""
        v, g = aggregators.value_and_gradient(
            self.loss, batch.features, batch.labels, batch.offsets,
            batch.weights, coef, self.norm,
        )
        return carry[0] + v, carry[1] + g

    def finalize_streamed(
        self, carry: Tuple[Array, Array], coef: Array, hyper: Hyper
    ) -> Tuple[Array, Array]:
        """Close a chunked pass: accumulated data term + the L2 mixin,
        applied exactly once (same mixin as ``value_and_gradient``)."""
        v, g = carry
        return (v + 0.5 * hyper.l2_weight * jnp.dot(coef, coef),
                g + hyper.l2_weight * coef)

    def directional_problem(
        self, batch: DataBatch, hyper: Hyper
    ) -> DirectionalProblem:
        """Margin-resident 1-D view of this objective (see
        ``DirectionalProblem``). The L2 mixin is folded in exactly:
        0.5*l2*|x + a*d|^2 = 0.5*l2*(xx + 2a*xd + a^2*dd)."""
        loss, norm = self.loss, self.norm
        x, y = batch.features, batch.labels
        off, w = batch.offsets, batch.weights

        def at_point(coef, margins, xx):
            f_data, g_data = aggregators.margin_value_and_gradient(
                loss, x, y, w, margins, norm, coef.shape[0])
            return (f_data + 0.5 * hyper.l2_weight * xx,
                    g_data + hyper.l2_weight * coef)

        def init(coef):
            margins = aggregators.compute_margins(x, coef, off, norm)
            xx = jnp.dot(coef, coef)
            f, g = at_point(coef, margins, xx)
            return f, g, margins, xx

        def dir_margins(direction):
            # offsets=None keeps only the part that scales with the
            # coefficients, so m(coef + a*d) = m(coef) + a*dir_margins(d)
            # holds exactly (normalization included — it is affine too)
            return aggregators.compute_margins(x, direction, None, norm)

        def trial(margins, m_d, xx, xd, dd, a):
            f_data, dphi_data = aggregators.margin_trial(
                loss, y, w, margins, m_d, a)
            f = f_data + 0.5 * hyper.l2_weight * (xx + a * (2.0 * xd + a * dd))
            dphi = dphi_data + hyper.l2_weight * (xd + a * dd)
            return f, dphi

        return DirectionalProblem(init=init, dir_margins=dir_margins,
                                  trial=trial, at_point=at_point)

    # -- second order -------------------------------------------------------

    def hessian_vector(
        self, coef: Array, vector: Array, batch: DataBatch, hyper: Hyper
    ) -> Array:
        hv = aggregators.hessian_vector(
            self.loss, batch.features, batch.labels, batch.offsets, batch.weights,
            coef, vector, self.norm,
        )
        return hv + hyper.l2_weight * vector

    def hessian_weights(self, coef: Array, batch: DataBatch) -> Array:
        """Per-sample curvature weights at ``coef``, by a pass of their own."""
        return aggregators.hessian_weights(
            self.loss, batch.features, batch.labels, batch.offsets, batch.weights,
            coef, self.norm,
        )

    def hessian_vector_from_weights(
        self, d2: Array, vector: Array, batch: DataBatch, hyper: Hyper
    ) -> Array:
        hv = aggregators.hessian_vector_from_weights(
            batch.features, d2, vector, self.norm, vector.shape[0],
        )
        return hv + hyper.l2_weight * vector

    def hessian_matrix_from_weights(
        self, d2: Array, dim: int, batch: DataBatch, hyper: Hyper
    ) -> Array:
        h = aggregators.hessian_matrix_from_weights(
            batch.features, d2, self.norm, dim,
        )
        return h + hyper.l2_weight * jnp.eye(dim, dtype=h.dtype)

    def hessian_diagonal(self, coef: Array, batch: DataBatch, hyper: Hyper) -> Array:
        d = aggregators.hessian_diagonal(
            self.loss, batch.features, batch.labels, batch.offsets, batch.weights,
            coef, self.norm,
        )
        return d + hyper.l2_weight

    def hessian_matrix(self, coef: Array, batch: DataBatch, hyper: Hyper) -> Array:
        h = aggregators.hessian_matrix(
            self.loss, batch.features, batch.labels, batch.offsets, batch.weights,
            coef, self.norm,
        )
        return h + hyper.l2_weight * jnp.eye(coef.shape[0], dtype=h.dtype)

    def value_gradient_and_weights(
        self, coef: Array, batch: DataBatch, hyper: Hyper
    ) -> Tuple[Array, Array, Array]:
        """``value_and_gradient`` and the per-sample curvature weights at
        ``coef`` (``hessian_weights``) from ONE evaluation: TRON's
        operator input, taken where the value and gradient were."""
        v, g, d2 = aggregators.value_gradient_and_weights(
            self.loss, batch.features, batch.labels, batch.offsets, batch.weights,
            coef, self.norm,
        )
        v = v + 0.5 * hyper.l2_weight * jnp.dot(coef, coef)
        g = g + hyper.l2_weight * coef
        return v, g, d2
