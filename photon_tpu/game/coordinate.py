"""GAME coordinates: per-coordinate training + scoring.

Reference: photon-lib algorithm/Coordinate.scala:60-63 (update against
residual-injected offsets), photon-api algorithm/FixedEffectCoordinate
.scala:136-165 (update = DistributedOptimizationProblem.runWithSampling,
score = broadcast dot), algorithm/RandomEffectCoordinate.scala:104-232
(update = co-partitioned join + per-entity local solves in mapValues;
score = join + dot + passive broadcast scoring), ModelCoordinate.scala:28
(frozen coordinates for partial retraining).

TPU re-design: the fixed effect trains one jitted solve over the sharded
flat batch; the random effect trains ALL entities at once with a vmap-ed
L-BFGS over the entity-blocked dataset (per-entity convergence masking via
the while_loop batching rule) — the reference's millions of independent
Breeze solves become one SPMD program on the entity-sharded mesh axis.
Residual injection is a gather a bucket; score emission is ONE gather.

One body solves a size bucket (``RandomEffectCoordinate._make_bucket_
solver``: the only vmap of the entity solver); the scalar ladder, the λ-lane
ladder and the blocked program are wrappers around it, and one host loop
(``_solve_blocked``) streams the blocked fits. How ladder order maps to flat
sample order and to entity rows is four mapping methods' business
(game/random_effect.py: ``EntityBlock.rows_from_flat`` / ``rows_from_table``
/ ``set_rows_in_table`` a bucket, and ``RandomEffectDataset.rows_to_flat``,
which takes every bucket's rows back to flat order by one gather through
the prepare-time inverse map); nothing here indexes a row map itself.

Names (PERF.md §3; they are an interface). Inside the programs, by
``jax.named_scope``: ``fe/score``, ``re/score``, and in every per-entity
solve program ``re/gather`` (warm start, residual and normalisation rows in)
and ``re/scatter`` (the failed-entity select, solved rows out), the
per-entity solve in between carrying the solver's own ``optim/`` and
``agg/`` names; the two ladder programs put each size bucket under its
``re/b<index>``, the blocked program serves every bucket with one executable
and names none (the bucket is the ``block`` attribute of the host span
``re/solve_block``). On the host, by ``obs.annotate`` (events of a profiler trace
when telemetry is on, nothing otherwise): ``fe/args`` / ``re/args`` (the
eager programs that build a solve's arguments), ``fe/solve`` / ``re/solve``
(the dispatch), ``fe/outcome`` / ``re/outcome`` (the blocking scalar read of
the failure code), ``fe/score`` / ``re/score``; a fixed effect's lambda-lane
update is ``fe/args``, ``fe/solve_swept``, ``fe/outcome`` (its ONE read of
the lanes' scalars) and ``fe/score_lanes``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.data.dataset import DataBatch
from photon_tpu.data.sampling import maybe_downsample
from photon_tpu.function.objective import GLMObjective, Hyper
from photon_tpu.game.model import FixedEffectModel, RandomEffectModel
from photon_tpu.game.random_effect import EntityBlock, RandomEffectDataset
from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_tpu.ops import features as F
from photon_tpu.ops.losses import loss_for_task
from photon_tpu.optim import lbfgs, owlqn, tron
from photon_tpu.optim.base import FailureMode
from photon_tpu.optim.problem import (
    GLMOptimizationConfiguration,
    GlmOptimizationProblem,
    solver_cache_key,
)
from photon_tpu.types import OptimizerType, TaskType
from photon_tpu.obs.spans import annotate as _obs_annotate, span as _obs_span
from photon_tpu.utils import jitcache

Array = jax.Array


@jax.jit
def _fixed_score(feats, coef: Array) -> Array:
    # data enters as an argument, never a closure: closed-over arrays
    # would be baked into the HLO as giant literal constants
    with jax.named_scope("fe/score"):
        return F.matvec(feats, coef)


@jax.jit
def _fixed_score_lanes(feats, coefs: Array) -> Array:
    # lane-batched validation/score pass for the sweep path: one shared
    # data read for all K coefficient lanes (ops/features.matvec_lanes)
    with jax.named_scope("fe/score"):
        return F.matvec_lanes(feats, coefs)


class FixedEffectCoordinate:
    """Reference: FixedEffectCoordinate.scala:136-165."""

    def __init__(
        self,
        batch: DataBatch,
        dim: int,
        feature_shard_id: str,
        task: TaskType,
        config: GLMOptimizationConfiguration = GLMOptimizationConfiguration(),
        norm=None,
        sampling_key: Optional[jax.Array] = None,
        mesh=None,
        variance_type=None,
        intercept_index: Optional[int] = None,
    ):
        from photon_tpu.ops.normalization import (
            NormalizationContext,
            no_normalization,
        )
        from photon_tpu.types import VarianceComputationType

        self.variance_type = variance_type or VarianceComputationType.NONE

        self._n_orig = batch.num_samples
        self._model_sharded = False
        self._dim_padded = dim
        if mesh is not None:
            from photon_tpu.parallel import mesh as M
            model_par = (M.MODEL_AXIS in mesh.axis_names
                         and M.axis_size(mesh, M.MODEL_AXIS) > 1)
            if model_par:
                # feature-dimension (tensor-parallel) sharding for theta
                # bigger than one chip's HBM (SURVEY §5.7). Dense: X placed
                # P(data, model), theta P(model); XLA turns the partial
                # dots of matvec/rmatvec into all-reduces over the model
                # axis. Sparse: nonzeros are re-partitioned at ingest into
                # per-feature-range blocks with LOCAL ids — the billion-
                # coefficient workload the reference serves with
                # partitioned PalDB indexes (PalDBIndexMap.scala:43) — in a
                # DUAL layout: ELL rows for the margin gather (matvec) and
                # a column-sorted CSC plan for contiguous-segment gradient
                # reductions (rmatvec), built once here at construction
                # (ops/features.ModelShardedSparse; mesh.shard_sparse_
                # features_model_parallel). Margins/gradients psum over the
                # model/data axes via shard_map, staging the gradient
                # all-reduce ICI-then-DCN on a two-level mesh, and the
                # L-BFGS solve itself runs margin-resident
                # (optim/lbfgs.minimize_directional via problem.run).
                if isinstance(batch.features, F.SparseFeatures):
                    if self.variance_type == VarianceComputationType.FULL:
                        raise ValueError(
                            "FULL variance needs the dense d x d Hessian, "
                            "which contradicts model-axis sharding of a "
                            "sparse theta; use SIMPLE variance or a "
                            "data-parallel mesh for this coordinate")
                    batch = M.shard_sparse_features_model_parallel(
                        batch, mesh, dim)
                    self._dim_padded = batch.features.padded_dim
                else:
                    batch = M.shard_features_model_parallel(batch, mesh)
                    self._dim_padded = batch.features.shape[1]
                self._model_sharded = True
                if norm is not None and not norm.is_identity:
                    # pad the context to the padded feature dim
                    pad = self._dim_padded - dim
                    norm = NormalizationContext(
                        None if norm.factors is None else jnp.pad(
                            norm.factors, (0, pad), constant_values=1.0),
                        None if norm.shifts is None else jnp.pad(
                            norm.shifts, (0, pad)))
            else:
                # sample-shard once at construction; every solve and score
                # pass then runs SPMD over the data axis
                batch = M.shard_batch(batch, mesh, coordinate=feature_shard_id)
        self.batch = batch
        self.dim = dim
        self.feature_shard_id = feature_shard_id
        self.task = task
        self.config = config
        self.problem = GlmOptimizationProblem(task, config,
                                              norm or no_normalization(),
                                              intercept_index=intercept_index)
        if config.optimizer.optimizer_type == OptimizerType.SDCA:
            # config-time typed refusal (SdcaUnsupportedLossError) for
            # tasks whose loss has no conjugate dual step (Poisson) —
            # don't wait for the first sweep to fail mid-fit
            from photon_tpu.optim.sdca import validate_loss
            validate_loss(loss_for_task(task).name)
        self._sampling_key = sampling_key
        self._update_count = 0
        self.mesh = mesh

    def _solve_args(self, prev: Optional[FixedEffectModel],
                    residual_scores: Optional[Array]):
        """(batch, initial coefficients) of one update: the residual into
        the offsets, the down-sample, the warm start placed. Each step is
        an eager device program of its own."""
        batch = self.batch
        if residual_scores is not None:
            extra = batch.num_samples - residual_scores.shape[0]
            if extra:  # mesh padding: zero residual on zero-weight pad rows
                residual_scores = jnp.pad(residual_scores, (0, extra))
            batch = batch.add_scores_to_offsets(residual_scores)
        if getattr(self, "_chaos_poison_once", False):
            # fault injection (resilience/chaos.py): a NaN offset poisons
            # the first objective evaluation exactly like a corrupt
            # upstream residual would
            self._chaos_poison_once = False
            batch = batch.add_scores_to_offsets(
                jnp.full((batch.num_samples,), jnp.nan, batch.labels.dtype))
        if self._sampling_key is not None and self.config.down_sampling_rate < 1.0:
            # fresh subsample per coordinate-descent sweep (the reference
            # draws a new down-sample on every update)
            key = jax.random.fold_in(self._sampling_key, self._update_count)
            self._update_count += 1
            batch = maybe_downsample(batch, self.task,
                                     self.config.down_sampling_rate, key)
        init = prev.model.coefficients.means if prev is not None else None
        if self._model_sharded:
            from photon_tpu.parallel import mesh as M
            # theta lives P(model): pad to the sharded feature dim and
            # place; zero-init also placed so the solve is fully SPMD
            init = jnp.zeros((self.dim,), batch.labels.dtype) \
                if init is None else jnp.asarray(init)
            init = M.shard_coef_model_parallel(init, self.mesh,
                                               padded_dim=self._dim_padded)
        return batch, init

    def update_model(
        self, prev: Optional[FixedEffectModel], residual_scores: Optional[Array]
    ) -> FixedEffectModel:
        """Train against residual-injected offsets
        (= dataset.addScoresToOffsets + runWithSampling).

        ``residual_scores`` is either the live partial score (sequential
        sweep) or a frozen group-entry snapshot (parallel sweep) — the
        solve is a pure function of it either way."""
        with _obs_annotate("fe/args"):
            batch, init = self._placed(*self._solve_args(prev, residual_scores))
        with _obs_annotate("fe/solve"):
            model, result = self.problem.run(
                batch, initial=init, dim=self.dim, dtype=batch.labels.dtype,
                # read the weight from the coordinate's (possibly
                # sweep-updated) config, not the problem's
                # construction-time copy
                regularization_weight=self.config.regularization_weight,
                # this coordinate's batch was sharded at construction; the
                # pallas kernel must not trace over mesh-placed arrays
                pallas_ok=self.mesh is None)
        from photon_tpu.optim.tracking import OptimizationStatesTracker
        self.last_result = result
        self.last_tracker = OptimizationStatesTracker.from_result(result)
        # one scalar host read at the coordinate boundary (never inside the
        # solve): the descent driver must branch on failure in Python to
        # roll the coordinate back
        self.last_failure = None
        if result.failure is not None:
            with _obs_annotate("fe/outcome"):
                code = int(np.asarray(result.failure))
            if code != FailureMode.NONE:
                self.last_failure = FailureMode(code)
        from photon_tpu.types import VarianceComputationType
        if self.variance_type != VarianceComputationType.NONE:
            # reference: DistributedOptimizationProblem.run computes
            # variances on the same (residual-injected) data as the solve
            with _obs_annotate("fe/variance"):
                var = self.problem.compute_variances(
                    batch, model.coefficients.means, self.variance_type,
                    regularization_weight=self.config.regularization_weight,
                    mesh=self.mesh)
            if var is not None:
                _count_variances(self.feature_shard_id, self.variance_type)
                model = GeneralizedLinearModel(
                    Coefficients(model.coefficients.means, var), model.task)
        if self._model_sharded and self._dim_padded != self.dim:
            # publish at the true feature dim; padding stays internal
            c = model.coefficients
            model = GeneralizedLinearModel(
                Coefficients(c.means[: self.dim],
                             None if c.variances is None
                             else c.variances[: self.dim]), model.task)
        return FixedEffectModel(model, self.feature_shard_id)

    def tron_counts(self) -> Optional[dict]:
        """The last update's curvature work under TRON: ``{"cg_steps",
        "hessian_builds", "rejected_steps"}``, None under any other solver
        or before an update. ``last_result`` carries them as device
        scalars; they cross to the host HERE, when asked (an update reads
        the failure code and nothing else), and feed the counters
        ``solver.tron.cg_steps`` / ``solver.tron.rejected_steps`` once a
        result."""
        result = getattr(self, "last_result", None)
        if result is None or result.cg_steps is None:
            return None
        cg, builds, rejected = (int(v) for v in jax.device_get(  # host-sync-ok: read when asked, after the fit
            (result.cg_steps, result.hessian_builds, result.rejected_steps)))
        if getattr(self, "_tron_counted", None) is not result:
            from photon_tpu.obs.metrics import registry
            self._tron_counted = result
            registry.counter("solver.tron.cg_steps").inc(cg)
            registry.counter("solver.tron.rejected_steps").inc(rejected)
        return {"cg_steps": cg, "hessian_builds": builds,
                "rejected_steps": rejected}

    def score(self, model: FixedEffectModel) -> Array:
        """Training-data scores WITHOUT offsets — coordinate-descent score
        algebra sums raw model scores (reference: scoreForCoordinateDescent).
        Mesh pad rows are sliced off so score algebra stays [n]; over a
        data-parallel mesh the scores come back whole on every device."""
        coef = model.model.coefficients.means
        if self._model_sharded:
            from photon_tpu.parallel import mesh as M
            coef = M.shard_coef_model_parallel(jnp.asarray(coef), self.mesh,
                                               padded_dim=self._dim_padded)
        elif self.mesh is not None:
            with _obs_annotate("fe/score"):
                return _fixed_score_whole(self.batch.features, coef,
                                          self._n_orig, self.mesh)
        with _obs_annotate("fe/score"):
            s = _fixed_score(self.batch.features, coef)
        if s.shape[0] != self._n_orig:
            s = s[: self._n_orig]
        return s

    def _placed(self, batch: DataBatch, init: Optional[Array]):
        """Over a data-parallel mesh, a solve's arguments placed the same
        way in every update: the offsets sharded as the rows are, the warm
        start replicated (zeros on the first update). An update's offsets
        and warm start arrive uncommitted on the first sweep and placed by
        the programs that made them later on, and the solve would be
        traced and compiled once for each."""
        if self.mesh is None or self._model_sharded:
            return batch, init
        from photon_tpu.parallel import mesh as M
        if batch.offsets is not None:
            batch = batch._replace(offsets=jax.device_put(
                batch.offsets, batch.labels.sharding))
        if init is None:
            init = jnp.zeros((self.dim,), batch.labels.dtype)
        return batch, jax.device_put(init, M.replicated(self.mesh))

    def update_model_swept(self, prev: Optional[FixedEffectModel],
                           residual_scores: Optional[Array],
                           weights,
                           initial_lanes: Optional[Array] = None):
        """Fit the whole regularization grid ``weights`` against the same
        residual-injected batch as ONE lane-batched program
        (optim/problem.solve_swept) — the per-coordinate sweep that used
        to cost K sequential ``update_model`` calls and K data passes.

        ``initial_lanes [K, d]`` warm-starts each lane independently
        (tuner rounds warm-start every lane from the previous round's
        best); otherwise every lane starts from ``prev``'s coefficients.
        Returns the :class:`~photon_tpu.optim.problem.SweptSolve`;
        per-lane failures stay per-lane (a poisoned lane freezes typed
        without sinking its siblings). The update crosses to the host
        once, under ``fe/outcome``, and leaves ``last_lane_result`` (the
        stacked result, per-lane scalars as host arrays),
        ``last_tracker`` (the lanes' ``lane_counts()``) and
        ``last_lane_failures``. Sweep telemetry: ``sweep.*`` metrics +
        the RunReport ``sweep`` section.
        """
        if self._model_sharded:
            raise ValueError(
                "lane-batched sweeps are not supported on model-axis "
                "sharded coordinates: K lanes hold K full coefficient "
                "vectors, which contradicts a range-sharded theta — sweep "
                "this coordinate sequentially")
        from photon_tpu.obs.metrics import registry
        from photon_tpu.optim import batched
        # the same arguments as update_model's: a poisoned residual
        # poisons every lane's shared data term
        with _obs_annotate("fe/args"):
            batch, init = self._solve_args(prev, residual_scores)
        with _obs_annotate("fe/solve_swept"):
            # the coordinate's batch was (possibly) sharded at
            # construction, so the solve gets mesh=None: GSPMD follows
            # the input placement exactly as in update_model
            swept = self.problem.solve_swept(
                batch, weights, initial=init, initial_lanes=initial_lanes,
                dim=self.dim, dtype=batch.labels.dtype)
        # host boundary: every per-lane scalar the host needs (telemetry,
        # failure typing, the lane counts) in ONE blocking transfer
        stacked = swept.stacked
        with _obs_annotate("fe/outcome"):
            iters, reasons, evals, losses, fails = jax.device_get(  # host-sync-ok: the coordinate boundary
                (stacked.iterations, stacked.reason, stacked.num_fun_evals,
                 stacked.value, stacked.failure))
        if fails is None:
            fails = np.zeros_like(iters)
        # the swept counterpart of ``last_result`` / ``last_tracker``: the
        # stacked result with its per-lane scalars already on the host,
        # and the K lanes as ONE vmapped loop's bucket (``lane_counts()``)
        from photon_tpu.optim.tracking import RandomEffectOptimizationTracker
        self.last_lane_result = stacked._replace(
            iterations=iters, reason=reasons, num_fun_evals=evals,
            value=losses, failure=fails)
        self.last_tracker = RandomEffectOptimizationTracker(
            iterations=iters, reasons=reasons,
            bucket_rows=(np.arange(len(iters)),))
        self.last_lane_failures = [
            None if code == FailureMode.NONE else FailureMode(int(code))
            for code in fails]
        registry.gauge("sweep.lanes_active").set(
            int(np.sum(fails == FailureMode.NONE)))
        hist = registry.histogram(
            "sweep.lane_iterations",
            buckets=(1, 2, 5, 10, 20, 50, 100, 200, 500))
        for it in iters:
            hist.observe(float(it))
        lams = batched.validate_lane_weights(weights)
        batched.record_sweep_run([
            {"weight": float(lams[i]), "loss": float(losses[i]),
             "iterations": int(iters[i]), "reason": int(reasons[i]),
             "failure": int(fails[i])}
            for i in range(len(lams))])
        return swept

    def score_lanes(self, coefs: Array) -> Array:
        """Training-data scores for K coefficient lanes ``[K, d] ->
        [K, n]`` — one shared feature pass (the sweep counterpart of
        ``score``). Mesh pad rows are sliced off per lane."""
        if self._model_sharded:
            raise ValueError(
                "score_lanes is not supported on model-axis sharded "
                "coordinates (see update_model_swept)")
        with _obs_annotate("fe/score_lanes"):
            s = _fixed_score_lanes(self.batch.features, jnp.asarray(coefs))
        if s.shape[1] != self._n_orig:
            s = s[:, : self._n_orig]
        return s

    @functools.cached_property
    def _objective_value_fn(self):
        obj = GLMObjective(loss_for_task(self.task))

        def build():
            @jax.jit
            def value(feats, labels, offsets, weights, coef, l2):
                return obj.value(coef, DataBatch(feats, labels, offsets,
                                                 weights), Hyper(l2_weight=l2))
            return value

        return jitcache.get_or_build(("fe_objval", self.task), build)

    def objective_value(self, model: Optional[FixedEffectModel],
                        residual_scores: Optional[Array]) -> Optional[Array]:
        """L2-regularized objective of ``model`` against a residual
        snapshot, as a DEVICE scalar (no host sync — the parallel-CD
        staleness guard sums these and reads one bool per group).
        ``None`` when the coordinate is model-axis sharded: the guard is
        skipped there rather than re-deriving the shard_map margin
        machinery for a diagnostic."""
        if self._model_sharded:
            return None
        batch = self.batch
        if residual_scores is not None:
            extra = batch.num_samples - residual_scores.shape[0]
            if extra:  # mesh padding: zero residual on zero-weight pad rows
                residual_scores = jnp.pad(residual_scores, (0, extra))
            batch = batch.add_scores_to_offsets(residual_scores)
        coef = (jnp.zeros((self.dim,), batch.labels.dtype) if model is None
                else jnp.asarray(model.model.coefficients.means))
        l2 = jnp.asarray(self.config.regularization.l2_weight(
            self.config.regularization_weight), batch.labels.dtype)
        return self._objective_value_fn(batch.features, batch.labels,
                                        batch.offsets, batch.weights,
                                        coef, l2)

    def predicted_decrease(self, prev: Optional[FixedEffectModel],
                           new: FixedEffectModel,
                           residual_scores: Optional[Array]
                           ) -> Optional[Array]:
        """Solver-predicted objective decrease for ``prev -> new`` against
        the FROZEN residual the solve actually saw (device scalar)."""
        a = self.objective_value(prev, residual_scores)
        b = self.objective_value(new, residual_scores)
        return None if a is None or b is None else a - b

    @functools.cached_property
    def _data_loss_fn(self):
        loss = loss_for_task(self.task)

        def build():
            @jax.jit
            def value(labels, offsets, weights, scores):
                l, _ = loss.loss_and_dz(offsets + scores, labels)
                return jnp.sum(l * weights) if weights is not None \
                    else jnp.sum(l)
            return value

        return jitcache.get_or_build(("fe_dataloss", self.task), build)

    def data_loss_at(self, total_scores: Array) -> Array:
        """Weighted GLM data loss at a TOTAL score vector (no features, no
        regularization), as a device scalar: ``sum_i w_i * l(y_i,
        base_offset_i + s_i)``. This is the score-space primitive of the
        parallel-CD staleness guard: every objective difference the guard
        needs is a difference of these at score vectors the group
        reconciliation already materialized, so the guard costs O(n)
        elementwise work instead of per-member feature passes (see
        descent._run_group). Mesh pad rows carry zero weight and
        contribute exactly 0."""
        batch = self.batch
        extra = batch.num_samples - total_scores.shape[0]
        if extra:
            total_scores = jnp.pad(total_scores, (0, extra))
        return self._data_loss_fn(batch.labels, batch.offsets,
                                  batch.weights, total_scores)


class RandomEffectCoordinate:
    """Reference: RandomEffectCoordinate.scala:104-232 — redesigned as one
    vmapped solve over the entity-blocked dataset."""

    def __init__(
        self,
        dataset: RandomEffectDataset,
        num_flat_samples: int,
        random_effect_type: str,
        feature_shard_id: str,
        task: TaskType,
        config: GLMOptimizationConfiguration = GLMOptimizationConfiguration(),
        mesh=None,
        variance_type=None,
        norm=None,
        intercept_index: Optional[int] = None,
    ):
        from photon_tpu.types import VarianceComputationType

        self.variance_type = variance_type or VarianceComputationType.NONE
        if dataset.num_flat_samples != num_flat_samples:
            raise ValueError(
                f"dataset was built over {dataset.num_flat_samples} flat "
                f"rows, the coordinate is given {num_flat_samples}")
        self._num_entities_orig = dataset.num_entities
        if mesh is not None:
            from photon_tpu.parallel import mesh as M
            # entity-shard once at construction (the co-partitioning
            # replacement); the vmapped solves are independent per entity,
            # so this axis runs collective-free
            # the dense flags, read from the blocks as they arrive (from
            # the estimator: on the host): the mesh's pad rows, indices
            # and values 0, change no flag, and no sharded block has to
            # come back to the host for them
            self.__dict__["_dense_local_blocks"] = _dense_flags(dataset)
            dataset = M.shard_entity_blocks(dataset, mesh,
                                            coordinate=random_effect_type)
        self.dataset = dataset
        self.n = num_flat_samples
        self.random_effect_type = random_effect_type
        self.feature_shard_id = feature_shard_id
        self.task = task
        self.config = config
        self.objective = GLMObjective(loss_for_task(task))
        self.mesh = mesh
        # per-entity normalization (reference: NormalizationContextWrapper):
        # the shard-level [D] context is gathered through each entity's
        # projection into local-slot space; pad slots get factor 1, shift 0
        self._norm_local = self._build_local_norm(norm, intercept_index)

    def _build_local_norm(self, norm, intercept_index: Optional[int]):
        """Gather a shard-space NormalizationContext [D] into per-entity
        local-slot arrays aligned with this dataset's projection table:
        (factors [E, D_loc], shifts [E, D_loc] | None, islot [E]).
        ``islot`` is each entity's local slot of the intercept feature
        (-1 when unobserved — only possible for entities with no active
        data, whose zero coefficients transform to zero anyway)."""
        if norm is None or norm.is_identity:
            return None
        import numpy as np

        proj = np.asarray(self.dataset.projection)
        E, d_loc = proj.shape
        valid = proj >= 0
        f = np.ones((E, d_loc), np.float32)
        if norm.factors is not None:
            f[valid] = np.asarray(norm.factors, np.float32)[proj[valid]]
        s = None
        islot = np.full((E,), -1, np.int32)
        if norm.shifts is not None:
            if intercept_index is None:
                raise ValueError(
                    "random-effect normalization with shifts requires the "
                    "shard's intercept_index")
            s = np.zeros((E, d_loc), np.float32)
            s[valid] = np.asarray(norm.shifts, np.float32)[proj[valid]]
            ent, slot = np.nonzero(proj == intercept_index)
            islot[ent] = slot
        return (jnp.asarray(f),
                None if s is None else jnp.asarray(s),
                jnp.asarray(islot))

    @functools.cached_property
    def _dense_local_blocks(self) -> Tuple[bool, ...]:
        """``_dense_flags`` of this coordinate's dataset, computed once at
        solve-build time; trace-time static."""
        return _dense_flags(self.dataset)

    def _validate_solver(self) -> None:
        opt = self.config.optimizer
        if opt.optimizer_type == OptimizerType.SDCA:
            raise ValueError(
                "SDCA is a streaming fixed-effect solver (per-example "
                "dual state over the chunk store); the per-entity "
                "random-effect solves have no dual-state batching rule — "
                "use LBFGS/DIRECT/NEWTON for random-effect coordinates")
        if opt.optimizer_type == OptimizerType.DIRECT:
            from photon_tpu.optim.problem import _validate_direct
            _validate_direct(self.task, opt, self.config.regularization)
        if opt.optimizer_type == OptimizerType.NEWTON:
            from photon_tpu.optim.problem import _validate_newton
            _validate_newton(self.task, opt, self.config.regularization)
            if (opt.explicit_hessian is not True
                    and self.dataset.projected_dim > 64):
                # same bound as TRON's explicit gate below: an [E, K, K]
                # Hessian block at large K (IDENTITY projectors / fat
                # entities) would dwarf the data itself — NEWTON has no
                # matrix-free mode, so refuse instead of OOMing
                raise ValueError(
                    f"NEWTON builds explicit [E, K, K] Hessians; projected "
                    f"dim {self.dataset.projected_dim} > 64 would dwarf the "
                    f"data. Use TRON (matrix-free above K=64) or set "
                    f"explicit_hessian=True to override")

    def _make_entity_solvers(self):
        """(solve_sparse, solve_dense): one entity's local solve, which
        every program vmaps through ``_make_bucket_solver``."""
        obj = self.objective
        opt = self.config.optimizer
        solver_cfg = opt.solver_config()
        opt_type = opt.optimizer_type
        from photon_tpu.ops.normalization import NormalizationContext

        def solve_core(feats, labels, offsets, weights, x0,
                       l2, l1, f_row=None, s_row=None, islot=None):
            batch = DataBatch(feats, labels, offsets, weights)
            hyper = Hyper(l2_weight=l2)
            if f_row is not None:
                # per-entity transformed space (NormalizationContext
                # Wrapper analog); x0/coef cross the boundary via the
                # margin-invariant maps, islot the dynamic intercept slot
                ctx = NormalizationContext(f_row, s_row)
                obj_e = GLMObjective(obj.loss, ctx)
                x0 = ctx.model_to_transformed_space(
                    x0, islot if s_row is not None else None)
            else:
                obj_e = obj
            vg = lambda c: obj_e.value_and_gradient(c, batch, hyper)
            if opt_type == OptimizerType.DIRECT:
                # one [K, K] normal-equations solve per entity; under
                # vmap one batch of E Cholesky solves, entities on the
                # lanes for small K (optim/spd.py) — no sequential
                # iterations at all
                from photon_tpu.optim import direct
                r = direct.minimize(
                    vg, lambda c: obj_e.hessian_matrix(c, batch, hyper),
                    x0)
            elif opt_type == OptimizerType.NEWTON:
                # damped Newton/IRLS: DIRECT's batched Cholesky solve
                # (optim/spd.py) for logistic/Poisson — a handful of
                # outer iterations, each one batched weighted-Gram
                # contraction + factorization, zero inner CG
                # (optim/newton.py; replaces per-entity iterative TRON,
                # SingleNodeOptimizationProblem.scala:40)
                from photon_tpu.optim import newton
                K = x0.shape[0]
                r = newton.minimize(
                    vg,
                    lambda c: obj_e.hessian_matrix_from_weights(
                        obj_e.hessian_weights(c, batch), K, batch,
                        hyper),
                    x0, config=solver_cfg)
            elif opt_type == OptimizerType.OWLQN:
                r = owlqn.minimize(vg, x0, l1_weight=l1, config=solver_cfg)
            elif opt_type == OptimizerType.TRON:
                # explicit K x K Gauss-Newton per outer iteration when
                # the per-entity dim is small (the common projected
                # case): under vmap it becomes one batched [E, K, K]
                # contraction (MXU) and CG touches no sample data.
                # IDENTITY projectors / fat entities keep the
                # matrix-free operator — an [E, K, K] block at large K
                # would dwarf the data itself. opt.explicit_hessian
                # overrides, mirroring the fixed-effect gate
                # (optim/problem.py).
                K = x0.shape[0]
                explicit = opt.explicit_hessian
                if explicit is None:
                    explicit = K <= 64
                if explicit:
                    hs = lambda d2: obj_e.hessian_matrix_from_weights(
                        d2, K, batch, hyper)
                    ha = lambda h, v: h @ v
                else:
                    hs, ha = None, lambda d2, v: (
                        obj_e.hessian_vector_from_weights(d2, v, batch, hyper))
                vgw = lambda c: obj_e.value_gradient_and_weights(c, batch, hyper)
                r = tron.minimize(vgw, None, x0, config=solver_cfg,
                                  hess_setup=hs, hess_apply=ha)
            else:
                r = lbfgs.minimize(vg, x0, config=solver_cfg)
            coef = r.coef
            if f_row is not None:
                coef = ctx.transformed_space_to_model(
                    coef, islot if s_row is not None else None)
            fail = (jnp.asarray(0, jnp.int32) if r.failure is None
                    else r.failure)
            return coef, r.iterations, r.reason, fail

        def solve_sparse(feat_idx, feat_val, *rest):
            return solve_core(F.SparseFeatures(feat_idx, feat_val), *rest)

        def solve_dense(feat_val, *rest):
            # dense-local block: ELL slot == local index everywhere,
            # so values ARE the entity's dense [S, K] design matrix
            return solve_core(feat_val, *rest)

        return solve_sparse, solve_dense

    def _make_bucket_solver(self, lanes: bool):
        """One size bucket's solve body, UNJITTED, and the one place the
        entity solver is vmapped: the scalar ladder (``_solve_fn``), the
        λ-lane ladder (``_solve_swept_fn``) and the blocked program
        (``_block_solve_swept_fn``) are wrappers around it. Given a bucket,
        its residual-injected offsets (``_residual_offsets``) and its
        warm-start rows, it picks the dense or sparse argument list,
        gathers the normalisation rows, vmaps the entity solver once and
        keeps a failed entity's warm start (``dense`` is the bucket's
        static flag from ``_dense_local_blocks``). ``re/gather`` and
        ``re/scatter`` are opened here, so every program carries them.

        Two static modes, and no more:

        ``lanes=False`` — ``x0 [E_b, K]``, ``l2`` / ``l1`` scalars the whole
        batch shares (``in_axes=None``). This is what a fit runs.

        ``lanes=True`` — ``x0 [c, E_b, K]``, ``l2`` / ``l1`` ``[c]``. The c
        lanes are FLATTENED into the entity axis: the bucket's arrays are
        tiled c× inside the program (lane-major virtual entities) and
        the per-entity solver is vmapped over ONE ``c*E``-wide batch
        axis, exactly the scalar mode's vmap structure.

        Flattening — not a nested ``vmap`` over lanes — is the bitwise
        contract. The entity-vmap is width-insensitive on every backend
        we pin (solving a tiled ``2E`` batch reproduces the ``E`` batch
        bit-for-bit), but NESTING a second vmap re-lowers the batched
        reductions with an extra batch dimension and reassociates their
        FP order: lane results then drift ~1e-9 from the scalar solve at
        f64, and a lane sitting at a convergence-threshold knife edge
        (observed at strong regularization) splits its ITERATION COUNT.
        With flattening, every lane of every chunk width — padded tails
        included — is bitwise-equal to its sequential scalar solve.

        The tile costs ``c×`` block data on device; parallel/memory's
        planner charges each lane ``data + lane_state`` bytes and chunks
        the grid when the budget can't carry full K. A streamed block
        still STAGES once — tiling is a device-side op, so storage→device
        traffic stays one pass per bucket regardless of K.

        The scalar mode is not the lane mode at ``c == 1``: one lane
        passes ``l2`` as an ``[E_b]`` array where the scalar program
        passes a scalar, which is another program (ROADMAP D6b)."""
        solve_sparse, solve_dense = self._make_entity_solvers()

        def solve_bucket(blk: EntityBlock, dense: bool, offsets: Array,
                         x0: Array, l2: Array, l1: Array,
                         norm_f: Optional[Array] = None,
                         norm_s: Optional[Array] = None,
                         norm_islot: Optional[Array] = None):
            shape = x0.shape[:-1]  # [E_b], or [c, E_b] with lanes
            tile = lambda a: a
            reg_axis = None
            if lanes:
                c, E = shape
                if c > 1:
                    tile = lambda a: jnp.concatenate([a] * c, axis=0)
                x0 = x0.reshape((c * E,) + x0.shape[2:])
                l2 = jnp.repeat(l2, E)
                l1 = jnp.repeat(l1, E)
                reg_axis = 0
            if dense:
                fn = solve_dense
                args = [tile(blk.features.values), tile(blk.labels),
                        tile(offsets), tile(blk.weights), x0, l2, l1]
                axes = [0, 0, 0, 0, 0, reg_axis, reg_axis]
            else:
                fn = solve_sparse
                args = [tile(blk.features.indices),
                        tile(blk.features.values), tile(blk.labels),
                        tile(offsets), tile(blk.weights), x0, l2, l1]
                axes = [0, 0, 0, 0, 0, 0, reg_axis, reg_axis]
            if norm_f is not None:
                with jax.named_scope("re/gather"):
                    args.append(tile(blk.rows_from_table(norm_f, 1.0)))
                    axes.append(0)
                    if norm_s is not None:
                        args.append(tile(blk.rows_from_table(norm_s, 0.0)))
                        args.append(tile(blk.rows_from_table(norm_islot, -1)))
                        axes.extend([0, 0])
            solved, it_b, reason_b, fail_b = jax.vmap(
                fn, in_axes=tuple(axes))(*args)
            with jax.named_scope("re/scatter"):
                # per-entity isolation (per lane, with lanes): a failed
                # entity keeps its warm start; healthy entities of the
                # same bucket keep their fresh solves (no host branch —
                # pure select)
                solved = jnp.where((fail_b != 0)[:, None], x0, solved)
            if lanes:
                return tuple(a.reshape(shape + a.shape[1:])
                             for a in (solved, it_b, reason_b, fail_b))
            return solved, it_b, reason_b, fail_b

        return solve_bucket

    def _make_ladder_solver(self, lanes: bool):
        """The whole-ladder solve body, UNJITTED: the bucket body under
        each size bucket's ``re/b<index>``, between the warm-start gather
        and the scatters back into the tables. ``lanes`` says whether a
        lane axis leads the tables (``coef0 [c, E, K]``, ``l2`` / ``l1``
        ``[c]``): per bucket the c lanes then solve against one shared
        staging of the ladder, every lane bitwise its scalar solve
        (``_make_bucket_solver``)."""
        dense_flags = self._dense_local_blocks
        solve_bucket = self._make_bucket_solver(lanes)

        # the dataset enters as a pytree argument, never a closure (a
        # closed-over array would be baked into the HLO as a constant);
        # the Python loop over size buckets unrolls into one program
        def solve_all(ds: RandomEffectDataset, residual_flat: Optional[Array],
                      coef0: Array, l2: Array, l1: Array,
                      norm_f: Optional[Array] = None,
                      norm_s: Optional[Array] = None,
                      norm_islot: Optional[Array] = None):
            out = coef0  # entities with no active data keep warm start
            # per-entity solver stats (-1 = entity never trained)
            stats = coef0.shape[:-1]  # [E], or [c, E] with lanes
            iters = jnp.full(stats, -1, jnp.int32)
            reasons = jnp.full(stats, -1, jnp.int32)
            fails = jnp.zeros(stats, jnp.int32)
            for bi, (blk, dense) in enumerate(zip(ds.blocks, dense_flags)):
                # one scope a bucket, so a bucket's device seconds can be
                # set beside its entity count and padded shape
                with jax.named_scope(f"re/b{bi}"):
                    with jax.named_scope("re/gather"):
                        offsets = _residual_offsets(blk, residual_flat)
                        x0 = blk.rows_from_table(coef0, 0.0, lanes)
                    solved, it_b, reason_b, fail_b = solve_bucket(
                        blk, dense, offsets, x0, l2, l1,
                        norm_f, norm_s, norm_islot)
                    with jax.named_scope("re/scatter"):
                        out = blk.set_rows_in_table(out, solved, lanes)
                        iters = blk.set_rows_in_table(iters, it_b, lanes)
                        reasons = blk.set_rows_in_table(
                            reasons, reason_b, lanes)
                        fails = blk.set_rows_in_table(fails, fail_b, lanes)
            return out, iters, reasons, fails

        # jit names the module for the callable, and the persistent
        # compile cache's key hashes the module's text with that name:
        # under another name every machine would compile the ladder anew
        solve_all.__name__ = solve_all.__qualname__ = (
            "solve_all_lanes" if lanes else "solve_all")
        return solve_all

    def _solver_program(self, name: str, flavour, make):
        """One jitted solve program from the process-wide cache, under a
        key of everything its trace depends on."""
        self._validate_solver()
        has_norm = self._norm_local is not None
        has_shifts = has_norm and self._norm_local[1] is not None
        key = (name, self.task, solver_cache_key(self.config.optimizer),
               has_norm, has_shifts, flavour)
        return jitcache.get_or_build(key, lambda: jax.jit(make()))

    @functools.cached_property
    def _solve_fn(self):
        return self._solver_program(
            "re_solve", self._dense_local_blocks,
            lambda: self._make_ladder_solver(lanes=False))

    @functools.cached_property
    def _solve_swept_fn(self):
        """λ-lane variant of ``_solve_fn``: c lanes of
        ``(coef0 [c, E, d], l2 [c], l1 [c])`` solved in one program per
        lane-chunk width, reading the bucket ladder's data once for all
        lanes (the dataset stays a shared jit argument — the
        ``minimize_lanes`` data-pass economics applied to the per-entity
        vmap). Per-entity failure isolation carries over per lane, and
        EVERY lane — not just K=1 — is bitwise its scalar solve (see
        ``_make_bucket_solver``)."""
        return self._solver_program(
            "re_solve_swept", self._dense_local_blocks,
            lambda: self._make_ladder_solver(lanes=True))

    def _block_solve_swept_fn(self, dense: bool):
        """One size bucket as a standalone program: the streaming unit of
        the blocked fits, solving c λ points (one, for
        ``update_model_blocked``) against ONE staging of the bucket (the
        tile to ``c*E`` virtual entities is a device-side op inside the
        program). One program per (bucket flavor, lane-chunk width) serves
        every bucket of that flavor, so it cannot carry a ``re/b<index>``
        scope: in a trace the bucket is the ``block`` attribute of the host
        span ``re/solve_block`` around each launch. Every lane is bitwise
        the ladder's solve of the same entity (see
        ``_make_bucket_solver``)."""
        def make():
            solve_bucket = self._make_bucket_solver(lanes=True)

            def solve_block_lanes(blk: EntityBlock,
                                  residual_flat: Optional[Array],
                                  x0_lanes: Array, l2_lanes: Array,
                                  l1_lanes: Array,
                                  norm_f: Optional[Array] = None,
                                  norm_s: Optional[Array] = None,
                                  norm_islot: Optional[Array] = None):
                with jax.named_scope("re/gather"):
                    offsets = _residual_offsets(blk, residual_flat)
                return solve_bucket(blk, dense, offsets, x0_lanes, l2_lanes,
                                    l1_lanes, norm_f, norm_s, norm_islot)

            return solve_block_lanes

        return self._solver_program("re_solve_block_swept", bool(dense), make)

    def _warm_table(self, prev: Optional[RandomEffectModel]):
        """(dtype, ``[E_pad, K]`` warm-start table) of a resident solve:
        ``prev``'s coefficients at this coordinate's (possibly mesh-padded)
        entity count, zeros from scratch."""
        ds = self.dataset
        dtype = self._solve_dtype(prev)
        coef0 = self._pad_entity_rows(
            prev.coefficients if prev is not None
            else jnp.zeros((ds.num_entities, ds.projected_dim), dtype))
        if self.mesh is not None:
            # placed the same way in every update (zeros on the first
            # come uncommitted), so the ladder compiles once
            from photon_tpu.parallel import mesh as M
            coef0 = jax.device_put(coef0, M.replicated(self.mesh))
        return dtype, coef0

    def _solve_dtype(self, prev: Optional[RandomEffectModel] = None):
        """A solve runs in its warm start's dtype, else the dataset's — the
        per-entity programs must see identical input dtypes for blocked /
        all-at-once parity to be bitwise."""
        ds = self.dataset
        return (prev.coefficients.dtype if prev is not None
                else (ds.blocks[0].labels.dtype if ds.blocks
                      else jnp.float32))

    def _solve_args(self, dtype, residual_scores: Optional[Array],
                    lams=None):
        """(residual, l2, l1, normalisation arguments): what every solve
        takes beside its warm start. Without ``lams`` the weights are the
        coordinate's own, as device scalars (each an eager program, like
        ``FixedEffectCoordinate._solve_args``'s steps); with a validated
        grid they are host ``[K]`` arrays the lane chunks are cut from."""
        reg = self.config.regularization
        if lams is None:
            lam = self.config.regularization_weight
            l2 = jnp.asarray(reg.l2_weight(lam), dtype)
            l1 = jnp.asarray(reg.l1_weight(lam), dtype)
        else:
            l2 = np.asarray([reg.l2_weight(float(w)) for w in lams], dtype)
            l1 = np.asarray([reg.l1_weight(float(w)) for w in lams], dtype)
        norm_args = ()
        if self._norm_local is not None:
            f, s, islot = self._norm_local
            norm_args = (f,) if s is None else (f, s, islot)
        if getattr(self, "_chaos_poison_once", False):
            # fault injection (resilience/chaos.py): NaN residuals
            # poison every entity's objective (every lane's: the residual
            # is shared), like a corrupt upstream score pass
            self._chaos_poison_once = False
            residual_scores = jnp.full((self.n,), jnp.nan, dtype)
        return residual_scores, l2, l1, norm_args

    def _outcome(self, fails):
        """(failed entities, the coordinate's typed failure or None) from
        one fit's per-entity failure codes ``[E_orig]``, on the device or
        on the host. Failure isolation already happened inside the program
        (a failed entity kept its warm start), so only the count crosses
        to the host — one scalar for a device array."""
        xp = jnp if isinstance(fails, jax.Array) else np
        n_failed = int(np.asarray(xp.sum(fails != 0)))
        failure = None
        if n_failed and n_failed == fails.shape[0]:
            # EVERY entity failed: the coordinate as a whole is poisoned
            # (a bad residual pass, not a few degenerate entities)
            failure = FailureMode(int(np.asarray(xp.max(fails))))
        return n_failed, failure

    def _publish(self, iters, reasons, fails) -> None:
        """What a scalar fit leaves behind, from its ``[E_pad]`` solver
        stats on the device or on the host: ``last_tracker`` (with the
        buckets' rows, which ``lane_counts()`` needs), ``last_failed_
        entities`` and ``last_failure`` — one blocking scalar read, under
        ``re/outcome``, when the stats are on the device."""
        from photon_tpu.optim.tracking import RandomEffectOptimizationTracker
        e_orig = self._num_entities_orig
        self.last_tracker = RandomEffectOptimizationTracker(
            iterations=iters[:e_orig], reasons=reasons[:e_orig],
            bucket_rows=tuple(blk.entity_rows
                              for blk in self.dataset.blocks))
        with _obs_annotate("re/outcome"):
            self.last_failed_entities, self.last_failure = self._outcome(
                fails[:e_orig])

    def _model(self, coefficients, variances=None) -> RandomEffectModel:
        """This coordinate's model around a coefficient table."""
        return RandomEffectModel(
            coefficients=coefficients,
            random_effect_type=self.random_effect_type,
            feature_shard_id=self.feature_shard_id,
            task=self.task,
            variances=variances,
        )

    def update_model(
        self, prev: Optional[RandomEffectModel], residual_scores: Optional[Array]
    ) -> RandomEffectModel:
        with _obs_annotate("re/args"):
            dtype, coef0 = self._warm_table(prev)
            residual_scores, l2, l1, norm_args = self._solve_args(
                dtype, residual_scores)
        with _obs_annotate("re/solve"):
            coefs, iters, reasons, fails = self._solve_fn(
                self.dataset, residual_scores, coef0, l2, l1, *norm_args)
        # per-entity outcome aggregation (RandomEffectOptimizationTracker).
        # Keep the DEVICE arrays: a blocking host transfer here would
        # serialize every CD sweep on the solver's completion; the tracker
        # converts lazily when someone actually reads a summary.
        self._publish(iters, reasons, fails)
        variances = None
        from photon_tpu.types import VarianceComputationType
        if (self.variance_type != VarianceComputationType.NONE
                and self.objective.loss.has_hessian):
            with _obs_annotate("re/variance"):
                variances = self._variance_fn(self.dataset, residual_scores,
                                              coefs, l2)
                variances = variances[: self._num_entities_orig]
            _count_variances(self.random_effect_type, self.variance_type)
        # publish the model at the vocabulary's true entity count; mesh
        # padding stays an internal detail of this coordinate
        return self._model(coefs[: self._num_entities_orig], variances)

    def update_model_swept(
        self,
        prev: Optional[RandomEffectModel],
        residual_scores: Optional[Array],
        weights,
        *,
        initial_lanes=None,
        plan=None,
        hbm_budget_bytes: Optional[int] = None,
    ):
        """Fit the whole regularization grid ``weights`` over the entity
        ladder as lane-batched programs — K λ points in ONE data pass
        over every bucket, instead of K sequential ``update_model``
        calls (the random-effect half of the PR 15 sweep machinery).

        The K per-entity theta tables stack to ``[K, E, d]`` and the
        existing entity-vmap body batches over (entity-lane × λ-lane);
        per-entity failure isolation carries over per lane, and K=1 is
        bitwise ``update_model``. Device footprint is governed by a
        ``parallel/memory.BlockPlan`` (computed here unless ``plan`` is
        passed; budget from the backend unless ``hbm_budget_bytes``
        overrides): when the full-K stack exceeds the budget the grid
        degrades to ⌈K/c⌉ chunked passes — typed in the plan, recorded
        in the RunReport ``re_plan`` section, never a runtime OOM.
        Chunking never changes results (each chunk is the same
        lane-vmapped program at width c).

        ``initial_lanes [K, E, d]`` warm-starts each lane independently;
        otherwise every lane starts from ``prev``'s coefficients.
        Returns a list of K :class:`RandomEffectModel`s (variances are
        not computed on the sweep path); per-lane telemetry lands in
        ``last_lane_trackers`` / ``last_lane_failed_entities`` /
        ``last_lane_failures`` and the ``sweep.*`` metrics."""
        from photon_tpu.obs.metrics import registry
        from photon_tpu.optim import batched

        lams = batched.validate_lane_weights(weights)
        K = int(lams.size)
        ds = self.dataset
        dtype, base = self._warm_table(prev)
        base = jnp.asarray(base, dtype)
        if initial_lanes is not None:
            init = jnp.asarray(initial_lanes, dtype)
            if init.ndim != 3 or init.shape[0] != K:
                raise ValueError(
                    f"initial_lanes must be [K={K}, E, d], got "
                    f"{init.shape}")
            lanes0 = jnp.stack(
                [self._pad_entity_rows(init[k]) for k in range(K)])
        else:
            lanes0 = jnp.broadcast_to(base, (K,) + base.shape)
        plan = self._record_lane_plan(K, plan, hbm_budget_bytes)
        chunk = max(1, min(plan.lane_chunk, K))
        residual_scores, l2_all, l1_all, norm_args = self._solve_args(
            dtype, residual_scores, lams)
        coefs: list = [None] * K
        iters: list = [None] * K
        reasons: list = [None] * K
        fails: list = [None] * K
        for idx, n_real in batched.pad_lane_grid(lams, chunk):
            x0c = jnp.take(lanes0, jnp.asarray(idx), axis=0)
            with _obs_annotate("re/solve_swept"):
                co, it_c, re_c, fa_c = self._solve_swept_fn(
                    ds, residual_scores, x0c, jnp.asarray(l2_all[idx]),
                    jnp.asarray(l1_all[idx]), *norm_args)
            # padded tail lanes (repeated last λ) are dropped, never
            # published
            for j in range(n_real):
                k = int(idx[j])
                coefs[k], iters[k] = co[j], it_c[j]
                reasons[k], fails[k] = re_c[j], fa_c[j]
        # host boundary: per-lane scalars for telemetry + failure typing
        e_orig = self._num_entities_orig
        self._publish_lanes(iters, reasons,
                            [np.asarray(fails[k][:e_orig]) for k in range(K)])
        lane_medians = []
        for k in range(K):
            it_np = np.asarray(iters[k][:e_orig])
            trained = it_np[it_np >= 0]
            lane_medians.append(
                float(np.median(trained)) if trained.size else 0.0)
        registry.gauge("sweep.lanes_active").set(
            sum(1 for lf in self.last_lane_failures if lf is None))
        hist = registry.histogram(
            "sweep.lane_iterations",
            buckets=(1, 2, 5, 10, 20, 50, 100, 200, 500))
        for med in lane_medians:
            hist.observe(med)
        batched.record_sweep_run([
            {"weight": float(lams[k]),
             "entities_failed": self.last_lane_failed_entities[k],
             "iterations": lane_medians[k],
             "failure": 0 if self.last_lane_failures[k] is None
             else int(self.last_lane_failures[k])}
            for k in range(K)])
        return [self._model(coefs[k][:e_orig]) for k in range(K)]

    def _record_lane_plan(self, lanes: int, plan, hbm_budget_bytes):
        """The HBM plan of a K-lane sweep (computed unless one is passed),
        recorded for the RunReport ``re_plan`` section and kept as
        ``last_block_plan``."""
        from photon_tpu.parallel import memory as hbm

        if plan is None:
            plan = hbm.plan_for_dataset(
                self.dataset, lanes=lanes,
                history=self.config.optimizer.solver_config()
                .num_corrections,
                hbm_budget_bytes=hbm_budget_bytes,
                coordinate=self.random_effect_type)
        hbm.record_plan(plan)
        self.last_block_plan = plan
        return plan

    def _publish_lanes(self, iters, reasons, fails) -> None:
        """Per-lane telemetry of a sweep: ``last_lane_trackers`` /
        ``last_lane_failed_entities`` / ``last_lane_failures`` from each
        lane's ``[E]`` iterations and reasons and its host ``[E_orig]``
        failure codes."""
        from photon_tpu.optim.tracking import RandomEffectOptimizationTracker
        e_orig = self._num_entities_orig
        self.last_lane_trackers = [
            RandomEffectOptimizationTracker(iterations=it[:e_orig],
                                            reasons=re[:e_orig])
            for it, re in zip(iters, reasons)]
        outcomes = [self._outcome(f) for f in fails]
        self.last_lane_failed_entities = [n for n, _ in outcomes]
        self.last_lane_failures = [failure for _, failure in outcomes]

    def update_model_blocked(
        self,
        residual_scores: Optional[Array],
        *,
        warm_start=None,
        entity_names: Optional[Tuple[str, ...]] = None,
        start_block: int = 0,
        on_block=None,
        prefetch: bool = True,
    ) -> RandomEffectModel:
        """Larger-than-HBM training: sequential per-bucket solves with the
        coefficient table resident in HOST RAM, warm starts streamed from
        the cold tier.

        ``update_model`` keeps the full [E, K] table plus every solve on
        device at once; here the device only ever holds ONE size bucket's
        samples-with-warm-starts-and-results, and the [E, K] table lives
        in host memory — the training-side counterpart of serving's
        two-tier store. Semantics match ``update_model`` per entity
        (same per-entity program, same failure isolation: a failed entity
        keeps its warm start) but the blocks run sequentially with a host
        round-trip between them, so use it only when [E, K] doesn't fit.

        It is the blocked sweep (``update_model_blocked_swept``: one host
        loop, one program) at ONE lane, the coordinate's own weight,
        bitwise ``update_model`` per entity; what it leaves behind is a
        scalar fit's (``last_tracker`` / ``last_failed_entities`` /
        ``last_failure``), and neither a sweep run nor an ``re_plan`` is
        recorded for a fit that swept nothing.

        ``warm_start``: ``None`` (zeros), a host/device [E, K] array, or
        an ``io.cold_store.ColdStore`` (requires ``entity_names``: the
        entity id of each dataset row, i.e. the ingest vocabulary order).
        ``start_block`` is the resume cursor — buckets before it are
        skipped and keep their ``warm_start`` rows, so resuming a
        preempted run must pass the checkpointed coefficients (schema v4
        records the cursor per coordinate; game/checkpoint.py).
        ``on_block(next_block, num_blocks)`` fires after each bucket —
        the checkpoint hook — OUTSIDE the per-bucket solve span, so
        checkpoint I/O never pollutes ``re/solve_block`` phase timings.

        With ``prefetch`` (default), a reader thread
        (game/block_stream.BlockPrefetcher) stages bucket b+1 while
        bucket b solves — staging order, solve math, and the v4 cursor
        contract are unchanged (results stay bitwise with
        ``prefetch=False``); overlap telemetry lands in
        ``last_block_overlap`` / the ``perf.re_block_overlap`` gauge."""
        lams = np.asarray([self.config.regularization_weight], np.float64)
        out, iters, reasons, fails, _ = self._solve_blocked(
            residual_scores, lams, None, warm_start=warm_start,
            entity_names=entity_names, start_block=start_block,
            on_block=on_block, prefetch=prefetch)
        self._publish(iters[0], reasons[0], fails[0])
        # coefficients stay a HOST array — materializing [E, K] on device
        # would defeat the mode; downstream jnp ops accept numpy, and
        # io.model_io.save_game_model writes cold stores straight from it
        return self._model(out[0][: self._num_entities_orig])

    def update_model_blocked_swept(
        self,
        residual_scores: Optional[Array],
        weights,
        *,
        warm_start=None,
        entity_names: Optional[Tuple[str, ...]] = None,
        start_block: int = 0,
        on_block=None,
        plan=None,
        hbm_budget_bytes: Optional[int] = None,
        prefetch: bool = True,
    ):
        """``update_model_blocked`` × λ lanes: the K coefficient tables
        live in HOST RAM as ``[K, E, d]`` while each staged bucket is
        solved for all K λ points — one storage→device staging per
        bucket for the whole grid (the sequential sweep staged every
        bucket K times). Per-bucket lane chunking follows the
        ``parallel/memory`` plan: a bucket whose full-K lane stack
        exceeds the budget re-solves the SAME staged copy in ⌈K/c⌉
        compute passes, so degradation costs FLOPs dispatches, never
        extra staging traffic, and never changes results.

        ``warm_start``: ``None`` (zeros), ``[E, d]`` (broadcast to all
        lanes), ``[K, E, d]`` (per-lane — the resume shape), or a
        ``ColdStore`` (broadcast; requires ``entity_names``). The
        ``start_block`` cursor and ``on_block(next_block, num_blocks)``
        hook keep the v4 ``re_block_cursor`` contract — kill after
        bucket b's hook, resume at ``start_block=b+1`` with the
        checkpointed ``[K, E, d]`` table, and the result is bitwise.
        Returns a list of K :class:`RandomEffectModel`s (host-resident
        coefficients, like ``update_model_blocked``); the plan and
        per-bucket planned-vs-measured footprints land in
        ``last_block_plan`` / ``last_block_measured`` and the
        ``perf.re_peak_hbm_bytes`` gauges."""
        from photon_tpu.optim import batched
        from photon_tpu.utils import flops

        lams = batched.validate_lane_weights(weights)
        K_lanes = int(lams.size)
        plan = self._record_lane_plan(K_lanes, plan, hbm_budget_bytes)
        out, iters, reasons, fails, measured = self._solve_blocked(
            residual_scores, lams, plan, warm_start=warm_start,
            entity_names=entity_names, start_block=start_block,
            on_block=on_block, prefetch=prefetch)
        self.last_block_measured = measured
        if measured:
            flops.re_peak_hbm(
                self.random_effect_type,
                max(m["planned_peak_bytes"] for m in measured),
                max(m["measured_peak_bytes"] for m in measured))
        # host boundary: per-lane telemetry + failure typing
        e_orig = self._num_entities_orig
        self._publish_lanes(iters, reasons, [f[:e_orig] for f in fails])
        batched.record_sweep_run([
            {"weight": float(lams[k]),
             "entities_failed": self.last_lane_failed_entities[k],
             "failure": 0 if self.last_lane_failures[k] is None
             else int(self.last_lane_failures[k])}
            for k in range(K_lanes)])
        return [self._model(out[k][:e_orig]) for k in range(K_lanes)]

    def _solve_blocked(self, residual_scores: Optional[Array],
                       lams: np.ndarray, plan, *, warm_start, entity_names,
                       start_block: int, on_block, prefetch: bool):
        """The one blocked host loop: every bucket from ``start_block`` on
        staged once (prefetched while its predecessor solves) and solved
        for all of ``lams``' lanes, in the plan's lane chunks (all lanes at
        once without a plan), against ``[K, E_pad, d]`` tables in HOST RAM.
        Returns the host tables ``(coefficients, iterations, reasons,
        failures)`` and the buckets' planned-vs-measured footprints; the
        staging counters (``last_blocks_staged`` / ``last_block_overlap``)
        are set here, what a fit publishes is its public method's."""
        from photon_tpu.game import block_stream
        from photon_tpu.optim import batched
        from photon_tpu.parallel import memory as hbm
        from photon_tpu.resilience import chaos
        from photon_tpu.utils import flops

        K_lanes = int(lams.size)
        ds = self.dataset
        n_blocks = len(ds.blocks)
        if not 0 <= start_block <= n_blocks:
            raise ValueError(
                f"start_block {start_block} outside [0, {n_blocks}]")
        E_pad = ds.num_entities
        D = ds.projected_dim
        dtype = np.dtype(self._solve_dtype())
        # K host-resident coefficient tables: init from the warm-start
        # source
        if warm_start is None:
            out = np.zeros((K_lanes, E_pad, D), dtype)
        elif isinstance(warm_start, np.ndarray) or isinstance(
                warm_start, jax.Array):
            w = np.asarray(warm_start, dtype)
            out = np.zeros((K_lanes, E_pad, D), dtype)
            if w.ndim == 2:
                out[:, : min(E_pad, w.shape[0])] = w[None, :E_pad]
            elif w.ndim == 3:
                if w.shape[0] != K_lanes:
                    raise ValueError(
                        f"per-lane warm_start must be [K={K_lanes}, E, d], "
                        f"got {w.shape}")
                out[:, : min(E_pad, w.shape[1])] = w[:, :E_pad]
            else:
                raise ValueError(
                    f"warm_start must be [E, d] or [K, E, d], got "
                    f"{w.shape}")
        else:  # ColdStore, broadcast to every lane
            if entity_names is None:
                raise ValueError(
                    "ColdStore warm_start requires entity_names (entity id "
                    "per dataset row, vocabulary order)")
            from photon_tpu.game.random_effect import (
                warm_start_from_cold_store,
            )
            w = warm_start_from_cold_store(
                warm_start, entity_names, ds.projection).astype(dtype)
            extra = E_pad - w.shape[0]
            if extra > 0:
                w = np.pad(w, [(0, extra), (0, 0)])
            out = np.repeat(w[None, :E_pad], K_lanes, axis=0)
        residual_scores, l2_all, l1_all, norm_args = self._solve_args(
            dtype, residual_scores, lams)
        iters = np.full((K_lanes, E_pad), -1, np.int32)
        reasons = np.full((K_lanes, E_pad), -1, np.int32)
        fails = np.zeros((K_lanes, E_pad), np.int32)
        measured: list = []
        stream = None
        if prefetch and n_blocks - start_block > 1:
            stream = block_stream.BlockPrefetcher(
                ds.blocks, start_block=start_block)
        try:
            with _obs_span("re/solve_blocked",
                           blocks=n_blocks - start_block, lanes=K_lanes):
                for bi, (blk, dense) in enumerate(
                        zip(ds.blocks, self._dense_local_blocks)):
                    if bi < start_block:
                        continue
                    bplan = plan.buckets[bi] \
                        if plan is not None and bi < len(plan.buckets) \
                        else None
                    chunk = max(1, min(
                        bplan.lane_chunk if bplan is not None else K_lanes,
                        K_lanes))
                    ents = np.asarray(blk.entity_rows)
                    valid = (ents >= 0) & (ents < E_pad)
                    # bucket b+1 is already staging on the reader thread
                    # while this bucket solves; values are identical to
                    # the unstaged block, so parity stays bitwise
                    staged = stream.get(bi) if stream is not None else blk
                    bucket_peak = 0
                    with _obs_span("re/solve_block", block=bi):
                        for idx, n_real in batched.pad_lane_grid(
                                lams, chunk):
                            x0 = np.zeros(
                                (idx.size, ents.shape[0], D), dtype)
                            for j, k in enumerate(idx):
                                x0[j, valid] = out[k][ents[valid]]
                            x0j = jnp.asarray(x0)
                            l2c = jnp.asarray(l2_all[idx])
                            l1c = jnp.asarray(l1_all[idx])
                            with _obs_annotate("re/solve_block_swept"):
                                solved, it_b, reason_b, fail_b = \
                                    self._block_solve_swept_fn(dense)(
                                        staged, residual_scores, x0j,
                                        l2c, l1c, *norm_args)
                            # the per-bucket host round-trip IS the design
                            # here: device peak memory stays one staged
                            # bucket (+ one in flight), results land in
                            # host RAM
                            solved_np = np.asarray(solved)
                            it_np = np.asarray(it_b)
                            re_np = np.asarray(reason_b)
                            fa_np = np.asarray(fail_b)
                            # padded tail lanes (repeated last λ) are
                            # dropped, never written back
                            for j in range(n_real):
                                k = int(idx[j])
                                out[k][ents[valid]] = solved_np[j][valid]
                                iters[k][ents[valid]] = it_np[j][valid]
                                reasons[k][ents[valid]] = re_np[j][valid]
                                fails[k][ents[valid]] = fa_np[j][valid]
                            # staging copies + the c×-tiled batch the
                            # flattened-lane program materializes
                            sb = block_stream.staged_bytes(staged)
                            tiled = sb * idx.size if idx.size > 1 else 0
                            bucket_peak = max(
                                bucket_peak,
                                sb * (2 if stream is not None else 1)
                                + tiled
                                + int(x0j.nbytes) + int(solved_np.nbytes))
                    measured.append({
                        "bucket": bi,
                        "lane_chunk": chunk,
                        "strategy": bplan.strategy if bplan is not None
                        else hbm.STRATEGY_FULL,
                        "planned_peak_bytes": bplan.peak_bytes
                        if bplan is not None else 0,
                        "measured_peak_bytes": int(bucket_peak),
                    })
                    if stream is not None:
                        # results are on the host: the staged buffer is
                        # consumed — return its token to the reader
                        stream.release()
                    if on_block is not None:
                        # checkpoint hook OUTSIDE the timed solve span
                        on_block(bi + 1, n_blocks)
                    if chaos.should_kill_re_block(bi):
                        # after on_block: the cursor is durable, resume
                        # must be bitwise (the v4 contract)
                        raise chaos.SimulatedKill(
                            f"chaos: killed after re block {bi} "
                            f"checkpoint")
        finally:
            if stream is not None:
                stream.close()
        self.last_block_overlap = None
        # storage->device data passes this run (the bench's accounting
        # unit): one staging per bucket, whether prefetched or inline,
        # serves EVERY lane chunk — the (1/K)-data-passes economics
        self.last_blocks_staged = (stream.blocks_staged
                                   if stream is not None
                                   else n_blocks - start_block)
        if stream is not None:
            self.last_block_overlap = flops.re_block_overlap(
                stream.reader_busy_s, stream.consumer_stall_s,
                stream.wall_s, stream.bytes_staged,
                coordinate=self.random_effect_type)
        return out, iters, reasons, fails, measured

    @functools.cached_property
    def _variance_fn(self):
        """vmapped per-entity coefficient variances: SIMPLE = 1/diag(H),
        FULL = diag(H^-1) via Cholesky — H is each entity's [K, K] Hessian
        (reference: DistributedOptimizationProblem.computeVariances :82-100
        applied per entity; Bayesian output of RandomEffectModel)."""
        from photon_tpu.optim.problem import (
            VARIANCE_GRAM_PRECISION,
            coefficient_variances,
        )

        obj = self.objective
        vtype = self.variance_type

        def build():
            def one(feat_idx, feat_val, labels, offsets, weights, coef, l2):
                batch = DataBatch(F.SparseFeatures(feat_idx, feat_val),
                                  labels, offsets, weights)
                has_data = jnp.sum(weights) > 0
                var = coefficient_variances(obj, coef, batch,
                                            Hyper(l2_weight=l2), vtype)
                return jnp.where(has_data, var, 0.0)

            @jax.jit
            def variance_all(ds: RandomEffectDataset, residual_flat,
                             coef_block, l2):
                out = jnp.zeros_like(coef_block)
                for blk in ds.blocks:
                    offsets = _residual_offsets(blk, residual_flat)
                    coefs_b = blk.rows_from_table(coef_block, 0.0)
                    var_b = jax.vmap(one, in_axes=(0, 0, 0, 0, 0, 0, None))(
                        blk.features.indices, blk.features.values,
                        blk.labels, offsets, blk.weights, coefs_b, l2)
                    out = blk.set_rows_in_table(out, var_b)
                return out

            return variance_all

        return jitcache.get_or_build(
            ("re_variance", self.task, vtype, VARIANCE_GRAM_PRECISION), build)

    def _pad_entity_rows(self, coef_block: Array) -> Array:
        """Match a model's entity rows to this coordinate's (possibly
        mesh-padded) block: pad with zero rows or slice down."""
        extra = self.dataset.num_entities - coef_block.shape[0]
        if extra > 0:
            coef_block = jnp.pad(coef_block, [(0, extra), (0, 0)])
        elif extra < 0:
            coef_block = coef_block[: self.dataset.num_entities]
        return coef_block

    @functools.cached_property
    def _score_fn(self):
        dense_flags, mesh = self._dense_local_blocks, self.mesh
        return jitcache.get_or_build(
            ("re_score", dense_flags, mesh),
            lambda: jax.jit(_re_score_builder(dense_flags, mesh)))

    def score(self, model: RandomEffectModel) -> Array:
        with _obs_annotate("re/score"):
            return self._score_fn(self.dataset,
                                  self._pad_entity_rows(model.coefficients))

    @functools.cached_property
    def _objective_value_fn(self):
        obj = self.objective
        dense_flags = self._dense_local_blocks

        def build():
            def one_core(feats, labels, offsets, weights, coef, l2):
                return obj.value(coef, DataBatch(feats, labels, offsets,
                                                 weights), Hyper(l2_weight=l2))

            def one_sparse(feat_idx, feat_val, *rest):
                return one_core(F.SparseFeatures(feat_idx, feat_val), *rest)

            @jax.jit
            def value_all(ds: RandomEffectDataset,
                          residual_flat: Optional[Array],
                          coef_block: Array, l2: Array) -> Array:
                total = jnp.zeros((), coef_block.dtype)
                for blk, dense in zip(ds.blocks, dense_flags):
                    offsets = _residual_offsets(blk, residual_flat)
                    rows = blk.rows_from_table(coef_block, 0.0)
                    if dense:
                        vals = jax.vmap(one_core,
                                        in_axes=(0, 0, 0, 0, 0, None))(
                            blk.features.values, blk.labels, offsets,
                            blk.weights, rows, l2)
                    else:
                        vals = jax.vmap(one_sparse,
                                        in_axes=(0, 0, 0, 0, 0, 0, None))(
                            blk.features.indices, blk.features.values,
                            blk.labels, offsets, blk.weights, rows, l2)
                    total = total + jnp.sum(vals)
                return total

            return value_all

        return jitcache.get_or_build(("re_objval", self.task, dense_flags),
                                     build)

    def objective_value(self, model: Optional[RandomEffectModel],
                        residual_scores: Optional[Array]) -> Array:
        """Sum of per-entity L2-regularized objectives against a residual
        snapshot, as a DEVICE scalar (no host sync; see the fixed-effect
        counterpart). Pad entities carry zero weights and zero coefficient
        rows, so they contribute exactly 0."""
        ds = self.dataset
        dtype = (model.coefficients.dtype if model is not None
                 else (ds.blocks[0].labels.dtype if ds.blocks
                       else jnp.float32))
        coef = (model.coefficients if model is not None
                else jnp.zeros((ds.num_entities, ds.projected_dim), dtype))
        coef = self._pad_entity_rows(jnp.asarray(coef))
        l2 = jnp.asarray(self.config.regularization.l2_weight(
            self.config.regularization_weight), coef.dtype)
        return self._objective_value_fn(ds, residual_scores, coef, l2)

    def predicted_decrease(self, prev: Optional[RandomEffectModel],
                           new: RandomEffectModel,
                           residual_scores: Optional[Array]) -> Array:
        """Solver-predicted objective decrease for ``prev -> new`` against
        the FROZEN residual the solve actually saw (device scalar)."""
        return (self.objective_value(prev, residual_scores)
                - self.objective_value(new, residual_scores))

    @functools.cached_property
    def _data_loss_fn(self):
        loss = self.objective.loss

        def build():
            @jax.jit
            def loss_all(ds: RandomEffectDataset, scores_flat: Array) -> Array:
                total = jnp.zeros((), scores_flat.dtype)
                for blk in ds.blocks:
                    z = _residual_offsets(blk, scores_flat)
                    l, _ = loss.loss_and_dz(z, blk.labels)
                    total = total + jnp.sum(l * blk.weights)
                return total
            return loss_all

        return jitcache.get_or_build(("re_dataloss", self.task), build)

    def data_loss_at(self, total_scores: Array) -> Array:
        """Weighted GLM data loss at a TOTAL score vector (no features, no
        regularization), as a device scalar — the random-effect counterpart
        of ``FixedEffectCoordinate.data_loss_at`` (the entity blocks
        partition the sample space, so the block-sum equals the flat
        weighted loss; pad rows carry zero weight)."""
        return self._data_loss_fn(self.dataset, total_scores)


def _dense_flags(dataset: RandomEffectDataset) -> Tuple[bool, ...]:
    """Per-block static flag: the ELL slots are exactly the local feature
    space (every nonzero sits at slot == its local index and the ELL width
    equals the projected dim), so the block's per-entity solves can treat
    values as a DENSE [S, K] matrix — margins/Gram/gradient become plain
    dot_generals (MXU) instead of gather/scatter kernels. Common case:
    per-entity feature vectors observed in full (the MovieLens-style GLMix
    workload). Read from the host copy of the blocks."""
    D = dataset.projected_dim
    flags = []
    for blk in dataset.blocks:
        k = blk.features.values.shape[-1]
        if k != D or not getattr(blk.features.indices,
                                 "is_fully_addressable", True):
            # multi-host entity sharding: the host copy isn't
            # reachable — skip the optimization, never crash
            flags.append(False)
            continue
        idx = np.asarray(blk.features.indices)
        slot = np.broadcast_to(np.arange(k, dtype=idx.dtype), idx.shape)
        idx_ok = idx == slot
        if idx_ok.all():
            # the common from_dense layout: indices alone prove it —
            # skip the device-to-host copy of the (much larger) values
            flags.append(True)
            continue
        val = np.asarray(blk.features.values)
        flags.append(bool(np.all((val == 0) | idx_ok)))
    return tuple(flags)


def _residual_offsets(blk: EntityBlock,
                      residual_flat: Optional[Array]) -> Array:
    """A bucket's ``[E_b, S_b]`` offsets with a flat score vector injected
    (Coordinate.scala:60-63: train against residual-injected offsets); pad
    slots read a zero residual."""
    if residual_flat is None:
        return blk.offsets
    return blk.offsets + blk.rows_from_flat(residual_flat)


def _re_score_builder(dense_flags=(), mesh=None):
    """The score program; over a ``mesh`` its flat scores come back whole
    on every device (``RandomEffectDataset.rows_to_flat``)."""
    @jax.named_scope("re/score")
    def score(ds: RandomEffectDataset, coef_block: Array) -> Array:
        flags = (dense_flags if len(dense_flags) == len(ds.blocks)
                 else (False,) * len(ds.blocks))
        # active blocks: per-entity margins, in ladder order
        margins = []
        for blk, dense in zip(ds.blocks, flags):
            rows = blk.rows_from_table(coef_block, 0.0)
            if dense:
                # dense-local block: one batched [S, K] x [K] contraction
                margins.append(
                    jnp.einsum("esk,ek->es", blk.features.values, rows))
            else:
                margins.append(jnp.sum(
                    blk.features.values
                    * jax.vmap(lambda c, i: c[i])(rows, blk.features.indices),
                    axis=-1,
                ))
        # passive: gather entity coef rows (out-of-range entity -> 0)
        pcoef = coef_block.at[ds.passive_entity].get(mode="fill", fill_value=0.0)
        pmargin = jnp.sum(ds.passive_features.values
                          * jnp.take_along_axis(pcoef, ds.passive_features.indices, axis=1),
                          axis=-1)
        return ds.rows_to_flat(margins, pmargin, mesh).astype(
            coef_block.dtype)

    return score


@functools.partial(jax.jit, static_argnums=(2, 3))
def _fixed_score_whole(feats, coef: Array, n: int, mesh) -> Array:
    """``_fixed_score`` over a data-parallel mesh: the sample-sharded
    scores made whole on every device, the mesh's pad rows cut off."""
    from photon_tpu.parallel.mesh import made_whole

    with jax.named_scope("fe/score"):
        s = F.matvec(feats, coef)
    return made_whole(s, mesh)[:n]


def _count_variances(coordinate: str, variance_type) -> None:
    """One tick of ``variance.computed{coordinate, type=SIMPLE|FULL}`` an
    update that computed variances (always on; an update under NONE, or of
    a loss with no Hessian, ticks nothing)."""
    from photon_tpu.obs.metrics import registry

    registry.counter("variance.computed", coordinate=coordinate,
                     type=variance_type.name).inc()
