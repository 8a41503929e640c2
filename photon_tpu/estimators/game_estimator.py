"""GameEstimator / GameTransformer: the user-facing GAME training API.

Reference: photon-api estimators/GameEstimator.scala:55 (fit :299, train
:699, prepareTrainingDatasets :399, prepareValidationDatasetAndEvaluators
:505, warm-started multi-config fit :344-360, partial-retrain locked
coordinates :728-751), transformers/GameTransformer.scala:39 (transform
:115).

TPU re-design: datasets are built once per fit (ingest-time grouping
replaces shuffles); each optimization configuration trains via
coordinate descent (game/descent.py) warm-started from the previous
config's model, mirroring the reference's config-sweep semantics.
"""

from __future__ import annotations

import os
import dataclasses
import logging
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.evaluation.evaluators import (
    EvaluatorType,
    default_evaluator_for_task,
    evaluate,
)
from photon_tpu.evaluation.multi import (
    EvaluationSuite,
    EvaluatorSpec,
    parse_evaluator,
)
from photon_tpu.game.coordinate import FixedEffectCoordinate, RandomEffectCoordinate
from photon_tpu.game.dataset import (
    EntityVocabulary,
    GameDataFrame,
    count_placed,
    store_rows_major,
)
from photon_tpu.game.descent import (
    CoordinateDescentConfig,
    CoordinateDescentResult,
    run_coordinate_descent,
)
from photon_tpu.game.model import FixedEffectModel, GameModel, RandomEffectModel
from photon_tpu.game.random_effect import (
    RandomEffectDataConfiguration,
    RandomEffectDataset,
    build_random_effect_dataset,
)
from photon_tpu.game.scoring import GameScorer
from photon_tpu.obs import solver as _obs_solver
from photon_tpu.obs.metrics import registry
from photon_tpu.optim.problem import GLMOptimizationConfiguration
from photon_tpu.types import TaskType
from photon_tpu.utils.timing import Timed

Array = jax.Array
logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class FixedEffectDataConfiguration:
    """Reference: CoordinateDataConfiguration.scala:37."""

    feature_shard_id: str


@dataclasses.dataclass(frozen=True)
class CoordinateConfiguration:
    """Data + optimization config for one coordinate (reference:
    io/CoordinateConfiguration.scala:57,81)."""

    data: Union[FixedEffectDataConfiguration, RandomEffectDataConfiguration]
    optimization: GLMOptimizationConfiguration = GLMOptimizationConfiguration()

    @property
    def is_random_effect(self) -> bool:
        return isinstance(self.data, RandomEffectDataConfiguration)

    def with_regularization_weight(self, w: float) -> "CoordinateConfiguration":
        """Round-trips everything but the weight. Negative / non-finite
        weights are refused with a typed
        :class:`~photon_tpu.optim.batched.SweepWeightError` HERE, at
        config time — a bad sweep value must never reach a compiled
        solve."""
        from photon_tpu.optim.batched import validate_lane_weights
        w = float(validate_lane_weights([w])[0])
        return dataclasses.replace(
            self, optimization=dataclasses.replace(
                self.optimization, regularization_weight=w))


@dataclasses.dataclass
class GameResult:
    model: GameModel
    config: Dict[str, CoordinateConfiguration]
    evaluation: Optional[Dict[str, float]]
    descent: CoordinateDescentResult
    # per-coordinate convergence summaries captured at the END of THIS
    # configuration's descent (coordinates are reused across a sweep, so
    # their live trackers only ever show the last configuration)
    tracker_summaries: Dict[str, str] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class TuneResult:
    """Outcome of :meth:`GameEstimator.tune`.

    ``best_value`` follows the search's MINIMIZE convention (the primary
    metric negated when bigger-is-better); ``best_metric`` is the same
    number in the metric's natural orientation."""

    best_config: Dict[str, float]
    best_value: float
    best_metric: float
    best_model: GameModel
    rounds: List[dict]
    total_iterations: int
    observations: List[Tuple[np.ndarray, float]]


class GameEstimator:
    """Train a GAME model by coordinate descent over configured coordinates."""

    def __init__(
        self,
        task: TaskType,
        coordinate_configs: Dict[str, CoordinateConfiguration],
        update_sequence: Optional[List[str]] = None,
        num_iterations: int = 1,
        validation_evaluators: Optional[Sequence[EvaluatorType]] = None,
        locked_coordinates: Sequence[str] = (),
        dtype=jnp.float32,
        mesh=None,
        variance_computation_type=None,
        normalization_contexts=None,
        intercept_indices=None,
        feature_dtype=None,
        parallel_cd: bool = False,
        parallel_groups: Optional[List[List[str]]] = None,
        staleness_tol: float = 1e-3,
        staleness_ratio: float = 0.5,
        staleness_patience: int = 2,
    ):
        """``mesh``: a `jax.sharding.Mesh` — fixed-effect batches are
        sample-sharded and random-effect entity blocks entity-sharded over
        its data axis, so each coordinate's solve runs SPMD (SURVEY §5.8).

        ``normalization_contexts``: {feature_shard_id: NormalizationContext}
        (reference: GameEstimator.scala:55-111 threading per-coordinate
        contexts built by the driver). Fixed effects fold the context into
        their solve; random effects gather it through each entity's
        projection (NormalizationContextWrapper analog). Published models
        are ALWAYS in original feature space. ``intercept_indices``:
        {feature_shard_id: index} — required by shift-ful types.

        ``parallel_cd``: run parallel (concurrency-grouped, bounded-stale)
        coordinate-descent sweeps; ``parallel_groups`` / ``staleness_tol``
        / ``staleness_patience`` forward to
        :class:`CoordinateDescentConfig` (game/descent.py)."""
        self.task = task
        self.coordinate_configs = coordinate_configs
        self.update_sequence = update_sequence or list(coordinate_configs.keys())
        self.num_iterations = num_iterations
        # evaluator names accept the reference's grouped syntax too:
        # "AUC", "RMSE", "PRECISION@5", "AUC:userId", "PRECISION@1:queryId"
        self.evaluators: List[EvaluatorSpec] = (
            [parse_evaluator(e) for e in validation_evaluators]
            if validation_evaluators
            else [EvaluatorSpec(default_evaluator_for_task(task))])
        self.locked = frozenset(locked_coordinates)
        self.dtype = dtype
        self.mesh = mesh
        self.normalization_contexts = dict(normalization_contexts or {})
        self.intercept_indices = dict(intercept_indices or {})
        # narrower on-device feature storage (e.g. jnp.bfloat16): the
        # bandwidth-bound fixed-effect solve reads half the HBM bytes
        # while solver math stays at `dtype` via in-register promotion
        self.feature_dtype = feature_dtype
        self.parallel_cd = parallel_cd
        self.parallel_groups = parallel_groups
        self.staleness_tol = staleness_tol
        self.staleness_ratio = staleness_ratio
        self.staleness_patience = staleness_patience
        from photon_tpu.types import VarianceComputationType
        self.variance_computation_type = (
            variance_computation_type or VarianceComputationType.NONE)

    # -- dataset / coordinate preparation ----------------------------------

    def _prepare(self, df: GameDataFrame, vocab: EntityVocabulary,
                 sampling_seed: int = 0):
        """Every coordinate's dataset and solver object, from the frame.
        The host seconds of each step are ``Timed`` phases, recorded with
        telemetry on or off (a job's set-up is measured with it off):
        ``ingest/prepare/<coordinate id>/<step>`` for host work,
        ``ingest/h2d/<coordinate id>`` for placements, ``ingest/stats``
        (game/random_effect.py, game/dataset.py; PERF.md §3). A fit on a
        frame ``_prepare_cached`` already holds records none.

        With a mesh every training array stays on the host until the
        coordinate places it, each device given its shard alone
        (``parallel/mesh.shard_batch`` / ``shard_entity_blocks``): no
        device holds a whole one. The coordinate is then built inside
        ``ingest/h2d/<coordinate id>``, and the host arrays it was handed
        are counted as placed."""
        coordinates: Dict[str, object] = {}
        re_datasets: Dict[str, RandomEffectDataset] = {}
        # original (pre-RANDOM-projection) feature dims per RE coordinate —
        # persistable_artifacts needs them to back-project trained models
        self._original_dims: Dict[str, int] = {}
        # one device: the builders place; a mesh: the coordinate does
        place = self.mesh is None
        for i, (cid, cfg) in enumerate(self.coordinate_configs.items()):
            shard_id = cfg.data.feature_shard_id
            norm = self.normalization_contexts.get(shard_id)
            icpt = self.intercept_indices.get(shard_id)
            build = Timed(f"ingest/prepare/{cid}/coordinate" if place
                          else f"ingest/h2d/{cid}", level=logging.DEBUG)
            if cfg.is_random_effect:
                if norm is not None and cfg.data.projector_type == "RANDOM":
                    # contexts are defined in the original feature space;
                    # a RANDOM projector replaces that space, so the
                    # coordinate trains unnormalized (the Gaussian mix
                    # already equalizes column scales)
                    logger.warning(
                        "coordinate %s: skipping normalization under a "
                        "RANDOM projector", cid)
                    norm, icpt = None, None
                self._original_dims[cid] = df.feature_shards[shard_id].dim
                data = build_random_effect_dataset(
                    df, cfg.data, vocab, dtype=np.dtype(self.dtype).type,
                    coordinate=cid, place=place)
                with build:
                    coordinates[cid] = RandomEffectCoordinate(
                        data, df.num_samples, cfg.data.random_effect_type,
                        cfg.data.feature_shard_id, self.task,
                        cfg.optimization, mesh=self.mesh,
                        variance_type=self.variance_computation_type,
                        norm=norm, intercept_index=icpt)
                re_datasets[cid] = coordinates[cid].dataset
            else:
                data = df.fixed_effect_batch(
                    shard_id, dtype=np.dtype(self.dtype).type,
                    feature_dtype=self.feature_dtype, coordinate=cid,
                    place=place)
                if place:
                    # this X is solved on again and again: on one device
                    # it is stored in the layout the solves read
                    with Timed(f"ingest/h2d/{cid}", level=logging.DEBUG):
                        data = data._replace(features=store_rows_major(
                            data.features, cid))
                elif isinstance(data.features, np.ndarray):
                    # a mesh lays the dense X out as it shards it
                    registry.counter("ingest.row_major", coordinate=cid,
                                     outcome="mesh").inc()
                with build:
                    key = jax.random.PRNGKey(sampling_seed + i)
                    coordinates[cid] = FixedEffectCoordinate(
                        data, df.feature_shards[shard_id].dim, shard_id,
                        self.task, cfg.optimization, sampling_key=key,
                        mesh=self.mesh,
                        variance_type=self.variance_computation_type,
                        norm=norm, intercept_index=icpt)
            if not place:
                # what the coordinate sent from the host (a sparse X was
                # placed, and counted, by fixed_effect_batch)
                count_placed(cid, [a for a in jax.tree_util.tree_leaves(data)
                                   if isinstance(a, np.ndarray)])
        return coordinates, re_datasets

    def _prepare_cached(self, df: GameDataFrame):
        """Dataset preparation (entity grouping, padding, device placement)
        is a pure function of (df, data configs, dtype, mesh) — cache it
        per estimator so repeated fits on the same frame (hyperparameter
        tuning candidates, warm re-fits) skip the host-side ingest
        entirely; only regularization weights change between candidates
        and those are traced arguments of the cached solves."""
        prep_key = (self.dtype, self.feature_dtype, self.mesh,
                    tuple((cid, cfg.data)
                          for cid, cfg in self.coordinate_configs.items()))
        cached = getattr(self, "_prep_cache", None)
        # identity check on the HELD frame (not id() of a possibly-freed
        # object): the cache keeps df alive, so `is` cannot false-hit
        if (cached is not None and cached[0] is df and cached[1] == prep_key):
            vocab, coordinates, re_datasets = cached[2]
            # a fresh fit must be reproducible: the down-sampling PRNG
            # fold-in counters restart at 0 exactly as _prepare would
            # have built them (checkpoint resume overwrites them later)
            for coord in coordinates.values():
                if hasattr(coord, "_update_count"):
                    coord._update_count = 0
        else:
            vocab = EntityVocabulary()
            coordinates, re_datasets = self._prepare(df, vocab)
            self._prep_cache = (df, prep_key, (vocab, coordinates, re_datasets))
        return vocab, coordinates, re_datasets

    def _build_scorer(self, df: GameDataFrame, vocab: EntityVocabulary,
                      re_datasets: Dict[str, RandomEffectDataset]) -> GameScorer:
        scorer = GameScorer(df.num_samples, dtype=self.dtype)
        for cid, cfg in self.coordinate_configs.items():
            if cfg.is_random_effect:
                scorer.add_random_effect(cid, df, cfg.data, vocab,
                                         re_datasets[cid].projection)
            else:
                scorer.add_fixed_effect(cid, df, cfg.data.feature_shard_id)
        return scorer

    def _validation_fn(self, scorer: GameScorer, df: GameDataFrame):
        suite = EvaluationSuite(self.evaluators, df.response,
                                offsets=df.offsets, weights=df.weights,
                                id_tags=df.id_tags, dtype=self.dtype)

        def fn(model: GameModel) -> Dict[str, float]:
            # offsets are applied inside the suite
            scores = scorer.score(model, offsets=None)
            return suite.evaluate(scores).evaluations

        return fn

    # -- fitting ------------------------------------------------------------

    def fit(
        self,
        df: GameDataFrame,
        validation_df: Optional[GameDataFrame] = None,
        configurations: Optional[Sequence[Dict[str, float]]] = None,
        initial_model: Optional[GameModel] = None,
        checkpoint_dir: Optional[str] = None,
        resume: bool = False,
    ) -> List[GameResult]:
        """Train one model per configuration, warm-starting each from the
        previous (reference: GameEstimator.fit :344-360). A configuration is
        {coordinate id: regularization weight} — reg weights are traced
        arguments of the compiled solves, so a sweep recompiles nothing
        (the reference's config sweep varies exactly these weights; see
        GameEstimatorEvaluationFunction.vectorToConfiguration).
        With ``configurations=None``, one fit with the coordinates' own
        weights."""
        vocab, coordinates, re_datasets = self._prepare_cached(df)
        # a model loaded from disk must be re-packed into this fit's entity
        # order / projection slots before it can warm-start or lock coords
        from photon_tpu.io.model_io import LoadedGameModel
        if isinstance(initial_model, LoadedGameModel):
            initial_model = initial_model.aligned_to(
                vocab, {cid: np.asarray(ds.projection)
                        for cid, ds in re_datasets.items()})
        cd_config = CoordinateDescentConfig(
            update_sequence=self.update_sequence,
            num_iterations=self.num_iterations,
            locked_coordinates=self.locked,
            parallel=self.parallel_cd,
            parallel_groups=self.parallel_groups,
            staleness_tol=self.staleness_tol,
            staleness_ratio=self.staleness_ratio,
            staleness_patience=self.staleness_patience,
        )

        validation_fn = None
        if validation_df is not None:
            scorer = self._build_scorer(validation_df, vocab, re_datasets)
            validation_fn = self._validation_fn(scorer, validation_df)
        primary_bigger = self.evaluators[0].bigger_is_better

        sweeps: List[Optional[Dict[str, float]]] = (
            list(configurations) if configurations else [None])

        results: List[GameResult] = []
        warm: Optional[GameModel] = initial_model
        for config_i, sweep in enumerate(sweeps):
            if sweep is not None:
                for cid, reg_weight in sweep.items():
                    # reg weight is a traced argument of the cached jitted
                    # solve — updating it recompiles nothing
                    coordinates[cid].config = dataclasses.replace(
                        coordinates[cid].config,
                        regularization_weight=float(reg_weight))
                    self.coordinate_configs = {
                        **self.coordinate_configs,
                        cid: self.coordinate_configs[cid].with_regularization_weight(
                            float(reg_weight)),
                    }
            descent = run_coordinate_descent(
                coordinates, cd_config, df.num_samples,
                initial_model=warm, validation_fn=validation_fn,
                primary_metric_bigger_is_better=primary_bigger,
                dtype=self.dtype,
                # per-configuration checkpoint namespace (SURVEY §5.3)
                checkpoint_dir=None if checkpoint_dir is None
                else os.path.join(checkpoint_dir, f"config_{config_i:03d}"),
                resume=resume,
            )
            evaluation = None
            if validation_fn is not None:
                evaluation = validation_fn(descent.model)
            results.append(GameResult(
                model=descent.model,
                config=dict(self.coordinate_configs),
                evaluation=evaluation,
                descent=descent,
                tracker_summaries=_tracker_summaries(coordinates),
            ))
            warm = descent.model
        # expose artifacts for transformer reuse / model IO / telemetry
        self._vocab = vocab
        self._re_datasets = re_datasets
        self._coordinates = coordinates
        return results

    def fit_swept(
        self,
        df: GameDataFrame,
        validation_df: Optional[GameDataFrame] = None,
        weights: Sequence[float] = (),
    ) -> List[GameResult]:
        """Fit an l2 grid over a single fixed-effect OR single
        random-effect model as ONE lane-batched solve
        (``cli/train --sweep-l2``): one compiled program, one shared
        data pass per iteration, one :class:`GameResult` per lane. The
        fixed path scores validation lanes batched; the random path
        (:meth:`RandomEffectCoordinate.update_model_swept`) reads its
        bucket ladder once for all λ points and scores per lane through
        the ordinary scorer. Multi-coordinate / entity- or model-sharded
        estimators fall back to :meth:`fit` with one configuration per
        weight — identical results, sequential solves."""
        from photon_tpu.optim import batched
        from photon_tpu.optim.base import ConvergenceReason

        lams = batched.validate_lane_weights(weights, name="sweep-l2 grid")
        cids = list(self.coordinate_configs.keys())
        vocab, coordinates, re_datasets = self._prepare_cached(df)
        only = coordinates[cids[0]] if len(cids) == 1 else None
        opt_ok = (only is not None
                  and self.coordinate_configs[cids[0]].optimization.optimizer
                      .optimizer_type.name in ("LBFGS", "OWLQN"))
        if (opt_ok and isinstance(only, RandomEffectCoordinate)
                and only.mesh is None):
            return self._fit_swept_random_effect(
                cids[0], only, lams, validation_df, vocab, coordinates,
                re_datasets)
        if not (opt_ok and isinstance(only, FixedEffectCoordinate)
                and not only._model_sharded):
            return self.fit(df, validation_df=validation_df,
                            configurations=[{cid: float(w) for cid in cids}
                                            for w in lams])
        cid = cids[0]
        shard_id = self.coordinate_configs[cid].data.feature_shard_id
        swept = only.update_model_swept(None, None, lams)
        # the lanes' tracker by reference, like a sweep's update
        # (game/descent.py): obs.solver.lane_counts() sums it when asked
        _obs_solver.record(cid, only.last_tracker, sweep=0)
        evaluations: List[Optional[Dict[str, float]]] = [None] * len(lams)
        if validation_df is not None:
            from photon_tpu.game.coordinate import _fixed_score_lanes
            vbatch = validation_df.fixed_effect_batch(
                shard_id, dtype=np.dtype(self.dtype).type,
                feature_dtype=self.feature_dtype)
            suite = EvaluationSuite(self.evaluators, validation_df.response,
                                    offsets=validation_df.offsets,
                                    weights=validation_df.weights,
                                    id_tags=validation_df.id_tags,
                                    dtype=self.dtype)
            scores = _fixed_score_lanes(vbatch.features,
                                        jnp.asarray(swept.coefs))
            evaluations = [suite.evaluate(scores[i]).evaluations
                           for i in range(len(lams))]
        # per-lane scalars the update already brought to the host
        iters = only.last_lane_result.iterations
        reasons = only.last_lane_result.reason
        results = []
        for i, w in enumerate(lams):
            gm = GameModel({cid: FixedEffectModel(swept.models[i], shard_id)})
            results.append(GameResult(
                model=gm,
                config={cid: self.coordinate_configs[cid]
                        .with_regularization_weight(float(w))},
                evaluation=evaluations[i],
                descent=CoordinateDescentResult(
                    model=gm, best_model=gm,
                    validation_history=[evaluations[i]]
                    if evaluations[i] is not None else []),
                tracker_summaries={cid: (
                    f"{int(iters[i])} iters, "
                    f"{ConvergenceReason(int(reasons[i])).name}")},
            ))
        self._vocab = vocab
        self._re_datasets = re_datasets
        self._coordinates = coordinates
        return results

    def _fit_swept_random_effect(self, cid, coord, lams, validation_df,
                                 vocab, coordinates, re_datasets
                                 ) -> List[GameResult]:
        """The random-effect arm of :meth:`fit_swept`: all λ lanes of
        the per-entity solves ride one swept program per lane-chunk
        (bitwise-equal per lane to the sequential fits), then each
        lane's model is validated through the ordinary scorer."""
        models = coord.update_model_swept(None, None, lams)
        validation_fn = None
        if validation_df is not None:
            scorer = self._build_scorer(validation_df, vocab, re_datasets)
            validation_fn = self._validation_fn(scorer, validation_df)
        results: List[GameResult] = []
        for i, w in enumerate(lams):
            gm = GameModel({cid: models[i]})
            ev = validation_fn(gm) if validation_fn is not None else None
            tracker = coord.last_lane_trackers[i]
            results.append(GameResult(
                model=gm,
                config={cid: self.coordinate_configs[cid]
                        .with_regularization_weight(float(w))},
                evaluation=ev,
                descent=CoordinateDescentResult(
                    model=gm, best_model=gm,
                    validation_history=[ev] if ev is not None else []),
                tracker_summaries={cid: tracker.summary()},
            ))
        self._vocab = vocab
        self._re_datasets = re_datasets
        self._coordinates = coordinates
        return results

    # -- hyperparameter tuning (lane-batched ask/tell) -----------------------

    def tune(
        self,
        df: GameDataFrame,
        validation_df: GameDataFrame,
        *,
        n_rounds: int = 2,
        ask_batch: int = 4,
        mode=None,
        ranges=None,
        seed: int = 0,
        warm_start_lanes: bool = True,
    ) -> TuneResult:
        """GP / random search over regularization weights where each
        ask-batch of candidates is evaluated as ONE lane-batched solve.

        Every round asks the search for ``ask_batch`` candidates, fits
        them as K lanes of one compiled program
        (:meth:`~photon_tpu.game.coordinate.FixedEffectCoordinate
        .update_model_swept`), scores all lanes against the validation
        frame in one shared feature pass, and tells the observed values
        back. Rounds warm-start every lane from the previous round's best
        lane (``warm_start_lanes``), so later rounds converge in fewer
        solver iterations than cold starts.

        The batched path applies to a single non-model-sharded
        fixed-effect coordinate on an LBFGS/OWLQN solver (the sweepable
        family); anything else — random effects, multi-coordinate
        models — evaluates candidates sequentially through :meth:`fit`
        with the same ask/tell search loop, so tuning semantics are
        identical either way.
        """
        from photon_tpu.hyperparameter.rescaling import scale_backward
        from photon_tpu.hyperparameter.search import (
            GaussianProcessSearch,
            RandomSearch,
        )
        from photon_tpu.hyperparameter.tuner import (
            HyperparameterTuningMode,
            TuningRange,
            game_hyperparameter_defaults,
        )
        from photon_tpu.obs.metrics import registry
        from photon_tpu.optim import batched

        if mode is None:
            mode = HyperparameterTuningMode.BAYESIAN
        if mode == HyperparameterTuningMode.NONE:
            raise ValueError("tune() needs a tuning mode (BAYESIAN/RANDOM)")
        if n_rounds <= 0 or ask_batch <= 0:
            raise ValueError(
                f"tune() needs n_rounds > 0 and ask_batch > 0, got "
                f"{n_rounds}/{ask_batch}")

        cids = list(self.coordinate_configs.keys())
        if ranges is None:
            ranges = game_hyperparameter_defaults(cids)
        else:
            ranges = {cid: ranges.get(cid, TuningRange()) for cid in cids}
        log_ranges = [ranges[cid].log_range for cid in cids]

        def to_config(cand: np.ndarray) -> Dict[str, float]:
            logw = scale_backward(np.asarray(cand, float), log_ranges)
            return {cid: float(10.0 ** w) for cid, w in zip(cids, logw)}

        search_cls = (GaussianProcessSearch
                      if mode == HyperparameterTuningMode.BAYESIAN
                      else RandomSearch)
        search = search_cls(len(cids), seed=seed)
        primary = self.evaluators[0]

        vocab, coordinates, re_datasets = self._prepare_cached(df)
        only = coordinates[cids[0]] if len(cids) == 1 else None
        batched_path = (
            only is not None
            and isinstance(only, FixedEffectCoordinate)
            and not only._model_sharded
            and self.coordinate_configs[cids[0]].optimization.optimizer
                .optimizer_type.name in ("LBFGS", "OWLQN"))

        best_value = np.inf
        best_config: Dict[str, float] = {}
        best_model: Optional[GameModel] = None
        best_coef: Optional[np.ndarray] = None
        rounds: List[dict] = []
        observations: List[Tuple[np.ndarray, float]] = []
        total_iterations = 0

        if batched_path:
            cid = cids[0]
            shard_id = self.coordinate_configs[cid].data.feature_shard_id
            vbatch = validation_df.fixed_effect_batch(
                shard_id, dtype=np.dtype(self.dtype).type,
                feature_dtype=self.feature_dtype)
            suite = EvaluationSuite(self.evaluators, validation_df.response,
                                    offsets=validation_df.offsets,
                                    weights=validation_df.weights,
                                    id_tags=validation_df.id_tags,
                                    dtype=self.dtype)
            from photon_tpu.game.coordinate import _fixed_score_lanes

        for r in range(n_rounds):
            cands = search.ask(ask_batch)
            values: List[float] = []
            round_weights: List[float] = []
            round_iters: List[int] = []

            if batched_path:
                weights = [to_config(c)[cids[0]] for c in cands]
                init_lanes = None
                if warm_start_lanes and best_coef is not None:
                    # every lane starts from the previous round's best lane
                    init_lanes = np.tile(best_coef, (ask_batch, 1))
                swept = only.update_model_swept(None, None, weights,
                                                initial_lanes=init_lanes)
                scores = _fixed_score_lanes(vbatch.features,
                                            jnp.asarray(swept.coefs))
                iters = np.asarray(swept.stacked.iterations)
                for i, w in enumerate(weights):
                    metric = suite.evaluate(scores[i]).evaluations[primary.name]
                    v = -metric if primary.bigger_is_better else metric
                    lane_fail = only.last_lane_failures[i]
                    if lane_fail is not None:
                        v = np.inf  # failed lane never wins selection
                    values.append(float(v))
                    round_weights.append(float(w))
                    round_iters.append(int(iters[i]))
                    total_iterations += int(iters[i])
                    if v < best_value:
                        best_value = float(v)
                        best_config = {cids[0]: float(w)}
                        best_coef = np.asarray(swept.coefs[i])
                        best_model = GameModel({cids[0]: FixedEffectModel(
                            swept.models[i], shard_id)})
            else:
                warm = best_model if warm_start_lanes else None
                for c in cands:
                    config = to_config(c)
                    result = self.fit(df, validation_df=validation_df,
                                      configurations=[config],
                                      initial_model=warm)[-1]
                    metric = result.evaluation[primary.name]
                    v = -metric if primary.bigger_is_better else metric
                    it = sum(
                        int(np.asarray(coord.last_result.iterations))
                        for coord in self._coordinates.values()
                        if getattr(coord, "last_result", None) is not None)
                    values.append(float(v))
                    round_weights.append(
                        config[cids[0]] if len(cids) == 1 else np.nan)
                    round_iters.append(it)
                    total_iterations += it
                    if v < best_value:
                        best_value = float(v)
                        best_config = dict(config)
                        best_model = result.model

            # ±inf is a sentinel, not an observable value — feed the
            # search a finite penalty so the GP fit stays well-posed
            told = [v if np.isfinite(v)
                    else (max(x for x in values if np.isfinite(x))
                          if any(np.isfinite(x) for x in values) else 0.0)
                    for v in values]
            search.tell(cands, told)
            observations.extend(
                (np.asarray(c, float), float(v))
                for c, v in zip(cands, told))
            registry.counter("tuner.rounds").inc()
            registry.gauge("tuner.best_value").set(float(best_value))
            rounds.append({
                "round": r,
                "weights": round_weights,
                "values": values,
                "iterations": round_iters,
                "best_value": float(best_value),
                "best_config": dict(best_config),
            })
            logger.info("tune round %d: best %s -> %s", r, best_config,
                        best_value)

        batched.record_tuner_summary({
            "mode": mode.value,
            "rounds": len(rounds),
            "ask_batch": ask_batch,
            "batched": bool(batched_path),
            "warm_start_lanes": bool(warm_start_lanes),
            "best_config": dict(best_config),
            "best_value": float(best_value),
            "total_iterations": int(total_iterations),
            "round_records": rounds,
        })
        best_metric = (-best_value if primary.bigger_is_better
                       else best_value)
        return TuneResult(
            best_config=best_config,
            best_value=float(best_value),
            best_metric=float(best_metric),
            best_model=best_model,
            rounds=rounds,
            total_iterations=int(total_iterations),
            observations=observations,
        )


def _tracker_summaries(coordinates) -> Dict[str, str]:
    """Snapshot each coordinate's convergence summary (ring-buffer tracker
    when state tracking is on, basic solver stats otherwise)."""
    out: Dict[str, str] = {}
    for cid, coord in coordinates.items():
        tracker = getattr(coord, "last_tracker", None)
        if tracker is not None:
            out[cid] = tracker.summary()
            continue
        r = getattr(coord, "last_result", None)
        if r is not None:
            from photon_tpu.optim.base import ConvergenceReason
            out[cid] = (f"{int(r.iterations)} iters, "
                        f"{ConvergenceReason(int(r.reason)).name}")
    return out


def persistable_artifacts(estimator: "GameEstimator", model: GameModel,
                          base_projections=None):
    """(model, projections) ready for model IO: coordinates trained under a
    RANDOM projector are back-projected into the original feature space
    (reference: Projector.projectCoefficients) so their coefficients can be
    written as (name, term, value) records.

    ``base_projections``: optional pre-fetched {cid: np.ndarray} projection
    tables (callers saving several models hoist the device->host copy)."""
    import numpy as np

    from photon_tpu.game.model import RandomEffectModel

    projections = dict(base_projections) if base_projections is not None \
        else {cid: np.asarray(ds.projection)
              for cid, ds in estimator._re_datasets.items()}
    out_models = dict(model.models)
    for cid, cfg in estimator.coordinate_configs.items():
        if not cfg.is_random_effect or cid not in out_models:
            continue
        m = out_models[cid]
        if not isinstance(m, RandomEffectModel):
            continue
        orig_dim = estimator._original_dims.get(cid)
        rp = cfg.data.random_projection(orig_dim) if orig_dim else None
        if rp is None:
            continue
        proj = projections[cid]
        # expand projected-slot coefficients to the full projected space,
        # then back-project: w_orig = P^T w_proj
        coef_p = np.zeros((m.num_entities, rp.projected_dim))
        block = np.asarray(m.coefficients)
        for s in range(proj.shape[1]):
            cols = proj[:, s]
            ok = cols >= 0
            coef_p[ok, cols[ok]] = block[ok, s]
        coef_orig = rp.back_project_coefficients(coef_p)  # [E, D]
        E, D = coef_orig.shape
        out_models[cid] = RandomEffectModel(
            coefficients=jnp.asarray(coef_orig.astype(block.dtype)),
            random_effect_type=m.random_effect_type,
            feature_shard_id=m.feature_shard_id,
            task=m.task,
            variances=None,  # variances do not survive back-projection
        )
        projections[cid] = np.tile(np.arange(D, dtype=np.int32), (E, 1))
    return GameModel(out_models), projections


class GameTransformer:
    """Score new frames under a trained GAME model
    (reference: GameTransformer.scala:39)."""

    def __init__(self, model: GameModel, estimator: GameEstimator,
                 vocab: Optional[EntityVocabulary] = None):
        self.model = model
        self.estimator = estimator
        self.vocab = vocab if vocab is not None else getattr(estimator, "_vocab", None)
        self._re_projections = {
            cid: ds.projection
            for cid, ds in getattr(estimator, "_re_datasets", {}).items()
        }

    def transform(self, df: GameDataFrame) -> Array:
        """Total scores [n] for the frame (offsets included)."""
        est = self.estimator
        scorer = GameScorer(df.num_samples, dtype=est.dtype)
        for cid, cfg in est.coordinate_configs.items():
            if cid not in self.model:
                continue
            if cfg.is_random_effect:
                scorer.add_random_effect(cid, df, cfg.data, self.vocab,
                                         self._re_projections[cid])
            else:
                scorer.add_fixed_effect(cid, df, cfg.data.feature_shard_id)
        offsets = None if df.offsets is None else jnp.asarray(df.offsets, est.dtype)
        return scorer.score(self.model, offsets=offsets)

    def evaluate(self, df: GameDataFrame,
                 evaluators: Optional[Sequence] = None) -> Dict[str, float]:
        scores = self.transform(df)
        evs = list(evaluators) if evaluators else self.estimator.evaluators
        # transform() already adds frame offsets to the scores
        suite = EvaluationSuite(evs, df.response, weights=df.weights,
                                id_tags=df.id_tags, dtype=self.estimator.dtype)
        return suite.evaluate(scores).evaluations
