"""GAME training driver: ingest -> validate -> fit -> select -> persist.

Reference: photon-client cli/game/training/GameTrainingDriver.scala
(params :67-155, run :346, main :833): read Avro training/validation
data, prepare feature maps, sanity-check, compute stats + normalization,
fit one model per optimization configuration (cartesian sweep), optional
hyperparameter tuning, select + save models per ModelOutputMode
(io/ModelOutputMode.scala:20-46).

Usage:
  python -m photon_tpu.cli.train \\
    --input-data-directories data/train \\
    --validation-data-directories data/val \\
    --root-output-directory out \\
    --training-task LOGISTIC_REGRESSION \\
    --feature-shard-configuration name=global,feature.bags=features \\
    --coordinate-configuration name=fixed,feature.shard=global,\\
optimizer=LBFGS,tolerance=1e-7,max.iter=50,regularization=L2,reg.weights=1|10 \\
    --coordinate-update-sequence fixed
"""

from __future__ import annotations

import argparse
import enum
import json
import logging
import os
import sys
from typing import Dict, List, Optional

import numpy as np

from photon_tpu.cli.config import (
    ParsedCoordinate,
    expand_sweep,
    parse_coordinate_config,
    parse_feature_shard_config,
)
from photon_tpu.data.validators import DataValidationType, validate_dataframe
from photon_tpu.estimators.game_estimator import GameEstimator, GameResult
from photon_tpu.hyperparameter.tuner import (
    HyperparameterTuningMode,
    run_hyperparameter_tuning,
)
from photon_tpu.io.fast_ingest import read_frame_with_fallback
from photon_tpu.io.model_io import save_game_model
from photon_tpu.ops.normalization import NormalizationType
from photon_tpu.types import TaskType, VarianceComputationType
from photon_tpu.utils.timing import Timed

logger = logging.getLogger("photon_tpu.train")


class ModelOutputMode(enum.Enum):
    """Reference: io/ModelOutputMode.scala:20-46."""

    NONE = "NONE"          # save nothing
    BEST = "BEST"          # only the best model by validation metric
    EXPLICIT = "EXPLICIT"  # all explicitly-configured models
    TUNED = "TUNED"        # only tuned models
    ALL = "ALL"            # explicit + tuned


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="photon_tpu.train",
        description="Train a GAME model (fixed + random effects) on TPU")
    p.add_argument("--input-data-directories", nargs="+", required=True)
    p.add_argument("--input-data-date-range", default=None,
                   help="yyyymmdd-yyyymmdd: expand each input dir to its "
                        "daily/yyyy/mm/dd partitions in range (reference: "
                        "DateRange.scala:107)")
    p.add_argument("--input-data-days-range", default=None,
                   help="START-END days ago, e.g. 90-1 (DaysRange.scala)")
    p.add_argument("--validation-data-directories", nargs="*", default=[])
    p.add_argument("--validation-data-date-range", default=None)
    p.add_argument("--validation-data-days-range", default=None)
    p.add_argument("--root-output-directory", required=True)
    p.add_argument("--training-task", required=True,
                   choices=[t.value for t in TaskType])
    p.add_argument("--feature-shard-configuration", action="append",
                   required=True, dest="feature_shards")
    p.add_argument("--coordinate-configuration", action="append",
                   required=True, dest="coordinates")
    p.add_argument("--coordinate-update-sequence", required=True,
                   help="comma-separated coordinate names")
    p.add_argument("--coordinate-descent-iterations", type=int, default=1)
    p.add_argument("--validation-evaluators", nargs="*", default=None,
                   help='e.g. AUC RMSE "AUC:userId" "PRECISION@5:userId"')
    p.add_argument("--id-tag-columns", nargs="*", default=[],
                   help="record columns carrying entity ids")
    p.add_argument("--model-input-directory", default=None,
                   help="warm-start GAME model directory")
    p.add_argument("--checkpoint-directory", default=None,
                   help="publish a per-sweep mid-training checkpoint here "
                        "(params, PRNG counters, best-model bookkeeping); "
                        "SURVEY §5.3's Spark-lineage replacement")
    p.add_argument("--resume-from", default=None,
                   help="resume coordinate descent from the latest sweep "
                        "checkpoint in this directory (bitwise-equal "
                        "continuation); implies checkpointing there")
    p.add_argument("--partial-retrain-locked-coordinates", nargs="*",
                   default=[])
    p.add_argument("--output-mode", default="BEST",
                   choices=[m.value for m in ModelOutputMode])
    p.add_argument("--variance-computation-type", default="NONE",
                   choices=[v.value for v in VarianceComputationType])
    p.add_argument("--data-validation", default="VALIDATE_FULL",
                   choices=[v.value for v in DataValidationType])
    p.add_argument("--data-validation-drop-invalid", action="store_true",
                   help="drop rows with non-finite/invalid fields instead "
                        "of failing the run (counts are logged and reported "
                        "via telemetry)")
    p.add_argument("--hyper-parameter-tuning", default="NONE",
                   choices=[m.value for m in HyperparameterTuningMode])
    p.add_argument("--hyper-parameter-tuning-iter", type=int, default=0)
    p.add_argument("--hyper-parameter-shrink-radius", type=float, default=None,
                   help="narrow search ranges around the prior best before "
                        "tuning; radius in rescaled [0,1] space (reference: "
                        "ShrinkSearchRange.scala:28)")
    p.add_argument("--hyper-parameter-prior-json", default=None,
                   help="path to serialized prior observations "
                        '{"records": [{<coord>: weight, "evaluationValue": '
                        "v}]} (reference: GameHyperparameterDefaults + "
                        "HyperparameterSerialization)")
    p.add_argument("--sweep-l2", default=None,
                   help="comma-separated l2 grid, e.g. 0.1,1,10: fitted as "
                        "ONE lane-batched solve for single fixed-effect "
                        "models (optim/batched), sequential configurations "
                        "otherwise; grid values are validated typed before "
                        "any training starts")
    p.add_argument("--tune", type=int, default=0,
                   help="run N rounds of lane-batched GP tuning "
                        "(GameEstimator.tune): each round's ask-batch of "
                        "candidates is fitted as one batched solve, rounds "
                        "warm-start from the previous best lane")
    p.add_argument("--tune-ask-batch", type=int, default=4,
                   help="candidates per tuning round (= lanes per batched "
                        "solve) for --tune")
    p.add_argument("--model-sparsity-threshold", type=float, default=1e-4)
    p.add_argument("--num-devices", type=int, default=0,
                   help="shard training over this many devices (0 = single)")
    p.add_argument("--normalization-type", default="NONE",
                   choices=[t.value for t in NormalizationType],
                   help="feature normalization, built from training-data "
                        "statistics per feature shard (reference: "
                        "GameTrainingDriver.scala:556)")
    p.add_argument("--data-summary-directory", default=None,
                   help="write per-shard FeatureSummarizationResultAvro here "
                        "(reference: ModelProcessingUtils.scala:393)")
    p.add_argument("--event-listeners", nargs="*", default=[],
                   help="fully-qualified EventListener class names "
                        "(reference: Driver.scala:62-73)")
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler trace of the fit into this "
                        "directory (SURVEY §5.1: the TPU-native analog of "
                        "the reference's Timed blocks + Spark UI)")
    p.add_argument("--telemetry", action="store_true",
                   help="enable the unified telemetry subsystem (same as "
                        "PHOTON_TPU_TELEMETRY=1): phase spans, solver "
                        "trajectories, compile/memory metrics; writes "
                        "runreport.json + trace.json (Perfetto-loadable) "
                        "under --root-output-directory")
    p.add_argument("--log-level", default="INFO")
    return p


def _emit_optimization_logs(estimator, results) -> None:
    """One PhotonOptimizationLogEvent per trained configuration with the
    per-coordinate convergence summaries snapshotted per configuration
    (reference: Driver.scala PhotonOptimizationLogEvent with the
    lambda-model trackers)."""
    from photon_tpu.utils import events

    for i, result in enumerate(results):
        payload = {"configuration": i,
                   "regularization": {
                       cid: c.optimization.regularization_weight
                       for cid, c in result.config.items()}}
        for cid, summary in result.tracker_summaries.items():
            payload[f"tracker/{cid}"] = summary
        if result.evaluation is not None:
            payload["evaluation"] = dict(result.evaluation)
        events.emitter.emit(events.optimization_log_event(**payload))


def compute_shard_statistics(df, shard_ids):
    """Per-shard FeatureDataStatistics over the training frame
    (reference: GameTrainingDriver.prepareFeatureMapsAndStats).

    A shard's pass is the ``Timed`` phase ``ingest/feature_stats/<shard>``
    (beside ``ingest/stats``, which is ``padding_waste()``'s) and its
    placement counts in ``ingest.h2d_bytes{coordinate=<shard>}``. Unlike
    the other ingest phases this one WAITS for the device: the pass holds
    its own copy of the shard's matrix (``shard_features``: plain,
    uncommitted), and it has to be gone before the estimator places the
    training matrix and its rows-major copy, or a design matrix of a
    quarter of the chip is held three times."""
    import jax

    from photon_tpu.data.stats import compute_feature_stats
    from photon_tpu.game.dataset import count_placed

    out = {}
    for sid in shard_ids:
        with Timed(f"ingest/feature_stats/{sid}", level=logging.DEBUG):
            feats = df.shard_features(sid)
            count_placed(sid, feats)
            out[sid] = jax.block_until_ready(
                compute_feature_stats(feats, df.feature_shards[sid].dim))
            del feats
    return out


def build_normalization(args, df, index_maps, shard_ids):
    """(contexts, intercept_indices, stats) for the estimator + summary
    output. Stats are computed when either normalization or a summary
    directory asks for them."""
    from photon_tpu.io.index_map import INTERCEPT_KEY
    from photon_tpu.ops.normalization import build_normalization_context

    ntype = NormalizationType(args.normalization_type)
    want_stats = ntype != NormalizationType.NONE or args.data_summary_directory
    if not want_stats:
        return {}, {}, {}
    stats = compute_shard_statistics(df, shard_ids)
    intercepts = {
        sid: idx for sid, idx in
        ((sid, index_maps[sid].get_index(INTERCEPT_KEY)) for sid in shard_ids)
        if idx >= 0
    }
    contexts = {}
    if ntype != NormalizationType.NONE:
        for sid in shard_ids:
            s = stats[sid]
            contexts[sid] = build_normalization_context(
                ntype, s.mean, s.variance, s.abs_max,
                intercept_index=intercepts.get(sid))
    return contexts, intercepts, stats


def write_feature_summaries(summary_dir, stats, index_maps) -> None:
    """One Avro file per shard with per-feature summary metrics
    (reference: ModelProcessingUtils.writeBasicStatistics :393)."""
    from photon_tpu.io.avro import write_avro
    from photon_tpu.io.index_map import split_feature_key
    from photon_tpu.io.schemas import FEATURE_SUMMARIZATION_RESULT_AVRO

    for sid, s in stats.items():
        imap = index_maps[sid]
        mean = np.asarray(s.mean)
        var = np.asarray(s.variance)
        mn = np.asarray(s.min)
        mx = np.asarray(s.max)
        nnz = np.asarray(s.num_nonzeros)
        records = []
        for j in range(len(mean)):
            key = imap.get_feature_name(j)
            name, term = split_feature_key(key) if key else (str(j), "")
            records.append({
                "featureName": name,
                "featureTerm": term,
                "metrics": {"mean": float(mean[j]), "variance": float(var[j]),
                            "min": float(mn[j]), "max": float(mx[j]),
                            "numNonzeros": float(nnz[j]),
                            "count": float(s.count)},
            })
        d = os.path.join(summary_dir, sid)
        os.makedirs(d, exist_ok=True)
        write_avro(os.path.join(d, "part-00000.avro"),
                   FEATURE_SUMMARIZATION_RESULT_AVRO, records)
        logger.info("wrote %d feature summaries for shard %s under %s",
                    len(records), sid, d)


def _id_tags_needed(args, parsed: List[ParsedCoordinate]) -> List[str]:
    tags = set(args.id_tag_columns)
    for p in parsed:
        re_type = getattr(p.configuration.data, "random_effect_type", None)
        if re_type:
            tags.add(re_type)
    for ev in args.validation_evaluators or []:
        _, _, tag = str(ev).partition(":")
        if tag:
            tags.add(tag)
    return sorted(tags)


def run(args: argparse.Namespace) -> List:
    logging.basicConfig(level=args.log_level,
                        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    from photon_tpu.utils import events

    with events.driver_listeners(args.event_listeners):
        events.emitter.emit(events.setup_event(driver="game-train",
                                               params=vars(args)))
        return _run(args)


def _run(args: argparse.Namespace) -> List:
    from photon_tpu import obs
    from photon_tpu.utils import events

    if getattr(args, "telemetry", False):
        obs.configure(True)
    _root_span = obs.span("train", driver="game-train")
    _root_span.__enter__()

    task = TaskType(args.training_task)
    out_dir = args.root_output_directory
    os.makedirs(out_dir, exist_ok=True)

    sweep_l2 = None
    if args.sweep_l2:
        # typed refusal of a bad grid BEFORE any data is read or compiled
        from photon_tpu.optim.batched import validate_lane_weights
        sweep_l2 = validate_lane_weights(
            [s.strip() for s in args.sweep_l2.split(",")], name="--sweep-l2")

    shard_configs = dict(parse_feature_shard_config(s)
                         for s in args.feature_shards)
    parsed = [parse_coordinate_config(c) for c in args.coordinates]
    coordinate_configs = {p.name: p.configuration for p in parsed}
    if obs.enabled():
        # device-resident solver telemetry needs the per-iteration ring
        # buffer in the while-loop carry; honor an explicit size if the
        # config set one, otherwise use the reference's 100-state window
        import dataclasses as _dc
        for name, cfg in list(coordinate_configs.items()):
            opt = cfg.optimization.optimizer
            if opt.track_states == 0:
                coordinate_configs[name] = _dc.replace(
                    cfg, optimization=_dc.replace(
                        cfg.optimization,
                        optimizer=_dc.replace(opt, track_states=100)))
    update_sequence = [s.strip() for s in
                       args.coordinate_update_sequence.split(",")]
    unknown = set(update_sequence) - set(coordinate_configs)
    if unknown:
        raise ValueError(f"update sequence references unknown coordinates: {unknown}")
    id_tags = _id_tags_needed(args, parsed)

    from photon_tpu.utils.date_range import (
        DateRange,
        DaysRange,
        resolve_input_dirs,
    )

    def date_range_of(range_text, days_text):
        if range_text and days_text:
            raise ValueError(
                "--*-date-range and --*-days-range are mutually exclusive "
                "(reference: GameDriver treats them so)")
        if range_text:
            return DateRange.from_string(range_text)
        if days_text:
            return DaysRange.from_string(days_text).to_date_range()
        return None

    def read_frame(dirs, imaps):
        """Columnar native ingest when the schema shape and C toolchain
        allow it, generic record path otherwise (io/fast_ingest.py)."""
        return read_frame_with_fallback(dirs, shard_configs,
                                        index_maps=imaps,
                                        id_tag_columns=id_tags)

    with Timed("read training data", logger):
        input_dirs = resolve_input_dirs(
            args.input_data_directories,
            date_range_of(args.input_data_date_range,
                          args.input_data_days_range))
        df, index_maps = read_frame(input_dirs, None)
    validation_df = None
    if args.validation_data_directories:
        with Timed("read validation data", logger):
            val_dirs = resolve_input_dirs(
                args.validation_data_directories,
                date_range_of(args.validation_data_date_range,
                              args.validation_data_days_range))
            validation_df, _ = read_frame(val_dirs, index_maps)

    with Timed("data validation", logger):
        df = validate_dataframe(
            df, task, DataValidationType(args.data_validation),
            drop_invalid_rows=getattr(args, "data_validation_drop_invalid",
                                      False))

    shard_ids = sorted({p.configuration.data.feature_shard_id for p in parsed})
    with Timed("feature stats + normalization", logger):
        norm_contexts, intercepts, stats = build_normalization(
            args, df, index_maps, shard_ids)
    if args.data_summary_directory and stats:
        with Timed("write feature summaries", logger):
            write_feature_summaries(args.data_summary_directory, stats,
                                    index_maps)

    mesh = None
    if args.num_devices:
        from photon_tpu.parallel import mesh as M
        mesh = M.create_mesh(args.num_devices)

    initial_model = None
    if args.model_input_directory:
        from photon_tpu.io.model_io import load_game_model
        # pass the LoadedGameModel through — the estimator re-aligns its
        # random-effect blocks to the fresh ingest's entity/slot layout
        initial_model = load_game_model(args.model_input_directory, index_maps)
        logger.info("warm-starting from %s", args.model_input_directory)

    estimator = GameEstimator(
        task=task,
        coordinate_configs=coordinate_configs,
        update_sequence=update_sequence,
        num_iterations=args.coordinate_descent_iterations,
        validation_evaluators=args.validation_evaluators,
        locked_coordinates=args.partial_retrain_locked_coordinates,
        mesh=mesh,
        variance_computation_type=VarianceComputationType(
            args.variance_computation_type),
        normalization_contexts=norm_contexts,
        intercept_indices=intercepts,
    )

    sweeps = expand_sweep(parsed)
    events.emitter.emit(events.training_start_event(
        task=task.value, configurations=len(sweeps),
        coordinates=list(update_sequence), num_samples=df.num_samples))
    ckpt_dir = args.resume_from or args.checkpoint_directory
    import contextlib
    profile_cm = contextlib.nullcontext()
    if args.profile_dir:
        import jax
        profile_cm = jax.profiler.trace(args.profile_dir)
    from photon_tpu.resilience.failures import (
        CoordinateFailureError,
        PreemptionRequested,
    )
    try:
        with profile_cm, Timed(f"train {len(sweeps)} configuration(s)",
                               logger):
            results = estimator.fit(df, validation_df=validation_df,
                                    configurations=sweeps,
                                    initial_model=initial_model,
                                    checkpoint_dir=ckpt_dir,
                                    resume=bool(args.resume_from))
    except (PreemptionRequested, CoordinateFailureError) as e:
        # the exception carries the emergency checkpoint path published at
        # the abort boundary; flush telemetry so the RunReport records the
        # failure trail, then let main() map it to a distinct exit code
        logger.warning("training interrupted: %s", e)
        _root_span.__exit__(None, None, None)
        obs.memory.record_phase("train")
        _write_telemetry_artifacts(out_dir, mesh, len(sweeps),
                                   update_sequence)
        raise
    if sweep_l2 is not None:
        with Timed(f"lane-batched l2 sweep over {len(sweep_l2)} weights",
                   logger):
            results = results + estimator.fit_swept(
                df, validation_df=validation_df, weights=sweep_l2)
    _emit_optimization_logs(estimator, results)

    tuned = []
    if args.tune > 0:
        if validation_df is None:
            logger.warning("--tune %d requested but no "
                           "--validation-data-directories given: skipping "
                           "tuning", args.tune)
        else:
            with Timed(f"lane-batched tuning ({args.tune} rounds)", logger):
                mode = HyperparameterTuningMode(args.hyper_parameter_tuning)
                tune_res = estimator.tune(
                    df, validation_df,
                    n_rounds=args.tune, ask_batch=args.tune_ask_batch,
                    mode=None if mode == HyperparameterTuningMode.NONE
                    else mode)
            from photon_tpu.game.descent import CoordinateDescentResult
            primary = estimator.evaluators[0]
            gm = tune_res.best_model
            tuned.append(GameResult(
                model=gm,
                config={cid: estimator.coordinate_configs[cid]
                        .with_regularization_weight(w)
                        for cid, w in tune_res.best_config.items()},
                evaluation={primary.name: tune_res.best_metric},
                descent=CoordinateDescentResult(
                    model=gm, best_model=gm,
                    validation_history=[{primary.name:
                                         tune_res.best_metric}]),
            ))
            logger.info("tuned best config %s -> %s=%s",
                        tune_res.best_config, primary.name,
                        tune_res.best_metric)
    mode = HyperparameterTuningMode(args.hyper_parameter_tuning)
    if mode != HyperparameterTuningMode.NONE:
        if args.hyper_parameter_tuning_iter <= 0:
            logger.warning("--hyper-parameter-tuning %s requested but "
                           "--hyper-parameter-tuning-iter is %d: skipping "
                           "tuning", mode.value, args.hyper_parameter_tuning_iter)
        if validation_df is None:
            logger.warning("--hyper-parameter-tuning %s requested but no "
                           "--validation-data-directories given: skipping "
                           "tuning", mode.value)
    if (mode != HyperparameterTuningMode.NONE
            and args.hyper_parameter_tuning_iter > 0
            and validation_df is not None):
        with Timed("hyperparameter tuning", logger):
            prior_json = None
            if args.hyper_parameter_prior_json:
                with open(args.hyper_parameter_prior_json) as f:
                    prior_json = f.read()
            tuned = run_hyperparameter_tuning(
                estimator, df, validation_df,
                n_iterations=args.hyper_parameter_tuning_iter,
                mode=mode, prior_results=results,
                prior_json=prior_json,
                shrink_radius=args.hyper_parameter_shrink_radius)

    best = _best_result(estimator, results + tuned)
    events.emitter.emit(events.training_finish_event(
        models_trained=len(results) + len(tuned),
        best_evaluation=None if best.evaluation is None
        else dict(best.evaluation)))
    save_models(args, estimator, results, tuned, index_maps, out_dir)
    _root_span.__exit__(None, None, None)
    # the driver's root is the phase boundary the RunReport's memory
    # watermarks are sampled at (a span itself samples nothing)
    obs.memory.record_phase("train")
    _write_telemetry_artifacts(out_dir, mesh, len(sweeps), update_sequence)
    return results + tuned


def _write_telemetry_artifacts(out_dir, mesh, n_configurations,
                               update_sequence) -> None:
    """RunReport + trace flush — shared by the normal exit path and the
    preemption/failure emergency path."""
    from photon_tpu import obs

    if not obs.enabled():
        return
    try:
        report_path = os.path.join(out_dir, "runreport.json")
        obs.write_run_report(
            report_path, driver="game-train",
            mesh=mesh,
            extra={"configurations": n_configurations,
                   "coordinates": list(update_sequence)},
            aggregate=True)
        trace_path = os.path.join(out_dir, "trace.json")
        obs.write_trace(trace_path)
        logger.info("telemetry: run report at %s, trace at %s",
                    report_path, trace_path)
    except Exception as e:  # noqa: BLE001 — telemetry must never fail a run
        logger.warning("failed to write telemetry artifacts: %r", e)


def _best_result(estimator: GameEstimator, results: List):
    primary = estimator.evaluators[0]
    scored = [r for r in results if r.evaluation is not None]
    if not scored:
        return results[-1]
    return (max if primary.bigger_is_better else min)(
        scored, key=lambda r: r.evaluation[primary.name])


def save_models(args, estimator, results, tuned, index_maps, out_dir) -> None:
    mode = ModelOutputMode(args.output_mode)
    if mode == ModelOutputMode.NONE:
        return
    to_save: Dict[str, object] = {}
    if mode == ModelOutputMode.BEST:
        to_save["best"] = _best_result(estimator, results + tuned)
    else:
        if mode in (ModelOutputMode.EXPLICIT, ModelOutputMode.ALL):
            for i, r in enumerate(results):
                to_save[f"models/{i}"] = r
        if mode in (ModelOutputMode.TUNED, ModelOutputMode.ALL):
            for i, r in enumerate(tuned):
                to_save[f"tuned/{i}"] = r
        to_save["best"] = _best_result(estimator, results + tuned)

    from photon_tpu.estimators.game_estimator import persistable_artifacts
    base_projections = {cid: np.asarray(ds.projection)
                        for cid, ds in estimator._re_datasets.items()}
    for rel, result in to_save.items():
        d = os.path.join(out_dir, rel)
        with Timed(f"save model {rel}", logger):
            # RANDOM-projected coordinates are back-projected into the
            # original feature space before hitting disk (reference:
            # Projector.projectCoefficients); INDEX_MAP/IDENTITY pass through
            model, projections = persistable_artifacts(
                estimator, result.model, base_projections=base_projections)
            save_game_model(
                d, model, index_maps,
                vocab=estimator._vocab, projections=projections,
                coordinate_configs=result.config,
                sparsity_threshold=args.model_sparsity_threshold)
        if result.evaluation is not None:
            with open(os.path.join(d, "evaluation.json"), "w") as f:
                json.dump(result.evaluation, f, indent=2)
    logger.info("saved %d model(s) under %s", len(to_save), out_dir)


def main(argv: Optional[List[str]] = None) -> None:
    from photon_tpu.resilience import shutdown as _shutdown
    from photon_tpu.resilience.failures import (
        EXIT_COORDINATE_FAILURE,
        EXIT_PREEMPTED,
        CoordinateFailureError,
        PreemptionRequested,
    )
    from photon_tpu.utils.compile_cache import maybe_enable
    maybe_enable()
    # SIGTERM/SIGINT -> graceful stop at the next coordinate boundary with
    # an emergency checkpoint (resilience/shutdown.py); a second SIGINT
    # still kills immediately
    _shutdown.install()
    try:
        run(build_arg_parser().parse_args(argv))
    except PreemptionRequested as e:
        logger.warning("preempted (%s); emergency checkpoint: %s",
                       _shutdown.reason(), e.checkpoint_path)
        sys.exit(EXIT_PREEMPTED)
    except CoordinateFailureError as e:
        logger.error("training aborted: %s (resume from checkpoint: %s)",
                     e, e.checkpoint_path)
        sys.exit(EXIT_COORDINATE_FAILURE)
    finally:
        _shutdown.uninstall()


if __name__ == "__main__":
    main()
