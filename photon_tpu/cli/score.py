"""GAME scoring driver: load model -> score data -> write results.

Reference: photon-client cli/game/scoring/GameScoringDriver.scala:39
(run :136 — read data, load GAME model, GameTransformer.transform,
optional evaluation, saveScoresToHDFS :187 as ScoringResultAvro).
"""

from __future__ import annotations

import argparse
import json
import logging
import os
from typing import List, Optional

import numpy as np
import jax.numpy as jnp

from photon_tpu.cli.config import parse_feature_shard_config
from photon_tpu.evaluation.multi import EvaluationSuite
from photon_tpu.game.random_effect import RandomEffectDataConfiguration
from photon_tpu.game.scoring import GameScorer
from photon_tpu.io.data_io import write_scores
from photon_tpu.io.model_io import load_game_model
from photon_tpu.game.model import RandomEffectModel
from photon_tpu.utils.timing import Timed

logger = logging.getLogger("photon_tpu.score")


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="photon_tpu.score",
        description="Score data under a trained GAME model")
    p.add_argument("--input-data-directories", nargs="+", required=True)
    p.add_argument("--model-input-directory", required=True)
    p.add_argument("--root-output-directory", required=True)
    p.add_argument("--feature-shard-configuration", action="append",
                   required=True, dest="feature_shards")
    p.add_argument("--evaluators", nargs="*", default=[],
                   help='e.g. AUC "AUC:userId"')
    p.add_argument("--id-tag-columns", nargs="*", default=[])
    p.add_argument("--model-id", default="photon_tpu")
    p.add_argument("--event-listeners", nargs="*", default=[],
                   help="fully-qualified EventListener class names "
                        "(reference: Driver.scala:62-73)")
    p.add_argument("--telemetry", action="store_true",
                   help="enable the unified telemetry subsystem (same as "
                        "PHOTON_TPU_TELEMETRY=1); writes runreport.json + "
                        "trace.json under --root-output-directory")
    p.add_argument("--log-level", default="INFO")
    return p


def run(args: argparse.Namespace) -> np.ndarray:
    logging.basicConfig(level=args.log_level,
                        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    from photon_tpu.utils import events

    with events.driver_listeners(args.event_listeners):
        events.emitter.emit(events.setup_event(driver="game-score",
                                               params=vars(args)))
        return _run(args)


def _run(args: argparse.Namespace) -> np.ndarray:
    from photon_tpu import obs
    from photon_tpu.utils import events

    if getattr(args, "telemetry", False):
        obs.configure(True)
    _root_span = obs.span("score", driver="game-score")
    _root_span.__enter__()

    out_dir = args.root_output_directory
    os.makedirs(out_dir, exist_ok=True)

    shard_configs = dict(parse_feature_shard_config(s)
                         for s in args.feature_shards)

    with Timed("read scoring data", logger):
        from photon_tpu.io.fast_ingest import read_frame_with_fallback
        df, index_maps, records = read_frame_with_fallback(
            args.input_data_directories, shard_configs, return_records=True)

    with Timed("load model", logger):
        loaded = load_game_model(args.model_input_directory, index_maps)

    id_tags = set(args.id_tag_columns)
    for m in loaded.model.models.values():
        if isinstance(m, RandomEffectModel):
            id_tags.add(m.random_effect_type)
    for ev in args.evaluators:
        _, _, tag = str(ev).partition(":")
        if tag:
            id_tags.add(tag)
    # id-tag columns become known only after the model loads; extract them
    # from the (bag-free on the fast path) records with the single
    # None-handling rule shared by every ingest path
    from photon_tpu.io.data_io import extract_id_tags
    df.id_tags.update(extract_id_tags(records, sorted(id_tags)))

    with Timed("score", logger):
        scorer = GameScorer(df.num_samples)
        for cid, m in loaded.model.models.items():
            if isinstance(m, RandomEffectModel):
                scorer.add_random_effect(
                    cid, df,
                    RandomEffectDataConfiguration(m.random_effect_type,
                                                  m.feature_shard_id),
                    loaded.vocab, loaded.projections[cid])
            else:
                scorer.add_fixed_effect(cid, df, m.feature_shard_id)
        offsets = None if df.offsets is None else jnp.asarray(df.offsets)
        scores = np.asarray(scorer.score(loaded.model, offsets=offsets))

    with Timed("write scores", logger):
        uids = [r.get("uid") for r in records]
        write_scores(os.path.join(out_dir, "scores", "part-00000.avro"),
                     scores,
                     labels=df.response,
                     weights=None if df.weights is None else df.weights,
                     uids=uids if any(u is not None for u in uids) else None,
                     model_id=args.model_id)

    evaluations = None
    if args.evaluators:
        suite = EvaluationSuite(args.evaluators, df.response,
                                weights=df.weights, id_tags=df.id_tags)
        results = suite.evaluate(jnp.asarray(scores))
        evaluations = results.evaluations
        with open(os.path.join(out_dir, "evaluation.json"), "w") as f:
            json.dump(evaluations, f, indent=2)
        logger.info("evaluation: %s", evaluations)
    events.emitter.emit(events.Event(
        "ScoringFinishEvent",
        payload={"num_scored": int(len(scores)),
                 "evaluation": evaluations}))
    _root_span.__exit__(None, None, None)
    obs.memory.record_phase("score")
    if obs.enabled():
        try:
            obs.write_run_report(
                os.path.join(out_dir, "runreport.json"), driver="game-score",
                extra={"num_scored": int(len(scores))}, aggregate=True)
            obs.write_trace(os.path.join(out_dir, "trace.json"))
        except Exception as e:  # noqa: BLE001 — telemetry must never fail a run
            logger.warning("failed to write telemetry artifacts: %r", e)
    return scores


def main(argv: Optional[List[str]] = None) -> None:
    from photon_tpu.utils.compile_cache import maybe_enable
    maybe_enable()
    run(build_arg_parser().parse_args(argv))


if __name__ == "__main__":
    main()
