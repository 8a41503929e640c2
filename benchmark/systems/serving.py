"""The serving system under test: a seeded model written with
``save_game_model``, loaded by ``ServingEngine.from_model_dir`` with the
default ``ServingConfig``, warmed with the engine's own ``warmup()``; and
the request mix a cell's traffic draws from."""

from __future__ import annotations

import dataclasses
import os
import shutil
import time
from typing import Dict, List

import numpy as np

from benchmark import generators as G


def feature_name(shard: str, j: int) -> str:
    return f"{shard[:5]}{j:04d}"


def serving_cfg(cfg: dict) -> dict:
    """The configuration with its serving entity counts: serving holds the
    full tables, whatever training was cut to."""
    counts = cfg["serving"]["entities"]
    return {**cfg, "entities": {e: {**spec, "count": counts[e]}
                                for e, spec in cfg["entities"].items()}}


def write_model(cfg: dict, seed: int, model_dir: str) -> Dict[str, np.ndarray]:
    """A model of the configuration's shape with seeded coefficients, saved
    the way cli/train.save_models saves one. Returns the saved arrays."""
    import jax.numpy as jnp

    from photon_tpu.game.dataset import EntityVocabulary
    from photon_tpu.game.model import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_tpu.io.index_map import IndexMap, feature_key
    from photon_tpu.io.model_io import save_game_model
    from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
    from photon_tpu.types import TaskType

    cfg = serving_cfg(cfg)
    task = TaskType[cfg["task"]]
    tables = G.planted_model(cfg, seed)
    models, projections, index_maps = {}, {}, {}
    vocab = EntityVocabulary()
    for c in cfg["coordinates"]:
        table = tables[c["id"]]
        index_maps[c["shard"]] = IndexMap(
            {feature_key(feature_name(c["shard"], j)): j
             for j in range(c["width"])})
        if c["kind"] == "fixed":
            models[c["id"]] = FixedEffectModel(GeneralizedLinearModel(
                Coefficients(jnp.asarray(table)), task), c["shard"])
            continue
        models[c["id"]] = RandomEffectModel(
            coefficients=jnp.asarray(table), random_effect_type=c["entity"],
            feature_shard_id=c["shard"], task=task)
        vocab.build(c["entity"], list(map(str, range(len(table)))))
        projections[c["id"]] = np.tile(
            np.arange(c["width"], dtype=np.int32), (len(table), 1))
    shutil.rmtree(model_dir, ignore_errors=True)
    save_game_model(model_dir, GameModel(models), index_maps, vocab=vocab,
                    projections=projections)
    return tables


@dataclasses.dataclass
class RequestMix:
    """What the requests of one run are drawn from: a pool of feature rows
    (request ``i`` carries row ``i % pool``) and an entity of every type a
    request, -1 for one the model has never seen."""

    x: Dict[str, np.ndarray]               # shard -> [pool, width] float64
    features: List[dict]                   # row -> {shard: [(name, "", v)]}
    ids: Dict[str, np.ndarray]             # entity type -> [n] int64

    def __post_init__(self):
        self.n = len(next(iter(self.ids.values())))

    def request(self, i: int):
        from photon_tpu.serving import ScoreRequest

        k = i % self.n
        entity_ids = {e: (str(ids[k]) if ids[k] >= 0 else f"never-seen-{i}")
                      for e, ids in self.ids.items()}
        return ScoreRequest(str(i), self.features[i % len(self.features)],
                            entity_ids)


def request_mix(cfg: dict, seed: int, n: int) -> RequestMix:
    """``n`` entity draws (replay.py's folded Zipf, a share of them unknown)
    over a pool of dense feature rows, every feature of every shard."""
    spec = cfg["serving"]
    pool = spec["request_pool"]
    x, ids = {}, {}
    for c in cfg["coordinates"]:
        rng = np.random.Generator(np.random.PCG64(
            G.stream(seed, "requests", "x", c["shard"])))
        x[c["shard"]] = (rng.standard_normal((pool, c["width"]))
                         * G.feature_scale(c))
    features = [{shard: [(feature_name(shard, j), "", float(v))
                         for j, v in enumerate(rows[r])]
                 for shard, rows in x.items()} for r in range(pool)]
    for etype, count in spec["entities"].items():
        rng = np.random.Generator(np.random.PCG64(
            G.stream(seed, "requests", "ids", etype)))
        drawn = G.zipf_folded(1.0 - rng.random(n), spec["request_zipf"][etype],
                              count)
        unknown = rng.random(n) < spec.get("unknown_share", {}).get(etype, 0.0)
        ids[etype] = np.where(unknown, -1, drawn)
    return RequestMix(x, features, ids)


def setup(ctx) -> dict:
    """Write, load and warm the engine and draw the request mix: the state
    a serving kind measures on."""
    from photon_tpu.serving import ServingEngine
    from photon_tpu.utils import compile_cache

    t0 = time.perf_counter()
    model_dir = os.path.join(ctx.out_dir, "model")
    tables = write_model(ctx.cfg, ctx.seed, model_dir)
    t1 = time.perf_counter()
    engine = ServingEngine.from_model_dir(model_dir)
    info = engine.warmup()
    t2 = time.perf_counter()
    ctx.say(f"model written in {t1 - t0:.2f}s; loaded and warmed "
            f"{info['programs']} programs over buckets {info['buckets']} "
            f"in {t2 - t1:.2f}s")
    return {"engine": engine, "tables": tables, "model_load_s": t2 - t1,
            "steady0": compile_cache.compile_counts()["steady_state"],
            "mix": request_mix(ctx.cfg, ctx.seed,
                               ctx.cell["traffic"]["distinct_requests"]),
            "next": 0}                  # the number of the next request


def histogram_totals() -> Dict[str, tuple]:
    """stage -> (sum, count) of the engine's latency histograms, and
    ``batches``/``rows`` from its batch counters, as they stand now."""
    from photon_tpu.obs.metrics import registry

    snap = registry.snapshot()
    out = {}
    for key, h in snap["histograms"].items():
        if key.startswith("serving.latency_seconds{"):
            out[key.split('stage="')[1].split('"')[0]] = (h["sum"], h["count"])
    batches = rows = 0
    for key, v in snap["counters"].items():
        if key.startswith("serving.batches{"):
            batches += v
            rows += v * int(key.split('bucket="')[1].split('"')[0])
    out["batches"] = (rows, batches)
    return out


def totals_since(before: Dict[str, tuple]) -> Dict[str, tuple]:
    now = histogram_totals()
    return {k: (v[0] - before.get(k, (0, 0))[0], v[1] - before.get(k, (0, 0))[1])
            for k, v in now.items()}


def classify(mix: RequestMix, responses: Dict[int, object], sent: int) -> dict:
    """How the run's responses count: a response is whole when it carries a
    finite score and no fallback but the UNKNOWN_ENTITY its request was
    made for."""
    from photon_tpu.serving import FallbackReason

    whole = 0
    for i, r in responses.items():
        by_design = {e for e, ids in mix.ids.items() if ids[i % mix.n] < 0}
        extra = [f for f in r.fallbacks
                 if f.reason != FallbackReason.UNKNOWN_ENTITY]
        unknown = sum(1 for f in r.fallbacks
                      if f.reason == FallbackReason.UNKNOWN_ENTITY)
        if (r.score is not None and np.isfinite(r.score) and not extra
                and unknown == len(by_design)):
            whole += 1
    return {"whole": whole, "failed": sent - whole}


def verify(ctx, state, windows) -> tuple:
    """(correct, attempted, failed) of a serving run: a seeded sample of the
    scored responses agrees with the reference, every score is finite, and
    the engine built no program after its warm-up."""
    from photon_tpu.utils import compile_cache

    from benchmark import correct

    mix, cfg = state["mix"], serving_cfg(ctx.cfg)
    responses, sent = {}, 0
    for w in windows:
        responses.update(w["responses"])
        sent += w["sent"]
    counts = classify(mix, responses, sent)
    scored = np.asarray(sorted(i for i, r in responses.items()
                               if r.score is not None))
    sample = np.random.default_rng(ctx.seed).choice(
        scored, size=min(len(scored), cfg["serving"]["checked_responses"]),
        replace=False)
    holds, measured = correct.serving(
        cfg, correct.load_reference(cfg["name"]), state["tables"],
        {shard: rows[sample % len(mix.features)]
         for shard, rows in mix.x.items()},
        {e: ids[sample % mix.n] for e, ids in mix.ids.items()},
        np.asarray([responses[i].score for i in sample], np.float64))
    steady = compile_cache.compile_counts()["steady_state"] - state["steady0"]
    state["engine"].shutdown()
    ctx.say(f"sent {sent}, answered {len(responses)}, whole "
            f"{counts['whole']}; reference check {holds}: {measured}; "
            f"programs built after warm-up: {steady}")
    return holds and steady == 0, sent, counts["failed"]
