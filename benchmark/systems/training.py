"""The training system under test: a configuration's rows handed to
``GameEstimator`` the way a user's script does (chip_smoke.build_estimator
is the model), and the fitted model read back as plain arrays."""

from __future__ import annotations

from typing import Dict

import numpy as np

from benchmark.generators import GameRows


def frame(cfg: dict, rows: GameRows):
    """The rows as a ``GameDataFrame``: the fixed effect's shard dense, a
    random effect's shard as columnar CsrRows, ids as strings."""
    from photon_tpu.game.dataset import CsrRows, FeatureShard, GameDataFrame

    shards = {}
    for c in cfg["coordinates"]:
        x = rows.x[c["shard"]]
        shards[c["shard"]] = FeatureShard(
            x if c["kind"] == "fixed" else CsrRows.from_dense(x), x.shape[1])
    return GameDataFrame(
        num_samples=len(rows.y), response=rows.y, feature_shards=shards,
        id_tags={etype: list(map(str, ids.tolist()))
                 for etype, ids in rows.ids.items()})


def estimator(cfg: dict, sweeps: int = 0, feature_dtype=None):
    """``GameEstimator`` as the configuration's file describes it.
    ``sweeps`` and ``feature_dtype`` are there for the tests that show a
    cut-short or lower-precision fit failing ``correct``."""
    import jax.numpy as jnp

    from photon_tpu.estimators.game_estimator import (
        CoordinateConfiguration,
        FixedEffectDataConfiguration,
        GameEstimator,
    )
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.game.random_effect import RandomEffectDataConfiguration
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        OptimizerConfig,
    )
    from photon_tpu.types import OptimizerType, TaskType

    coords = {}
    for c in cfg["coordinates"]:
        o = c["optimizer"]
        opt = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(
                optimizer_type=OptimizerType[o["type"]],
                max_iterations=o["max_iterations"],
                tolerance=o["tolerance"],
                **({"num_corrections": o["corrections"]}
                   if "corrections" in o else {})),
            regularization=L2Regularization,
            regularization_weight=cfg["l2"])
        data = (FixedEffectDataConfiguration(c["shard"])
                if c["kind"] == "fixed"
                else RandomEffectDataConfiguration(c["entity"], c["shard"]))
        coords[c["id"]] = CoordinateConfiguration(data, opt)
    return GameEstimator(
        TaskType[cfg["task"]], coords,
        update_sequence=[c["id"] for c in cfg["coordinates"]],
        num_iterations=sweeps or cfg["sweeps"],
        dtype=jnp.dtype(cfg["dtype"]).type, feature_dtype=feature_dtype)


def coefficient_arrays(cfg: dict, model) -> list:
    """The device arrays a finished fit is waited on."""
    return [model[c["id"]].model.coefficients.means if c["kind"] == "fixed"
            else model[c["id"]].coefficients for c in cfg["coordinates"]]


def model_tables(cfg: dict, est, model) -> Dict[str, np.ndarray]:
    """The fitted model in the reference's layout: [width] for a fixed
    effect; [entities, width] for a random effect, row = the generator's
    entity number, column = feature of the shard (the program keeps rows
    in first-seen order and columns in per-entity slots)."""
    out = {}
    for c, arr in zip(cfg["coordinates"], coefficient_arrays(cfg, model)):
        a = np.asarray(arr, np.float32)
        if c["kind"] == "fixed":
            out[c["id"]] = a
            continue
        proj = np.asarray(est._re_datasets[c["id"]].projection)
        entity = np.asarray(est._vocab.names(c["entity"])).astype(np.int64)
        table = np.zeros((cfg["entities"][c["entity"]]["count"], c["width"]),
                         np.float32)
        rows, slots = np.nonzero(proj >= 0)
        table[entity[rows], proj[rows, slots]] = a[rows, slots]
        out[c["id"]] = table
    return out


def solver_iterations(cfg: dict, est) -> Dict[str, int]:
    """Last sweep: a fixed effect's iterations, a random effect's largest
    per-entity count."""
    out = {}
    for c in cfg["coordinates"]:
        coord = est._coordinates[c["id"]]
        out[c["id"]] = (int(coord.last_result.iterations)
                        if c["kind"] == "fixed"
                        else int(np.asarray(coord.last_tracker.iterations).max()))
    return out


def objective_evaluations(cfg: dict, est) -> Dict[str, int]:
    """Last sweep: objective evaluations of each fixed-effect solve (a
    random effect's vmapped solves do not count theirs)."""
    return {c["id"]: int(est._coordinates[c["id"]].last_result.num_fun_evals)
            for c in cfg["coordinates"] if c["kind"] == "fixed"}


def failures(cfg: dict, est) -> int:
    """Recorded failures plus failed entities since the last clear()."""
    from photon_tpu.resilience import failures as recorded

    return len(recorded.snapshot()) + sum(
        est._coordinates[c["id"]].last_failed_entities
        for c in cfg["coordinates"] if c["kind"] == "random")
