"""The training system under test for a configuration with a
``normalization``: ``benchmark/systems/training.py``'s, with the feature
statistics and the normalization contexts built the way the training driver
builds them (``photon_tpu/cli/train.py::build_normalization``: one
statistics pass a shard, the intercept found in the shard's index map) and
put where the driver's constructor call puts them. Frames and fitted
models are read through ``training``'s functions: published coefficients
are in ORIGINAL feature space whatever the solve ran in. ``objective_at``
evaluates the fitted estimator's own objective at a point of the caller's
choosing."""

from __future__ import annotations

import copy
import types
from typing import Tuple

import numpy as np

from benchmark.systems import training


def normalization(cfg: dict, frame):
    """(contexts, intercept_indices) of the configuration's one fixed
    effect, from the training frame. The shard's index map is what a job's
    feature bags give: the features by name, Photon's intercept key last
    (the column of ones the generator appended)."""
    from photon_tpu.cli.train import build_normalization
    from photon_tpu.io.index_map import IndexMap, feature_key

    (c,) = cfg["coordinates"]
    maps = {c["shard"]: IndexMap.from_keys(
        (feature_key(f"f{j:04d}") for j in range(c["width"] - 1)),
        add_intercept=cfg["intercept"])}
    args = types.SimpleNamespace(normalization_type=cfg["normalization"],
                                 data_summary_directory=None)
    contexts, intercepts, _ = build_normalization(args, frame, maps,
                                                  [c["shard"]])
    return contexts, intercepts


def estimator(cfg: dict, contexts: dict, intercepts: dict,
              max_iterations: int = 0, feature_dtype=None):
    """``training.estimator``'s ``GameEstimator`` under the contexts: they
    are the public attributes its constructor fills
    (``normalization_contexts``, ``intercept_indices``), read when a fit
    prepares the frame. ``max_iterations`` and ``feature_dtype`` are there
    for the runs that show a cut-short or lower-precision fit failing
    ``correct``."""
    if max_iterations:
        cfg = copy.deepcopy(cfg)
        for c in cfg["coordinates"]:
            c["optimizer"]["max_iterations"] = max_iterations
    est = training.estimator(cfg, feature_dtype=feature_dtype)
    est.normalization_contexts.update(contexts)
    est.intercept_indices.update(intercepts)
    return est


def objective_at(cfg: dict, est, theta) -> Tuple[float, np.ndarray]:
    """(value, gradient) of the fitted estimator's OWN regularised objective
    at the original-space coefficients ``theta``, the gradient with respect
    to them, float64 ``[width]``: ONE evaluation through what every solve
    of the coordinate evaluates (``problem.objective.value_and_gradient``:
    the aggregators, and on a TPU the kernel they route to) on the batch
    the estimator placed, the way ``GlmOptimizationProblem.run`` takes a
    warm start: into transformed space by the context's own
    ``model_to_transformed_space``, and the gradient back through that
    map's transpose (``jax.vjp``). Nothing of it depends on where a solve
    stops."""
    import jax
    import jax.numpy as jnp

    from photon_tpu.function.objective import Hyper

    (c,) = cfg["coordinates"]
    coordinate = est._coordinates[c["id"]]
    problem, batch = coordinate.problem, coordinate.batch
    objective, intercept = problem.objective, problem.intercept_index

    @jax.jit
    def at(theta, batch, l2):
        transformed, back = jax.vjp(
            lambda t: objective.norm.model_to_transformed_space(t, intercept),
            theta)
        value, gradient = objective.value_and_gradient(
            transformed, batch, Hyper(l2_weight=l2))
        return value, back(gradient)[0]

    dtype = batch.labels.dtype
    config = coordinate.config            # what ``update_model`` reads
    l2 = config.regularization.l2_weight(config.regularization_weight)
    value, gradient = at(jnp.asarray(theta, dtype), batch,
                         jnp.asarray(l2, dtype))
    return float(value), np.asarray(gradient, np.float64)
