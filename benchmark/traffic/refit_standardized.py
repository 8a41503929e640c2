"""Traffic kind ``refit_standardized``: ``refit``'s loop on RAW rows under a
``normalization``: one estimator, one prepared frame, fit after fit.

Who sends it: Photon's configuration sweep (``GameEstimator.fit(
configurations=...)``) for a job ported with ``normalization=
STANDARDIZATION`` on features in raw units: every candidate a from-zero fit
on a frame prepared once, each ending in ``block_until_ready`` on the
coefficients. Closed loop, one caller, no rate.

Set-up is a job's: the raw rows (``benchmark/generators_raw.py``: the
configuration's unit rows moved to assumed raw units, an intercept column
appended), the frame, ONE statistics pass and the contexts built from it
(``benchmark/systems/training_standardized.py``, the training driver's own
``build_normalization``), one fit (ingest, compilation), and ``correct``:
``benchmark/correct.training`` as it is, given the configuration's
reference BOUND to statistics it takes from the training rows itself, in
float64, under the configuration's ``correct_standardized`` limits (its
``rehearse.correct`` is the plain toy fit's, which
``benchmark/tests/test_rehearse.py`` makes of every configuration). The
fitted model is read in ORIGINAL space, as published; the reference maps
it into its own standardised space, so the statistics the program computed
are part of what is judged. That reading says how far the solve came;
``at_planted`` adds the one that says how exactly the normalised
evaluation computes, whatever the solve did: the estimator's own objective
and gradient at the planted model against the reference's at the same
point (``correct_standardized.at_planted``).

The window is ``refit``'s (``measure``, ``verify`` and the samples are its
own, by import: ``fits``, one ``{"start", "end", "iterations",
"evaluations", "failures"}`` a fit), and so are its readers.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import correct
from benchmark import generators as G
from benchmark import generators_raw as raw
from benchmark.systems import training, training_standardized
from benchmark.traffic.refit import GAP_LABELS, _fit, measure, verify  # noqa: F401

# what ``at_planted`` holds under ``correct_standardized.at_planted``
GAPS = ("value_gap", "gradient_gap_over_objective")


def setup(ctx) -> dict:
    cfg = ctx.cfg
    t0 = time.perf_counter()
    planted = G.planted_model(raw.unit_cfg(cfg), cfg["data_seed"])
    train = raw.game_rows(cfg, cfg["rows"], cfg["data_seed"], "train",
                          planted)
    validation = raw.game_rows(cfg, cfg["validation_rows"], cfg["data_seed"],
                               f"validation-{ctx.seed}", planted)
    generate_s = time.perf_counter() - t0
    frame = training.frame(cfg, train)
    contexts, intercepts = training_standardized.normalization(cfg, frame)
    statistics_s = time.perf_counter() - t0 - generate_s
    state = {"frame": frame, "est": training_standardized.estimator(
        cfg, contexts, intercepts)}
    first = _fit(ctx, state)
    if "error" in first:
        raise RuntimeError(f"the set-up fit failed: {first['error']}")
    state["first_fit_s"] = time.perf_counter() - t0 - generate_s
    ctx.say(f"generated {cfg['rows']} + {cfg['validation_rows']} raw rows in "
            f"{generate_s:.2f}s; frame + statistics + contexts "
            f"{statistics_s:.2f}s; with the first fit (ingest, compile or "
            f"cache load, one fit) {state['first_fit_s']:.2f}s, the fit "
            f"alone {first['end'] - first['start']:.2f}s; {first}")
    t0 = time.perf_counter()
    state["first"] = first
    state["fitted"] = training.model_tables(cfg, state["est"], state["model"])
    (c,) = cfg["coordinates"]
    bound = correct.load_reference(cfg["name"]).bind(train.x[c["shard"]])
    fit_holds, measured = correct.training(
        {**cfg, "correct": cfg["correct_standardized"]}, bound,
        state["fitted"], train, validation)
    point_holds, measured["at_planted"] = at_planted(
        cfg, state["est"], bound, train)
    state["holds"] = fit_holds and point_holds
    ctx.say(f"correct {state['holds']} (the fit {fit_holds}, the planted "
            f"point {point_holds}) in {time.perf_counter() - t0:.2f}s (the "
            f"reference's own statistics included): {measured}")
    return state


def at_planted(cfg, est, bound, train) -> tuple:
    """(holds, what was measured): ONE evaluation of the estimator's own
    objective (``training_standardized.objective_at``: the normalised
    value-and-gradient every solve evaluates, on the batch it placed)
    against the bound reference's, both at the published planted model in
    raw units, a point that is nobody's optimum and no solve chose. A
    fitted model's gradient reading is set by the iteration a float32
    solve stops at, and a rounded matrix, factor, shift or ``sum(w dz)``
    hides under it (PERF.md section 6, PR 38); here the two sides differ
    by their arithmetic alone. The gap of the gradients is taken in the
    reference's standardised space, where every coefficient has the same
    scale (in raw units the widest feature would carry the norm), over the
    objective, as the fit's reading is."""
    (c,) = cfg["coordinates"]
    theta = raw.planted_model(cfg, cfg["data_seed"])[c["id"]].astype(
        np.float32)
    value, gradient = training_standardized.objective_at(cfg, est, theta)
    want, want_gradient = correct.objective_and_gradient(
        bound, {c["id"]: theta}, train, cfg["l2"])
    gap = bound.gradient_in_transformed_space(
        gradient - want_gradient[c["id"]])
    measured = {
        "objective": want,
        "value_gap": abs(value - want) / want,
        "gradient_gap_over_objective": float(np.sqrt(np.sum(gap * gap))
                                             / want)}
    limits = cfg["correct_standardized"]["at_planted"]
    # a gap that is not a number is under no limit
    return all(measured[k] <= limits[k] for k in GAPS), measured
