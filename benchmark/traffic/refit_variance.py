"""Traffic kind ``refit_variance``: ``refit``'s loop for a job that
publishes a Bayesian model: one estimator, one prepared frame, fit after
fit, every fit the solve AND the coefficient variances.

Who sends it: Photon's configuration sweep (``GameEstimator.fit(
configurations=...)``) for a job run with a variance computation type
(``FULL``: ``diag(H^-1)``): upstream computes the variances inside every
coordinate ``run``, so every candidate is a from-zero fit with its
variances, on a frame prepared once. Closed loop, ONE caller, no rate, no
think time. A fit ends in ``block_until_ready`` on the means AND on the
variances (``training_variance.wait``): ``refit``'s ``_fit`` waits on the
means alone, and the variance is a second program dispatched after the
solve's result was read, so this kind has its own.

Set-up is ``refit``'s (the configuration's rows from its ``data_seed``, the
frame, one fit: ingest and compilation) and ``correct`` takes two readings:
``benchmark/correct.training`` as it is for the fitted means (how far the
solve came), and ``variance_gap``: the largest, over the coefficients, of
``|program's variance - reference's| / reference's``, the reference's
(``benchmark/reference/<config>.py``: ``curvature`` over row blocks, summed
in float64 on the host, ``variances`` of the sum) evaluated AT THE
PROGRAM'S OWN FITTED MEANS, so that no solve moves it: how exactly the
variance computes, under ``correct_variance.relative_gap``.

Samples: ``fits``, ``refit``'s ``{"start", "end", "iterations",
"evaluations", "failures"}`` a fit, and ``"waited_on"`` (the names of the
arrays the fit was waited on), ``"ready"`` (whether everything the model
publishes was on the device when ``end`` was taken) and ``"variances"``
(ticks of the program's ``variance.computed{type=FULL}`` during the fit).
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import correct
from benchmark import generators as G
from benchmark.systems import training, training_variance
from benchmark.traffic import refit
from benchmark.traffic.refit import GAP_LABELS  # noqa: F401


def _fit(ctx, state) -> dict:
    import jax

    from photon_tpu.resilience import failures

    cfg, est = ctx.cfg, state["est"]
    failures.clear()
    counted = training_variance.variances_counted()
    sample = {"start": time.perf_counter()}
    try:
        with jax.profiler.TraceAnnotation("fit"):
            model = est.fit(state["frame"])[-1].model
            waited_on = training_variance.wait(cfg, model)
    except Exception as e:          # a fit that raises is a failed fit
        sample.update(end=time.perf_counter(), error=repr(e))
        return sample
    sample.update(
        end=time.perf_counter(),
        waited_on=waited_on,
        ready=all(a.is_ready() for a in
                  training_variance.published(cfg, model).values()),
        variances=training_variance.variances_counted() - counted,
        iterations=training.solver_iterations(cfg, est),
        evaluations=training.objective_evaluations(cfg, est),
        failures=training.failures(cfg, est))
    state["model"] = model
    return sample


def reference_variances(ref, params, rows, l2) -> dict:
    """``{coordinate id: [width] float64}``: the reference's variances at
    ``params``. It reads X in ``correct``'s row blocks, one block on the
    device beside the program's copy, and its blocks' Hessians are summed in
    float64 on the host, as ``correct.objective_and_gradient`` sums a
    gradient's."""
    import jax

    step = jax.jit(ref.curvature)
    dev = jax.device_put(params)
    hessian = {}
    for x, ids, y, weight in correct._blocks(rows):
        for k, h in step(dev, x, ids, y, weight).items():
            hessian[k] = hessian.get(k, 0.0) + np.asarray(h, np.float64)
    return {k: np.asarray(v, np.float64)
            for k, v in ref.variances(hessian, l2).items()}


def variance_gap(cfg, ref, fitted, variances, train) -> tuple:
    """(holds, what was measured): the program's variances against the
    reference's at the program's own fitted means, coefficient by
    coefficient, relative to the reference's."""
    want = reference_variances(ref, fitted, train, cfg["l2"])
    gaps = {k: float(np.max(np.abs(variances[k] - want[k]) / want[k]))
            if k in variances else float("nan") for k in want}
    measured = {
        "relative_gap": gaps,
        "reference_variance_range": {
            k: [float(v.min()), float(v.max())] for k, v in want.items()}}
    limit = cfg["correct_variance"]["relative_gap"]
    # a gap that is not a number (no variances published) is under no limit
    return all(g <= limit for g in gaps.values()), measured


def setup(ctx) -> dict:
    cfg = ctx.cfg
    t0 = time.perf_counter()
    planted = G.planted_model(cfg, cfg["data_seed"])
    train = G.game_rows(cfg, cfg["rows"], cfg["data_seed"], "train", planted)
    validation = G.game_rows(cfg, cfg["validation_rows"], cfg["data_seed"],
                             f"validation-{ctx.seed}", planted)
    generate_s = time.perf_counter() - t0
    state = {"frame": training.frame(cfg, train),
             "est": training_variance.estimator(cfg)}
    first = _fit(ctx, state)
    if "error" in first:
        raise RuntimeError(f"the set-up fit failed: {first['error']}")
    state["first_fit_s"] = time.perf_counter() - t0 - generate_s
    ctx.say(f"generated {cfg['rows']} + {cfg['validation_rows']} rows in "
            f"{generate_s:.2f}s; frame + first fit (ingest, compile or "
            f"cache load, one fit with its variances) "
            f"{state['first_fit_s']:.2f}s, the fit alone "
            f"{first['end'] - first['start']:.2f}s; {first}")
    t0 = time.perf_counter()
    state["first"] = first
    state["fitted"] = training.model_tables(cfg, state["est"], state["model"])
    state["variances"] = training_variance.variance_tables(cfg, state["model"])
    ref = correct.load_reference(cfg["name"])
    fit_holds, measured = correct.training(cfg, ref, state["fitted"], train,
                                           validation)
    variance_holds, measured["variance"] = variance_gap(
        cfg, ref, state["fitted"], state["variances"], train)
    state["holds"] = fit_holds and variance_holds
    ctx.say(f"correct {state['holds']} (the fit {fit_holds}, the variances "
            f"{variance_holds}) in {time.perf_counter() - t0:.2f}s: "
            f"{measured}")
    return state


def measure(ctx, state, seconds: float) -> dict:
    """``refit.measure`` with this kind's ``_fit``."""
    fits = []
    t_end = time.perf_counter() + seconds
    while time.perf_counter() < t_end:
        fits.append(_fit(ctx, state))
        if "error" in fits[-1]:
            break
    done = [f["end"] - f["start"] for f in fits
            if "error" not in f and f["end"] <= t_end]
    if done:
        q = np.percentile(done, [25, 50, 75])
        ctx.say(f"{len(fits)} fits started, {len(done)} ended inside "
                f"{seconds:.1f}s; seconds a fit with its variances: "
                f"quartiles {q[0]:.4f} {q[1]:.4f} {q[2]:.4f}")
    return {"fits": fits, "end": t_end}


def verify(ctx, state, windows) -> tuple:
    """(correct, attempted, failed): what ``refit.verify`` holds (the
    set-up fit agrees with the reference, every fit repeated its iteration
    counts, none failed, the last means equal the first bit for bit), and:
    every fit was waited on its variances and found them ready, the
    program counted one variance computation a fit (where it counts any),
    and the last fit's variances equal the first's bit for bit."""
    holds, attempted, failed = refit.verify(ctx, state, windows)
    cfg = ctx.cfg
    fits = [state["first"]] + [f for w in windows for f in w["fits"]
                               if "error" not in f]
    fixed = [c["id"] for c in cfg["coordinates"] if c["kind"] == "fixed"]
    waited = all(f"{cid}.variances" in f["waited_on"] and f["ready"]
                 for f in fits for cid in fixed)
    counted = sum(f["variances"] for f in fits)
    last = training_variance.variance_tables(cfg, state["model"])
    bitwise = (set(last) == set(fixed) == set(state["variances"])
               and all(np.array_equal(last[k], v)
                       for k, v in state["variances"].items()))
    ctx.say(f"every fit waited on its variances and found them ready: "
            f"{waited}; the program counted {counted:g} variance "
            f"computations in {len(fits)} fits; last variances equal the "
            f"first bit for bit: {bitwise}")
    return (holds and waited and bitwise
            and counted in (0, len(fits) * len(fixed))), attempted, failed
