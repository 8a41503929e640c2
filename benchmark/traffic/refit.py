"""Traffic kind ``refit``: one estimator, one prepared frame, fit after fit.

Set-up generates the configuration's data set, builds the frame and fits
once; that first fit pays ingest and compilation. The window then repeats
``est.fit(df)`` from zero coefficients on the same frame, one fit after
another: ``GameEstimator._prepare_cached`` keeps the prepared dataset, so a
repeat is what Photon's configuration sweep
(``GameEstimator.fit(configurations=...)``) runs for every candidate: the
configuration's stopping rule from zero, nothing to ingest, nothing to
compile. A fit ends in ``block_until_ready`` on every coordinate's
coefficients.

The training rows are the configuration's own, drawn from its ``data_seed``
(epsilon and MovieLens are fixed data sets too); ``--seed`` draws the
validation rows ``correct`` is judged on. A fit's seconds follow its solver
iterations, and those move with the problem: three freshly drawn problems
took 9, 10 and 11 L-BFGS iterations, 17% apart in time, and the SAME GLMix
rows in another order took 1.3% longer in one order of three (my chip runs,
PR 22). Neither could be told from a regression, so every run fits the same
rows in the same order.

Samples: ``fits``, one ``{"start", "end", "iterations", "evaluations",
"failures"}`` a fit, the clock being ``time.perf_counter``; a fit that
raised has ``"error"``.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import correct
from benchmark import generators as G
from benchmark.systems import training

# host annotations an idle gap of the device may be labelled with: the
# program's spans (obs/spans.py) and this kind's own
GAP_LABELS = ("cd/", "fe/", "re/", "fit")


def _fit(ctx, state) -> dict:
    import jax

    from photon_tpu.resilience import failures

    cfg, est = ctx.cfg, state["est"]
    failures.clear()
    sample = {"start": time.perf_counter()}
    try:
        with jax.profiler.TraceAnnotation("fit"):
            model = est.fit(state["frame"])[-1].model
            jax.block_until_ready(training.coefficient_arrays(cfg, model))
    except Exception as e:          # a fit that raises is a failed fit
        sample.update(end=time.perf_counter(), error=repr(e))
        return sample
    sample.update(
        end=time.perf_counter(),
        iterations=training.solver_iterations(cfg, est),
        evaluations=training.objective_evaluations(cfg, est),
        failures=training.failures(cfg, est))
    state["model"] = model
    return sample


def setup(ctx) -> dict:
    cfg = ctx.cfg
    t0 = time.perf_counter()
    planted = G.planted_model(cfg, cfg["data_seed"])
    train = G.game_rows(cfg, cfg["rows"], cfg["data_seed"], "train", planted)
    validation = G.game_rows(cfg, cfg["validation_rows"], cfg["data_seed"],
                             f"validation-{ctx.seed}", planted)
    generate_s = time.perf_counter() - t0
    state = {"frame": training.frame(cfg, train),
             "est": training.estimator(cfg)}
    first = _fit(ctx, state)
    if "error" in first:
        raise RuntimeError(f"the set-up fit failed: {first['error']}")
    state["first_fit_s"] = time.perf_counter() - t0 - generate_s
    ctx.say(f"generated {cfg['rows']} + {cfg['validation_rows']} rows in "
            f"{generate_s:.2f}s; frame + first fit (ingest, compile or "
            f"cache load, one fit) {state['first_fit_s']:.2f}s, the fit "
            f"alone {first['end'] - first['start']:.2f}s; {first}")
    t0 = time.perf_counter()
    state["first"] = first
    state["fitted"] = training.model_tables(cfg, state["est"], state["model"])
    state["holds"], measured = correct.training(
        cfg, correct.load_reference(cfg["name"]), state["fitted"], train,
        validation)
    ctx.say(f"correct {state['holds']} in {time.perf_counter() - t0:.2f}s: "
            f"{measured}")
    return state


def measure(ctx, state, seconds: float) -> dict:
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
                f"{seconds:.1f}s; seconds a fit: quartiles {q[0]:.4f} "
                f"{q[1]:.4f} {q[2]:.4f}")
    return {"fits": fits, "end": t_end}


def verify(ctx, state, windows) -> tuple:
    """(correct, attempted, failed): the set-up fit agrees with the
    reference, every fit repeated its iteration counts, none failed, and
    the last fit's model is the first's, bit for bit."""
    fits = [f for w in windows for f in w["fits"]]
    failed = sum(1 for f in fits if "error" in f or f["failures"])
    first = state["first"]
    same = all(f.get("iterations") == first["iterations"]
               and f.get("evaluations") == first["evaluations"] for f in fits)
    last = training.model_tables(ctx.cfg, state["est"], state["model"])
    bitwise = all(np.array_equal(last[k], v)
                  for k, v in state["fitted"].items())
    ctx.say(f"every fit repeated {first['iterations']} iterations: {same}; "
            f"last model equals the first bit for bit: {bitwise}")
    return (state["holds"] and same and bitwise and not failed
            and not first["failures"]), len(fits), failed
