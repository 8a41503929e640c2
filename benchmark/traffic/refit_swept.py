"""Traffic kind ``refit_swept``: one estimator, one prepared frame, one
regularisation GRID fitted again and again as ONE lane-batched program.

Who sends it: a Photon training job whose coordinate lists several
regularisation weights (``reg.weights=0.1|1|10|100``; every documented GAME
configuration sweeps such a list) and wants one model a weight. The call is
the one a user makes, ``GameEstimator.fit_swept(frame, weights=grid)`` with
the grid the configuration's ``l2_grid``: the K coefficient vectors are K
lanes of one solve, every lane from zero, and the design matrix is read once
an evaluation for all of them. Set-up generates the configuration's data
set, builds the frame and fits the grid once (ingest, compilation); the
window repeats the grid fit on the same frame (``_prepare_cached``), closed
loop, nothing in flight beside it. A fit ends in ``block_until_ready`` on
every lane's coefficients.

Rows as in ``refit``: the training rows are the configuration's own
(``data_seed``), ``--seed`` draws the validation rows. ``correct`` is judged
lane by lane: the configuration overlaid with the lane's ``l2`` and the
lane's limits (``correct_by_l2``), through ``benchmark/correct.training``.

Samples keep ``refit``'s keys, so its readers read them: ``fits``, one
``{"start", "end", "iterations", "evaluations", "failures"}`` a grid fit,
``iterations`` / ``evaluations`` the LARGEST lane's (the batched loop trips
until its slowest lane is done), plus ``lane_iterations`` and
``lane_evaluations``, one count a lane. The counts are the host copies the
program's one read at the end of a swept update left behind
(``FixedEffectCoordinate.last_lane_result``); a program from before it kept
them fails its set-up fit.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import correct
from benchmark import generators as G
from benchmark.systems import training

# host annotations an idle gap of the device may be labelled with: the
# program's spans of a swept update (fe/args, fe/solve_swept, fe/outcome)
# and this kind's own
GAP_LABELS = ("fe/", "fit")


def _lane_key(weight) -> str:
    return f"{weight:g}"


def _fit(ctx, state) -> dict:
    import jax

    from photon_tpu.resilience import failures

    cfg, est = ctx.cfg, state["est"]
    cid = cfg["coordinates"][0]["id"]
    failures.clear()
    sample = {"start": time.perf_counter()}
    try:
        with jax.profiler.TraceAnnotation("fit"):
            models = [r.model for r in
                      est.fit_swept(state["frame"], weights=cfg["l2_grid"])]
            jax.block_until_ready(
                [training.coefficient_arrays(cfg, m) for m in models])
        coord = est._coordinates[cid]
        lanes = coord.last_lane_result
    except Exception as e:          # a fit that raises is a failed fit
        sample.update(end=time.perf_counter(), error=repr(e))
        return sample
    sample.update(
        end=time.perf_counter(),
        iterations={cid: int(max(lanes.iterations))},
        evaluations={cid: int(max(lanes.num_fun_evals))},
        lane_iterations=[int(i) for i in lanes.iterations],
        lane_evaluations=[int(i) for i in lanes.num_fun_evals],
        failures=training.failures(cfg, est) + sum(
            f is not None for f in coord.last_lane_failures))
    state["models"] = models
    return sample


def _lane_tables(ctx, state) -> list:
    """The last grid fit's models, one reference-layout table a lane."""
    return [training.model_tables(ctx.cfg, state["est"], m)
            for m in state["models"]]


def setup(ctx) -> dict:
    cfg = ctx.cfg
    if len(cfg["coordinates"]) != 1 or cfg["coordinates"][0]["kind"] != "fixed":
        raise ValueError("refit_swept fits one fixed-effect coordinate's grid")
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
            f"{generate_s:.2f}s; frame + first grid fit (ingest, compile or "
            f"cache load, one fit) {state['first_fit_s']:.2f}s, the fit "
            f"alone {first['end'] - first['start']:.2f}s; {first}")
    t0 = time.perf_counter()
    state["first"] = first
    state["fitted"] = _lane_tables(ctx, state)
    ref = correct.load_reference(cfg["name"])
    state["holds"] = True
    for weight, tables in zip(cfg["l2_grid"], state["fitted"]):
        lane = {**cfg, "l2": weight,
                "correct": cfg["correct_by_l2"][_lane_key(weight)]}
        holds, measured = correct.training(lane, ref, tables, train,
                                           validation)
        state["holds"] = state["holds"] and holds
        ctx.say(f"lane l2={_lane_key(weight)} correct {holds} against "
                f"{lane['correct']}: {measured}")
    ctx.say(f"correct {state['holds']} over {len(cfg['l2_grid'])} lanes in "
            f"{time.perf_counter() - t0:.2f}s")
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
        ctx.say(f"{len(fits)} grid fits started, {len(done)} ended inside "
                f"{seconds:.1f}s; seconds a grid fit: quartiles {q[0]:.4f} "
                f"{q[1]:.4f} {q[2]:.4f}")
    return {"fits": fits, "end": t_end}


def verify(ctx, state, windows) -> tuple:
    """(correct, attempted, failed): every lane of the set-up fit agrees
    with the reference, every grid fit repeated its per-lane iteration and
    evaluation counts, no lane failed, and the last fit's models are the
    first's, bit for bit."""
    fits = [f for w in windows for f in w["fits"]]
    failed = sum(1 for f in fits if "error" in f or f["failures"])
    first = state["first"]
    same = all(f.get("lane_iterations") == first["lane_iterations"]
               and f.get("lane_evaluations") == first["lane_evaluations"]
               for f in fits)
    bitwise = all(np.array_equal(last[k], v)
                  for last, lane in zip(_lane_tables(ctx, state),
                                        state["fitted"])
                  for k, v in lane.items())
    ctx.say(f"every grid fit repeated {first['lane_iterations']} iterations "
            f"and {first['lane_evaluations']} evaluations a lane: {same}; "
            f"last models equal the first bit for bit: {bitwise}")
    return (state["holds"] and same and bitwise and not failed
            and not first["failures"]), len(fits), failed
