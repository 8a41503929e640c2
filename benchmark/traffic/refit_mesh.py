"""Traffic kind ``refit_mesh``: ``refit``'s loop with the estimator over the
configuration's mesh.

Who sends it: Photon's configuration sweep (``GameEstimator.fit(
configurations=...)``) for a job whose training set does not fit one chip,
spread over the chips of one host as Photon spreads it over executors: every
candidate a from-zero fit on a frame prepared once, each ending in
``block_until_ready`` on the coefficients. Closed loop, one caller, no rate.

Set-up is ``refit``'s with one change: ``training.estimator``'s result is
handed a ``Mesh`` of the configuration's ``mesh`` sizes (``{"data": 4}``:
samples sharded for the fixed effect, entity blocks for the random effects,
coefficients replicated), the way a user's script hands it one. Its log
says what the mesh padded and staged (``mesh.entity_slots``,
``mesh.staged_bytes``) and, after the window, each chip's peak memory and
the host's.

``correct`` takes ``refit``'s reading (``correct.training``: the fitted
model's gradient over the objective and the validation AUC) and two more.
``at_planted`` is one that no solve moves: ONE evaluation of the fixed
effect's own objective and gradient, on the batch the estimator placed over
the mesh, at the planted fixed-effect model with the random effects at
zero, against the reference's at the same point. The fixed effect is the
first coordinate of a sweep, so what the later ones move hides a rounded
design matrix in the fitted model's reading (PERF.md section 2); here the
two sides differ by their arithmetic alone. ``solved`` runs the fixed
effect's own solve, the program every fit runs, once more with the random
effects at zero, and reads the reference's gradient at its result: a solve
whose sums were not all-reduced over the mesh stops where its own rows'
gradient vanishes, and the whole's does not (PERF.md section 2).

The window is ``refit``'s (``measure`` and the samples by import; ``verify``
is ``refit``'s, with the memory it reads logged), and so are its readers.
"""

from __future__ import annotations

import resource
import time

import numpy as np

from benchmark import correct
from benchmark import generators as G
from benchmark.systems import training
from benchmark.traffic import refit
from benchmark.traffic.refit import GAP_LABELS, _fit, measure  # noqa: F401

# what ``at_planted`` holds under ``correct.at_planted``
GAPS = ("value_gap", "gradient_gap_over_objective")


def mesh(cfg: dict):
    from photon_tpu.parallel import mesh as M

    return M.create_mesh(cfg["mesh"]["data"], (M.DATA_AXIS,))


def estimator(cfg: dict, **kw):
    """``training.estimator(cfg, **kw)`` over the configuration's mesh."""
    est = training.estimator(cfg, **kw)
    est.mesh = mesh(cfg)
    return est


def _mesh_counters() -> dict:
    from photon_tpu.obs.metrics import registry

    return {k: v for k, v in registry.snapshot()["counters"].items()
            if k.startswith("mesh.")}


def setup(ctx) -> dict:
    cfg = ctx.cfg
    t0 = time.perf_counter()
    planted = G.planted_model(cfg, cfg["data_seed"])
    train = G.game_rows(cfg, cfg["rows"], cfg["data_seed"], "train", planted)
    validation = G.game_rows(cfg, cfg["validation_rows"], cfg["data_seed"],
                             f"validation-{ctx.seed}", planted)
    generate_s = time.perf_counter() - t0
    state = {"frame": training.frame(cfg, train), "est": estimator(cfg)}
    first = _fit(ctx, state)
    if "error" in first:
        raise RuntimeError(f"the set-up fit failed: {first['error']}")
    state["first_fit_s"] = time.perf_counter() - t0 - generate_s
    ctx.say(f"generated {cfg['rows']} + {cfg['validation_rows']} rows in "
            f"{generate_s:.2f}s; frame + first fit over {state['est'].mesh} "
            f"(ingest, compile or cache load, one fit) "
            f"{state['first_fit_s']:.2f}s, the fit alone "
            f"{first['end'] - first['start']:.2f}s; {first}; "
            f"{_mesh_counters()}")
    t0 = time.perf_counter()
    state["first"] = first
    state["fitted"] = training.model_tables(cfg, state["est"], state["model"])
    ref = correct.load_reference(cfg["name"])
    fit_holds, measured = correct.training(cfg, ref, state["fitted"], train,
                                           validation)
    point_holds, measured["at_planted"] = at_planted(cfg, state["est"], ref,
                                                     train)
    solve_holds, measured["solved"] = solved(cfg, state["est"], ref, train)
    state["holds"] = fit_holds and point_holds and solve_holds
    ctx.say(f"correct {state['holds']} (the fit {fit_holds}, the planted "
            f"point {point_holds}, the solve {solve_holds}) in "
            f"{time.perf_counter() - t0:.2f}s: {measured}")
    return state


def _fixed_at_zero(cfg, theta) -> dict:
    """The planted model's tables with the fixed effect's set to ``theta``
    and every random effect's at zero."""
    (fixed,) = [c for c in cfg["coordinates"] if c["kind"] == "fixed"]
    planted = G.planted_model(cfg, cfg["data_seed"])
    return {k: np.asarray(theta if k == fixed["id"] else np.zeros_like(v),
                          v.dtype) for k, v in planted.items()}


def at_planted(cfg, est, ref, train) -> tuple:
    """(holds, what was measured): the fixed effect's objective and
    gradient as every solve of it evaluates them
    (``problem.objective.value_and_gradient``, on the batch the estimator
    placed) against the reference's, at the planted fixed-effect model
    with every random effect at zero; the gaps over the objective."""
    import jax
    import jax.numpy as jnp

    from photon_tpu.function.objective import Hyper

    (fixed,) = [c for c in cfg["coordinates"] if c["kind"] == "fixed"]
    params = _fixed_at_zero(
        cfg, G.planted_model(cfg, cfg["data_seed"])[fixed["id"]])
    coordinate = est._coordinates[fixed["id"]]
    objective, batch = coordinate.problem.objective, coordinate.batch
    dtype = batch.labels.dtype

    @jax.jit
    def at(theta, batch, l2):
        return objective.value_and_gradient(theta, batch,
                                            Hyper(l2_weight=l2))

    value, gradient = at(jnp.asarray(params[fixed["id"]], dtype), batch,
                         jnp.asarray(cfg["l2"], dtype))
    want, want_gradient = correct.objective_and_gradient(ref, params, train,
                                                         cfg["l2"])
    gap = np.asarray(gradient, np.float64) - want_gradient[fixed["id"]]
    measured = {
        "objective": want,
        "value_gap": abs(float(value) - want) / want,
        "gradient_gap_over_objective": float(np.sqrt(np.sum(gap * gap))
                                             / want)}
    limits = cfg["correct"]["at_planted"]
    # a gap that is not a number is under no limit
    return all(measured[k] <= limits[k] for k in GAPS), measured


def solved(cfg, est, ref, train) -> tuple:
    """(holds, what was measured): the fixed effect's own update
    (``FixedEffectCoordinate.update_model``, the solve program of every
    fit, on the batch placed over the mesh) from zero with every random
    effect at zero, and the reference's gradient of the whole objective at
    its result, over the objective: what the solver's tolerance and the
    arithmetic leave, and no later coordinate moves."""
    import jax.numpy as jnp

    (fixed,) = [c for c in cfg["coordinates"] if c["kind"] == "fixed"]
    coordinate = est._coordinates[fixed["id"]]
    zeros = jnp.zeros((len(train.y),), coordinate.batch.labels.dtype)
    model = coordinate.update_model(None, zeros)
    theta = np.asarray(model.model.coefficients.means, np.float64)
    objective, gradient = correct.objective_and_gradient(
        ref, _fixed_at_zero(cfg, theta), train, cfg["l2"])
    g = gradient[fixed["id"]]
    measured = {"objective": objective,
                "gradient_over_objective": float(np.sqrt(np.sum(g * g))
                                                 / objective)}
    limit = cfg["correct"]["solved"]["gradient_over_objective"]
    return measured["gradient_over_objective"] <= limit, measured


def verify(ctx, state, windows) -> tuple:
    out = refit.verify(ctx, state, windows)
    peaks = [d.memory_stats().get("peak_bytes_in_use")
             for d in state["est"].mesh.devices.flat if d.memory_stats()]
    ctx.say(f"peak_bytes_in_use by chip {peaks}; the host's ru_maxrss "
            f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024} "
            f"bytes")
    return out
