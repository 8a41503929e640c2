"""A logistic GLMix with L-BFGS on EVERY coordinate, as Photon configures it
and as this repo's default leaves it: one L-BFGS solve for the fixed effect
and one per entity, vmapped through the bucket ladder
(``game/coordinate.py::_make_entity_solvers``'s last branch). The benchmark
cell ``glmix-ml20m-lbfgs.refit`` times this path on the chip; here it is
held, small and seeded, to the plain equations in float64 numpy

    margin_i  = x_global_i . theta + x_user_i . U[user_i] + x_movie_i . M[movie_i]
    objective = sum_i (log(1 + exp(margin_i)) - y_i margin_i)
                + (l2 / 2) (|theta|^2 + |U|^2 + |M|^2)

to each entity's own float64 minimiser, to NEWTON on the same rows, to the
same counts and, to rounding, coefficients whatever the width of the batch
an entity is solved in, and to the lane counts the cell's per-layer metrics read."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.estimators.game_estimator import (
    CoordinateConfiguration,
    FixedEffectDataConfiguration,
    GameEstimator,
)
from photon_tpu.function.objective import L2Regularization
from photon_tpu.game.dataset import FeatureShard, GameDataFrame
from photon_tpu.game.random_effect import RandomEffectDataConfiguration
from photon_tpu.optim.problem import (
    GLMOptimizationConfiguration,
    OptimizerConfig,
)
from photon_tpu.types import OptimizerType, TaskType

L2 = 1.0
WIDTHS = {"fixed": 6, "per_user": 3, "per_movie": 2}
SHARDS = {"fixed": "global", "per_user": "user_feats",
          "per_movie": "movie_feats"}
ENTITY = {"per_user": "userId", "per_movie": "movieId"}


def _rows(seed=31, users=24, movies=15):
    """Rows a user 6..130 (several size buckets), movies by a power law."""
    rng = np.random.default_rng(seed)
    per_user = np.geomspace(6, 130, users).astype(int)
    user = np.repeat(np.arange(users), per_user)
    n = len(user)
    p = 1.0 / np.arange(1, movies + 1)
    movie = rng.choice(movies, size=n, p=p / p.sum())
    x = {cid: rng.normal(size=(n, d)) / (np.sqrt(d) if cid == "fixed" else 1)
         for cid, d in WIDTHS.items()}
    theta = rng.normal(size=WIDTHS["fixed"])
    u = 0.7 * rng.normal(size=(users, WIDTHS["per_user"]))
    m = 0.7 * rng.normal(size=(movies, WIDTHS["per_movie"]))
    z = (x["fixed"] @ theta + np.sum(x["per_user"] * u[user], 1)
         + np.sum(x["per_movie"] * m[movie], 1))
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float64)
    return x, {"userId": user, "movieId": movie}, y


def _frame(x, ids, y):
    return GameDataFrame(
        num_samples=len(y), response=y,
        feature_shards={SHARDS[cid]: FeatureShard(x[cid], WIDTHS[cid])
                        for cid in WIDTHS},
        id_tags={k: [str(v) for v in vals] for k, vals in ids.items()})


def _estimator(solver, sweeps, dtype=jnp.float64, tolerance=1e-10):
    opt = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(optimizer_type=OptimizerType[solver],
                                  max_iterations=100, tolerance=tolerance),
        regularization=L2Regularization, regularization_weight=L2)
    coords = {"fixed": CoordinateConfiguration(
        FixedEffectDataConfiguration(SHARDS["fixed"]), opt)}
    for cid, entity in ENTITY.items():
        coords[cid] = CoordinateConfiguration(
            RandomEffectDataConfiguration(entity, SHARDS[cid]), opt)
    return GameEstimator(TaskType.LOGISTIC_REGRESSION, coords,
                         update_sequence=list(WIDTHS), num_iterations=sweeps,
                         dtype=dtype)


def _tables(est, model, ids):
    """{coordinate: [width] or [entities, width]} in the rows' own entity
    numbers and feature columns (the program keeps first-seen order and
    per-entity slots)."""
    out = {"fixed": np.asarray(model["fixed"].model.coefficients.means)}
    for cid, entity in ENTITY.items():
        coef = np.asarray(model[cid].coefficients)
        proj = np.asarray(est._re_datasets[cid].projection)
        names = np.asarray(est._vocab.names(entity)).astype(np.int64)
        table = np.zeros((ids[entity].max() + 1, WIDTHS[cid]))
        rows, slots = np.nonzero(proj >= 0)
        table[names[rows], proj[rows, slots]] = coef[rows, slots]
        out[cid] = table
    return out


def _margins(tables, x, ids, leave_out=None):
    z = np.zeros(len(x["fixed"]))
    if leave_out != "fixed":
        z += x["fixed"] @ tables["fixed"]
    for cid, entity in ENTITY.items():
        if cid != leave_out:
            z += np.sum(x[cid] * tables[cid][ids[entity]], 1)
    return z


def _objective_and_gradient(tables, x, ids, y):
    z = _margins(tables, x, ids)
    value = np.sum(np.logaddexp(0.0, z) - y * z) + 0.5 * L2 * sum(
        np.sum(t * t) for t in tables.values())
    dz = 1.0 / (1.0 + np.exp(-z)) - y
    grad = {"fixed": x["fixed"].T @ dz + L2 * tables["fixed"]}
    for cid, entity in ENTITY.items():
        g = np.zeros_like(tables[cid])
        np.add.at(g, ids[entity], x[cid] * dz[:, None])
        grad[cid] = g + L2 * tables[cid]
    return value, grad


def _entity_minimiser(x, y, offset):
    """Float64 Newton on one entity's own regularised logistic problem."""
    w = np.zeros(x.shape[1])
    for _ in range(50):
        p = 1.0 / (1.0 + np.exp(-(x @ w + offset)))
        g = x.T @ (p - y) + L2 * w
        h = (x * (p * (1 - p))[:, None]).T @ x + L2 * np.eye(len(w))
        step = np.linalg.solve(h, g)
        w -= step
        if np.abs(step).max() < 1e-14:
            break
    return w


@pytest.fixture(scope="module")
def fits():
    x, ids, y = _rows()
    frame = _frame(x, ids, y)
    out = {}
    for solver in ("LBFGS", "NEWTON"):
        est = _estimator(solver, sweeps=12)
        out[solver] = _tables(est, est.fit(frame)[-1].model, ids)
    return x, ids, y, out


# after 12 sweeps what is left is what the coordinates still move each other
# by; the last updated stands at its solver's tolerance
GRADIENT_LIMITS = {"fixed": 1e-6, "per_user": 1e-6, "per_movie": 1e-8}


@pytest.mark.parametrize("cid", list(WIDTHS))
def test_lbfgs_on_every_coordinate_reaches_the_plain_objectives_optimum(
        fits, cid):
    x, ids, y, tables = fits
    value, grad = _objective_and_gradient(tables["LBFGS"], x, ids, y)
    assert np.linalg.norm(grad[cid]) / value <= GRADIENT_LIMITS[cid], (
        cid, np.linalg.norm(grad[cid]) / value)


@pytest.mark.parametrize("cid", list(ENTITY))
def test_each_entity_matches_a_float64_minimiser_of_its_own_problem(fits,
                                                                    cid):
    x, ids, y, tables = fits
    offset = _margins(tables["LBFGS"], x, ids, leave_out=cid)
    entity = ids[ENTITY[cid]]
    worst = 0.0
    for e in np.unique(entity):
        rows = entity == e
        want = _entity_minimiser(x[cid][rows], y[rows], offset[rows])
        worst = max(worst, np.abs(tables["LBFGS"][cid][e] - want).max())
    # per_movie was solved against exactly these offsets; per_user against
    # the movies' previous sweep, which 12 sweeps have brought this close
    assert worst <= {"per_movie": 1e-7, "per_user": 1e-4}[cid], worst


@pytest.mark.parametrize("cid", list(WIDTHS))
def test_newton_on_the_same_rows_gives_the_same_coefficients(fits, cid):
    _, _, _, tables = fits
    np.testing.assert_allclose(tables["LBFGS"][cid], tables["NEWTON"][cid],
                               rtol=0, atol=1e-4)


# --------------------------------------------------------------------------
# an entity's solve is the same bits in any batch
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def entity_solvers():
    """The per-entity solve the ladder vmaps, float32 as on the chip, with
    the benchmark's solver settings."""
    x, ids, y = _rows(users=6, movies=4)
    est = _estimator("LBFGS", sweeps=1, dtype=jnp.float32, tolerance=1e-6)
    est.fit(_frame(x, ids, y))
    return est._coordinates["per_user"]._make_entity_solvers()


def _entities(count, rows=37, width=5, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(count, rows, width)).astype(np.float32)
    w = rng.normal(size=(count, width))
    z = np.einsum("erk,ek->er", x, w)
    y = (rng.random((count, rows)) < 1 / (1 + np.exp(-z))).astype(np.float32)
    weights = np.ones((count, rows), np.float32)
    weights[:, rows - 5:] = 0.0                       # a bucket's padding
    offsets = rng.normal(size=(count, rows)).astype(np.float32)
    return x, y, offsets, weights


@pytest.mark.parametrize("width", [7, 100])
@pytest.mark.parametrize("block", ["dense", "sparse"])
def test_an_entitys_lbfgs_result_does_not_depend_on_its_batch(
        entity_solvers, block, width):
    """The first two entities in a batch of 2, of 7 and of 100, though a
    batch's loops trip until its slowest lane is done: the same iteration
    and evaluation counts, reason and failure code, and the same
    coefficients to rounding. NOT always bit for bit: XLA re-associates
    the reductions over rows (the aggregators) and over K (the two-loop
    recursion) with the width of the batch, on the CPU already at other
    shapes than these and for an entity ALONE at these (PERF.md §6, PR 27),
    and a rounding that differs is amplified through the history."""
    solve_sparse, solve_dense = entity_solvers
    x, y, offsets, weights = _entities(width)
    one = jnp.asarray(1.0, jnp.float32)

    def solve(first):
        data = [jnp.asarray(a[:first]) for a in (x, y, offsets, weights)]
        x0 = jnp.zeros((first, x.shape[2]), jnp.float32)
        if block == "dense":
            return jax.jit(jax.vmap(
                solve_dense, in_axes=(0, 0, 0, 0, 0, None, None)))(
                    *data, x0, one, one)
        idx = jnp.broadcast_to(jnp.arange(x.shape[2], dtype=jnp.int32),
                               data[0].shape)
        return jax.jit(jax.vmap(
            solve_sparse, in_axes=(0, 0, 0, 0, 0, 0, None, None)))(
                idx, *data, x0, one, one)

    pair, batched = solve(2), solve(width)
    iterations = np.asarray(batched[1])
    assert iterations.min() < iterations.max()       # the lanes ARE ragged
    assert int(np.asarray(batched[3]).max()) == 0
    for a, b in zip(pair[1:], batched[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[:2])
    np.testing.assert_allclose(np.asarray(pair[0]),
                               np.asarray(batched[0])[:2], rtol=0, atol=2e-6)


# --------------------------------------------------------------------------
# the lane counts
# --------------------------------------------------------------------------

def _sums_from_the_tracker(coord):
    """The counts' definitions, taken by hand from ``last_tracker``."""
    iterations = np.asarray(coord.last_tracker.iterations)
    want = {"sum": 0, "capacity": 0, "trips": 0}
    for blk in coord.dataset.blocks:
        rows = np.asarray(blk.entity_rows)
        its = iterations[rows[rows < len(iterations)]]
        want["sum"] += its.sum()
        want["trips"] += its.max()
        want["capacity"] += len(its) * its.max()
    return want


@pytest.mark.parametrize("solver", ["LBFGS", "NEWTON"])
def test_the_lane_counts_are_the_trackers_sums(solver):
    """``obs.solver.lane_counts()`` is ONE fit's: a second fit's updates
    replace the first's, and nothing is read until it is asked."""
    from photon_tpu import obs
    from photon_tpu.obs import solver as obs_solver

    x, ids, y = _rows(seed=5)
    est = _estimator(solver, sweeps=1, tolerance=1e-8)
    obs.reset()
    obs.configure(enabled=True)
    try:
        est.fit(_frame(x, ids, y))
        once = obs_solver.lane_counts()
        est.fit(_frame(x, ids, y))
        assert obs_solver.lane_counts() == once
        drained = obs.drain_solver_telemetry()["random_effects"]
        assert obs_solver.lane_counts() == {}
    finally:
        obs.reset()
    assert set(once) == set(ENTITY)                  # no fixed effect
    for cid in ENTITY:
        coord = est._coordinates[cid]
        assert len(coord.dataset.blocks) > 1
        want = _sums_from_the_tracker(coord)
        assert want["sum"] < want["capacity"]        # some lanes rode along
        assert once[cid] == want
        assert coord.last_tracker.lane_counts() == want
        # a RunReport carries them an update
        assert [d["lanes"] for d in drained if d["coordinate"] == cid] == \
            [want]


def test_the_lane_counts_are_empty_with_telemetry_off(monkeypatch):
    from photon_tpu import obs
    from photon_tpu.obs import solver as obs_solver

    x, ids, y = _rows(seed=5)
    obs.reset()
    monkeypatch.delenv("PHOTON_TPU_TELEMETRY", raising=False)
    est = _estimator("LBFGS", sweeps=1, tolerance=1e-8)
    est.fit(_frame(x, ids, y))
    assert obs_solver.lane_counts() == {}
    # the tracker still carries what a reader would have summed
    assert est._coordinates["per_user"].last_tracker.lane_counts()["sum"] > 0
