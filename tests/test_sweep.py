"""Lane-batched multi-λ sweep tests (grid-in-one-program).

The contract under test: K hyperparameter configurations solved as ONE
vmapped L-BFGS/OWL-QN program (optim/batched) must be indistinguishable
from K sequential scalar solves — per-lane coefficient parity, per-lane
iteration counts (lanes freeze independently as they converge), typed
per-lane failure isolation — while keeping the scalar solver's
communication structure on a mesh (ONE staged DCN psum per evaluation,
independent of K) and its compilation footprint (zero recompiles as
convergence patterns change between grids).
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.dataset import DataBatch
from photon_tpu.function.objective import (
    GLMObjective,
    L1Regularization,
    L2Regularization,
)
from photon_tpu.game.coordinate import FixedEffectCoordinate
from photon_tpu.ops import features as F
from photon_tpu.ops.losses import LogisticLoss
from photon_tpu.optim import batched
from photon_tpu.optim.base import ConvergenceReason, FailureMode, SolverConfig
from photon_tpu.optim.problem import (
    GlmOptimizationProblem,
    GLMOptimizationConfiguration,
    OptimizerConfig,
)
from photon_tpu.types import OptimizerType, TaskType

F64 = jnp.float64


def _config(max_iterations=200, tolerance=1e-10, **kw):
    return GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=max_iterations,
                                  tolerance=tolerance, **kw),
        regularization=L2Regularization, regularization_weight=1.0)


def _task_data(rng, task, n=900, d=10):
    X = rng.normal(size=(n, d))
    w = rng.normal(size=d) / np.sqrt(d)
    eta = X @ w
    if task == TaskType.LOGISTIC_REGRESSION:
        y = (rng.random(n) < 1.0 / (1.0 + np.exp(-eta))).astype(np.float64)
    elif task == TaskType.POISSON_REGRESSION:
        y = rng.poisson(np.exp(np.clip(eta, -5, 3))).astype(np.float64)
    else:
        y = (eta + 0.1 * rng.normal(size=n)).astype(np.float64)
    return DataBatch(jnp.asarray(X, F64), jnp.asarray(y, F64))


@pytest.fixture
def clean_sweep_stats():
    batched.reset_sweep_stats()
    yield
    batched.reset_sweep_stats()


# -- weight validation -------------------------------------------------------


class TestValidateLaneWeights:
    def test_roundtrip_and_dtype(self):
        arr = batched.validate_lane_weights([0.0, 1, 2.5])
        assert arr.dtype == np.float64 and arr.tolist() == [0.0, 1.0, 2.5]

    @pytest.mark.parametrize("bad", [[], [[1.0, 2.0]], [1.0, -2.0],
                                     [np.nan], [np.inf], [1.0, -np.inf]])
    def test_typed_refusal(self, bad):
        with pytest.raises(batched.SweepWeightError):
            batched.validate_lane_weights(bad)

    def test_refusal_is_a_value_error(self):
        # callers that only know ValueError still catch it
        with pytest.raises(ValueError, match="negative"):
            batched.validate_lane_weights([-1.0], name="l2")


# -- matvec_lanes ------------------------------------------------------------


class TestMatvecLanes:
    def test_dense_matches_per_lane(self, rng):
        X = jnp.asarray(rng.normal(size=(50, 7)))
        thetas = jnp.asarray(rng.normal(size=(4, 7)))
        got = F.matvec_lanes(X, thetas)
        want = jnp.stack([F.matvec(X, thetas[k]) for k in range(4)])
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)

    def test_sparse_ell_matches_per_lane(self, rng):
        n, d, k = 60, 12, 3
        idx = np.stack([rng.choice(d, size=k, replace=False)
                        for _ in range(n)])
        sf = F.SparseFeatures(jnp.asarray(idx, jnp.int32),
                              jnp.asarray(rng.normal(size=(n, k))))
        thetas = jnp.asarray(rng.normal(size=(5, d)))
        got = F.matvec_lanes(sf, thetas)
        want = jnp.stack([F.matvec(sf, thetas[j]) for j in range(5)])
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)

    def test_model_sharded_refused(self, rng):
        ms = object.__new__(F.ModelShardedSparse)
        with pytest.raises(NotImplementedError, match="ModelShardedSparse"):
            F.matvec_lanes(ms, jnp.zeros((2, 4)))


# -- lane vs scalar parity ---------------------------------------------------


class TestLaneScalarParity:
    GRID = [0.01, 0.3, 3.0, 30.0]

    @pytest.mark.parametrize("task", [TaskType.LOGISTIC_REGRESSION,
                                      TaskType.LINEAR_REGRESSION,
                                      TaskType.POISSON_REGRESSION])
    def test_l2_grid_parity(self, rng, task):
        batch = _task_data(rng, task)
        p = GlmOptimizationProblem(task, _config())
        swept = p.solve_swept(batch, self.GRID, dim=10)
        for i, w in enumerate(self.GRID):
            _, ref = p.run(batch, dim=10, regularization_weight=w)
            diff = float(jnp.max(jnp.abs(swept.stacked.coef[i] - ref.coef)))
            assert diff <= 1e-6, f"{task} lane {i} (l2={w}): {diff:.3e}"
            assert int(swept.stacked.iterations[i]) == int(ref.iterations)

    def test_singleton_lane_matches_scalar(self, rng):
        # K=1: "any over one lane" is the scalar cond — identical
        # iteration count, not just close coefficients
        batch = _task_data(rng, TaskType.LOGISTIC_REGRESSION)
        p = GlmOptimizationProblem(TaskType.LOGISTIC_REGRESSION, _config())
        swept = p.solve_swept(batch, [0.7], dim=10)
        _, ref = p.run(batch, dim=10, regularization_weight=0.7)
        assert int(swept.stacked.iterations[0]) == int(ref.iterations)
        assert int(swept.stacked.reason[0]) == int(ref.reason)
        assert float(jnp.max(jnp.abs(swept.stacked.coef[0] - ref.coef))) \
            <= 1e-6

    def test_mixed_convergence_lanes_freeze_independently(self, rng):
        # a heavily regularized lane converges in a handful of
        # iterations; a nearly unregularized one keeps going. The early
        # lane's recorded iterations/reason must equal its own scalar
        # solve — frozen, not dragged to the loop's exit count.
        batch = _task_data(rng, TaskType.LOGISTIC_REGRESSION, n=1200)
        p = GlmOptimizationProblem(TaskType.LOGISTIC_REGRESSION,
                                   _config(tolerance=1e-9))
        grid = [1e-4, 500.0]
        swept = p.solve_swept(batch, grid, dim=10)
        iters = [int(v) for v in np.asarray(swept.stacked.iterations)]
        assert iters[1] < iters[0], iters
        for i, w in enumerate(grid):
            _, ref = p.run(batch, dim=10, regularization_weight=w)
            assert iters[i] == int(ref.iterations)
            assert int(swept.stacked.reason[i]) == int(ref.reason)
            assert int(swept.stacked.reason[i]) != \
                ConvergenceReason.NOT_CONVERGED

    def test_owlqn_l1_grid_per_lane_sparsity(self, rng):
        batch = _task_data(rng, TaskType.LOGISTIC_REGRESSION, n=1500)
        cfg = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(optimizer_type=OptimizerType.OWLQN,
                                      max_iterations=300, tolerance=1e-10),
            regularization=L1Regularization, regularization_weight=1.0)
        p = GlmOptimizationProblem(TaskType.LOGISTIC_REGRESSION, cfg)
        grid = [0.001, 1.0, 20.0, 200.0]
        swept = p.solve_swept(batch, grid, dim=10)
        coefs = np.asarray(swept.stacked.coef)
        nnz = [int(np.sum(np.abs(coefs[i]) > 1e-12)) for i in range(4)]
        # stronger l1 per lane -> sparser lane, down to all-zero
        assert nnz == sorted(nnz, reverse=True), nnz
        assert nnz[0] > 0 and nnz[-1] == 0, nnz
        for i, w in enumerate(grid):
            _, ref = p.run(batch, dim=10, regularization_weight=w)
            ref_nnz = np.abs(np.asarray(ref.coef)) > 1e-12
            np.testing.assert_array_equal(
                np.abs(coefs[i]) > 1e-12, ref_nnz,
                err_msg=f"lane {i} (l1={w}) support != scalar solve")

    def test_second_order_solvers_refused(self, rng):
        cfg = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(optimizer_type=OptimizerType.TRON),
            regularization=L2Regularization, regularization_weight=1.0)
        p = GlmOptimizationProblem(TaskType.LOGISTIC_REGRESSION, cfg)
        batch = _task_data(rng, TaskType.LOGISTIC_REGRESSION, n=100)
        with pytest.raises(ValueError, match="LBFGS/OWLQN"):
            p.solve_swept(batch, [0.1, 1.0], dim=10)


# -- recompile / cache behavior ----------------------------------------------


class TestNoRecompiles:
    def test_different_grids_reuse_one_program(self, rng):
        from photon_tpu.obs.metrics import registry
        batch = _task_data(rng, TaskType.LOGISTIC_REGRESSION)
        p = GlmOptimizationProblem(TaskType.LOGISTIC_REGRESSION, _config())
        p.solve_swept(batch, [0.1, 1.0, 10.0], dim=10)
        solve = p._swept_solve_fn(None)
        before = solve._cache_size()
        rc_before = registry.snapshot()["counters"].get(
            "jitcache.recompiles", 0)
        # different weights, different convergence patterns; same trace
        p.solve_swept(batch, [5.0, 0.01, 300.0], dim=10)
        p.solve_swept(batch, [1e-4, 1e4, 1.0], dim=10)
        assert solve._cache_size() == before
        assert registry.snapshot()["counters"].get(
            "jitcache.recompiles", 0) == rc_before


# -- per-lane failure isolation ----------------------------------------------


class TestLaneFailureIsolation:
    def test_nan_lane_fails_typed_without_sinking_siblings(self, rng):
        # one lane's hyper is poisoned (NaN l2) -> its objective goes
        # non-finite; the lane must freeze with a typed FailureMode while
        # its siblings converge to the same answer as their scalar solves
        batch = _task_data(rng, TaskType.LOGISTIC_REGRESSION)
        obj = GLMObjective(LogisticLoss)
        cfg = SolverConfig(max_iterations=200, tolerance=1e-10)

        @jax.jit
        def solve(b, x0, l2):
            vg = lambda c, hyper: obj.value_and_gradient(c, b, hyper)
            return batched.minimize_lanes(vg, x0, l2=l2, config=cfg)

        l2 = jnp.asarray([0.5, jnp.nan, 5.0], F64)
        res = solve(batch, jnp.zeros((3, 10), F64), l2)
        fails = np.asarray(res.failure)
        assert fails[1] != FailureMode.NONE
        assert fails[0] == FailureMode.NONE and fails[2] == FailureMode.NONE
        assert np.all(np.isfinite(np.asarray(res.coef)[[0, 2]]))
        p = GlmOptimizationProblem(TaskType.LOGISTIC_REGRESSION,
                                   _config(tolerance=1e-10))
        for lane, w in ((0, 0.5), (2, 5.0)):
            _, ref = p.run(batch, dim=10, regularization_weight=w)
            np.testing.assert_allclose(res.coef[lane], ref.coef,
                                       rtol=1e-6, atol=1e-8)

    def test_chaos_poisoned_sweep_degrades_typed(self, rng, clean_sweep_stats):
        # the chaos hook poisons the shared data term (a corrupt upstream
        # residual): every lane must fail TYPED — no exception, no
        # silent garbage model
        batch = _task_data(rng, TaskType.LOGISTIC_REGRESSION, n=300)
        coord = FixedEffectCoordinate(batch, 10, "g",
                                      TaskType.LOGISTIC_REGRESSION,
                                      _config())
        coord._chaos_poison_once = True
        coord.update_model_swept(None, None, [0.1, 1.0, 10.0])
        assert all(f is not None for f in coord.last_lane_failures)
        # and a clean re-run on the same coordinate recovers all lanes
        coord.update_model_swept(None, None, [0.1, 1.0, 10.0])
        assert all(f is None for f in coord.last_lane_failures)


# -- meshed lane batch: communication structure ------------------------------


class TestMeshedLanes:
    def _setup(self, rng, mesh, K, n=1024, d=12):
        from photon_tpu.parallel import mesh as M
        batch = _task_data(rng, TaskType.LOGISTIC_REGRESSION, n=n, d=d)
        sharded = M.shard_batch(batch, mesh,
                                axis=(M.DCN_AXIS, M.DATA_AXIS))
        x0 = jnp.zeros((K, d), F64)
        l2 = jnp.asarray(np.logspace(-2, 1, K), F64)
        return batch, sharded, x0, l2

    def test_one_staged_dcn_psum_independent_of_k(self, rng, devices8):
        from photon_tpu.parallel import mesh as M
        mesh = M.create_two_level_mesh(8, 2)
        obj = GLMObjective(LogisticLoss)
        cfg = SolverConfig(max_iterations=40, tolerance=1e-9)
        counts = {}
        for K in (1, 2, 8):
            _, sharded, x0, l2 = self._setup(rng, mesh, K)
            fn = lambda x0_, l2_, b: batched.minimize_lanes_meshed(
                obj, b, x0_, l2=l2_, mesh=mesh, config=cfg)
            counts[K] = M.count_axis_psums(fn, M.DCN_AXIS, x0, l2, sharded)
        # one staged DCN psum per objective-evaluation SITE (the pre-loop
        # evaluation + the solver body), and — the lane-batching claim —
        # the collective batching rule folds all K lanes' packed
        # [grad | value] reductions into those same eqns: the count is
        # identical to the singleton lane's, independent of K
        assert counts[2] == counts[8] == counts[1] == 2, counts

    def test_meshed_matches_local_lanes(self, rng, devices8):
        from photon_tpu.parallel import mesh as M
        mesh = M.create_two_level_mesh(8, 2)
        obj = GLMObjective(LogisticLoss)
        cfg = SolverConfig(max_iterations=200, tolerance=1e-10)
        batch, sharded, x0, l2 = self._setup(rng, mesh, K=4)

        meshed = jax.jit(
            lambda x0_, l2_, b: batched.minimize_lanes_meshed(
                obj, b, x0_, l2=l2_, mesh=mesh, config=cfg)
        )(x0, l2, sharded)

        @jax.jit
        def local(b, x0_, l2_):
            vg = lambda c, hyper: obj.value_and_gradient(c, b, hyper)
            return batched.minimize_lanes(vg, x0_, l2=l2_, config=cfg)

        ref = local(batch, x0, l2)
        np.testing.assert_allclose(meshed.coef, ref.coef,
                                   rtol=1e-6, atol=1e-8)


# -- coordinate-level sweep + telemetry --------------------------------------


class TestCoordinateSweep:
    def test_update_model_swept_records_lanes(self, rng, clean_sweep_stats):
        from photon_tpu.obs.metrics import registry
        batch = _task_data(rng, TaskType.LOGISTIC_REGRESSION, n=400)
        coord = FixedEffectCoordinate(batch, 10, "g",
                                      TaskType.LOGISTIC_REGRESSION,
                                      _config())
        grid = [0.1, 1.0, 10.0]
        swept = coord.update_model_swept(None, None, grid)
        assert swept.stacked.coef.shape == (3, 10)
        assert len(swept.models) == 3 and len(swept.results) == 3
        section = batched.report_section()
        assert section["runs"] == 1 and section["lanes_total"] == 3
        lanes = section["lane_records"][0]
        assert [r["weight"] for r in lanes] == grid
        assert all(r["failure"] == int(FailureMode.NONE) for r in lanes)
        assert registry.snapshot()["gauges"]["sweep.lanes_active"] == 3

    def test_score_lanes_matches_per_lane_score(self, rng):
        batch = _task_data(rng, TaskType.LOGISTIC_REGRESSION, n=200)
        coord = FixedEffectCoordinate(batch, 10, "g",
                                      TaskType.LOGISTIC_REGRESSION,
                                      _config())
        thetas = jnp.asarray(rng.normal(size=(3, 10)))
        scores = coord.score_lanes(thetas)
        assert scores.shape == (3, 200)
        for i in range(3):
            want = F.matvec(batch.features, thetas[i])
            np.testing.assert_allclose(scores[i], want,
                                       rtol=1e-12, atol=1e-12)

    def test_run_report_sweep_section_roundtrip(self, rng,
                                                clean_sweep_stats):
        from photon_tpu.obs.report import build_run_report, \
            validate_run_report
        # idle module -> no section
        report = build_run_report("test_sweep")
        assert "sweep" not in report
        batch = _task_data(rng, TaskType.LOGISTIC_REGRESSION, n=300)
        coord = FixedEffectCoordinate(batch, 10, "g",
                                      TaskType.LOGISTIC_REGRESSION,
                                      _config())
        coord.update_model_swept(None, None, [0.5, 5.0])
        batched.record_tuner_summary({"mode": "BAYESIAN", "rounds": 2})
        report = build_run_report("test_sweep")
        assert report["sweep"]["runs"] == 1
        assert report["sweep"]["lanes_total"] == 2
        assert report["sweep"]["tuner"]["rounds"] == 2
        assert validate_run_report(report) == []
        # schema check catches a malformed section
        broken = dict(report, sweep={"runs": 1})
        assert any("sweep" in e for e in validate_run_report(broken))


# -- estimator-level sweep + tuning ------------------------------------------


def _frame(rng, n, d=6):
    from photon_tpu.game.dataset import FeatureShard, GameDataFrame
    X = rng.normal(size=(n, d))
    w = rng.normal(size=d)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-(X @ w)))).astype(np.float64)
    return GameDataFrame(num_samples=n, response=y,
                         feature_shards={"g": FeatureShard(X, d)})


def _estimator(d=6, **cfg_kw):
    from photon_tpu.estimators.game_estimator import (
        CoordinateConfiguration,
        FixedEffectDataConfiguration,
        GameEstimator,
    )
    # f64 so lane-vs-scalar parity asserts stay tight (conftest x64)
    return GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {"fixed": CoordinateConfiguration(
            FixedEffectDataConfiguration("g"), _config(**cfg_kw))},
        dtype=jnp.float64)


class TestEstimatorSweep:
    def test_with_regularization_weight_roundtrip(self):
        from photon_tpu.estimators.game_estimator import (
            CoordinateConfiguration,
            FixedEffectDataConfiguration,
        )
        base = CoordinateConfiguration(FixedEffectDataConfiguration("g"),
                                       _config())
        out = base.with_regularization_weight(7.5)
        assert out.optimization.regularization_weight == 7.5
        assert base.optimization.regularization_weight == 1.0  # unchanged
        assert out.data == base.data
        assert out.optimization.optimizer == base.optimization.optimizer
        for bad in (-1.0, np.nan, np.inf):
            with pytest.raises(batched.SweepWeightError):
                base.with_regularization_weight(bad)

    def test_fit_swept_matches_sequential_fits(self, rng,
                                               clean_sweep_stats):
        df, vdf = _frame(rng, 500), _frame(rng, 200)
        grid = [0.1, 1.0, 10.0]
        results = _estimator().fit_swept(df, validation_df=vdf,
                                         weights=grid)
        assert len(results) == 3
        seq = _estimator().fit(
            df, validation_df=vdf,
            configurations=[{"fixed": w} for w in grid])
        for i in range(3):
            got = results[i].model.models["fixed"].model.coefficients.means
            want = seq[i].model.models["fixed"].model.coefficients.means
            # sequential fit warm-starts each config from the previous
            # one (the reference's warm-start chain), so both paths reach
            # the optimum from different iterates: parity here is bounded
            # by solver tolerance, not lane arithmetic (the tight <=1e-6
            # same-start bound lives in TestLaneScalarParity)
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_fit_swept_refuses_bad_grid(self, rng):
        df = _frame(rng, 120)
        with pytest.raises(batched.SweepWeightError):
            _estimator().fit_swept(df, weights=[1.0, -2.0])

    def test_tune_smoke(self, rng, clean_sweep_stats):
        df, vdf = _frame(rng, 500), _frame(rng, 250)
        res = _estimator().tune(df, vdf, n_rounds=2, ask_batch=3, seed=0)
        assert len(res.rounds) == 2
        assert res.total_iterations > 0
        assert res.best_config["fixed"] > 0
        assert np.isfinite(res.best_value)
        # search minimizes; AUC is bigger-is-better, so value = -metric
        assert res.best_value == pytest.approx(-res.best_metric)
        every = [v for rnd in res.rounds for v in rnd["values"]]
        assert res.best_value == pytest.approx(min(every))
        section = batched.report_section()
        assert section["tuner"] is not None
        assert section["tuner"]["rounds"] == 2
        assert section["runs"] == 2  # one batched solve per round


# -- a K=4 grid in one program, then warm-started GP tuning, end to end -----
#
# A 4-point l2 grid (2,000 x 8 logistic) solved as one lane-batched
# program against 4 sequential solves, a second and third grid through
# the same program, then GameEstimator.tune() warm-started against the
# same tune cold-started and against every candidate fitted alone. One
# run; each gate is one case.


@pytest.fixture(scope="module")
def sweep_quick_run():
    from photon_tpu.estimators.game_estimator import (
        CoordinateConfiguration,
        FixedEffectDataConfiguration,
        GameEstimator,
    )
    from photon_tpu.game.dataset import FeatureShard, GameDataFrame
    from photon_tpu.obs.metrics import registry

    n, d, K = 2_000, 8, 4
    grid = np.logspace(-3.0, 2.0, K)
    rng = np.random.default_rng(11)
    X = rng.normal(size=(n, d))
    theta = rng.normal(size=d)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-(X @ theta)))).astype(np.float64)
    batch = DataBatch(features=jnp.asarray(X, F64), labels=jnp.asarray(y, F64))
    opt = _config(max_iterations=120, tolerance=1e-8)
    p = GlmOptimizationProblem(TaskType.LOGISTIC_REGRESSION, opt)

    swept = p.solve_swept(batch, grid, dim=d).stacked
    seq = [p.run(batch, dim=d, regularization_weight=float(w))[1]
           for w in grid]
    parity = max(float(jnp.max(jnp.abs(swept.coef[i] - seq[i].coef)))
                 for i in range(K))
    lane_iters = [int(v) for v in np.asarray(swept.iterations)]

    # other grids freeze their lanes at other iterations: same program
    solve = p._swept_solve_fn(None)
    traces0 = solve._cache_size()
    recompiles0 = registry.snapshot()["counters"].get(
        "jitcache.recompiles", 0)
    for g in (np.logspace(-2.0, 3.0, K), grid[::-1].copy()):
        p.solve_swept(batch, g, dim=d).stacked.coef.block_until_ready()
    new_traces = solve._cache_size() - traces0
    new_recompiles = registry.snapshot()["counters"].get(
        "jitcache.recompiles", 0) - recompiles0

    def frame(m):
        Xm = rng.normal(size=(m, d))
        ym = (rng.random(m)
              < 1.0 / (1.0 + np.exp(-(Xm @ theta)))).astype(np.float64)
        return GameDataFrame(num_samples=m, response=ym,
                             feature_shards={"g": FeatureShard(Xm, d)})

    df, val_df = frame(1_200), frame(1_200)

    def estimator():
        return GameEstimator(TaskType.LOGISTIC_REGRESSION, {
            "fixed": CoordinateConfiguration(
                FixedEffectDataConfiguration("g"), opt)})

    warm = estimator().tune(df, val_df, n_rounds=2, ask_batch=4, seed=3)
    cold = estimator().tune(df, val_df, n_rounds=2, ask_batch=4, seed=3,
                            warm_start_lanes=False)
    # every candidate the tuner observed, fitted as its own solve; the
    # tuner's pick must be within 1e-4 of the best of them
    seq_est = estimator()
    primary = seq_est.evaluators[0]
    seq_values = {}
    for rnd in warm.rounds:
        for w in rnd["weights"]:
            v = seq_est.fit(df, validation_df=val_df,
                            configurations=[{"fixed": float(w)}]
                            )[-1].evaluation[primary.name]
            seq_values[float(w)] = float(-v if primary.bigger_is_better
                                         else v)
    picked = min(seq_values, key=lambda w: abs(w - warm.best_config["fixed"]))
    batched.reset_sweep_stats()
    return {
        "lane_parity_le_1e6": parity <= 1e-6,
        "lane_iterations_match_sequential":
            lane_iters == [int(np.asarray(r.iterations)) for r in seq],
        "zero_recompiles": new_traces == 0 and new_recompiles == 0,
        "warm_fewer_iterations_than_cold":
            warm.total_iterations < cold.total_iterations,
        "tune_matches_sequential_best":
            seq_values[picked] <= min(seq_values.values()) + 1e-4,
    }


@pytest.mark.parametrize("gate", [
    "lane_parity_le_1e6", "lane_iterations_match_sequential",
    "zero_recompiles", "warm_fewer_iterations_than_cold",
    "tune_matches_sequential_best"])
def test_grid_in_one_program_then_tuning(sweep_quick_run, gate):
    assert sweep_quick_run[gate] is True, sweep_quick_run
