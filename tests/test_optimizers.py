"""Solver tests vs analytic objectives and scipy/sklearn oracles — the role
of the reference's OptimizerTest/TRON tests against TestObjective."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.dataset import DataBatch
from photon_tpu.function.objective import GLMObjective, Hyper
from photon_tpu.ops.losses import LogisticLoss, PoissonLoss
from photon_tpu.optim import ConvergenceReason, SolverConfig, lbfgs, minimize, owlqn, tron
from photon_tpu.types import OptimizerType

D = 12


def rosen_vg(x):
    fn = lambda z: jnp.sum(100.0 * (z[1:] - z[:-1] ** 2) ** 2 + (1 - z[:-1]) ** 2)
    return fn(x), jax.grad(fn)(x)


def make_logistic(rng, n=1500, d=D, seed_scale=1.0):
    X = rng.normal(size=(n, d))
    w = rng.normal(size=d) * seed_scale
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-X @ w))).astype(np.float64)
    return DataBatch(jnp.asarray(X), jnp.asarray(y)), X, y


def test_lbfgs_rosenbrock():
    res = jax.jit(
        lambda x: lbfgs.minimize(rosen_vg, x,
                                 config=SolverConfig(max_iterations=300, tolerance=1e-12))
    )(jnp.zeros(10))
    assert float(jnp.linalg.norm(res.coef - 1.0)) < 1e-5
    assert int(res.reason) != ConvergenceReason.NOT_CONVERGED


def test_lbfgs_quadratic_exact(rng):
    A = rng.normal(size=(25, 25))
    Q = jnp.asarray(A @ A.T + 25 * np.eye(25))
    b = jnp.asarray(rng.normal(size=25))
    vg = lambda x: (0.5 * x @ Q @ x - b @ x, Q @ x - b)
    res = lbfgs.minimize(vg, jnp.zeros(25),
                         config=SolverConfig(tolerance=1e-13, max_iterations=400))
    xstar = np.linalg.solve(np.asarray(Q), np.asarray(b))
    np.testing.assert_allclose(res.coef, xstar, rtol=1e-6, atol=1e-8)


def test_lbfgs_logistic_vs_sklearn(rng):
    from sklearn.linear_model import LogisticRegression

    batch, X, y = make_logistic(rng)
    obj = GLMObjective(LogisticLoss)
    hyper = Hyper.of(1.0, dtype=jnp.float64)
    vg = lambda c: obj.value_and_gradient(c, batch, hyper)
    res = lbfgs.minimize(vg, jnp.zeros(D),
                         config=SolverConfig(tolerance=1e-12, max_iterations=300))
    sk = LogisticRegression(C=1.0, fit_intercept=False, tol=1e-12, max_iter=5000)
    sk.fit(X, y)
    np.testing.assert_allclose(res.coef, sk.coef_[0], rtol=1e-4, atol=1e-6)


def test_tron_matches_lbfgs_logistic(rng):
    batch, _, _ = make_logistic(rng)
    obj = GLMObjective(LogisticLoss)
    hyper = Hyper.of(0.5, dtype=jnp.float64)
    vg = lambda c: obj.value_and_gradient(c, batch, hyper)
    hv = lambda c, v: obj.hessian_vector(c, v, batch, hyper)
    r1 = lbfgs.minimize(vg, jnp.zeros(D), config=SolverConfig(tolerance=1e-12, max_iterations=300))
    r2 = tron.minimize(vg, hv, jnp.zeros(D),
                       config=SolverConfig(max_iterations=50, tolerance=1e-12))
    np.testing.assert_allclose(r1.coef, r2.coef, rtol=1e-5, atol=1e-7)
    # TRON (Newton) should use far fewer outer iterations
    assert int(r2.iterations) <= int(r1.iterations)


def test_tron_poisson(rng):
    n = 800
    X = rng.normal(size=(n, D)) * 0.3
    w = rng.normal(size=D) * 0.5
    y = rng.poisson(np.exp(X @ w)).astype(np.float64)
    batch = DataBatch(jnp.asarray(X), jnp.asarray(y))
    obj = GLMObjective(PoissonLoss)
    hyper = Hyper.of(1e-3, dtype=jnp.float64)
    vg = lambda c: obj.value_and_gradient(c, batch, hyper)
    hv = lambda c, v: obj.hessian_vector(c, v, batch, hyper)
    res = tron.minimize(vg, hv, jnp.zeros(D),
                        config=SolverConfig(max_iterations=60, tolerance=1e-12))
    # the f0-relative value tolerance may legitimately fire before the
    # gradient tolerance (an accepted decrease of ~1e-10 <= 1e-12*|f0|), so
    # assert a *converged* reason and a near-stationary point, not 1e-6
    assert int(res.reason) in (ConvergenceReason.FUNCTION_VALUES_CONVERGED,
                               ConvergenceReason.GRADIENT_CONVERGED)
    assert float(jnp.linalg.norm(res.gradient)) < 1e-4
    # recovered coefficients close to truth on easy data
    assert float(jnp.linalg.norm(res.coef - w)) / np.linalg.norm(w) < 0.35


def test_owlqn_l1_logistic_vs_sklearn(rng):
    from sklearn.linear_model import LogisticRegression

    batch, X, y = make_logistic(rng)
    obj = GLMObjective(LogisticLoss)
    vg = lambda c: obj.value_and_gradient(c, batch, Hyper.of(0.0, dtype=jnp.float64))
    lam = 8.0
    res = owlqn.minimize(vg, jnp.zeros(D), l1_weight=lam,
                         config=SolverConfig(tolerance=1e-12, max_iterations=400))
    sk = LogisticRegression(penalty="l1", C=1.0 / lam, solver="liblinear",
                            fit_intercept=False, tol=1e-12, max_iter=5000)
    sk.fit(X, y)
    f = lambda c: float(obj.value(jnp.asarray(c), batch, Hyper.of(0.0, dtype=jnp.float64))
                        + lam * np.abs(np.asarray(c)).sum())
    # at least as good an objective as the sklearn solution, same support
    assert f(res.coef) <= f(sk.coef_[0]) + 1e-4
    assert set(np.nonzero(np.asarray(res.coef))[0]) == set(np.nonzero(sk.coef_[0])[0])


def test_owlqn_sparsity_path_vs_sklearn(rng):
    """Support must match liblinear's along a whole lambda path, shrinking
    to the empty model — genuine L1 sparsity, not incidental zeros."""
    from sklearn.linear_model import LogisticRegression

    batch, X, y = make_logistic(rng)
    obj = GLMObjective(LogisticLoss)
    vg = lambda c: obj.value_and_gradient(c, batch, Hyper.of(0.0, dtype=jnp.float64))
    prev_nnz = D + 1
    for lam, expect_nnz_below in [(60.0, None), (150.0, D // 2), (500.0, 1)]:
        res = owlqn.minimize(vg, jnp.zeros(D), l1_weight=lam,
                             config=SolverConfig(tolerance=1e-10, max_iterations=400))
        sk = LogisticRegression(penalty="l1", C=1.0 / lam, solver="liblinear",
                                fit_intercept=False, tol=1e-13, max_iter=20000)
        sk.fit(X, y)
        ours = set(np.nonzero(np.asarray(res.coef))[0])
        theirs = set(np.nonzero(sk.coef_[0])[0])
        assert ours == theirs, f"lambda={lam}: support {ours} != sklearn {theirs}"
        nnz = len(ours)
        assert nnz <= prev_nnz
        prev_nnz = nnz
        if expect_nnz_below is not None:
            assert nnz < expect_nnz_below


def test_box_constrained_lbfgs(rng):
    # minimize ||x - 2|| s.t. x <= 1 -> solution clipped at 1
    vg = lambda x: (0.5 * jnp.sum((x - 2.0) ** 2), x - 2.0)
    cfg = SolverConfig(tolerance=1e-12, max_iterations=100,
                       upper_bounds=jnp.ones(5), lower_bounds=-jnp.ones(5))
    res = minimize(OptimizerType.LBFGSB, vg, jnp.zeros(5), config=cfg)
    np.testing.assert_allclose(res.coef, np.ones(5), rtol=1e-8)


def test_solver_vmaps_over_problems(rng):
    """The property the random-effect path depends on: the same jittable
    solver vmaps over a batch of independent problems."""
    B, d = 6, 5
    Xs = rng.normal(size=(B, 200, d))
    ws = rng.normal(size=(B, d))
    ys = (rng.random((B, 200)) < 1.0 / (1.0 + np.exp(-np.einsum("bnd,bd->bn", Xs, ws)))).astype(np.float64)

    obj = GLMObjective(LogisticLoss)
    hyper = Hyper.of(0.1, dtype=jnp.float64)

    def solve_one(x, y):
        batch = DataBatch(x, y)
        vg = lambda c: obj.value_and_gradient(c, batch, hyper)
        return lbfgs.minimize(vg, jnp.zeros(d, dtype=x.dtype),
                              config=SolverConfig(tolerance=1e-10, max_iterations=100))

    batched = jax.jit(jax.vmap(solve_one))(jnp.asarray(Xs), jnp.asarray(ys))
    for b in range(B):
        single = solve_one(jnp.asarray(Xs[b]), jnp.asarray(ys[b]))
        np.testing.assert_allclose(batched.coef[b], single.coef, rtol=1e-5, atol=1e-7)


def _dense_bfgs_direction(g, pairs):
    """-H g with H the inverse-BFGS matrix built from ``pairs`` (oldest
    first) on gamma * I, gamma from the newest pair: float64, no recursion."""
    g = np.asarray(g, np.float64)
    d = g.shape[0]
    h = np.eye(d)
    if pairs:
        s, y = pairs[-1]
        h = h * ((s @ y) / (y @ y))
    for s, y in pairs:
        rho = 1.0 / (s @ y)
        v = np.eye(d) - rho * np.outer(s, y)
        h = v @ h @ v.T + rho * np.outer(s, s)
    return -h @ g


@pytest.mark.parametrize("stored", [0, 1, 3, 6, 9])
def test_two_loop_direction_over_the_age_ordered_history(rng, stored):
    """``push_pair`` keeps slot 0 the newest pair and drops the oldest once
    ``m`` are held (``stored`` = 9 > m = 6); ``two_loop_direction`` over that
    layout is the dense inverse-BFGS product of the pairs still held."""
    m, d = 6, 7
    s_hist, y_hist = jnp.zeros((m, d)), jnp.zeros((m, d))
    rho = jnp.zeros((m,))
    pairs = []
    for _ in range(stored):
        s = rng.normal(size=d)
        y = s + 0.3 * rng.normal(size=d)          # s . y > 0
        pairs.append((s, y))
        s_hist, y_hist, rho = lbfgs.push_pair(
            jnp.asarray(True), s_hist, y_hist, rho, jnp.asarray(s),
            jnp.asarray(y), jnp.asarray(s @ y))
    # a refused pair leaves the history as it was
    kept = lbfgs.push_pair(jnp.asarray(False), s_hist, y_hist, rho,
                           jnp.ones(d), jnp.ones(d), jnp.asarray(float(d)))
    for was, now in zip((s_hist, y_hist, rho), kept):
        np.testing.assert_array_equal(now, was)
    held = pairs[-m:]
    n_pairs = len(held)
    for age, (s, y) in enumerate(reversed(held)):
        np.testing.assert_array_equal(s_hist[age], s)
        np.testing.assert_array_equal(y_hist[age], y)
    np.testing.assert_array_equal(s_hist[n_pairs:], 0.0)

    g = rng.normal(size=d)
    got = lbfgs.two_loop_direction(jnp.asarray(g), s_hist, y_hist, rho,
                                   jnp.asarray(n_pairs, jnp.int32), m)
    np.testing.assert_allclose(got, _dense_bfgs_direction(g, held),
                               rtol=1e-10, atol=1e-12)


@pytest.mark.parametrize("solver", ["lbfgs", "owlqn"])
def test_a_vmapped_solve_gathers_and_scatters_nothing(solver):
    """The pin of the age-ordered history: no read or write of it is
    addressed by a value a lane owns, so ``vmap`` over a problem that
    gathers nothing itself leaves no ``gather`` and no ``scatter`` in the
    program (a ring buffer's position made every history read a per-lane
    gather over ``[E, m, K]``: PERF.md, PR 28)."""
    def vg(x, a, b):
        r = a * x - b
        return 0.5 * jnp.dot(r, r), a * r

    if solver == "lbfgs":
        solve = lambda x, a, b: lbfgs.minimize(vg, x, a, b)
    else:
        solve = lambda x, a, b: owlqn.minimize(vg, x, a, b, l1_weight=0.1)
    a = jnp.linspace(0.5, 3.0, 7 * 8).reshape(7, 8)
    b = jnp.cos(jnp.arange(7.0 * 8)).reshape(7, 8)
    x0 = jnp.zeros((7, 8))
    # the printed jaxpr holds the loops' bodies in full
    program = str(jax.make_jaxpr(jax.vmap(solve))(x0, a, b))
    assert "while[" in program and "dot_general[" in program
    addressed = set(re.findall(
        r"\b(?:gather|scatter[-\w]*|dynamic_slice|dynamic_update_slice)\[",
        program))
    assert not addressed, addressed
    res = jax.jit(jax.vmap(solve))(x0, a, b)
    assert np.all(np.asarray(res.reason) != ConvergenceReason.NOT_CONVERGED)
    if solver == "lbfgs":
        np.testing.assert_allclose(res.coef, b / a, atol=1e-3)


def _rosenbrock_lanes():
    starts = jnp.asarray(np.linspace(-0.6, 0.6, 7)[:, None] * np.ones((1, 10)))
    solve = lambda x0: lbfgs.minimize(
        rosen_vg, x0, config=SolverConfig(max_iterations=300, tolerance=1e-12))
    return solve, starts


def _logistic_lanes():
    batch, _, _ = make_logistic(np.random.default_rng(42))
    obj = GLMObjective(LogisticLoss)

    def solve(l2):
        vg = lambda c: obj.value_and_gradient(
            c, batch, Hyper.of(l2, dtype=jnp.float64))
        return lbfgs.minimize(
            vg, jnp.zeros(D),
            config=SolverConfig(max_iterations=300, tolerance=1e-12))

    return solve, jnp.asarray([0.01, 0.1, 0.3, 1.0, 3.0, 10.0, 100.0])


# what the ring-buffer history gave (the parent of PR 28, float64, this
# container): the age-ordered recursion is the same arithmetic
@pytest.mark.parametrize("lanes, iterations, evaluations", [
    (_rosenbrock_lanes, [68, 67, 66, 60, 58, 56, 36],
     [83, 81, 81, 79, 76, 69, 40]),
    (_logistic_lanes, [11, 11, 11, 11, 11, 10, 8],
     [12, 12, 12, 12, 12, 11, 9]),
], ids=["rosenbrock", "logistic"])
def test_lbfgs_counts_are_the_ring_buffers_alone_and_in_a_batch_of_7(
        lanes, iterations, evaluations):
    solve, inputs = lanes()
    batched = jax.jit(jax.vmap(solve))(inputs)
    np.testing.assert_array_equal(batched.iterations, iterations)
    np.testing.assert_array_equal(batched.num_fun_evals, evaluations)
    np.testing.assert_array_equal(
        batched.reason, ConvergenceReason.FUNCTION_VALUES_CONVERGED)
    alone = jax.jit(solve)
    for lane in (0, 3, 6):
        one = alone(inputs[lane])
        assert (int(one.iterations), int(one.num_fun_evals), int(one.reason)) \
            == (iterations[lane], evaluations[lane],
                ConvergenceReason.FUNCTION_VALUES_CONVERGED)
        np.testing.assert_allclose(batched.coef[lane], one.coef,
                                   rtol=1e-7, atol=1e-9)


def test_minimize_dispatch_errors():
    with pytest.raises(ValueError):
        minimize(OptimizerType.TRON, lambda x: (x @ x, 2 * x), jnp.zeros(3))


def test_tron_explicit_matches_matrix_free(rng):
    """The explicit d x d Gauss-Newton path and the matrix-free Hv path
    must produce the same solve (optim/problem.py auto gate: explicit on
    CPU up to d=256, on TPU up to d=2048 — both sides of the gate are
    exercised here regardless of backend)."""
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        GlmOptimizationProblem,
        OptimizerConfig,
    )
    from photon_tpu.types import TaskType

    batch, X, y = make_logistic(rng, n=600)
    coefs = {}
    for explicit in (False, True):
        cfg = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(
                optimizer_type=OptimizerType.TRON,
                max_iterations=60, tolerance=1e-11,
                explicit_hessian=explicit),
            regularization=L2Regularization, regularization_weight=0.5)
        prob = GlmOptimizationProblem(TaskType.LOGISTIC_REGRESSION, cfg)
        model, res = prob.run(batch, dim=D, dtype=jnp.float64)
        coefs[explicit] = np.asarray(model.coefficients.means)
    np.testing.assert_allclose(coefs[True], coefs[False],
                               rtol=1e-6, atol=1e-8)


def test_direct_solver_matches_ridge_and_tron(rng):
    """DIRECT (normal equations, optim/direct.py) computes the exact ridge
    minimizer: parity vs sklearn Ridge(cholesky) and vs a tightly-converged
    TRON on the same problem; non-quadratic tasks are rejected."""
    from sklearn.linear_model import Ridge

    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        GlmOptimizationProblem,
        OptimizerConfig,
    )
    from photon_tpu.types import TaskType

    n = 800
    X = rng.normal(size=(n, D))
    y = X @ rng.normal(size=D) + 0.3 * rng.normal(size=n)
    batch = DataBatch(jnp.asarray(X), jnp.asarray(y))
    lam = 2.5

    def solve(opt_type, **kw):
        cfg = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(optimizer_type=opt_type, **kw),
            regularization=L2Regularization, regularization_weight=lam)
        prob = GlmOptimizationProblem(TaskType.LINEAR_REGRESSION, cfg)
        model, res = prob.run(batch, dim=D, dtype=jnp.float64)
        return np.asarray(model.coefficients.means), res

    c_direct, res = solve(OptimizerType.DIRECT)
    assert int(res.iterations) == 1

    sk = Ridge(alpha=lam, fit_intercept=False, solver="cholesky")
    sk.fit(X, y)
    # same objective: photon minimizes sum of 0.5*(m-y)^2 + 0.5*lam*||w||^2,
    # sklearn minimizes ||Xw-y||^2 + alpha*||w||^2 — identical minimizer
    # when alpha = lam (both quadratic forms scale together)
    np.testing.assert_allclose(c_direct, sk.coef_, rtol=1e-8, atol=1e-10)

    c_tron, _ = solve(OptimizerType.TRON, max_iterations=100, tolerance=1e-13)
    np.testing.assert_allclose(c_direct, c_tron, rtol=1e-6, atol=1e-8)

    with pytest.raises(ValueError, match="DIRECT"):
        cfg = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(optimizer_type=OptimizerType.DIRECT),
            regularization=L2Regularization, regularization_weight=1.0)
        prob = GlmOptimizationProblem(TaskType.LOGISTIC_REGRESSION, cfg)
        prob.run(batch, dim=D, dtype=jnp.float64)


def test_direct_reg_path_shared_gram(rng):
    """The DIRECT lambda path (one data pass + per-lambda Cholesky,
    optim/direct.minimize_path) equals per-lambda DIRECT solves, raw and
    under STANDARDIZATION normalization, with and without a warm start."""
    from photon_tpu.data.stats import compute_feature_stats
    from photon_tpu.estimators.model_training import (
        train_generalized_linear_model,
    )
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.ops.normalization import (
        NormalizationType,
        build_normalization_context,
        no_normalization,
    )
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        OptimizerConfig,
    )
    from photon_tpu.types import TaskType

    n = 600
    X = rng.normal(size=(n, D)) * (1.0 + np.arange(D))
    X[:, -1] = 1.0                                     # intercept column
    y = X @ rng.normal(size=D) + 0.4 * rng.normal(size=n)
    batch = DataBatch(jnp.asarray(X), jnp.asarray(y))
    lambdas = [0.1, 1.0, 10.0]
    cfg = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(optimizer_type=OptimizerType.DIRECT),
        regularization=L2Regularization)

    s = compute_feature_stats(batch.features, D)
    norm = build_normalization_context(
        NormalizationType.STANDARDIZATION, s.mean, s.variance, s.abs_max,
        intercept_index=D - 1)
    x_init = np.asarray(rng.normal(size=D) * 0.1)

    for nrm, icpt in ((no_normalization(), None), (norm, D - 1)):
        for init in (None, x_init):
            path_models, path_stats = train_generalized_linear_model(
                TaskType.LINEAR_REGRESSION, batch, D, cfg,
                regularization_weights=lambdas, norm=nrm, initial=init,
                dtype=jnp.float64, intercept_index=icpt)
            for lam in lambdas:
                single, sres = train_generalized_linear_model(
                    TaskType.LINEAR_REGRESSION, batch, D, cfg,
                    regularization_weights=[lam], norm=nrm, initial=init,
                    dtype=jnp.float64, intercept_index=icpt)
                np.testing.assert_allclose(
                    np.asarray(path_models[lam].coefficients.means),
                    np.asarray(single[lam].coefficients.means),
                    rtol=1e-8, atol=1e-10)
                np.testing.assert_allclose(
                    float(path_stats[lam].value), float(sres[lam].value),
                    rtol=1e-8)


def test_direct_path_respects_regularization_context(rng):
    """The shared-Gram path splits lambda through the SAME regularization
    context as the per-lambda path: NoRegularization yields identical
    (unregularized) solutions for every lambda, and non-quadratic tasks
    are rejected before the path runs."""
    from photon_tpu.estimators.model_training import (
        train_generalized_linear_model,
    )
    from photon_tpu.function.objective import NoRegularization
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        OptimizerConfig,
    )
    from photon_tpu.types import TaskType

    n = 300
    X = rng.normal(size=(n, D))
    y = X @ rng.normal(size=D) + 0.1 * rng.normal(size=n)
    batch = DataBatch(jnp.asarray(X), jnp.asarray(y))
    cfg = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(optimizer_type=OptimizerType.DIRECT),
        regularization=NoRegularization)
    models, _ = train_generalized_linear_model(
        TaskType.LINEAR_REGRESSION, batch, D, cfg,
        regularization_weights=[0.5, 5.0], dtype=jnp.float64)
    c = {lam: np.asarray(m.coefficients.means) for lam, m in models.items()}
    np.testing.assert_allclose(c[0.5], c[5.0], rtol=1e-12)  # both raw OLS
    single, _ = train_generalized_linear_model(
        TaskType.LINEAR_REGRESSION, batch, D, cfg,
        regularization_weights=[0.5], dtype=jnp.float64)
    np.testing.assert_allclose(
        c[0.5], np.asarray(single[0.5].coefficients.means), rtol=1e-8)

    with pytest.raises(ValueError, match="DIRECT"):
        train_generalized_linear_model(
            TaskType.LOGISTIC_REGRESSION, batch, D, cfg,
            regularization_weights=[0.5, 5.0], dtype=jnp.float64)


def test_direct_singular_hessian_reports_not_converged(rng):
    """A rank-deficient unregularized problem must keep the start point
    AND say NOT_CONVERGED — a failed entity may not masquerade as
    converged in the per-entity trackers."""
    from photon_tpu.function.objective import GLMObjective, Hyper
    from photon_tpu.ops.losses import SquaredLoss
    from photon_tpu.optim import direct

    X = np.zeros((20, 4))          # all-zero features: H = 0 at lambda=0
    y = rng.normal(size=20)
    batch = DataBatch(jnp.asarray(X), jnp.asarray(y))
    obj = GLMObjective(SquaredLoss)
    hyper = Hyper.of(0.0, dtype=jnp.float64)
    x0 = jnp.asarray(rng.normal(size=4))
    res = direct.minimize(
        lambda c: obj.value_and_gradient(c, batch, hyper),
        lambda c: obj.hessian_matrix(c, batch, hyper), x0)
    np.testing.assert_array_equal(np.asarray(res.coef), np.asarray(x0))
    assert int(res.reason) == ConvergenceReason.NOT_CONVERGED
    assert np.isfinite(float(res.value))


def test_newton_logistic_vs_sklearn_and_tron(rng):
    """NEWTON (damped IRLS, optim/newton.py) matches sklearn and a
    tightly-converged TRON on L2 logistic regression, in far fewer outer
    iterations than L-BFGS (the point: each iteration is one batched
    Hessian Cholesky, so sequential depth is ~5, not ~50)."""
    from sklearn.linear_model import LogisticRegression

    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        GlmOptimizationProblem,
        OptimizerConfig,
    )
    from photon_tpu.types import TaskType

    batch, X, y = make_logistic(rng)

    def solve(opt_type, **kw):
        cfg = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(optimizer_type=opt_type, **kw),
            regularization=L2Regularization, regularization_weight=1.0)
        prob = GlmOptimizationProblem(TaskType.LOGISTIC_REGRESSION, cfg)
        model, res = prob.run(batch, dim=D, dtype=jnp.float64)
        return np.asarray(model.coefficients.means), res

    c_newton, res = solve(OptimizerType.NEWTON,
                          max_iterations=50, tolerance=1e-12)
    sk = LogisticRegression(C=1.0, fit_intercept=False, tol=1e-12,
                            max_iter=5000)
    sk.fit(X, y)
    np.testing.assert_allclose(c_newton, sk.coef_[0], rtol=1e-5, atol=1e-7)

    c_tron, _ = solve(OptimizerType.TRON, max_iterations=100, tolerance=1e-12)
    np.testing.assert_allclose(c_newton, c_tron, rtol=1e-6, atol=1e-8)

    c_lbfgs, res_l = solve(OptimizerType.LBFGS,
                           max_iterations=300, tolerance=1e-12)
    assert int(res.iterations) < int(res_l.iterations)
    assert int(res.iterations) <= 12
    assert int(res.reason) in (ConvergenceReason.FUNCTION_VALUES_CONVERGED,
                               ConvergenceReason.GRADIENT_CONVERGED)


def test_newton_poisson_vs_tron(rng):
    """NEWTON on Poisson: the exp-margin Hessian is where the Armijo
    safeguard earns its keep (a full Newton step can overflow); parity vs
    TRON at tight tolerance."""
    n = 800
    X = rng.normal(size=(n, D)) * 0.3
    w = rng.normal(size=D) * 0.5
    y = rng.poisson(np.exp(X @ w)).astype(np.float64)
    batch = DataBatch(jnp.asarray(X), jnp.asarray(y))

    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        GlmOptimizationProblem,
        OptimizerConfig,
    )
    from photon_tpu.types import TaskType

    def solve(opt_type):
        cfg = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(optimizer_type=opt_type,
                                      max_iterations=60, tolerance=1e-12),
            regularization=L2Regularization, regularization_weight=1e-3)
        prob = GlmOptimizationProblem(TaskType.POISSON_REGRESSION, cfg)
        model, res = prob.run(batch, dim=D, dtype=jnp.float64)
        return np.asarray(model.coefficients.means), res

    c_newton, res = solve(OptimizerType.NEWTON)
    c_tron, _ = solve(OptimizerType.TRON)
    np.testing.assert_allclose(c_newton, c_tron, rtol=1e-5, atol=1e-7)
    assert float(jnp.linalg.norm(res.gradient)) < 1e-6


def test_newton_vmaps_over_problems(rng):
    """The property the random-effect path depends on: NEWTON vmaps over a
    batch of independent logistic problems (batched [E, K, K] Cholesky),
    matching per-problem solves."""
    from photon_tpu.function.objective import GLMObjective, Hyper
    from photon_tpu.optim import newton

    B, d = 6, 5
    Xs = rng.normal(size=(B, 200, d))
    ws = rng.normal(size=(B, d))
    ys = (rng.random((B, 200))
          < 1.0 / (1.0 + np.exp(-np.einsum("bnd,bd->bn", Xs, ws)))
          ).astype(np.float64)

    obj = GLMObjective(LogisticLoss)
    hyper = Hyper.of(0.1, dtype=jnp.float64)
    cfg = SolverConfig(tolerance=1e-10, max_iterations=30)

    def solve_one(x, y):
        batch = DataBatch(x, y)
        vg = lambda c: obj.value_and_gradient(c, batch, hyper)
        hm = lambda c: obj.hessian_matrix_from_weights(
            obj.hessian_weights(c, batch), d, batch, hyper)
        return newton.minimize(vg, hm, jnp.zeros(d, dtype=x.dtype),
                               config=cfg)

    batched = jax.jit(jax.vmap(solve_one))(jnp.asarray(Xs), jnp.asarray(ys))
    for b in range(B):
        single = solve_one(jnp.asarray(Xs[b]), jnp.asarray(ys[b]))
        np.testing.assert_allclose(batched.coef[b], single.coef,
                                   rtol=1e-6, atol=1e-8)
        assert int(batched.iterations[b]) == int(single.iterations)


def test_newton_rejects_unsupported_configs(rng):
    """No Hessian (smoothed hinge), L1 terms, and box constraints are all
    rejected up front — same contract style as DIRECT."""
    from photon_tpu.function.objective import (
        L2Regularization,
        RegularizationContext,
        RegularizationType,
    )
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        GlmOptimizationProblem,
        OptimizerConfig,
    )
    from photon_tpu.types import TaskType

    batch, _, _ = make_logistic(rng, n=50)
    with pytest.raises(ValueError, match="NEWTON"):
        GlmOptimizationProblem(
            TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM,
            GLMOptimizationConfiguration(
                optimizer=OptimizerConfig(optimizer_type=OptimizerType.NEWTON),
                regularization=L2Regularization, regularization_weight=1.0),
        ).run(batch, dim=D, dtype=jnp.float64)
    with pytest.raises(ValueError, match="NEWTON"):
        GlmOptimizationProblem(
            TaskType.LOGISTIC_REGRESSION,
            GLMOptimizationConfiguration(
                optimizer=OptimizerConfig(optimizer_type=OptimizerType.NEWTON),
                regularization=RegularizationContext(
                    RegularizationType.ELASTIC_NET, elastic_net_alpha=0.5),
                regularization_weight=1.0),
        ).run(batch, dim=D, dtype=jnp.float64)
    with pytest.raises(ValueError, match="NEWTON"):
        GlmOptimizationProblem(
            TaskType.LOGISTIC_REGRESSION,
            GLMOptimizationConfiguration(
                optimizer=OptimizerConfig(
                    optimizer_type=OptimizerType.NEWTON,
                    upper_bounds=jnp.ones(D)),
                regularization=L2Regularization, regularization_weight=1.0),
        ).run(batch, dim=D, dtype=jnp.float64)


def test_newton_singular_hessian_descent_fallback(rng):
    """Rank-deficient unregularized logistic: the Cholesky step is
    non-finite, the iteration must fall back to steepest descent and keep
    making progress (never stall at the start with a bogus reason)."""
    from photon_tpu.function.objective import GLMObjective, Hyper
    from photon_tpu.optim import newton

    n = 300
    Xhalf = rng.normal(size=(n, 3))
    X = np.concatenate([Xhalf, Xhalf], axis=1)       # exactly collinear
    w = rng.normal(size=6)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-X @ w))).astype(np.float64)
    batch = DataBatch(jnp.asarray(X), jnp.asarray(y))
    obj = GLMObjective(LogisticLoss)
    hyper = Hyper.of(0.0, dtype=jnp.float64)          # lambda = 0: H singular
    vg = lambda c: obj.value_and_gradient(c, batch, hyper)
    hm = lambda c: obj.hessian_matrix_from_weights(
        obj.hessian_weights(c, batch), 6, batch, hyper)
    x0 = jnp.zeros(6, jnp.float64)
    f0, _ = vg(x0)
    res = newton.minimize(vg, hm, x0,
                          config=SolverConfig(max_iterations=20,
                                              tolerance=1e-10))
    assert np.isfinite(float(res.value))
    assert float(res.value) < float(f0)              # made real progress
