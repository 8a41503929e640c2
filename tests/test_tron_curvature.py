"""TRON as a deployment runs it (PERF.md §4, ``fe-epsilon-tron``): the fit
through ``GameEstimator.fit`` against a plain NumPy float64 trust-region
Newton written from LIBLINEAR's description (Lin, Weng, Keerthi, JMLR 9,
2008; tron.cpp), the explicit and the matrix-free Hessian against each
other, the gate between them, the curvature counts ``SolverResult`` carries
for TRON and for no other solver, and no operator build after a refused
step."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.dataset import DataBatch
from photon_tpu.estimators.game_estimator import (
    CoordinateConfiguration,
    FixedEffectDataConfiguration,
    GameEstimator,
)
from photon_tpu.function.objective import L1Regularization, L2Regularization
from photon_tpu.game.dataset import FeatureShard, GameDataFrame
from photon_tpu.obs.metrics import registry
from photon_tpu.ops import features as F
from photon_tpu.optim import problem as P
from photon_tpu.optim import tron
from photon_tpu.optim.base import ConvergenceReason, SolverConfig
from photon_tpu.optim.problem import (
    GLMOptimizationConfiguration,
    GlmOptimizationProblem,
    OptimizerConfig,
)
from photon_tpu.types import OptimizerType, TaskType
from photon_tpu.utils import jitcache
from test_pallas_glm import _ticked, _ticks     # the routing's counters

N, D, L2 = 3000, 40, 1.0


# --------------------------------------------------------------------------
# the plain reference: nothing of photon_tpu below this line
# --------------------------------------------------------------------------

def numpy_tron(x, y, offsets, l2, max_iterations=15, tolerance=1e-5,
               max_cg=20, max_failures=5):
    """LIBLINEAR's trust-region Newton for L2-regularised logistic
    regression, float64: the outer loop of tron.cpp (eta0/1/2 = 1e-4, 0.25,
    0.75; sigma1/2/3 = 0.25, 0.5, 4; the first step caps the radius),
    Steihaug's truncated CG (trcg: stop at ||r|| <= 0.1 ||g||, step to the
    boundary when the iterate leaves the region), with Photon ML's caps and
    stopping rule around it (TRON.scala:256-262, Optimizer.scala:135-149:
    iterations, then the objective's change, then the gradient, each
    relative to the start; five refused steps in a row end the solve).
    Returns (w, accepted flags, CG steps)."""
    x = x.astype(np.float64)
    y = y.astype(np.float64)

    def fun(w):
        z = x @ w + offsets
        return np.sum(np.logaddexp(0.0, z) - y * z) + 0.5 * l2 * w @ w

    def grad(w):
        z = x @ w + offsets
        return x.T @ (1.0 / (1.0 + np.exp(-z)) - y) + l2 * w

    def curvature(w):
        p = 1.0 / (1.0 + np.exp(-(x @ w + offsets)))
        return p * (1.0 - p)

    def trcg(d2, g, delta):
        s, r = np.zeros_like(g), -g
        d, rtr, steps = r.copy(), g @ g, 0
        while np.sqrt(rtr) > 0.1 * np.linalg.norm(g) and steps < max_cg:
            steps += 1
            hd = x.T @ (d2 * (x @ d)) + l2 * d
            alpha = rtr / (d @ hd)
            s = s + alpha * d
            if np.linalg.norm(s) > delta:
                s = s - alpha * d
                std, sts, dtd = s @ d, s @ s, d @ d
                rad = np.sqrt(std * std + dtd * (delta * delta - sts))
                alpha = ((delta * delta - sts) / (std + rad) if std >= 0
                         else (rad - std) / dtd)
                return s + alpha * d, r - alpha * hd, steps
            r = r - alpha * hd
            rnew = r @ r
            d = r + (rnew / rtr) * d
            rtr = rnew
        return s, r, steps

    w = np.zeros(x.shape[1])
    f, g = fun(w), grad(w)
    delta = np.linalg.norm(g)
    value_tol, gradient_tol = tolerance * abs(f), tolerance * delta
    accepted, cg_steps, failures = [], 0, 0
    while True:
        s, r, steps = trcg(curvature(w), g, delta)
        cg_steps += steps
        gs = g @ s
        prered = -0.5 * (gs - s @ r)
        f_new = fun(w + s)
        actred = f - f_new
        snorm = np.linalg.norm(s)
        if not accepted:
            delta = min(delta, snorm)
        denom = f_new - f - gs
        alpha = 4.0 if denom <= 0 else max(0.25, -0.5 * (gs / denom))
        if actred < 1e-4 * prered:
            delta = min(max(alpha, 0.25) * snorm, 0.5 * delta)
        elif actred < 0.25 * prered:
            delta = max(0.25 * delta, min(alpha * snorm, 0.5 * delta))
        elif actred < 0.75 * prered:
            delta = max(0.25 * delta, min(alpha * snorm, 4.0 * delta))
        else:
            delta = max(delta, min(alpha * snorm, 4.0 * delta))
        ok = actred > 1e-4 * prered
        accepted.append(bool(ok))
        f_prev = f
        if ok:
            w, f, g, failures = w + s, f_new, grad(w + s), 0
        else:
            failures += 1
        if (len(accepted) >= max_iterations
                or (ok and abs(f_prev - f) <= value_tol)
                or np.linalg.norm(g) <= gradient_tol
                or failures >= max_failures):
            return w, accepted, cg_steps


# --------------------------------------------------------------------------
# the problem: 3,000 x 40, seeded; the offsets put the start where the model
# is confidently wrong, so the first Newton steps overshoot and one is
# refused
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def rows():
    rng = np.random.default_rng(33)
    x = rng.normal(size=(N, D)).astype(np.float32)
    theta = rng.normal(size=D) * 0.8
    y = (rng.random(N) < 1.0 / (1.0 + np.exp(-(x @ theta)))).astype(
        np.float32)
    offsets = np.where(y > 0, -3.0, 3.0).astype(np.float32)
    return x, y, offsets


def _fit(rows, explicit=None, optimizer=OptimizerType.TRON, **opt):
    """One fixed-effect coordinate through GameEstimator.fit, float32."""
    x, y, offsets = rows
    jitcache.clear()
    config = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(
            optimizer_type=optimizer, track_states=16,
            explicit_hessian=explicit,
            **{"max_iterations": 15, "tolerance": 1e-5, **opt}),
        regularization=L2Regularization, regularization_weight=L2)
    est = GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {"fixed": CoordinateConfiguration(
            FixedEffectDataConfiguration("features"), config)},
        update_sequence=["fixed"], num_iterations=1)
    frame = GameDataFrame(num_samples=N, response=y, offsets=offsets,
                          feature_shards={"features": FeatureShard(x, D)})
    model = est.fit(frame)[-1].model
    coord = est._coordinates["fixed"]
    return (np.asarray(model["fixed"].model.coefficients.means, np.float64),
            coord)


def _accepted(losses):
    """The accepted / refused sequence from the tracked objective: a
    refused step leaves it where it was."""
    losses = np.asarray(losses)
    return [bool(v) for v in losses != np.concatenate([[np.nan], losses[:-1]])]


def test_the_fit_takes_the_references_steps(rows):
    """Same accepted / refused sequence (one refused), outer iterations and
    CG steps as the float64 reference; coefficients within 2e-6 absolute
    (float32 against float64 at the same stopping point; read 3.1e-7)."""
    x, y, offsets = rows
    want, accepted, cg_steps = numpy_tron(x, y, offsets.astype(np.float64),
                                          L2)
    assert accepted.count(False) == 1 and accepted[-1]
    got, coord = _fit(rows)
    counts = coord.tron_counts()
    assert _accepted(coord.last_tracker.losses) == accepted
    assert int(coord.last_result.iterations) == len(accepted)
    assert counts == {"cg_steps": cg_steps,
                      "rejected_steps": accepted.count(False),
                      # one at the start, one after each accepted step but
                      # the last: none after the refused one
                      "hessian_builds": accepted[:-1].count(True) + 1}
    assert int(coord.last_result.num_fun_evals) == len(accepted) + 1
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


def test_explicit_and_matrix_free_agree(rows):
    """The two operators are one Hessian: the same counts, and coefficients
    within 2e-6 absolute of each other in float32 (read 1.5e-7)."""
    fits = {explicit: _fit(rows, explicit=explicit)
            for explicit in (True, False)}
    (c_exp, k_exp), (c_free, k_free) = fits[True], fits[False]
    assert k_exp.tron_counts() == k_free.tron_counts()
    assert (_accepted(k_exp.last_tracker.losses)
            == _accepted(k_free.last_tracker.losses))
    assert (int(k_exp.last_result.iterations)
            == int(k_free.last_result.iterations))
    np.testing.assert_allclose(c_exp, c_free, rtol=0, atol=2e-6)


def test_the_counts_feed_the_counters_once_a_result(rows):
    _, coord = _fit(rows)
    before = registry.snapshot()["counters"]
    counts = coord.tron_counts()
    assert coord.tron_counts() == counts          # asked twice, fed once
    after = registry.snapshot()["counters"]
    assert (after["solver.tron.cg_steps"]
            - before.get("solver.tron.cg_steps", 0)) == counts["cg_steps"]
    assert (after["solver.tron.rejected_steps"]
            - before.get("solver.tron.rejected_steps", 0)) == 1


# --------------------------------------------------------------------------
# the gate, and the counter that says which side a solve was traced on
# --------------------------------------------------------------------------

def _traced_path(features, d, explicit=None):
    """Which label of kernels.tron_hessian ONE traced solve ticks."""
    jitcache.clear()
    n = features.shape[0] if hasattr(features, "shape") else \
        features.indices.shape[0]
    batch = DataBatch(features, jnp.zeros(n), jnp.zeros(n), jnp.ones(n))
    problem = GlmOptimizationProblem(
        TaskType.LOGISTIC_REGRESSION, GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(optimizer_type=OptimizerType.TRON,
                                      explicit_hessian=explicit),
            regularization=L2Regularization, regularization_weight=1.0))
    before = registry.snapshot()["counters"]
    one = jnp.asarray(1.0)
    problem._solve_fn.lower(jnp.zeros(d), batch, one, one)
    after = registry.snapshot()["counters"]
    jitcache.clear()
    ticked = {k: after[k] - before.get(k, 0) for k in after
              if k.startswith("kernels.tron_hessian") and
              after[k] != before.get(k, 0)}
    assert len(ticked) == 1 and set(ticked.values()) == {1.0}, ticked
    return next(iter(ticked)).split('"')[1]


GATE_CASES = [
    # (backend, dense, dim) -> path, on either side of each backend's gate
    ("cpu", True, P.TRON_EXPLICIT_MAX_DIM_CPU, "explicit"),
    ("cpu", True, P.TRON_EXPLICIT_MAX_DIM_CPU + 1, "matrix_free"),
    ("tpu", True, P.TRON_EXPLICIT_MAX_DIM_TPU, "explicit"),
    ("tpu", True, P.TRON_EXPLICIT_MAX_DIM_TPU + 1, "matrix_free"),
    ("cpu", False, 8, "matrix_free"),
    ("tpu", False, 8, "matrix_free"),
]


@pytest.mark.parametrize("backend,dense,dim,path", GATE_CASES)
def test_the_gate_is_pinned(backend, dense, dim, path, monkeypatch):
    """Dense d on either side of the gate, and sparse features at any d,
    on the backend a solve would observe (the TPU's side is steered from
    here: the gate reads ``jax.default_backend()`` and nothing else)."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert P.tron_explicit_hessian(dense, dim) is (path == "explicit")
    n = 16
    feats = (jnp.zeros((n, dim)) if dense else F.SparseFeatures(
        jnp.zeros((n, 2), jnp.int32), jnp.zeros((n, 2))))
    assert _traced_path(feats, dim) == path


def test_the_gate_holds_the_measured_widths():
    """PERF.md §5's table (my chip runs, PR 34): on a TPU the GLMix cells'
    128 features sit on the explicit side (the product is XLA's two passes
    there and a build costs what one costs); from the least width whose
    product is ONE read of X through the fused kernel a build costs 1.5-4.5
    products and the table's fits (1.3-1.6 CG steps a build) are faster
    matrix-free: the gate is the last width under
    ``pallas_glm._DENSE_MIN_WIDTH``."""
    from photon_tpu.ops import pallas_glm

    assert P.TRON_EXPLICIT_MAX_DIM_TPU == pallas_glm._DENSE_MIN_WIDTH - 1 == 255
    assert P.TRON_EXPLICIT_MAX_DIM_CPU == 256


def test_a_configured_operator_overrides_the_gate():
    x = jnp.zeros((16, 8))
    assert _traced_path(x, 8, explicit=False) == "matrix_free"
    assert _traced_path(x, 8, explicit=True) == "explicit"


# --------------------------------------------------------------------------
# the counts are TRON's alone: no other solver's program carries them
# --------------------------------------------------------------------------

def _small_problem(optimizer, regularization=L2Regularization):
    rng = np.random.default_rng(5)
    n, d = 64, 6
    x = jnp.asarray(rng.normal(size=(n, d)))
    y = jnp.asarray((rng.random(n) < 0.5).astype(float))
    batch = DataBatch(x, y, jnp.zeros(n), jnp.ones(n))
    problem = GlmOptimizationProblem(
        TaskType.LOGISTIC_REGRESSION, GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(optimizer_type=optimizer,
                                      max_iterations=20),
            regularization=regularization, regularization_weight=1.0))
    return problem, batch, d


@pytest.mark.parametrize("optimizer,regularization", [
    (OptimizerType.LBFGS, L2Regularization),
    (OptimizerType.NEWTON, L2Regularization),
    (OptimizerType.OWLQN, L1Regularization)], ids=["LBFGS", "NEWTON", "OWLQN"])
def test_no_other_solver_carries_the_counts(optimizer, regularization):
    jitcache.clear()
    problem, batch, d = _small_problem(optimizer, regularization)
    _, result = problem.run(batch, dim=d, dtype=batch.labels.dtype)
    assert result.cg_steps is None
    assert result.hessian_builds is None
    assert result.rejected_steps is None
    # seven outputs, as before TRON counted anything
    assert len(jax.tree_util.tree_leaves(result)) == 7
    jitcache.clear()
    _, tron_result = _small_problem(OptimizerType.TRON)[0].run(
        batch, dim=d, dtype=batch.labels.dtype)
    assert len(jax.tree_util.tree_leaves(tron_result)) == 10


# sha256 of the lowered (StableHLO) text of one small float64 solve on the
# CPU, taken from the PARENT commit of PR 33 (4753a8d) with this very
# function: the fields TRON gained are None elsewhere, so the text is the
# parent's to the byte. A PR that means to change either solver re-pins it.
PARENT_LOWERED = {
    "LBFGS": "fe0ecb671dc1afec39c40f9ccefd24b5ede4c57e93b1d19ca4c28c4f3104cc0e",
    "NEWTON": "f91bb243aa0213f80dc8dc2271e9e3cbc013346f1f6a0680e0daea30f9382bc1",
}


def lowered_digest(optimizer):
    jitcache.clear()
    problem, batch, d = _small_problem(OptimizerType[optimizer])
    one = jnp.asarray(1.0)
    text = problem._solve_fn.lower(jnp.zeros(d), batch, one, one).as_text()
    jitcache.clear()
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("optimizer", sorted(PARENT_LOWERED))
def test_the_lowered_solve_is_the_parents(optimizer):
    assert lowered_digest(optimizer) == PARENT_LOWERED[optimizer]


# --------------------------------------------------------------------------
# a refused step keeps its operator
# --------------------------------------------------------------------------

def test_no_operator_build_after_a_refused_step():
    """``hess_setup`` RUNS (a host callback counts it, under the solver's
    ``lax.cond``) once at the start and once after each accepted step that
    another iteration follows, on a problem made to refuse steps: f(x) =
    sum(log(cosh(x))) from a far start, where the curvature is next to
    nothing, the gradient is not, and the quadratic model overshoots. Its
    input is the curvature each evaluation hands back beside value and
    gradient: the operator is built from the weights of the point the
    solver stands at, never from an evaluation of its own."""
    ran = []

    def value_and_grad(x):
        return (jnp.sum(jnp.log(jnp.cosh(x))), jnp.tanh(x),
                1.0 / jnp.cosh(x) ** 2)

    def hess_setup(d2):
        jax.debug.callback(lambda: ran.append(1))
        return d2

    result = jax.jit(lambda x0: tron.minimize(
        value_and_grad, None, x0,
        config=SolverConfig(max_iterations=40, tolerance=1e-9,
                            track_states=64),
        hess_setup=hess_setup, hess_apply=lambda h, v: h * v))(
            jnp.asarray([10.0, -8.0, 6.0]))
    jax.block_until_ready(result)
    jax.effects_barrier()
    iterations, rejected = int(result.iterations), int(result.rejected_steps)
    assert rejected == 2, "the problem was made to refuse two steps"
    accepted = _accepted(np.asarray(result.loss_history)[:iterations])
    assert accepted.count(False) == rejected
    assert len(ran) == int(result.hessian_builds)
    assert len(ran) == accepted[:-1].count(True) + 1
    assert len(ran) < iterations
    np.testing.assert_allclose(np.asarray(result.coef), 0.0, atol=1e-9)


# --------------------------------------------------------------------------
# the matrix-free product through the fused kernel (PR 34): one read of X a
# CG step where ``pallas_glm.dense_route`` admits the matrix
# --------------------------------------------------------------------------

@pytest.fixture
def on_tpu(monkeypatch):
    """The routing driven on the CPU (the kernel in interpret mode), every
    width admitted: the file's problem is 40 features wide."""
    from photon_tpu.ops import pallas_glm

    jitcache.clear()
    monkeypatch.setattr(pallas_glm, "_on_tpu", lambda: True)
    monkeypatch.setattr(pallas_glm, "_DENSE_MIN_WIDTH", 1)
    yield pallas_glm
    jitcache.clear()


def test_a_solve_through_the_kernel_takes_the_xla_paths_steps(rows, on_tpu):
    """A whole matrix-free TRON fit whose products (and evaluations) run
    the fused kernel ends with XLA's counts, accepted / refused sequence
    and reason, and its coefficients within 2e-6 absolute (read 4.8e-7).
    At tolerance 1e-4: at the file's 1e-5 this problem's LAST step lowers
    the objective (2,440.88) by one unit in its last place, so whether it
    is accepted or refused five times over turns on a summation order
    (PERF.md §7, float32 and the trust region), not on the operator."""
    with on_tpu.disabled():
        want, k_xla = _fit(rows, explicit=False, tolerance=1e-4)
        xla = (k_xla.tron_counts(), _accepted(k_xla.last_tracker.losses),
               int(k_xla.last_result.iterations),
               int(k_xla.last_result.reason),
               int(k_xla.last_result.num_fun_evals))
    before = _ticks("dense_hv")
    got, k_fused = _fit(rows, explicit=False, tolerance=1e-4)
    # one traced solve: one CG body, one product call site
    assert _ticked(before, "dense_hv") == {"hit": 1}
    assert (k_fused.tron_counts(), _accepted(k_fused.last_tracker.losses),
            int(k_fused.last_result.iterations),
            int(k_fused.last_result.reason),
            int(k_fused.last_result.num_fun_evals)) == xla
    assert xla[0]["rejected_steps"] == 1 and xla[0]["cg_steps"] > 0
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


def test_the_routed_cg_step_reads_x_once(rows, on_tpu):
    """The traced solve holds ONE kernel call in the CG step and no
    contraction over X outside its three kernel calls: the operator is
    taken from the curvature weights the evaluations hand back, with no
    pass over X of its own."""
    x, y, offsets = rows
    batch = DataBatch(jnp.asarray(x), jnp.asarray(y), jnp.asarray(offsets),
                      jnp.ones(N, jnp.float32))
    problem = GlmOptimizationProblem(
        TaskType.LOGISTIC_REGRESSION, GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(optimizer_type=OptimizerType.TRON,
                                      explicit_hessian=False),
            regularization=L2Regularization, regularization_weight=L2))
    one = jnp.float32(1.0)
    jaxpr = jax.make_jaxpr(problem._solve_fn)(
        jnp.zeros(D, jnp.float32), batch, one, one)

    def walk(jaxpr, inside=()):
        for eqn in jaxpr.eqns:
            yield eqn, inside
            if eqn.primitive.name == "pallas_call":
                continue
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from walk(inner, inside + (eqn.primitive.name,))

    eqns = list(walk(jaxpr.jaxpr))
    over_x = lambda e: any(getattr(v.aval, "shape", ()) == x.shape
                           for v in e.invars)
    # the kernel: the first evaluation, the trial point's, the product's
    # (the only one two ``while``s deep: the CG loop inside the outer loop)
    calls = [inside for e, inside in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 3, calls
    assert sum(inside.count("while") == 2 for inside in calls) == 1, calls
    # the weights' pass (X theta under the operator build's ``cond``) is
    # gone: no contraction over X anywhere outside the kernel
    dots = [inside for e, inside in eqns
            if e.primitive.name == "dot_general" and over_x(e)]
    assert dots == [], dots
    assert not any(e.primitive.name == "cond" for e, _ in eqns)


# The matrix-free fit of the file's problem at tolerance 1e-4 as the solver
# took it while every operator build read X for its weights (f93ae62, on
# the CPU), on XLA's path and through the kernel alike: 19 CG steps, 5
# operators, one refused step, six iterations ending on the objective's
# change, seven evaluations.
PARENT_FIT = ({"cg_steps": 19, "hessian_builds": 5, "rejected_steps": 1},
              [True, False, True, True, True, True], 6,
              ConvergenceReason.FUNCTION_VALUES_CONVERGED, 7)


@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_the_weights_from_the_evaluations_take_the_parents_steps(
        rows, on_tpu, route):
    """A matrix-free fit whose operator is the trial point's curvature
    weights (the kernel's per-row output, or the margins of XLA's first
    pass) takes the steps it took when every operator build read X for
    them: the same counts, accepted / refused sequence, iterations, reason
    and evaluations, and one evaluation routed a trace under
    ``dense_curv``, none under ``dense``."""
    before = {path: _ticks(path) for path in ("dense", "dense_curv")}
    if route == "xla":
        with on_tpu.disabled():
            _, k = _fit(rows, explicit=False, tolerance=1e-4)
        curv = {"mesh": 2}
    else:
        _, k = _fit(rows, explicit=False, tolerance=1e-4)
        curv = {"hit": 2}
    r = k.last_result
    assert (k.tron_counts(), _accepted(k.last_tracker.losses),
            int(r.iterations), int(r.reason), int(r.num_fun_evals)
            ) == PARENT_FIT
    # two evaluation call sites a traced solve: the first, the trial's
    assert _ticked(before["dense_curv"], "dense_curv") == curv
    assert _ticked(before["dense"], "dense") == {}


def test_per_entity_tron_under_vmap_keeps_xlas_products(on_tpu, monkeypatch):
    """Per-entity TRON (``game/coordinate.py``) batches its objective: the
    kernel's sequential grid is not vmap-safe, so every product there is
    turned away under ``vmap`` and the fit is the CPU's fit."""
    from photon_tpu.game.dataset import CsrRows
    from photon_tpu.game.random_effect import RandomEffectDataConfiguration

    rng = np.random.default_rng(2)
    n, d_u, users = 300, 4, 6
    xu = rng.normal(size=(n, d_u)).astype(np.float32)
    uid = rng.integers(0, users, size=n)
    y = (rng.random(n) < 0.5).astype(np.float32)
    frame = GameDataFrame(
        num_samples=n, response=y,
        feature_shards={"u": FeatureShard(CsrRows.from_dense(xu), d_u)},
        id_tags={"userId": [f"u{v}" for v in uid]})

    def fit():
        jitcache.clear()
        config = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(
                optimizer_type=OptimizerType.TRON, max_iterations=15,
                tolerance=1e-6, explicit_hessian=False),
            regularization=L2Regularization, regularization_weight=0.5)
        est = GameEstimator(
            TaskType.LOGISTIC_REGRESSION,
            {"per_user": CoordinateConfiguration(
                RandomEffectDataConfiguration("userId", "u"), config)},
            update_sequence=["per_user"], num_iterations=1,
            dtype=jnp.float32)
        model = est.fit(frame)[-1].model
        assert all(est._coordinates["per_user"]._dense_local_blocks)
        return np.asarray(model["per_user"].coefficients)

    before = _ticks("dense_hv")
    routed = fit()
    ticked = _ticked(before, "dense_hv")
    assert set(ticked) == {"vmap"}, ticked
    monkeypatch.setattr(on_tpu, "_on_tpu", lambda: False)
    before = _ticks("dense_hv")
    plain = fit()
    assert _ticked(before, "dense_hv") == {}
    np.testing.assert_array_equal(routed, plain)
