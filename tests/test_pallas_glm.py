"""Pallas fused dense GLM kernel vs the XLA aggregator path.

Interpret mode makes these exact-semantics checks run on every backend
(the TPU lowering shares the same kernel body); parity pins the kernel
to ValueAndGradientAggregator semantics the same way the aggregator
tests pin the XLA path to jax.grad.
"""

import inspect
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout

from photon_tpu.data.dataset import DataBatch
from photon_tpu.ops import aggregators
from photon_tpu.ops.losses import LogisticLoss, PoissonLoss, SquaredLoss
from photon_tpu.ops.normalization import no_normalization
from photon_tpu.ops.pallas_glm import (
    fused_dense_hessian_vector,
    fused_dense_value_grad,
)

# tests/ is on sys.path (pytest's prepend import mode): the shapes and what
# a v5e lays them as, on record beside the rule's CPU tests
from test_dataset_layout import V5E_DEFAULTS  # noqa: E402

_IDN = no_normalization()


@pytest.fixture
def problem():
    rng = np.random.default_rng(7)
    n, d = 997, 37          # deliberately not tile-aligned
    X = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    y = jnp.asarray((rng.random(n) > 0.4), jnp.float32)
    off = jnp.asarray(rng.normal(size=n) * 0.2, jnp.float32)
    w = jnp.asarray(rng.random(n) + 0.1, jnp.float32)
    coef = jnp.asarray(rng.normal(size=d) * 0.4, jnp.float32)
    return X, y, off, w, coef


@pytest.mark.parametrize("loss", [LogisticLoss, SquaredLoss, PoissonLoss],
                         ids=lambda l: l.name)
def test_fused_matches_aggregator(problem, loss):
    X, y, off, w, coef = problem
    v0, g0 = aggregators.value_and_gradient(
        loss, X, y, off, w, coef, no_normalization())
    v1, g1 = fused_dense_value_grad(loss, X, y, off, w, coef, tile_n=256)
    np.testing.assert_allclose(float(v1), float(v0), rtol=5e-6)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0),
                               rtol=5e-5, atol=5e-5)


def test_fused_none_offsets_weights(problem):
    X, y, _, _, coef = problem
    v0, g0 = aggregators.value_and_gradient(
        LogisticLoss, X, y, None, None, coef, no_normalization())
    v1, g1 = fused_dense_value_grad(LogisticLoss, X, y, None, None, coef)
    np.testing.assert_allclose(float(v1), float(v0), rtol=5e-6)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0),
                               rtol=5e-5, atol=5e-5)


def _ticks(path="dense"):
    """The routing's trace-time counters of one path (``dense``: the
    evaluations; ``dense_hv``: the Hessian-vector products), by (name,
    reason)."""
    from photon_tpu.obs.metrics import registry

    out = {}
    for key, v in registry.snapshot()["counters"].items():
        if key.startswith(f"kernels.pallas_hits{{path=\"{path}\""):
            out["hit"] = out.get("hit", 0) + int(v)
        elif key.startswith(f"kernels.xla_fallbacks{{path=\"{path}\""):
            reason = key.split('reason="')[1].split('"')[0]
            out[reason] = out.get(reason, 0) + int(v)
    return out


def _ticked(before, path="dense"):
    now = _ticks(path)
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v != before.get(k, 0)}


@pytest.fixture
def on_tpu(monkeypatch):
    """Drive the routing on the CPU: the ONE backend check answers "a
    TPU", and the kernel runs in interpret mode (``_default_interpret``
    still sees the CPU). Compiled programs traced under it are dropped
    on the way in and out."""
    from photon_tpu.ops import pallas_glm
    from photon_tpu.utils import jitcache

    jitcache.clear()
    monkeypatch.setattr(pallas_glm, "_on_tpu", lambda: True)
    yield pallas_glm
    jitcache.clear()


@pytest.fixture
def wide_problem():
    """epsilon's width, ragged rows: d = 2,000 is no multiple of 128 and
    n = 700 no multiple of the 256-row tile the kernel picks there."""
    rng = np.random.default_rng(11)
    n, d = 700, 2000
    X = jnp.asarray(rng.normal(size=(n, d)) / np.sqrt(d), jnp.float32)
    y = jnp.asarray((rng.random(n) > 0.4), jnp.float32)
    off = jnp.asarray(rng.normal(size=n) * 0.2, jnp.float32)
    w = jnp.asarray(rng.random(n) + 0.1, jnp.float32)
    coef = jnp.asarray(rng.normal(size=d) * 4.0, jnp.float32)
    return X, y, off, w, coef


def test_routing_takes_the_objective_through_the_kernel(wide_problem,
                                                         on_tpu):
    """On a TPU the dense f32 identity objective of an admitted width
    runs the fused kernel, by what the function observes and nothing
    else, with unchanged results at the solver boundary."""
    from photon_tpu.function.objective import GLMObjective, Hyper

    X, y, off, w, coef = wide_problem
    batch = DataBatch(X, y, off, w)
    obj = GLMObjective(LogisticLoss)
    hyper = Hyper(l2_weight=jnp.float32(0.3))
    with on_tpu.disabled():
        v0, g0 = obj.value_and_gradient(coef, batch, hyper)
    before = _ticks()
    v1, g1 = obj.value_and_gradient(coef, batch, hyper)
    assert _ticked(before) == {"hit": 1}
    np.testing.assert_allclose(float(v1), float(v0), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0),
                               rtol=2e-6, atol=2e-6)
    # sparse features go to the XLA path untouched and uncounted: the
    # ELL kernel is routed by nothing
    from photon_tpu.ops import features as F
    before = _ticks()
    idx = jnp.tile(jnp.arange(8, dtype=jnp.int32), (X.shape[0], 1))
    sb = DataBatch(F.SparseFeatures(idx, X[:, :8]), y, off, w)
    vs, gs = obj.value_and_gradient(coef[:8], sb, hyper)
    vr, gr = aggregators.value_and_gradient(
        LogisticLoss, sb.features, y, off, w, coef[:8], no_normalization())
    np.testing.assert_allclose(
        float(vs), float(vr) + 0.15 * float(coef[:8] @ coef[:8]), rtol=1e-6)
    assert np.isfinite(float(vs)) and bool(jnp.all(jnp.isfinite(gs)))
    assert _ticked(before) == {}


@pytest.mark.parametrize("n,d,tile", [
    (700, 2000, None),      # epsilon's width, the tile the kernel picks
    (997, 2000, 256),       # three whole tiles, 229 rows over
    (130, 2000, 128),       # one tile, two rows over
    (513, 1500, 512),       # 11 whole lane tiles + 92 lanes; one row over
    (1000, 37, 256),        # narrower than one lane tile
    (100, 300, None),       # fewer rows than one 128-row block: no kernel
    (1024, 256, 512),       # nothing left over
], ids=lambda v: str(v))
def test_fused_ragged_shapes_against_xla(n, d, tile):
    """d not a multiple of 128 AND n not a multiple of the tile: X goes
    in as placed; the kernel takes the whole tiles and is never shown a
    block past the end of X (interpret mode would fill it with NaN, a
    stale buffer on the chip with anything); the rows left over are
    summed beside it."""
    rng = np.random.default_rng(n + d)
    X = jnp.asarray(rng.normal(size=(n, d)) / np.sqrt(d), jnp.float32)
    y = jnp.asarray((rng.random(n) > 0.4), jnp.float32)
    off = jnp.asarray(rng.normal(size=n) * 0.2, jnp.float32)
    w = jnp.asarray(rng.random(n) + 0.1, jnp.float32)
    coef = jnp.asarray(rng.normal(size=d) * 4.0, jnp.float32)
    v0, g0 = aggregators.value_and_gradient(
        LogisticLoss, X, y, off, w, coef, no_normalization())
    v1, g1 = fused_dense_value_grad(LogisticLoss, X, y, off, w, coef,
                                    tile_n=tile)
    assert np.isfinite(float(v1)) and np.isfinite(np.asarray(g1)).all()
    np.testing.assert_allclose(float(v1), float(v0), rtol=2e-6)
    scale = float(jnp.abs(g0).max())
    np.testing.assert_allclose(np.asarray(g1) / scale,
                               np.asarray(g0) / scale, atol=2e-6)


@pytest.mark.parametrize("sample_weights", [False, True],
                         ids=["unweighted", "weighted"])
@pytest.mark.parametrize("n,d", [
    (300, 2000),            # one tile, 44 rows over
    (4000, 2000),           # 15 tiles, 160 rows over
    (8016, 2000),           # the chip's small-X-in-VMEM shapes (PR 32)
    (8100, 2000),
    (20001, 2000),
    (8016, 1000),
    (1000, 256),            # the least width the gate admits
], ids=lambda v: str(v))
def test_product_through_the_kernel_against_xla(n, d, sample_weights):
    """A Hessian-vector product IS the kernel's evaluation of the squared
    loss at labels 0 and offsets 0 with the curvature weights for sample
    weights: ``fused_dense_hessian_vector`` against XLA's two passes, at
    ragged and aligned shapes; the value it returns beside the product is
    the quadratic form ``v . Hv / 2``."""
    from photon_tpu.ops import pallas_glm

    rng = np.random.default_rng(n + d)
    X = jnp.asarray(rng.normal(size=(n, d)) / np.sqrt(d), jnp.float32)
    p = 1.0 / (1.0 + np.exp(-rng.normal(size=n) * 2.0))
    d2 = p * (1.0 - p)
    if sample_weights:      # folded into d2, as ``hessian_weights`` does
        d2 = d2 * (rng.random(n) + 0.1)
    d2 = jnp.asarray(d2, jnp.float32)
    v = jnp.asarray(rng.normal(size=d), jnp.float32)
    with pallas_glm.disabled():
        hv0 = aggregators.hessian_vector_from_weights(X, d2, v, _IDN, d)
    q, hv1 = fused_dense_hessian_vector(X, d2, v)
    assert hv1.shape == (d,) and hv1.dtype == jnp.float32
    assert np.isfinite(np.asarray(hv1)).all()
    # two float32 summation orders over up to 20,001 rows (read 4.8e-6)
    scale = float(jnp.abs(hv0).max())
    np.testing.assert_allclose(np.asarray(hv1) / scale,
                               np.asarray(hv0) / scale, atol=1e-5)
    np.testing.assert_allclose(float(q), 0.5 * float(v @ hv1), rtol=5e-6)


@pytest.mark.parametrize("case", ["admitted", "narrow", "vmap", "disabled",
                                  "float64_vector", "normalised",
                                  "not_a_tpu"])
def test_product_route(case, wide_problem, on_tpu, monkeypatch):
    """``hessian_vector_from_weights`` goes where ``dense_route`` sends
    it, the gate of ``value_and_gradient``, and says so under its own
    label: ``kernels.pallas_hits{path=dense_hv}`` once a traced program
    that took the kernel, ``kernels.xla_fallbacks{path=dense_hv, reason}``
    once a traced program turned away; a float64 vector and a backend
    that is no TPU are not the kernel's case and tick nothing; a
    normalised product takes the kernel and ticks ``path=dense_hv_norm``,
    nothing under ``dense_hv``. Either way the product is XLA's."""
    from photon_tpu.ops.normalization import NormalizationContext

    X, _, _, w, v = wide_problem
    d2, d = 0.25 * w, X.shape[1]
    hv = lambda x=X, v=v, norm=_IDN: aggregators.hessian_vector_from_weights(
        x, d2, v, norm, v.shape[-1])
    with on_tpu.disabled():
        want = hv()
    before = {path: _ticks(path) for path in ("dense", "dense_hv")}
    ticked = lambda: _ticked(before["dense_hv"], "dense_hv")
    if case == "admitted":
        got = hv()
        assert ticked() == {"hit": 1}
        jaxpr = jax.make_jaxpr(hv)()
        eqns = list(_eqns(jaxpr.jaxpr))
        assert sum(e.primitive.name == "pallas_call" for e in eqns) == 1
    elif case == "narrow":
        narrow = on_tpu._DENSE_MIN_WIDTH // 2
        with on_tpu.disabled():
            want = hv(X[:, :narrow], v[:narrow])
        before["dense_hv"] = _ticks("dense_hv")
        got = hv(X[:, :narrow], v[:narrow])
        assert ticked() == {"shape": 1}
    elif case == "vmap":
        got = jax.vmap(lambda u: hv(v=u))(jnp.stack([v, v]))[1]
        assert ticked() == {"vmap": 1}
    elif case == "disabled":
        with on_tpu.disabled():
            got = hv()
        assert ticked() == {"mesh": 1}
    elif case == "float64_vector":
        got = hv(v=v.astype(jnp.float64))
        assert got.dtype == jnp.float64 and ticked() == {}
    elif case == "normalised":
        ones = NormalizationContext(factors=jnp.ones(d, jnp.float32),
                                    shifts=None)
        normed = _ticks("dense_hv_norm")
        got = hv(norm=ones)
        assert ticked() == {}
        assert _ticked(normed, "dense_hv_norm") == {"hit": 1}
    else:
        monkeypatch.setattr(on_tpu, "_on_tpu", lambda: False)
        got = hv()
        assert ticked() == {}
    # a product ticks nothing under the evaluations' label
    assert _ticked(before["dense"]) == {}
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got) / scale,
                               np.asarray(want) / scale, atol=2e-6)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold
    (``while`` bodies, ``pjit`` calls), a ``pallas_call``'s kernel body
    left out: what the kernel does in VMEM is not a copy of X."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_routed_solve_pads_no_matrix(wide_problem, on_tpu):
    """The routed L-BFGS solve holds the kernel inside its loops and no
    ``pad`` at all: X goes in as placed and the per-row vectors are cut
    to the whole tiles."""
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        GlmOptimizationProblem,
        OptimizerConfig,
    )
    from photon_tpu.types import TaskType

    X, y, off, w, _ = wide_problem
    prob = GlmOptimizationProblem(
        TaskType.LOGISTIC_REGRESSION, GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(max_iterations=5),
            regularization=L2Regularization, regularization_weight=1.0))
    one = jnp.float32(1.0)
    jaxpr = jax.make_jaxpr(prob._solve_fn)(
        jnp.zeros(X.shape[1], jnp.float32), DataBatch(X, y, off, w), one, one)
    eqns = list(_eqns(jaxpr.jaxpr))
    assert sum(e.primitive.name == "pallas_call" for e in eqns) >= 2
    assert not [e for e in eqns if e.primitive.name == "pad"]
    assert not any(e.primitive.name in ("dot_general", "concatenate")
                   and any(getattr(v.aval, "shape", ()) == X.shape
                           for v in e.invars) for e in eqns)


def test_fused_empty_batch():
    """n=0 must return zeros, not uninitialized buffers (grid would be
    empty) — the XLA path's empty-sum contract."""
    X = jnp.zeros((0, 5), jnp.float32)
    y = jnp.zeros((0,), jnp.float32)
    v, g = fused_dense_value_grad(LogisticLoss, X, y, None, None,
                                  jnp.ones((5,), jnp.float32))
    assert float(v) == 0.0
    np.testing.assert_array_equal(np.asarray(g), np.zeros(5))


def test_routed_solve_parity(wide_problem, on_tpu):
    """A full L-BFGS solve through the routed kernel lands on the same
    coefficients as the XLA path (f32 tolerance), and says so in the
    counters: one tick a traced program."""
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        GlmOptimizationProblem,
        OptimizerConfig,
    )
    from photon_tpu.types import TaskType
    from photon_tpu.utils import jitcache

    X, y, off, w, _ = wide_problem
    batch = DataBatch(X, y, off, w)
    cfg = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=80, tolerance=1e-8),
        regularization=L2Regularization, regularization_weight=1.0)

    def solve(**kw):
        prob = GlmOptimizationProblem(TaskType.LOGISTIC_REGRESSION, cfg)
        m, _ = prob.run(batch, dim=X.shape[1], dtype=jnp.float32, **kw)
        return np.asarray(m.coefficients.means)

    before = _ticks()
    c1 = solve()
    took = _ticked(before)
    # the objective is traced at the solver's init and in its line search
    assert set(took) == {"hit"} and took["hit"] >= 2, took
    before = _ticks()
    c0 = solve(pallas_ok=False)     # a caller-declared sharded batch
    assert set(_ticked(before)) == {"mesh"}
    # 2,000 coefficients from 700 rows in float32: the objective (485)
    # resolves 3e-5, which leaves each solve's end point 1e-2 of room in
    # norm under a curvature of one; the objective itself agrees closely
    assert np.linalg.norm(c1 - c0) < 1e-2 * np.linalg.norm(c0)
    from photon_tpu.function.objective import GLMObjective, Hyper
    with on_tpu.disabled():
        f1, f0 = (float(GLMObjective(LogisticLoss).value(
            jnp.asarray(c), batch, Hyper(l2_weight=jnp.float32(1.0))))
            for c in (c1, c0))
    assert abs(f1 - f0) <= 2e-6 * f0
    # the same program again: compiled once, counted once
    before = _ticks()
    solve()
    assert _ticked(before) == {}
    jitcache.clear()


def test_routing_turns_vmapped_re_solves_away(on_tpu, monkeypatch):
    """The vmapped per-entity objectives (dense-local random-effect
    blocks) must NOT reach the kernel — its sequential-grid accumulation
    is not vmap-safe — whatever their width: the fit is the CPU's fit and
    the refusals are counted under ``vmap``."""
    from photon_tpu.estimators.game_estimator import (
        CoordinateConfiguration,
        GameEstimator,
    )
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.game.dataset import CsrRows, FeatureShard, GameDataFrame
    from photon_tpu.game.random_effect import RandomEffectDataConfiguration
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        OptimizerConfig,
    )
    from photon_tpu.types import TaskType
    from photon_tpu.utils import jitcache

    rng = np.random.default_rng(2)
    n, d_u, users = 300, 4, 6
    Xu = rng.normal(size=(n, d_u)).astype(np.float32)
    uid = rng.integers(0, users, size=n)
    y = (rng.random(n) < 0.5).astype(np.float32)
    df = GameDataFrame(
        num_samples=n, response=y,
        feature_shards={"u": FeatureShard(CsrRows.from_dense(Xu), d_u)},
        id_tags={"userId": [f"u{v}" for v in uid]})

    def fit():
        jitcache.clear()
        opt = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(max_iterations=40, tolerance=1e-8),
            regularization=L2Regularization, regularization_weight=0.5)
        est = GameEstimator(
            TaskType.LOGISTIC_REGRESSION,
            {"per_user": CoordinateConfiguration(
                RandomEffectDataConfiguration("userId", "u"), opt)},
            update_sequence=["per_user"], num_iterations=1,
            dtype=jnp.float32)
        res = est.fit(df)
        # the dense-local fast path must actually be active
        assert all(est._coordinates["per_user"]._dense_local_blocks)
        return np.asarray(res[-1].model["per_user"].coefficients)

    # even with every width admitted, vmap refuses first
    monkeypatch.setattr(on_tpu, "_DENSE_MIN_WIDTH", 1)
    before = _ticks()
    c_on = fit()
    took = _ticked(before)
    assert set(took) == {"vmap"}, took
    monkeypatch.setattr(on_tpu, "_on_tpu", lambda: False)
    before = _ticks()
    c_off = fit()
    assert _ticked(before) == {}          # off a TPU nothing is counted
    jitcache.clear()
    np.testing.assert_allclose(c_on, c_off, rtol=1e-6, atol=1e-7)
    assert np.all(np.isfinite(c_on))


def test_routing_mesh_solve_gated_off(wide_problem, on_tpu, monkeypatch,
                                      devices8):
    """ADVICE r4: a mesh-sharded SPMD solve must NOT trace the kernel
    (pallas_call has no sharding annotations) — with no flag to have been
    set or forgotten: ``run(mesh=...)`` and the lambda lanes trace inside
    ``disabled()`` unconditionally, run the XLA path and match the
    off-TPU result bit for bit."""
    import jax

    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        GlmOptimizationProblem,
        OptimizerConfig,
    )
    from photon_tpu.parallel import mesh as M
    from photon_tpu.types import TaskType
    from photon_tpu.utils import jitcache

    X, y, _, _, _ = wide_problem
    X, y = X[:256], y[:256]
    batch = DataBatch(X, y)
    cfg = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=10, tolerance=1e-8),
        regularization=L2Regularization, regularization_weight=1.0)
    mesh = M.create_mesh(8, (M.DATA_AXIS,), (8,))

    def run_mesh():
        jitcache.clear()
        prob = GlmOptimizationProblem(TaskType.LOGISTIC_REGRESSION, cfg)
        m, _ = prob.run(batch, dim=X.shape[1], dtype=jnp.float32, mesh=mesh)
        return np.asarray(m.coefficients.means)

    def run_swept():
        jitcache.clear()
        prob = GlmOptimizationProblem(TaskType.LOGISTIC_REGRESSION, cfg)
        return np.asarray(prob.solve_swept(
            batch, [0.5, 2.0], dim=X.shape[1], dtype=jnp.float32).coefs)

    before = _ticks()
    c_on, s_on = run_mesh(), run_swept()
    took = _ticked(before)
    # the mesh solve under "mesh"; the lanes are a vmap inside disabled()
    assert set(took) == {"mesh", "vmap"}, took
    monkeypatch.setattr(on_tpu, "_on_tpu", lambda: False)
    c_off, s_off = run_mesh(), run_swept()
    # bitwise: the same (XLA) trace must have been used
    np.testing.assert_array_equal(c_on, c_off)
    np.testing.assert_array_equal(s_on, s_off)
    jitcache.clear()


@pytest.mark.parametrize("case", ["float64_coef", "normalised", "bfloat16_x",
                                  "sparse", "not_a_tpu", "disabled", "vmap",
                                  "narrow", "too_wide", "admitted"])
def test_dense_route(case, monkeypatch):
    """What the predicate sees, case by case: ``None`` (not the kernel's
    case: XLA's two passes, uncounted), the kernel, or the reason a TPU's
    dense float32 evaluation was turned away; the normalization context
    is not its business (PR 38: the aggregator folds it in around the
    kernel, so the predicate takes its arrays only to see whether they
    are batched). ADVICE r4: an
    f64 solve over f32 features must not take the fused path (it would
    silently return f32 and break the while_loop carry dtype); the XLA
    path promotes instead."""
    from photon_tpu.ops import features as F
    from photon_tpu.ops import pallas_glm

    monkeypatch.setattr(pallas_glm, "_on_tpu", lambda: case != "not_a_tpu")
    d = pallas_glm._DENSE_MIN_WIDTH
    x = jnp.zeros((8, d), jnp.float32)
    coef = jnp.zeros(d, jnp.float32)
    route = lambda x=x, coef=coef: pallas_glm.dense_route(x, coef)
    if case == "float64_coef":
        assert route(coef=jnp.zeros(d, jnp.float64)) is None
    elif case == "normalised":
        # the predicate is shown a context only as arrays that would
        # reach the kernel (``rest``: batched or not, nothing else of
        # them counts); the per-entity ladders, which gather a context a
        # lane, come with a batched X: "vmap"
        assert list(inspect.signature(pallas_glm.dense_route).parameters) \
            == ["x", "coef", "rest"]
        assert pallas_glm.dense_route(x, coef, None, coef + 2.0) == \
            pallas_glm.KERNEL
        seen = []
        jax.vmap(lambda xs, c: seen.append(route(x=xs, coef=c)) or c)(
            jnp.zeros((3, 8, d), jnp.float32), jnp.zeros((3, d), jnp.float32))
        jax.vmap(lambda f: seen.append(
            pallas_glm.dense_route(x, coef, None, f)) or f)(
            jnp.ones((3, d), jnp.float32))
        assert seen == ["vmap", "vmap"]
    elif case == "bfloat16_x":
        # the kernel takes bfloat16 rows when called (below); nothing has
        # timed it, so nothing routes it
        assert route(x=x.astype(jnp.bfloat16)) is None
    elif case == "sparse":
        assert route(x=F.SparseFeatures(jnp.zeros((8, 2), jnp.int32),
                                        jnp.zeros((8, 2), jnp.float32))) is None
    elif case == "not_a_tpu":
        assert route() is None
    elif case == "disabled":
        with pallas_glm.disabled():
            assert route() == "mesh"
        assert route() == pallas_glm.KERNEL
    elif case == "vmap":
        seen = []
        jax.vmap(lambda c: seen.append(route(coef=c)) or c)(
            jnp.zeros((3, d), jnp.float32))
        assert seen == ["vmap"]
    elif case == "narrow":
        assert d > 1, "the gate admits every width: drop this case"
        assert route(x=x[:, :d - 1], coef=coef[:d - 1]) == "shape"
    elif case == "too_wide":
        wide = pallas_glm._MAX_DENSE_DIM + 1
        assert route(x=jnp.zeros((8, wide), jnp.float32),
                     coef=jnp.zeros(wide, jnp.float32)) == "shape"
    else:
        assert route() == pallas_glm.KERNEL
        assert route(x=jnp.zeros((8, 2000), jnp.float32),
                     coef=jnp.zeros(2000, jnp.float32)) == pallas_glm.KERNEL


def test_refusals_tick_only_where_a_tpu_turned_the_kernel_away(
        wide_problem, on_tpu, monkeypatch):
    """``kernels.xla_fallbacks{path=dense, reason}`` ticks once a traced
    program for ``vmap``, ``mesh`` and ``shape``; float64 coefficients
    and a non-TPU backend are not the kernel's case and tick nothing; a
    normalised objective ticks nothing under ``path=dense`` (it has a
    label of its own: ``test_a_normalised_evaluation_is_counted``)."""
    from photon_tpu.ops.normalization import NormalizationContext

    X, y, off, w, coef = wide_problem
    vg = lambda x, c, norm=_IDN: aggregators.value_and_gradient(
        LogisticLoss, x, y, off, w, c, norm)
    d = X.shape[1]

    before = _ticks()
    vg(X, coef.astype(jnp.float64))
    vg(X, coef, NormalizationContext(factors=jnp.ones(d, jnp.float32),
                                     shifts=None))
    assert _ticked(before) == {}
    before = _ticks()
    narrow = on_tpu._DENSE_MIN_WIDTH - 1
    vg(X[:, :narrow], coef[:narrow])
    assert _ticked(before) == {"shape": 1}
    before = _ticks()
    jax.vmap(lambda c: vg(X, c))(jnp.stack([coef, coef]))
    assert _ticked(before) == {"vmap": 1}
    before = _ticks()
    with on_tpu.disabled():
        vg(X, coef)
    assert _ticked(before) == {"mesh": 1}
    monkeypatch.setattr(on_tpu, "_on_tpu", lambda: False)
    before = _ticks()
    vg(X, coef)
    assert _ticked(before) == {}


# ---------------------------------------------------------------------------
# a normalised objective through the same kernel (PR 38): effective
# coefficients in, the margin shift on the offsets, sum(w dz) as a third
# result, factors and shifts applied to what comes out
# ---------------------------------------------------------------------------


def _raw_problem(n=700, d=300, seed=5):
    """Features in raw units (scales four decades apart, means up to three
    deviations from zero) and an intercept column, last."""
    rng = np.random.default_rng(seed)
    s = np.exp(rng.uniform(np.log(1e-2), np.log(1e2), d - 1))
    m = rng.uniform(-3, 3, d - 1) * s
    x = np.concatenate([m + s * rng.normal(size=(n, d - 1)),
                        np.ones((n, 1))], axis=1)
    X = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(rng.random(n) > 0.4, jnp.float32)
    off = jnp.asarray(rng.normal(size=n) * 0.2, jnp.float32)
    w = jnp.asarray(rng.random(n) + 0.1, jnp.float32)
    coef = jnp.asarray(rng.normal(size=d) * 0.1, jnp.float32)
    return X, y, off, w, coef


def _context(kind, X):
    """A context of each ``NormalizationType`` from X's own statistics, as
    ``cli/train.py::build_normalization`` builds it (intercept last), and
    ``shifts_only``: shifts without factors, which no type builds and the
    algebra allows."""
    from photon_tpu.data.stats import compute_feature_stats
    from photon_tpu.ops.normalization import (
        NormalizationContext,
        NormalizationType,
        build_normalization_context,
    )

    stats = compute_feature_stats(X, X.shape[1])
    if kind == "shifts_only":
        return NormalizationContext(None, stats.mean.at[-1].set(0.0))
    return build_normalization_context(
        NormalizationType(kind), stats.mean, stats.variance, stats.abs_max,
        intercept_index=X.shape[1] - 1)


_CONTEXTS = ["STANDARDIZATION", "SCALE_WITH_STANDARD_DEVIATION",
             "SCALE_WITH_MAX_MAGNITUDE", "shifts_only"]


@pytest.mark.parametrize("sample_vectors", [True, False],
                         ids=["offsets_weights", "bare"])
@pytest.mark.parametrize("kind", _CONTEXTS)
def test_normalised_evaluation_through_the_kernel_against_xla(
        kind, sample_vectors, on_tpu):
    """Value, gradient and Hessian-vector product of a normalised objective
    through the kernel (interpret mode) against XLA's two passes, under
    each ``NormalizationType``, with and without shifts, with and without
    offsets and weights; counted under ``dense_norm`` / ``dense_hv_norm``
    and nothing under the identity labels."""
    X, y, off, w, coef = _raw_problem()
    norm = _context(kind, X)
    off, w = (off, w) if sample_vectors else (None, None)
    d2 = 0.25 * (w if w is not None else jnp.ones_like(y))
    vg = lambda: aggregators.value_and_gradient(
        LogisticLoss, X, y, off, w, coef, norm)
    hv = lambda: aggregators.hessian_vector_from_weights(
        X, d2, coef, norm, coef.shape[0])
    with on_tpu.disabled():
        (v0, g0), h0 = vg(), hv()
    before = {p: _ticks(p) for p in ("dense", "dense_hv", "dense_norm",
                                     "dense_hv_norm")}
    (v1, g1), h1 = vg(), hv()
    assert _ticked(before["dense_norm"], "dense_norm") == {"hit": 1}
    assert _ticked(before["dense_hv_norm"], "dense_hv_norm") == {"hit": 1}
    assert _ticked(before["dense"], "dense") == {}
    assert _ticked(before["dense_hv"], "dense_hv") == {}
    np.testing.assert_allclose(float(v1), float(v0), rtol=2e-6)
    for got, want in ((g1, g0), (h1, h0)):
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(np.asarray(got) / scale,
                                   np.asarray(want) / scale, atol=5e-6)
    # the third result is asked for only where there are shifts
    outs = [len(e.outvars) for e in _eqns(jax.make_jaxpr(vg)().jaxpr)
            if e.primitive.name == "pallas_call"]
    assert outs == [3 if norm.shifts is not None else 2]


@pytest.mark.parametrize("reason", ["vmap", "vmap_contexts", "vmap_rows",
                                    "mesh", "shape"])
def test_a_normalised_evaluation_is_counted(reason, on_tpu):
    """A normalised evaluation or product that the gate turns away ticks
    ``kernels.xla_fallbacks{path=dense_norm | dense_hv_norm, reason}``,
    once a traced program, and nothing under the identity labels: until
    PR 38 it left the kernel's path uncounted. A batch over the contexts
    or over the offsets and curvature weights ALONE (X and the vector
    shared) is a ``vmap`` too: the effective coefficients and the shifted
    offsets would reach the kernel batched."""
    X, y, off, w, coef = _raw_problem()
    norm = _context("STANDARDIZATION", X)
    if reason.startswith("vmap_"):
        two = lambda a: jnp.stack([a, a])
        before = {p: _ticks(p) for p in ("dense_norm", "dense_hv_norm")}
        if reason == "vmap_contexts":
            ctx = type(norm)(two(norm.factors), two(norm.shifts))
            axes = type(norm)(0, 0)
            jax.vmap(lambda n: aggregators.value_and_gradient(
                LogisticLoss, X, y, off, w, coef, n), in_axes=(axes,))(ctx)
            jax.vmap(lambda n: aggregators.hessian_vector_from_weights(
                X, 0.25 * w, coef, n, coef.shape[0]), in_axes=(axes,))(ctx)
        else:
            jax.vmap(lambda o: aggregators.value_and_gradient(
                LogisticLoss, X, y, o, w, coef, norm))(two(off))
            jax.vmap(lambda d2: aggregators.hessian_vector_from_weights(
                X, d2, coef, norm, coef.shape[0]))(two(0.25 * w))
        for p in before:
            assert _ticked(before[p], p) == {"vmap": 1}
        return
    if reason == "shape":
        narrow = on_tpu._DENSE_MIN_WIDTH - 1
        X, coef = X[:, :narrow], coef[:narrow]
        norm = type(norm)(norm.factors[:narrow], norm.shifts[:narrow])
    d = coef.shape[0]
    vg = lambda c: aggregators.value_and_gradient(
        LogisticLoss, X, y, off, w, c, norm)
    hv = lambda c: aggregators.hessian_vector_from_weights(
        X, 0.25 * w, c, norm, d)
    before = {p: _ticks(p) for p in ("dense", "dense_hv", "dense_norm",
                                     "dense_hv_norm")}
    if reason == "vmap":
        jax.vmap(vg)(jnp.stack([coef, coef]))
        jax.vmap(hv)(jnp.stack([coef, coef]))
    elif reason == "mesh":
        with on_tpu.disabled():
            vg(coef), hv(coef)
    else:
        vg(coef), hv(coef)
    assert _ticked(before["dense_norm"], "dense_norm") == {reason: 1}
    assert _ticked(before["dense_hv_norm"], "dense_hv_norm") == {reason: 1}
    assert _ticked(before["dense"], "dense") == {}
    assert _ticked(before["dense_hv"], "dense_hv") == {}


@pytest.mark.parametrize("call", ["evaluation", "product"])
def test_an_identity_context_traces_the_program_it_traced(
        call, wide_problem, on_tpu):
    """Under an identity context the aggregator's trace is the kernel's own
    call and nothing else, equation for equation (the five accepted cells
    run it): two results a ``pallas_call``, no margin shift, no third
    sum."""
    from photon_tpu.ops import pallas_glm

    X, y, off, w, coef = wide_problem
    d = X.shape[1]
    if call == "evaluation":
        through = lambda x, c: aggregators.value_and_gradient(
            LogisticLoss, x, y, off, w, c, _IDN)
        direct = lambda x, c: pallas_glm.fused_dense_value_grad(
            LogisticLoss, x, y, off, w, c)
    else:
        through = lambda x, c: aggregators.hessian_vector_from_weights(
            x, w, c, _IDN, d)
        direct = lambda x, c: pallas_glm.fused_dense_hessian_vector(
            x, w, c)[1]
    got, want = (jax.make_jaxpr(f)(X, coef) for f in (through, direct))
    names = lambda j: [e.primitive.name for e in _eqns(j.jaxpr)]
    assert names(got) == names(want)
    calls = [e for e in _eqns(got.jaxpr) if e.primitive.name == "pallas_call"]
    assert [len(e.outvars) for e in calls] == [2]


def test_normalised_solves_repeat_xlas_counts(on_tpu):
    """L-BFGS and TRON on raw rows under STANDARDIZATION, routed through the
    kernel and on XLA's two passes: the same iteration counts, models in
    ORIGINAL space within float32 of each other."""
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        GlmOptimizationProblem,
        OptimizerConfig,
    )
    from photon_tpu.types import OptimizerType, TaskType
    from photon_tpu.utils import jitcache

    X, y, _, _, _ = _raw_problem(n=1500, d=300, seed=8)
    norm = _context("STANDARDIZATION", X)
    batch = DataBatch(X, y, None, None)
    for kind, tol in ((OptimizerType.LBFGS, 1e-6), (OptimizerType.TRON, 1e-4)):
        cfg = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(optimizer_type=kind, tolerance=tol,
                                      max_iterations=60,
                                      explicit_hessian=False
                                      if kind == OptimizerType.TRON else None),
            regularization=L2Regularization, regularization_weight=1.0)

        def run():
            jitcache.clear()
            prob = GlmOptimizationProblem(
                TaskType.LOGISTIC_REGRESSION, cfg, norm=norm,
                intercept_index=X.shape[1] - 1)
            model, result = prob.run(batch, dim=X.shape[1],
                                     dtype=jnp.float32)
            return (np.asarray(model.coefficients.means),
                    int(result.iterations))

        # TRON's evaluations hand out the curvature weights too
        label = "dense_norm" if kind == OptimizerType.LBFGS else (
            "dense_curv_norm")
        before = _ticks(label)
        routed, its = run()
        assert _ticked(before, label).get("hit", 0) >= 1
        with on_tpu.disabled():
            xla, its_xla = run()
        assert its == its_xla, (kind, its, its_xla)
        margins = lambda theta: np.asarray(X, np.float64) @ theta
        np.testing.assert_allclose(margins(routed), margins(xla), atol=2e-3)
    jitcache.clear()


def test_fused_bf16_feature_storage():
    """bf16 feature storage through the fused kernel: the two HBM levers
    (single pass + half-width storage) compose; parity vs the XLA path on
    the SAME bf16 inputs at bf16-appropriate tolerance."""
    rng = np.random.default_rng(9)
    n, d = 96, 12
    X16 = jnp.asarray(rng.normal(size=(n, d)), jnp.bfloat16)
    y = jnp.asarray((rng.random(n) > 0.4), jnp.float32)
    coef = jnp.asarray(rng.normal(size=d) * 0.3, jnp.float32)

    v_f, g_f = fused_dense_value_grad(LogisticLoss, X16, y, None, None, coef)
    v_x, g_x = aggregators.value_and_gradient(
        LogisticLoss, X16, y, None, None, coef, no_normalization())
    np.testing.assert_allclose(float(v_f), float(v_x), rtol=2e-2)
    np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_x, np.float32),
                               rtol=5e-2, atol=5e-2)
    assert g_f.dtype == jnp.float32


# ---------------------------------------------------------------------------
# sparse ELL kernel edges: tile remainders, zero weights, empty segments
# ---------------------------------------------------------------------------


def _sparse_problem(n, k, d, seed=13):
    from photon_tpu.ops import features as F

    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.integers(0, d, size=(n, k)), jnp.int32)
    val = jnp.asarray(rng.normal(size=(n, k)) / np.sqrt(max(k, 1)),
                      jnp.float32)
    y = jnp.asarray((rng.random(n) > 0.4), jnp.float32)
    off = jnp.asarray(rng.normal(size=n) * 0.2, jnp.float32)
    w = jnp.asarray(rng.random(n) + 0.1, jnp.float32)
    coef = jnp.asarray(rng.normal(size=d) * 0.4, jnp.float32)
    return F.SparseFeatures(idx, val), y, off, w, coef


def _sparse_xla(x, y, off, w, coef):
    from photon_tpu.ops import pallas_glm

    with pallas_glm.disabled():
        return aggregators.value_and_gradient(
            LogisticLoss, x, y, off, w, coef, no_normalization())


@pytest.mark.parametrize("n", [1, 7, 127, 128, 129, 333])
def test_sparse_tile_remainders(n):
    """N not divisible by the tile: pad rows are zero-weight all-pad rows
    and must contribute exactly nothing."""
    from photon_tpu.ops.pallas_glm import fused_sparse_value_grad

    x, y, off, w, coef = _sparse_problem(n, 4, 64)
    v0, g0 = _sparse_xla(x, y, off, w, coef)
    v1, g1 = fused_sparse_value_grad(LogisticLoss, x, y, off, w, coef,
                                     tile_n=128)
    np.testing.assert_allclose(float(v1), float(v0), rtol=5e-6)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0),
                               rtol=5e-5, atol=5e-5)


def test_sparse_zero_weight_rows():
    from photon_tpu.ops.pallas_glm import fused_sparse_value_grad

    x, y, off, w, coef = _sparse_problem(100, 4, 64)
    w = w.at[::3].set(0.0)
    v0, g0 = _sparse_xla(x, y, off, w, coef)
    v1, g1 = fused_sparse_value_grad(LogisticLoss, x, y, off, w, coef)
    np.testing.assert_allclose(float(v1), float(v0), rtol=5e-6)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0),
                               rtol=5e-5, atol=5e-5)


def test_sparse_empty_segments_and_zero_width():
    """Rows whose slots are ALL pads contribute only their offset's
    loss; a width-zero ELL block (k=0) is every row empty."""
    from photon_tpu.ops import features as F
    from photon_tpu.ops.pallas_glm import fused_sparse_value_grad

    x, y, off, w, coef = _sparse_problem(60, 3, 32)
    idx = x.indices.at[::4].set(0)
    val = x.values.at[::4].set(0.0)          # (0, 0.0) = pad slots
    x2 = F.SparseFeatures(idx, val)
    v0, g0 = _sparse_xla(x2, y, off, w, coef)
    v1, g1 = fused_sparse_value_grad(LogisticLoss, x2, y, off, w, coef)
    np.testing.assert_allclose(float(v1), float(v0), rtol=5e-6)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0),
                               rtol=5e-5, atol=5e-5)

    k0 = F.SparseFeatures(jnp.zeros((16, 0), jnp.int32),
                          jnp.zeros((16, 0), jnp.float32))
    y0, off0, w0 = y[:16], off[:16], w[:16]
    v0, g0 = _sparse_xla(k0, y0, off0, w0, coef)
    v1, g1 = fused_sparse_value_grad(LogisticLoss, k0, y0, off0, w0, coef)
    np.testing.assert_allclose(float(v1), float(v0), rtol=5e-6)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), atol=5e-6)


def test_sparse_empty_batch():
    from photon_tpu.ops import features as F
    from photon_tpu.ops.pallas_glm import fused_sparse_value_grad

    x = F.SparseFeatures(jnp.zeros((0, 4), jnp.int32),
                         jnp.zeros((0, 4), jnp.float32))
    v, g = fused_sparse_value_grad(
        LogisticLoss, x, jnp.zeros((0,), jnp.float32), None, None,
        jnp.zeros(8, jnp.float32))
    assert float(v) == 0.0
    np.testing.assert_array_equal(np.asarray(g), np.zeros(8, np.float32))


# ---------------------------------------------------------------------------
# serving gather+margin kernel edges
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 5, 64, 127, 128, 129])
def test_serving_margin_tile_remainders(n):
    from photon_tpu.ops.pallas_glm import fused_gather_margin

    rng = np.random.default_rng(21)
    d, k = 96, 6
    idx = jnp.asarray(rng.integers(0, d, size=(n, k)), jnp.int32)
    val = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    off = jnp.asarray(rng.normal(size=n), jnp.float32)
    theta = jnp.asarray(rng.normal(size=d) * 0.3, jnp.float32)
    got = fused_gather_margin(idx, val, off, theta)
    want = off + jnp.sum(val * theta[idx], axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_serving_margin_degenerate_shapes():
    from photon_tpu.ops.pallas_glm import fused_gather_margin

    theta = jnp.arange(8, dtype=jnp.float32)
    # empty batch
    out = fused_gather_margin(jnp.zeros((0, 3), jnp.int32),
                              jnp.zeros((0, 3), jnp.float32), None, theta)
    assert out.shape == (0,)
    # zero slot width: margins are just the offsets
    off = jnp.asarray([1.5, -2.0], jnp.float32)
    out = fused_gather_margin(jnp.zeros((2, 0), jnp.int32),
                              jnp.zeros((2, 0), jnp.float32), off, theta)
    np.testing.assert_allclose(np.asarray(out), np.asarray(off))
    # None offsets
    idx = jnp.asarray([[2], [5]], jnp.int32)
    val = jnp.asarray([[2.0], [1.0]], jnp.float32)
    out = fused_gather_margin(idx, val, None, theta)
    np.testing.assert_allclose(np.asarray(out), [4.0, 5.0])


def test_serving_supported_gate():
    from photon_tpu.ops import pallas_glm

    theta = jnp.zeros(64, jnp.float32)
    assert pallas_glm._supported_serving(theta, 4)
    assert not pallas_glm._supported_serving(theta, 0)
    assert not pallas_glm._supported_serving(
        jnp.zeros(64, jnp.float64), 4)
    assert not pallas_glm._supported_serving(
        jnp.zeros(pallas_glm._MAX_SPARSE_DIM + 1, jnp.float32), 4)
    with pallas_glm.disabled():
        assert not pallas_glm._supported_serving(theta, 4)


def test_entry_hook_prefetches_the_toolchain_only_off_the_cpu(monkeypatch,
                                                              tmp_path):
    """``compile_cache.maybe_enable`` (every driver's first call) starts
    the Pallas import on a daemon thread where the process is not held to
    the CPU, once; a CPU run (this suite) starts none."""
    from photon_tpu.ops import pallas_glm
    from photon_tpu.utils import compile_cache

    monkeypatch.setenv(compile_cache.ENV_OPT_OUT, "1")   # touch no cache
    monkeypatch.setattr(pallas_glm, "_PREFETCH", None)
    assert compile_cache._held_to_cpu()
    compile_cache.maybe_enable()
    assert pallas_glm._PREFETCH is None
    monkeypatch.setattr(compile_cache, "_held_to_cpu", lambda: False)
    compile_cache.maybe_enable()
    thread = pallas_glm._PREFETCH
    assert thread is not None and thread.daemon
    compile_cache.maybe_enable()
    assert pallas_glm._PREFETCH is thread
    thread.join(60)
    assert not thread.is_alive()
    import sys
    assert "jax.experimental.pallas.tpu" in sys.modules


# ---------------------------------------------------------------------------
# compiled FOR the chip, from here: what interpret mode cannot show (the
# TPU's compiler is installed; the chip is described, not attached)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def v5e():
    """One described v5e device's sharding; the persistent compile cache
    is off around the compiles (an entry written for a described chip
    cannot be read back without one)."""
    os = __import__("os")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shaped(v5e, *shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)


@pytest.mark.parametrize("n,d,dtype,call", [
    (530_000, 2_000, jnp.float32, "evaluation"),    # fe-epsilon: ragged both ways
    (4_000_000, 256, jnp.float32, "evaluation"),    # the least width the gate admits
    (500_000, 4_096, jnp.float32, "evaluation"),    # the widest
    (8_101, 2_000, jnp.bfloat16, "evaluation"),     # packed rows, an odd ragged edge
    (530_000, 2_000, jnp.float32, "product"),       # fe-epsilon-tron's CG step
    (4_000_000, 256, jnp.float32, "product"),
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_dense_kernel_compiles_for_a_v5e(v5e, n, d, dtype, call):
    """Mosaic takes the kernel at the real shapes, inside the scoped VMEM
    a core hands out by default, and the program around it holds no copy
    of X (the matrix goes in as placed: here row-major, as the caller
    states it). A Hessian-vector product is the same ONE custom call."""
    row = _shaped(v5e, n)
    if call == "product":
        f = jax.jit(lambda x, d2, v: fused_dense_hessian_vector(
            x, d2, v, interpret=False))
        args = (_shaped(v5e, n, d, dtype=dtype), row, _shaped(v5e, d))
    else:
        f = jax.jit(lambda x, y, off, w, c: fused_dense_value_grad(
            LogisticLoss, x, y, off, w, c, interpret=False))
        args = (_shaped(v5e, n, d, dtype=dtype), row, row, row,
                _shaped(v5e, d))
    compiled = f.lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    x_bytes = n * d * jnp.dtype(dtype).itemsize
    # the argument's default layout is column-major where the width is no
    # multiple of 128: ONE re-layout then, the compiler's; never two
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (1.1 if d % 128 else 0.1) * x_bytes, temp


@pytest.mark.parametrize("shape,dtype,default,outcome", V5E_DEFAULTS,
                         ids=lambda v: str(v))
def test_row_major_admission_against_a_v5e(v5e, shape, dtype, default,
                                           outcome):
    """The chip's own default for the argument (``compiled.input_formats``
    of a program that states none) is what ``tests/test_dataset_layout.py``
    has on record, and ``store_rows_major``'s rule admits the matrix exactly
    where that default is column-major AND whole lane tiles cost at most
    an eighth."""
    from photon_tpu.game.dataset import ROW_MAJOR, row_major_outcome

    compiled = jax.jit(lambda x: x[0]).lower(
        _shaped(v5e, *shape, dtype=jnp.dtype(dtype))).compile()
    (fmt,), _ = compiled.input_formats
    assert fmt.layout.major_to_minor == default
    got = row_major_outcome(shape[1], fmt.layout.major_to_minor)
    assert got == outcome
    assert (got == "relaid") == (
        default != ROW_MAJOR
        and -(-shape[1] // 128) * 128 <= 1.125 * shape[1])


_EPSILON = (530_000, 2_000)        # fe-epsilon's rows: 2,000 is no multiple of 128
_X_COPY = re.compile(r"= f32\[530000,2000\]\S* copy\(")


def _epsilon_batch(v5e, placed):
    """fe-epsilon's batch as shapes on the described chip: X ``default``
    (no layout stated: the compiler's, column-major at this width) or
    ``row_major`` (as ``GameEstimator._prepare`` stores it there: a
    committed array whose ``Format`` the solve is compiled for)."""
    from photon_tpu.game.dataset import ROW_MAJOR

    n, d = _EPSILON
    x = _shaped(v5e, n, d) if placed == "default" else jax.ShapeDtypeStruct(
        (n, d), jnp.float32, sharding=Format(Layout(ROW_MAJOR), v5e))
    row = _shaped(v5e, n)
    return DataBatch(x, row, row, row)


def _solve_compiled(v5e, monkeypatch, optimizer, placed, routed):
    """``GlmOptimizationProblem._solve_fn`` at fe-epsilon's shape as the
    chip's compiler leaves it, the kernel routed or XLA's two passes."""
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.ops import pallas_glm
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        GlmOptimizationProblem,
    )
    from photon_tpu.types import TaskType
    from photon_tpu.utils import jitcache

    monkeypatch.setattr(pallas_glm, "_default_interpret", lambda: False)
    monkeypatch.setattr(pallas_glm, "_on_tpu", lambda: routed)
    jitcache.clear()
    prob = GlmOptimizationProblem(
        TaskType.LOGISTIC_REGRESSION, GLMOptimizationConfiguration(
            optimizer=optimizer, regularization=L2Regularization,
            regularization_weight=1.0))
    one = _shaped(v5e)
    try:
        return prob._solve_fn.lower(_shaped(v5e, _EPSILON[1]),
                                    _epsilon_batch(v5e, placed), one,
                                    one).compile()
    finally:
        jitcache.clear()


def _held_to_the_layout(placed, compiled):
    """What X's layout costs a solve program: as the compiler lays the
    argument, ONE X-sized temporary (its re-layout ``copy``, S10: kept as
    the record of what the default costs); as ``store_rows_major`` stores
    it, temporaries under 1% of X and no ``copy`` of X in the text."""
    temp = compiled.memory_analysis().temp_size_in_bytes
    x_tiled = _EPSILON[0] * 2_048 * 4
    copies = _X_COPY.findall(compiled.as_text())
    if placed == "default":
        assert x_tiled <= temp < 1.01 * x_tiled, temp
        assert copies
    else:
        assert temp < 0.01 * x_tiled, temp
        assert not copies, copies
    return temp


_PLACED = pytest.mark.parametrize("placed", ["default", "row_major"])


@_PLACED
def test_routed_solve_compiles_for_a_v5e_without_a_copy_of_its_own(
        v5e, monkeypatch, placed):
    """The fe-epsilon solve as the chip's compiler leaves it: the kernel
    twice (the solver's first evaluation, the line search's), under
    ``agg/value_and_gradient``; no X-sized temporary but the re-layout
    copy of a default-layout argument, which the XLA program holds too,
    and none at all for X as it is placed; no ``pad`` that makes a
    matrix."""
    from photon_tpu.optim.problem import OptimizerConfig

    opt = OptimizerConfig(max_iterations=100, tolerance=1e-6)
    fused, xla = (_solve_compiled(v5e, monkeypatch, opt, placed, routed)
                  for routed in (True, False))
    text = fused.as_text()
    names = re.findall(r'custom_call_target="tpu_custom_call".*?'
                       r'op_name="([^"]+)"', text)
    assert len(names) == 2, names
    assert all("agg/value_and_gradient/jit(_fused)/pallas_call" in name
               for name in names), names
    assert "tpu_custom_call" not in xla.as_text()
    temp = [_held_to_the_layout(placed, c) for c in (fused, xla)]
    assert abs(temp[0] - temp[1]) < 0.001 * _EPSILON[0] * 2_048 * 4, temp
    padded = re.findall(r"= f32\[(\d+),(\d+)\][^=\n]* pad\(", text)
    assert not [shape for shape in padded
                if int(shape[0]) >= _EPSILON[0]], padded


@_PLACED
def test_routed_tron_solve_compiles_for_a_v5e_with_one_kernel_a_product(
        v5e, monkeypatch, placed):
    """The fe-epsilon-tron solve as the chip's compiler leaves it: the
    kernel three times (the first evaluation, the trial point's, and ONE
    under ``agg/hessian_vector`` inside the CG ``while``), and no temporary
    the XLA program does not hold: the one re-layout copy of a
    default-layout X (none for X as it is placed), none of the product's
    own. The routed program holds fewer since its evaluations hand out the
    curvature weights (no pass of XLA's for them: 1.45 against 4.30 MB
    for X as placed), XLA's more (it carries the trial point's weights
    beside the point's: 7.75 against 3.72 MB)."""
    from photon_tpu.optim.problem import OptimizerConfig
    from photon_tpu.types import OptimizerType

    opt = OptimizerConfig(optimizer_type=OptimizerType.TRON,
                          max_iterations=15, tolerance=1e-5,
                          explicit_hessian=False)
    fused, xla = (_solve_compiled(v5e, monkeypatch, opt, placed, routed)
                  for routed in (True, False))
    names = re.findall(r'custom_call_target="tpu_custom_call".*?'
                       r'op_name="([^"]+)"', fused.as_text())
    assert len(names) == 3, names
    assert sum("optim/tron/direction/while/body/agg/hessian_vector/"
               "jit(_fused)/pallas_call" in name for name in names) == 1, names
    assert sum("agg/value_and_gradient/jit(_fused)/pallas_call" in name
               for name in names) == 2, names
    assert "tpu_custom_call" not in xla.as_text()
    temp = [_held_to_the_layout(placed, c) for c in (fused, xla)]
    assert temp[0] - temp[1] < 0.001 * _EPSILON[0] * 2_048 * 4, temp


@pytest.mark.parametrize("solver", ["LBFGS", "TRON"])
def test_normalised_solve_compiles_for_a_v5e(v5e, monkeypatch, solver):
    """fe-epsilon-standardized's solve (530,000 x 2,001 raw rows stored
    rows-major, a STANDARDIZATION context) as the chip's compiler leaves
    it: the SAME kernel under the same names, each call with its third
    result (``sum(w dz)``: 81 ragged lanes and three outputs are Mosaic's
    to take or refuse), no copy of X and no X-sized temporary; and under
    TRON one more call under ``agg/hessian_vector``, and the evaluations'
    fourth result, every whole tile's curvature weights."""
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.game.dataset import ROW_MAJOR
    from photon_tpu.ops import pallas_glm
    from photon_tpu.ops.normalization import NormalizationContext
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        GlmOptimizationProblem,
        OptimizerConfig,
    )
    from photon_tpu.types import OptimizerType, TaskType
    from photon_tpu.utils import jitcache

    n, d = 530_000, 2_001
    monkeypatch.setattr(pallas_glm, "_default_interpret", lambda: False)
    monkeypatch.setattr(pallas_glm, "_on_tpu", lambda: True)
    jitcache.clear()
    opt = (OptimizerConfig(max_iterations=100, tolerance=1e-6)
           if solver == "LBFGS" else OptimizerConfig(
               optimizer_type=OptimizerType.TRON, max_iterations=15,
               tolerance=1e-5, explicit_hessian=False))
    prob = GlmOptimizationProblem(
        TaskType.LOGISTIC_REGRESSION, GLMOptimizationConfiguration(
            optimizer=opt, regularization=L2Regularization,
            regularization_weight=1.0),
        norm=NormalizationContext(jnp.ones(d, jnp.float32),
                                  jnp.zeros(d, jnp.float32)),
        intercept_index=d - 1)
    x = jax.ShapeDtypeStruct((n, d), jnp.float32,
                             sharding=Format(Layout(ROW_MAJOR), v5e))
    one = _shaped(v5e)
    try:
        compiled = prob._solve_fn.lower(
            _shaped(v5e, d), DataBatch(x, _shaped(v5e, n), None, None), one,
            one).compile()
    finally:
        jitcache.clear()
    text = compiled.as_text()
    names = re.findall(r'custom_call_target="tpu_custom_call".*?'
                       r'op_name="([^"]+)"', text)
    evaluations = [m for m in names
                   if "agg/value_and_gradient/jit(_fused)/pallas_call" in m]
    products = [m for m in names
                if "agg/hessian_vector/jit(_fused)/pallas_call" in m]
    assert len(evaluations) == 2, names
    assert len(products) == (1 if solver == "TRON" else 0), names
    assert len(names) == len(evaluations) + len(products)
    # value's lanes, the gradient's sublanes, sum(w dz)'s lanes; TRON's
    # evaluations add the weights of 2,070 tiles of 256 rows
    results = re.findall(r"= \((f32\[2,128\]\S*, f32\[8,2001\]\S*, "
                         r"f32\[2,128\]\S*)(, f32\[2070,2,128\]\S*)?\) "
                         r"custom-call\(", text)
    assert len(results) == len(names), results
    assert sum(bool(weights) for _, weights in results) == (
        len(evaluations) if solver == "TRON" else 0), results
    assert not re.findall(r"= f32\[530000,2001\]\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.01 * n * 2_048 * 4


@_PLACED
def test_swept_lane_solve_compiles_for_a_v5e(v5e, placed):
    """The fe-epsilon-l2grid solve (``_swept_solve_fn(None)``, four lambda
    lanes, traced inside ``disabled()`` as ``solve_swept`` traces it): XLA's
    MXU contractions and no kernel; the re-layout copy of a default-layout
    X survives the GEMM consumer, X as it is placed has none."""
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.ops import pallas_glm
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        GlmOptimizationProblem,
        OptimizerConfig,
    )
    from photon_tpu.types import TaskType
    from photon_tpu.utils import jitcache

    jitcache.clear()
    prob = GlmOptimizationProblem(
        TaskType.LOGISTIC_REGRESSION, GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(max_iterations=100, tolerance=1e-6),
            regularization=L2Regularization, regularization_weight=1.0))
    lanes = _shaped(v5e, 4)
    try:
        with pallas_glm.disabled():
            compiled = prob._swept_solve_fn(None).lower(
                _shaped(v5e, 4, _EPSILON[1]), _epsilon_batch(v5e, placed),
                lanes, lanes).compile()
    finally:
        jitcache.clear()
    assert "tpu_custom_call" not in compiled.as_text()
    _held_to_the_layout(placed, compiled)


def test_full_variance_program_compiles_for_a_v5e_on_the_upper_triangle(v5e):
    """The fe-epsilon-variance FULL program (``_variance_fns``, X as it is
    placed) as the chip's compiler leaves it: the Gram's loop holds one MXU
    contraction a column block, each a strip of the upper triangle and none
    the whole ``[2000, 2000]`` product, reading the stored matrix in place:
    no temporary of X's size, nor of a row block's."""
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.ops import features
    from photon_tpu.optim.problem import (
        VARIANCE_GRAM_BLOCK_ROWS,
        GLMOptimizationConfiguration,
        GlmOptimizationProblem,
        OptimizerConfig,
    )
    from photon_tpu.types import TaskType
    from photon_tpu.utils import jitcache

    jitcache.clear()
    prob = GlmOptimizationProblem(
        TaskType.LOGISTIC_REGRESSION, GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(), regularization=L2Regularization,
            regularization_weight=1.0))
    try:
        compiled = prob._variance_fns[1].lower(
            _shaped(v5e, _EPSILON[1]), _epsilon_batch(v5e, "row_major"),
            _shaped(v5e)).compile()
    finally:
        jitcache.clear()
    text = compiled.as_text()
    width, block = _EPSILON[1], features.GRAM_COLUMN_BLOCK
    strips = {(min(block, width - s), width - s)
              for s in range(0, width, block)}
    # the Gram's contractions carry its scope; the factorisation's do not
    gram = {(int(a), int(b)) for a, b in re.findall(
        r"= f32\[(\d+),(\d+)\]\S* convolution\([^\n]*agg/hessian_matrix",
        text)}
    assert gram == strips, gram
    assert compiled.memory_analysis().temp_size_in_bytes < (
        VARIANCE_GRAM_BLOCK_ROWS * 2_048 * 4)
    assert not _X_COPY.findall(text)


def _stores_to(jaxpr, refs):
    """The equations of ``jaxpr`` (and of the jaxprs its ``cond`` /
    ``scan`` / ``pjit`` equations hold, their operands matched by
    position) that store to one of the variables ``refs``."""
    found = []
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name in ("swap", "masked_swap", "addupdate")
                and not hasattr(eqn.invars[0], "val")
                and eqn.invars[0] in refs):
            found.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    skip = len(eqn.invars) - len(inner.invars)
                    found += _stores_to(inner, {
                        inner.invars[k - skip]
                        for k, var in enumerate(eqn.invars)
                        if k >= skip and not hasattr(var, "val")
                        and var in refs})
    return found


@pytest.mark.parametrize("call", ["evaluation", "product"])
def test_dense_kernel_stores_to_no_input(call):
    """The kernel writes its two outputs and its two scratch vectors and
    nothing else: where XLA has placed a small X in VMEM a block of an
    input IS the operand, and a store past its end lands in whatever lies
    behind it (on the chip 8,100 x 2,000 read 0.0 for it; interpret mode
    cannot show it, so the kernel's jaxpr is held to it). A product is
    the same ONE ``pallas_call`` with no contraction over X beside it."""
    n, d = 700, 2000
    x, row = jnp.zeros((n, d), jnp.float32), jnp.zeros(n, jnp.float32)
    if call == "product":
        jaxpr = jax.make_jaxpr(fused_dense_hessian_vector)(
            x, row, jnp.zeros(d, jnp.float32))
    else:
        jaxpr = jax.make_jaxpr(lambda *a: fused_dense_value_grad(
            LogisticLoss, *a, jnp.zeros(d, jnp.float32)))(x, row, row, row)
    eqns = list(_eqns(jaxpr.jaxpr))
    (call,) = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert not any(e.primitive.name == "dot_general"
                   and any(getattr(v.aval, "shape", ()) == x.shape
                           for v in e.invars) for e in eqns)
    kernel = call.params["jaxpr"]
    inputs, rest = set(kernel.invars[:5]), set(kernel.invars[5:])
    assert not _stores_to(kernel, inputs)
    assert _stores_to(kernel, rest)       # the walk does see a store


# ---------------------------------------------------------------------------
# the curvature weights beside value and gradient (TRON's operator input):
# the kernel hands out each whole tile's w * l''(m) from the margins it
# holds, the rows left over are the same arithmetic beside it
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("context", ["identity", "STANDARDIZATION"])
@pytest.mark.parametrize("sample_vectors", [True, False],
                         ids=["offsets_weights", "bare"])
@pytest.mark.parametrize("loss", [LogisticLoss, PoissonLoss],
                         ids=lambda l: l.name)
@pytest.mark.parametrize("n", [100, 700, 4000],
                         ids=["no_whole_tile", "one_tile_60_over",
                              "three_tiles_160_over"])
def test_the_evaluation_hands_out_hessian_weights(n, loss, sample_vectors,
                                                  context, on_tpu):
    """``value_gradient_and_weights`` through the kernel (interpret mode):
    its weights are ``hessian_weights`` at the same point within float32
    rounding (two summation orders of the margins: read 1.0e-6 of the
    largest weight, 9.5e-7 of each under an identity context), its value
    and gradient are the routed ``value_and_gradient``'s to the bit. At 300
    features the kernel's tile is 1,280 rows: the rows are all left over,
    one tile and 60 over, three tiles and 160 over. Counted once, as
    ``dense_curv`` / ``dense_curv_norm``, nothing under the evaluations'
    labels."""
    X, y, off, w, coef = _raw_problem(n=n, d=300, seed=n)
    if loss is PoissonLoss:
        y = jnp.round(jnp.exp(0.5 * y))
    norm = _IDN if context == "identity" else _context(context, X)
    if context == "identity":       # raw rows' margins reach 1e2: scale
        X = X / jnp.max(jnp.abs(X), axis=0)
    off, w = (off, w) if sample_vectors else (None, None)
    with on_tpu.disabled():
        want = aggregators.hessian_weights(loss, X, y, off, w, coef, norm)
    labels = ("dense", "dense_norm", "dense_curv", "dense_curv_norm")
    before = {p: _ticks(p) for p in labels}
    value, grad, got = aggregators.value_gradient_and_weights(
        loss, X, y, off, w, coef, norm)
    path = "dense_curv" if context == "identity" else "dense_curv_norm"
    assert {p: _ticked(before[p], p) for p in labels} == {
        p: {"hit": 1} if p == path else {} for p in labels}
    v0, g0 = aggregators.value_and_gradient(loss, X, y, off, w, coef, norm)
    assert float(value) == float(v0)
    np.testing.assert_array_equal(np.asarray(grad), np.asarray(g0))
    assert got.shape == (n,) and got.dtype == jnp.float32
    got, want = np.asarray(got), np.asarray(want)
    scale = np.abs(want).max()
    np.testing.assert_allclose(got / scale, want / scale, rtol=0, atol=5e-6)
    if context == "identity":
        np.testing.assert_allclose(got, want, rtol=5e-6)


@pytest.mark.parametrize("reason", ["vmap", "mesh", "shape", "not_a_tpu"])
def test_weights_off_the_kernel_come_from_the_evaluations_margins(
        reason, wide_problem, on_tpu, monkeypatch):
    """Where the gate turns the matrix away the weights come from the
    margins XLA's first pass computed: two contractions over X (``X
    theta``, ``X^T (w dz)``) and no third, the weights ``hessian_weights``'
    to the bit (batched alike under ``vmap``). ``kernels.xla_fallbacks{path=dense_curv, reason}`` ticks
    once a traced evaluation; a backend that is no TPU ticks nothing."""
    X, y, off, w, coef = wide_problem
    if reason == "shape":
        narrow = on_tpu._DENSE_MIN_WIDTH - 1
        X, coef = X[:, :narrow], coef[:narrow]
    if reason == "not_a_tpu":
        monkeypatch.setattr(on_tpu, "_on_tpu", lambda: False)
    vgw = lambda c: aggregators.value_gradient_and_weights(
        LogisticLoss, X, y, off, w, c, _IDN)
    before = {p: _ticks(p) for p in ("dense", "dense_curv")}
    if reason == "vmap":
        got = jax.vmap(vgw)(jnp.stack([coef, coef]))[2][1]
    elif reason == "mesh":
        with on_tpu.disabled():
            got = vgw(coef)[2]
    else:
        got = vgw(coef)[2]
    assert _ticked(before["dense_curv"], "dense_curv") == (
        {} if reason == "not_a_tpu" else {reason: 1})
    assert _ticked(before["dense"]) == {}
    hw = lambda c: aggregators.hessian_weights(LogisticLoss, X, y, off, w, c,
                                               _IDN)
    with on_tpu.disabled():
        want = (jax.vmap(hw)(jnp.stack([coef, coef]))[1] if reason == "vmap"
                else hw(coef))
        jaxpr = jax.make_jaxpr(vgw)(coef)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    over_x = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "dot_general"
              and any(getattr(v.aval, "shape", ()) == X.shape for v in e.invars)]
    assert len(over_x) == 2, over_x


# sha256 of the jaxprs of the kernel's two- and three-result evaluations and
# of its Hessian-vector product at 700 x 300, traced with x64 on as the
# tests run, from f93ae62 (before the kernel could hand out curvature
# weights) with this very function: what the four cells that run the kernel
# without them trace stays what it was.
PARENT_KERNEL_JAXPR = {
    "two": "0d44f1f5a0879cc4cabb2e535805e358345bf1258d070df6b1945d6793276d29",
    "three": "c4ed8889a3b07dee99dc75236b35ef6a2300946e7c054b5790afa6fe4926bac9",
    "product": "0eec1a0c15885c286c5fe9069fec41a9db04f5d9f18b5a30618d94cc4ebda32f",
}


def kernel_jaxpr_digest(call):
    import hashlib

    from photon_tpu.ops import pallas_glm

    n, d = 700, 300
    args = ([jnp.zeros((n, d), jnp.float32)]
            + [jnp.zeros(n, jnp.float32)] * 3 + [jnp.zeros(d, jnp.float32)])
    calls = {
        "two": lambda x, y, off, w, c: pallas_glm.fused_dense_value_grad(
            LogisticLoss, x, y, off, w, c, interpret=True),
        "three": lambda x, y, off, w, c: pallas_glm.fused_dense_value_grad(
            LogisticLoss, x, y, off, w, c, interpret=True, with_dz_sum=True),
        "product": lambda x, y, off, w, c: (
            pallas_glm.fused_dense_hessian_vector(x, w, c, interpret=True)),
    }
    text = str(jax.make_jaxpr(calls[call])(*args))
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("call", sorted(PARENT_KERNEL_JAXPR))
def test_the_kernel_without_weights_traces_as_it_did(call):
    """The weights are an output asked for: a call that does not ask traces
    the jaxpr it traced before there was one, to the byte."""
    assert kernel_jaxpr_digest(call) == PARENT_KERNEL_JAXPR[call]


def test_the_kernel_with_weights_adds_one_output():
    """Asked for, the same kernel with ONE more output, laid out as the
    labels are: [tiles, tile / 128, 128], written once a step."""
    from photon_tpu.ops import pallas_glm

    n, d = 700, 300
    x = jnp.zeros((n, d), jnp.float32)
    y = jnp.zeros(n, jnp.float32)
    c = jnp.zeros(d, jnp.float32)
    calls = {}
    for weights in (False, True):
        jaxpr = jax.make_jaxpr(lambda x, c: pallas_glm.fused_dense_value_grad(
            LogisticLoss, x, y, y, y, c, with_weights=weights))(x, c)
        calls[weights], = [e for e in _eqns(jaxpr.jaxpr)
                           if e.primitive.name == "pallas_call"]
    assert len(calls[True].outvars) == len(calls[False].outvars) + 1 == 3
    assert calls[True].outvars[-1].aval.shape == (1, 5, 128)


def kernel_body_digest(call):
    """sha256 of the kernel a TPU program carries (the Mosaic module inside
    the custom call, decoded), lowered for a TPU from here, for the
    identity and the normalised evaluation and the product at 700 x 300
    through ``aggregators``, as the cells' solves call them. Its locations
    hold file paths, line and column numbers, and the persistent compile
    cache keys on it: a line that moves in the kernel or in the aggregator
    frames that call it re-keys every cell's solve (ROADMAP D13). Paths are
    cut to ``photon_tpu/...`` and every frame outside the package (this
    file's, pytest's) is one placeholder. JAX's caches are cleared first:
    an inner jitted function (``jax.nn.sigmoid``, ``jnp.where``) keeps the
    locations of the call that traced it first, in whatever test that
    was."""
    import base64
    import hashlib

    from jax._src.lib.mlir import ir

    from photon_tpu.ops.normalization import NormalizationContext

    jax.clear_caches()

    n, d = 700, 300
    x = jnp.zeros((n, d), jnp.float32)
    y = jnp.zeros(n, jnp.float32)
    c = jnp.zeros(d, jnp.float32)
    shifted = NormalizationContext(jnp.ones(d, jnp.float32),
                                   jnp.zeros(d, jnp.float32))
    calls = {
        "two": lambda x, c: aggregators.value_and_gradient(
            LogisticLoss, x, y, y, y, c, _IDN),
        "three": lambda x, c: aggregators.value_and_gradient(
            LogisticLoss, x, y, y, y, c, shifted),
        "product": lambda x, c: aggregators.hessian_vector_from_weights(
            x, y, c, _IDN, d),
    }
    text = jax.jit(calls[call]).trace(x, c).lower(
        lowering_platforms=("tpu",)).as_text()
    body, = re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]*)\\22', text)
    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(body))
        text = module.operation.get_asm(enable_debug_info=True)
    text = re.sub(r'"[^"]*/photon_tpu/', '"photon_tpu/', text)
    outside = set()
    lines = []
    for line in text.splitlines():
        m = re.match(r"(#loc\d+) = loc\((.*)\)$", line)
        if m and (re.match(r'"(?!photon_tpu/)[^"]*\.py":', m[2]) or any(
                ref in outside for ref in re.findall(r"#loc\d+", m[2])
                if not m[2].startswith("callsite"))):
            outside.add(m[1])
            line = f'{m[1]} = loc("caller")'
        lines.append(line)
    return hashlib.sha256("\n".join(lines).encode()).hexdigest()


# ``kernel_body_digest`` of f93ae62 (before the kernel could hand out
# curvature weights): the cells that run the kernel without them lower the
# kernel, and key their compile cache on it, as they did.
PARENT_KERNEL_BODY = {
    "two": "d70e67d98c5cca22ac4599803a5fcc51ac114552ca6ff8553382a5eef9bc8f04",
    "three": "e81645aeebe0a9353aca2a03aecb3c014135b968023b70d3622d616af0614268",
    "product": "ae39e8e2003b33562c5a440d8097f3d84b64962ddc87a59e276a3f5b9fea6a03",
}


@pytest.mark.parametrize("call", sorted(PARENT_KERNEL_BODY))
def test_the_kernel_without_weights_lowers_as_it_did(call, on_tpu,
                                                      monkeypatch):
    """The serialised kernel of the calls that do not ask for the weights,
    locations included, is the one they lowered before (a blank line added
    above ``_fused`` changes all three digests; the jaxprs above do not
    see it)."""
    monkeypatch.setattr(on_tpu, "_default_interpret", lambda: False)
    assert kernel_body_digest(call) == PARENT_KERNEL_BODY[call]
