"""FULL coefficient variances of a dense fixed effect at float32 (PR 40):
``GameEstimator(variance_computation_type=FULL)`` through ``fit`` against a
plain float64 oracle at the fitted means, the precision its Gram states
against NEWTON's and TRON's, the upper triangle it forms in row blocks
(PR 41), and the counters that say what was computed and which way.

``tests/test_variances.py`` holds the same path in float64 at 300 x 6; the
chip holds it at 530,000 x 2,000 (``benchmark/``: ``fe-epsilon-variance``).
"""

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
from photon_tpu.function.objective import L2Regularization
from photon_tpu.game.dataset import FeatureShard, GameDataFrame
from photon_tpu.obs.metrics import registry
from photon_tpu.optim import problem as P
from photon_tpu.optim.problem import (
    GLMOptimizationConfiguration,
    GlmOptimizationProblem,
    OptimizerConfig,
)
from photon_tpu.types import OptimizerType, TaskType, VarianceComputationType

FULL, SIMPLE, NONE = (VarianceComputationType.FULL,
                      VarianceComputationType.SIMPLE,
                      VarianceComputationType.NONE)
L2 = 1.0
# The largest relative gap, over the coefficients, that a float32 FULL
# variance may show against the float64 oracle AT ITS OWN fitted means. On
# a CPU a float32 Gram is exact products summed in float32, and the
# Cholesky inverse of a matrix whose condition number is under 10 adds as
# much: both shapes below read 3.6e-7. bfloat16 features (every product off
# by up to 2^-8, which 1,200 to 2,000 rows do not average away) read 2.5e-4
# and 3.4e-4, and SIMPLE, which drops every off-diagonal term, 5.8e-2 and
# 6.0e-2: both are held to FAIL it. The tolerance is the geometric middle
# of 3.6e-7 and 2.5e-4. (At 530,000 x 2,000 on the chip the readings, and
# so the limit, are the configuration's: ``benchmark/configs/
# fe-epsilon-variance.json``, ``correct_variance``.)
TOLERANCE = 1e-5
SHAPES = [(2000, 64), (1200, 320)]     # the second is past 256 features


def _rows(rows, width, seed=0):
    """epsilon-shaped: unit rows, labels from a planted N(0, 4^2) model."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, width))
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    z = x @ rng.normal(scale=4.0, size=width)
    y = (rng.random(rows) < 1 / (1 + np.exp(-z))).astype(np.float32)
    return x.astype(np.float32), y


def _fit(x, y, variance_type, feature_dtype=None):
    frame = GameDataFrame(num_samples=len(y), response=y,
                          feature_shards={"g": FeatureShard(x, x.shape[1])},
                          id_tags={})
    est = GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {"fixed": CoordinateConfiguration(
            FixedEffectDataConfiguration("g"),
            GLMOptimizationConfiguration(
                OptimizerConfig(max_iterations=100, tolerance=1e-6),
                L2Regularization, L2))},
        variance_computation_type=variance_type, dtype=jnp.float32,
        feature_dtype=feature_dtype)
    return est.fit(frame)[-1].model["fixed"].model.coefficients


def _oracle(x, means):
    """diag((X^T diag(s (1 - s)) X + l2 I)^-1) in float64."""
    x = x.astype(np.float64)
    s = 1 / (1 + np.exp(-(x @ np.asarray(means, np.float64))))
    h = x.T @ ((s * (1 - s))[:, None] * x) + L2 * np.eye(x.shape[1])
    return np.diag(np.linalg.inv(h))


def _gap(x, coefficients):
    want = _oracle(x, coefficients.means)
    got = np.asarray(coefficients.variances, np.float64)
    return float(np.max(np.abs(got - want) / want))


@pytest.fixture(scope="module", params=SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def rows(request):
    return _rows(*request.param)


def test_full_variances_through_fit_match_the_oracle_at_float32(rows):
    x, _ = rows
    coefficients = _fit(*rows, FULL)
    assert coefficients.variances.dtype == jnp.float32
    assert coefficients.variances.shape == (x.shape[1],)
    assert _gap(x, coefficients) <= TOLERANCE


def test_simple_in_fulls_place_fails_the_same_tolerance(rows):
    assert _gap(rows[0], _fit(*rows, SIMPLE)) > TOLERANCE


def test_bfloat16_features_fail_the_same_tolerance(rows):
    assert _gap(rows[0], _fit(*rows, FULL, jnp.bfloat16)) > TOLERANCE


def test_a_fit_with_none_publishes_no_variances(rows):
    assert _fit(*rows, NONE).variances is None


# --------------------------------------------------------------------------
# the variance's Gram states its own precision; NEWTON's and TRON's keep theirs
# --------------------------------------------------------------------------

def _equations(jaxpr):
    """Every equation of a jaxpr and of every jaxpr nested in it (pjit,
    while, scan, cond, custom calls)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _strip_shapes(width, column_block):
    """The contractions the upper-triangle route makes a row block: column
    block I against the columns from I's first on, ``[c_I, width - start]``."""
    return [(min(column_block, width - start), width - start)
            for start in range(0, width, column_block)]


def _gram_dots(jaxpr, width, column_block=None):
    """Every dot of a Gram in a jaxpr: the ``[width, width]`` one and, where
    a column block is given, the upper triangle's strips."""
    shapes = {(width, width)}
    if column_block is not None:
        shapes.update(_strip_shapes(width, column_block))
    return [eqn for eqn in _equations(jaxpr)
            if eqn.primitive.name == "dot_general"
            and eqn.outvars[0].aval.shape in shapes]


def _gram_precisions(jaxpr, width, column_block=None):
    """The ``precision`` of every dot of a Gram in a jaxpr."""
    return [eqn.params["precision"]
            for eqn in _gram_dots(jaxpr, width, column_block)]


def _has_loop(jaxpr):
    return any(eqn.primitive.name in ("while", "scan")
               for eqn in _equations(jaxpr))


def _stated(precision):
    """A dot's ``precision`` parameter, as one ``lax.Precision`` or None."""
    if isinstance(precision, tuple):
        assert precision[0] == precision[1], precision
        precision = precision[0]
    return precision


def _batch(rows=96, width=48):
    x, y = _rows(rows, width, seed=3)
    return DataBatch(jnp.asarray(x), jnp.asarray(y), jnp.zeros(rows, jnp.float32),
                     jnp.ones(rows, jnp.float32)), width


def _problem(solver=OptimizerType.LBFGS):
    return GlmOptimizationProblem(
        TaskType.LOGISTIC_REGRESSION, GLMOptimizationConfiguration(
            OptimizerConfig(optimizer_type=solver, max_iterations=5),
            L2Regularization, L2))


@pytest.fixture
def blocks(monkeypatch):
    """Set the variance's row block and the triangle's column block small
    enough for a test's matrix to span several; the shared programs are
    dropped around it (they were traced under other constants)."""
    from photon_tpu.ops import features as F
    from photon_tpu.utils import jitcache

    def set_blocks(rows=None, columns=None):
        if rows is not None:
            monkeypatch.setattr(P, "VARIANCE_GRAM_BLOCK_ROWS", rows)
        if columns is not None:
            monkeypatch.setattr(F, "GRAM_COLUMN_BLOCK", columns)
        jitcache.clear()

    yield set_blocks
    jitcache.clear()


@pytest.mark.parametrize("rows,columns", [(None, None), (32, 16)],
                         ids=["one_product", "upper_triangle"])
def test_the_variances_gram_carries_the_stated_precision(rows, columns,
                                                         blocks):
    blocks(rows, columns)
    batch, width = _batch()                      # 96 rows, 48 columns
    _, full = _problem()._variance_fns
    jaxpr = jax.make_jaxpr(full)(jnp.zeros(width, jnp.float32), batch,
                                 jnp.float32(L2))
    stated = [_stated(p)
              for p in _gram_precisions(jaxpr.jaxpr, width, columns)]
    # the Gram, and nothing else of its shapes below the stated precision
    # (the factorisation's own dots, where a backend has any, state theirs)
    assert P.VARIANCE_GRAM_PRECISION in stated, stated
    assert jax.lax.Precision.DEFAULT not in stated and None not in stated
    if columns is not None:          # 3 strips in the loop's body, no tail
        assert stated == [P.VARIANCE_GRAM_PRECISION] * 3, stated


@pytest.mark.parametrize("solver", [OptimizerType.NEWTON, OptimizerType.TRON],
                         ids=lambda s: s.name)
def test_a_solvers_gram_still_carries_default(solver):
    """NEWTON's and TRON's Hessian is a means to an optimum their exact
    gradient fixes: one bfloat16 pass, the program the parent compiled."""
    batch, width = _batch()
    one = jnp.float32(1.0)
    jaxpr = jax.make_jaxpr(_problem(solver)._solve_fn)(
        jnp.zeros(width, jnp.float32), batch, one, one)
    stated = {_stated(p) for p in _gram_precisions(jaxpr.jaxpr, width)}
    assert stated == {jax.lax.Precision.DEFAULT}, stated


# --------------------------------------------------------------------------
# the variance's Gram is summed in row blocks; a mesh keeps one contraction
# --------------------------------------------------------------------------

@pytest.mark.parametrize("rows,block_rows", [(1000, 256), (1024, 256),
                                             (200, 256)],
                         ids=["a_tail", "whole_blocks", "one_block"])
def test_the_gram_in_row_blocks_is_the_gram(rows, block_rows):
    """``X^T diag(w) X`` summed ``block_rows`` rows at a time, the rows
    over the last whole block in one more trip of the loop, and fewer
    rows than a block in the ONE contraction every other caller makes."""
    from photon_tpu.ops import features as F

    rng = np.random.default_rng(rows)
    x, w = rng.normal(size=(rows, 24)), rng.random(rows)
    want = x.T @ (w[:, None] * x)
    got = F.weighted_gram(jnp.asarray(x), jnp.asarray(w), 24,
                          jax.lax.Precision.HIGHEST, block_rows)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-12, atol=1e-12)
    jaxpr = jax.make_jaxpr(lambda x, w: F.weighted_gram(
        x, w, 24, jax.lax.Precision.HIGHEST, block_rows))(x, w)
    assert _has_loop(jaxpr.jaxpr) == (rows > block_rows)


@pytest.mark.parametrize("block_rows,looped", [(64, True), (None, False)],
                         ids=["one_device", "mesh"])
def test_a_mesh_keeps_the_one_contraction(block_rows, looped, monkeypatch):
    """On one device FULL's Gram loops over ``VARIANCE_GRAM_BLOCK_ROWS``
    rows at a time; ``compute_variances(mesh=...)`` asks for the program
    with ONE contraction, whose partial sums a mesh reduces (a slice of a
    sample-sharded matrix at a traced offset would gather it)."""
    batch, width = _batch()                      # 96 rows
    monkeypatch.setattr(P, "VARIANCE_GRAM_BLOCK_ROWS", 64)
    problem = _problem()
    asked = []
    monkeypatch.setattr(problem, "_variance_fns_for", lambda rows: (
        asked.append(rows), GlmOptimizationProblem._variance_fns_for(
            problem, rows))[1])
    variances = problem.compute_variances(
        batch, jnp.zeros(width, jnp.float32), FULL,
        mesh=None if looped else object())
    assert asked == [block_rows] and variances.shape == (width,)
    _, full = GlmOptimizationProblem._variance_fns_for(problem, block_rows)
    jaxpr = jax.make_jaxpr(full)(jnp.zeros(width, jnp.float32), batch,
                                 jnp.float32(L2))
    assert _has_loop(jaxpr.jaxpr) == looped
    stated = [_stated(p) for p in _gram_precisions(jaxpr.jaxpr, width)]
    assert P.VARIANCE_GRAM_PRECISION in stated


# --------------------------------------------------------------------------
# the Gram summed in row blocks forms the UPPER triangle's column blocks only
# --------------------------------------------------------------------------

def _loop_body(jaxpr):
    (loop,) = [eqn for eqn in _equations(jaxpr)
               if eqn.primitive.name in ("while", "scan")]
    body = loop.params["body_jaxpr" if loop.primitive.name == "while"
                       else "jaxpr"]
    return getattr(body, "jaxpr", body)


@pytest.mark.parametrize("rows,block_rows,width,columns", [
    (1024, 256, 64, 16),        # four whole column blocks, whole row blocks
    (1024, 256, 72, 32),        # 32 + 32 + 8
    (1000, 256, 96, 32),        # 232 rows over the last whole row block
    (512, 256, 80, 40),         # exactly two
    (600, 256, 41, 40),         # one column over a block
    (513, 256, 48, 16),         # one row over the last whole row block
    (1000, 256, 32, 32),        # one column block: one strip, the product
], ids=["whole_column_blocks", "a_ragged_last_block", "a_row_tail",
        "exactly_two_blocks", "one_column_over", "one_row_over",
        "one_column_block"])
def test_the_upper_triangle_in_row_blocks_is_the_gram(rows, block_rows, width,
                                                      columns, monkeypatch):
    """The row-blocked Gram forms, a row block, ONE contraction a column
    block I (I's columns against the columns from I's first on: the block
    pairs I <= J side by side), past one column block never the ``[width,
    width]`` product, every one at the stated precision, the rows over the
    last whole row block in one more trip of the same loop; the lower
    triangle is the mirror, so the result is symmetric bit for bit and
    equals the full product's."""
    from photon_tpu.ops import features as F

    monkeypatch.setattr(F, "GRAM_COLUMN_BLOCK", columns)
    rng = np.random.default_rng(rows + width)
    x, w = rng.normal(size=(rows, width)), rng.random(rows)
    assert F.gram_route(jnp.asarray(x), block_rows) == "dense_upper"

    def gram(x, w):
        return F.weighted_gram(x, w, width, P.VARIANCE_GRAM_PRECISION,
                               block_rows)

    got = np.asarray(gram(jnp.asarray(x), jnp.asarray(w)))
    np.testing.assert_allclose(got, x.T @ (w[:, None] * x),
                               rtol=1e-12, atol=1e-12)
    assert (got == got.T).all()
    jaxpr = jax.make_jaxpr(gram)(x, w).jaxpr
    strips = _strip_shapes(width, columns)
    in_body = [eqn.outvars[0].aval.shape for eqn in _equations(
        _loop_body(jaxpr)) if eqn.primitive.name == "dot_general"]
    assert in_body == strips, in_body
    # and they are all of them: the rows over the last whole row block ride
    # in one more trip of the same loop, with no contractions of their own
    dots = _gram_dots(jaxpr, width, columns)
    assert len(dots) == len(strips)
    assert len(_gram_dots(jaxpr, width)) == (width <= columns), (
        "a full [width, width] product")
    assert {_stated(eqn.params["precision"]) for eqn in dots} == {
        P.VARIANCE_GRAM_PRECISION}
    # the multiply-adds kept: (width^2 + sum c_i^2) / (2 width^2) of them
    assert 2 * sum(a * b for a, b in strips) == (
        width ** 2 + sum(a * a for a, _ in strips))


@pytest.mark.parametrize("rows,block_rows,width", [
    (1000, None, 96),           # all rows at once: a mesh, NEWTON, TRON
    (200, 256, 96),             # fewer rows than a block
], ids=["no_row_blocks", "one_row_block"])
def test_what_keeps_the_one_full_product(rows, block_rows, width,
                                         monkeypatch):
    """The triangle exists only inside the row-blocked sum; everything else
    is the ONE ``[width, width]`` product over all rows it was, with no
    loop."""
    from photon_tpu.ops import features as F

    monkeypatch.setattr(F, "GRAM_COLUMN_BLOCK", 32)
    rng = np.random.default_rng(rows + width)
    x, w = rng.normal(size=(rows, width)), rng.random(rows)
    assert F.gram_route(jnp.asarray(x), block_rows) == "dense"

    def gram(x, w):
        return F.weighted_gram(x, w, width, P.VARIANCE_GRAM_PRECISION,
                               block_rows)

    np.testing.assert_allclose(np.asarray(gram(jnp.asarray(x), jnp.asarray(w))),
                               x.T @ (w[:, None] * x), rtol=1e-12, atol=1e-12)
    jaxpr = jax.make_jaxpr(gram)(x, w).jaxpr
    dots = [eqn for eqn in _equations(jaxpr)
            if eqn.primitive.name == "dot_general"]
    assert [eqn.outvars[0].aval.shape for eqn in dots] == [(width, width)]
    assert not _has_loop(jaxpr)


def test_the_shipped_column_block_is_whole_lane_tiles():
    """X is stored rows-major at whole 128-lane tiles (PR 37), so a column
    block's edge is a tile's; epsilon's 2,000 columns span several."""
    from photon_tpu.ops import features as F

    assert F.GRAM_COLUMN_BLOCK % 128 == 0
    assert F.gram_route(jax.ShapeDtypeStruct((530_000, 2_000), jnp.float32),
                        P.VARIANCE_GRAM_BLOCK_ROWS) == "dense_upper"
    # NEWTON's Gram in the GLMix cells asks for no row blocks
    assert F.gram_route(jax.ShapeDtypeStruct((5_000_000, 128), jnp.float32),
                        None) == "dense"
    assert P.VARIANCE_GRAM_BLOCK_ROWS == 8192
    assert P.VARIANCE_GRAM_PRECISION == jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# the counters
# --------------------------------------------------------------------------

def _counter(name, **labels):
    return sum(value for found, value in registry.series(name)
               if all(found.get(k) == v for k, v in labels.items()))


@pytest.mark.parametrize("variance_type,width,path,traced", [
    (FULL, 37, "dense", 1), (SIMPLE, 38, "dense", 0),
    (FULL, 41, "dense_upper", 1)],
    ids=["FULL", "SIMPLE", "FULL_past_two_column_blocks"])
def test_an_update_with_variances_ticks_once(variance_type, width, path,
                                             traced, blocks):
    """``variance.computed{coordinate, type}`` once an update, always on;
    ``kernels.variance_gram{precision, path}`` once a TRACED FULL program
    (SIMPLE builds no Gram), under the way its Gram went (``dense_upper``:
    the upper triangle in row blocks; ``dense``: one full product), and not
    again on a repeat."""
    if path == "dense_upper":
        blocks(rows=64, columns=16)              # 300 rows, 41 columns
    x, y = _rows(300, width, seed=5)
    labels = {"coordinate": "g", "type": variance_type.name}
    gram = {"precision": P.VARIANCE_GRAM_PRECISION.name, "path": path}
    other = dict(gram, path="dense" if path == "dense_upper"
                 else "dense_upper")

    def read():
        return (_counter("variance.computed", **labels),
                _counter("kernels.variance_gram", **gram),
                _counter("kernels.variance_gram", **other))

    before = read()
    _fit(x, y, variance_type)
    once = read()
    assert tuple(b - a for a, b in zip(before, once)) == (1, traced, 0)
    _fit(x, y, variance_type)
    assert tuple(b - a for a, b in zip(once, read())) == (1, 0, 0)


def test_a_fit_with_none_ticks_neither():
    x, y = _rows(300, 39, seed=6)
    before = (_counter("variance.computed"), _counter("kernels.variance_gram"))
    _fit(x, y, NONE)
    assert (_counter("variance.computed"),
            _counter("kernels.variance_gram")) == before
