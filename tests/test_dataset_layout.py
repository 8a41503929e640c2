"""Where a dense fixed effect's matrix is placed, and in which layout.

``GameDataFrame.shard_features`` / ``fixed_effect_batch`` place a dense X
plainly, uncommitted. ``GameEstimator._prepare``, the layer that solves on
it again and again and knows its mesh, passes it through
``dataset.store_rows_major``: stored rows-major where the device's own
layout of that shape is not (a TPU's, where the width is no multiple of
128), the padding costs at most an eighth and no mesh re-places the batch;
everywhere else, every CPU array among them, it is the ``jnp.asarray`` it
always was. A validation or transform X never takes it. The rule is held here to
the defaults a v5e's compiler reports (``compiled.input_formats``, read
for those shapes by ``tests/test_pallas_glm.py``, the one file that may
describe the chip: a second file's fixture would skip in silence under
several workers), and the CPU's behaviour to the parent's.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_tpu.data.dataset import DataBatch
from photon_tpu.game import dataset
from photon_tpu.game.dataset import (
    ROW_MAJOR,
    FeatureShard,
    GameDataFrame,
    row_major_outcome,
)
from photon_tpu.obs.metrics import registry

COLUMN_MAJOR = (1, 0)

# (shape, dtype, what a described v5e reports for the argument when nothing
# is stated, the outcome that follows): ``test_pallas_glm.py::
# test_row_major_admission_against_a_v5e`` reads the same shapes from the
# chip's compiler and fails if one of these moves
V5E_DEFAULTS = [
    ((530_000, 2_000), "float32", COLUMN_MAJOR, "relaid"),     # fe-epsilon
    ((530_000, 2_000), "bfloat16", COLUMN_MAJOR, "relaid"),
    ((1_000_000, 1_000), "float32", COLUMN_MAJOR, "relaid"),
    ((5_000_000, 128), "float32", ROW_MAJOR, "default"),       # glmix-ml20m
    ((4_000_000, 256), "float32", ROW_MAJOR, "default"),
    ((100_000, 130), "float32", COLUMN_MAJOR, "padding"),      # 130 -> 256
    ((100_000, 100), "float32", COLUMN_MAJOR, "padding"),      # 100 -> 128
]


@pytest.mark.parametrize("shape,dtype,default,outcome", V5E_DEFAULTS,
                         ids=lambda v: str(v))
def test_admitted_where_column_major_and_padded_under_an_eighth(
        shape, dtype, default, outcome):
    width = shape[1]
    padded = -(-width // 128) * 128
    admitted = default != ROW_MAJOR and padded <= 1.125 * width
    assert (row_major_outcome(width, default) == "relaid") == admitted
    assert row_major_outcome(width, default) == outcome
    # a mesh re-places the batch: never relaid, and says so unless the
    # default is the rows-major layout already
    assert row_major_outcome(width, default, on_mesh=True) == (
        "default" if default == ROW_MAJOR else "mesh")


@pytest.mark.parametrize("width,admitted", [
    (2_000, True), (1_000, True), (1_024, True), (904, False), (912, True),
    (1, False), (127, True), (129, False), (114, True), (113, False)])
def test_the_eighth(width, admitted):
    """``ceil(width / 128) * 128 <= 1.125 * width`` and nothing else."""
    assert (row_major_outcome(width, COLUMN_MAJOR) == "relaid") == admitted
    assert row_major_outcome(width, None) == "default"


def _columns_first(monkeypatch):
    """A device whose DEFAULT layout is column-major, stood in for by the
    ONE function that reads an array's layout: what ``jnp.asarray`` places
    (uncommitted) and what a mesh places from the host (over several
    devices) read column-major, what the relayout program hands out
    (committed, on one device) reads as it lies."""
    real = dataset._major_to_minor
    monkeypatch.setattr(
        dataset, "_major_to_minor",
        lambda x: real(x) if x.committed and len(x.sharding.device_set) == 1
        else COLUMN_MAJOR)


def _frame(n=600, d=48, seed=3):
    rng = np.random.default_rng(seed)
    x = (rng.normal(size=(n, d)) / np.sqrt(d)).astype(np.float32)
    beta = rng.normal(size=d) * 2.0
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-x @ beta))).astype(np.float32)
    return GameDataFrame(
        num_samples=n, response=y,
        feature_shards={"g": FeatureShard(x, d)},
        offsets=rng.normal(size=n).astype(np.float32) * 0.1,
        weights=(rng.random(n) + 0.5).astype(np.float32))


def _ticks():
    return {k: int(v) for k, v in registry.snapshot()["counters"].items()
            if k.startswith("ingest.row_major{")}


def _ticked(before):
    return {k: v - before.get(k, 0) for k, v in _ticks().items()
            if v != before.get(k, 0)}


def _problem(optimizer_type="LBFGS"):
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        GlmOptimizationProblem,
        OptimizerConfig,
    )
    from photon_tpu.types import OptimizerType, TaskType

    return GlmOptimizationProblem(
        TaskType.LOGISTIC_REGRESSION, GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(
                optimizer_type=OptimizerType[optimizer_type],
                max_iterations=30, tolerance=1e-6),
            regularization=L2Regularization, regularization_weight=1.0))


def test_on_the_cpu_the_batch_is_the_parents():
    """``fixed_effect_batch`` and ``shard_features`` are the parent's: the
    uncommitted ``jnp.asarray`` of the rows and no tick, whatever the
    device lays first. On the CPU, whose default IS rows-major,
    ``store_rows_major`` hands the same array back under one ``default``
    tick, and the solve lowered on it is the solve lowered on a plain
    array, text for text."""
    df = _frame()
    before = _ticks()
    batch = df.fixed_effect_batch("g", coordinate="fixed")
    df.shard_features("g")
    assert _ticked(before) == {}
    x = dataset.store_rows_major(batch.features, "fixed")
    assert x is batch.features
    assert _ticked(before) == {
        'ingest.row_major{coordinate="fixed",outcome="default"}': 1}
    plain = jnp.asarray(df.feature_shards["g"].rows, np.float32)
    assert not x.committed and x.dtype == plain.dtype
    assert x.format == plain.format
    np.testing.assert_array_equal(np.asarray(x), np.asarray(plain))

    one, x0 = jnp.float32(1.0), jnp.zeros(x.shape[1], jnp.float32)
    texts = [_problem()._solve_fn.lower(
        x0, DataBatch(feats, batch.labels, batch.offsets, batch.weights),
        one, one).as_text() for feats in (x, plain)]
    assert texts[0] == texts[1]


@pytest.mark.parametrize("case,width,on_mesh,outcome", [
    ("relaid", 2_000, False, "relaid"),
    ("mesh", 2_000, True, "mesh"),
    ("padding", 130, False, "padding"),
])
def test_counter_and_placement_where_the_device_lays_columns_first(
        monkeypatch, case, width, on_mesh, outcome):
    """One tick under the outcome the shape implies; only ``relaid``
    commits the array, rows-major, with the values it had; the frame's own
    placement never does."""
    _columns_first(monkeypatch)
    rows = np.random.default_rng(width).normal(size=(40, width)).astype(
        np.float32)
    df = GameDataFrame(num_samples=40, response=np.zeros(40, np.float32),
                       feature_shards={"g": FeatureShard(rows, width)})
    before = _ticks()
    placed = df.fixed_effect_batch("g", coordinate=case).features
    assert not placed.committed and _ticked(before) == {}
    x = dataset.store_rows_major(placed, case, on_mesh=on_mesh)
    assert _ticked(before) == {
        f'ingest.row_major{{coordinate="{case}",outcome="{outcome}"}}': 1}
    assert x.committed == (outcome == "relaid")
    assert (x is placed) == (outcome != "relaid")
    assert x.format.layout.major_to_minor == ROW_MAJOR    # the CPU's own
    np.testing.assert_array_equal(np.asarray(x), rows)


def test_a_mislabelled_relayout_is_dropped_for_the_plain_array(monkeypatch):
    """An executable that hands out a label other than rows-major (a stale
    persistent-cache entry on the chip) cannot break a fit: the plain array
    comes back, uncommitted, counted ``mislabelled``."""
    monkeypatch.setattr(dataset, "_major_to_minor", lambda x: COLUMN_MAJOR)
    placed = jnp.ones((16, 912), jnp.float32)
    before = _ticks()
    x = dataset.store_rows_major(placed, "stale")
    assert x is placed and not x.committed
    assert _ticked(before) == {
        'ingest.row_major{coordinate="stale",outcome="mislabelled"}': 1}


def test_only_a_dense_matrix_is_counted(monkeypatch):
    _columns_first(monkeypatch)
    from photon_tpu.ops import features as F

    before = _ticks()
    sparse = F.from_csr_arrays(np.array([0, 1, 2]), np.array([0, 3]),
                               np.array([1.0, 2.0], np.float32))
    assert dataset.store_rows_major(sparse, "sparse") is sparse
    blocks = jnp.ones((2, 8, 912), jnp.float32)
    assert dataset.store_rows_major(blocks, "blocks") is blocks
    assert _ticked(before) == {}


def test_the_estimator_says_when_a_mesh_replaces_the_batch(monkeypatch,
                                                           devices8):
    """``GameEstimator._prepare`` knows its mesh: a meshed estimator's
    matrix is counted ``mesh`` and left to ``shard_batch``; without one
    it is a placement on one device."""
    from photon_tpu.parallel import mesh as M

    _columns_first(monkeypatch)
    df = _frame(n=256, d=912)
    for mesh, outcome in ((M.create_mesh(8, (M.DATA_AXIS,), (8,)), "mesh"),
                          (None, "relaid")):
        est = _estimator("LBFGS", mesh=mesh)
        before = _ticks()
        _, coordinates, _ = est._prepare_cached(df)
        assert _ticked(before) == {
            f'ingest.row_major{{coordinate="fixed",outcome="{outcome}"}}': 1}
        x = coordinates["fixed"].batch.features
        assert len(x.sharding.device_set) == (8 if mesh is not None else 1)


@pytest.mark.parametrize("meshed", [False, True], ids=["one-device", "mesh"])
def test_validation_and_transform_take_the_plain_matrix(monkeypatch,
                                                        devices8, meshed):
    """The scorers' X (``fit(validation_df=...)``, ``GameTransformer``) is
    placed by ``shard_features`` alone: never relaid, never committed, so
    it can be scored against coefficients that live on one device or on a
    mesh. Only the training X of an estimator without a mesh is relaid."""
    from photon_tpu.estimators.game_estimator import GameTransformer
    from photon_tpu.parallel import mesh as M

    _columns_first(monkeypatch)
    train, val = _frame(n=256, d=912), _frame(n=128, d=912, seed=5)
    mesh = M.create_mesh(8, (M.DATA_AXIS,), (8,)) if meshed else None
    est = _estimator("LBFGS", mesh=mesh, evaluators=["AUC"])
    before = _ticks()
    (result,) = est.fit(train, validation_df=val)
    assert _ticked(before) == {
        'ingest.row_major{coordinate="fixed",outcome="%s"}'
        % ("mesh" if meshed else "relaid"): 1}
    assert est._coordinates["fixed"].batch.features.committed
    assert 0.5 < result.evaluation["AUC"] <= 1.0
    scores = GameTransformer(result.model, est).transform(val)
    assert _ticked(before).keys() == {
        'ingest.row_major{coordinate="fixed",outcome="%s"}'
        % ("mesh" if meshed else "relaid")}
    coef = np.asarray(result.model["fixed"].model.coefficients.means)
    want = val.feature_shards["g"].rows @ coef + val.offsets
    np.testing.assert_allclose(np.asarray(scores), want, rtol=2e-5,
                               atol=2e-5)


def _estimator(optimizer_type, mesh=None, evaluators=None):
    from photon_tpu.estimators.game_estimator import (
        CoordinateConfiguration,
        FixedEffectDataConfiguration,
        GameEstimator,
    )
    from photon_tpu.types import TaskType

    return GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {"fixed": CoordinateConfiguration(
            FixedEffectDataConfiguration("g"),
            _problem(optimizer_type).config)},
        update_sequence=["fixed"], num_iterations=1, dtype=jnp.float32,
        mesh=mesh, validation_evaluators=evaluators)


@pytest.mark.parametrize("fit", ["LBFGS", "TRON", "swept"])
def test_a_fit_on_a_committed_matrix_is_the_fit_on_a_plain_one(monkeypatch,
                                                               fit):
    """The relaid array is COMMITTED and every other argument of the
    solve, the score and the lane programs is not: the programs take it as
    it lies and, where the two layouts agree (here), give the same bits."""
    from photon_tpu.utils import jitcache

    df = _frame(d=912)

    def coefficients(relaid):
        jitcache.clear()
        if relaid:
            _columns_first(monkeypatch)
        else:
            monkeypatch.undo()
        est = _estimator("LBFGS" if fit == "swept" else fit)
        if fit == "swept":
            results = est.fit_swept(df, weights=[0.1, 1.0, 10.0, 100.0])
        else:
            results = est.fit(df)
        x = est._coordinates["fixed"].batch.features
        assert x.committed == relaid
        return np.stack([np.asarray(
            r.model["fixed"].model.coefficients.means) for r in results])

    want, got = coefficients(False), coefficients(True)
    jitcache.clear()
    assert np.isfinite(got).all() and np.abs(got).max() > 0
    np.testing.assert_array_equal(got, want)


def test_the_relayout_program_is_compiled_in_the_process(monkeypatch):
    """The one program with a stated OUTPUT layout never reaches the
    persistent cache (a served executable mislabels such an output on the
    chip: ``compile_cache.compiled_in_this_process``): it is traced and
    compiled with the write threshold at infinity, which is back where it
    was afterwards."""
    name = "jax_persistent_cache_min_compile_time_secs"
    was = getattr(jax.config, name)
    seen = []

    def rows_major(x):
        seen.append(getattr(jax.config, name))
        return x

    _columns_first(monkeypatch)
    monkeypatch.setattr(dataset, "_rows_major", rows_major)
    x = dataset.store_rows_major(jnp.ones((16, 912), jnp.float32), "c")
    assert seen == [float("inf")] and x.committed
    assert getattr(jax.config, name) == was
    with pytest.raises(RuntimeError):
        with dataset.compile_cache.compiled_in_this_process():
            raise RuntimeError
    assert getattr(jax.config, name) == was
