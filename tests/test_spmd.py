"""SPMD execution tests on the 8-virtual-device mesh: sharded solves must
match single-device results and the compiled programs must actually
communicate (all-reduce in HLO) — the proof that the treeAggregate
replacement (SURVEY §5.8) executes, not just exists.

Reference behaviors being replaced: ValueAndGradientAggregator.scala:240-255
(treeAggregate), DistributedObjectiveFunction.scala:34 (coefficient
broadcast), RandomEffectCoordinate.scala:104-129 (co-partitioned per-entity
solves)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_tpu.data.dataset import DataBatch
from photon_tpu.function.objective import GLMObjective, Hyper
from photon_tpu.ops import features as F
from photon_tpu.ops.losses import LogisticLoss
from photon_tpu.parallel import mesh as M
from photon_tpu.optim.problem import GlmOptimizationProblem, GLMOptimizationConfiguration, OptimizerConfig
from photon_tpu.types import TaskType

from tests.test_game import glmix, glmix_estimator, make_glmix_frame  # noqa: F401


def make_logistic(rng, n=1024, d=16):
    X = rng.normal(size=(n, d))
    w = rng.normal(size=d)
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-X @ w))).astype(np.float64)
    return DataBatch(jnp.asarray(X), jnp.asarray(y)), X, y


def test_sharded_gradient_matches_and_allreduces(rng, devices8):
    """Data-sharded value+gradient == replicated result, and the compiled
    HLO contains an all-reduce (the treeAggregate equivalent on ICI)."""
    batch, _, _ = make_logistic(rng)
    mesh = M.create_mesh()
    obj = GLMObjective(LogisticLoss)
    hyper = Hyper.of(0.3, dtype=jnp.float64)
    coef = jnp.asarray(rng.normal(size=16))

    f_ref, g_ref = obj.value_and_gradient(coef, batch, hyper)

    sharded = M.shard_batch(batch, mesh)
    coef_r = M.replicate(coef, mesh)
    fn = jax.jit(lambda c, b: obj.value_and_gradient(c, b, hyper))
    f_sh, g_sh = fn(coef_r, sharded)

    np.testing.assert_allclose(float(f_sh), float(f_ref), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(g_sh), np.asarray(g_ref), rtol=1e-10)

    hlo = fn.lower(coef_r, sharded).compile().as_text()
    assert "all-reduce" in hlo, "sharded gradient must communicate over the mesh"


def test_sharded_solve_matches_single_device(rng, devices8):
    """A whole L-BFGS solve over the sharded batch equals the unsharded
    solve (the reference's Distributed vs SingleNode parity)."""
    batch, _, _ = make_logistic(rng, n=1000)  # 1000 % 8 != 0: exercises padding
    mesh = M.create_mesh()
    problem = GlmOptimizationProblem(
        TaskType.LOGISTIC_REGRESSION,
        GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(max_iterations=200, tolerance=1e-12)),
    )
    m_single, r_single = problem.run(batch, dim=16, dtype=jnp.float64,
                                     regularization_weight=1.0)
    problem2 = GlmOptimizationProblem(
        TaskType.LOGISTIC_REGRESSION,
        GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(max_iterations=200, tolerance=1e-12)),
    )
    m_mesh, r_mesh = problem2.run(batch, dim=16, dtype=jnp.float64,
                                  regularization_weight=1.0, mesh=mesh)
    np.testing.assert_allclose(np.asarray(m_mesh.coefficients.means),
                               np.asarray(m_single.coefficients.means),
                               rtol=1e-8, atol=1e-10)


def test_zero_weight_padding_is_exact(rng, devices8):
    """Padding to the device multiple must not change value or gradient."""
    batch, _, _ = make_logistic(rng, n=997)  # prime: heavy padding
    obj = GLMObjective(LogisticLoss)
    hyper = Hyper.of(0.0, dtype=jnp.float64)
    coef = jnp.asarray(rng.normal(size=16))
    f0, g0 = obj.value_and_gradient(coef, batch, hyper)
    padded = M.pad_batch(batch, 8)
    assert padded.num_samples == 1000
    f1, g1 = obj.value_and_gradient(coef, padded, hyper)
    np.testing.assert_allclose(float(f1), float(f0), rtol=1e-14)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), rtol=1e-14)


def test_game_estimator_mesh_parity(glmix, devices8):  # noqa: F811
    """GLMix fit on the 8-device mesh == single-device fit (sharded fixed
    batch + entity-sharded random effects), and validation AUC matches."""
    train, val, _ = glmix
    mesh = M.create_mesh()

    est_single = glmix_estimator(num_iterations=1)
    res_single = est_single.fit(train, validation_df=val)[-1]

    est_mesh = glmix_estimator(num_iterations=1)
    est_mesh.mesh = mesh
    res_mesh = est_mesh.fit(train, validation_df=val)[-1]

    fixed_s = res_single.model["fixed"].model.coefficients.means
    fixed_m = res_mesh.model["fixed"].model.coefficients.means
    np.testing.assert_allclose(np.asarray(fixed_m), np.asarray(fixed_s),
                               rtol=1e-6, atol=1e-8)

    re_s = np.asarray(res_single.model["per-user"].coefficients)
    re_m = np.asarray(res_mesh.model["per-user"].coefficients)
    # published models carry the vocabulary's true entity count either way
    assert re_m.shape == re_s.shape
    np.testing.assert_allclose(re_m, re_s, rtol=1e-6, atol=1e-8)

    assert abs(res_mesh.evaluation["AUC"] - res_single.evaluation["AUC"]) < 1e-9


def test_entity_sharded_blocks_cover_all_devices(glmix, devices8):  # noqa: F811
    """Entity blocks must actually land sharded across the mesh."""
    train, _, _ = glmix
    mesh = M.create_mesh()
    est = glmix_estimator(num_iterations=1)
    est.mesh = mesh
    model = est.fit(train)[0].model["per-user"]
    from photon_tpu.game.coordinate import RandomEffectCoordinate
    from photon_tpu.game.random_effect import build_random_effect_dataset
    # the estimator keeps the dataset its coordinate trains on, placed
    assert est._re_datasets["per-user"] is (
        est._coordinates["per-user"].dataset)
    # rebuild a coordinate directly, from one device, to inspect placement
    ds = build_random_effect_dataset(
        train, est.coordinate_configs["per-user"].data, est._vocab)
    coord = RandomEffectCoordinate(ds, train.num_samples, "userId",
                                   "user_feats", TaskType.LOGISTIC_REGRESSION,
                                   mesh=mesh)
    assert coord.dataset.blocks, "expected at least one entity block"
    for blk in coord.dataset.blocks:
        sharding = blk.labels.sharding
        assert len(sharding.device_set) == 8, "entity block not spread over mesh"
    # the flat-order map, re-derived for the padded buckets, is whole on
    # every device, and the score's one gather over the sharded margins
    # gives the one-device score
    assert coord.dataset.flat_source.sharding.is_fully_replicated
    assert [b.num_rows for b in coord.dataset.blocks] != [
        b.num_rows for b in ds.blocks]
    single = RandomEffectCoordinate(ds, train.num_samples, "userId",
                                    "user_feats", TaskType.LOGISTIC_REGRESSION)
    # (to the last bits: a margin's K-sum is ordered by the batch width)
    np.testing.assert_allclose(np.asarray(coord.score(model)),
                               np.asarray(single.score(model)),
                               rtol=1e-12, atol=1e-13)


def test_model_parallel_margins_allreduce(rng, devices8):
    """Feature-dimension sharding of theta (SURVEY §5.7): dense X sharded
    (data, model), theta sharded (model,) -> psum-ed partial dots."""
    n, d = 256, 64
    X = rng.normal(size=(n, d))
    coef = rng.normal(size=d)
    mesh = M.create_mesh(axis_names=(M.DATA_AXIS, M.MODEL_AXIS), shape=(4, 2))
    batch = M.shard_features_model_parallel(
        DataBatch(jnp.asarray(X), jnp.zeros(n)), mesh)
    theta = M.shard_coef_model_parallel(jnp.asarray(coef), mesh)

    fn = jax.jit(lambda x, t: F.matvec(x, t))
    margins = fn(batch.features, theta)
    np.testing.assert_allclose(np.asarray(margins), X @ coef, rtol=1e-10)
    hlo = fn.lower(batch.features, theta).compile().as_text()
    assert "all-reduce" in hlo, "model-parallel matvec must psum partial dots"


def test_estimator_model_axis_sharding_parity():
    """Fixed-effect training with theta sharded over the model axis through
    the PUBLIC estimator API: a (data=4, model=2) mesh must produce the
    same model as the (8, 1) data-parallel mesh, with all-reduce in the
    solve HLO (SURVEY §5.7; VERDICT r2 item 5 done-criterion)."""
    import numpy as np

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
    from photon_tpu.types import TaskType

    rng = np.random.default_rng(5)
    n, d, users, d_u = 512, 24, 10, 3   # d=24 pads to 24 (div by 2)
    Xg = rng.normal(size=(n, d))
    Xu = rng.normal(size=(n, d_u))
    uid = rng.integers(0, users, size=n)
    y = (rng.random(n) < 1 / (1 + np.exp(-(Xg @ rng.normal(size=d))))
         ).astype(np.float64)
    iu = np.arange(d_u, dtype=np.int32)
    df = GameDataFrame(
        num_samples=n, response=y,
        feature_shards={"global": FeatureShard(Xg, d),   # DENSE -> tp path
                        "u": FeatureShard([(iu, Xu[i]) for i in range(n)], d_u)},
        id_tags={"userId": [str(v) for v in uid]})

    opt = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=60, tolerance=1e-10),
        regularization=L2Regularization, regularization_weight=1.0)

    def fit(mesh):
        est = GameEstimator(
            TaskType.LOGISTIC_REGRESSION,
            {"fixed": CoordinateConfiguration(
                FixedEffectDataConfiguration("global"), opt),
             "per_user": CoordinateConfiguration(
                 RandomEffectDataConfiguration("userId", "u"), opt)},
            update_sequence=["fixed", "per_user"], num_iterations=2,
            dtype=jnp.float64, mesh=mesh)
        res = est.fit(df)
        return est, res[-1].model

    mesh_dp = M.create_mesh(8, (M.DATA_AXIS, M.MODEL_AXIS), (8, 1))
    mesh_tp = M.create_mesh(8, (M.DATA_AXIS, M.MODEL_AXIS), (4, 2))

    est_dp, m_dp = fit(mesh_dp)
    est_tp, m_tp = fit(mesh_tp)
    assert est_tp._coordinates["fixed"]._model_sharded
    assert not est_dp._coordinates["fixed"]._model_sharded

    np.testing.assert_allclose(
        np.asarray(m_tp["fixed"].model.coefficients.means),
        np.asarray(m_dp["fixed"].model.coefficients.means),
        rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(
        np.asarray(m_tp["per_user"].coefficients),
        np.asarray(m_dp["per_user"].coefficients),
        rtol=1e-8, atol=1e-10)

    # the tp solve must communicate over the mesh
    coord = est_tp._coordinates["fixed"]
    l2 = jnp.asarray(1.0, jnp.float64)
    theta0 = M.shard_coef_model_parallel(
        jnp.zeros((d,), jnp.float64), mesh_tp)
    hlo = coord.problem._solve_fn.lower(
        theta0, coord.batch, l2, jnp.asarray(0.0, jnp.float64)
    ).compile().as_text()
    assert "all-reduce" in hlo

    # memory property (SURVEY §5.7): dense-path theta is genuinely
    # partitioned — each device holds d/2 entries on the (4, 2) mesh,
    # vs a full replica per device data-parallel; this is what lets a
    # dense theta exceed one chip's replicable size at width d/P_model
    per_dev = {s.data.nbytes for s in theta0.addressable_shards}
    assert per_dev == {theta0.nbytes // 2}
    rep = M.replicate(jnp.zeros((d,), jnp.float64), mesh_dp)
    assert {s.data.nbytes for s in rep.addressable_shards} == {rep.nbytes}


# -- sparse feature-sharded fixed effect (SURVEY §5.7, VERDICT r3 item 3) ----

def _ell(rng, n, d, k):
    """Random ELL rows: k distinct feature ids per sample out of d."""
    idx = np.stack([rng.choice(d, size=k, replace=False) for _ in range(n)])
    val = rng.normal(size=(n, k))
    return F.SparseFeatures(jnp.asarray(idx, jnp.int32), jnp.asarray(val))


def test_sparse_model_parallel_kernel_parity(rng, devices8):
    """matvec/rmatvec/sq_rmatvec on feature-range-partitioned ELL blocks
    must match the plain data-parallel ELL kernels, and the margins program
    must all-reduce over the model axis (the psum of partial gather-dots)."""
    n, d, k = 64, 37, 5                      # d deliberately not % 2
    sf = _ell(rng, n, d, k)
    theta = rng.normal(size=d)
    w = rng.normal(size=n)

    mesh = M.create_mesh(8, (M.DATA_AXIS, M.MODEL_AXIS), (4, 2))
    batch = M.shard_sparse_features_model_parallel(
        DataBatch(sf, jnp.zeros(n)), mesh, dim=d)
    ms = batch.features
    assert isinstance(ms, F.ModelShardedSparse)
    d_pad = ms.padded_dim
    th = M.shard_coef_model_parallel(jnp.asarray(theta), mesh,
                                     padded_dim=d_pad)

    mv = jax.jit(lambda x, t: F.matvec(x, t))
    margins = mv(ms, th)
    np.testing.assert_allclose(np.asarray(margins),
                               np.asarray(F.matvec(sf, jnp.asarray(theta))),
                               rtol=1e-12)
    hlo = mv.lower(ms, th).compile().as_text()
    assert "all-reduce" in hlo, "partial gather-dots must psum over model axis"

    wj = jax.device_put(jnp.asarray(w), NamedSharding(mesh, P(M.DATA_AXIS)))
    g = jax.jit(lambda x, v: F.rmatvec(x, v, d_pad))(ms, wj)
    np.testing.assert_allclose(np.asarray(g)[:d],
                               np.asarray(F.rmatvec(sf, jnp.asarray(w), d)),
                               rtol=1e-12, atol=1e-12)
    assert np.allclose(np.asarray(g)[d:], 0.0)
    g2 = jax.jit(lambda x, v: F.sq_rmatvec(x, v, d_pad))(ms, wj)
    np.testing.assert_allclose(np.asarray(g2)[:d],
                               np.asarray(F.sq_rmatvec(sf, jnp.asarray(w), d)),
                               rtol=1e-12, atol=1e-12)


def test_partition_by_feature_range_layout():
    """Host-side partitioner invariants: local ids in range, per-range
    widths cover the worst row, values preserved."""
    idx = np.array([[0, 5, 9, 0], [3, 4, 8, 2]], np.int32)
    val = np.array([[1., 2., 3., 0.], [4., 5., 6., 7.]])
    sf = F.SparseFeatures(jnp.asarray(idx), jnp.asarray(val))
    out_idx, out_val, shard_size = F.partition_by_feature_range(sf, 10, 2)
    assert shard_size == 5
    assert out_idx.shape[0] == 2 and out_idx.max() < 5
    # row 1: shard0 gets {3:4, 4:5, 2:7}, shard1 gets {8:6} (local 3)
    got0 = {(i, v) for i, v in zip(out_idx[0, 1], out_val[0, 1]) if v != 0}
    assert got0 == {(3, 4.0), (4, 5.0), (2, 7.0)}
    got1 = {(i, v) for i, v in zip(out_idx[1, 1], out_val[1, 1]) if v != 0}
    assert got1 == {(3, 6.0)}


def test_sparse_feature_sharded_fixed_effect_parity(rng, devices8):
    """A sparse fixed effect trains with theta sharded over the model axis:
    (4, 2) mesh == (8, 1) data-parallel coefficients, all-reduce in the
    solve HLO, and theta is genuinely partitioned (per-device bytes sum to
    ONE copy, vs 8 replicas on the data-parallel mesh) — the memory
    property that lets theta exceed a single chip's replicable size."""
    from photon_tpu.game.coordinate import FixedEffectCoordinate

    n, d, k = 512, 1000, 8
    sf = _ell(rng, n, d, k)
    w = rng.normal(size=d) * 0.5
    margins = np.asarray(F.matvec(sf, jnp.asarray(w)))
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-margins))).astype(np.float64)
    batch = DataBatch(sf, jnp.asarray(y))

    from photon_tpu.function.objective import L2Regularization

    cfg = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=50, tolerance=1e-10),
        regularization=L2Regularization, regularization_weight=1.0)

    def fit(shape):
        mesh = M.create_mesh(8, (M.DATA_AXIS, M.MODEL_AXIS), shape)
        coord = FixedEffectCoordinate(batch, d, "g",
                                      TaskType.LOGISTIC_REGRESSION,
                                      cfg, mesh=mesh)
        model = coord.update_model(None, None)
        return coord, model

    coord_dp, m_dp = fit((8, 1))
    coord_tp, m_tp = fit((4, 2))
    assert coord_tp._model_sharded and not coord_dp._model_sharded
    assert isinstance(coord_tp.batch.features, F.ModelShardedSparse)

    np.testing.assert_allclose(
        np.asarray(m_tp.model.coefficients.means),
        np.asarray(m_dp.model.coefficients.means), rtol=1e-7, atol=1e-9)

    # scoring parity through the coordinate's own (model-sharded) batch
    np.testing.assert_allclose(np.asarray(coord_tp.score(m_tp)),
                               np.asarray(coord_dp.score(m_dp)),
                               rtol=1e-7, atol=1e-9)

    # communication proof: the jitted solve all-reduces
    l2 = jnp.asarray(1.0, jnp.float64)
    th0 = M.shard_coef_model_parallel(
        jnp.zeros((d,), jnp.float64), coord_tp.mesh,
        padded_dim=coord_tp._dim_padded)
    hlo = coord_tp.problem._solve_fn.lower(
        th0, coord_tp.batch, l2, jnp.asarray(0.0, jnp.float64)
    ).compile().as_text()
    assert "all-reduce" in hlo

    # memory proof: each device holds HALF of theta on the (4, 2) mesh
    # (sharded over model, replicated over data), vs a FULL copy per
    # device when data-parallel — the property that lets theta exceed a
    # single chip's replicable size at model-axis width d/P_model
    per_dev_tp = {s.data.nbytes for s in th0.addressable_shards}
    assert per_dev_tp == {th0.nbytes // 2}
    th_rep = M.replicate(jnp.zeros((d,), jnp.float64), coord_dp.mesh)
    per_dev_rep = {s.data.nbytes for s in th_rep.addressable_shards}
    assert per_dev_rep == {th_rep.nbytes}


def test_estimator_sparse_model_axis_through_public_api(rng, devices8):
    """Sparse fixed effect + random effect trained through GameEstimator
    on the (4, 2) mesh == (8, 1) data-parallel (the public-API version of
    the coordinate-level sparse tp test)."""
    from photon_tpu.estimators.game_estimator import (
        CoordinateConfiguration,
        FixedEffectDataConfiguration,
        GameEstimator,
    )
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.game.dataset import FeatureShard, GameDataFrame
    from photon_tpu.game.random_effect import RandomEffectDataConfiguration

    n, d, k, users, d_u = 512, 300, 6, 10, 3
    idx = np.stack([rng.choice(d, size=k, replace=False) for _ in range(n)])
    val = rng.normal(size=(n, k))
    uid = rng.integers(0, users, size=n)
    Xu = rng.normal(size=(n, d_u))
    w = rng.normal(size=d) * 0.5
    margins = np.asarray(
        F.matvec(F.SparseFeatures(jnp.asarray(idx, jnp.int32),
                                  jnp.asarray(val)), jnp.asarray(w)))
    y = (rng.random(n) < 1 / (1 + np.exp(-margins))).astype(np.float64)
    iu = np.arange(d_u, dtype=np.int32)
    df = GameDataFrame(
        num_samples=n, response=y,
        feature_shards={
            "g": FeatureShard([(idx[i], val[i]) for i in range(n)], d),
            "u": FeatureShard([(iu, Xu[i]) for i in range(n)], d_u)},
        id_tags={"userId": [str(v) for v in uid]})

    opt = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=50, tolerance=1e-10),
        regularization=L2Regularization, regularization_weight=1.0)

    def fit(shape):
        mesh = M.create_mesh(8, (M.DATA_AXIS, M.MODEL_AXIS), shape)
        est = GameEstimator(
            TaskType.LOGISTIC_REGRESSION,
            {"fixed": CoordinateConfiguration(
                FixedEffectDataConfiguration("g"), opt),
             "per_user": CoordinateConfiguration(
                 RandomEffectDataConfiguration("userId", "u"), opt)},
            update_sequence=["fixed", "per_user"], num_iterations=2,
            dtype=jnp.float64, mesh=mesh)
        return est, est.fit(df)[-1].model

    est_dp, m_dp = fit((8, 1))
    est_tp, m_tp = fit((4, 2))
    assert est_tp._coordinates["fixed"]._model_sharded
    assert isinstance(est_tp._coordinates["fixed"].batch.features,
                      F.ModelShardedSparse)
    np.testing.assert_allclose(
        np.asarray(m_tp["fixed"].model.coefficients.means),
        np.asarray(m_dp["fixed"].model.coefficients.means),
        rtol=1e-7, atol=1e-9)
    np.testing.assert_allclose(
        np.asarray(m_tp["per_user"].coefficients),
        np.asarray(m_dp["per_user"].coefficients), rtol=1e-7, atol=1e-9)


def test_create_pod_mesh_layout(devices8):
    """Pod mesh: data outermost / model innermost; initialize_distributed
    is a no-op single-process (SURVEY §5.8 DCN staging as mesh layout)."""
    assert M.initialize_distributed() == 1
    mesh = M.create_pod_mesh(model_axis_size=2)
    assert mesh.shape == {"data": 4, "model": 2}
    # a fit through the pod mesh matches the plain mesh
    rng = np.random.default_rng(3)
    batch, _, _ = make_logistic(rng, n=256)
    prob = GlmOptimizationProblem(
        TaskType.LOGISTIC_REGRESSION,
        GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(max_iterations=60, tolerance=1e-12)))
    m_pod, _ = prob.run(batch, dim=16, dtype=jnp.float64,
                        regularization_weight=1.0, mesh=mesh)
    prob2 = GlmOptimizationProblem(
        TaskType.LOGISTIC_REGRESSION,
        GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(max_iterations=60, tolerance=1e-12)))
    m_flat, _ = prob2.run(batch, dim=16, dtype=jnp.float64,
                          regularization_weight=1.0)
    np.testing.assert_allclose(np.asarray(m_pod.coefficients.means),
                               np.asarray(m_flat.coefficients.means),
                               rtol=1e-8, atol=1e-10)


def test_model_axis_explicit_hessian_tron_parity():
    """TRON with the EXPLICIT [d, d] Gauss-Newton Hessian (the TPU-default
    gate) under a model-sharded theta: GSPMD must partition the Gram
    build/CG identically to the data-parallel solve. This is the
    combination the round-4 TRON switch makes the on-chip default for
    dense fixed effects."""
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.types import OptimizerType

    rng = np.random.default_rng(9)
    n, d = 512, 16
    X = rng.normal(size=(n, d))
    y = (rng.random(n) < 1 / (1 + np.exp(-(X @ rng.normal(size=d))))
         ).astype(np.float64)

    cfg = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(optimizer_type=OptimizerType.TRON,
                                  max_iterations=60, tolerance=1e-11,
                                  explicit_hessian=True),
        regularization=L2Regularization, regularization_weight=0.7)

    def solve(mesh, model_par):
        prob = GlmOptimizationProblem(TaskType.LOGISTIC_REGRESSION, cfg)
        batch = DataBatch(jnp.asarray(X), jnp.asarray(y))
        if model_par:
            batch = M.shard_features_model_parallel(batch, mesh)
            init = M.shard_coef_model_parallel(
                jnp.zeros((d,), jnp.float64), mesh)
        else:
            batch = M.shard_batch(batch, mesh)
            init = M.replicate(jnp.zeros((d,), jnp.float64), mesh)
        model, _ = prob.run(batch, initial=init, dim=d, dtype=jnp.float64)
        return np.asarray(model.coefficients.means)

    mesh_dp = M.create_mesh(8, (M.DATA_AXIS, M.MODEL_AXIS), (8, 1))
    mesh_tp = M.create_mesh(8, (M.DATA_AXIS, M.MODEL_AXIS), (4, 2))
    c_dp = solve(mesh_dp, model_par=False)
    c_tp = solve(mesh_tp, model_par=True)
    np.testing.assert_allclose(c_tp, c_dp, rtol=1e-8, atol=1e-10)


def test_dcn_staged_psum_two_collectives(rng, devices8):
    """treeAggregateDepth>1 analog (GameEstimator.scala:100): on a
    (dcn, data, model) two-level mesh, staged_psum reduces the gradient
    with TWO collectives — replica groups within the slice first, then
    across slices — and equals the flat joint-axis reduction."""
    from jax.sharding import NamedSharding

    mesh = M.create_two_level_mesh(8, dcn_factor=2, model_axis_size=2)
    assert mesh.shape == {"dcn": 2, "data": 2, "model": 2}
    n, d = 48, 10
    X = rng.normal(size=(n, d)).astype(np.float32)
    r = rng.normal(size=n).astype(np.float32)
    spec_x = P((M.DCN_AXIS, M.DATA_AXIS), None)
    spec_r = P((M.DCN_AXIS, M.DATA_AXIS))
    Xs = jax.device_put(jnp.asarray(X), NamedSharding(mesh, spec_x))
    rs = jax.device_put(jnp.asarray(r), NamedSharding(mesh, spec_r))

    staged = jax.jit(M.shard_map(
        lambda xb, rb: M.staged_psum(xb.T @ rb),
        mesh=mesh, in_specs=(spec_x, spec_r), out_specs=P()))
    flat = jax.jit(M.shard_map(
        lambda xb, rb: jax.lax.psum(xb.T @ rb, (M.DCN_AXIS, M.DATA_AXIS)),
        mesh=mesh, in_specs=(spec_x, spec_r), out_specs=P()))

    np.testing.assert_allclose(np.asarray(staged(Xs, rs)), X.T @ r,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(staged(Xs, rs)),
                               np.asarray(flat(Xs, rs)), rtol=1e-6)

    # structure: two distinct all-reduce ops, replica groups of size 2
    # each (stage 1: the data pairs, stage 2: the dcn pairs) — vs the
    # flat reduction's single size-4 groups
    hlo = staged.lower(Xs, rs).compile().as_text()
    ars = [l for l in hlo.splitlines() if "all-reduce(" in l]
    assert len(ars) >= 2, hlo
    hlo_flat = flat.lower(Xs, rs).compile().as_text()
    ars_flat = [l for l in hlo_flat.splitlines() if "all-reduce(" in l]
    assert len(ars_flat) == 1


def test_newton_solve_data_parallel_parity(rng, devices8):
    """NEWTON (the flagship bench solver) under a data-parallel mesh: the
    sharded solve equals the single-device solve and its compiled HLO
    all-reduces — the explicit-Hessian Gram contraction reduces over the
    data axis exactly like the gradient treeAggregate."""
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.types import OptimizerType

    batch, _, _ = make_logistic(rng, n=512, d=12)
    cfg = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(optimizer_type=OptimizerType.NEWTON,
                                  max_iterations=30, tolerance=1e-10),
        regularization=L2Regularization, regularization_weight=1.0)

    prob_single = GlmOptimizationProblem(TaskType.LOGISTIC_REGRESSION, cfg)
    m_single, _ = prob_single.run(batch, dim=12, dtype=jnp.float64)

    mesh = M.create_mesh()
    prob_mesh = GlmOptimizationProblem(TaskType.LOGISTIC_REGRESSION, cfg)
    m_mesh, res = prob_mesh.run(batch, dim=12, dtype=jnp.float64, mesh=mesh)
    np.testing.assert_allclose(np.asarray(m_mesh.coefficients.means),
                               np.asarray(m_single.coefficients.means),
                               rtol=1e-7, atol=1e-9)

    sharded = M.shard_batch(batch, mesh)
    th0 = M.replicate(jnp.zeros((12,), jnp.float64), mesh)
    one = jnp.asarray(1.0, jnp.float64)
    hlo = prob_mesh._solve_fn.lower(
        th0, sharded, one, jnp.asarray(0.0, jnp.float64)).compile().as_text()
    assert "all-reduce" in hlo


def test_segment_reduce_rmatvec_matches_scatter_path(rng, devices8):
    """Parity pin for the sharded-sparse gradient kernels: the
    column-sorted contiguous-segment reduction (csc_* plan present — the
    fast path shard_sparse_features_model_parallel now builds at ingest)
    must match the serialized per-slot at[].add scatter fallback (plan
    stripped) on the SAME partitioned nonzeros, in f64 to 1e-12."""
    import dataclasses

    n, d, k = 96, 53, 7
    sf = _ell(rng, n, d, k)
    w = rng.normal(size=n)

    mesh = M.create_mesh(8, (M.DATA_AXIS, M.MODEL_AXIS), (4, 2))
    batch = M.shard_sparse_features_model_parallel(
        DataBatch(sf, jnp.zeros(n)), mesh, dim=d)
    ms = batch.features
    assert ms.csc_ptr is not None, "ingest must build the CSC plan"
    scatter = dataclasses.replace(
        ms, csc_rows=None, csc_vals=None, csc_ptr=None)
    d_pad = ms.padded_dim
    wj = jax.device_put(jnp.asarray(w), NamedSharding(mesh, P(M.DATA_AXIS)))

    for kern in (F.rmatvec, F.sq_rmatvec):
        g_seg = jax.jit(lambda x, v, f=kern: f(x, v, d_pad))(ms, wj)
        g_sc = jax.jit(lambda x, v, f=kern: f(x, v, d_pad))(scatter, wj)
        np.testing.assert_allclose(np.asarray(g_seg), np.asarray(g_sc),
                                   rtol=1e-12, atol=1e-12,
                                   err_msg=kern.__name__)
    # and against the unsharded oracle, which neither path shares code with
    np.testing.assert_allclose(
        np.asarray(jax.jit(lambda x, v: F.rmatvec(x, v, d_pad))(ms, wj))[:d],
        np.asarray(F.rmatvec(sf, jnp.asarray(w), d)),
        rtol=1e-12, atol=1e-12)


def test_sparse_tp_two_level_mesh_staged_reduction(rng):
    """Sparse TP composed with the two-level (dcn, data, model) mesh: the
    CSC plan chunks samples over dcn*data, the gradient psum stages
    ICI-then-DCN (>= 2 all-reduce ops in HLO), and the kernels still match
    the unsharded oracle."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 virtual devices")
    n, d, k = 64, 41, 5
    sf = _ell(rng, n, d, k)
    theta = rng.normal(size=d)
    w = rng.normal(size=n)

    mesh = M.create_two_level_mesh(8, dcn_factor=2, model_axis_size=2)
    batch = M.shard_sparse_features_model_parallel(
        DataBatch(sf, jnp.zeros(n)), mesh, dim=d)
    ms = batch.features
    assert ms.dcn_axis == M.DCN_AXIS
    d_pad = ms.padded_dim
    th = M.shard_coef_model_parallel(jnp.asarray(theta), mesh,
                                     padded_dim=d_pad)
    mv = jax.jit(lambda x, t: F.matvec(x, t))
    np.testing.assert_allclose(np.asarray(mv(ms, th)),
                               np.asarray(F.matvec(sf, jnp.asarray(theta))),
                               rtol=1e-12)

    wj = jax.device_put(
        jnp.asarray(w), NamedSharding(mesh, P((M.DCN_AXIS, M.DATA_AXIS))))
    rv = jax.jit(lambda x, v: F.rmatvec(x, v, d_pad))
    np.testing.assert_allclose(np.asarray(rv(ms, wj))[:d],
                               np.asarray(F.rmatvec(sf, jnp.asarray(w), d)),
                               rtol=1e-12, atol=1e-12)
    hlo = rv.lower(ms, wj).compile().as_text()
    n_ar = sum(1 for line in hlo.splitlines() if "all-reduce(" in line)
    assert n_ar >= 2, \
        f"expected staged ICI-then-DCN all-reduces in rmatvec, found {n_ar}"
