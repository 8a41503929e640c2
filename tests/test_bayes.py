"""Bayesian GLMix subsystem tests (photon_tpu/bayes + the layers it
rides): diagonal-Hessian Laplace posteriors vs finite differences and
closed forms, the cold-store variance column, the BayesianLinearModelAvro
variance contract, posterior-interval calibration against a known
truth, Thompson-sampling serving determinism and the nearline variance
republish path.

Reference semantics: SIMPLE variances are ``1 / (H_ii + lambda)`` at the
fitted optimum (DistributedOptimizationProblem.computeVariances); losses
without a Hessian (smoothed hinge) are first-order only and must be
refused typed, never silently approximated.
"""

import types

import numpy as np
import pytest
import jax.numpy as jnp

from photon_tpu.bayes import (
    StreamedLaplace,
    entity_variances_blocked,
    fixed_effect_variances_streamed,
)
from photon_tpu.data.dataset import DataBatch
from photon_tpu.function.objective import GLMObjective, Hyper
from photon_tpu.ops import features as F
from photon_tpu.ops.losses import (
    LogisticLoss,
    PoissonLoss,
    SmoothedHingeLoss,
    SquaredLoss,
)

# ---------------------------------------------------------------------------
# losses: second derivatives vs central finite differences (f64)
# ---------------------------------------------------------------------------

# margins chosen away from the smoothed hinge's kinks at t = 0 and t = 1
# (t = +-z for y in {0, 1}), so the a.e. second derivative is exact there
_MARGINS = np.array([-2.3, -1.7, -0.6, 0.21, 0.55, 0.83, 1.9, 3.1])

_LOSS_LABELS = {
    "logistic": (LogisticLoss, np.array([0.0, 1.0])),
    "squared": (SquaredLoss, np.array([-0.7, 1.3])),
    "poisson": (PoissonLoss, np.array([0.0, 2.0])),
    "smoothed_hinge": (SmoothedHingeLoss, np.array([0.0, 1.0])),
}


@pytest.mark.parametrize("loss_name", sorted(_LOSS_LABELS))
def test_d2z_matches_central_difference(loss_name):
    loss, ys = _LOSS_LABELS[loss_name]
    h = 1e-5
    z = jnp.asarray(_MARGINS, jnp.float64)
    for y0 in ys:
        y = jnp.full_like(z, float(y0))
        lp = np.asarray(loss.value(z + h, y), np.float64)
        l0 = np.asarray(loss.value(z, y), np.float64)
        lm = np.asarray(loss.value(z - h, y), np.float64)
        fd = (lp - 2.0 * l0 + lm) / (h * h)
        np.testing.assert_allclose(np.asarray(loss.d2z(z, y)), fd,
                                   rtol=1e-4, atol=1e-4)


def _fd_batch(loss_name, n=40, d=5, seed=17):
    loss, _ = _LOSS_LABELS[loss_name]
    rng = np.random.default_rng(seed)
    idx = np.tile(np.arange(d, dtype=np.int32), (n, 1))
    val = rng.normal(size=(n, d))
    if loss is PoissonLoss:
        y = rng.integers(0, 4, size=n).astype(np.float64)
    elif loss is SquaredLoss:
        y = rng.normal(size=n)
    else:
        y = rng.integers(0, 2, size=n).astype(np.float64)
    batch = DataBatch(
        F.SparseFeatures(jnp.asarray(idx), jnp.asarray(val, jnp.float64)),
        jnp.asarray(y, jnp.float64),
        jnp.asarray(rng.normal(size=n) * 0.1, jnp.float64),
        jnp.asarray(rng.uniform(0.5, 1.5, size=n), jnp.float64))
    theta = rng.normal(size=d) * 0.3
    return loss, batch, theta


@pytest.mark.parametrize("loss_name", ["logistic", "squared", "poisson"])
def test_hessian_diagonal_matches_fd_of_value(loss_name):
    """H_ii from the aggregator kernel == central second difference of
    the full objective (weights, offsets, and the L2 mixin included)."""
    loss, batch, theta = _fd_batch(loss_name)
    obj = GLMObjective(loss=loss)
    hyper = Hyper.of(l2_weight=0.3, dtype=jnp.float64)
    d = len(theta)
    diag = np.asarray(obj.hessian_diagonal(
        jnp.asarray(theta, jnp.float64), batch, hyper), np.float64)
    h = 1e-4

    def v(t):
        return float(obj.value(jnp.asarray(t, jnp.float64), batch, hyper))

    v0 = v(theta)
    for i in range(d):
        e = np.zeros(d)
        e[i] = h
        fd = (v(theta + e) - 2.0 * v0 + v(theta - e)) / (h * h)
        np.testing.assert_allclose(diag[i], fd, rtol=5e-5, atol=1e-6)


def test_laplace_refuses_first_order_losses_typed():
    obj = GLMObjective(loss=SmoothedHingeLoss)
    with pytest.raises(ValueError, match="has no Hessian"):
        StreamedLaplace(obj, loader=None)
    coord = types.SimpleNamespace(objective=obj)
    with pytest.raises(ValueError, match="has no Hessian"):
        entity_variances_blocked(coord, np.zeros((1, 1)))


# ---------------------------------------------------------------------------
# fixed effect: streamed Laplace vs the dense ridge closed form
# ---------------------------------------------------------------------------


def _ridge_stream(n=256, d=12, lam=0.7, seed=113):
    from photon_tpu.data.streaming import (
        ChunkLoader,
        DenseSource,
        StreamConfig,
        ensure_aligned,
    )

    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.normal(size=(n, d)))
    x = ensure_aligned(np.ascontiguousarray(
        q * rng.uniform(0.5, 2.0, size=d)[None, :], np.float64))
    y = ensure_aligned(rng.normal(size=n).astype(np.float64))
    loader = ChunkLoader(DenseSource(x, y),
                         StreamConfig(chunk_rows=64, dtype=np.float64))
    return x, y, lam, loader


def test_streamed_laplace_matches_ridge_closed_form():
    """Squared loss at theta=0: Sigma = (X'X + lambda I)^-1, and the
    orthogonal design makes X'X exactly diagonal, so the diagonal
    Laplace IS the dense closed form to f64 roundoff."""
    x, _, lam, loader = _ridge_stream()
    d = x.shape[1]
    var = fixed_effect_variances_streamed(
        GLMObjective(loss=SquaredLoss), loader, np.zeros(d, np.float64),
        l2_weight=lam)
    closed = np.diag(np.linalg.inv(x.T @ x + lam * np.eye(d)))
    np.testing.assert_allclose(var, closed, rtol=1e-10)


def test_streamed_laplace_bitwise_run_to_run():
    x, _, lam, loader1 = _ridge_stream()
    _, _, _, loader2 = _ridge_stream()
    d = x.shape[1]
    obj = GLMObjective(loss=SquaredLoss)
    v1 = fixed_effect_variances_streamed(obj, loader1,
                                         np.zeros(d, np.float64),
                                         l2_weight=lam)
    v2 = fixed_effect_variances_streamed(obj, loader2,
                                         np.zeros(d, np.float64),
                                         l2_weight=lam)
    assert v1.tobytes() == v2.tobytes()


# ---------------------------------------------------------------------------
# random effects: blocked per-entity variances vs an exact oracle
# ---------------------------------------------------------------------------


def _re_fit(e_c=12, k_c=3, m_c=6, d_c=10, lam=1.0, seed=211, buckets=3):
    """One-feature-per-sample linear GLMix: X'X is diagonal per entity,
    so H_kk = sum x^2 exactly and the ridge solve is per-slot closed
    form. Each true coefficient is drawn from the L2 prior N(0, 1/lam)
    (``truth``), so the ridge posterior is exactly calibrated."""
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.game.coordinate import RandomEffectCoordinate
    from photon_tpu.game.dataset import (
        EntityVocabulary,
        FeatureShard,
        GameDataFrame,
    )
    from photon_tpu.game.random_effect import (
        RandomEffectDataConfiguration,
        build_random_effect_dataset,
    )
    from photon_tpu.optim.problem import GLMOptimizationConfiguration
    from photon_tpu.types import TaskType

    rng = np.random.default_rng(seed)
    ent_ids = [f"e{i:03d}" for i in range(e_c)]
    sq = {}                       # (entity, global col) -> sum x^2
    truth = {}                    # (entity, global col) -> w_true
    rows, ids, resp = [], [], []
    for ent in ent_ids:
        cols = np.sort(rng.choice(d_c, size=k_c, replace=False))
        for c in cols:
            w = rng.normal() / np.sqrt(lam)
            truth[(ent, int(c))] = w
            for _ in range(m_c):
                x = rng.normal()
                sq[(ent, int(c))] = sq.get((ent, int(c)), 0.0) + x * x
                rows.append((np.array([c], np.int32),
                             np.array([x], np.float64)))
                ids.append(ent)
                resp.append(x * w + rng.normal())
    n_s = len(rows)
    df = GameDataFrame(
        num_samples=n_s, response=np.asarray(resp, np.float64),
        feature_shards={"u": FeatureShard(rows, d_c)},
        offsets=np.zeros(n_s), weights=np.ones(n_s),
        id_tags={"userId": ids})
    vocab = EntityVocabulary()
    ds = build_random_effect_dataset(
        df, RandomEffectDataConfiguration("userId", "u",
                                          max_entity_buckets=buckets),
        vocab)
    coord = RandomEffectCoordinate(
        ds, n_s, "userId", "u", TaskType.LINEAR_REGRESSION,
        config=GLMOptimizationConfiguration(
            regularization=L2Regularization, regularization_weight=lam))
    rem = coord.update_model_blocked(None)
    return coord, rem, vocab, np.asarray(ds.projection), sq, lam, truth


def test_entity_variances_match_per_slot_oracle():
    coord, rem, vocab, proj, sq, lam, _ = _re_fit()
    var = entity_variances_blocked(coord, rem.coefficients)
    names = vocab.names("userId")
    assert var.shape[0] == len(names)
    checked = 0
    for r, name in enumerate(names):
        for k in range(proj.shape[1]):
            c = int(proj[r, k])
            if c < 0:
                continue
            want = 1.0 / (sq[(name, c)] + lam)
            np.testing.assert_allclose(var[r, k], want, rtol=1e-6)
            checked += 1
    assert checked > 0


def test_entity_variances_bitwise_and_prefetch_invariant():
    coord, rem, *_ = _re_fit()
    v1 = entity_variances_blocked(coord, rem.coefficients)
    v2 = entity_variances_blocked(coord, rem.coefficients)
    v3 = entity_variances_blocked(coord, rem.coefficients, prefetch=False)
    assert v1.tobytes() == v2.tobytes()
    assert v1.tobytes() == v3.tobytes()


def test_posterior_intervals_cover_the_truth_at_90pct():
    """Known-truth GLMix: fit, run the blocked variance pass, and the
    90% intervals ``coef +- z90 * sigma`` cover the true coefficients
    at an empirical rate in [0.85, 0.95]."""
    z90 = 1.6448536269514722           # two-sided 90% normal quantile
    coord, rem, vocab, proj, _, _, truth = _re_fit(
        e_c=16, k_c=3, m_c=6, d_c=12, buckets=4)
    coefs = np.asarray(rem.coefficients)
    var = entity_variances_blocked(coord, rem.coefficients)
    covered = total = 0
    for r, name in enumerate(vocab.names("userId")):
        for k in range(proj.shape[1]):
            c = int(proj[r, k])
            if c < 0 or var[r, k] <= 0:
                continue
            total += 1
            covered += (abs(float(coefs[r, k]) - truth[(name, c)])
                        <= z90 * float(np.sqrt(var[r, k])))
    assert total == 48
    assert 0.85 <= covered / total <= 0.95, (covered, total)


# ---------------------------------------------------------------------------
# cold store: the variance column's persistence contract
# ---------------------------------------------------------------------------


def _cold_fixture(tmp_path, with_var):
    from photon_tpu.io.cold_store import write_cold_store

    rng = np.random.default_rng(5)
    E, K = 6, 3
    coef = rng.normal(size=(E, K)).astype(np.float32)
    proj = np.sort(rng.integers(0, 9, size=(E, K)).astype(np.int32), axis=1)
    var = np.abs(rng.normal(size=(E, K))).astype(np.float32)
    ids = [f"e{i}" for i in range(E)]
    path = str(tmp_path / ("v4.cold" if with_var else "v2.cold"))
    write_cold_store(path, "cid", "userId", "u", coef, proj,
                     np.asarray(ids), updatable=True, capacity=E + 4,
                     variances=var if with_var else None)
    return path, ids, coef, proj, var


def test_cold_store_variance_roundtrip(tmp_path):
    from photon_tpu.io.cold_store import ColdStore

    path, ids, _, _, var = _cold_fixture(tmp_path, True)
    cs = ColdStore(path)
    assert cs.has_variances
    rows = [cs.entity_row(e) for e in ids]
    got = cs.read_var_rows(np.asarray(rows))
    assert got.astype(np.float32).tobytes() == var.tobytes()

    path2, _, _, _, _ = _cold_fixture(tmp_path, False)
    cs2 = ColdStore(path2)
    assert not cs2.has_variances


def test_cold_store_delta_variance_contract(tmp_path):
    from photon_tpu.io.cold_store import (
        ColdStore,
        apply_cold_store_delta,
        rollback_cold_store_delta,
    )

    path, ids, coef, proj, var = _cold_fixture(tmp_path, True)
    cs = ColdStore(path)
    r2 = cs.entity_row("e2")
    K = coef.shape[1]
    new_coef = np.full((1, K), 2.5, np.float32)
    new_var = np.full((1, K), 0.125, np.float32)

    # mean-only refresh on a v4 file: variance bytes must NOT move —
    # a mean refresh never silently zeroes uncertainty
    undo_mean = apply_cold_store_delta(
        path, update_rows=np.asarray([r2]), update_coef=new_coef,
        update_proj=proj[2:3], normalize=False)
    cs = ColdStore(path)
    assert np.asarray(cs.var[r2], np.float32).tobytes() == \
        var[2].tobytes()
    rollback_cold_store_delta(path, undo_mean)

    # full update + append with variance rows; undo restores bitwise
    undo = apply_cold_store_delta(
        path, update_rows=np.asarray([r2]), update_coef=new_coef,
        update_proj=proj[2:3], update_var=new_var,
        append_ids=["zz-new"], append_coef=new_coef,
        append_proj=proj[2:3], append_var=new_var, normalize=False)
    cs = ColdStore(path)
    assert np.asarray(cs.var[r2], np.float32).tobytes() == new_var.tobytes()
    ra = cs.entity_row("zz-new")
    assert ra is not None
    assert np.asarray(cs.var[ra], np.float32).tobytes() == new_var.tobytes()
    rollback_cold_store_delta(path, undo)
    cs = ColdStore(path)
    assert cs.entity_row("zz-new") is None
    assert np.asarray(cs.coef[r2], np.float32).tobytes() == \
        coef[2].tobytes()
    assert np.asarray(cs.var[r2], np.float32).tobytes() == var[2].tobytes()

    # appends WITHOUT variance rows land zeros (mean-served until a
    # variance-carrying republish)
    apply_cold_store_delta(
        path, append_ids=["zz-novar"], append_coef=new_coef,
        append_proj=proj[2:3], normalize=False)
    cs = ColdStore(path)
    rn = cs.entity_row("zz-novar")
    assert np.asarray(cs.var[rn], np.float32).tobytes() == \
        np.zeros((K,), np.float32).tobytes()


def test_cold_store_delta_var_on_varless_is_typed_error(tmp_path):
    from photon_tpu.io.cold_store import apply_cold_store_delta

    path, ids, coef, proj, var = _cold_fixture(tmp_path, False)
    with pytest.raises(ValueError):
        apply_cold_store_delta(
            path, update_rows=np.asarray([0]), update_coef=coef[:1],
            update_proj=proj[:1], update_var=var[:1], normalize=False)


# ---------------------------------------------------------------------------
# Avro: BayesianLinearModelAvro variance contract
# ---------------------------------------------------------------------------


def test_bayesian_avro_schema_conformance():
    """The schema IS the wire contract with the reference — field names,
    order, and the nullable variances union are pinned."""
    from photon_tpu.io.schemas import BAYESIAN_LINEAR_MODEL_AVRO, NS

    s = BAYESIAN_LINEAR_MODEL_AVRO
    assert s["name"] == "BayesianLinearModelAvro"
    assert s["namespace"] == NS
    assert [f["name"] for f in s["fields"]] == [
        "modelId", "modelClass", "means", "variances", "lossFunction"]
    var_field = s["fields"][3]
    assert var_field["type"][0] == "null"
    assert var_field["default"] is None
    arr = var_field["type"][1]
    assert arr["type"] == "array" and arr["items"] == "NameTermValueAvro"
    means_items = s["fields"][2]["type"]["items"]
    assert [f["name"] for f in means_items["fields"]] == \
        ["name", "term", "value"]


def test_bayesian_avro_variance_roundtrip(tmp_path):
    from photon_tpu.io.avro import read_avro, write_avro
    from photon_tpu.io.schemas import BAYESIAN_LINEAR_MODEL_AVRO

    recs = [
        {"modelId": "global",
         "modelClass": "com.linkedin.photon.ml.supervised"
                       ".classification.LogisticRegressionModel",
         "means": [{"name": "f0", "term": "", "value": 1.25},
                   {"name": "f1", "term": "t", "value": -0.5}],
         "variances": [{"name": "f0", "term": "", "value": 0.03125},
                       {"name": "f1", "term": "t", "value": 2.0}],
         "lossFunction": ""},
        {"modelId": "mean-only", "modelClass": None,
         "means": [{"name": "f0", "term": "", "value": 0.75}],
         "variances": None, "lossFunction": None},
    ]
    path = str(tmp_path / "bayes.avro")
    write_avro(path, BAYESIAN_LINEAR_MODEL_AVRO, recs)
    _, got = read_avro(path)
    assert got == recs


# ---------------------------------------------------------------------------
# serving: Thompson sampling determinism, typed cold start, refusals
# ---------------------------------------------------------------------------


def _bayes_model_dir(out_dir, with_var, d_g=8, d_u=6, n_users=4, k=3,
                     seed=41):
    from photon_tpu.game.dataset import EntityVocabulary
    from photon_tpu.game.model import (
        FixedEffectModel,
        GameModel,
        RandomEffectModel,
    )
    from photon_tpu.io.index_map import IndexMap, feature_key
    from photon_tpu.io.model_io import save_game_model
    from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
    from photon_tpu.types import TaskType

    rng = np.random.default_rng(seed)
    im_g = IndexMap.from_keys([feature_key("g", str(j)) for j in range(d_g)])
    im_u = IndexMap.from_keys([feature_key("u", str(j)) for j in range(d_u)])
    theta = rng.normal(size=d_g).astype(np.float32)
    fvar = (np.abs(rng.normal(size=d_g)) * 0.1).astype(np.float32)
    proj = np.full((n_users, k), -1, np.int32)
    coef = np.zeros((n_users, k), np.float32)
    rvar = np.zeros((n_users, k), np.float32)
    for e in range(n_users):
        proj[e] = np.sort(rng.choice(d_u, size=k, replace=False))
        coef[e] = rng.normal(size=k)
        rvar[e] = np.abs(rng.normal(size=k)) * 0.05
    users = [f"user{e}" for e in range(n_users)]
    vocab = EntityVocabulary()
    vocab.build("userId", users)
    model = GameModel({
        "fixed": FixedEffectModel(
            GeneralizedLinearModel(
                Coefficients(jnp.asarray(theta),
                             jnp.asarray(fvar) if with_var else None),
                TaskType.LOGISTIC_REGRESSION), "g"),
        "per_user": RandomEffectModel(
            jnp.asarray(coef), "userId", "u", TaskType.LOGISTIC_REGRESSION,
            variances=jnp.asarray(rvar) if with_var else None),
    })
    save_game_model(out_dir, model, {"g": im_g, "u": im_u}, vocab=vocab,
                    projections={"per_user": proj}, sparsity_threshold=0.0)
    return users


def _bayes_requests(users, n=32, d_g=8, d_u=6, seed=307):
    from photon_tpu.serving.types import ScoreRequest

    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        gf = [("g", str(j), float(rng.normal())) for j in range(d_g)]
        uf = [("u", str(j), float(rng.normal())) for j in range(d_u)]
        ent = (f"cold{i}" if i % 5 == 0
               else users[int(rng.integers(0, len(users)))])
        reqs.append(ScoreRequest(f"r{i:04d}", {"g": gf, "u": uf},
                                 {"userId": ent}))
    return reqs


def test_load_for_serving_carries_variances(tmp_path):
    from photon_tpu.io.model_io import load_for_serving

    _bayes_model_dir(str(tmp_path / "var"), True)
    _bayes_model_dir(str(tmp_path / "mean"), False)
    sv = load_for_serving(str(tmp_path / "var"))
    assert sv.fixed[0].variances is not None
    assert np.isfinite(sv.fixed[0].variances).all()
    assert sv.random[0].has_variances
    assert sv.random[0].variances is not None
    sm = load_for_serving(str(tmp_path / "mean"))
    assert sm.fixed[0].variances is None
    assert not sm.random[0].has_variances


def test_thompson_serving_bitwise_and_typed_cold_start(tmp_path):
    import random as _random

    from photon_tpu.serving.engine import ServingEngine
    from photon_tpu.serving.types import FallbackReason, ServingConfig
    from photon_tpu.utils import compile_cache

    users = _bayes_model_dir(str(tmp_path / "var"), True)
    eng = ServingEngine.from_model_dir(
        str(tmp_path / "var"),
        config=ServingConfig(max_batch=8, max_wait_s=0.0,
                             thompson_serving=True, thompson_seed=77))
    info = eng.warmup()
    assert eng.model.thompson_enabled
    assert "thompson" in info["modes"]

    reqs = _bayes_requests(users)
    first = {r.uid: r.score for r in eng.serve(reqs)}
    shuffled = list(reqs)
    _random.Random(19).shuffle(shuffled)
    steady0 = compile_cache.compile_counts().get("steady_state", 0)
    resp2 = eng.serve(shuffled)
    steady1 = compile_cache.compile_counts().get("steady_state", 0)
    # replayed traffic in a different arrival order: bitwise-identical
    # scores (seeds derive from request identity, not arrival slot)
    assert {r.uid: r.score for r in resp2} == first
    assert steady1 == steady0

    for req, resp in zip(shuffled, resp2):
        reasons = {f.reason for f in resp.fallbacks}
        if req.entity_ids["userId"].startswith("cold"):
            assert FallbackReason.EXPLORING_COLD_START in reasons
            assert FallbackReason.UNKNOWN_ENTITY not in reasons
        else:
            assert FallbackReason.EXPLORING_COLD_START not in reasons
        assert np.isfinite(resp.score)


def test_thompson_flag_on_mean_only_model_is_byte_identical(tmp_path):
    from photon_tpu.serving.engine import ServingEngine
    from photon_tpu.serving.types import ServingConfig

    users = _bayes_model_dir(str(tmp_path / "mean"), False)
    reqs = _bayes_requests(users)
    plain = ServingEngine.from_model_dir(str(tmp_path / "mean"))
    plain.warmup()
    base = [r.score for r in plain.serve(reqs)]
    flagged = ServingEngine.from_model_dir(
        str(tmp_path / "mean"),
        config=ServingConfig(max_batch=8, max_wait_s=0.0,
                             thompson_serving=True, thompson_seed=77))
    flagged.warmup()
    assert not flagged.model.thompson_enabled
    assert [r.score for r in flagged.serve(reqs)] == base


def test_thompson_two_tier_typed_refusal(tmp_path):
    from photon_tpu.serving.engine import ServingEngine
    from photon_tpu.serving.types import CoeffStoreConfig, ServingConfig

    _bayes_model_dir(str(tmp_path / "var"), True)
    with pytest.raises(ValueError, match="full-resident"):
        ServingEngine.from_model_dir(
            str(tmp_path / "var"),
            config=ServingConfig(
                max_batch=8, max_wait_s=0.0, thompson_serving=True,
                coeff_store=CoeffStoreConfig(hot_capacity=2,
                                             transfer_batch=1)))


# ---------------------------------------------------------------------------
# nearline: variance rows republish coherently with means
# ---------------------------------------------------------------------------


def test_nearline_variance_republish_and_rollback(tmp_path):
    from photon_tpu.io.cold_store import ColdStore, cold_store_path
    from photon_tpu.nearline.delta_trainer import DeltaTrainer
    from photon_tpu.nearline.publisher import DeltaPublisher
    from photon_tpu.serving.engine import ServingEngine
    from photon_tpu.serving.types import ServingConfig

    d_g, d_u = 8, 6
    mdir = str(tmp_path / "model")
    _bayes_model_dir(mdir, True, d_g=d_g, d_u=d_u, seed=42)
    eng = ServingEngine.from_model_dir(
        mdir, config=ServingConfig(max_batch=8, max_wait_s=0.0,
                                   thompson_serving=True, thompson_seed=5,
                                   append_reserve=4))
    eng.warmup()
    rs = eng.model.random[0]
    assert rs.var_coef is not None

    r = np.random.default_rng(3)
    events = []
    for i in range(12):
        ent = "user0" if i % 2 == 0 else "newuser"
        events.append({
            "features": {
                "g": [("g", str(j), float(r.normal())) for j in range(d_g)],
                "u": [("u", str(j), float(r.normal())) for j in range(3)],
            },
            "entities": {"userId": ent},
            "response": float(r.integers(0, 2)),
            "offset": 0.0, "weight": 1.0, "ts": float(i),
        })
    trainer = DeltaTrainer(eng, model_dir=mdir)
    res = trainer.train(events)
    cd = res.coordinates["per_user"]
    # every delta row carries a finite non-negative variance row
    assert set(cd.var_rows) == set(cd.rows)
    for v in cd.var_rows.values():
        assert np.isfinite(v).all() and (v >= 0).all()

    pub = DeltaPublisher(eng, model_dir=mdir)
    prior_var = np.asarray(rs.var_coef[rs.entity_rows["user0"]],
                           np.float32).copy()
    out = pub.publish(res, label="r1")
    assert out.accepted, out
    assert out.gates.get("variance") == "pass"
    assert out.rows_updated == 1 and out.rows_appended == 1

    new_var = np.asarray(rs.var_coef[rs.entity_rows["user0"]], np.float32)
    assert new_var.tobytes() != prior_var.tobytes()
    # appended entity explores with its fresh posterior, not zeros
    nrow = np.asarray(rs.var_coef[rs.entity_rows["newuser"]], np.float32)
    assert (nrow > 0).any()
    # pad writes are idempotent: the unknown row still holds the prior
    urow = np.asarray(rs.var_coef[rs.unknown_row], np.float32)
    assert np.allclose(urow, eng.model.prior_variance)
    # disk mirror carries the same bytes as the resident table
    cs = ColdStore(cold_store_path(mdir, "per_user"))
    r0 = cs.entity_row("user0")
    assert np.asarray(cs.var[r0], np.float32).tobytes() == \
        new_var.tobytes()
    del cs

    assert pub.rollback_last("test")
    back = np.asarray(rs.var_coef[rs.entity_rows["user0"]], np.float32)
    assert back.tobytes() == prior_var.tobytes()
    cs = ColdStore(cold_store_path(mdir, "per_user"))
    assert cs.entity_row("newuser") is None
    assert np.asarray(cs.var[r0], np.float32).tobytes() == \
        prior_var.tobytes()
