"""GAME end-to-end: GLMix (fixed + per-entity random effect) training via
coordinate descent on synthetic data — the role of GameEstimatorIntegTest /
GameTrainingDriverIntegTest's fixed-and-random-effect cases."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.estimators.game_estimator import (
    CoordinateConfiguration,
    FixedEffectDataConfiguration,
    GameEstimator,
    GameTransformer,
)
from photon_tpu.evaluation.evaluators import EvaluatorType
from photon_tpu.function.objective import L2Regularization
from photon_tpu.game.dataset import FeatureShard, GameDataFrame
from photon_tpu.game.random_effect import RandomEffectDataConfiguration
from photon_tpu.optim.problem import GLMOptimizationConfiguration, OptimizerConfig
from photon_tpu.types import TaskType


def make_glmix_frame(rng, n=3000, d_global=8, n_users=40, d_user=4, seed_frames=1):
    """Global fixed effect + per-user random effect, logistic response.
    Returns (train_frame, val_frame, params)."""
    w_global = rng.normal(size=d_global)
    w_users = rng.normal(size=(n_users, d_user)) * 1.5

    def build(n):
        Xg = rng.normal(size=(n, d_global))
        Xu = rng.normal(size=(n, d_user))
        users = rng.integers(0, n_users, size=n)
        logits = Xg @ w_global + np.einsum("nd,nd->n", Xu, w_users[users])
        y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logits))).astype(np.float64)
        rows_g = [(np.nonzero(x)[0].astype(np.int32), x[np.nonzero(x)[0]]) for x in Xg]
        rows_u = [(np.arange(d_user, dtype=np.int32), x) for x in Xu]
        return GameDataFrame(
            num_samples=n,
            response=y,
            feature_shards={
                "global": FeatureShard(rows_g, d_global),
                "user_feats": FeatureShard(rows_u, d_user),
            },
            id_tags={"userId": [f"u{u}" for u in users]},
        )

    return build(n), build(n // 2), (w_global, w_users)


@pytest.fixture(scope="module")
def glmix():
    rng = np.random.default_rng(7)
    return make_glmix_frame(rng)


def glmix_estimator(num_iterations=2, re_upper_bound=None):
    opt = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=60, tolerance=1e-9),
        regularization=L2Regularization,
        regularization_weight=1.0,
    )
    return GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configs={
            "fixed": CoordinateConfiguration(
                FixedEffectDataConfiguration("global"), opt),
            "per-user": CoordinateConfiguration(
                RandomEffectDataConfiguration(
                    "userId", "user_feats",
                    active_data_upper_bound=re_upper_bound), opt),
        },
        update_sequence=["fixed", "per-user"],
        num_iterations=num_iterations,
        validation_evaluators=[EvaluatorType.AUC, EvaluatorType.LOGISTIC_LOSS],
        dtype=jnp.float64,
    )


def test_glmix_beats_fixed_only(glmix):
    train, val, _ = glmix

    # fixed-effect-only baseline
    fixed_only = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configs={
            "fixed": glmix_estimator().coordinate_configs["fixed"]},
        num_iterations=1,
        validation_evaluators=[EvaluatorType.AUC],
        dtype=jnp.float64,
    )
    auc_fixed = fixed_only.fit(train, val)[0].evaluation["AUC"]

    est = glmix_estimator()
    result = est.fit(train, val)[0]
    auc_game = result.evaluation["AUC"]

    assert auc_fixed > 0.6  # sanity: global signal learned
    assert auc_game > auc_fixed + 0.05, (auc_game, auc_fixed)
    assert auc_game > 0.75


def test_glmix_cd_iterations_monotone_on_train(glmix):
    """Training-objective sanity: later full sweeps shouldn't get worse on
    validation by much; history exists per coordinate update."""
    train, val, _ = glmix
    est = glmix_estimator(num_iterations=3)
    result = est.fit(train, val)[0]
    hist = result.descent.validation_history
    assert len(hist) == 3 * 2  # iterations x coordinates
    first_auc = hist[0]["AUC"]
    last_auc = hist[-1]["AUC"]
    assert last_auc >= first_auc - 0.01


def test_active_data_upper_bound_and_passive_scoring(glmix):
    train, val, _ = glmix
    est = glmix_estimator(num_iterations=2, re_upper_bound=30)
    result = est.fit(train, val)[0]
    # capping active data still trains a useful model
    assert result.evaluation["AUC"] > 0.72
    ds = est._re_datasets["per-user"]
    assert ds.max_samples <= 30
    # passive samples exist (entities above the cap)
    assert int(np.sum(np.asarray(ds.passive_rows) < train.num_samples)) > 0


def test_partial_retrain_locked_coordinate(glmix):
    """Reference: partial retraining with locked coordinates
    (GameTrainingDriverIntegTest.compareModelEvaluation)."""
    train, val, _ = glmix
    est = glmix_estimator(num_iterations=2)
    full = est.fit(train, val)[0]

    est2 = glmix_estimator(num_iterations=2)
    est2.locked = frozenset(["fixed"])
    retrained = est2.fit(train, val, initial_model=full.model)[0]
    # locked fixed effect untouched
    np.testing.assert_array_equal(
        np.asarray(retrained.model["fixed"].model.coefficients.means),
        np.asarray(full.model["fixed"].model.coefficients.means))
    # retrained model stays within AUC tolerance of the full model
    assert abs(retrained.evaluation["AUC"] - full.evaluation["AUC"]) < 0.02


def test_transformer_scores_match_validation(glmix):
    train, val, _ = glmix
    est = glmix_estimator()
    result = est.fit(train, val)[0]
    tr = GameTransformer(result.model, est)
    metrics = tr.evaluate(val)
    np.testing.assert_allclose(metrics["AUC"], result.evaluation["AUC"], rtol=1e-12)


def test_config_sweep_warm_start(glmix):
    train, val, _ = glmix
    est = glmix_estimator(num_iterations=1)
    results = est.fit(train, val,
                      configurations=[{"fixed": 100.0, "per-user": 100.0},
                                      {"fixed": 1.0, "per-user": 1.0}])
    assert len(results) == 2
    # lighter regularization should help on this well-specified problem
    assert results[1].evaluation["AUC"] >= results[0].evaluation["AUC"] - 0.01
    assert results[0].config["fixed"].optimization.regularization_weight == 100.0
    assert results[1].config["fixed"].optimization.regularization_weight == 1.0


def test_random_effect_ingest_scales_with_bucketing():
    """VERDICT round-1 item 5: vectorized ingest (no per-sample Python
    loops) with power-law entities must run in seconds and keep sample-slot
    padding waste under 2x via size bucketing."""
    import time

    from photon_tpu.game.dataset import EntityVocabulary, FeatureShard, GameDataFrame
    from photon_tpu.game.random_effect import (
        RandomEffectDataConfiguration,
        build_random_effect_dataset,
    )

    rng = np.random.default_rng(0)
    n, E_target, d_user, nnz = 200_000, 20_000, 12, 4
    ent = rng.zipf(1.3, size=n) % E_target
    rows = [(rng.integers(0, d_user, size=nnz).astype(np.int32),
             rng.normal(size=nnz)) for _ in range(n)]
    df = GameDataFrame(
        num_samples=n, response=rng.random(n),
        feature_shards={"u": FeatureShard(rows, d_user)},
        id_tags={"userId": [str(e) for e in ent]})
    vocab = EntityVocabulary()
    cfg = RandomEffectDataConfiguration("userId", "u",
                                        active_data_upper_bound=1000)
    t0 = time.perf_counter()
    ds = build_random_effect_dataset(df, cfg, vocab)
    elapsed = time.perf_counter() - t0
    assert elapsed < 30, f"ingest too slow: {elapsed:.1f}s"
    assert len(ds.blocks) > 3, "expected multiple size buckets"
    waste = ds.padding_waste()
    assert waste < 2.0, f"padding waste {waste:.2f}x >= 2x"
    # every sample lands exactly once (active or passive)
    placed = sum(int(np.sum(np.asarray(b.sample_rows) < n)) for b in ds.blocks)
    placed += int(np.sum(np.asarray(ds.passive_rows) < n))
    assert placed == n


def test_entity_bucket_cap_bounds_compiles_and_preserves_results():
    """A long-tailed (power-law) entity distribution produces many pow-2
    size buckets; max_entity_buckets coarsens them to bound XLA compile
    count. Per-entity solves are independent, so the capped grouping must
    produce EXACTLY the same models (VERDICT r2 weak #8)."""
    import numpy as np

    from photon_tpu.game.coordinate import RandomEffectCoordinate
    from photon_tpu.game.dataset import EntityVocabulary, FeatureShard, GameDataFrame
    from photon_tpu.game.random_effect import (
        RandomEffectDataConfiguration,
        build_random_effect_dataset,
    )
    from photon_tpu.optim.problem import GLMOptimizationConfiguration, OptimizerConfig
    from photon_tpu.types import TaskType

    rng = np.random.default_rng(17)
    n, d, ents = 6000, 4, 800
    p = 1.0 / np.arange(1, ents + 1) ** 1.3
    ent = rng.choice(ents, size=n, p=p / p.sum())
    idx = np.arange(d, dtype=np.int32)
    rows = [(idx, rng.normal(size=d)) for _ in range(n)]
    y = (rng.random(n) > 0.5).astype(np.float64)
    df = GameDataFrame(num_samples=n, response=y,
                       feature_shards={"u": FeatureShard(rows, d)},
                       id_tags={"userId": [str(e) for e in ent]})

    def fit(max_buckets):
        cfg = RandomEffectDataConfiguration(
            "userId", "u", max_entity_buckets=max_buckets)
        vocab = EntityVocabulary()
        ds = build_random_effect_dataset(df, cfg, vocab, dtype=np.float64)
        coord = RandomEffectCoordinate(
            ds, n, "userId", "u", TaskType.LOGISTIC_REGRESSION,
            GLMOptimizationConfiguration(
                optimizer=OptimizerConfig(max_iterations=25, tolerance=1e-8)))
        return ds, coord.update_model(None, None)

    ds_raw, m_raw = fit(max_buckets=None)
    ds_cap, m_cap = fit(max_buckets=6)
    assert len(ds_raw.blocks) > 6          # power law really is long-tailed
    assert len(ds_cap.blocks) <= 6
    # more padding, same math (different bucket layouts may route blocks
    # through the dense-local vs gather/scatter kernels, so agreement is
    # at f64 reduction-order level, not bitwise)
    assert ds_cap.padding_waste() >= ds_raw.padding_waste()
    np.testing.assert_allclose(np.asarray(m_cap.coefficients),
                               np.asarray(m_raw.coefficients),
                               rtol=1e-7, atol=1e-10)


# -- the padded fill against the loop it replaced (PR 39) ----------------------

def _reference_blocks(df, config, vocab, dtype=np.float32,
                      scores_offsets=None):
    """``build_random_effect_dataset`` as it stood before PR 39, on the
    host: its loop over buckets VERBATIM (a ``row_of_entity``, a
    ``pos_of_sample`` and an ``nz_mask`` over EVERY nonzero of the shard,
    rebuilt a bucket), behind the grouping it read its arrays from, less
    the ``Timed`` phases and the placement. What the one-visit fill is held
    to, array for array."""
    from photon_tpu.game.random_effect import (
        _bucket_of,
        _csr_of,
        _maybe_random_project,
        _pearson_scores_vectorized,
        _splitmix64,
        flat_source_map,
    )

    re_type = config.random_effect_type
    shard = df.feature_shards[config.feature_shard_id]
    # sparse row lists, columnar CsrRows, and dense [n, d] matrices all
    # funnel through _csr_of into the same columnar pipeline
    shard = _maybe_random_project(shard, config)
    n = df.num_samples
    D = shard.dim

    entity_idx = vocab.build(re_type, df.id_tags[re_type]).astype(np.int64)
    E = vocab.size(re_type)
    base_offsets = np.zeros(n) if df.offsets is None else np.asarray(df.offsets, np.float64)
    if scores_offsets is not None:
        base_offsets = base_offsets + np.asarray(scores_offsets, np.float64)
    weights = np.ones(n) if df.weights is None else np.asarray(df.weights, np.float64)
    resp = np.asarray(df.response, np.float64)

    indptr, cols, vals = _csr_of(shard.rows)
    nnz = np.diff(indptr)

    # -- deterministic ordering within entities + active/passive split -------
    counts = np.bincount(entity_idx, minlength=E)
    keys = _splitmix64(np.arange(n, dtype=np.uint64))
    order = np.lexsort((keys, entity_idx))           # by (entity, hash)
    starts = np.concatenate([[0], np.cumsum(counts)])
    pos = np.arange(n) - np.repeat(starts[:-1], counts)  # rank within entity

    e_sorted = entity_idx[order]
    active_sorted = np.ones(n, bool)
    if config.active_data_lower_bound is not None:
        active_sorted &= counts[e_sorted] >= config.active_data_lower_bound
    if config.active_data_upper_bound is not None:
        active_sorted &= pos < config.active_data_upper_bound
    passive_sorted = ~active_sorted
    if config.active_data_upper_bound is not None and not config.keep_passive_data:
        # over-cap samples are dropped entirely; below-lower-bound samples
        # stay passive (they are scored, just never trained on)
        over_cap = pos >= config.active_data_upper_bound
        if config.active_data_lower_bound is not None:
            over_cap &= counts[e_sorted] >= config.active_data_lower_bound
        passive_sorted &= ~over_cap

    active = np.zeros(n, bool)
    active[order] = active_sorted
    passive = np.zeros(n, bool)
    passive[order] = passive_sorted
    act_counts = np.bincount(entity_idx[active], minlength=E)

    # -- observed (entity, feature) pairs over ACTIVE data -------------------
    s_nz = np.repeat(np.arange(n), nnz)              # sample id per nonzero
    keep_nz = active[s_nz]
    e_nz = entity_idx[s_nz]
    pair = e_nz * D + cols                            # int64 composite key
    uniq = np.unique(pair[keep_nz]) if keep_nz.any() else np.zeros(0, np.int64)

    # -- optional Pearson feature selection (reference: LocalDataset:122) ----
    if config.features_to_samples_ratio is not None and len(uniq):
        ratio = config.features_to_samples_ratio
        k_per_entity = np.maximum((ratio * act_counts).astype(np.int64), 1)
        scores = _pearson_scores_vectorized(
            uniq, np.searchsorted(uniq, pair[keep_nz]), vals[keep_nz],
            resp[s_nz[keep_nz]], entity_idx, resp, weights, active, E, D)
        u_e = uniq // D
        sel_order = np.lexsort((-scores, u_e))
        u_starts = np.searchsorted(u_e[sel_order], np.arange(E))
        sel_pos = np.arange(len(uniq)) - u_starts[u_e[sel_order]]
        need_cap = k_per_entity[u_e[sel_order]]
        keep_pair = np.zeros(len(uniq), bool)
        keep_pair[sel_order[sel_pos < need_cap]] = True
        # entities whose feature count is within bound keep everything
        feat_counts = np.bincount(u_e, minlength=E)
        within = feat_counts[u_e] <= np.maximum(
            (ratio * act_counts[u_e]).astype(np.int64), 1)
        keep_pair |= within
        uniq = uniq[keep_pair]

    # -- projection table ----------------------------------------------------
    u_e = uniq // D
    u_f = uniq % D
    d_loc_per_entity = np.bincount(u_e, minlength=E) if len(uniq) else np.zeros(E, np.int64)
    D_loc = max(int(d_loc_per_entity.max()) if E else 1, 1)
    u_starts = np.searchsorted(u_e, np.arange(E + 1))
    slot_of_pair = np.arange(len(uniq)) - u_starts[u_e]
    projection = np.full((E, D_loc), -1, np.int32)
    if len(uniq):
        projection[u_e, slot_of_pair] = u_f.astype(np.int32)

    # -- per-nonzero local slots (kept nonzeros only) ------------------------
    rank = np.searchsorted(uniq, pair) if len(uniq) else np.zeros(len(pair), np.int64)
    rank = np.minimum(rank, max(len(uniq) - 1, 0))
    kept_nz_mask = np.zeros(len(pair), bool)
    if len(uniq):
        kept_nz_mask = uniq[rank] == pair
    slot_nz = slot_of_pair[rank] if len(uniq) else np.zeros(len(pair), np.int64)

    # position of each kept nonzero within its sample
    def _slot_positions(mask: np.ndarray) -> np.ndarray:
        if not len(pair):
            return np.zeros(0, np.int64)
        kept_i = mask.astype(np.int64)
        c = np.cumsum(kept_i)
        excl = c - kept_i
        # indptr may equal total_nnz for trailing empty rows; those repeat
        # zero times, so clamp the index to keep the gather in range
        base = np.repeat(excl[np.minimum(indptr[:-1], len(excl) - 1)], nnz)
        return excl - base

    # -- bucketed active blocks ---------------------------------------------
    has_active = act_counts > 0
    bucket_id = np.where(has_active, _bucket_of(act_counts), -1)
    uniq_buckets = np.unique(bucket_id[bucket_id >= 0])
    cap = config.max_entity_buckets
    if cap and len(uniq_buckets) > cap:
        # coarsen: merge adjacent pow-2 buckets into at most `cap` groups
        # (each group pads to its largest member's S_b) — bounded compile
        # count at the cost of extra padding, both reported below
        groups = np.array_split(uniq_buckets, cap)
        lut = np.arange(int(uniq_buckets.max()) + 1)
        for g in groups:
            lut[g] = g[-1]
        bucket_id = np.where(bucket_id >= 0, lut[np.maximum(bucket_id, 0)], -1)
    blocks = []
    block_rows = []                       # host sample_rows a bucket

    # active samples sorted by (entity, hash) and within cap
    act_idx_sorted = order[active_sorted]             # flat rows, grouped
    act_pos = pos[active_sorted]                      # rank within entity
    act_entity = entity_idx[act_idx_sorted]

    k_nz_pos_all = _slot_positions(kept_nz_mask & active[s_nz])

    for b in np.unique(bucket_id[bucket_id >= 0]):
        ents = np.flatnonzero(bucket_id == b)         # global entity rows
        E_b = len(ents)
        S_b = int(act_counts[ents].max())
        # block row per global entity
        row_of_entity = np.full(E, -1, np.int64)
        row_of_entity[ents] = np.arange(E_b)

        in_b = row_of_entity[act_entity] >= 0
        rows_flat = act_idx_sorted[in_b]              # flat sample rows
        r_idx = row_of_entity[act_entity[in_b]]
        c_idx = act_pos[in_b]

        labels_b = np.zeros((E_b, S_b), dtype)
        offsets_b = np.zeros((E_b, S_b), dtype)
        weights_b = np.zeros((E_b, S_b), dtype)
        rows_b = np.full((E_b, S_b), n, np.int32)
        labels_b[r_idx, c_idx] = resp[rows_flat]
        offsets_b[r_idx, c_idx] = base_offsets[rows_flat]
        weights_b[r_idx, c_idx] = weights[rows_flat]
        rows_b[r_idx, c_idx] = rows_flat
        block_rows.append(rows_b)

        # ELL features: nonzeros of this bucket's active samples
        nz_mask = kept_nz_mask & active[s_nz] & (row_of_entity[e_nz] >= 0)
        nz_sample = s_nz[nz_mask]
        nz_r = row_of_entity[e_nz[nz_mask]]
        # column of the sample within the block
        pos_of_sample = np.full(n, -1, np.int64)
        pos_of_sample[act_idx_sorted[in_b]] = c_idx
        nz_c = pos_of_sample[nz_sample]
        nz_k = k_nz_pos_all[nz_mask]
        K_b = max(int(nz_k.max()) + 1 if len(nz_k) else 1, 1)

        f_idx = np.zeros((E_b, S_b, K_b), np.int32)
        f_val = np.zeros((E_b, S_b, K_b), dtype)
        f_idx[nz_r, nz_c, nz_k] = slot_nz[nz_mask].astype(np.int32)
        f_val[nz_r, nz_c, nz_k] = vals[nz_mask]

        blocks.append(dict(
            indices=f_idx, values=f_val, labels=labels_b, offsets=offsets_b,
            weights=weights_b, sample_rows=rows_b,
            entity_rows=ents.astype(np.int32)))

    # -- passive block (projected through each entity's local map) -----------
    pas_rows = np.flatnonzero(passive)
    P = max(len(pas_rows), 1)
    pas_nz_mask = kept_nz_mask & passive[s_nz]
    pas_k = _slot_positions(pas_nz_mask)
    K_p = max(int(pas_k[pas_nz_mask].max()) + 1 if pas_nz_mask.any() else 1, 1)
    p_idx = np.zeros((P, K_p), np.int32)
    p_val = np.zeros((P, K_p), dtype)
    p_entity = np.full(P, E, np.int32)
    p_rows = np.full(P, n, np.int32)
    if len(pas_rows):
        row_rank = np.full(n, -1, np.int64)
        row_rank[pas_rows] = np.arange(len(pas_rows))
        p_entity[: len(pas_rows)] = entity_idx[pas_rows]
        p_rows[: len(pas_rows)] = pas_rows
        sel = pas_nz_mask
        p_idx[row_rank[s_nz[sel]], pas_k[sel]] = slot_nz[sel].astype(np.int32)
        p_val[row_rank[s_nz[sel]], pas_k[sel]] = vals[sel]
    flat_source = flat_source_map(block_rows, p_rows, n)
    return dict(blocks=blocks, passive_indices=p_idx, passive_values=p_val,
                passive_entity=p_entity, passive_rows=p_rows,
                projection=projection, flat_source=flat_source)


def _fill_rows(rng, n, d):
    """Sparse rows of 0 to ``d`` nonzeros; the last three rows and a few
    inside are empty."""
    rows = []
    for i in range(n):
        k = 0 if i >= n - 3 or i % 97 == 0 else int(rng.integers(0, d + 1))
        cols = np.sort(rng.choice(d, size=k, replace=False)).astype(np.int32)
        rows.append((cols, rng.normal(size=k)))
    return rows


def _fill_case(name):
    """(frame, configuration keywords, builder keywords, vocabulary
    factory) of one case of the fill's parity test."""
    from photon_tpu.game.dataset import CsrRows, EntityVocabulary

    rng = np.random.default_rng(sum(map(ord, name)))
    n, d, ents = 3000, 6, 200
    p = 1.0 / np.arange(1, ents + 1) ** 1.3           # a skewed histogram
    ent = rng.choice(ents, size=n, p=p / p.sum())
    shard = FeatureShard(_fill_rows(rng, n, d), d)
    config, build, frame, vocab = {}, {}, {}, EntityVocabulary
    if name == "dense":
        x = rng.normal(size=(n, d))
        x[rng.random((n, d)) < 0.3] = 0.0
        shard = FeatureShard(x, d)
    elif name == "csr":
        rows = _fill_rows(rng, n, d)
        shard = FeatureShard(CsrRows(
            np.concatenate([[0], np.cumsum([len(c) for c, _ in rows])]),
            np.concatenate([c for c, _ in rows]),
            np.concatenate([v for _, v in rows])), d)
    elif name == "repeated_columns":
        rows = [(np.array([0, 2, 2, 5], np.int32), rng.normal(size=4))
                for _ in range(n)]
        shard = FeatureShard(rows, d)
    elif name == "upper_bound_keeps_passive":
        config = dict(active_data_upper_bound=25)
    elif name == "upper_bound_drops_the_overflow":
        config = dict(active_data_upper_bound=25, keep_passive_data=False)
    elif name == "lower_bound":
        config = dict(active_data_lower_bound=5)
    elif name == "both_bounds_drop_the_overflow":
        config = dict(active_data_lower_bound=5, active_data_upper_bound=25,
                      keep_passive_data=False)
    elif name == "pearson_selection":
        config = dict(features_to_samples_ratio=0.25)
    elif name.startswith("buckets_"):
        # sixteen power-of-two sizes: entity k has 2^k rows
        config = dict(max_entity_buckets=int(name.split("_")[1]))
        ent = np.repeat(np.arange(16), 2 ** np.arange(16))[
            rng.permutation(2 ** 16 - 1)]
        n, d = len(ent), 3
        x = rng.normal(size=(n, d))
        x[rng.random((n, d)) < 0.3] = 0.0
        shard = FeatureShard(x, d)
    elif name == "uncapped_ladder":
        config = dict(max_entity_buckets=None)
    elif name == "entity_without_active_row":
        config = dict(active_data_lower_bound=3)

        def vocab():
            # a known entity that this frame has no row of, ahead of the
            # frame's own: global entity row 0 is in no bucket
            v = EntityVocabulary()
            v.build("userId", ["ghost"])
            return v
    elif name == "scores_offsets":
        build = dict(scores_offsets=rng.normal(size=n))
        frame = dict(offsets=rng.normal(size=n), weights=rng.random(n) + 0.5)
    elif name == "random_projection":
        config = dict(projector_type="RANDOM", projected_dimension=3)
    elif name == "float64":
        build = dict(dtype=np.float64)
    else:
        assert name == "ragged_rows_empty_trailing", name
    df = GameDataFrame(
        num_samples=n, response=rng.random(n), feature_shards={"u": shard},
        id_tags={"userId": [str(e) for e in ent]}, **frame)
    return df, config, build, vocab


@pytest.mark.parametrize("case", [
    "dense", "ragged_rows_empty_trailing", "csr", "repeated_columns",
    "upper_bound_keeps_passive", "upper_bound_drops_the_overflow",
    "lower_bound", "both_bounds_drop_the_overflow", "pearson_selection",
    "buckets_1", "buckets_4", "buckets_16", "uncapped_ladder",
    "entity_without_active_row", "scores_offsets", "random_projection",
    "float64"])
@pytest.mark.parametrize("route", ["table", "sort"])
def test_the_padded_fill_is_the_per_bucket_loops_array_for_array(route, case):
    """Every array of the dataset, its shape and its dtype, is what the
    loop that rescanned the shard once a bucket built, whichever way the
    pairs find their slots (a table over the E x D keys or the sorted
    keys, searched): the same blocks in the same order reach the same
    cached programs."""
    from photon_tpu.game.random_effect import _build_random_effect_dataset
    from photon_tpu.obs.metrics import registry

    df, config, build, vocab = _fill_case(case)
    cfg = RandomEffectDataConfiguration("userId", "u", **config)
    want = _reference_blocks(df, cfg, vocab(), **build)
    key = f'ingest.pair_route{{coordinate="userId",path="{route}"}}'
    before = registry.snapshot()["counters"].get(key, 0)
    ds = _build_random_effect_dataset(df, cfg, vocab(), **build,
                                      route=lambda *_: route)
    assert registry.snapshot()["counters"][key] - before == 1

    def same(got, wanted, what):
        got = np.asarray(got)
        assert got.dtype == wanted.dtype and got.shape == wanted.shape, what
        np.testing.assert_array_equal(got, wanted, err_msg=what)

    assert len(ds.blocks) == len(want["blocks"])
    if case.startswith("buckets_"):
        assert len(ds.blocks) == cfg.max_entity_buckets
    for i, (block, ref) in enumerate(zip(ds.blocks, want["blocks"])):
        same(block.features.indices, ref["indices"], f"indices {i}")
        same(block.features.values, ref["values"], f"values {i}")
        for field in ("labels", "offsets", "weights", "sample_rows",
                      "entity_rows"):
            same(getattr(block, field), ref[field], f"{field} {i}")
    same(ds.passive_features.indices, want["passive_indices"], "passive")
    same(ds.passive_features.values, want["passive_values"], "passive")
    same(ds.passive_entity, want["passive_entity"], "passive_entity")
    same(ds.passive_rows, want["passive_rows"], "passive_rows")
    same(ds.projection, want["projection"], "projection")
    same(ds.flat_source, want["flat_source"], "flat_source")
    if case == "entity_without_active_row":
        assert not any(0 in np.asarray(b.entity_rows) for b in ds.blocks)
    if "bound" in case:
        assert len(ds.blocks) > 1


@pytest.mark.parametrize("cap", [1, 4, 16])
def test_the_pad_phase_reads_each_active_nonzero_once(cap):
    """``ingest.pad_nonzeros{coordinate}`` counts the nonzero positions the
    ``pad`` phase reads: the active samples' nonzeros, whatever the number
    of buckets (the loop it replaced read buckets x all of the shard's).
    The phases keep their names: one ``group``, ``bucket`` and ``passive``,
    one ``pad`` a bucket."""
    from photon_tpu.game.dataset import EntityVocabulary
    from photon_tpu.game.random_effect import build_random_effect_dataset
    from photon_tpu.obs.metrics import registry
    from photon_tpu.utils import timing

    df, _, _, _ = _fill_case("buckets_16")
    n = df.num_samples
    key = 'ingest.pad_nonzeros{coordinate="per-user"}'
    before = registry.snapshot()["counters"].get(key, 0)
    timing.clear_timings()
    ds = build_random_effect_dataset(
        df, RandomEffectDataConfiguration(
            "userId", "u", active_data_upper_bound=20000,
            max_entity_buckets=cap),
        EntityVocabulary(), coordinate="per-user")
    labels = [label for label, _ in timing.timing_records()]
    timing.clear_timings()

    assert len(ds.blocks) == cap
    held = np.concatenate([np.asarray(b.sample_rows).ravel()
                           for b in ds.blocks])
    held = held[held < n]
    row_nonzeros = np.count_nonzero(df.feature_shards["u"].rows, axis=1)
    assert len(held) == n - (2 ** 15 - 20000)         # the rest is passive
    assert 0 < row_nonzeros[held].sum() < row_nonzeros.sum()
    assert (registry.snapshot()["counters"][key] - before
            == row_nonzeros[held].sum())
    for step, count in (("group", 1), ("bucket", 1), ("passive", 1),
                        ("pad", len(ds.blocks))):
        assert labels.count(f"ingest/prepare/per-user/{step}") == count, step


@pytest.mark.parametrize("E, D, nnz, path", [
    (10, 20, 100, "table"), (10, 21, 100, "sort"), (0, 0, 0, "table"),
    (34624, 20, 100_000_000, "table"), (27278, 8, 40_000_000, "table"),
    (1000, 1_000_000, 10_000_000, "sort")])
def test_pair_route_takes_the_table_while_it_is_no_larger_than_the_keys(
        E, D, nnz, path):
    """An int32 table over the E x D keys where it is no larger than the
    int64 sorted keys of the active nonzeros (E x D <= 2 nnz): both GLMix
    cells' random effects; a wide vocabulary keeps the sorted keys."""
    from photon_tpu.game.random_effect import pair_route

    assert pair_route(E, D, nnz) == path


@pytest.mark.parametrize("passive_rows", [False, True])
def test_the_pair_route_and_the_passive_phase_are_counted(passive_rows):
    """``ingest.pair_route{coordinate, path}`` ticks once a coordinate on
    the path ``pair_route`` chose; ``ingest.passive_nonzeros`` counts the
    nonzero positions the ``passive`` phase reads: none without a passive
    set, the passive samples' own under ``active_data_upper_bound``, while
    ``ingest.pad_nonzeros`` counts the active samples' (PR 39)."""
    from photon_tpu.game.dataset import CsrRows, EntityVocabulary
    from photon_tpu.game.random_effect import build_random_effect_dataset
    from photon_tpu.obs.metrics import registry

    table, sort, pad, passive_nz = (
        'ingest.pair_route{coordinate="per-user",path="table"}',
        'ingest.pair_route{coordinate="per-user",path="sort"}',
        'ingest.pad_nonzeros{coordinate="per-user"}',
        'ingest.passive_nonzeros{coordinate="per-user"}')

    def counts():
        c = registry.snapshot()["counters"]
        return {k: c.get(k, 0) for k in (table, sort, pad, passive_nz)}

    df, _, _, _ = _fill_case("buckets_16")
    row_nonzeros = np.count_nonzero(df.feature_shards["u"].rows, axis=1)
    bound = dict(active_data_upper_bound=20000) if passive_rows else {}
    before = counts()
    ds = build_random_effect_dataset(
        df, RandomEffectDataConfiguration("userId", "u", **bound),
        EntityVocabulary(), coordinate="per-user")
    after = counts()
    passive = np.asarray(ds.passive_rows)
    passive = passive[passive < df.num_samples]
    assert len(passive) == (2 ** 15 - 20000 if passive_rows else 0)
    moved = {k: after[k] - before[k] for k in after}
    assert moved[table] == 1 and moved[sort] == 0
    assert moved[passive_nz] == row_nonzeros[passive].sum()
    assert moved[pad] == row_nonzeros.sum() - row_nonzeros[passive].sum()

    # a sparse shard over a wide vocabulary keeps the sorted keys
    n, d = df.num_samples, 1_000_000
    cols = np.random.default_rng(0).integers(0, d, size=n)
    wide = GameDataFrame(
        num_samples=n, response=df.response, id_tags=df.id_tags,
        feature_shards={"u": FeatureShard(
            CsrRows(np.arange(n + 1), cols, np.ones(n)), d)})
    before = counts()
    build_random_effect_dataset(
        wide, RandomEffectDataConfiguration("userId", "u"),
        EntityVocabulary(), coordinate="per-user")
    after = counts()
    assert after[sort] - before[sort] == 1 and after[table] == before[table]


@pytest.fixture(scope="module")
def skewed_blocks():
    """A Zipf-skewed random-effect dataset with passive rows (the cap
    pushes the head entities' overflow to the passive split): (dataset, n,
    entity count)."""
    from photon_tpu.game.dataset import EntityVocabulary
    from photon_tpu.game.random_effect import build_random_effect_dataset

    rng = np.random.default_rng(3)
    n, d, ents = 1500, 3, 90
    p = 1.0 / np.arange(1, ents + 1) ** 1.3
    ent = rng.choice(ents, size=n, p=p / p.sum())
    idx = np.arange(d, dtype=np.int32)
    rows = [(idx, rng.normal(size=d)) for _ in range(n)]
    df = GameDataFrame(num_samples=n, response=rng.random(n),
                       feature_shards={"u": FeatureShard(rows, d)},
                       id_tags={"userId": [str(e) for e in ent]})
    ds = build_random_effect_dataset(
        df, RandomEffectDataConfiguration(
            "userId", "u", active_data_upper_bound=40, max_entity_buckets=4),
        EntityVocabulary(), dtype=np.float64)
    assert len(ds.blocks) > 2
    assert int(np.sum(np.asarray(ds.passive_rows) < n)) > 0
    # the ladder pads: some slot of some bucket is not a sample
    assert any(np.any(np.asarray(b.sample_rows) == n) for b in ds.blocks)
    return ds, n, ds.num_entities


def _lane_stack(a, lanes):
    """``a`` itself, or three lanes of it that differ (lane j is
    ``(j + 1) * a``) so that a lane mix-up shows."""
    return jnp.stack([(j + 1) * a for j in range(3)]) if lanes else a


def _held_slots(ds, n):
    """(flat row, slot) of every non-pad slot, slots numbered through the
    buckets in order, then the passive rows."""
    rows = np.concatenate([np.asarray(b.sample_rows).ravel()
                           for b in ds.blocks]
                          + [np.asarray(ds.passive_rows)])
    slots = np.flatnonzero(rows < n)
    return rows[slots], slots, len(rows)


def _rows_to_flat(ds, block_values, passive_values, lanes):
    """``ds.rows_to_flat``, a lane at a time under a leading lane axis."""
    back = jax.vmap(ds.rows_to_flat) if lanes else ds.rows_to_flat
    return back(list(block_values), passive_values)


@pytest.mark.parametrize("lanes", [False, True], ids=["flat", "lanes"])
def test_flat_rows_read_zero_on_pads_and_pads_are_dropped(skewed_blocks,
                                                           lanes):
    """``EntityBlock.rows_from_flat`` reads every pad slot as 0 and every
    real slot as its flat row; ``rows_to_flat`` reads no pad."""
    ds, n, _ = skewed_blocks
    flat = _lane_stack(jnp.arange(1.0, n + 1.0), lanes)  # zero nowhere
    nowhere = [_lane_stack(jnp.zeros(b.sample_rows.shape), lanes)
               for b in ds.blocks]
    no_passive = _lane_stack(jnp.zeros(ds.passive_rows.shape), lanes)
    for at, blk in enumerate(ds.blocks):
        rows_of = np.asarray(blk.sample_rows)
        pad = rows_of == n
        got = np.asarray(blk.rows_from_flat(flat, lanes))
        assert got.shape == flat.shape[:-1] + rows_of.shape
        assert np.all(got[..., pad] == 0.0)
        np.testing.assert_array_equal(
            got[..., ~pad], np.asarray(flat)[..., rows_of[~pad]])
        # pads carry weight 0: the same slots the solve ignores
        assert np.all(np.asarray(blk.weights)[pad] == 0.0)
        # ones in this bucket's every slot, pads too: only its samples
        # come back, and a pad's value lands on no row
        ones = _lane_stack(jnp.ones(rows_of.shape), lanes)
        back = np.asarray(_rows_to_flat(
            ds, nowhere[:at] + [ones] + nowhere[at + 1:], no_passive, lanes))
        assert back.shape == flat.shape
        want = np.zeros(n)
        want[rows_of[~pad]] = 1.0
        np.testing.assert_array_equal(
            back, np.asarray(_lane_stack(jnp.asarray(want), lanes)))


@pytest.mark.parametrize("lanes", [False, True], ids=["flat", "lanes"])
def test_blocks_and_passive_rows_partition_the_flat_frame(skewed_blocks,
                                                          lanes):
    """Gathering a flat vector into every bucket and the passive slots
    and taking it back to flat order returns the vector: each flat row is
    held by exactly one slot (what ``data_loss_at`` and the one-gather
    score rely on)."""
    ds, n, _ = skewed_blocks
    rng = np.random.default_rng(0)
    flat = _lane_stack(jnp.asarray(rng.normal(size=n)), lanes)
    rows = [blk.rows_from_flat(flat, lanes) for blk in ds.blocks]
    passive = flat.at[..., ds.passive_rows].get(mode="fill", fill_value=0.0)
    back = _rows_to_flat(ds, rows, passive, lanes)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(flat))
    np.testing.assert_array_equal(
        np.bincount(_held_slots(ds, n)[0], minlength=n), 1)


@pytest.mark.parametrize("lanes", [False, True], ids=["table", "lanes"])
def test_table_rows_read_the_fill_on_pad_rows_and_pad_rows_are_dropped(
        skewed_blocks, lanes):
    """``rows_from_table`` / ``set_rows_in_table`` against the
    out-of-range pad row a mesh-padded bucket carries: it reads the fill,
    it writes nothing, and over the buckets the two are inverse on every
    entity with active data."""
    ds, _, E = skewed_blocks
    rng = np.random.default_rng(1)
    table = _lane_stack(jnp.asarray(rng.normal(size=(E, 3))), lanes)
    back = jnp.full(table.shape, -7.0)
    active = np.zeros(E, bool)
    for blk in ds.blocks:
        ents = np.asarray(blk.entity_rows)
        active[ents] = True
        # the first block row becomes a pad row, as parallel/mesh pads
        padded = blk._replace(entity_rows=blk.entity_rows.at[0].set(E))
        rows = np.asarray(padded.rows_from_table(table, 1.5, lanes))
        assert rows.shape == table.shape[:-2] + (len(ents), 3)
        assert np.all(rows[..., 0, :] == 1.5)
        np.testing.assert_array_equal(
            rows[..., 1:, :], np.asarray(table)[..., ents[1:], :])
        wrote = np.asarray(padded.set_rows_in_table(
            jnp.full(table.shape, -7.0), jnp.asarray(rows), lanes))
        assert np.all(wrote[..., ents[0], :] == -7.0)
        back = blk.set_rows_in_table(
            back, blk.rows_from_table(table, 0.0, lanes), lanes)
    np.testing.assert_array_equal(
        np.asarray(back)[..., active, :], np.asarray(table)[..., active, :])
    assert np.all(np.asarray(back)[..., ~active, :] == -7.0)


def _mapping_reads(path, fields=("sample_rows", "flat_source")):
    """(line, what) of every direct use of the flat/ladder mapping in a
    source file: an attribute read of one of ``fields``, or an
    ``.at[...]`` whose index mentions ``entity_rows``."""
    import ast

    found = []
    for node in ast.walk(ast.parse(open(path).read(), path)):
        if isinstance(node, ast.Attribute) and node.attr in fields:
            found.append((node.lineno, node.attr))
        if (isinstance(node, ast.Subscript)
                and isinstance(node.value, ast.Attribute)
                and node.value.attr == "at"
                and any(isinstance(sub, ast.Attribute)
                        and sub.attr == "entity_rows"
                        for sub in ast.walk(node.slice))):
            found.append((node.lineno, ".at[entity_rows]"))
    return found


def _package_root():
    import os

    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "photon_tpu")


def _mapping_free_sources():
    import glob
    import os

    root = _package_root()
    return [os.path.join(root, "game", "coordinate.py")] + sorted(
        glob.glob(os.path.join(root, "bayes", "*.py")))


@pytest.mark.parametrize(
    "path", _mapping_free_sources(),
    ids=lambda p: "/".join(p.split("/")[-2:]))
def test_the_flat_ladder_mapping_is_read_only_through_entity_block(path):
    """How ladder order maps to flat order is ``game/random_effect.py``'s
    to know (its four mapping methods): the solve, score, objective, loss
    and variance programs index neither ``sample_rows`` nor
    ``flat_source`` nor by ``entity_rows`` themselves, so a re-layout is
    a change to ``game/random_effect.py`` alone."""
    assert _mapping_reads(path) == []


def test_flat_source_is_read_in_random_effect_and_mesh_only():
    """The inverse map has two homes: ``game/random_effect.py`` (built,
    read by ``rows_to_flat``) and ``parallel/mesh.py`` (re-derived when
    entity padding moves the slots, placed). Nothing else in the package
    reads it."""
    import glob
    import os

    root = _package_root()
    homes = {os.path.join(root, "game", "random_effect.py"),
             os.path.join(root, "parallel", "mesh.py")}
    sources = set(glob.glob(os.path.join(root, "**", "*.py"),
                            recursive=True))
    assert homes <= sources
    reads = {os.path.relpath(path, root): _mapping_reads(
        path, fields=("flat_source",)) for path in sources - homes}
    assert {p: r for p, r in reads.items() if r} == {}
    for path in homes:
        assert _mapping_reads(path, fields=("flat_source",))


def test_the_mapping_walk_sees_a_direct_read(tmp_path):
    src = tmp_path / "fork.py"
    src.write_text(
        "def f(blk, ds, flat, table):\n"
        "    a = flat.at[blk.sample_rows].get(mode='fill', fill_value=0.0)\n"
        "    b = flat[ds.flat_source]\n"
        "    return a, b, table.at[:, blk.entity_rows].set(a, mode='drop')\n")
    assert sorted(what for _, what in _mapping_reads(str(src))) == [
        ".at[entity_rows]", "flat_source", "sample_rows"]


_SCORE_LAYOUTS = ["dense", "sparse", "no-passive", "dropped", "mesh-padded"]


def _score_layout(layout, dtype):
    """(dataset, n) of one shape the builder can make: dense-local or
    sparse buckets, passive rows or none, capped entities whose overflow
    is DROPPED (``keep_passive_data=False``: flat rows no slot holds), or
    entity-padded as ``parallel/mesh`` pads for a mesh."""
    from photon_tpu.game.dataset import EntityVocabulary
    from photon_tpu.game.random_effect import build_random_effect_dataset
    from photon_tpu.parallel.mesh import pad_entities

    rng = np.random.default_rng(5)
    n, d, ents = 1200, 5, 70
    p = 1.0 / np.arange(1, ents + 1) ** 1.3
    ent = rng.choice(ents, size=n, p=p / p.sum())

    def whole(size):
        # small whole numbers: a margin is exact in any order of summation
        return rng.choice([-3.0, -2.0, -1.0, 1.0, 2.0, 3.0], size=size)

    if layout == "sparse":
        rows = []
        for _ in range(n):
            idx = np.sort(rng.choice(d, size=rng.integers(1, d),
                                     replace=False)).astype(np.int32)
            rows.append((idx, whole(len(idx))))
    else:
        rows = [(np.arange(d, dtype=np.int32), whole(d)) for _ in range(n)]
    df = GameDataFrame(num_samples=n, response=rng.random(n),
                       feature_shards={"u": FeatureShard(rows, d)},
                       id_tags={"userId": [str(e) for e in ent]})
    ds = build_random_effect_dataset(
        df, RandomEffectDataConfiguration(
            "userId", "u", max_entity_buckets=4,
            active_data_upper_bound=None if layout == "no-passive" else 30,
            keep_passive_data=layout != "dropped"),
        EntityVocabulary(), dtype=dtype)
    if layout == "mesh-padded":
        padded = pad_entities(ds, 8)
        assert padded is not ds
        assert [b.num_rows for b in padded.blocks] != [
            b.num_rows for b in ds.blocks]
        ds = padded
    return ds, n


@pytest.mark.parametrize("layout", _SCORE_LAYOUTS)
def test_flat_source_inverts_the_ladder(layout):
    """``flat_source`` restricted to the rows some slot holds is a
    bijection onto the non-pad slots; every other row points at the
    trailing zero; no index is out of bounds."""
    ds, n = _score_layout(layout, np.float64)
    source = np.asarray(ds.flat_source)
    rows, slots, total = _held_slots(ds, n)
    assert source.shape == (n,) and source.dtype == np.int32
    assert source.min() >= 0 and source.max() <= total
    held = source < total
    np.testing.assert_array_equal(np.sort(source[held]), slots)
    np.testing.assert_array_equal(source[rows], slots)
    n_passive = int(np.sum(np.asarray(ds.passive_rows) < n))
    assert (n_passive > 0) == (layout not in ("no-passive", "dropped"))
    assert (int(np.sum(~held)) > 0) == (layout == "dropped")


def _score_program(ds, n, dtype):
    """(the coordinate's jitted score program, a random coefficient
    table, its dense flags)."""
    from photon_tpu.game.coordinate import RandomEffectCoordinate

    coord = RandomEffectCoordinate(ds, n, "userId", "u",
                                   TaskType.LOGISTIC_REGRESSION)
    table = jnp.asarray(np.random.default_rng(9).integers(
        -4, 5, size=(ds.num_entities, ds.projected_dim)).astype(dtype))
    return coord._score_fn, table, coord._dense_local_blocks


@pytest.mark.parametrize("dtype", [np.float32, np.float64],
                         ids=["float32", "float64"])
@pytest.mark.parametrize("layout", _SCORE_LAYOUTS)
def test_one_gather_score_is_the_reference_bit_for_bit(layout, dtype):
    """The score program against numpy's placement of each slot's margin
    at its ``sample_rows`` / ``passive_rows`` row (whole-number features
    and coefficients, so a margin has one value however it is summed):
    the same bits, 0 on rows no slot holds (``want`` is 0 there)."""
    ds, n = _score_layout(layout, dtype)
    score, table, dense = _score_program(ds, n, dtype)
    if layout != "mesh-padded":   # the ELL width of padding is not local
        assert all(dense) == (layout != "sparse")
    got = np.asarray(score(ds, table))
    assert got.shape == (n,) and got.dtype == dtype

    coef = np.asarray(table)
    want = np.zeros(n, dtype)
    for blk in ds.blocks:
        rows_of = np.asarray(blk.sample_rows)
        ents = np.asarray(blk.entity_rows)
        c = np.zeros((len(ents), coef.shape[1]), dtype)   # a pad row: 0
        c[ents < ds.num_entities] = coef[ents[ents < ds.num_entities]]
        margins = np.sum(
            np.asarray(blk.features.values) * np.take_along_axis(
                c[:, None, :], np.asarray(blk.features.indices), axis=2),
            axis=-1)
        want[rows_of[rows_of < n]] = margins[rows_of < n]
    live = np.asarray(ds.passive_rows) < n
    want[np.asarray(ds.passive_rows)[live]] = np.sum(
        np.asarray(ds.passive_features.values)[live] * np.take_along_axis(
            coef[np.asarray(ds.passive_entity)[live]],
            np.asarray(ds.passive_features.indices)[live], axis=1), axis=-1)
    assert np.any(want != 0.0)
    np.testing.assert_array_equal(got, want)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("layout", _SCORE_LAYOUTS)
def test_score_program_holds_one_gather_onto_the_flat_rows(layout):
    """The way back to flat order is ONE gather with an ``[n]`` output
    and no scatter of any kind, however many buckets the ladder has."""
    ds, n = _score_layout(layout, np.float32)
    score, table, _ = _score_program(ds, n, np.float32)
    assert len(ds.blocks) > 1
    eqns = list(_equations(jax.make_jaxpr(score)(ds, table).jaxpr))
    names = [e.primitive.name for e in eqns]
    assert not [m for m in names if m.startswith("scatter")], names
    onto_flat = [e for e in eqns if e.primitive.name == "gather"
                 and e.outvars[0].aval.shape == (n,)]
    assert len(onto_flat) == 1
    slots = sum(b.sample_rows.size for b in ds.blocks) + len(ds.passive_rows)
    assert onto_flat[0].invars[0].aval.shape == (slots + 1,)


def test_random_effect_tron_matches_lbfgs(glmix):
    """A TRON-solved random effect (explicit per-entity K x K Hessian,
    batched under vmap) must reach the same convex optimum as L-BFGS
    (reference: RandomEffectOptimizationProblem supports every optimizer,
    OptimizerFactory.scala)."""
    train, _, _ = glmix

    def fit(opt_type):
        opt = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(optimizer_type=opt_type,
                                      max_iterations=60, tolerance=1e-10),
            regularization=L2Regularization, regularization_weight=1.0)
        est = GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinate_configs={
                "per-user": CoordinateConfiguration(
                    RandomEffectDataConfiguration("userId", "user_feats"),
                    opt)},
            update_sequence=["per-user"], num_iterations=1,
            dtype=jnp.float64)
        return np.asarray(est.fit(train)[-1].model["per-user"].coefficients)

    from photon_tpu.types import OptimizerType

    a = fit(OptimizerType.LBFGS)
    b = fit(OptimizerType.TRON)
    # both stop on FunctionValuesConverged; the optima agree to solver
    # tolerance, not bitwise (different iterates)
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_bf16_feature_storage_preserves_quality(glmix):
    """Opt-in bfloat16 feature storage (halved HBM traffic on the
    bandwidth-bound fixed-effect solve) must keep solver math at the
    solve dtype and land within quality tolerance of f32 storage."""
    train, val, _ = glmix

    def fit(feature_dtype):
        est = GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinate_configs={
                "fixed": glmix_estimator().coordinate_configs["fixed"]},
            update_sequence=["fixed"], num_iterations=1,
            validation_evaluators=[EvaluatorType.AUC],
            dtype=jnp.float32, feature_dtype=feature_dtype)
        res = est.fit(train, validation_df=val)[-1]
        coord = est._coordinates["fixed"]
        return res, coord

    res32, coord32 = fit(None)
    res16, coord16 = fit(jnp.bfloat16)

    def feat_dtype(coord):
        f = coord.batch.features
        return f.values.dtype if hasattr(f, "values") else f.dtype

    assert feat_dtype(coord16) == jnp.bfloat16
    assert feat_dtype(coord32) == jnp.float32
    # solver ran in f32 space
    assert res16.model["fixed"].model.coefficients.means.dtype == jnp.float32
    assert abs(res16.evaluation["AUC"] - res32.evaluation["AUC"]) < 0.01


def test_direct_solver_game_parity():
    """DIRECT (batched per-entity normal equations) lands on the same GAME
    model as tightly-converged TRON for linear regression — fixed AND
    random effects."""
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
    from photon_tpu.types import OptimizerType, TaskType

    rng = np.random.default_rng(3)
    n, d, users, d_u = 500, 6, 7, 3
    Xg = rng.normal(size=(n, d))
    Xu = rng.normal(size=(n, d_u))
    uid = rng.integers(0, users, size=n)
    y = (Xg @ rng.normal(size=d)
         + np.einsum("nk,nk->n", Xu, rng.normal(size=(users, d_u))[uid])
         + 0.2 * rng.normal(size=n))
    iu = np.arange(d_u, dtype=np.int32)
    df = GameDataFrame(
        num_samples=n, response=y,
        feature_shards={"g": FeatureShard(Xg, d),
                        "u": FeatureShard([(iu, Xu[i]) for i in range(n)], d_u)},
        id_tags={"userId": [f"u{v}" for v in uid]})

    def fit(opt_type, **kw):
        opt = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(optimizer_type=opt_type, **kw),
            regularization=L2Regularization, regularization_weight=1.0)
        est = GameEstimator(
            TaskType.LINEAR_REGRESSION,
            {"fixed": CoordinateConfiguration(
                FixedEffectDataConfiguration("g"), opt),
             "per_user": CoordinateConfiguration(
                 RandomEffectDataConfiguration("userId", "u"), opt)},
            update_sequence=["fixed", "per_user"], num_iterations=3,
            dtype=np.float64)
        res = est.fit(df)
        return (np.asarray(res[-1].model["fixed"].model.coefficients.means),
                np.asarray(res[-1].model["per_user"].coefficients))

    f_direct, re_direct = fit(OptimizerType.DIRECT)
    f_tron, re_tron = fit(OptimizerType.TRON,
                          max_iterations=100, tolerance=1e-13)
    np.testing.assert_allclose(f_direct, f_tron, rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(re_direct, re_tron, rtol=1e-6, atol=1e-8)


def test_random_effect_accepts_dense_shard():
    """A dense [n, d] matrix as a random-effect feature shard trains the
    same model as the equivalent sparse row list (previously crashed in
    _csr_of with an obscure TypeError)."""
    import numpy as np

    from photon_tpu.estimators.game_estimator import (
        CoordinateConfiguration,
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

    rng = np.random.default_rng(11)
    n, d_u, users = 200, 3, 5
    Xu = rng.normal(size=(n, d_u))
    Xu[rng.random((n, d_u)) < 0.3] = 0.0      # real zeros: sparse != dense trap
    uid = rng.integers(0, users, size=n)
    y = np.einsum("nk,nk->n", Xu, rng.normal(size=(users, d_u))[uid])
    iu = np.arange(d_u, dtype=np.int32)

    def fit(shard):
        df = GameDataFrame(num_samples=n, response=y,
                           feature_shards={"u": shard},
                           id_tags={"userId": [f"u{v}" for v in uid]})
        opt = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(max_iterations=50, tolerance=1e-10),
            regularization=L2Regularization, regularization_weight=0.5)
        est = GameEstimator(
            TaskType.LINEAR_REGRESSION,
            {"per_user": CoordinateConfiguration(
                RandomEffectDataConfiguration("userId", "u"), opt)},
            update_sequence=["per_user"], num_iterations=1,
            dtype=np.float64)
        res = est.fit(df)
        return np.asarray(res[-1].model["per_user"].coefficients)

    dense = fit(FeatureShard(Xu, d_u))
    sparse = fit(FeatureShard(
        [(iu[Xu[i] != 0], Xu[i][Xu[i] != 0]) for i in range(n)], d_u))
    np.testing.assert_allclose(dense, sparse, rtol=1e-8, atol=1e-10)


def test_dense_local_score_matches_sparse_path(glmix):
    """The dense-local einsum score branch must equal the gather/scatter
    branch on the same dataset (guards the einsum subscripts directly,
    not just via downstream AUC thresholds)."""
    import numpy as np

    from photon_tpu.game.coordinate import _re_score_builder

    train, val, _ = glmix
    est = glmix_estimator()
    result = est.fit(train, val)[0]
    coord = est._coordinates["per-user"]
    flags = coord._dense_local_blocks
    assert any(flags)   # user_feats rows are observed in full
    coefs = coord._pad_entity_rows(result.model["per-user"].coefficients)
    s_dense = _re_score_builder(flags)(coord.dataset, coefs)
    s_sparse = _re_score_builder((False,) * len(flags))(
        coord.dataset, coefs)
    np.testing.assert_allclose(np.asarray(s_dense), np.asarray(s_sparse),
                               rtol=1e-6, atol=1e-8)


def test_newton_solver_game_parity_logistic():
    """NEWTON (batched per-entity IRLS, optim/newton.py) lands on the same
    GAME model as tightly-converged TRON for LOGISTIC regression — fixed
    AND random effects (the flagship GLMix workload the reference solves
    with per-entity iterative TRON, SingleNodeOptimizationProblem.scala:40)."""
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
    from photon_tpu.types import OptimizerType, TaskType

    rng = np.random.default_rng(5)
    n, d, users, d_u = 600, 6, 7, 3
    Xg = rng.normal(size=(n, d))
    Xu = rng.normal(size=(n, d_u))
    uid = rng.integers(0, users, size=n)
    logits = (Xg @ rng.normal(size=d)
              + np.einsum("nk,nk->n", Xu, rng.normal(size=(users, d_u))[uid]))
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logits))).astype(np.float64)
    iu = np.arange(d_u, dtype=np.int32)
    df = GameDataFrame(
        num_samples=n, response=y,
        feature_shards={"g": FeatureShard(Xg, d),
                        "u": FeatureShard([(iu, Xu[i]) for i in range(n)], d_u)},
        id_tags={"userId": [f"u{v}" for v in uid]})

    def fit(opt_type, **kw):
        opt = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(optimizer_type=opt_type, **kw),
            regularization=L2Regularization, regularization_weight=1.0)
        est = GameEstimator(
            TaskType.LOGISTIC_REGRESSION,
            {"fixed": CoordinateConfiguration(
                FixedEffectDataConfiguration("g"), opt),
             "per_user": CoordinateConfiguration(
                 RandomEffectDataConfiguration("userId", "u"), opt)},
            update_sequence=["fixed", "per_user"], num_iterations=3,
            dtype=np.float64)
        res = est.fit(df)
        return (np.asarray(res[-1].model["fixed"].model.coefficients.means),
                np.asarray(res[-1].model["per_user"].coefficients))

    f_newton, re_newton = fit(OptimizerType.NEWTON,
                              max_iterations=30, tolerance=1e-12)
    f_tron, re_tron = fit(OptimizerType.TRON,
                          max_iterations=100, tolerance=1e-13)
    np.testing.assert_allclose(f_newton, f_tron, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(re_newton, re_tron, rtol=1e-5, atol=1e-7)
