"""Two-tier coefficient store (ISSUE 8): cold-store format, hot-tier
LRU/promotion mechanics, lazy serving loads, and the blocked
(cold-tier-streaming) training mode.

Engine-level tier-boundary parity and the two-tier store under Zipf
traffic live in tests/test_serving.py; this file covers the store and training layers
directly.
"""

from __future__ import annotations

import os
import time

import numpy as np
import pytest

from photon_tpu.io.cold_store import (
    ColdStore,
    ColdStoreCorruptError,
    cold_store_path,
    write_cold_store,
)
from photon_tpu.resilience import chaos
from photon_tpu.serving.coeff_store import (
    COLD,
    HIT,
    UNKNOWN,
    TwoTierCoeffStore,
)
from photon_tpu.serving.types import CoeffStoreConfig


def _write_store(path, E=10, K=3, D=16, seed=0, ids=None):
    rng = np.random.default_rng(seed)
    coef = rng.normal(size=(E, K)).astype(np.float32)
    proj = np.stack([np.sort(rng.choice(D, size=K, replace=False))
                     for _ in range(E)]).astype(np.int32)
    if ids is None:
        ids = [f"u{e:03d}" for e in range(E)]
    write_cold_store(path, "per_user", "userId", "u", coef, proj,
                     np.asarray(ids))
    return coef, proj, list(ids)


# -- cold-store format -------------------------------------------------------


class TestColdStoreFormat:
    def test_roundtrip_sorted_by_entity_id(self, tmp_path):
        p = str(tmp_path / "a.coldstore")
        # ids deliberately unsorted: the writer re-sorts rows
        ids = ["zed", "alpha", "mid"]
        coef, proj, _ = _write_store(p, E=3, ids=ids)
        cs = ColdStore(p, verify=True)
        assert cs.num_entities == 3
        order = np.argsort(np.asarray(ids))
        for out_row, src_row in enumerate(order):
            assert cs.entity_id(out_row) == ids[src_row]
            np.testing.assert_array_equal(
                cs.read_rows(np.asarray([out_row]))[0], coef[src_row])
            np.testing.assert_array_equal(
                cs.read_proj_rows(np.asarray([out_row]))[0], proj[src_row])
        assert cs.entity_row("alpha") == 0
        assert cs.entity_row("nobody") is None

    def test_write_normalizes_slot_order(self, tmp_path):
        """Rows arrive with slots in arbitrary column order (training
        projections carry no ordering guarantee); the format sorts each
        row's valid slots ascending by global column — the invariant the
        serving searchsorted replay depends on."""
        p = str(tmp_path / "b.coldstore")
        coef = np.asarray([[1.0, 2.0, 3.0], [4.0, 5.0, 6.0]], np.float32)
        proj = np.asarray([[7, 2, 5], [3, -1, 1]], np.int32)  # unsorted,
        write_cold_store(p, "c", "userId", "u", coef, proj,   # -1 mid-row
                         np.asarray(["a", "b"]))
        cs = ColdStore(p)
        got_proj = cs.read_proj_rows(np.asarray([0, 1]))
        got_coef = cs.read_rows(np.asarray([0, 1]))
        np.testing.assert_array_equal(got_proj[0], [2, 5, 7])
        np.testing.assert_array_equal(got_coef[0], [2.0, 3.0, 1.0])
        # -1 pads sort to the END; values ride along with their column
        np.testing.assert_array_equal(got_proj[1], [1, 3, -1])
        np.testing.assert_array_equal(got_coef[1], [6.0, 4.0, 5.0])

    def test_corrupt_file_refused(self, tmp_path):
        p = str(tmp_path / "c.coldstore")
        _write_store(p)
        flipped = chaos.corrupt_cold_store(p, seed=3)
        assert flipped
        with pytest.raises(ColdStoreCorruptError):
            ColdStore(p, verify=True)

    def test_iter_blocks_streams_all_rows(self, tmp_path):
        p = str(tmp_path / "d.coldstore")
        coef, proj, ids = _write_store(p, E=7)
        cs = ColdStore(p)
        seen = []
        for lo, blk_ids, coef_b, proj_b in cs.iter_blocks(3):
            assert coef_b.shape[0] == len(blk_ids) == proj_b.shape[0]
            seen.extend(blk_ids)
        assert seen == sorted(ids)
        # resume mid-stream: start_row skips exactly the first block
        rest = [i for _lo, bi, _c, _p in cs.iter_blocks(3, start_row=3)
                for i in bi]
        assert rest == seen[3:]

    def test_chaos_cold_read_delay_counts_down(self, tmp_path):
        p = str(tmp_path / "e.coldstore")
        _write_store(p)
        cs = ColdStore(p)
        cfg = chaos.ChaosConfig(cold_read_delay_s=0.05,
                                cold_read_delay_reads=2)
        with chaos.active(cfg):
            t0 = time.perf_counter()
            cs.read_rows(np.asarray([0]))
            cs.read_rows(np.asarray([1]))
            slow = time.perf_counter() - t0
            t0 = time.perf_counter()
            cs.read_rows(np.asarray([2]))       # budget spent: fast again
            fast = time.perf_counter() - t0
        assert slow >= 0.1
        assert fast < 0.05


# -- hot tier ----------------------------------------------------------------


class TestTwoTierStore:
    def _store(self, tmp_path, capacity=4, E=10, **kw):
        p = str(tmp_path / "s.coldstore")
        coef, proj, ids = _write_store(p, E=E)
        cs = ColdStore(p)
        store = TwoTierCoeffStore(
            cs, CoeffStoreConfig(hot_capacity=capacity, transfer_batch=2),
            start_thread=False, **kw)
        return store, coef, proj, ids

    def test_cold_miss_then_promote_then_hit(self, tmp_path):
        store, coef, proj, ids = self._store(tmp_path)
        with store.lock:
            row, status = store.lookup_locked(ids[0])
        assert status == COLD and row == store.unknown_row
        # the zero row really is zero: a COLD gather contributes nothing
        np.testing.assert_array_equal(
            np.asarray(store.table)[store.unknown_row], 0.0)
        assert store.drain_prefetch()
        with store.lock:
            row, status = store.lookup_locked(ids[0])
            assert status == HIT
            np.testing.assert_array_equal(store.proj_row_locked(row),
                                          proj[0])
        np.testing.assert_array_equal(np.asarray(store.table)[row], coef[0])

    def test_unknown_entity(self, tmp_path):
        store, *_ = self._store(tmp_path)
        with store.lock:
            row, status = store.lookup_locked("nobody")
        assert status == UNKNOWN and row == store.unknown_row
        assert store.stats()["unknown"] == 1

    def test_lru_eviction_and_counters(self, tmp_path):
        store, coef, _proj, ids = self._store(tmp_path, capacity=4, E=8)
        for e in range(6):                    # 6 entities through cap 4
            with store.lock:
                store.lookup_locked(ids[e])
            store.drain_prefetch()
        st = store.stats()
        assert st["occupancy"] == 4
        assert st["evictions"] == 2
        assert st["promotes"] == 6
        # LRU: the two oldest (ids[0], ids[1]) were evicted
        with store.lock:
            assert store.lookup_locked(ids[0])[1] == COLD
            assert store.lookup_locked(ids[5])[1] == HIT
        store.drain_prefetch()                # re-promote ids[0] (evicts 2)
        with store.lock:
            assert store.lookup_locked(ids[2])[1] == COLD
        # hit refreshes recency: touch ids[3], promote two more — the
        # refreshed entry survives both evictions (victims: 4 then 5)
        with store.lock:
            store._pending.clear()            # drop the ids[2] re-promote
            assert store.lookup_locked(ids[3])[1] == HIT
            store.lookup_locked(ids[6])
            store.lookup_locked(ids[7])
        store.drain_prefetch()
        with store.lock:
            assert store.lookup_locked(ids[3])[1] == HIT
            assert store.lookup_locked(ids[4])[1] == COLD

    def test_prefetch_lookahead_avoids_cold_miss(self, tmp_path):
        store, coef, _proj, ids = self._store(tmp_path)
        store.prefetch(ids[3])
        assert store.drain_prefetch()
        with store.lock:
            row, status = store.lookup_locked(ids[3])
        assert status == HIT
        assert store.stats()["cold_misses"] == 0

    def test_power_of_two_capacity_and_budget(self, tmp_path):
        store, *_ = self._store(tmp_path, capacity=5)
        assert store.capacity == 4            # pow2 floor
        p = str(tmp_path / "tiny.coldstore")
        _write_store(p)
        with pytest.raises(ValueError):
            TwoTierCoeffStore(ColdStore(p),
                              CoeffStoreConfig(hbm_budget_bytes=1),
                              start_thread=False)

    def test_background_thread_drains(self, tmp_path):
        p = str(tmp_path / "bg.coldstore")
        coef, _proj, ids = _write_store(p)
        store = TwoTierCoeffStore(
            ColdStore(p), CoeffStoreConfig(hot_capacity=4, transfer_batch=2))
        try:
            store.prefetch(ids[1])
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                with store.lock:
                    if ids[1] in store._hot:
                        break
                time.sleep(0.01)
            with store.lock:
                assert store.lookup_locked(ids[1])[1] == HIT
        finally:
            store.close()


# -- lazy serving loads ------------------------------------------------------


class TestLazyLoad:
    def _model_dir(self, tmp_path):
        import jax.numpy as jnp

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

        rng = np.random.default_rng(1)
        D, E, K = 8, 5, 3
        imap = IndexMap({feature_key(f"f{i}", ""): i for i in range(D)})
        theta = rng.normal(size=D)
        coef = rng.normal(size=(E, K)).astype(np.float32)
        proj = np.stack([np.sort(rng.choice(D, size=K, replace=False))
                         for _ in range(E)]).astype(np.int32)
        vocab = EntityVocabulary()
        vocab.build("userId", [f"user{e}" for e in range(E)])
        model = GameModel({
            "fixed": FixedEffectModel(
                GeneralizedLinearModel(Coefficients(jnp.asarray(theta)),
                                       TaskType.LINEAR_REGRESSION), "shardA"),
            "per_user": RandomEffectModel(jnp.asarray(coef), "userId",
                                          "shardA",
                                          TaskType.LINEAR_REGRESSION)})
        d = str(tmp_path / "m")
        save_game_model(d, model, {"shardA": imap}, vocab=vocab,
                        projections={"per_user": proj},
                        sparsity_threshold=0.0)
        return d, coef, proj

    def test_save_writes_cold_store_and_sidecar(self, tmp_path):
        d, _coef, _proj = self._model_dir(tmp_path)
        assert os.path.exists(cold_store_path(d, "per_user"))
        assert os.path.exists(
            os.path.join(d, "feature-index", "shardA.json"))

    def test_load_for_serving_is_lazy_then_materializes(self, tmp_path):
        from photon_tpu.io.model_io import load_for_serving

        d, coef, proj = self._model_dir(tmp_path)
        sm = load_for_serving(d)
        re = sm.random[0]
        assert re.cold_store_path is not None
        assert re._coefficients is None       # nothing materialized yet
        assert re.num_entities == 5           # header-only open
        assert re._coefficients is None
        got = np.asarray(re.coefficients)     # first access materializes
        assert got.shape == coef.shape
        np.testing.assert_allclose(got, coef, atol=0)
        assert re.entity_rows["user0"] == 0
        assert len(re.entity_rows) == 5

    def test_save_without_cold_stores_loads_eagerly(self, tmp_path):
        from photon_tpu.io.model_io import load_for_serving, save_game_model

        d, _coef, _proj = self._model_dir(tmp_path)
        # re-save the same dir content without cold tier
        import shutil
        shutil.rmtree(os.path.join(d, "cold-store"))
        sm = load_for_serving(d)
        assert sm.random[0].cold_store_path is None
        assert sm.random[0].coefficients is not None


# -- blocked training --------------------------------------------------------


def _coordinate(seed=7, n=3000, d=4, ents=200, max_buckets=4):
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
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        OptimizerConfig,
    )
    from photon_tpu.types import TaskType

    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, ents + 1) ** 1.3
    ent = rng.choice(ents, size=n, p=p / p.sum())
    idx = np.arange(d, dtype=np.int32)
    rows = [(idx, rng.normal(size=d)) for _ in range(n)]
    y = (rng.random(n) > 0.5).astype(np.float64)
    df = GameDataFrame(num_samples=n, response=y,
                       feature_shards={"u": FeatureShard(rows, d)},
                       id_tags={"userId": [str(e) for e in ent]})
    vocab = EntityVocabulary()
    ds = build_random_effect_dataset(
        df, RandomEffectDataConfiguration("userId", "u",
                                          max_entity_buckets=max_buckets),
        vocab, dtype=np.float64)
    coord = RandomEffectCoordinate(
        ds, n, "userId", "u", TaskType.LOGISTIC_REGRESSION,
        GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(max_iterations=25, tolerance=1e-8)))
    return coord, ds, vocab


class TestBlockedTraining:
    def test_blocked_matches_all_at_once_bitwise(self):
        coord, ds, _vocab = _coordinate()
        ref = np.asarray(coord.update_model(None, None).coefficients)
        it_ref = np.asarray(coord.last_tracker.iterations)
        cursor = []
        m = coord.update_model_blocked(
            None, on_block=lambda b, nb: cursor.append((b, nb)))
        assert isinstance(m.coefficients, np.ndarray)  # host-resident
        np.testing.assert_array_equal(
            m.coefficients.astype(np.float32), ref.astype(np.float32))
        np.testing.assert_array_equal(
            np.asarray(coord.last_tracker.iterations), it_ref)
        nb = len(ds.blocks)
        assert cursor == [(i + 1, nb) for i in range(nb)]

    def test_cold_store_warm_start(self, tmp_path):
        from photon_tpu.game.random_effect import warm_start_from_cold_store

        coord, ds, vocab = _coordinate()
        base = np.asarray(coord.update_model(None, None).coefficients)
        names = vocab.names("userId")
        proj = np.asarray(ds.projection)[: len(names)]
        p = str(tmp_path / "warm.coldstore")
        write_cold_store(p, "per_user", "userId", "u",
                         base.astype(np.float32), proj.astype(np.int32),
                         np.asarray(names))
        cold = ColdStore(p, verify=True)
        # streamed replay reproduces the table (same column spaces)
        streamed = warm_start_from_cold_store(cold, names, proj,
                                              block_rows=64)
        np.testing.assert_allclose(streamed, base.astype(np.float32),
                                   atol=0)
        # a blocked second pass from the cold tier == the all-at-once
        # second pass from the same (f32 round-tripped) warm start
        import jax.numpy as jnp

        from photon_tpu.game.model import RandomEffectModel
        from photon_tpu.types import TaskType

        # the blocked path casts the cold tier's f32 rows up to the
        # dataset dtype; the oracle must start from the same values
        prev = RandomEffectModel(
            coefficients=jnp.asarray(
                base.astype(np.float32).astype(np.float64)),
            random_effect_type="userId", feature_shard_id="u",
            task=TaskType.LOGISTIC_REGRESSION)
        oracle = np.asarray(coord.update_model(prev, None).coefficients)
        got = np.asarray(coord.update_model_blocked(
            None, warm_start=cold, entity_names=names).coefficients)
        np.testing.assert_allclose(got, oracle, rtol=1e-6, atol=1e-9)

    def test_resume_from_cursor_is_bitwise(self):
        """Preemption mid-stream: rebuilding from (table-at-cursor,
        start_block) reproduces the uninterrupted run bitwise — entities
        live in exactly one block, so the cursor fully determines which
        rows are solved vs warm."""
        coord, ds, _vocab = _coordinate()
        full = np.asarray(coord.update_model_blocked(None).coefficients)
        half = len(ds.blocks) // 2 or 1
        tbl = np.zeros_like(full)
        E = full.shape[0]
        for blk in ds.blocks[:half]:
            ents = np.asarray(blk.entity_rows)
            ok = (ents >= 0) & (ents < E)
            tbl[ents[ok]] = full[ents[ok]]
        resumed = np.asarray(coord.update_model_blocked(
            None, warm_start=tbl, start_block=half).coefficients)
        np.testing.assert_array_equal(resumed, full)

    def test_start_block_bounds(self):
        coord, ds, _vocab = _coordinate()
        with pytest.raises(ValueError):
            coord.update_model_blocked(None,
                                       start_block=len(ds.blocks) + 1)

    def test_replay_maps_columns_not_positions(self):
        """Cold slots land by GLOBAL column id, not slot position: a cold
        model trained on different per-entity feature sets contributes
        exactly its overlapping columns."""
        from photon_tpu.game.random_effect import replay_cold_rows

        ds_proj = np.asarray([[2, 5, 9], [1, 3, -1]], np.int32)
        cold_proj = np.asarray([[5, 9, 11], [3, -1, -1]], np.int32)
        cold_coef = np.asarray([[0.5, 0.9, 1.1], [0.3, 0.0, 0.0]],
                               np.float32)
        out = replay_cold_rows(ds_proj, cold_proj, cold_coef)
        np.testing.assert_array_equal(out[0],
                                      np.asarray([0.0, 0.5, 0.9], np.float32))
        np.testing.assert_array_equal(out[1],
                                      np.asarray([0.0, 0.3, 0.0], np.float32))


# -- checkpoint schema v4 ----------------------------------------------------


class TestCheckpointCursor:
    def test_cursor_roundtrip_and_default(self, tmp_path):
        import jax.numpy as jnp

        from photon_tpu.game import checkpoint as ckpt
        from photon_tpu.game.model import RandomEffectModel
        from photon_tpu.types import TaskType

        m = RandomEffectModel(jnp.ones((3, 2)), "userId", "u",
                              TaskType.LINEAR_REGRESSION)
        d = str(tmp_path / "ck")
        ckpt.save_checkpoint(d, 0, {"per_user": m}, {"per_user": 1},
                             re_block_cursor={"per_user": 2})
        state = ckpt.load_checkpoint(ckpt.latest_checkpoint(d))
        assert state.re_block_cursor == {"per_user": 2}
        # v3-style save (no cursor argument) loads with an empty map
        ckpt.save_checkpoint(d, 1, {"per_user": m}, {"per_user": 2})
        state = ckpt.load_checkpoint(ckpt.latest_checkpoint(d))
        assert state.re_block_cursor == {}

    def test_v3_meta_without_cursor_key_loads(self, tmp_path):
        """True backward compat: a checkpoint whose meta.json predates
        the key entirely (schema v3) must load with an empty cursor."""
        import json
        import zlib

        import jax.numpy as jnp

        from photon_tpu.game import checkpoint as ckpt
        from photon_tpu.game.model import RandomEffectModel
        from photon_tpu.types import TaskType

        m = RandomEffectModel(jnp.ones((3, 2)), "userId", "u",
                              TaskType.LINEAR_REGRESSION)
        d = str(tmp_path / "ck")
        path = ckpt.save_checkpoint(d, 0, {"per_user": m}, {"per_user": 1})
        meta_path = os.path.join(path, "meta.json")
        with open(meta_path) as f:
            meta = json.load(f)
        del meta["re_block_cursor"]
        meta["schema"] = 3
        with open(meta_path, "w") as f:
            json.dump(meta, f)
        state = ckpt.load_checkpoint(path)
        assert state.re_block_cursor == {}
