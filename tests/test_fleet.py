"""Entity-sharded serving fleet tests (photon_tpu/serving/fleet.py,
photon_tpu/io/fleet_store.py, photon_tpu/parallel/partition.py).

Covers the fleet contract end to end on CPU:

  * the shared partitioner: scalar / vectorized / crc-reference
    agreement, adversarial id sets (negative ids, dense ranges, one
    entity, one shard), pinned hash values (the hash may NEVER change —
    it is burned into every split cold-store file layout on disk), and
    train-placement == serve-routing via ``entity_axis_assignment``,
  * the split store: every row lands in its crc-owner's shard file,
    union of shards == source, manifest crc round-trip, torn-manifest
    refusal (chaos injector),
  * routing parity: fleet scores bitwise-equal the single-host engine
    for hot rows, cold-then-promoted rows, and no-entity requests,
  * degradation: a killed shard (chaos or admin API) yields typed
    SHARD_UNAVAILABLE fixed-only responses — never an exception, other
    shards' scores bitwise-unchanged, full parity after revival,
  * hedging: a chaos-slowed shard is overtaken by the hedged second
    attempt,
  * obs: per-shard snapshots merge through ``merge_snapshots``,
  * the shard-mode CLI entrypoint,
  * split -> per-shard serving -> one shard killed behind the router,
    end to end.
"""

import json
import os
import subprocess
import sys
import tempfile
import zlib

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from photon_tpu.io.cold_store import ColdStore, cold_store_path
from photon_tpu.io.fleet_store import (
    FleetManifestError,
    build_fleet_dir,
    read_fleet_manifest,
    shard_dir,
    shard_store_path,
)
from photon_tpu.parallel.partition import (
    entity_shard,
    entity_shards,
    partition_ids,
)
from photon_tpu.resilience import chaos
from photon_tpu.serving import (
    CoeffStoreConfig,
    FallbackReason,
    FleetConfig,
    ScoreRequest,
    ServingConfig,
    ServingEngine,
    ShardedServingFleet,
    SLOConfig,
)


# -- fixtures: a saved GAME model dir + a split fleet dir --------------------


def _build_model_dir(seed: int, out_dir: str):
    """Synthetic GAME model saved to disk with a per-coordinate cold
    store and feature-index sidecars. Returns the feature names."""
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

    rng = np.random.default_rng(seed)
    names = [f"f{j}" for j in range(17)]
    imap = IndexMap({feature_key(n, ""): i for i, n in enumerate(names)})
    D = imap.feature_dimension
    E, K = 5, 3
    coef = rng.normal(size=(E, K)).astype(np.float32)
    proj = np.zeros((E, K), np.int32)
    for e in range(E):
        proj[e] = np.sort(rng.choice(D, size=K, replace=False))
    fixed = FixedEffectModel(
        GeneralizedLinearModel(
            Coefficients(jnp.asarray(rng.normal(size=D).astype(np.float32))),
            TaskType.LINEAR_REGRESSION), "shardA")
    rem = RandomEffectModel(
        coefficients=jnp.asarray(coef), random_effect_type="userId",
        feature_shard_id="shardA", task=TaskType.LINEAR_REGRESSION)
    vocab = EntityVocabulary()
    vocab.build("userId", [f"u{e}" for e in range(E)])
    save_game_model(out_dir, GameModel({"global": fixed, "per-user": rem}),
                    {"shardA": imap}, vocab=vocab,
                    projections={"per-user": proj}, sparsity_threshold=0.0)
    return names


@pytest.fixture(scope="module")
def fleet_dirs():
    """(model_dir, fleet_dir(2 shards), names) shared by the module —
    building + splitting the model once keeps the suite fast."""
    with tempfile.TemporaryDirectory(prefix="fleet_t_") as td:
        mdir = os.path.join(td, "model")
        fdir = os.path.join(td, "fleet")
        names = _build_model_dir(7, mdir)
        build_fleet_dir(mdir, fdir, 2)
        yield mdir, fdir, names


def _mkreq(rng, uid, names, user):
    feats = [(names[j], "", float(rng.normal()))
             for j in rng.choice(len(names), size=5, replace=False)]
    return ScoreRequest(uid, {"shardA": feats},
                        {"userId": user} if user else {})


def _serving_config(hot_capacity=8):
    return ServingConfig(
        max_batch=4, max_wait_s=0.0,
        slo=SLOConfig(shed_queue_depth=60, reject_queue_depth=100),
        coeff_store=CoeffStoreConfig(hot_capacity=hot_capacity,
                                     transfer_batch=2))


def _mk_fleet(fdir, **cfg_kw):
    cfg_kw.setdefault("serving", _serving_config())
    fleet = ShardedServingFleet.from_fleet_dir(fdir, FleetConfig(**cfg_kw))
    fleet.warmup()
    return fleet


def _mk_single(mdir, two_tier=True):
    cfg = _serving_config() if two_tier else ServingConfig(
        max_batch=4, max_wait_s=0.0,
        slo=SLOConfig(shed_queue_depth=60, reject_queue_depth=100))
    engine = ServingEngine.from_model_dir(mdir, config=cfg)
    engine.warmup()
    return engine


def _bits(score):
    return np.float32(score).tobytes()


def _promote(fleet_or_engine, rng, names, users):
    """One pass of traffic + prefetch drain so ``users`` are hot."""
    reqs = [_mkreq(rng, f"pp-{i}", names, u) for i, u in enumerate(users)]
    if isinstance(fleet_or_engine, ShardedServingFleet):
        fleet_or_engine.serve(reqs)
        for c in fleet_or_engine.clients:
            c.engine.model.drain_prefetch()
    else:
        fleet_or_engine.serve(reqs)
        fleet_or_engine.model.drain_prefetch()


# -- the shared partitioner --------------------------------------------------


class TestPartitioner:
    def test_scalar_vector_and_reference_agree(self):
        rng = np.random.default_rng(3)
        ids = ([f"m{i}" for i in range(200)]
               + [f"e{int(v):09d}" for v in rng.integers(0, 10**9, 100)])
        for n in (1, 2, 3, 7, 16):
            ref = np.array([zlib.crc32(s.encode("utf-8")) % n
                            for s in ids])
            vec = entity_shards(ids, n)
            assert vec.dtype == np.int64 or np.issubdtype(
                vec.dtype, np.integer)
            np.testing.assert_array_equal(vec, ref)
            assert [entity_shard(s, n) for s in ids] == list(ref)

    def test_adversarial_id_sets(self):
        # negative numeric ids, a dense id range, one entity, one shard
        negative = [str(v) for v in range(-50, 0)]
        dense = [str(v) for v in range(1000)]
        for ids in (negative, dense, ["solo"]):
            for n in (1, 2, 16):
                ref = [zlib.crc32(s.encode("utf-8")) % n for s in ids]
                assert list(entity_shards(ids, n)) == ref
        assert list(entity_shards(dense, 1)) == [0] * len(dense)
        assert entity_shard("anything", 1) == 0
        with pytest.raises(ValueError):
            entity_shard("x", 0)

    def test_pinned_hash_values(self):
        # the partitioner is burned into on-disk shard layouts: these
        # exact values may NEVER change across refactors
        pins = {
            "u0": {2: 0, 4: 0, 16: 0},
            "u1": {2: 0, 4: 2, 16: 6},
            "u2": {2: 0, 4: 0, 16: 12},
            "u3": {2: 0, 4: 2, 16: 10},
            "u4": {2: 1, 4: 1, 16: 9},
            "e000000042": {2: 0, 4: 2, 16: 2},
            "-17": {2: 0, 4: 0, 16: 12},
        }
        for eid, by_n in pins.items():
            for n, want in by_n.items():
                assert entity_shard(eid, n) == want, (eid, n)

    def test_bytes_and_str_ids_hash_identically(self):
        ids = ["u0", "e000000042", "-17", "solo"]
        as_bytes = np.array([s.encode() for s in ids])
        np.testing.assert_array_equal(entity_shards(ids, 16),
                                      entity_shards(as_bytes, 16))

    def test_partition_ids_covers_all_rows(self):
        ids = [f"u{i}" for i in range(40)]
        parts = partition_ids(ids, 4)
        assert len(parts) == 4
        got = sorted(i for rows in parts for i in rows)
        assert got == list(range(40))
        for s, rows in enumerate(parts):
            assert all(entity_shard(ids[i], 4) == s for i in rows)

    def test_train_placement_agrees_with_serve_routing(self):
        # entity_axis_assignment (train-time placement) must be the SAME
        # function application as the fleet router's shard ownership
        import jax
        from jax.sharding import Mesh

        from photon_tpu.parallel.mesh import entity_axis_assignment

        ids = [f"u{i}" for i in range(20)] + ["-17", "e000000042"]
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        np.testing.assert_array_equal(
            entity_axis_assignment(ids, mesh),
            entity_shards(ids, 1))


# -- the split store + manifest ----------------------------------------------


class TestFleetStore:
    def test_split_layout_matches_partitioner(self, fleet_dirs):
        mdir, fdir, _ = fleet_dirs
        src = ColdStore(cold_store_path(mdir, "per-user"))
        src_ids = [i.decode() for i in src.entity_ids_array()]
        seen = {}
        for s in range(2):
            store = ColdStore(shard_store_path(fdir, s, "per-user"))
            for eid in store.entity_ids_array():
                eid = eid.decode()
                assert entity_shard(eid, 2) == s, (eid, s)
                seen[eid] = s
        assert sorted(seen) == sorted(src_ids)

    def test_manifest_round_trip(self, fleet_dirs):
        _, fdir, _ = fleet_dirs
        man = read_fleet_manifest(fdir)
        assert man["num_shards"] == 2
        assert man["partitioner"] == "crc32-utf8-mod"
        assert "per-user" in man["coordinates"]
        for s in range(2):
            assert os.path.isdir(shard_dir(fdir, s))
            assert os.path.isfile(shard_store_path(fdir, s, "per-user"))

    def test_torn_manifest_refused(self):
        with tempfile.TemporaryDirectory(prefix="fleet_torn_") as td:
            mdir, fdir = os.path.join(td, "m"), os.path.join(td, "f")
            _build_model_dir(7, mdir)
            build_fleet_dir(mdir, fdir, 2)
            removed = chaos.manifest_torn_write(fdir)
            assert removed > 0
            with pytest.raises(FleetManifestError):
                read_fleet_manifest(fdir)
            # a router must never boot on guessed shard ownership
            with pytest.raises(FleetManifestError):
                ShardedServingFleet.from_fleet_dir(fdir)


# -- routing parity vs the single-host engine --------------------------------


class TestFleetParity:
    def test_hot_rows_bitwise_equal_single_host(self, fleet_dirs):
        mdir, fdir, names = fleet_dirs
        fleet = _mk_fleet(fdir)
        single = _mk_single(mdir)
        users = [f"u{e}" for e in range(5)]
        _promote(fleet, np.random.default_rng(5), names, users * 2)
        _promote(single, np.random.default_rng(5), names, users * 2)

        rng_a, rng_b = (np.random.default_rng(11) for _ in range(2))
        for lo in range(0, 20, 4):
            batch_a = [_mkreq(rng_a, f"q{lo + i}", names,
                              users[(lo + i) % 5]) for i in range(4)]
            batch_b = [_mkreq(rng_b, f"q{lo + i}", names,
                              users[(lo + i) % 5]) for i in range(4)]
            fa = fleet.serve(batch_a)
            sb = single.serve(batch_b)
            for f, s in zip(fa, sb):
                assert not f.degraded and not s.degraded, (f, s)
                assert _bits(f.score) == _bits(s.score), f.uid
        fleet.shutdown()
        single.shutdown()

    def test_cold_then_promoted_parity(self, fleet_dirs):
        mdir, fdir, names = fleet_dirs
        fleet = _mk_fleet(fdir)
        single = _mk_single(mdir)
        rng_a, rng_b = (np.random.default_rng(13) for _ in range(2))
        # first touch: both placements cold-miss the same way (typed
        # fixed-only fallback), bitwise-equal degraded scores
        ra = fleet.serve([_mkreq(rng_a, "c0", names, "u3")])[0]
        rb = single.serve([_mkreq(rng_b, "c0", names, "u3")])[0]
        assert {f.reason for f in ra.fallbacks} \
            == {f.reason for f in rb.fallbacks}
        assert _bits(ra.score) == _bits(rb.score)
        # after promotion: full-model scores, bitwise-equal
        for c in fleet.clients:
            c.engine.model.drain_prefetch()
        single.model.drain_prefetch()
        ra = fleet.serve([_mkreq(rng_a, "c1", names, "u3")])[0]
        rb = single.serve([_mkreq(rng_b, "c1", names, "u3")])[0]
        assert not ra.degraded and not rb.degraded
        assert _bits(ra.score) == _bits(rb.score)
        fleet.shutdown()
        single.shutdown()

    def test_requests_without_entities_score_at_the_front(self, fleet_dirs):
        mdir, fdir, names = fleet_dirs
        fleet = _mk_fleet(fdir)
        single = _mk_single(mdir)
        rng_a, rng_b = (np.random.default_rng(17) for _ in range(2))
        ra = fleet.serve([_mkreq(rng_a, "n0", names, None)])[0]
        rb = single.serve([_mkreq(rng_b, "n0", names, None)])[0]
        assert _bits(ra.score) == _bits(rb.score)
        assert sum(st.requests for st in fleet._stats.values()) == 0
        fleet.shutdown()
        single.shutdown()


# -- degradation: killed shards ----------------------------------------------


class TestFleetDegradation:
    def _routed_users(self):
        # u4 is the only shard-1 user under 2 shards (pinned above)
        return ["u0", "u1", "u2", "u3"], ["u4"]

    def test_chaos_killed_shard_degrades_typed(self, fleet_dirs):
        mdir, fdir, names = fleet_dirs
        fleet = _mk_fleet(fdir)
        s0_users, s1_users = self._routed_users()
        users = [u for pair in zip(s0_users, s1_users * 4)
                 for u in pair]
        _promote(fleet, np.random.default_rng(5), names, users)

        def scores(tag):
            rng = np.random.default_rng(23)
            out = []
            for i, u in enumerate(users):
                out.append(fleet.serve(
                    [_mkreq(rng, f"{tag}{i}", names, u)])[0])
            return out

        healthy = scores("h")
        assert all(not r.degraded for r in healthy)
        with chaos.active(chaos.ChaosConfig(shard_kill_id=1)):
            killed = scores("k")
        for h, k, u in zip(healthy, killed, users):
            assert k.score is not None
            if u in s1_users:     # owner down -> typed fixed-only
                assert k.degraded
                assert any(f.reason == FallbackReason.SHARD_UNAVAILABLE
                           for f in k.fallbacks), k
            else:                 # other shards bitwise-unaffected
                assert not k.degraded
                assert _bits(k.score) == _bits(h.score)
        st = fleet.stats()
        assert st["merged"]["counters"]["fleet.shard.unavailable"] > 0
        # chaos uninstalled: full parity returns, no residual state
        recovered = scores("r")
        for h, r in zip(healthy, recovered):
            assert not r.degraded and _bits(r.score) == _bits(h.score)
        fleet.shutdown()

    def test_admin_kill_and_revive(self, fleet_dirs):
        mdir, fdir, names = fleet_dirs
        fleet = _mk_fleet(fdir)
        _promote(fleet, np.random.default_rng(5), names,
                 ["u0", "u4", "u0", "u4"])
        rng = np.random.default_rng(29)
        fleet.kill_shard(1)
        r = fleet.serve([_mkreq(rng, "a0", names, "u4")])[0]
        assert r.degraded and any(
            f.reason == FallbackReason.SHARD_UNAVAILABLE
            for f in r.fallbacks)
        assert fleet.stats()["per_shard"][1]["alive"] is False
        fleet.revive_shard(1)
        r = fleet.serve([_mkreq(rng, "a1", names, "u4")])[0]
        assert not r.degraded
        fleet.shutdown()


# -- hedging -----------------------------------------------------------------


class TestFleetHedging:
    def test_slow_shard_is_hedged(self, fleet_dirs):
        mdir, fdir, names = fleet_dirs
        fleet = _mk_fleet(fdir, hedge_timeout_s=0.02)
        _promote(fleet, np.random.default_rng(5), names,
                 ["u4", "u4", "u4", "u4"])
        rng = np.random.default_rng(31)
        with chaos.active(chaos.ChaosConfig(
                shard_slow_id=1, shard_slow_s=0.4,
                shard_slow_requests=1)):
            r = fleet.serve([_mkreq(rng, "s0", names, "u4")])[0]
        assert r.score is not None and not r.degraded
        assert fleet._stats[1].hedges >= 1
        fleet.shutdown()


# -- obs ---------------------------------------------------------------------


class TestFleetObs:
    def test_per_shard_snapshots_merge(self, fleet_dirs):
        mdir, fdir, names = fleet_dirs
        fleet = _mk_fleet(fdir)
        rng = np.random.default_rng(37)
        for i in range(8):
            fleet.serve([_mkreq(rng, f"o{i}", names, f"u{i % 5}")])
        st = fleet.stats()
        merged = st["merged"]["counters"]["fleet.shard.requests"]
        per_shard = sum(v["requests"] for v in st["per_shard"].values())
        assert merged == per_shard > 0
        hist = st["merged"]["histograms"]["fleet.shard.latency_seconds"]
        assert hist["count"] == merged
        for v in st["per_shard"].values():
            assert v["breaker_state"] == "closed"
            assert v["alive"] is True
        fleet.shutdown()


# -- CLI ---------------------------------------------------------------------


class TestFleetCli:
    def test_shard_mode_serves_and_reports_stats(self, fleet_dirs):
        mdir, fdir, names = fleet_dirs
        rng = np.random.default_rng(41)
        lines = []
        for i in range(6):
            feats = [[names[j], "", float(rng.normal())]
                     for j in rng.choice(len(names), size=5,
                                         replace=False)]
            lines.append(json.dumps(
                {"uid": f"r{i}", "features": {"shardA": feats},
                 "ids": {"userId": f"u{i % 5}"}}))
        lines.append(json.dumps({"control": "stats"}))
        proc = subprocess.run(
            [sys.executable, "-m", "photon_tpu.cli.serve",
             "--fleet-manifest", fdir, "--shard-id", "0",
             "--max-wait-ms", "0"],
            input="\n".join(lines) + "\n", capture_output=True,
            text=True, cwd=REPO, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == 0, proc.stderr[-2000:]
        outs = [json.loads(l) for l in proc.stdout.splitlines()
                if l.strip()]
        scored = [o for o in outs if "uid" in o]
        ctrl = [o for o in outs if o.get("control") == "stats"]
        assert len(scored) == 6
        assert ctrl and ctrl[0]["ok"]
        # shard 0 owns u0..u3; u4 is an unknown entity HERE (typed
        # fallback, not an error) — routing is the fleet router's job
        assert all(o["score"] is not None for o in scored)

    def test_shard_mode_requires_shard_id(self, fleet_dirs):
        _, fdir, _ = fleet_dirs
        proc = subprocess.run(
            [sys.executable, "-m", "photon_tpu.cli.serve",
             "--fleet-manifest", fdir],
            input="", capture_output=True, text=True, cwd=REPO,
            timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode != 0


# -- split -> per-shard serving -> kill one shard, end to end ----------------
#
# A 20,000-entity cold store is split across two shard stores by the
# canonical partitioner; each shard engine serves the Zipf traffic it
# owns under the compile monitors; then the router serves identical
# traffic before and during a chaos kill of one shard. One run; each
# gate is one case.

_Q_E, _Q_K, _Q_D, _Q_NNZ, _Q_SEED = 20_000, 2, 32, 16, 13


def _quick_row_ids(rows):
    return np.char.add(b"e", np.char.zfill(
        np.asarray(rows).astype("S9"), 9))


def _quick_shard_engine(store_path, names, theta=None):
    from photon_tpu.io.index_map import IndexMap, feature_key
    from photon_tpu.io.model_io import (
        ServingFixedEffect,
        ServingGameModel,
        ServingRandomEffect,
    )
    from photon_tpu.serving import DeviceResidentModel
    from photon_tpu.types import TaskType

    imap = IndexMap({feature_key(n, ""): i for i, n in enumerate(names)})
    res = ([ServingRandomEffect("per_user", "userId", "g",
                                cold_store_path=store_path)]
           if store_path else [])
    fixed = ([ServingFixedEffect("fixed", "g", theta)]
             if theta is not None else [])
    cs = (CoeffStoreConfig(hot_capacity=512, transfer_batch=64)
          if store_path else None)
    m = ServingGameModel(TaskType.LINEAR_REGRESSION, fixed, res,
                         {"g": imap}, {})
    return ServingEngine(DeviceResidentModel(m, coeff_store=cs),
                         ServingConfig(max_batch=64, max_wait_s=0.001,
                                       coeff_store=cs))


def _quick_serve_owned(engine, names, rows, seed, n_warm=250,
                       n_steady=400):
    """Warm, then serve ``n_steady`` owned requests under the three
    compile monitors. Returns (responses served, zero compiles)."""
    from photon_tpu.obs.metrics import registry
    from photon_tpu.serving.scorer import get_scorer, serving_modes
    from photon_tpu.utils import compile_cache

    rng = np.random.default_rng(seed)
    rows = list(rows) * ((n_warm + n_steady) // max(len(rows), 1) + 1)

    def req(i):
        cols = rng.choice(_Q_D, size=_Q_NNZ, replace=False)
        return ScoreRequest(f"q{i}", {"g": [(names[c], "", float(
            rng.normal())) for c in cols]}, {"userId": f"e{rows[i]:09d}"})

    for i in range(n_warm):
        engine.submit(req(i))
        if i % 256 == 255:
            engine.pump()
    engine.drain()
    engine.model.drain_prefetch()
    jitted = [get_scorer(engine.model, mode, b)
              for mode in serving_modes(engine.model)
              for b in engine.ladder.buckets]
    jitted = [f if hasattr(f, "_cache_size")
              else getattr(f, "__wrapped__", f) for f in jitted]
    jitted = [f for f in jitted if hasattr(f, "_cache_size")]
    compiles0 = compile_cache.compile_counts()["steady_state"]
    misses0 = registry.counter("jitcache.misses").value
    traces0 = [f._cache_size() for f in jitted]
    done = 0
    for i in range(n_warm, n_warm + n_steady):
        engine.submit(req(i))
        done += len(engine.pump())
    done += len(engine.drain())
    zero = (compile_cache.compile_counts()["steady_state"] == compiles0
            and registry.counter("jitcache.misses").value == misses0
            and all(t1 <= t0 for t0, t1 in zip(
                traces0, [f._cache_size() for f in jitted])))
    return done, zero


@pytest.fixture(scope="module")
def fleet_quick_run():
    from photon_tpu.io.cold_store import COLD_STORE_DIR, write_cold_store
    from photon_tpu.serving import LocalShardClient

    rng = np.random.default_rng(_Q_SEED)
    names = [f"g{j}" for j in range(_Q_D)]
    coef = rng.normal(size=(_Q_E, _Q_K)).astype(np.float32)
    lo = rng.integers(0, _Q_D - 1, size=_Q_E)
    hi = rng.integers(lo + 1, _Q_D)
    proj = np.stack([lo, hi], axis=1).astype(np.int32)
    theta = rng.normal(size=_Q_D).astype(np.float32)
    with tempfile.TemporaryDirectory(prefix="fleet_q_") as td:
        mdir, fdir = os.path.join(td, "model"), os.path.join(td, "fleet")
        os.makedirs(os.path.join(mdir, COLD_STORE_DIR))
        write_cold_store(cold_store_path(mdir, "per_user"), "per_user",
                         "userId", "g", coef, proj,
                         _quick_row_ids(np.arange(_Q_E)))
        build_fleet_dir(mdir, fdir, 2)
        manifest = read_fleet_manifest(fdir)

        trng = np.random.default_rng(_Q_SEED)
        rows = (trng.zipf(1.5, size=2 * 680 + 64) - 1) % _Q_E
        owners = entity_shards(_quick_row_ids(rows), 2)
        served, zero = [], []
        for s in range(2):
            eng = _quick_shard_engine(shard_store_path(fdir, s, "per_user"),
                                      names)
            eng.warmup()
            done, ok = _quick_serve_owned(eng, names, rows[owners == s],
                                          seed=_Q_SEED + 1000 + s)
            eng.shutdown()
            served.append(done)
            zero.append(ok)

        # the router over both shards; identical traffic before and
        # during the kill
        cs = CoeffStoreConfig(hot_capacity=512, transfer_batch=64)
        cfg = ServingConfig(max_batch=64, max_wait_s=0.001, coeff_store=cs)
        clients = []
        for s in range(2):
            eng = _quick_shard_engine(shard_store_path(fdir, s, "per_user"),
                                      names)
            clients.append(LocalShardClient(s, eng))
        fleet = ShardedServingFleet(
            _quick_shard_engine(None, names, theta), clients,
            [("per_user", "userId")], FleetConfig(serving=cfg))
        fleet.warmup()
        frng = np.random.default_rng(_Q_SEED + 7)
        krows = (frng.zipf(1.5, size=20 * 64) - 1) % _Q_E
        batches = [[ScoreRequest(
            f"k{b * 64 + i}",
            {"g": [(names[c], "", float(frng.normal()))
                   for c in frng.choice(_Q_D, size=_Q_NNZ, replace=False)]},
            {"userId": f"e{krows[b * 64 + i]:09d}"}) for i in range(64)]
            for b in range(20)]

        def segment():
            scored = degraded = 0
            for batch in batches:
                for r in fleet.serve(batch):
                    scored += r.score is not None
                    degraded += any(
                        f.reason == FallbackReason.SHARD_UNAVAILABLE
                        for f in r.fallbacks)
            return scored, degraded

        segment()                          # promote the kill rows
        pre_scored, pre_degraded = segment()
        with chaos.active(chaos.ChaosConfig(shard_kill_id=1)):
            post_scored, post_degraded = segment()
        counters = fleet.stats()["merged"]["counters"]
        fleet.shutdown()
    n = 20 * 64
    yield {
        "manifest_verified": manifest["num_shards"] == 2,
        "every_shard_served_its_traffic": served == [400, 400],
        "zero_steady_state_compiles_all_shards": all(zero),
        "no_degradation_before_kill": pre_degraded == 0,
        "typed_shard_unavailable": (
            post_degraded > 0
            and counters["fleet.shard.unavailable"] > 0),
        "no_score_dropped": pre_scored == n and post_scored == n,
    }


@pytest.mark.parametrize("gate", [
    "manifest_verified", "every_shard_served_its_traffic",
    "zero_steady_state_compiles_all_shards", "no_degradation_before_kill",
    "typed_shard_unavailable", "no_score_dropped"])
def test_fleet_split_serve_kill(fleet_quick_run, gate):
    assert fleet_quick_run[gate] is True, fleet_quick_run
