"""Elastic serving fleet tests (photon_tpu/serving/migrate.py,
photon_tpu/serving/autoscale.py, the v2 virtual-bucket partition in
photon_tpu/parallel/partition.py and photon_tpu/io/fleet_store.py).

Covers the elastic contract end to end on CPU:

  * the virtual-bucket partitioner: pinned crc32 bucket values (burned
    into every v2 fleet layout on disk — they may NEVER change),
    bucket -> shard composition, the v1 identity-map equivalence, and
    ``BucketMap`` round-trip/validation,
  * manifest compat: v1 read as the degenerate identity map, v2 round
    trip, unknown FUTURE schemas refused typed naming the schema
    string, a v1 doc smuggling a bucket_map refused, and the
    ``manifest_torn_write`` chaos injector against a v2 manifest,
  * hedging: a shard KNOWN dead at hedge-arm time never gets a hedge
    (the second attempt would burn a pool slot racing an answer that
    cannot come), while a live-but-slow shard still does,
  * live migration: copy -> double-read -> reconcile -> cutover with
    routed traffic flowing through the window — served scores stay
    bitwise-identical to the settled baseline the whole way, the only
    visible artifact is a typed BUCKET_MIGRATING fallback, and the
    steady-state compile counter stays frozen,
  * mismatch abort: a tampered destination copy poisons the window,
    cutover is refused typed, the new copy is never served, and
    ``abort`` rolls the destination back,
  * chaos kills at every phase (mid-copy, mid-double-read with a FULL
    process restart, between destination commit and manifest bump):
    torn state is refused typed, the old map keeps serving, and
    ``resume_migration`` restores a bitwise-clean fleet,
  * elastic fleet ops: add/remove guards, ``provision_shard`` /
    ``decommission_shard`` manifest discipline, v1 refusal,
  * the autoscaler: gauge-share decisions on synthetic snapshots and a
    full split -> drain round trip under traffic,
  * the whole lifecycle under replayed traffic: a gauge-driven split
    and a drain back down mid-replay, then a chaos kill mid-copy and
    its resume, one case a gate.
"""

import json
import os
import shutil
import tempfile
import time
import zlib

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from photon_tpu.io.cold_store import (
    ColdStore,
    ColdStoreCorruptError,
    apply_cold_store_delta,
)
from photon_tpu.io.fleet_store import (
    FLEET_MANIFEST_SCHEMA,
    FLEET_MANIFEST_SCHEMA_V2,
    FleetManifestError,
    build_fleet_dir,
    read_fleet_manifest,
    shard_store_path,
    write_fleet_manifest,
)
from photon_tpu.parallel.partition import (
    DEFAULT_NUM_BUCKETS,
    BucketMap,
    entity_bucket,
    entity_buckets,
    entity_shard,
    entity_shards,
    validate_num_buckets,
)
from photon_tpu.resilience import chaos
from photon_tpu.serving import (
    AutoscaleConfig,
    BucketMigrator,
    FallbackReason,
    FleetConfig,
    HotShardAutoscaler,
    MigrationError,
    ShardedServingFleet,
    decommission_shard,
    provision_shard,
    read_migration_journal,
    resume_migration,
)
from photon_tpu.serving.migrate import MIGRATION_JOURNAL_FILE
from photon_tpu.utils import compile_cache

from test_fleet import _build_model_dir, _mkreq, _serving_config

#: the module fleet splits with 32 virtual buckets over 2 shards;
#: under BucketMap.initial(32, 2), u4 (bucket 25) is the lone seeded
#: entity on shard 1 — the bucket every migration test moves
NB = 32
B_U4 = 25


# -- fixtures ----------------------------------------------------------------


@pytest.fixture(scope="module")
def elastic_base():
    """model dir + a pristine v2 fleet dir (2 shards, 32 buckets),
    built once; tests that mutate the fleet dir copy it first."""
    with tempfile.TemporaryDirectory(prefix="elastic_t_") as td:
        mdir = os.path.join(td, "model")
        fdir = os.path.join(td, "fleet_v2")
        names = _build_model_dir(7, mdir)
        build_fleet_dir(mdir, fdir, 2, num_buckets=NB)
        yield mdir, fdir, names


@pytest.fixture()
def elastic_fleet_dir(elastic_base, tmp_path):
    """A fresh mutable copy of the pristine v2 fleet dir."""
    mdir, fdir, names = elastic_base
    dst = os.path.join(str(tmp_path), "fleet")
    shutil.copytree(fdir, dst)
    return mdir, dst, names


def _mk_fleet(fdir, **cfg_kw):
    cfg_kw.setdefault("serving", _serving_config())
    fleet = ShardedServingFleet.from_fleet_dir(fdir, FleetConfig(**cfg_kw))
    fleet.warmup()
    return fleet


def _mk_reqs(seed, names, n=10):
    """A FIXED request list (u0..u4 round-robin) reused across serves so
    bitwise score comparisons are meaningful."""
    rng = np.random.default_rng(seed)
    users = [f"u{i % 5}" for i in range(n)]
    return [_mkreq(rng, f"q{i}", names, u)
            for i, u in enumerate(users)], users


def _score_bits(resps):
    return [None if r.score is None else np.float32(r.score).tobytes()
            for r in resps]


def _drain(fleet):
    for c in fleet.clients:
        c.engine.model.drain_prefetch()


def _settle(fleet, reqs, rounds=8):
    """Serve until the two-tier stores are promoted (no COLD_MISS) —
    the settled responses are the bitwise baseline."""
    for _ in range(rounds):
        resps = fleet.serve(reqs)
        _drain(fleet)
        if not any(f.reason == FallbackReason.COLD_MISS
                   for r in resps for f in r.fallbacks):
            return resps
    return fleet.serve(reqs)


# -- the virtual-bucket partitioner ------------------------------------------


#: crc32 % n for power-of-two bucket counts: burned into every v2 fleet
#: layout on disk, these exact values may NEVER change across refactors
_PINS = {
    "u0": {64: 32, 256: 224, 1024: 992},
    "u1": {64: 54, 256: 118, 1024: 886},
    "u2": {64: 12, 256: 204, 1024: 716},
    "u3": {64: 26, 256: 90, 1024: 602},
    "u4": {64: 57, 256: 249, 1024: 1017},
    "e000000042": {64: 18, 256: 210, 1024: 466},
    "-17": {64: 28, 256: 28, 1024: 540},
    "solo": {64: 17, 256: 17, 1024: 17},
}


class TestBucketPartitioner:
    def test_pinned_bucket_values(self):
        for eid, by_n in _PINS.items():
            for n, want in by_n.items():
                assert entity_bucket(eid, n) == want, (eid, n)
                assert zlib.crc32(eid.encode()) % n == want, (eid, n)
        assert DEFAULT_NUM_BUCKETS == 1024
        assert entity_bucket("u4") == _PINS["u4"][1024]
        assert entity_bucket("u4", NB) == B_U4

    def test_vectorized_agrees_and_pow2_gate(self):
        ids = list(_PINS) + [f"m{i}" for i in range(100)]
        for n in (64, 1024):
            np.testing.assert_array_equal(
                entity_buckets(ids, n),
                [zlib.crc32(s.encode()) % n for s in ids])
        for bad in (0, -4, 3, 48):
            with pytest.raises(ValueError):
                entity_bucket("x", bad)
            with pytest.raises(ValueError):
                validate_num_buckets(bad)
        assert validate_num_buckets(1024) == 1024

    def test_bucket_to_shard_composition(self):
        bm = BucketMap.initial(64, 3)
        ids = list(_PINS) + [str(v) for v in range(-20, 40)]
        for eid in ids:
            b = entity_bucket(eid, 64)
            assert bm.bucket_of(eid) == b
            assert bm.shard_of(b) == b % 3
            assert bm.shard_for_entity(eid) == b % 3
        np.testing.assert_array_equal(
            bm.shards_for_ids(ids),
            [bm.shard_for_entity(e) for e in ids])

    def test_identity_map_is_v1_routing(self):
        # the degenerate map must route bitwise-identically to the v1
        # single-level partition for ANY shard count (pow2 or not)
        ids = list(_PINS) + [str(v) for v in range(-10, 30)]
        for n in (1, 2, 3, 7):
            bm = BucketMap.identity(n)
            assert bm.num_buckets == n and bm.num_shards == n
            np.testing.assert_array_equal(bm.shards_for_ids(ids),
                                          entity_shards(ids, n))
            for eid in ids:
                assert bm.shard_for_entity(eid) == entity_shard(eid, n)

    def test_with_assignment_and_round_trip(self):
        bm = BucketMap.initial(NB, 2)
        assert bm.assignment == tuple(b % 2 for b in range(NB))
        assert bm.shard_ids == (0, 1)
        moved = bm.with_assignment(B_U4, 5)
        assert moved.shard_of(B_U4) == 5
        assert all(moved.shard_of(b) == bm.shard_of(b)
                   for b in range(NB) if b != B_U4)
        assert bm.shard_of(B_U4) == 1     # the original is immutable
        assert BucketMap.from_json(moved.to_json()) == moved
        assert B_U4 in moved.buckets_on(5)
        assert bm.buckets_on(5) == ()

    def test_validation_errors(self):
        with pytest.raises(ValueError):
            BucketMap.initial(32, 33)     # a shard would own no bucket
        with pytest.raises(ValueError):
            BucketMap.initial(31, 2)      # new layouts pin power of two
        with pytest.raises(ValueError):
            BucketMap(2, (0,))            # length mismatch
        with pytest.raises(ValueError):
            BucketMap(2, (0, -1))         # negative shard id
        for bad in ("x", {"num_buckets": 2}, {"assignment": [0, 1]},
                    {"num_buckets": "2", "assignment": [0, 1]}):
            with pytest.raises(ValueError):
                BucketMap.from_json(bad)


# -- manifest compat ---------------------------------------------------------


class TestManifestCompat:
    def test_v1_manifest_reads_as_identity_map(self, elastic_base, tmp_path):
        mdir, _, _ = elastic_base
        fdir = os.path.join(str(tmp_path), "fleet_v1")
        build_fleet_dir(mdir, fdir, 2)
        doc = read_fleet_manifest(fdir)
        assert doc["schema"] == FLEET_MANIFEST_SCHEMA
        bm = BucketMap.from_json(doc["bucket_map"])
        assert bm == BucketMap.identity(2)

    def test_v2_manifest_round_trip(self, elastic_base):
        _, fdir, _ = elastic_base
        doc = read_fleet_manifest(fdir)
        assert doc["schema"] == FLEET_MANIFEST_SCHEMA_V2
        bm = BucketMap.from_json(doc["bucket_map"])
        assert bm == BucketMap.initial(NB, 2)
        assert bm.shard_for_entity("u4") == 1

    def test_unknown_future_schema_refused_typed(self, elastic_fleet_dir):
        _, fdir, _ = elastic_fleet_dir
        doc = read_fleet_manifest(fdir)
        doc["schema"] = "photon_tpu.fleet.manifest.v3"
        write_fleet_manifest(fdir, doc)   # crc-valid, schema from the future
        with pytest.raises(FleetManifestError,
                           match="unknown schema.*manifest.v3"):
            read_fleet_manifest(fdir)
        # a router must never boot on a manifest it cannot interpret
        with pytest.raises(FleetManifestError):
            ShardedServingFleet.from_fleet_dir(fdir)

    def test_v1_doc_carrying_bucket_map_refused(self, elastic_base, tmp_path):
        mdir, _, _ = elastic_base
        fdir = os.path.join(str(tmp_path), "fleet_v1")
        build_fleet_dir(mdir, fdir, 2)
        # read_fleet_manifest injects the identity map; writing that doc
        # back verbatim is exactly a torn v1->v2 upgrade
        doc = read_fleet_manifest(fdir)
        assert "bucket_map" in doc
        write_fleet_manifest(fdir, doc)
        with pytest.raises(FleetManifestError, match="torn upgrade"):
            read_fleet_manifest(fdir)

    def test_manifest_torn_write_v2(self, elastic_fleet_dir):
        _, fdir, _ = elastic_fleet_dir
        removed = chaos.manifest_torn_write(fdir)
        assert removed > 0
        with pytest.raises(FleetManifestError):
            read_fleet_manifest(fdir)
        with pytest.raises(FleetManifestError):
            ShardedServingFleet.from_fleet_dir(fdir)


# -- hedging vs known-dead shards --------------------------------------------


class TestHedgeDeadShard:
    def test_no_hedge_for_known_dead_shard(self, elastic_fleet_dir):
        """A hop whose shard is KNOWN dead at hedge-arm time must not
        arm a hedge — the second attempt would burn a pool slot racing
        an answer that cannot come."""
        mdir, fdir, names = elastic_fleet_dir
        fleet = _mk_fleet(fdir, hedge_timeout_s=0.01)
        try:
            rng = np.random.default_rng(13)
            sid = fleet.bucket_map.shard_for_entity("u4")
            client = fleet._by_id[sid]

            def slow_dead(reqs):
                time.sleep(0.08)
                return None

            client.serve = slow_dead     # a remote that died mid-flight
            client.alive = False
            resps = fleet.serve([_mkreq(rng, "hx", names, "u4")])
            assert fleet._stats[sid].hedges == 0
            assert any(f.reason == FallbackReason.SHARD_UNAVAILABLE
                       for f in resps[0].fallbacks)

            # control: the SAME lag on a live shard still hedges
            del client.serve             # back to the class method
            client.alive = True
            orig = type(client).serve

            def slow_live(reqs):
                time.sleep(0.05)
                return orig(client, reqs)

            client.serve = slow_live
            fleet.serve([_mkreq(rng, "hy", names, "u4")])
            assert fleet._stats[sid].hedges >= 1
            del client.serve
        finally:
            fleet.shutdown()


# -- live migration ----------------------------------------------------------


class TestLiveMigration:
    def test_happy_path_bitwise_through_window(self, elastic_fleet_dir):
        mdir, fdir, names = elastic_fleet_dir
        fleet = _mk_fleet(fdir)
        try:
            assert fleet.bucket_map.num_buckets == NB
            assert fleet.bucket_map.shard_for_entity("u4") == 1
            reqs, users = _mk_reqs(11, names)
            base = _score_bits(_settle(fleet, reqs))
            assert all(b is not None for b in base)
            c0 = compile_cache.compile_counts().get("steady_state", 0)
            v0 = read_fleet_manifest(fdir)["version"]

            m = BucketMigrator(fleet, B_U4, 0)
            copied = m.copy()
            assert sum(copied.values()) >= 1
            assert read_migration_journal(fdir)["phase"] == "copy"
            w = m.open_double_read()

            # routed traffic THROUGH the double-read window
            for _ in range(3):
                resps = fleet.serve(reqs)
                assert _score_bits(resps) == base
                for r, u in zip(resps, users):
                    migrating = any(
                        f.reason == FallbackReason.BUCKET_MIGRATING
                        for f in r.fallbacks)
                    assert migrating == (u == "u4")
                _drain(fleet)
            assert w.double_reads > 0
            assert w.mismatches == 0 and not w.aborted

            m.reconcile()
            res = m.cutover()
            assert res["version"] == v0 + 1
            assert res["double_reads"] == w.double_reads
            assert fleet.bucket_map.shard_of(B_U4) == 0
            assert fleet.migration_windows() == {}
            assert read_migration_journal(fdir) is None
            doc = read_fleet_manifest(fdir)
            assert doc["schema"] == FLEET_MANIFEST_SCHEMA_V2
            assert BucketMap.from_json(doc["bucket_map"]).shard_of(B_U4) == 0

            post = _settle(fleet, reqs)
            assert _score_bits(post) == base
            assert not any(f.reason == FallbackReason.BUCKET_MIGRATING
                           for r in post for f in r.fallbacks)
            # the whole migration compiled NOTHING new
            assert compile_cache.compile_counts().get(
                "steady_state", 0) == c0
        finally:
            fleet.shutdown()

    def test_mismatch_poisons_window_and_abort_rolls_back(
            self, elastic_fleet_dir):
        mdir, fdir, names = elastic_fleet_dir
        fleet = _mk_fleet(fdir)
        try:
            reqs, _ = _mk_reqs(17, names)
            base = _score_bits(_settle(fleet, reqs))
            m = BucketMigrator(fleet, B_U4, 0)
            m.copy()
            w = m.open_double_read()

            # tamper the DESTINATION copy: the double-read must catch it
            dst_path = shard_store_path(fdir, 0, "per-user")
            st = ColdStore(dst_path)
            r = st.entity_row("u4")
            assert r is not None
            rows = np.asarray([r], np.int64)
            apply_cold_store_delta(
                dst_path, update_rows=rows,
                update_coef=st.read_rows(rows) + np.float32(0.25),
                update_proj=st.read_proj_rows(rows))
            m._refresh(0, "per-user")

            during = []
            for _ in range(3):
                during.append(_score_bits(fleet.serve(reqs)))
                _drain(fleet)
            assert w.mismatches >= 1 and w.aborted
            assert w.mismatch_detail
            # the source stayed authoritative: served bits never moved
            assert all(bits == base for bits in during)
            with pytest.raises(MigrationError, match="poisoned"):
                m.cutover()
            assert fleet.bucket_map.shard_of(B_U4) == 1

            m.abort("tampered destination")
            assert fleet.migration_windows() == {}
            assert read_migration_journal(fdir) is None
            assert _score_bits(_settle(fleet, reqs)) == base
        finally:
            fleet.shutdown()


# -- chaos: kills at every phase ---------------------------------------------


class TestMigrationChaos:
    def test_kill_mid_copy_then_resume(self, elastic_fleet_dir):
        mdir, fdir, names = elastic_fleet_dir
        fleet = _mk_fleet(fdir)
        try:
            reqs, _ = _mk_reqs(31, names)
            base = _score_bits(_settle(fleet, reqs))
            m = BucketMigrator(fleet, B_U4, 0)
            with chaos.active(chaos.ChaosConfig(
                    kill_publish_ops=("bucket_copy",))):
                with pytest.raises(chaos.SimulatedKill):
                    m.copy()
            j = read_migration_journal(fdir)
            assert j["phase"] == "copy" and j["bucket"] == B_U4
            # the destination file is torn — and typed-refused
            with pytest.raises(ColdStoreCorruptError):
                ColdStore(shard_store_path(fdir, 0, "per-user")).verify()
            # the router never read the copy: the old map keeps serving
            assert _score_bits(fleet.serve(reqs)) == base

            out = resume_migration(fleet)
            assert out["resumed_phase"] == "copy" and out["dst"] == 0
            assert read_migration_journal(fdir) is None
            assert fleet.bucket_map.shard_of(B_U4) == 0
            ColdStore(shard_store_path(fdir, 0, "per-user")).verify()
            assert _score_bits(_settle(fleet, reqs)) == base
        finally:
            fleet.shutdown()

    def test_kill_mid_double_read_fresh_process_resume(
            self, elastic_fleet_dir):
        """Die mid-window, then a FULL restart: a fresh fleet boots off
        the old manifest (no window), the journal names the phase, and
        resume rolls the migration forward bitwise."""
        mdir, fdir, names = elastic_fleet_dir
        fleet = _mk_fleet(fdir)
        reqs, _ = _mk_reqs(37, names)
        base = _score_bits(_settle(fleet, reqs))
        m = BucketMigrator(fleet, B_U4, 0)
        m.copy()
        m.open_double_read()
        fleet.serve(reqs)
        fleet.shutdown()                  # the process "dies" mid-window

        fleet2 = _mk_fleet(fdir)
        try:
            assert fleet2.bucket_map.shard_of(B_U4) == 1   # old map
            assert fleet2.migration_windows() == {}
            assert read_migration_journal(fdir)["phase"] == "double_read"
            assert _score_bits(_settle(fleet2, reqs)) == base
            out = resume_migration(fleet2)
            assert out["resumed_phase"] == "double_read"
            assert fleet2.bucket_map.shard_of(B_U4) == 0
            assert read_migration_journal(fdir) is None
            assert _score_bits(_settle(fleet2, reqs)) == base
        finally:
            fleet2.shutdown()

    def test_kill_between_commit_and_manifest_bump(self, elastic_fleet_dir):
        mdir, fdir, names = elastic_fleet_dir
        fleet = _mk_fleet(fdir)
        try:
            reqs, _ = _mk_reqs(41, names)
            base = _score_bits(_settle(fleet, reqs))
            m = BucketMigrator(fleet, B_U4, 0)
            m.copy()
            m.open_double_read()
            for _ in range(2):
                fleet.serve(reqs)
                _drain(fleet)
            m.reconcile()
            v0 = read_fleet_manifest(fdir)["version"]
            with chaos.active(chaos.ChaosConfig(
                    kill_publish_ops=("fleet_manifest",))):
                with pytest.raises(chaos.SimulatedKill):
                    m.cutover()
            # the atomic bump never landed: OLD manifest intact, owner
            # unchanged, journal pinned at cutover, fleet still serving
            doc = read_fleet_manifest(fdir)
            assert doc["version"] == v0
            assert BucketMap.from_json(doc["bucket_map"]).shard_of(
                B_U4) == 1
            assert fleet.bucket_map.shard_of(B_U4) == 1
            assert read_migration_journal(fdir)["phase"] == "cutover"
            assert _score_bits(fleet.serve(reqs)) == base

            out = resume_migration(fleet)
            assert out["resumed_phase"] == "cutover"
            assert read_fleet_manifest(fdir)["version"] == v0 + 1
            assert fleet.bucket_map.shard_of(B_U4) == 0
            assert read_migration_journal(fdir) is None
            assert _score_bits(_settle(fleet, reqs)) == base
        finally:
            fleet.shutdown()

    def test_torn_journal_refused_typed(self, elastic_fleet_dir):
        _, fdir, _ = elastic_fleet_dir
        # no journal: nothing in flight
        assert resume_migration(object(), fleet_dir=fdir) is None
        path = os.path.join(fdir, MIGRATION_JOURNAL_FILE)
        # torn mid-write
        with open(path, "w") as f:
            f.write('{"schema": "photon_tpu.fleet.migration.v1", "buc')
        with pytest.raises(MigrationError, match="unreadable"):
            read_migration_journal(fdir)
        with pytest.raises(MigrationError):
            resume_migration(object(), fleet_dir=fdir)
        # crc mismatch
        doc = {"schema": "photon_tpu.fleet.migration.v1", "bucket": B_U4,
               "src": 1, "dst": 0, "num_buckets": NB, "phase": "copy",
               "coordinates": ["per-user"], "crc": 1}
        with open(path, "w") as f:
            json.dump(doc, f)
        with pytest.raises(MigrationError, match="crc mismatch"):
            read_migration_journal(fdir)
        # unknown schema names the schema string
        doc["schema"] = "photon_tpu.fleet.migration.v9"
        with open(path, "w") as f:
            json.dump(doc, f)
        with pytest.raises(MigrationError, match="migration.v9"):
            read_migration_journal(fdir)


# -- elastic fleet ops -------------------------------------------------------


class TestElasticOps:
    def test_provision_and_decommission(self, elastic_fleet_dir):
        mdir, fdir, names = elastic_fleet_dir
        fleet = _mk_fleet(fdir)
        try:
            reqs, _ = _mk_reqs(43, names)
            base = _score_bits(_settle(fleet, reqs))
            v0 = read_fleet_manifest(fdir)["version"]
            doc = provision_shard(fleet, 5)
            assert doc["num_shards"] == 3 and fleet.num_shards == 3
            assert doc["version"] == v0 + 1
            st = ColdStore(shard_store_path(fdir, 5, "per-user"))
            assert st.num_entities == 0    # empty, updatable, idle
            # an idle provisioned shard changes nothing the router serves
            assert _score_bits(fleet.serve(reqs)) == base
            # refuse removing a shard that still owns buckets
            with pytest.raises(ValueError, match="still owns buckets"):
                fleet.remove_shard(0)
            doc2 = decommission_shard(fleet, 5)
            assert doc2["num_shards"] == 2 and fleet.num_shards == 2
            assert _score_bits(fleet.serve(reqs)) == base
        finally:
            fleet.shutdown()

    def test_provision_refused_on_v1_layout(self, elastic_base, tmp_path):
        mdir, _, names = elastic_base
        fdir = os.path.join(str(tmp_path), "fleet_v1")
        build_fleet_dir(mdir, fdir, 2)
        fleet = _mk_fleet(fdir)
        try:
            with pytest.raises(MigrationError, match="v2 virtual-bucket"):
                provision_shard(fleet, 2)
        finally:
            fleet.shutdown()


# -- the autoscaler ----------------------------------------------------------


class _FakeRegistry:
    def __init__(self, shares, interval_s=1.0):
        self._snap = {"timeseries": {
            'fleet.shard.responses{shard="%d"}' % sid: {
                "kind": "counter", "interval_s": interval_s,
                "labels": {"shard": str(sid)},
                "windows": [{"idx": 0, "value": float(v)}],
            } for sid, v in shares.items()}}

    def snapshot(self):
        return self._snap


class TestAutoscaler:
    def test_decisions_on_synthetic_gauges(self, elastic_fleet_dir):
        mdir, fdir, names = elastic_fleet_dir
        fleet = _mk_fleet(fdir)
        try:
            cfg = AutoscaleConfig(hot_factor=1.5, cold_factor=0.25)
            # hot skew -> split the hot shard
            s = HotShardAutoscaler(fleet, cfg,
                                   registry=_FakeRegistry({0: 90, 1: 10}))
            assert s.decide() == {"action": "split", "shard": 0,
                                  "share": 90.0, "mean": 50.0}
            # balanced -> hold
            s = HotShardAutoscaler(fleet, cfg,
                                   registry=_FakeRegistry({0: 50, 1: 50}))
            assert s.decide() is None
            # cold shard (without a hot one) -> drain
            cfg2 = AutoscaleConfig(hot_factor=10.0, cold_factor=0.25)
            s = HotShardAutoscaler(fleet, cfg2,
                                   registry=_FakeRegistry({0: 30, 1: 1}))
            assert s.decide() == {"action": "drain", "shard": 1,
                                  "share": 1.0, "mean": 15.5}
            # below min_total -> hold (no signal)
            s = HotShardAutoscaler(
                fleet, AutoscaleConfig(min_total=100.0),
                registry=_FakeRegistry({0: 30, 1: 1}))
            assert s.decide() is None
            # at min_shards a drain is never proposed
            s = HotShardAutoscaler(
                fleet, AutoscaleConfig(hot_factor=10.0, min_shards=2),
                registry=_FakeRegistry({0: 30, 1: 1}))
            assert s.decide() is None
        finally:
            fleet.shutdown()

    def test_split_then_drain_end_to_end(self, elastic_fleet_dir):
        mdir, fdir, names = elastic_fleet_dir
        fleet = _mk_fleet(fdir)
        try:
            reqs, _ = _mk_reqs(23, names)
            base = _score_bits(_settle(fleet, reqs))
            scaler = HotShardAutoscaler(
                fleet, AutoscaleConfig(hot_factor=1.5, buckets_per_step=2),
                serving=_serving_config())
            shares = scaler.shard_shares()
            assert set(shares) == {0, 1}

            # split shard 0 (owns u0..u3): provision shard 2, move the
            # two hottest buckets, traffic flows through the windows
            plan = scaler.step({"action": "split", "shard": 0})
            assert plan["new_shard"] == 2 and len(plan["buckets"]) == 2
            assert fleet.num_shards == 3
            for _ in range(3):
                assert _score_bits(fleet.serve(reqs)) == base
                _drain(fleet)
            wins = fleet.migration_windows()
            assert set(wins) == set(plan["buckets"])
            assert all(w["mismatches"] == 0 for w in wins.values())
            assert any(w["double_reads"] > 0 for w in wins.values())
            done = scaler.finish()
            assert len(done["results"]) == 2
            assert all(fleet.bucket_map.shard_of(b) == 2
                       for b in plan["buckets"])
            assert _score_bits(_settle(fleet, reqs)) == base

            # drain shard 2 straight back and decommission it
            plan2 = scaler.step({"action": "drain", "shard": 2})
            assert set(plan2["buckets"]) == set(plan["buckets"])
            for _ in range(2):
                assert _score_bits(fleet.serve(reqs)) == base
                _drain(fleet)
            scaler.finish()
            assert fleet.num_shards == 2
            doc = read_fleet_manifest(fdir)
            assert doc["num_shards"] == 2
            assert all(sh["shard_id"] in (0, 1) for sh in doc["shards"])
            assert _score_bits(_settle(fleet, reqs)) == base
        finally:
            fleet.shutdown()

    def test_step_refused_while_plan_in_flight(self, elastic_fleet_dir):
        mdir, fdir, names = elastic_fleet_dir
        fleet = _mk_fleet(fdir)
        try:
            reqs, _ = _mk_reqs(29, names)
            _settle(fleet, reqs)
            scaler = HotShardAutoscaler(fleet, AutoscaleConfig(),
                                        serving=_serving_config())
            scaler.step({"action": "split", "shard": 0})
            with pytest.raises(MigrationError, match="not finished"):
                scaler.step({"action": "split", "shard": 1})
            scaler.abort()                 # bitwise rollback, windows shut
            assert fleet.migration_windows() == {}
            assert read_migration_journal(fdir) is None
            assert scaler.step({"action": "split", "shard": 0}) is not None
            scaler.finish()
        finally:
            fleet.shutdown()


# -- the elastic lifecycle under replayed traffic, end to end ---------------
#
# A v2 virtual-bucket fleet (two-tier stores) serves a deterministic
# Zipf+burst stream on a virtual clock while scheduled actions drive a
# gauge-driven hot-shard split and a drain back down mid-replay; then a
# chaos kill mid-copy resumes. One run; each gate is one case.

_Q_E, _Q_K, _Q_D, _Q_NB = 64, 2, 16, 32
_Q_INTERVAL, _Q_TICK = 0.25, 0.05


def _quick_model_dir(out_dir, seed):
    """Saved GAME model whose entity ids match the replay generator's
    ``e{:09d}``: a fixed effect on shard ``g`` plus a cold-backed
    ``per_user`` coordinate. Returns the entity ids."""
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
    imap = IndexMap({feature_key(f"f{j}", ""): j for j in range(_Q_D)})
    ids = [f"e{i:09d}" for i in range(_Q_E)]
    coef = rng.normal(size=(_Q_E, _Q_K)).astype(np.float32)
    proj = np.zeros((_Q_E, _Q_K), np.int32)
    for e in range(_Q_E):
        proj[e] = np.sort(rng.choice(_Q_D, size=_Q_K, replace=False))
    fixed = FixedEffectModel(
        GeneralizedLinearModel(
            Coefficients(jnp.asarray(
                rng.normal(size=_Q_D).astype(np.float32))),
            TaskType.LINEAR_REGRESSION), "g")
    rem = RandomEffectModel(
        coefficients=jnp.asarray(coef), random_effect_type="userId",
        feature_shard_id="g", task=TaskType.LINEAR_REGRESSION)
    vocab = EntityVocabulary()
    vocab.build("userId", ids)
    save_game_model(out_dir, GameModel({"global": fixed, "per_user": rem}),
                    {"g": imap}, vocab=vocab,
                    projections={"per_user": proj}, sparsity_threshold=0.0)
    return ids


def _compile_monitors(fleet):
    """Steady-state compile events, jitcache misses and per-program
    trace counts over every engine of the fleet."""
    from photon_tpu.obs.metrics import registry
    from photon_tpu.serving.scorer import get_scorer, serving_modes

    engines = [fleet.front] + [c.engine for c in fleet.clients]
    programs = [get_scorer(e.model, mode, b) for e in engines
                for mode in serving_modes(e.model)
                for b in e.ladder.buckets]
    jitted = [p if hasattr(p, "_cache_size")
              else getattr(p, "__wrapped__", p) for p in programs]
    return (compile_cache.compile_counts()["steady_state"],
            registry.counter("jitcache.misses").value,
            [f._cache_size() for f in jitted if hasattr(f, "_cache_size")])


@pytest.fixture(scope="module")
def elastic_lifecycle():
    from photon_tpu import obs
    from photon_tpu.obs import slo
    from photon_tpu.obs import timeseries as tsmod
    from photon_tpu.serving import CoeffStoreConfig, ScoreRequest
    from photon_tpu.serving import ServingConfig, SLOConfig
    from photon_tpu.serving.replay import (
        Replayer,
        TrafficProfile,
        VirtualClock,
        generate,
    )

    seed = 32
    max_batch, n_requests, base_qps, n_probe = 16, 1_000, 150.0, 24
    interval0 = tsmod.series.interval_s
    # every windowed series shares one window grid on the virtual clock
    tsmod.series.interval_s = _Q_INTERVAL
    obs.reset()
    td = tempfile.mkdtemp(prefix="elastic_q_")
    try:
        profile = TrafficProfile(
            kind="burst", n_requests=n_requests, entities=_Q_E, zipf_a=1.5,
            base_qps=base_qps, feature_dim=_Q_D, nnz=4, burst_at_s=1.0,
            burst_len_s=1.0, burst_factor=3.0)
        records = generate(profile, seed)
        ts_all = [t for t, _ in records]
        # split opens inside the burst, drains after it
        t_split = ts_all[int(0.25 * n_requests)]
        t_split_done = ts_all[int(0.45 * n_requests)]
        t_drain = ts_all[int(0.65 * n_requests)]
        t_drain_done = ts_all[int(0.80 * n_requests)]

        mdir, fdir = os.path.join(td, "model"), os.path.join(td, "fleet")
        ids = _quick_model_dir(mdir, seed)
        build_fleet_dir(mdir, fdir, 2, num_buckets=_Q_NB)
        clk = VirtualClock()
        serving_cfg = ServingConfig(
            max_batch=max_batch, max_wait_s=0.0,
            slo=SLOConfig(shed_queue_depth=5_000, reject_queue_depth=10_000),
            coeff_store=CoeffStoreConfig(hot_capacity=256, transfer_batch=8))
        fleet = ShardedServingFleet.from_fleet_dir(
            fdir, FleetConfig(serving=serving_cfg), clock=clk)
        fleet.warmup()

        frng = np.random.default_rng(seed)
        id_bucket = {eid: entity_bucket(eid, _Q_NB) for eid in ids}

        def req(uid, eid):
            cols = frng.choice(_Q_D, size=4, replace=False)
            return ScoreRequest(
                uid, {"g": [(f"f{c}", "", float(frng.normal()))
                            for c in cols]}, {"userId": eid})

        # promote every entity first: degradation gates then measure the
        # migrations, not promotion cold misses
        all_reqs = [req(f"s{i}", eid) for i, eid in enumerate(ids)]
        _settle(fleet, all_reqs, rounds=10)
        probes = [req(f"p{i}", ids[i]) for i in range(n_probe)]
        base_bits = _score_bits(_settle(fleet, probes, rounds=10))
        mon0 = _compile_monitors(fleet)
        scaler = HotShardAutoscaler(
            fleet,
            AutoscaleConfig(hot_factor=1.02, cold_factor=0.25, min_shards=2,
                            max_shards=3, buckets_per_step=2,
                            lookback_windows=8, min_total=1.0),
            serving=serving_cfg)
        st = {"parity": [], "windows": [], "split": {}, "drain": {}}

        def migrated_reqs(buckets):
            bset = {int(b) for b in buckets}
            sub = [r for r, eid in zip(all_reqs, ids)
                   if id_bucket[eid] in bset]
            return sub[:max_batch * 4] or probes

        def window_counts():
            wins = fleet.migration_windows().values()
            return {"double_reads": sum(w["double_reads"] for w in wins),
                    "mismatches": sum(w["mismatches"] for w in wins)}

        def open_window(plan, phase):
            # warm the destination through the double-read mirrors, so
            # replayed traffic compares bitwise instead of cold-missing
            st[phase].update(buckets=[int(b) for b in plan["buckets"]],
                             t_open=clk.now())
            warm = migrated_reqs(plan["buckets"])
            for _ in range(4):
                fleet.serve(warm)
                _drain(fleet)
            st["parity"].append(_score_bits(fleet.serve(probes)))

        def close_window(phase):
            st["windows"].append(window_counts())
            done = scaler.finish()
            st[phase].update(t_cutover=clk.now(),
                             results=len(done["results"]),
                             num_shards=fleet.num_shards)
            _settle(fleet, migrated_reqs(st[phase]["buckets"]), rounds=10)
            st["parity"].append(_score_bits(
                _settle(fleet, probes, rounds=10)))

        def act_split():
            dec = scaler.decide()
            st["gauge_decision"] = dict(dec) if dec else None
            if not (dec and dec["action"] == "split"):
                shares = scaler.shard_shares()
                dec = {"action": "split",
                       "shard": max(shares, key=lambda s: (shares[s], -s))}
            plan = scaler.step(dec)
            st["split"]["new_shard"] = int(plan["new_shard"])
            open_window(plan, "split")

        def act_split_done():
            close_window("split")
            sp = st["split"]
            sp["owners_moved"] = all(
                fleet.bucket_map.shard_of(b) == sp["new_shard"]
                for b in sp["buckets"])

        def act_drain():
            st["drain"]["shard"] = st["split"]["new_shard"]
            open_window(scaler.step({"action": "drain",
                                     "shard": st["drain"]["shard"]}),
                        "drain")

        def act_drain_done():
            close_window("drain")
            dr = st["drain"]
            dr["owners_off"] = all(fleet.bucket_map.shard_of(b) != dr["shard"]
                                   for b in dr["buckets"])

        res = Replayer(fleet, clk, tick_s=_Q_TICK).run(
            records, [(t_split, act_split), (t_split_done, act_split_done),
                      (t_drain, act_drain), (t_drain_done, act_drain_done)])
        mon1 = _compile_monitors(fleet)
        compile_delta = (
            (mon1[0] - mon0[0]) + (mon1[1] - mon0[1])
            + sum(max(0, b - a) for a, b in zip(mon0[2], mon1[2])))

        # chaos: kill the copy of the busiest bucket mid-flight, resume
        loads = {b: sum(1 for eid in ids if id_bucket[eid] == b)
                 for b in fleet.bucket_map.buckets_on(0)}
        b2 = max(loads, key=lambda b: (loads[b], -b))
        dst2 = next(s for s in fleet.bucket_map.shard_ids if s != 0)
        killed = False
        with chaos.active(chaos.ChaosConfig(
                kill_publish_ops=("bucket_copy",))):
            try:
                BucketMigrator(fleet, b2, dst2).copy()
            except chaos.SimulatedKill:
                killed = True
        j_kill = read_migration_journal(fdir)
        served_during = _score_bits(fleet.serve(probes)) == base_bits
        out = resume_migration(fleet)
        ColdStore(shard_store_path(fdir, dst2, "per_user")).verify()
        resumed = (out is not None and fleet.bucket_map.shard_of(b2) == dst2
                   and read_migration_journal(fdir) is None)
        _settle(fleet, migrated_reqs([b2]), rounds=10)
        post_bits = _score_bits(_settle(fleet, probes, rounds=10))

        # SLO verdicts: p99 breaches may only sit in migration windows
        mig_idx = set()
        for ph in (st["split"], st["drain"]):
            if "t_open" in ph and "t_cutover" in ph:
                mig_idx.update(range(
                    int(ph["t_open"] // _Q_INTERVAL),
                    int((ph["t_cutover"] + _Q_TICK) // _Q_INTERVAL) + 2))
        rules = [
            slo.P99Ceiling(
                rule_id="p99", series="replay.latency",
                ceiling_s=4 * _Q_TICK, qps_series="replay.responses",
                qps_floor=0.25 * base_qps),
            slo.MaxDegradationRate(
                rule_id="no_shard_unavailable",
                degraded_series="replay.degraded",
                total_series="replay.responses", max_rate=0.0,
                degraded_labels={"reason": "shard_unavailable"}),
            slo.ZeroSteadyStateCompiles(rule_id="compiles"),
        ]
        by_rule = {v.rule_id: v for v in slo.evaluate(
            slo.SLOSpec(rules), tsmod.series.snapshot(),
            compile_delta=compile_delta)}
        sp, dr = st["split"], st["drain"]
        wins = st["windows"] + [{}, {}]
        gates = {
            "scale_out_completed": bool(
                sp.get("owners_moved") and sp.get("results", 0) >= 1
                and sp.get("num_shards") == 3),
            "scale_in_completed": bool(
                dr.get("owners_off") and dr.get("num_shards") == 2
                and read_fleet_manifest(fdir)["num_shards"] == 2),
            "gauge_driven_split": bool(
                st.get("gauge_decision")
                and st["gauge_decision"].get("action") == "split"),
            "zero_downtime": bool(
                all(b is not None for b in base_bits) and res.refusals == 0
                and set(res.degraded_reasons) <= {"bucket_migrating"}
                and by_rule["no_shard_unavailable"].status == slo.PASS),
            "double_read_parity": all(
                w.get("double_reads", 0) > 0 and w.get("mismatches", 1) == 0
                for w in wins[:2]),
            "zero_steady_state_compiles": bool(
                compile_delta == 0
                and by_rule["compiles"].status == slo.PASS),
            "survivor_bitwise_parity": bool(
                st["parity"]
                and all(pb == base_bits for pb in st["parity"])),
            "p99_outside_migration_windows": (
                by_rule["p99"].status == slo.PASS
                or {w["idx"] for w in by_rule["p99"].offending_windows}
                <= mig_idx),
            "chaos_kill_resume": bool(
                killed and j_kill is not None and j_kill["phase"] == "copy"
                and served_during and resumed and post_bits == base_bits),
        }
        fleet.shutdown()
        yield gates
    finally:
        shutil.rmtree(td, ignore_errors=True)
        tsmod.series.interval_s = interval0
        obs.reset()


@pytest.mark.parametrize("gate", [
    "scale_out_completed", "scale_in_completed", "gauge_driven_split",
    "zero_downtime", "double_read_parity", "zero_steady_state_compiles",
    "survivor_bitwise_parity", "p99_outside_migration_windows",
    "chaos_kill_resume"])
def test_elastic_lifecycle_under_replay(elastic_lifecycle, gate):
    assert elastic_lifecycle[gate] is True, elastic_lifecycle
