"""Nearline delta-training pipeline tests (photon_tpu/nearline).

Covers the whole loop against live engines on CPU:

  * event log: watermark resume, checkpoint crc refusal, torn tails,
    duplicate shard replay, out-of-order delivery (chaos injectors),
  * delta trainer: only the entities the events touch are re-solved,
  * delta publisher: bitwise parity vs a full retrain-and-swap of the
    same solve results, untouched rows bitwise-unchanged, bitwise
    rollback on both placements, UNKNOWN_ENTITY -> scored appends,
    poison-row readback rollback,
  * crash seams: kill between manifest and checkpoint (exactly-once
    recovery), kill mid cold-store delta (torn-update refusal + heal by
    replay from the unadvanced watermark),
  * admission lookahead: pending-publish rows are never prefetched,
  * obs (RunReport section), the CLI driver, and the closed loop:
    delta rounds published while a thread serves the same engine.
"""

import json
import os
import tempfile
import time

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from photon_tpu.io.cold_store import (
    ColdStore,
    ColdStoreCorruptError,
    cold_store_path,
)
from photon_tpu.nearline import (
    DeltaPublisher,
    DeltaTrainer,
    EventLogReader,
    EventLogWriter,
    NearlineCheckpointError,
    NearlineConfig,
    NearlinePipeline,
    NearlinePublishConfig,
    load_checkpoint,
    save_checkpoint,
)
from photon_tpu.nearline.delta_trainer import current_entity_row
from photon_tpu.obs.metrics import registry as _metrics
from photon_tpu.resilience import chaos
from photon_tpu.resilience.chaos import SimulatedKill
from photon_tpu.serving import (
    CoeffStoreConfig,
    ScoreRequest,
    ServingConfig,
    ServingEngine,
    SLOConfig,
)


# -- fixtures: a saved GAME model dir + engines on both placements -----------


def _build_model_dir(seed: int, out_dir: str):
    """Synthetic GAME model saved to disk with a per-coordinate cold
    store and feature-index sidecars; the seed only varies coefficient
    values. Returns the feature names for request/event building."""
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


def _mk_engine(model_dir: str, two_tier: bool, clock=None) -> ServingEngine:
    cfg = dict(max_batch=4, max_wait_s=0.0,
               slo=SLOConfig(shed_queue_depth=60, reject_queue_depth=100),
               append_reserve=4)
    if two_tier:
        cfg["coeff_store"] = CoeffStoreConfig(hot_capacity=4,
                                              transfer_batch=2)
    engine = ServingEngine.from_model_dir(
        model_dir, config=ServingConfig(**cfg), clock=clock)
    assert engine.model.has_stores == two_tier
    engine.warmup()
    return engine


def _mkreq(rng, uid, names, user):
    feats = [(names[j], "", float(rng.normal()))
             for j in rng.choice(len(names), size=5, replace=False)]
    return ScoreRequest(uid, {"shardA": feats}, {"userId": user})


def _mkevent(rng, names, user, ts):
    feats = [[names[j], "", float(rng.normal())]
             for j in rng.choice(len(names), size=5, replace=False)]
    return {"ts": ts, "response": float(rng.normal()),
            "features": {"shardA": feats}, "entities": {"userId": user}}


def _drive(engine, rng, names, users, n=12):
    """Serve a little traffic so recent_requests has a shadow sample."""
    for lo in range(0, n, 4):
        engine.serve([_mkreq(rng, f"d{lo}-{i}", names, users[i % len(users)])
                      for i in range(min(4, n - lo))])
    engine.model.drain_prefetch()


def _write_events(log_dir, rng, names, users, per_user=4, ts=None):
    w = EventLogWriter(log_dir)
    ts = time.time() if ts is None else ts
    w.append([_mkevent(rng, names, u, ts) for u in users
              for _ in range(per_user)])
    return w


def _pipeline(engine, log_dir, model_dir, **pub_kw):
    pub_kw.setdefault("parity_tol", 1e-3)
    return NearlinePipeline(
        engine, log_dir, model_dir=model_dir,
        config=NearlineConfig(publish=NearlinePublishConfig(**pub_kw)))


def _rows(engine, entities):
    """{entity: (coef, proj)} snapshot of the live serving rows."""
    rs = engine.model.random[0]
    D = engine.model.shard_dims["shardA"]
    return {e: current_entity_row(rs, e, D) for e in entities}


# -- event log: watermarks, checkpoints, chaos delivery ----------------------


def test_event_log_watermark_resume_across_shards():
    with tempfile.TemporaryDirectory(prefix="nl_ev_") as td:
        rng = np.random.default_rng(0)
        names = [f"f{j}" for j in range(17)]
        w = EventLogWriter(td, shard_records=3)
        w.append([_mkevent(rng, names, f"u{i}", 1.0) for i in range(4)])
        r1 = EventLogReader(td)
        got = r1.poll()
        assert [ev["seq"] for ev in got] == [0, 1, 2, 3]
        assert r1.max_seq == 3

        # checkpoint, write more (new shard after rotation), resume
        ckpt = os.path.join(td, "ck", "checkpoint.json")
        os.makedirs(os.path.dirname(ckpt))
        save_checkpoint(ckpt, r1.state(), published_version=7)
        w.append([_mkevent(rng, names, "u9", 2.0) for _ in range(3)])
        r2 = EventLogReader(td)
        doc = load_checkpoint(ckpt)
        assert doc is not None and doc["published_version"] == 7
        r2.restore(doc["state"])
        got2 = r2.poll()
        assert [ev["seq"] for ev in got2] == [4, 5, 6]
        assert r2.poll() == []
        assert load_checkpoint(os.path.join(td, "absent.json")) is None


def test_checkpoint_crc_refusal():
    with tempfile.TemporaryDirectory(prefix="nl_ck_") as td:
        path = os.path.join(td, "checkpoint.json")
        save_checkpoint(path, {"max_seq": 5, "shards": {}},
                        published_version=1)
        doc = json.loads(open(path).read())
        doc["state"]["max_seq"] = 99          # tamper without fixing crc
        with open(path, "w") as f:
            json.dump(doc, f)
        with pytest.raises(NearlineCheckpointError):
            load_checkpoint(path)


def test_torn_tail_held_back_then_new_shard_polls():
    with tempfile.TemporaryDirectory(prefix="nl_torn_") as td:
        rng = np.random.default_rng(1)
        names = [f"f{j}" for j in range(17)]
        w = EventLogWriter(td)
        w.append([_mkevent(rng, names, f"u{i}", 1.0) for i in range(4)])
        shard = os.path.join(td, sorted(os.listdir(td))[0])
        removed = chaos.torn_tail_write(shard)
        assert removed > 0

        r = EventLogReader(td)
        got = r.poll()
        # complete records before the tear are consumed; the torn final
        # record is neither parsed nor advanced past
        assert [ev["seq"] for ev in got] == [0, 1, 2]
        assert r.stats["torn_records"] == 1
        assert r.poll() == []                  # tail still torn: no spin
        assert r.stats["torn_records"] == 1    # ...and counted only once

        # the dead writer's replacement starts a new shard; it polls fine
        w2 = EventLogWriter(td, start_seq=4)
        w2.append([_mkevent(rng, names, "u7", 2.0) for _ in range(2)])
        got2 = r.poll()
        assert [ev["seq"] for ev in got2] == [4, 5]


def test_duplicate_shard_replay_fully_deduped():
    with tempfile.TemporaryDirectory(prefix="nl_dup_") as td:
        rng = np.random.default_rng(2)
        names = [f"f{j}" for j in range(17)]
        w = EventLogWriter(td)
        w.append([_mkevent(rng, names, f"u{i}", 1.0) for i in range(5)])
        r = EventLogReader(td)
        assert len(r.poll()) == 5
        chaos.duplicate_shard_replay(td, seed=3)
        assert r.poll() == []
        assert r.stats["duplicates"] == 5


def test_out_of_order_delivery_resorted_and_counted():
    with tempfile.TemporaryDirectory(prefix="nl_ooo_") as td:
        rng = np.random.default_rng(3)
        names = [f"f{j}" for j in range(17)]
        w = EventLogWriter(td)
        w.append([_mkevent(rng, names, f"u{i}", 1.0) for i in range(8)])
        shard = os.path.join(td, sorted(os.listdir(td))[0])
        moved = chaos.shuffle_shard_records(shard, seed=5)
        assert moved > 0
        r = EventLogReader(td)
        got = r.poll()
        assert [ev["seq"] for ev in got] == list(range(8))  # re-sorted
        assert r.stats["out_of_order"] > 0


# -- delta trainer: dirty entities only --------------------------------------


def test_trainer_resolves_only_touched_entities():
    with tempfile.TemporaryDirectory(prefix="nl_tr_") as td:
        d = os.path.join(td, "m")
        names = _build_model_dir(7, d)
        engine = _mk_engine(d, two_tier=False)
        try:
            rng = np.random.default_rng(11)
            trainer = DeltaTrainer(engine, model_dir=d)
            events = [_mkevent(rng, names, "u1", 1.0) for _ in range(6)]
            for i, ev in enumerate(events):
                ev["seq"] = i
            delta = trainer.train(events)
            assert delta.num_rows == 1
            cd = delta.coordinates["per-user"]
            assert set(cd.rows) == {"u1"}
            coef, proj = cd.rows["u1"]
            assert np.isfinite(coef).all()
            # warm-started from the live row, but the events moved it
            live = current_entity_row(engine.model.random[0], "u1",
                                      engine.model.shard_dims["shardA"])
            assert coef.tobytes() != live[0].tobytes()
        finally:
            engine.shutdown()


# -- delta publish: parity vs full retrain-and-swap, untouched rows ----------


def test_delta_publish_bitwise_matches_full_swap():
    """The tentpole acceptance: publishing delta rows into the live
    tables must be bitwise-identical — same rows, same served scores —
    to a full retrain-and-swap that bakes the SAME solve results into a
    complete candidate model."""
    from photon_tpu.io.model_io import (
        ServingGameModel,
        ServingRandomEffect,
        load_for_serving,
    )
    from photon_tpu.serving.swap import swap_staged

    with tempfile.TemporaryDirectory(prefix="nl_par_") as td:
        d = os.path.join(td, "m")
        names = _build_model_dir(7, d)
        eng_a = _mk_engine(d, two_tier=False)
        eng_b = _mk_engine(d, two_tier=False)
        try:
            rng = np.random.default_rng(21)
            _drive(eng_a, rng, names, [f"u{i}" for i in range(5)])
            log_dir = os.path.join(td, "log")
            _write_events(log_dir, rng, names,
                          ["u0", "u1", "u2", "newuser"])
            pipe = _pipeline(eng_a, log_dir, d)
            s = pipe.run_round()
            pub = s["publish"]
            assert pub["accepted"], pub
            assert pub["rows_updated"] == 3 and pub["rows_appended"] == 1

            # rebuild the SAME rows as a full candidate model for B
            touched = ["u0", "u1", "u2", "newuser"]
            published = _rows(eng_a, touched)
            base = load_for_serving(d)
            (re,) = base.random
            coef = np.asarray(re.coefficients, np.float32).copy()
            proj = np.asarray(re.projection, np.int32).copy()
            entity_rows = dict(re.entity_rows)
            app_coef, app_proj = [], []
            for e in touched:
                c, p = published[e]
                if e in entity_rows:
                    coef[entity_rows[e]] = c
                    proj[entity_rows[e]] = p
                else:
                    entity_rows[e] = len(coef) + len(app_coef)
                    app_coef.append(c)
                    app_proj.append(p)
            coef = np.vstack([coef] + app_coef)
            proj = np.vstack([proj] + app_proj)
            candidate = ServingGameModel(
                base.task, base.fixed,
                [ServingRandomEffect(re.coordinate_id,
                                     re.random_effect_type,
                                     re.feature_shard_id, coef, proj,
                                     entity_rows)],
                base.index_maps, base.metadata)
            _drive(eng_b, np.random.default_rng(21), names,
                   [f"u{i}" for i in range(5)])
            swap = swap_staged(eng_b, candidate, "full-retrain")
            assert swap.accepted, (swap.reason, swap.gates)

            # rows bitwise-equal between the two publish mechanisms
            rows_b = _rows(eng_b, touched)
            for e in touched:
                assert published[e][0].tobytes() == rows_b[e][0].tobytes(), e
                assert published[e][1].tobytes() == rows_b[e][1].tobytes(), e

            # and the scores the two engines serve are identical
            rq = np.random.default_rng(33)
            reqs = [_mkreq(rq, f"q{i}", names, touched[i % len(touched)])
                    for i in range(8)]
            sa = [r.score for r in eng_a.serve(reqs)]
            sb = [r.score for r in eng_b.serve(reqs)]
            assert sa == sb
        finally:
            eng_a.shutdown()
            eng_b.shutdown()


def test_untouched_rows_bitwise_unchanged():
    with tempfile.TemporaryDirectory(prefix="nl_unt_") as td:
        d = os.path.join(td, "m")
        names = _build_model_dir(7, d)
        engine = _mk_engine(d, two_tier=False)
        try:
            rng = np.random.default_rng(31)
            _drive(engine, rng, names, [f"u{i}" for i in range(5)])
            before = _rows(engine, ["u3", "u4"])
            log_dir = os.path.join(td, "log")
            _write_events(log_dir, rng, names, ["u0", "u1"])
            pipe = _pipeline(engine, log_dir, d)
            s = pipe.run_round()
            assert s["publish"]["accepted"], s["publish"]
            after = _rows(engine, ["u3", "u4"])
            for e in ("u3", "u4"):
                assert before[e][0].tobytes() == after[e][0].tobytes()
                assert before[e][1].tobytes() == after[e][1].tobytes()
        finally:
            engine.shutdown()


# -- append path, rollback, poison -------------------------------------------


@pytest.mark.parametrize("two_tier", [False, True],
                         ids=["full_resident", "two_tier"])
def test_unknown_entity_append_then_bitwise_rollback(two_tier):
    with tempfile.TemporaryDirectory(prefix="nl_app_") as td:
        d = os.path.join(td, "m")
        names = _build_model_dir(7, d)
        engine = _mk_engine(d, two_tier=two_tier)
        try:
            rng = np.random.default_rng(41)
            users = [f"u{i}" for i in range(5)]
            _drive(engine, rng, names, users)

            # pre-publish: the new entity is a typed UNKNOWN_ENTITY
            pre = engine.serve([_mkreq(rng, "pre", names, "newuser")])[0]
            assert "UNKNOWN_ENTITY" in {f.reason.name for f in pre.fallbacks}
            before = _rows(engine, ["u0", "u1", "u2"])

            log_dir = os.path.join(td, "log")
            _write_events(log_dir, rng, names, ["u0", "u1", "u2", "newuser"])
            pipe = _pipeline(engine, log_dir, d)
            s = pipe.run_round()
            pub = s["publish"]
            assert pub["accepted"], pub
            assert pub["rows_appended"] == 1

            if two_tier:
                r = _mkreq(rng, "warm", names, "newuser")
                engine.model.prefetch_request(r)
                engine.model.drain_prefetch()
            post = engine.serve([_mkreq(rng, "post", names, "newuser")])[0]
            assert "UNKNOWN_ENTITY" not in \
                {f.reason.name for f in post.fallbacks}

            # rollback restores the prior rows bitwise; appends vanish
            assert pipe.publisher.rollback_last("test")
            after = _rows(engine, ["u0", "u1", "u2", "newuser"])
            assert after["newuser"] is None
            for e in ("u0", "u1", "u2"):
                assert before[e][0].tobytes() == after[e][0].tobytes(), e
                assert before[e][1].tobytes() == after[e][1].tobytes(), e
            # the watermark stands: rolled-back events are not replayed
            assert pipe.run_round()["events"] == 0
        finally:
            engine.shutdown()


def test_publish_poison_row_caught_by_readback_and_rolled_back():
    with tempfile.TemporaryDirectory(prefix="nl_poi_") as td:
        d = os.path.join(td, "m")
        names = _build_model_dir(7, d)
        engine = _mk_engine(d, two_tier=False)
        try:
            rng = np.random.default_rng(51)
            _drive(engine, rng, names, [f"u{i}" for i in range(5)])
            before = _rows(engine, ["u0", "u1"])
            log_dir = os.path.join(td, "log")
            _write_events(log_dir, rng, names, ["u0", "u1"])
            pipe = _pipeline(engine, log_dir, d)
            rollbacks0 = _metrics.counter("nearline.publish.rollbacks").value
            with chaos.active(chaos.ChaosConfig(publish_poison_row=True)):
                s = pipe.run_round()
            pub = s["publish"]
            assert not pub["accepted"]
            assert pub["gates"]["verify"] == "fail"
            assert pub["rolled_back"]
            assert _metrics.counter("nearline.publish.rollbacks").value \
                == rollbacks0 + 1
            after = _rows(engine, ["u0", "u1"])
            for e in ("u0", "u1"):
                assert before[e][0].tobytes() == after[e][0].tobytes(), e
            # no NaN ever reached the live scores
            resp = engine.serve([_mkreq(rng, "q", names, "u0")])[0]
            assert np.isfinite(resp.score)
        finally:
            engine.shutdown()


# -- crash seams: exactly-once + torn cold update ----------------------------


def test_kill_between_manifest_and_checkpoint_recovers_exactly_once():
    """The exactly-once handshake: a crash after the manifest landed but
    before the reader checkpoint advanced must NOT replay the events —
    recovery adopts the manifest's watermark."""
    with tempfile.TemporaryDirectory(prefix="nl_k1_") as td:
        d = os.path.join(td, "m")
        names = _build_model_dir(7, d)
        engine = _mk_engine(d, two_tier=False)
        try:
            rng = np.random.default_rng(61)
            _drive(engine, rng, names, [f"u{i}" for i in range(5)])
            log_dir = os.path.join(td, "log")
            _write_events(log_dir, rng, names, ["u0", "u1"])
            pipe = _pipeline(engine, log_dir, d)
            with chaos.active(chaos.ChaosConfig(
                    kill_publish_ops=("nearline_checkpoint",))):
                with pytest.raises(SimulatedKill):
                    pipe.run_round()
            # rows are live, manifest durable, checkpoint missing
            assert pipe.publisher.version == 1
            assert load_checkpoint(pipe.checkpoint_path) is None

            published = _rows(engine, ["u0", "u1"])
            pipe2 = _pipeline(engine, log_dir, d)
            assert pipe2.recovered
            assert pipe2.publisher.version == 1
            # no replay: the recovered watermark already covers the log
            assert pipe2.run_round()["events"] == 0
            # and the live rows were untouched by recovery
            now = _rows(engine, ["u0", "u1"])
            for e in ("u0", "u1"):
                assert published[e][0].tobytes() == now[e][0].tobytes()
            ck = load_checkpoint(pipe2.checkpoint_path)
            assert ck is not None and ck["published_version"] == 1
        finally:
            engine.shutdown()


def test_kill_mid_cold_delta_refused_then_healed_by_replay():
    """A kill inside the cold-store row update leaves a torn file (new
    data rows, stale crcs): verify() must refuse it, and replaying the
    round from the unadvanced watermark must republish and heal it."""
    with tempfile.TemporaryDirectory(prefix="nl_k2_") as td:
        d = os.path.join(td, "m")
        names = _build_model_dir(7, d)
        engine = _mk_engine(d, two_tier=True)
        try:
            rng = np.random.default_rng(71)
            _drive(engine, rng, names, [f"u{i}" for i in range(5)])
            log_dir = os.path.join(td, "log")
            _write_events(log_dir, rng, names, ["u0", "u1", "newuser"])
            pipe = _pipeline(engine, log_dir, d)
            with chaos.active(chaos.ChaosConfig(
                    kill_publish_ops=("cold_delta",))):
                with pytest.raises(SimulatedKill):
                    pipe.run_round()

            cold_path = engine.model.random[0].store.cold.path
            with pytest.raises(ColdStoreCorruptError):
                ColdStore(cold_path).verify()      # torn-update refusal
            assert pipe.publisher.version == 0     # no manifest landed
            # publish locks were released and the pending set cleared
            assert engine.pending_publish_rows == frozenset()

            # replay from the unadvanced watermark heals the file
            pipe2 = _pipeline(engine, log_dir, d)
            s = pipe2.run_round()
            assert s["events"] > 0
            assert s["publish"]["accepted"], s["publish"]
            ColdStore(cold_path).verify()          # crcs repaired
            assert pipe2.run_round()["events"] == 0
        finally:
            engine.shutdown()


# -- admission lookahead: pending-publish rows are not prefetched ------------


def test_on_admit_defers_prefetch_of_pending_publish_rows():
    with tempfile.TemporaryDirectory(prefix="nl_adm_") as td:
        d = os.path.join(td, "m")
        names = _build_model_dir(7, d)
        t = {"now": 0.0}
        engine = _mk_engine(d, two_tier=True, clock=lambda: t["now"])
        try:
            rng = np.random.default_rng(81)
            engine.pending_publish_rows = frozenset({("userId", "u1")})
            deferred0 = _metrics.counter(
                "serving.prefetch_publish_deferred").value
            # admission (not batch pop) fires the lookahead: with the
            # injectable clock frozen, nothing dispatches while we look
            engine.submit(_mkreq(rng, "a", names, "u0"))
            engine.submit(_mkreq(rng, "b", names, "u1"))
            assert _metrics.counter(
                "serving.prefetch_publish_deferred").value == deferred0 + 1
            engine.model.drain_prefetch()
            store = engine.model.random[0].store
            with store.lock:
                assert store.hot_slot_locked("u0") is not None
                assert store.hot_slot_locked("u1") is None  # deferred
            engine.pending_publish_rows = frozenset()
            t["now"] = 10.0
            engine.drain()
            # after the publish window clears, the next natural request
            # promotes the entity as usual
            engine.serve([_mkreq(rng, "c", names, "u1")])
            engine.model.drain_prefetch()
            with store.lock:
                assert store.hot_slot_locked("u1") is not None
        finally:
            engine.shutdown()


# -- obs + cli ---------------------------------------------------------------


def test_run_report_has_nearline_section():
    from photon_tpu.obs.report import build_run_report

    with tempfile.TemporaryDirectory(prefix="nl_rep_") as td:
        d = os.path.join(td, "m")
        names = _build_model_dir(7, d)
        engine = _mk_engine(d, two_tier=False)
        try:
            rng = np.random.default_rng(91)
            _drive(engine, rng, names, [f"u{i}" for i in range(5)])
            log_dir = os.path.join(td, "log")
            _write_events(log_dir, rng, names, ["u0"])
            pipe = _pipeline(engine, log_dir, d)
            s = pipe.run_round()
            assert s["publish"]["accepted"]
            report = build_run_report(driver="test")
            nl = report.get("nearline")
            assert nl is not None
            assert nl["rounds"] == 1
            assert nl["published_version"] == pipe.publisher.version
            assert nl["totals"]["rows_updated"] == 1
        finally:
            engine.shutdown()
            from photon_tpu.nearline.pipeline import set_active
            set_active(None)


def test_cli_nearline_end_to_end(tmp_path):
    from photon_tpu.cli.nearline import build_arg_parser, run

    d = str(tmp_path / "m")
    names = _build_model_dir(7, d)
    log_dir = str(tmp_path / "log")
    rng = np.random.default_rng(101)
    _write_events(log_dir, rng, names, ["u0", "u1", "newuser"])
    stats = str(tmp_path / "stats.json")
    report = str(tmp_path / "report.json")
    args = build_arg_parser().parse_args([
        "--model-input-directory", d, "--event-log", log_dir,
        "--max-rounds", "1", "--poll-interval-s", "0",
        "--max-batch", "4", "--append-reserve", "4",
        "--parity-tol", "1e-3",
        "--stats-output", stats, "--runreport-output", report])
    assert run(args) == 0
    summary = json.loads(open(stats).read())
    assert summary["rounds"] == 1
    assert summary["published_version"] == 1
    assert summary["totals"]["rows_updated"] == 2
    assert summary["totals"]["rows_appended"] == 1
    doc = json.loads(open(report).read())
    assert doc["nearline"]["rounds"] == 1
    from photon_tpu.nearline.pipeline import set_active
    set_active(None)


# -- closed loop: serving on one thread, delta rounds on another ------------
#
# A two-tier engine over 200 entities scores closed-loop traffic from a
# serving thread while the nearline loop (event log -> delta train ->
# row-level live publish, appends included) runs rounds against it. One
# run; each gate is one case.


def _quick_model_dir(out_dir, E=200, K=2, d=32, seed=29):
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
    names = [f"g{j}" for j in range(d)]
    imap = IndexMap({feature_key(n, ""): i for i, n in enumerate(names)})
    ids = [f"e{e:09d}" for e in range(E)]
    lo = rng.integers(0, d - 1, size=E)
    proj = np.stack([lo, rng.integers(lo + 1, d)], axis=1).astype(np.int32)
    fixed = FixedEffectModel(GeneralizedLinearModel(
        Coefficients(jnp.asarray(rng.normal(size=d).astype(np.float32))),
        TaskType.LINEAR_REGRESSION), "g")
    rem = RandomEffectModel(
        coefficients=jnp.asarray(rng.normal(size=(E, K)).astype(np.float32)),
        random_effect_type="userId", feature_shard_id="g",
        task=TaskType.LINEAR_REGRESSION)
    vocab = EntityVocabulary()
    vocab.build("userId", ids)
    save_game_model(out_dir, GameModel({"global": fixed, "per_user": rem}),
                    {"g": imap}, vocab=vocab,
                    projections={"per_user": proj}, sparsity_threshold=0.0)
    return names, ids


@pytest.fixture(scope="module")
def nearline_closed_loop():
    import threading

    from photon_tpu.serving.scorer import MODES, get_scorer
    from photon_tpu.utils import compile_cache

    n_rounds, ents_per_round, max_batch, nnz = 3, 16, 8, 8
    rng = np.random.default_rng(29)
    with tempfile.TemporaryDirectory(prefix="nearline_q_") as td:
        mdir = os.path.join(td, "model")
        names, ids = _quick_model_dir(mdir)
        engine = ServingEngine.from_model_dir(mdir, config=ServingConfig(
            max_batch=max_batch, max_wait_s=0.0,
            slo=SLOConfig(shed_queue_depth=200, reject_queue_depth=400),
            coeff_store=CoeffStoreConfig(hot_capacity=64,
                                         transfer_batch=16)))
        engine.warmup()
        zipf_rows = (rng.zipf(1.4, size=1 << 16) - 1) % len(ids)
        zi = [0]

        def req(i):
            row = int(zipf_rows[zi[0] % len(zipf_rows)])
            zi[0] += 1
            cols = rng.choice(len(names), size=nnz, replace=False)
            return ScoreRequest(f"q{i}", {"g": [
                (names[c], "", float(rng.normal())) for c in cols]},
                {"userId": ids[row]})

        def event(user, ts):
            cols = rng.choice(len(names), size=nnz, replace=False)
            return {"ts": ts, "response": float(rng.normal()),
                    "features": {"g": [[names[c], "", float(rng.normal())]
                                       for c in cols]},
                    "entities": {"userId": user}}

        log_dir = os.path.join(td, "events")
        writer = EventLogWriter(log_dir)
        pipe = _pipeline(engine, log_dir, mdir)
        # warm rounds: the trainer's programs at the measured rounds'
        # entity count, and the publisher end to end, appends included
        for i in range(256):
            engine.submit(req(i))
            if i % 64 == 63:
                engine.pump()
        engine.drain()
        engine.model.drain_prefetch()
        uniq = sorted({ids[int(r)] for r in zipf_rows[:512]})
        writer.append([event(u, time.time()) for u in uniq[:ents_per_round]])
        warm_ok = pipe.run_round().get("publish", {}).get("accepted")
        writer.append([event(u, time.time()) for u in ("nb_new0", "nb_new1")])
        warm_ok = warm_ok and pipe.run_round().get(
            "publish", {}).get("accepted")

        jitted = [get_scorer(engine.model, mode, b)
                  for mode in MODES for b in engine.ladder.buckets]
        jitted = [p if hasattr(p, "_cache_size")
                  else getattr(p, "__wrapped__", p) for p in jitted]
        jitted = [f for f in jitted if hasattr(f, "_cache_size")]
        compiles0 = compile_cache.compile_counts()["steady_state"]
        misses0 = _metrics.counter("jitcache.misses").value
        traces0 = [f._cache_size() for f in jitted]

        stop, served = threading.Event(), [0]

        def serve_loop():
            i = 1 << 20
            while not stop.is_set():
                engine.serve([req(i + j) for j in range(max_batch)])
                served[0] += max_batch
                i += max_batch

        th = threading.Thread(target=serve_loop, daemon=True)
        th.start()
        accepted = rows_pub = 0
        verify_ok = True
        try:
            for rnd in range(n_rounds):
                users = sorted({uniq[(rnd * ents_per_round + j) % len(uniq)]
                                for j in range(ents_per_round)})
                writer.append([event(u, time.time()) for u in users])
                pub = pipe.run_round().get("publish")
                if pub and pub.get("accepted"):
                    accepted += 1
                    rows_pub += pub["rows_updated"] + pub["rows_appended"]
                    verify_ok &= pub["gates"].get("verify") == "pass"
                else:
                    verify_ok = False
        finally:
            stop.set()
            th.join()
        zero = (compile_cache.compile_counts()["steady_state"] == compiles0
                and _metrics.counter("jitcache.misses").value == misses0
                and all(t1 <= t0 for t0, t1 in zip(
                    traces0, [f._cache_size() for f in jitted])))

        # a touched entity's served row is its cold-tier row, bitwise
        rs = engine.model.random[0]
        served_row = current_entity_row(rs, uniq[0],
                                        engine.model.shard_dims["g"])
        r = rs.store.cold.entity_row(uniq[0])
        parity = (served_row is not None
                  and served_row[0].tobytes()
                  == np.array(rs.store.cold.coef[r], np.float32).tobytes()
                  and served_row[1].tobytes()
                  == np.array(rs.store.cold.proj[r], np.int32).tobytes())
        engine.shutdown()
    yield {
        "warm_publishes_accepted": bool(warm_ok),
        "every_round_published": accepted == n_rounds,
        "rows_published": rows_pub > 0,
        "served_while_publishing": served[0] > 0,
        "zero_steady_state_compiles": bool(zero),
        "publish_parity_ok": bool(parity and verify_ok),
    }


@pytest.mark.parametrize("gate", [
    "warm_publishes_accepted", "every_round_published", "rows_published",
    "served_while_publishing", "zero_steady_state_compiles",
    "publish_parity_ok"])
def test_nearline_rounds_under_concurrent_serving(nearline_closed_loop, gate):
    assert nearline_closed_loop[gate] is True, nearline_closed_loop


# -- int8 serving arm: publish consistency + rollback ------------------------


def test_int8_tables_track_publishes_and_rollback():
    """Row-level publishes into an int8 engine must keep the quantized
    tables consistent with the f32 rows: touched rows are requantized at
    commit (per-row symmetric quantization is row-local and
    deterministic, so this equals from-scratch staging), appends land in
    both representations, and rollback restores the quantized tables
    bitwise alongside the f32 ones."""
    from photon_tpu.serving.model_state import quantize_rows

    with tempfile.TemporaryDirectory(prefix="nl_i8_") as td:
        d = os.path.join(td, "m")
        names = _build_model_dir(7, d)
        engine = ServingEngine.from_model_dir(d, config=ServingConfig(
            max_batch=4, max_wait_s=0.0, append_reserve=4,
            slo=SLOConfig(shed_queue_depth=60, reject_queue_depth=100),
            int8_serving=True))
        engine.warmup()
        try:
            rng = np.random.default_rng(51)
            users = [f"u{i}" for i in range(5)]
            _drive(engine, rng, names, users)
            rs = engine.model.random[0]
            assert rs.coef_q is not None
            q_before = np.asarray(rs.coef_q).tobytes()
            s_before = np.asarray(rs.scales).tobytes()

            log_dir = os.path.join(td, "log")
            _write_events(log_dir, rng, names, ["u0", "u1", "newuser"])
            pipe = _pipeline(engine, log_dir, d)
            s = pipe.run_round()
            assert s["publish"]["accepted"], s["publish"]
            assert s["publish"]["rows_appended"] == 1

            # requantize-on-commit invariant: every known entity's live
            # int8 row equals from-scratch quantization of its f32 row
            rs = engine.model.random[0]
            coef = np.asarray(rs.coef, np.float32)
            q_now = np.asarray(rs.coef_q)
            sc_now = np.asarray(rs.scales, np.float32)
            for e in rs.entity_rows.values():
                qe, se = quantize_rows(coef[e][None])
                np.testing.assert_array_equal(q_now[e], qe[0])
                np.testing.assert_array_equal(sc_now[e], se[0])
            assert q_now.tobytes() != q_before    # the publish was live

            # the appended entity scores through the int8 arm
            post = engine.serve([_mkreq(rng, "post", names, "newuser")])[0]
            assert "UNKNOWN_ENTITY" not in \
                {f.reason.name for f in post.fallbacks}

            # rollback restores the quantized tables bitwise
            assert pipe.publisher.rollback_last("test")
            rs = engine.model.random[0]
            assert np.asarray(rs.coef_q).tobytes() == q_before
            assert np.asarray(rs.scales).tobytes() == s_before
        finally:
            engine.shutdown()


# -- fleet publish fan-out (FleetDeltaPublisher) -----------------------------
#
# The entity-sharded fleet's nearline path: one DeltaPublisher per shard
# engine, rows routed to their crc-owner only. Contract under test:
# publish-to-owning-shard is bitwise-identical to publishing the same
# delta into a single whole-model engine, shards that own none of the
# rows stay BYTE-identical on disk, per-shard watermarks are durable,
# and a rejection anywhere rolls every already-committed shard back.


def _sha256(path):
    import hashlib

    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _mk_fleet_pair(td, num_shards=4):
    """(fleet, fleet_dir, single whole-model engine, names) over the
    same saved model — the parity pair every fleet-publish test uses."""
    from photon_tpu.io.fleet_store import build_fleet_dir
    from photon_tpu.serving import FleetConfig, ShardedServingFleet

    mdir, fdir = os.path.join(td, "m"), os.path.join(td, "f")
    names = _build_model_dir(7, mdir)
    build_fleet_dir(mdir, fdir, num_shards)
    serving = ServingConfig(
        max_batch=4, max_wait_s=0.0,
        slo=SLOConfig(shed_queue_depth=60, reject_queue_depth=100),
        coeff_store=CoeffStoreConfig(hot_capacity=8, transfer_batch=2))
    fleet = ShardedServingFleet.from_fleet_dir(
        fdir, FleetConfig(serving=serving))
    fleet.warmup()
    single = _mk_engine(mdir, two_tier=True)
    return fleet, fdir, mdir, single, names


def _fleet_drive(fleet, rng, names, users, n=12):
    for lo in range(0, n, 4):
        fleet.serve([_mkreq(rng, f"fd{lo}-{i}", names,
                            users[(lo + i) % len(users)])
                     for i in range(min(4, n - lo))])
    for c in fleet.clients:
        c.engine.model.drain_prefetch()


def test_fleet_publish_owning_shard_bitwise_untouched_shards_byte_identical():
    from photon_tpu.io.fleet_store import shard_store_path
    from photon_tpu.nearline import FleetDeltaPublisher
    from photon_tpu.parallel.partition import entity_shard

    with tempfile.TemporaryDirectory(prefix="fleet_pub_") as td:
        fleet, fdir, mdir, single, names = _mk_fleet_pair(td, 4)
        try:
            users = [f"u{e}" for e in range(5)]
            rng = np.random.default_rng(8)
            _fleet_drive(fleet, rng, names, users)
            _drive(single, rng, names, users)
            # promote every user on both sides so the parity serves
            # below are hot-path, not cold-tier fallbacks
            for u in users:
                fleet.serve([_mkreq(rng, f"warm-f-{u}", names, u)])
                single.serve([_mkreq(rng, f"warm-s-{u}", names, u)])
            for c in fleet.clients:
                c.engine.model.drain_prefetch()
            single.model.drain_prefetch()

            # delta for u1 + u4: owners are shards 2 and 1 under the
            # pinned crc hash; shards 0 and 3 must stay byte-identical
            touched_users = ["u1", "u4"]
            owners = {entity_shard(u, 4) for u in touched_users}
            assert owners == {2, 1}
            ts = time.time()
            events = [_mkevent(rng, names, u, ts + i)
                      for i, u in enumerate(touched_users * 3)]
            trainer = DeltaTrainer(single, model_dir=mdir)
            delta = trainer.train(events)

            shas = {s: _sha256(shard_store_path(fdir, s, "per-user"))
                    for s in range(4)}
            pre = {u: fleet.serve([_mkreq(rng, f"pre-{u}", names, u)])[0]
                   for u in touched_users}
            assert all(not r.degraded for r in pre.values())

            pub = FleetDeltaPublisher(fleet, fdir)
            res = pub.publish(delta, "d1", watermark={"pos": 17})
            assert res.accepted, res.reason
            assert set(res.shards) == owners
            assert res.rows_updated == 2

            # rows landed ONLY in the owning shards' files
            for s in range(4):
                now = _sha256(shard_store_path(fdir, s, "per-user"))
                if s in owners:
                    assert now != shas[s], f"shard {s} should have rows"
                else:
                    assert now == shas[s], f"shard {s} was touched"
            wm = pub.watermarks()
            for s in owners:
                assert wm[s] == {"pos": 17}

            # bitwise parity: the same delta through a single-host
            # publisher gives byte-equal scores for the touched users
            sp = DeltaPublisher(single, model_dir=mdir)
            assert sp.publish(delta, "d1").accepted
            for u in touched_users:
                rf = fleet.serve([_mkreq(rng, f"pf-{u}", names, u)])[0]
                rs = single.serve([_mkreq(rng, f"pf-{u}", names, u)])[0]
                # identical uid+rng draw order: same features both sides
                assert not rf.degraded and not rs.degraded
            rng_f, rng_s = (np.random.default_rng(77) for _ in range(2))
            for u in touched_users:
                rf = fleet.serve([_mkreq(rng_f, f"pp-{u}", names, u)])[0]
                rs = single.serve([_mkreq(rng_s, f"pp-{u}", names, u)])[0]
                assert np.float32(rf.score).tobytes() \
                    == np.float32(rs.score).tobytes(), u

            # bitwise rollback per shard: files AND scores return
            assert pub.rollback_last("test") == sorted(owners)
            for s in range(4):
                assert _sha256(shard_store_path(fdir, s, "per-user")) \
                    == shas[s]
            rng_a, rng_b = (np.random.default_rng(91) for _ in range(2))
            post = {u: fleet.serve([_mkreq(rng_a, f"rb-{u}", names, u)])[0]
                    for u in touched_users}
            # a fresh fleet over the rolled-back files scores identically
            # (the rollback healed both the live tables and the disk)
            from photon_tpu.serving import FleetConfig, ShardedServingFleet
            fleet2 = ShardedServingFleet.from_fleet_dir(
                fdir, FleetConfig(serving=ServingConfig(
                    max_batch=4, max_wait_s=0.0,
                    coeff_store=CoeffStoreConfig(hot_capacity=8,
                                                 transfer_batch=2))))
            fleet2.warmup()
            try:
                _fleet_drive(fleet2, np.random.default_rng(8), names,
                             touched_users)
                for u in touched_users:
                    r2 = fleet2.serve(
                        [_mkreq(rng_b, f"rb-{u}", names, u)])[0]
                    assert np.float32(post[u].score).tobytes() \
                        == np.float32(r2.score).tobytes(), u
            finally:
                fleet2.shutdown()
        finally:
            fleet.shutdown()
            single.shutdown()


def test_fleet_publish_rejection_rolls_back_every_shard():
    from photon_tpu.io.fleet_store import shard_store_path
    from photon_tpu.nearline import FleetDeltaPublisher

    with tempfile.TemporaryDirectory(prefix="fleet_rej_") as td:
        fleet, fdir, mdir, single, names = _mk_fleet_pair(td, 4)
        try:
            users = [f"u{e}" for e in range(5)]
            rng = np.random.default_rng(9)
            _fleet_drive(fleet, rng, names, users)
            _drive(single, rng, names, users)
            ts = time.time()
            events = [_mkevent(rng, names, u, ts + i)
                      for i, u in enumerate(["u1", "u4"] * 3)]
            delta = DeltaTrainer(single, model_dir=mdir).train(events)
            shas = {s: _sha256(shard_store_path(fdir, s, "per-user"))
                    for s in range(4)}

            # poison the FIRST shard publish's commit payload: the
            # readback gate refuses it, and the fleet round must land
            # on NO shard — all four files stay byte-identical
            pub = FleetDeltaPublisher(fleet, fdir)
            with chaos.active(chaos.ChaosConfig(publish_poison_row=True)):
                res = pub.publish(delta, "bad")
            assert not res.accepted
            for s in range(4):
                assert _sha256(shard_store_path(fdir, s, "per-user")) \
                    == shas[s], f"shard {s} diverged after rejection"

            # the same publisher recovers: a clean retry lands
            res2 = pub.publish(delta, "good")
            assert res2.accepted, res2.reason
        finally:
            fleet.shutdown()
            single.shutdown()
