"""Traffic capture & deterministic replay tests (photon_tpu/serving/
replay.py, photon_tpu/obs/slo.py and the chaos injectors).

Covers the replay-harness contract:

  * generators: bitwise-identical (seed, profile) -> stream, profile
    rate shapes (burst/diurnal/flash-crowd), distinct feature indices,
  * capture: crc32-framed JSONL round-trip, torn-tail hold-back with a
    typed CAPTURE_TRUNCATED count (chaos ``capture_kill_at`` and
    ``replay_torn_capture``), interior corruption skipped not fatal,
  * virtual clock: monotonicity enforced, injected recorded-offset skew
    clamped with a typed CLOCK_SKEW_CLAMPED count,
  * replay determinism: the same capture replayed twice through two
    independently built engines on fresh virtual clocks is bitwise
    identical — response digest AND windowed qps/p99 timeline digest,
  * per-tenant windowed isolation: a chaos-slowed tenant's latencies do
    not pollute another tenant's windowed p99 (the PR 12 regression),
  * SLO verdicts: PASS/WARN/BREACH ladder, offending-window capture,
    qps-floor masking, the compile-delta rule, verdict file round-trip,
  * the harness end to end: capture, two replays, then a live swap and
    a shard kill whose breach stays in the kill windows.
"""

import json
import math
import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from photon_tpu import obs
from photon_tpu.io.index_map import IndexMap, feature_key
from photon_tpu.io.model_io import (
    ServingFixedEffect,
    ServingGameModel,
    ServingRandomEffect,
)
from photon_tpu.obs import slo
from photon_tpu.obs import timeseries as ts
from photon_tpu.resilience import chaos
from photon_tpu.serving import (
    DeviceResidentModel,
    Replayer,
    ScoreRequest,
    ServingConfig,
    ServingEngine,
    TrafficProfile,
    VirtualClock,
    generate,
    read_capture,
    record_capture,
    stream_digest,
    timeline_digest,
)
from photon_tpu.serving.replay import CAPTURE_TRUNCATED, CaptureWriter
from photon_tpu.types import TaskType

D_GLOBAL = 8
N_ENTITIES = 64


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.reset()
    yield
    obs.reset()


def _profile(**kw):
    base = dict(kind="zipf", n_requests=40, entities=N_ENTITIES,
                base_qps=200.0, feature_dim=D_GLOBAL, nnz=3)
    base.update(kw)
    return TrafficProfile(**base)


def _engine(clock=None, tenant=None, seed=0):
    rng = np.random.default_rng(seed)
    imap = IndexMap({feature_key(f"f{j}", ""): j for j in range(D_GLOBAL)})
    theta = rng.normal(size=D_GLOBAL).astype(np.float32)
    coef = rng.normal(size=(N_ENTITIES, 2)).astype(np.float32)
    proj = np.tile(np.arange(2, dtype=np.int32), (N_ENTITIES, 1))
    rows = {f"e{i:09d}": i for i in range(N_ENTITIES)}
    re = ServingRandomEffect("per_user", "userId", "g",
                             coefficients=coef, projection=proj,
                             entity_rows=rows)
    m = ServingGameModel(TaskType.LINEAR_REGRESSION,
                         [ServingFixedEffect("fixed", "g", theta)], [re],
                         {"g": imap}, {})
    labels = {"tenant": tenant} if tenant else {}
    eng = ServingEngine(DeviceResidentModel(m),
                        ServingConfig(max_batch=8, max_wait_s=0.002),
                        clock=clock, obs_labels=labels)
    eng.warmup()
    return eng


# -- generators --------------------------------------------------------------


def test_generate_bitwise_deterministic():
    p = _profile(n_requests=200, entities=5_000_000)
    a, b = generate(p, seed=9), generate(p, seed=9)
    assert stream_digest(a) == stream_digest(b)
    assert a[0][1].features == b[0][1].features
    assert stream_digest(generate(p, seed=10)) != stream_digest(a)
    assert stream_digest(generate(_profile(n_requests=200,
                                           entities=5_000_000,
                                           zipf_a=2.0), 9)) \
        != stream_digest(a)


def test_generate_feature_indices_distinct_and_timestamps_increase():
    p = _profile(n_requests=100, nnz=D_GLOBAL)
    recs = generate(p, seed=4)
    last = 0.0
    for t, req in recs:
        assert t > last
        last = t
        names = [n for n, _, _ in req.features["g"]]
        assert len(set(names)) == len(names) == D_GLOBAL


def test_profile_rate_shapes():
    burst = _profile(kind="burst", burst_at_s=2.0, burst_len_s=1.0,
                     burst_factor=4.0)
    assert burst.rate(1.0) == 200.0
    assert burst.rate(2.5) == 800.0
    assert burst.rate(3.5) == 200.0
    diurnal = _profile(kind="diurnal", diurnal_period_s=60.0,
                       diurnal_amplitude=0.5)
    assert diurnal.rate(15.0) == pytest.approx(300.0)
    assert diurnal.rate(45.0) == pytest.approx(100.0)
    flash = _profile(kind="flash_crowd", flash_at_s=1.0, flash_ramp_s=2.0,
                     flash_factor=8.0)
    assert flash.rate(0.5) == 200.0
    assert flash.rate(3.0) == 1600.0


def test_flash_crowd_concentrates_entities():
    p = _profile(kind="flash_crowd", n_requests=800, entities=1_000_000,
                 base_qps=400.0, flash_at_s=0.25, flash_ramp_s=0.25,
                 flash_factor=8.0, flash_entity_frac=1e-5)
    recs = generate(p, seed=2)
    hot = max(1, int(p.entities * p.flash_entity_frac))
    late = [r for t, r in recs if t >= 0.5]
    frac_hot = np.mean([int(r.entity_ids["userId"][1:]) < hot
                        for r in late])
    assert frac_hot > 0.5


def test_profile_validation():
    with pytest.raises(ValueError):
        TrafficProfile(kind="banana")
    with pytest.raises(ValueError):
        TrafficProfile(zipf_a=1.0)


# -- capture -----------------------------------------------------------------


def test_capture_roundtrip(tmp_path):
    recs = generate(_profile(timeout_ms=50.0, tenant="t0"), seed=1)
    path = str(tmp_path / "cap.jsonl")
    assert record_capture(path, recs) == len(recs)
    got, stats = read_capture(path)
    assert stats == {CAPTURE_TRUNCATED: 0, "bad_records": 0}
    assert len(got) == len(recs)
    assert stream_digest([(r.t, r.request) for r in got]) \
        == stream_digest(recs)
    assert got[0].request.timeout_s == pytest.approx(0.05)
    assert got[0].request.tenant == "t0"


def test_capture_kill_mid_append_is_typed_truncation(tmp_path):
    """chaos.capture_kill_at: the writer dies mid-append; the reader
    returns every complete record and a typed CAPTURE_TRUNCATED count."""
    recs = generate(_profile(n_requests=12), seed=1)
    path = str(tmp_path / "cap.jsonl")
    with chaos.active(chaos.ChaosConfig(capture_kill_at=5)):
        with pytest.raises(chaos.SimulatedKill):
            record_capture(path, recs)
    got, stats = read_capture(path)
    assert len(got) == 5
    assert stats[CAPTURE_TRUNCATED] == 1
    assert obs.metrics.counter("replay.capture_truncated").value >= 1


def test_replay_torn_capture_injector(tmp_path):
    recs = generate(_profile(n_requests=8), seed=1)
    path = str(tmp_path / "cap.jsonl")
    record_capture(path, recs)
    assert chaos.replay_torn_capture(path)
    got, stats = read_capture(path)
    assert len(got) == 7                 # torn final record held back
    assert stats[CAPTURE_TRUNCATED] == 1


def test_capture_interior_corruption_skipped_not_fatal(tmp_path):
    recs = generate(_profile(n_requests=6), seed=1)
    path = str(tmp_path / "cap.jsonl")
    record_capture(path, recs)
    lines = open(path, "rb").read().splitlines(keepends=True)
    lines[2] = b'{"garbage": true}\n'
    open(path, "wb").write(b"".join(lines))
    got, stats = read_capture(path)
    assert len(got) == 5
    assert stats["bad_records"] == 1
    assert stats[CAPTURE_TRUNCATED] == 0


def test_read_capture_missing_and_empty(tmp_path):
    got, stats = read_capture(str(tmp_path / "nope.jsonl"))
    assert got == [] and stats[CAPTURE_TRUNCATED] == 0
    p = tmp_path / "empty.jsonl"
    p.write_bytes(b"")
    got, stats = read_capture(str(p))
    assert got == [] and stats[CAPTURE_TRUNCATED] == 0


# -- virtual clock -----------------------------------------------------------


def test_virtual_clock_monotone():
    clk = VirtualClock()
    assert clk() == 0.0
    clk.advance(1.5)
    assert clk.now() == 1.5
    clk.advance_to(1.0)                  # past: monotone clamp, no-op
    assert clk.now() == 1.5
    with pytest.raises(ValueError):
        clk.advance(-0.1)


def test_clock_skew_clamped_typed(tmp_path):
    """chaos.replay_clock_skew: skewed-backwards recorded offsets are
    clamped to the virtual now and counted, typed, per record."""
    recs = generate(_profile(n_requests=30), seed=5)
    clk = VirtualClock()
    eng = _engine(clock=clk)
    try:
        cfg = chaos.ChaosConfig(replay_skew_s=-5.0, replay_skew_from=10,
                                replay_skew_records=7)
        with chaos.active(cfg):
            res = Replayer(eng, clk).run(recs)
        assert res.clock_skew_clamped == 7
        assert res.responses == 30
        snap = ts.series.snapshot()["timeseries"]
        clamped = sum(w["value"] for w in
                      snap["replay.clock_skew_clamped"]["windows"])
        assert clamped == 7
    finally:
        eng.shutdown()


# -- deterministic replay ----------------------------------------------------


def test_replay_twice_bitwise_identical():
    """THE determinism contract (tentpole): same capture, two fresh
    engine+clock stacks -> identical response digest AND identical
    windowed replay timeline digest."""
    recs = generate(_profile(n_requests=120, kind="burst", base_qps=300.0,
                             burst_at_s=0.2, burst_len_s=0.2), seed=7)
    outs = []
    for _ in range(2):
        clk = VirtualClock()
        eng = _engine(clock=clk)
        reg = ts.WindowedRegistry(interval_s=0.25)
        try:
            res = Replayer(eng, clk, registry=reg).run(recs)
        finally:
            eng.shutdown()
        outs.append((res, timeline_digest(reg.snapshot())))
    (r1, t1), (r2, t2) = outs
    assert r1.responses == r2.responses == 120
    assert r1.refusals == 0
    assert r1.response_digest == r2.response_digest
    assert t1 == t2
    assert r1.virtual_seconds == r2.virtual_seconds


def test_replay_latency_is_virtual_time():
    """Replay latencies come off the virtual clock: all windowed
    latencies are bounded by the drain tick, independent of how slow the
    host actually is."""
    recs = generate(_profile(n_requests=40), seed=3)
    clk = VirtualClock()
    eng = _engine(clock=clk)
    reg = ts.WindowedRegistry(interval_s=0.25)
    try:
        Replayer(eng, clk, registry=reg, tick_s=0.05).run(recs)
    finally:
        eng.shutdown()
    cum = reg.cumulative("replay.latency")
    assert cum["count"] == 40
    # queueing in virtual time never exceeds a few coalescing ticks
    assert cum["p99"] <= 0.25


def test_replay_actions_fire_at_virtual_time():
    recs = generate(_profile(n_requests=60, base_qps=300.0), seed=3)
    clk = VirtualClock()
    eng = _engine(clock=clk)
    fired = []
    try:
        res = Replayer(eng, clk).run(
            recs, actions=[(0.1, lambda: fired.append(clk.now()))])
    finally:
        eng.shutdown()
    assert res.responses == 60
    assert len(fired) == 1
    assert 0.1 <= fired[0] < 0.2


# -- per-tenant windowed isolation (the PR 12 regression) --------------------


def test_tenant_latency_windows_do_not_pollute_each_other():
    """Before windowed per-label quantiles, one process-global histogram
    mixed every tenant's latencies; a slow tenant dragged every p99 up.
    Now each (name, labels) series owns its sketches: tenant B scored
    under a chaos-injected scorer delay must not move tenant A's p99."""
    eng_a = _engine(tenant="a", seed=0)
    eng_b = _engine(tenant="b", seed=1)
    reqs = [ScoreRequest(f"q{i}", {"g": [(f"f{i % D_GLOBAL}", "", 1.0)]},
                         {"userId": f"e{i % N_ENTITIES:09d}"})
            for i in range(32)]
    try:
        eng_a.serve(reqs)
        with chaos.active(chaos.ChaosConfig(scorer_delay_s=0.05,
                                            scorer_delay_batches=10_000)):
            eng_b.serve(reqs)
    finally:
        eng_a.shutdown()
        eng_b.shutdown()
    pa = ts.series.cumulative("serving.latency", mode="full",
                              tenant="a")["p99"]
    pb = ts.series.cumulative("serving.latency", mode="full",
                              tenant="b")["p99"]
    # the injected 50ms delay is visible in B (within the sketch's
    # relative-error bound)... and ONLY in B's series
    assert pb >= 0.045
    assert pa < 0.045
    assert pb > 2 * pa


# -- SLO verdicts ------------------------------------------------------------


def _slo_snapshot():
    reg = ts.WindowedRegistry(interval_s=1.0)
    lat = reg.quantile("replay.latency")
    qps = reg.counter("replay.responses")
    deg = reg.counter("replay.degraded", reason="shard_unavailable")
    for w in range(4):
        t = w + 0.5
        n = 100 if w != 1 else 2         # window 1 is nearly idle
        qps.inc(t, n)
        for _ in range(20):
            # window 2 is slow; idle window 1 is slow but under-floor
            lat.observe(t, 0.5 if w in (1, 2) else 0.01)
    deg.inc(2.5, 30)                     # degradation burst in window 2
    return reg.snapshot()


def test_p99_ceiling_verdict_and_qps_floor_masking():
    snap = _slo_snapshot()
    rule = slo.P99Ceiling(rule_id="p99", series="replay.latency",
                          ceiling_s=0.1, qps_series="replay.responses",
                          qps_floor=50.0)
    v = rule.evaluate(snap)
    assert v.status == slo.BREACH
    assert [w["idx"] for w in v.offending_windows] == [2]
    assert v.windows_evaluated == 3      # idle window 1 masked
    # without the floor the idle window is judged too
    v2 = slo.P99Ceiling(rule_id="p99", series="replay.latency",
                        ceiling_s=0.1).evaluate(snap)
    assert [w["idx"] for w in v2.offending_windows] == [1, 2]
    # warn_windows tolerates the transient
    v3 = slo.P99Ceiling(rule_id="p99", series="replay.latency",
                        ceiling_s=0.1, qps_series="replay.responses",
                        qps_floor=50.0, warn_windows=1).evaluate(snap)
    assert v3.status == slo.WARN


def test_max_degradation_rate_verdict():
    snap = _slo_snapshot()
    rule = slo.MaxDegradationRate(
        rule_id="deg", degraded_series="replay.degraded",
        total_series="replay.responses", max_rate=0.05,
        degraded_labels={"reason": "shard_unavailable"})
    v = rule.evaluate(snap)
    assert v.status == slo.BREACH
    assert [w["idx"] for w in v.offending_windows] == [2]
    assert v.offending_windows[0]["value"] == pytest.approx(0.3)
    assert slo.MaxDegradationRate(
        rule_id="deg", degraded_series="replay.degraded",
        total_series="replay.responses", max_rate=0.5,
        degraded_labels={"reason": "shard_unavailable"}
    ).evaluate(snap).status == slo.PASS


def test_zero_compile_rule():
    r = slo.ZeroSteadyStateCompiles(rule_id="zc")
    assert r.evaluate({}, compile_delta=0).status == slo.PASS
    bad = r.evaluate({}, compile_delta=3)
    assert bad.status == slo.BREACH
    assert bad.offending_windows[0]["value"] == 3.0
    assert r.evaluate({}, compile_delta=None).status == slo.WARN


def test_evaluate_records_and_verdict_file_roundtrip(tmp_path):
    snap = _slo_snapshot()
    spec = slo.SLOSpec([
        slo.P99Ceiling(rule_id="p99", series="replay.latency",
                       ceiling_s=10.0),
        slo.ZeroSteadyStateCompiles(rule_id="zc"),
    ])
    verdicts = slo.evaluate(spec, snap, compile_delta=0)
    assert slo.worst_status(verdicts) == slo.PASS
    assert len(slo.recorded_verdicts()) == 2
    path = tmp_path / "verdicts.json"
    doc = slo.write_verdicts(str(path), verdicts)
    on_disk = json.loads(path.read_text())
    assert on_disk == doc
    assert on_disk["schema"] == slo.SCHEMA
    assert on_disk["status"] == slo.PASS
    assert [v["rule_id"] for v in on_disk["verdicts"]] == ["p99", "zc"]
    # the RunReport slo section mirrors the sink, schema-validated
    rep = obs.build_run_report("test-slo")
    assert rep["slo"]["status"] == slo.PASS
    assert obs.validate_run_report(rep) == []
    obs.reset()
    assert slo.recorded_verdicts() == []


# -- capture -> two replays -> kill/swap, end to end -------------------------
#
# A Zipf+burst profile is captured and read back, replayed twice through
# two independently built sharded fleets on fresh virtual clocks, then
# replayed a third time with a live front swap and a shard kill/revive
# scheduled mid-stream; the SLO rules must localise the breach to the
# kill windows. One run; each gate is one case.

_Q_E, _Q_K, _Q_D, _Q_SHARDS, _Q_BATCH = 3_000, 2, 16, 2, 32
_Q_INTERVAL, _Q_TICK = 0.25, 0.05


def _quick_fleet_models(seed):
    """A fixed-effect front model plus one RE-only model a shard, with
    fully resident tables (cold-miss promotion is wall-clock state a
    bitwise timeline cannot admit). Entities are owned by the canonical
    partitioner over their id strings, as the router hashes them."""
    from photon_tpu.parallel.partition import entity_shards

    rng = np.random.default_rng(seed)
    imap = IndexMap({feature_key(f"f{j}", ""): j for j in range(_Q_D)})
    theta = rng.normal(size=_Q_D).astype(np.float32)
    coef = rng.normal(size=(_Q_E, _Q_K)).astype(np.float32)
    lo = rng.integers(0, _Q_D - 1, size=_Q_E)
    hi = rng.integers(lo + 1, _Q_D)
    proj = np.stack([lo, hi], axis=1).astype(np.int32)
    names = [f"e{i:09d}" for i in range(_Q_E)]
    owners = entity_shards(np.array(names, dtype="S10"), _Q_SHARDS)
    front = ServingGameModel(
        TaskType.LINEAR_REGRESSION,
        [ServingFixedEffect("fixed", "g", theta)], [], {"g": imap}, {})
    shards = []
    for s in range(_Q_SHARDS):
        rows = np.flatnonzero(owners == s)
        re = ServingRandomEffect(
            "per_user", "userId", "g",
            coefficients=np.ascontiguousarray(coef[rows]),
            projection=np.ascontiguousarray(proj[rows]),
            entity_rows={names[i]: j for j, i in enumerate(rows)})
        shards.append(ServingGameModel(
            TaskType.LINEAR_REGRESSION, [], [re], {"g": imap}, {}))
    return front, shards


def _quick_fleet(front_model, shard_models, clock):
    """Front + shard engines + router, all on one virtual clock."""
    from photon_tpu.serving import (
        FleetConfig,
        LocalShardClient,
        ShardedServingFleet,
    )

    cfg = ServingConfig(max_batch=_Q_BATCH, max_wait_s=0.001)
    front = ServingEngine(DeviceResidentModel(front_model), cfg,
                          clock=clock, obs_labels={"shard": "front"})
    clients = [LocalShardClient(s, ServingEngine(
        DeviceResidentModel(m), cfg, clock=clock,
        obs_labels={"shard": str(s)})) for s, m in enumerate(shard_models)]
    fleet = ShardedServingFleet(front, clients, [("per_user", "userId")],
                                FleetConfig(serving=cfg), clock=clock)
    fleet.warmup()
    return fleet


def _compile_monitors(fleet):
    """Steady-state compile events, jitcache misses and per-program
    trace counts over every engine of the fleet."""
    from photon_tpu.obs.metrics import registry
    from photon_tpu.serving.scorer import get_scorer, serving_modes
    from photon_tpu.utils import compile_cache

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
def replay_incident(tmp_path_factory):
    from photon_tpu.obs.report import build_run_report, validate_run_report
    from photon_tpu.serving.scorer import warmup_scorers

    seed = 31
    n_requests, base_qps = 300, 150.0
    t_swap, t_kill, t_revive = 0.4, 0.6, 1.1
    interval0 = ts.series.interval_s
    # engine, router and replayer series share one window grid
    ts.series.interval_s = _Q_INTERVAL
    obs.reset()
    try:
        profile = TrafficProfile(
            kind="burst", n_requests=n_requests, entities=_Q_E, zipf_a=1.5,
            base_qps=base_qps, feature_dim=_Q_D, nnz=4, burst_at_s=1.0,
            burst_len_s=0.6, burst_factor=3.0)
        records = generate(profile, seed)
        sdig = stream_digest(records)
        cap_path = str(tmp_path_factory.mktemp("replay_q") / "capture.jsonl")
        record_capture(cap_path, records)
        cap_records, cap_stats = read_capture(cap_path)
        front_model, shard_models = _quick_fleet_models(seed)

        runs = []
        for _ in (1, 2):
            clk = VirtualClock()
            fleet = _quick_fleet(front_model, shard_models, clk)
            reg = ts.WindowedRegistry(interval_s=_Q_INTERVAL)
            res = Replayer(fleet, clk, registry=reg, tick_s=_Q_TICK).run(
                cap_records)
            runs.append((res.response_digest,
                         timeline_digest(reg.snapshot())))
            fleet.shutdown()

        # the incident: live front swap, then a shard killed and revived
        ts.clear()
        clk = VirtualClock()
        fleet = _quick_fleet(front_model, shard_models, clk)
        staged = DeviceResidentModel(front_model)
        warmup_scorers(staged, fleet.front.ladder.buckets)
        victim = _Q_SHARDS // 2
        mon0 = _compile_monitors(fleet)
        swap_info = {}
        actions = [
            (t_swap, lambda: swap_info.update(fleet.front.publish_model(
                staged, "replay-live-swap"))),
            (t_kill, lambda: fleet.kill_shard(victim)),
            (t_revive, lambda: fleet.revive_shard(victim)),
        ]
        res_kill = Replayer(fleet, clk, tick_s=_Q_TICK).run(
            cap_records, actions)
        mon1 = _compile_monitors(fleet)
        compile_delta = (
            (mon1[0] - mon0[0]) + (mon1[1] - mon0[1])
            + sum(max(0, b - a) for a, b in zip(mon0[2], mon1[2])))
        snap_kill = ts.series.snapshot()
        fleet.shutdown()

        # every window the victim could have been dead in
        kill_idx = set(range(int(t_kill // _Q_INTERVAL),
                             int((t_revive + _Q_TICK) // _Q_INTERVAL) + 1))
        rules = [
            slo.P99Ceiling(
                rule_id="p99", series="replay.latency",
                ceiling_s=4 * _Q_TICK, qps_series="replay.responses",
                qps_floor=0.25 * base_qps),
            slo.MaxDegradationRate(
                rule_id="no_typed_degradation",
                degraded_series="replay.degraded",
                total_series="replay.responses", max_rate=0.0,
                degraded_labels={"reason": "shard_unavailable"}),
            slo.ZeroSteadyStateCompiles(rule_id="compiles"),
        ] + [slo.MaxDegradationRate(
            rule_id=f"shard{s}", degraded_series="fleet.shard.unavailable",
            total_series="replay.responses", max_rate=0.0,
            degraded_labels={"shard": str(s)}) for s in range(_Q_SHARDS)]
        by_rule = {v.rule_id: v for v in slo.evaluate(
            slo.SLOSpec(rules), snap_kill, compile_delta=compile_delta)}
        deg, vic = by_rule["no_typed_degradation"], by_rule[f"shard{victim}"]
        report = build_run_report("replay")
        yield {
            "stream_digest_stable":
                stream_digest(generate(profile, seed)) == sdig,
            "capture_roundtrip": (
                len(cap_records) == n_requests
                and cap_stats["capture_truncated"] == 0
                and stream_digest([(r.t, r.request)
                                   for r in cap_records]) == sdig),
            "response_digest_identical": runs[0][0] == runs[1][0],
            "timeline_digest_identical": runs[0][1] == runs[1][1],
            "kill_breach_registered": (
                deg.status == slo.BREACH and vic.status == slo.BREACH
                and res_kill.degraded_reasons.get(
                    "shard_unavailable", 0) > 0),
            "breach_localized_to_kill_windows": (
                {w["idx"] for w in deg.offending_windows} <= kill_idx
                and {w["idx"] for w in vic.offending_windows} <= kill_idx),
            "survivor_shards_pass": all(
                by_rule[f"shard{s}"].status == slo.PASS
                for s in range(_Q_SHARDS) if s != victim),
            "p99_slo_held": by_rule["p99"].status != slo.BREACH,
            "zero_steady_state_compiles":
                by_rule["compiles"].status == slo.PASS,
            "live_swap_published": swap_info.get("version") == 2,
            "runreport_roundtrip": (validate_run_report(report) == []
                                    and "timeline" in report
                                    and "slo" in report),
        }
    finally:
        ts.series.interval_s = interval0
        obs.reset()


@pytest.mark.parametrize("gate", [
    "stream_digest_stable", "capture_roundtrip",
    "response_digest_identical", "timeline_digest_identical",
    "kill_breach_registered", "breach_localized_to_kill_windows",
    "survivor_shards_pass", "p99_slo_held", "zero_steady_state_compiles",
    "live_swap_published", "runreport_roundtrip"])
def test_replay_capture_twice_then_incident(replay_incident, gate):
    assert replay_incident[gate] is True, replay_incident
