"""Online serving subsystem (photon_tpu/serving): parity, batching, SLO.

The load-bearing assertions:

  * serving-vs-offline parity: the engine's scores equal the offline
    ``GameScorer``'s to <= 1e-6 for EVERY ladder bucket, including
    padded-remainder batches and unknown-entity fallback rows;
  * the micro-batcher's coalescing policy is exact under an injected
    deterministic clock;
  * the SLO ladder degrades typed (shed -> fixed-effect-only scores,
    reject -> score=None), never raises;
  * after warmup, steady-state serving performs zero compiles (wired to
    ``scripts/check_serving_no_recompile.py``).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from photon_tpu.game.dataset import EntityVocabulary, FeatureShard, GameDataFrame
from photon_tpu.game.model import (
    Coefficients,
    FixedEffectModel,
    GameModel,
    GeneralizedLinearModel,
    RandomEffectModel,
)
from photon_tpu.game.random_effect import RandomEffectDataConfiguration
from photon_tpu.game.scoring import GameScorer
from photon_tpu.io.index_map import IndexMap, feature_key
from photon_tpu.io.model_io import (
    load_for_serving,
    load_game_model,
    save_game_model,
)
from photon_tpu.serving import (
    BucketLadder,
    DeviceResidentModel,
    FallbackReason,
    MicroBatcher,
    ScoreRequest,
    ServingConfig,
    ServingEngine,
    SLOConfig,
)
from photon_tpu.types import TaskType

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

D_GLOBAL, D_USER = 8, 6
N_USERS = 4


# -- model + traffic fixture -------------------------------------------------


def _build_model_dir(tmp_path):
    """Save a GAME model (fixed + per-user random effect) in the
    reference layout; return (dir, index maps, arrays for oracles)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(42)
    im_g = IndexMap.from_keys([feature_key("g", str(j))
                               for j in range(D_GLOBAL)])
    im_u = IndexMap.from_keys([feature_key("u", str(j))
                               for j in range(D_USER)])
    theta = rng.normal(size=D_GLOBAL)

    K = 3
    proj = np.full((N_USERS, K), -1, np.int32)
    coef = np.zeros((N_USERS, K))
    for e in range(N_USERS):
        cols = np.sort(rng.choice(D_USER, size=K, replace=False))
        proj[e] = cols
        coef[e] = rng.normal(size=K)
    users = [f"user{e}" for e in range(N_USERS)]
    vocab = EntityVocabulary()
    vocab.build("userId", users)

    model = GameModel({
        "fixed": FixedEffectModel(
            GeneralizedLinearModel(Coefficients(jnp.asarray(theta)),
                                   TaskType.LOGISTIC_REGRESSION), "g"),
        "per_user": RandomEffectModel(jnp.asarray(coef), "userId", "u",
                                      TaskType.LOGISTIC_REGRESSION),
    })
    d = str(tmp_path / "model")
    save_game_model(d, model, {"g": im_g, "u": im_u}, vocab=vocab,
                    projections={"per_user": proj}, sparsity_threshold=0.0)
    return d, {"g": im_g, "u": im_u}, vocab, users


def _make_traffic(n, users, seed=7, unknown_every=5):
    """n samples over both shards; every ``unknown_every``-th sample uses
    an entity the model has never seen."""
    rng = np.random.default_rng(seed)
    samples = []
    for i in range(n):
        gf = [("g", str(j), float(rng.normal()))
              for j in sorted(rng.choice(D_GLOBAL,
                                         size=int(rng.integers(1, D_GLOBAL)),
                                         replace=False))]
        uf = [("u", str(j), float(rng.normal()))
              for j in sorted(rng.choice(D_USER,
                                         size=int(rng.integers(1, D_USER)),
                                         replace=False))]
        user = (f"cold{i}" if unknown_every and i % unknown_every == 0
                else users[int(rng.integers(0, len(users)))])
        samples.append({"uid": f"r{i}", "g": gf, "u": uf, "user": user,
                        "offset": float(rng.normal() * 0.1)})
    return samples


def _offline_scores(model_dir, imaps, vocab, samples):
    """The existing batch path: GameDataFrame -> GameScorer."""
    n = len(samples)

    def shard_rows(bag, imap):
        rows = []
        for s in samples:
            cols = np.asarray([imap.index_of(nm, t) for nm, t, _ in s[bag]],
                              np.int32)
            vals = np.asarray([v for _, _, v in s[bag]])
            rows.append((cols, vals))
        return rows

    df = GameDataFrame(
        num_samples=n, response=np.zeros(n),
        feature_shards={
            "g": FeatureShard(shard_rows("g", imaps["g"]), D_GLOBAL),
            "u": FeatureShard(shard_rows("u", imaps["u"]), D_USER)},
        id_tags={"userId": [s["user"] for s in samples]})

    loaded = load_game_model(model_dir, imaps)
    scorer = GameScorer(n)
    scorer.add_fixed_effect("fixed", df, "g")
    scorer.add_random_effect("per_user", df,
                             RandomEffectDataConfiguration("userId", "u"),
                             vocab, loaded.projections["per_user"])
    offsets = np.asarray([s["offset"] for s in samples], np.float32)
    return np.asarray(scorer.score(loaded.model, offsets))


def _requests(samples):
    return [ScoreRequest(s["uid"], {"g": s["g"], "u": s["u"]},
                         {"userId": s["user"]}, s["offset"])
            for s in samples]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One engine, warmed, plus offline reference scores for 23 samples
    (covers buckets 1..8 with full and remainder batches)."""
    tmp_path = tmp_path_factory.mktemp("serving")
    model_dir, imaps, vocab, users = _build_model_dir(tmp_path)
    samples = _make_traffic(23, users)
    offline = _offline_scores(model_dir, imaps, vocab, samples)

    engine = ServingEngine.from_model_dir(
        model_dir, config=ServingConfig(max_batch=8, max_wait_s=0.0))
    info = engine.warmup()
    return engine, samples, offline, info, model_dir


# -- parity ------------------------------------------------------------------


def test_parity_all_buckets_and_remainders(served):
    """Every bucket size, full and partially filled: serving == offline
    to <=1e-6. Group sizes 1..8 cover each ladder bucket both exactly
    full (1, 2, 4, 8) and with padded remainder rows (3, 5, 6, 7)."""
    engine, samples, offline, _, _ = served
    reqs = _requests(samples)
    pos = 0
    for size in (1, 2, 3, 4, 5, 6, 7, 8):
        chunk = reqs[pos:pos + size]
        want = offline[pos:pos + size]
        pos += size
        if not chunk:
            break
        resps = engine.serve(chunk)
        got = np.asarray([r.score for r in resps])
        np.testing.assert_allclose(got, want[:len(chunk)], atol=1e-6,
                                   err_msg=f"parity broke at batch size {size}")


def test_parity_unknown_entity_rows(served):
    """Unknown entities degrade to fixed-effect-only scores — which is
    exactly what the offline scorer produces for unseen entities, so
    parity holds AND the response carries the typed fallback."""
    engine, samples, offline, _, _ = served
    reqs = _requests(samples)
    resps = engine.serve(reqs)
    for s, resp, want in zip(samples, resps, offline):
        assert resp.score == pytest.approx(float(want), abs=1e-6)
        is_cold = s["user"].startswith("cold")
        reasons = {f.reason for f in resp.fallbacks}
        assert (FallbackReason.UNKNOWN_ENTITY in reasons) == is_cold
        assert resp.degraded == is_cold


def test_zero_steady_state_compiles_after_warmup(served):
    """The core serving contract: the whole ladder is compiled at model
    load; the traffic the other tests pushed compiled nothing."""
    from photon_tpu.utils import compile_cache

    engine, samples, _, info, _ = served
    # both modes warmed over every bucket
    assert info["programs"] == 2 * len(engine.ladder.buckets)
    assert info["compile_counts"]["warmup"] >= info["programs"]

    # delta-based: the counter is process-global and other tests in the
    # session compile programs of their own
    before = compile_cache.compile_counts()["steady_state"]
    engine.serve(_requests(samples))
    after = compile_cache.compile_counts()["steady_state"]
    assert after == before


def test_scorer_programs_name_their_scopes(served):
    """Every scorer program lowers with ``serve/gather`` around its table
    look-ups and ``serve/score`` around the multiply-and-sum (PERF.md §3):
    what a serving cell's device seconds will be grouped by."""
    from photon_tpu.serving import scorer

    engine = served[0]
    model, bucket = engine.model, engine.ladder.buckets[0]
    for mode in scorer.serving_modes(model):
        args = scorer.mode_args(model, mode, model.dummy_args(bucket))
        text = scorer.build_scorer_fn(model, mode, bucket).lower(
            *args).as_text(debug_info=True)
        assert "serve/score" in text and "serve/gather" in text, mode


def test_load_for_serving_matches_offline_load(served):
    """The serving fast path (one pass, no variances, self-built compact
    index space) scores identically to an engine fed the offline maps."""
    engine, samples, offline, _, model_dir = served
    model = load_for_serving(model_dir)
    assert not model.index_maps.keys() - {"g", "u"}
    eng2 = ServingEngine(
        DeviceResidentModel(model),
        ServingConfig(max_batch=4, max_wait_s=0.0))
    eng2.warmup()
    resps = eng2.serve(_requests(samples))
    got = np.asarray([r.score for r in resps])
    np.testing.assert_allclose(got, offline, atol=1e-6)


# -- batching ----------------------------------------------------------------


def test_bucket_ladder():
    ladder = BucketLadder(max_batch=64, min_bucket=1)
    assert ladder.buckets == (1, 2, 4, 8, 16, 32, 64)
    assert ladder.bucket_for(1) == 1
    assert ladder.bucket_for(3) == 4
    assert ladder.bucket_for(64) == 64
    assert ladder.bucket_for(1000) == 64          # caller caps the take
    assert BucketLadder(max_batch=6, min_bucket=3).buckets == (4, 8)
    with pytest.raises(ValueError):
        ladder.bucket_for(0)
    with pytest.raises(ValueError):
        BucketLadder(max_batch=2, min_bucket=4)


def test_microbatcher_deterministic_clock():
    """Coalescing policy under a fake clock: nothing releases before the
    deadline unless the ladder top fills; the deadline is measured from
    the OLDEST queued request."""
    now = [0.0]
    batcher = MicroBatcher(BucketLadder(max_batch=4), max_wait_s=0.010,
                           clock=lambda: now[0])

    def req(uid):
        return ScoreRequest(uid, {})

    # one request: not ready until its deadline passes
    batcher.submit(req("a"))
    assert batcher.next_batch() is None
    now[0] = 0.009
    assert batcher.next_batch() is None
    now[0] = 0.010
    items, bucket = batcher.next_batch()
    assert [p.request.uid for p in items] == ["a"] and bucket == 1

    # deadline runs from the oldest request, not the newest
    now[0] = 1.000
    batcher.submit(req("b"))
    now[0] = 1.008
    batcher.submit(req("c"))
    assert batcher.next_batch() is None
    now[0] = 1.010                       # b is 10ms old, c only 2ms
    items, bucket = batcher.next_batch()
    assert [p.request.uid for p in items] == ["b", "c"] and bucket == 2

    # a full ladder-top batch releases immediately, no deadline needed
    now[0] = 2.000
    for uid in "defg":
        batcher.submit(req(uid))
    items, bucket = batcher.next_batch()
    assert len(items) == 4 and bucket == 4
    assert batcher.depth() == 0

    # flush overrides the deadline; remainder takes the smallest bucket
    batcher.submit(req("h"))
    batcher.submit(req("i"))
    batcher.submit(req("j"))
    assert batcher.next_batch() is None
    items, bucket = batcher.next_batch(flush=True)
    assert len(items) == 3 and bucket == 4        # padded remainder


def test_feature_overflow_truncates_with_typed_fallback(served):
    engine, _, _, _, model_dir = served
    model = load_for_serving(model_dir)
    eng = ServingEngine(DeviceResidentModel(model, feature_pad=2),
                        ServingConfig(max_batch=2, max_wait_s=0.0,
                                      feature_pad=2))
    eng.warmup()
    feats = [("g", str(j), 1.0) for j in range(5)]
    [resp] = eng.serve([ScoreRequest("x", {"g": feats})])
    assert resp.degraded
    assert FallbackReason.FEATURE_OVERFLOW in {f.reason
                                               for f in resp.fallbacks}
    assert resp.score is not None


# -- SLO degradation ---------------------------------------------------------


def test_slo_shed_and_reject(served):
    """Past the shed depth, batches run fixed-effect-only (typed fallback
    on every row, still scored); past the reject depth, submit() returns
    an immediate typed rejection with score=None."""
    _, samples, _, _, model_dir = served
    model = load_for_serving(model_dir)
    eng = ServingEngine(
        DeviceResidentModel(model),
        ServingConfig(max_batch=4, max_wait_s=0.0,
                      slo=SLOConfig(shed_queue_depth=2,
                                    reject_queue_depth=6)))
    eng.warmup()
    reqs = _requests(samples)[:10]

    rejected = []
    for r in reqs:
        resp = eng.submit(r)            # no pumping: queue depth climbs
        if resp is not None:
            rejected.append(resp)
    assert len(rejected) == 4           # admits 6, rejects the rest
    for resp in rejected:
        assert resp.score is None and resp.degraded
        assert resp.fallbacks[0].reason == FallbackReason.SLO_REJECTED

    served_resps = eng.drain()
    assert len(served_resps) == 6
    shed = [r for r in served_resps
            if FallbackReason.SLO_SHED_RANDOM_EFFECTS in
            {f.reason for f in r.fallbacks}]
    # depth was 6 > shed threshold 2 when the first batch formed
    assert shed and all(r.score is not None for r in shed)

    # fixed-only scores really exclude the random effect: compare against
    # a fixed-effect-only oracle for one shed response
    fixed_model = load_for_serving(model_dir, coordinates_to_load=["fixed"])
    oracle = ServingEngine(DeviceResidentModel(fixed_model),
                           ServingConfig(max_batch=1, max_wait_s=0.0))
    oracle.warmup()
    by_uid = {r.uid: r for r in served_resps}
    for req in reqs[:3]:
        if by_uid[req.uid] in shed:
            [want] = oracle.serve([ScoreRequest(req.uid, {"g": req.features["g"]},
                                                offset=req.offset)])
            assert by_uid[req.uid].score == pytest.approx(want.score, abs=1e-6)


# -- observability -----------------------------------------------------------


def test_serving_metrics_and_stats(served):
    from photon_tpu.utils import compile_cache

    engine, samples, _, _, _ = served
    before = compile_cache.compile_counts()["steady_state"]
    engine.serve(_requests(samples))
    stats = engine.stats()
    assert stats["warmed"] is True
    # delta-based: the compile counter is process-global
    assert stats["compile_counts"]["steady_state"] == before
    assert stats["counters"]["serving.requests"] >= len(samples)
    lat = stats["latency_seconds"]
    for stage in ("queue", "assemble", "score", "total"):
        assert stage in lat, lat
        assert lat[stage]["count"] > 0
        assert lat[stage]["p50"] is not None
        assert lat[stage]["p50"] <= lat[stage]["p95"] <= lat[stage]["p99"]
    json.dumps(stats)                   # report-safe


def test_runreport_gains_serving_section(served):
    import photon_tpu.serving as serving_pkg
    from photon_tpu.obs.report import build_run_report, validate_run_report

    engine, samples, _, _, _ = served
    engine.serve(_requests(samples))
    serving_pkg.set_active_engine(engine)
    try:
        report = build_run_report("serve-test")
        assert validate_run_report(report) == []
        assert isinstance(
            report["serving"]["compile_counts"]["steady_state"], float)
        assert report["serving"]["buckets"] == list(engine.ladder.buckets)
        assert "total" in report["serving"]["latency_seconds"]
    finally:
        serving_pkg.set_active_engine(None)


def test_histogram_bucket_quantiles():
    from photon_tpu.obs.metrics import MetricsRegistry, bucket_quantile

    reg = MetricsRegistry()
    h = reg.histogram("t.lat", buckets=(1.0, 2.0, 4.0))
    assert h.quantile(0.5) is None      # empty
    for v in (0.5, 1.5, 1.6, 3.0):
        h.observe(v)
    # p50 lands in the (1, 2] bucket, interpolated
    assert 1.0 <= h.quantile(0.5) <= 2.0
    assert h.quantile(0.99) <= 4.0
    # +Inf bucket clamps to the last finite bound
    assert bucket_quantile((1.0,), [0, 5], 0.99) == 1.0
    snap = reg.snapshot()["histograms"]["t.lat"]
    assert snap["p50"] == h.quantile(0.5)
    assert snap["p95"] == h.quantile(0.95)


# -- cli + tier-1 wiring -----------------------------------------------------


def test_cli_serve_jsonl_roundtrip(served, tmp_path):
    """python -m photon_tpu.cli.serve: JSONL in -> JSONL out, every uid
    answered, scores match the offline reference."""
    _, samples, offline, _, model_dir = served
    lines = []
    for s in samples:
        lines.append(json.dumps({
            "uid": s["uid"],
            "features": {"g": [[n, t, v] for n, t, v in s["g"]],
                         "u": [[n, t, v] for n, t, v in s["u"]]},
            "ids": {"userId": s["user"]},
            "offset": s["offset"]}))
    lines.append("this is not json")    # malformed lines are skipped
    stats_path = str(tmp_path / "stats.json")
    r = subprocess.run(
        [sys.executable, "-m", "photon_tpu.cli.serve",
         "--model-input-directory", model_dir,
         "--max-batch", "4", "--max-wait-ms", "0",
         "--stats-output", stats_path, "--log-level", "ERROR"],
        input="\n".join(lines) + "\n", text=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO)
    assert r.returncode == 0, r.stderr
    out = [json.loads(l) for l in r.stdout.splitlines() if l.strip()]
    by_uid = {o["uid"]: o for o in out}
    assert len(by_uid) == len(samples)
    for s, want in zip(samples, offline):
        assert by_uid[s["uid"]]["score"] == pytest.approx(float(want),
                                                          abs=1e-6)
    stats = json.load(open(stats_path))
    assert stats["compile_counts"]["steady_state"] == 0


def test_cli_serve_capture_records_admitted_requests(served, tmp_path):
    """--capture PATH: every admitted request lands in a crc32-framed
    JSONL capture that round-trips through read_capture with monotone
    engine-clock offsets — the recording half of the replay harness."""
    from photon_tpu.serving.replay import read_capture, stream_digest

    _, samples, _, _, model_dir = served
    lines = []
    for s in samples:
        lines.append(json.dumps({
            "uid": s["uid"],
            "features": {"g": [[n, t, v] for n, t, v in s["g"]],
                         "u": [[n, t, v] for n, t, v in s["u"]]},
            "ids": {"userId": s["user"]},
            "offset": s["offset"]}))
    cap_path = str(tmp_path / "traffic.jsonl")
    r = subprocess.run(
        [sys.executable, "-m", "photon_tpu.cli.serve",
         "--model-input-directory", model_dir,
         "--max-batch", "4", "--max-wait-ms", "0",
         "--capture", cap_path, "--log-level", "ERROR"],
        input="\n".join(lines) + "\n", text=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO)
    assert r.returncode == 0, r.stderr
    recs, stats = read_capture(cap_path)
    assert stats == {"capture_truncated": 0, "bad_records": 0}
    assert [c.request.uid for c in recs] == [s["uid"] for s in samples]
    offsets = [c.t for c in recs]
    assert offsets == sorted(offsets)
    assert all(t >= 0.0 for t in offsets)
    # the capture is replayable input: digest well-defined and stable
    pairs = [(c.t, c.request) for c in recs]
    assert stream_digest(pairs) == stream_digest(pairs)


def test_no_recompile_script():
    """Tier-1 wiring for scripts/check_serving_no_recompile.py: the
    zero-steady-state-compiles contract, checked dynamically."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "check_serving_no_recompile.py")],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stdout
    assert "ok:" in r.stdout


# -- two-tier coefficient store: tier boundaries -----------------------------


def _two_tier_engine(model_dir, prefetch=True):
    from photon_tpu.serving import CoeffStoreConfig

    cfg = ServingConfig(
        max_batch=8, max_wait_s=0.0,
        coeff_store=CoeffStoreConfig(hot_capacity=4, transfer_batch=2,
                                     prefetch=prefetch))
    engine = ServingEngine.from_model_dir(model_dir, config=cfg)
    engine.warmup()
    return engine


def test_two_tier_hot_scores_bitwise_equal_full_resident(served):
    """Once an entity's rows are resident, the two-tier engine and the
    fully-resident engine score it from the SAME f32 values through the
    same gather+dot shape — equality is exact, not approximate. With
    hot_capacity == N_USERS every known user stays resident after one
    promotion pass, so the whole second sweep crosses no tier boundary."""
    engine_full, samples, _offline, _, model_dir = served
    engine = _two_tier_engine(model_dir)
    try:
        reqs = _requests(samples)
        engine.serve(reqs)                    # promote the working set
        assert engine.model.drain_prefetch()
        got = engine.serve(reqs)
        want = engine_full.serve(reqs)
        for s, g, w in zip(samples, got, want):
            assert g.score == w.score, s["user"]
            if not s["user"].startswith("cold"):
                assert not g.degraded and not g.fallbacks
    finally:
        engine.shutdown()


def test_two_tier_cold_then_promoted(served):
    """The tier transition itself: first touch of a known entity with
    admission prefetch off degrades typed (COLD_MISS, fixed-effect-only
    score) AND queues the promotion; after the transfer drains, the same
    request scores clean and matches the offline reference."""
    _engine_full, samples, offline, _, model_dir = served
    engine = _two_tier_engine(model_dir, prefetch=False)
    try:
        i = next(i for i, s in enumerate(samples)
                 if not s["user"].startswith("cold"))
        req = _requests([samples[i]])
        r1 = engine.serve(req)[0]
        assert r1.degraded
        assert FallbackReason.COLD_MISS in {f.reason for f in r1.fallbacks}
        assert r1.score is not None           # fixed-effect-only, not a drop
        assert engine.model.drain_prefetch()
        r2 = engine.serve(req)[0]
        assert not r2.degraded and not r2.fallbacks
        assert r2.score == pytest.approx(float(offline[i]), abs=1e-6)
        st = engine.model.coeff_store_stats()
        assert st and list(st.values())[0]["cold_misses"] >= 1
    finally:
        engine.shutdown()


def test_two_tier_unknown_entity_typed(served):
    """An entity absent from the cold store is UNKNOWN (not COLD_MISS):
    no promotion is queued and the degradation reason distinguishes
    'never seen' from 'not resident yet'."""
    _engine_full, samples, offline, _, model_dir = served
    engine = _two_tier_engine(model_dir)
    try:
        i = next(i for i, s in enumerate(samples)
                 if s["user"].startswith("cold"))
        r = engine.serve(_requests([samples[i]]))[0]
        assert r.degraded
        reasons = {f.reason for f in r.fallbacks}
        assert FallbackReason.UNKNOWN_ENTITY in reasons
        assert FallbackReason.COLD_MISS not in reasons
        assert r.score == pytest.approx(float(offline[i]), abs=1e-6)
    finally:
        engine.shutdown()


# -- admission lookahead (MicroBatcher.on_admit) -----------------------------


def _req(uid, user="user0"):
    return ScoreRequest(uid, {"g": [], "u": []}, {"userId": user})


def test_on_admit_fires_once_before_queueing():
    t = {"now": 0.0}
    seen = []
    mb = MicroBatcher(BucketLadder(max_batch=4), max_wait_s=1.0,
                      clock=lambda: t["now"],
                      on_admit=lambda r: seen.append((r.uid, mb.depth())))
    mb.submit(_req("a"))
    mb.submit(_req("b"))
    # called exactly once per request, BEFORE it lands in the queue —
    # the depth the hook observes excludes the request being admitted
    assert seen == [("a", 0), ("b", 1)]


def test_on_admit_deadline_override_still_sees_request():
    """A request released early by its own deadline (tighter than the
    oldest-waiter wait) was still prefetched at admission: the hook ran
    under submit(), before any release policy could pop the batch."""
    t = {"now": 0.0}
    seen = []
    mb = MicroBatcher(BucketLadder(max_batch=8), max_wait_s=1.0,
                      clock=lambda: t["now"], deadline_headroom_s=0.1,
                      on_admit=lambda r: seen.append(r.uid))
    mb.submit(_req("slow"))
    mb.submit(_req("urgent"), deadline=0.5)
    assert not mb.ready()                     # 0 < 0.5 - 0.1, wait 0 < 1.0
    t["now"] = 0.41                           # inside deadline headroom
    assert mb.ready()
    batch, bucket = mb.next_batch()
    assert {p.request.uid for p in batch} == {"slow", "urgent"}
    assert seen == ["slow", "urgent"]         # both prefetched pre-pop
    assert bucket >= len(batch)


def test_on_admit_errors_never_refuse_admission():
    def boom(_r):
        raise RuntimeError("lookahead broke")

    mb = MicroBatcher(BucketLadder(max_batch=4), max_wait_s=0.0,
                      on_admit=boom)
    mb.submit(_req("a"))                      # must not raise
    assert mb.depth() == 1
    batch, _ = mb.next_batch(flush=True)
    assert batch[0].request.uid == "a"


# -- two-tier store under Zipf traffic, end to end ---------------------------
#
# A 2,000-entity cold store behind a hot set of 256 rows: a warm phase
# promotes the Zipf head through prefetch, a steady phase runs under the
# compile monitors, and a head row's served score is checked against a
# host oracle. One run; each gate is one case.


@pytest.fixture(scope="module")
def coldtier_quick_run(tmp_path_factory):
    from photon_tpu.io.cold_store import write_cold_store
    from photon_tpu.io.model_io import (
        ServingFixedEffect,
        ServingGameModel,
        ServingRandomEffect,
    )
    from photon_tpu.obs.metrics import registry
    from photon_tpu.serving import CoeffStoreConfig
    from photon_tpu.serving.scorer import MODES, get_scorer
    from photon_tpu.utils import compile_cache

    E, K, d, nnz = 2_000, 2, 32, 16
    n_warm, n_steady = 400, 600
    rng = np.random.default_rng(13)
    ids = np.char.add(b"e", np.char.zfill(np.arange(E).astype("S9"), 9))
    coef = rng.normal(size=(E, K)).astype(np.float32)
    lo = rng.integers(0, d - 1, size=E)
    proj = np.stack([lo, rng.integers(lo + 1, d)], axis=1).astype(np.int32)
    cold_path = str(tmp_path_factory.mktemp("coldtier_q")
                    / "per_user.coldstore")
    write_cold_store(cold_path, "per_user", "userId", "g", coef, proj, ids)
    names = [f"g{j}" for j in range(d)]
    imap = IndexMap({feature_key(n, ""): i for i, n in enumerate(names)})
    theta = rng.normal(size=d).astype(np.float32)
    cs = CoeffStoreConfig(hot_capacity=256, transfer_batch=64)
    model = DeviceResidentModel(ServingGameModel(
        TaskType.LINEAR_REGRESSION, [ServingFixedEffect("fixed", "g", theta)],
        [ServingRandomEffect("per_user", "userId", "g",
                             cold_store_path=cold_path)],
        {"g": imap}, {}), coeff_store=cs)
    engine = ServingEngine(model, ServingConfig(max_batch=64, max_wait_s=0.001,
                                                coeff_store=cs))
    engine.warmup()
    stats = lambda: next(iter(engine.model.coeff_store_stats().values()))
    zipf_rows = (rng.zipf(1.5, size=n_warm + n_steady) - 1) % E

    def req(i, row):
        cols = rng.choice(d, size=nnz, replace=False)
        return ScoreRequest(f"q{i}", {"g": [
            (names[c], "", float(rng.normal())) for c in cols]},
            {"userId": ids[row].decode()})

    for i in range(n_warm):
        engine.submit(req(i, zipf_rows[i]))
        if i % 256 == 255:
            engine.pump()
    engine.drain()
    engine.model.drain_prefetch()
    st0 = stats()

    jitted = [get_scorer(engine.model, mode, b)
              for mode in MODES for b in engine.ladder.buckets]
    jitted = [p if hasattr(p, "_cache_size")
              else getattr(p, "__wrapped__", p) for p in jitted]
    jitted = [f for f in jitted if hasattr(f, "_cache_size")]
    compiles0 = compile_cache.compile_counts()["steady_state"]
    misses0 = registry.counter("jitcache.misses").value
    traces0 = [f._cache_size() for f in jitted]
    for i in range(n_warm, n_warm + n_steady):
        engine.submit(req(i, zipf_rows[i]))
        engine.pump()
    engine.drain()
    engine.model.drain_prefetch()
    st = stats()
    hits = st["hits"] - st0["hits"]
    lookups = hits + st["cold_misses"] - st0["cold_misses"]
    zero = (compile_cache.compile_counts()["steady_state"] == compiles0
            and registry.counter("jitcache.misses").value == misses0
            and all(t1 <= t0 for t0, t1 in zip(
                traces0, [f._cache_size() for f in jitted])))

    # a Zipf-head row, served hot, against the host oracle
    hot_row = int(np.argmax(np.bincount((rng.zipf(1.5, size=512) - 1) % E)))
    vals = rng.normal(size=nnz)
    preq = ScoreRequest("parity", {"g": [(names[c], "", float(vals[c]))
                                         for c in range(nnz)]},
                        {"userId": ids[hot_row].decode()})
    engine.serve([preq])
    engine.model.drain_prefetch()
    resp = engine.serve([preq])[0]
    x = np.zeros(d, np.float32)
    x[:nnz] = vals.astype(np.float32)
    oracle = float(x @ theta) + float(
        sum(coef[hot_row, k] * x[proj[hot_row, k]] for k in range(K)))
    engine.shutdown()
    yield {
        "hot_parity_ok": abs(resp.score - oracle) <= 1e-6
        and not resp.fallbacks,
        "zero_steady_state_compiles": bool(zero),
        "steady_hit_rate_above_half": hits / max(lookups, 1) > 0.5,
        "store_promotes": st["promotes"] > 0,
    }


@pytest.mark.parametrize("gate", [
    "hot_parity_ok", "zero_steady_state_compiles",
    "steady_hit_rate_above_half", "store_promotes"])
def test_coldtier_warm_then_steady(coldtier_quick_run, gate):
    assert coldtier_quick_run[gate] is True, coldtier_quick_run


# -- fused serving kernel + int8 quantized arm -------------------------------


def test_fused_serving_kernel_parity(tmp_path, monkeypatch):
    """PHOTON_TPU_PALLAS_SERVING=1 routes the fixed-effect margin through
    the fused gather+margin kernel with offline parity intact, and the
    serving kernel-activation counter records the hits."""
    from photon_tpu.obs.metrics import registry

    monkeypatch.setenv("PHOTON_TPU_PALLAS_SERVING", "1")
    model_dir, imaps, vocab, users = _build_model_dir(tmp_path)
    samples = _make_traffic(23, users)
    offline = _offline_scores(model_dir, imaps, vocab, samples)
    hits0 = registry.counter("kernels.pallas_hits", path="serving").value
    engine = ServingEngine.from_model_dir(
        model_dir, config=ServingConfig(max_batch=8, max_wait_s=0.0))
    engine.warmup()
    got = np.asarray([r.score for r in engine.serve(_requests(samples))])
    np.testing.assert_allclose(got, offline, atol=1e-6)
    hits1 = registry.counter("kernels.pallas_hits", path="serving").value
    assert hits1 > hits0
    engine.shutdown()


def test_int8_arm_bounded_deviation_zero_compiles(tmp_path):
    """The int8 quantized arm: full_int8 joins the warmed modes, scores
    stay within quantization tolerance of the f32 offline scores (but
    are NOT bitwise-identical — the arm must actually be live), and
    steady-state traffic stays compile-free."""
    from photon_tpu.utils import compile_cache

    model_dir, imaps, vocab, users = _build_model_dir(tmp_path)
    samples = _make_traffic(23, users)
    offline = _offline_scores(model_dir, imaps, vocab, samples)
    engine = ServingEngine.from_model_dir(
        model_dir, config=ServingConfig(max_batch=8, max_wait_s=0.0,
                                        int8_serving=True))
    info = engine.warmup()
    assert "full_int8" in info["modes"]
    got = np.asarray([r.score for r in engine.serve(_requests(samples))])
    dev = float(np.max(np.abs(got - offline)))
    assert 0.0 < dev < 0.05, dev
    c0 = compile_cache.compile_counts().get("steady_state", 0)
    engine.serve(_requests(samples))
    assert compile_cache.compile_counts().get("steady_state", 0) == c0
    engine.shutdown()


def test_int8_quantize_rows_invariants():
    """Per-row symmetric int8: deterministic, row-local, zero rows get
    scale 1.0 (inert), and dequantization error is bounded by scale/2
    per slot."""
    from photon_tpu.serving.model_state import quantize_rows

    rng = np.random.default_rng(5)
    rows = rng.normal(size=(32, 6)).astype(np.float32) * 3.0
    rows[7] = 0.0
    q, s = quantize_rows(rows)
    q2, s2 = quantize_rows(rows)
    np.testing.assert_array_equal(q, q2)       # deterministic
    np.testing.assert_array_equal(s, s2)
    assert q.dtype == np.int8 and s.dtype == np.float32
    assert s[7, 0] == 1.0 and not q[7].any()   # zero row inert
    deq = q.astype(np.float32) * s
    assert np.max(np.abs(deq - rows)) <= float(np.max(s)) / 2.0 + 1e-7


def test_swap_int8_shadow_gate(tmp_path):
    """The swap ladder's int8_shadow gate: a sane deviation bound
    accepts (gate=pass); an impossible bound rejects with the typed
    gate failure and the live model is untouched."""
    from photon_tpu.serving.swap import swap_staged
    from photon_tpu.serving.types import SwapConfig

    model_dir, imaps, vocab, users = _build_model_dir(tmp_path)
    samples = _make_traffic(23, users)
    engine = ServingEngine.from_model_dir(
        model_dir, config=ServingConfig(
            max_batch=8, max_wait_s=0.0, int8_serving=True,
            swap=SwapConfig(int8_max_deviation=0.5)))
    engine.warmup()
    engine.serve(_requests(samples))           # shadow-gate sample
    res = swap_staged(engine, load_for_serving(model_dir), "v2")
    assert res.accepted, (res.reason, res.gates)
    assert res.gates.get("int8_shadow") == "pass"

    engine2 = ServingEngine.from_model_dir(
        model_dir, config=ServingConfig(
            max_batch=8, max_wait_s=0.0, int8_serving=True,
            swap=SwapConfig(int8_max_deviation=1e-12)))
    engine2.warmup()
    engine2.serve(_requests(samples))
    res2 = swap_staged(engine2, load_for_serving(model_dir), "v3")
    assert not res2.accepted
    assert res2.gates.get("int8_shadow") == "fail"
    engine.shutdown()
    engine2.shutdown()


# -- the fused kernels beside their XLA paths ------------------------------
#
# The ELL-sparse fused value+grad kernel against the XLA gather/scatter
# path, the serving gather+margin kernel against the XLA gathered dot,
# and the int8 dequant-gather deviation against its analytic bound, at
# n=4096, d=512, 8 slots a row. On a CPU the kernels run interpreted:
# the single pass is certified by the kernels the program holds.


@pytest.fixture(scope="module")
def fused_quick_run():
    import jax
    import jax.numpy as jnp

    from photon_tpu.ops import aggregators, pallas_glm
    from photon_tpu.ops.features import SparseFeatures
    from photon_tpu.ops.losses import LogisticLoss
    from photon_tpu.ops.normalization import no_normalization
    from photon_tpu.serving.model_state import quantize_rows

    rng = np.random.default_rng(11)
    n, d, k, bsz, kq = 4096, 512, 8, 64, 16
    x = SparseFeatures(
        jnp.asarray(rng.integers(0, d, size=(n, k)).astype(np.int32)),
        jnp.asarray((rng.normal(size=(n, k)) / np.sqrt(k))
                    .astype(np.float32)))
    y = jnp.asarray((rng.random(n) < 0.5).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.5, 1.5, size=n).astype(np.float32))
    coef = jnp.asarray((rng.normal(size=d) * 0.1).astype(np.float32))
    fused = lambda c: pallas_glm.fused_sparse_value_grad(
        LogisticLoss, x, y, None, w, c)
    vf, gf = jax.jit(fused)(coef)
    vx, gx = jax.jit(lambda c: aggregators.value_and_gradient(
        LogisticLoss, x, y, None, w, c, no_normalization()))(coef)
    sparse_dev = max(
        float(jnp.abs(vf - vx)) / max(abs(float(vx)), 1.0),
        float(jnp.max(jnp.abs(gf - gx)))
        / max(float(jnp.max(jnp.abs(gx))), 1e-30))

    si = jnp.asarray(rng.integers(0, d, size=(bsz, kq)).astype(np.int32))
    sval = rng.normal(size=(bsz, kq)).astype(np.float32)
    so = jnp.asarray(rng.normal(size=bsz).astype(np.float32))
    th = jnp.asarray((rng.normal(size=d) * 0.1).astype(np.float32))
    mf = jax.jit(pallas_glm.fused_gather_margin)(si, jnp.asarray(sval), so,
                                                 th)
    mx = so + jnp.sum(jnp.asarray(sval) * th[si], axis=-1)

    table = (rng.normal(size=(1024, kq)) * 0.5).astype(np.float32)
    q, s = quantize_rows(table)
    ent = rng.integers(0, 1024, size=bsz)
    int8_dev = float(np.max(np.abs(
        np.sum(sval * table[ent], axis=-1)
        - np.sum(sval * (q[ent].astype(np.float32) * s[ent]), axis=-1))))
    int8_bound = float(np.max(np.sum(np.abs(sval) * (s[ent] / 2.0),
                                     axis=-1)))
    return {
        "sparse_pallas_hits": str(jax.make_jaxpr(fused)(coef)).count(
            "pallas_call") >= 1,
        "sparse_parity_le_1e5": sparse_dev < 1e-5,
        "serving_parity_le_1e5": float(jnp.max(jnp.abs(mf - mx))) < 1e-5,
        "int8_within_bound": int8_dev <= int8_bound + 1e-6,
    }


@pytest.mark.parametrize("gate", [
    "sparse_pallas_hits", "sparse_parity_le_1e5", "serving_parity_le_1e5",
    "int8_within_bound"])
def test_fused_kernels_one_pass_and_parity(fused_quick_run, gate):
    assert fused_quick_run[gate] is True, fused_quick_run
