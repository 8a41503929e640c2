"""Random-effect λ-lane sweep tests: HBM footprint planner, lane-vs-scalar
bitwise parity, double-buffered blocked sweeps, chaos resilience.

Contract under test (the random-effect half of the sweep machinery):

* ``parallel/memory`` plans a K-lane sweep per size bucket from pure,
  pinned byte arithmetic — full_k / chunked / single_lambda, never a
  runtime OOM — and the plan lands in the RunReport ``re_plan`` section.
* ``update_model_swept`` / ``update_model_blocked_swept`` solve K λ
  points per staged entity block with ONE data pass over every bucket,
  and every lane is BITWISE equal to the sequential ``update_model`` /
  ``update_model_blocked`` fit at that λ (the flattened-lane program
  tiles lanes into the entity axis, so XLA lowers the exact reductions
  of the scalar program — stronger than the fixed-effect sweep's
  tolerance contract in test_sweep.py).
* Lane chunking under a forced-small budget degrades passes, never
  results; padded tail lanes are dropped, never published.
* The v4 ``re_block_cursor`` kill/resume contract extends to K>1: kill
  after bucket b's checkpoint hook, resume at ``start_block=b+1`` with
  the ``[K, E, d]`` table, bitwise.
"""

import dataclasses

import numpy as np
import pytest

# import-order guard: problem must come in before function.objective
from photon_tpu.optim.problem import (  # noqa: F401  (import order)
    GLMOptimizationConfiguration,
    OptimizerConfig,
)
from photon_tpu.function.objective import L2Regularization
from photon_tpu.parallel import memory as hbm

GRID = [0.1, 0.5, 2.0, 10.0]  # includes the λ=10 convergence knife edge


def _coordinate(seed=7, n=800, d=4, ents=60, max_buckets=3, nnz=None):
    """Zipf-skewed logistic random-effect coordinate with L2 sweeps
    enabled (mirrors test_coeff_store._coordinate; ``nnz`` makes the
    feature rows sparse so the sparse block kernel is exercised)."""
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
    from photon_tpu.types import TaskType

    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, ents + 1) ** 1.3
    ent = rng.choice(ents, size=n, p=p / p.sum())
    if nnz is None:
        idx = np.arange(d, dtype=np.int32)
        rows = [(idx, rng.normal(size=d)) for _ in range(n)]
    else:
        rows = [(np.sort(rng.choice(d, size=nnz, replace=False))
                 .astype(np.int32), rng.normal(size=nnz))
                for _ in range(n)]
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
            optimizer=OptimizerConfig(max_iterations=25, tolerance=1e-8),
            regularization=L2Regularization))
    return coord, ds, vocab


def _sequential_fits(coord, grid, blocked=False):
    """The oracle: one scalar fit per λ. Returns (coefs, iters) lists."""
    base = coord.config
    coefs, iters = [], []
    try:
        for w in grid:
            coord.config = dataclasses.replace(
                base, regularization_weight=float(w))
            m = (coord.update_model_blocked(None) if blocked
                 else coord.update_model(None, None))
            coefs.append(np.asarray(m.coefficients))
            iters.append(np.asarray(coord.last_tracker.iterations))
    finally:
        coord.config = base
    return coefs, iters


# -- planner: pinned byte arithmetic ----------------------------------------


class TestPlannerBytes:
    # E=4 entities, S=8 samples, W=3 ELL width, f64:
    #   ELL 4*8*3*(4+8) + labels/offsets/weights/sample_rows 4*8*(3*8+4)
    #   + entity_rows 4*4
    def test_block_data_bytes_pinned(self):
        assert hbm.block_data_bytes(4, 8, 3, 8) == 1152 + 896 + 16  # 2064

    def test_lane_state_bytes_pinned(self):
        # E=4, d=3, f64, history=10: theta stack + result + 2*history
        # L-BFGS pairs + 6 working vectors = 4*3*8*(2 + 20 + 6)
        assert hbm.lane_state_bytes(4, 3, 8, 10) == 2688

    def test_full_k_peak_formula(self):
        # peak(c) = 2*data + c*(data + lane): each lane re-tiles the
        # block (flattened-lane program) on top of the double buffer
        plan = hbm.plan_block_ladder(
            [(4, 8, 3)], lanes=4, dim=3, itemsize=8, history=10,
            hbm_budget_bytes=1 << 30)
        (b,) = plan.buckets
        assert b.strategy == hbm.STRATEGY_FULL
        assert b.lane_chunk == 4 and b.passes == 1
        assert b.peak_bytes == 2 * 2064 + 4 * (2064 + 2688)  # 23136
        assert not b.over_budget and not plan.degraded

    def test_chunked_at_exact_budget_boundary(self):
        base, per_lane = 2 * 2064, 2064 + 2688
        plan = hbm.plan_block_ladder(
            [(4, 8, 3)], lanes=4, dim=3, itemsize=8, history=10,
            hbm_budget_bytes=base + 2 * per_lane)
        (b,) = plan.buckets
        assert b.strategy == hbm.STRATEGY_CHUNKED
        assert b.lane_chunk == 2 and b.passes == 2
        assert b.peak_bytes == base + 2 * per_lane
        assert not b.over_budget
        # one byte less: c=1, typed single_lambda, K passes
        plan = hbm.plan_block_ladder(
            [(4, 8, 3)], lanes=4, dim=3, itemsize=8, history=10,
            hbm_budget_bytes=base + 2 * per_lane - 1)
        (b,) = plan.buckets
        assert b.strategy == hbm.STRATEGY_SINGLE
        assert b.lane_chunk == 1 and b.passes == 4
        assert not b.over_budget

    def test_over_budget_is_typed_never_raised(self):
        # even c=1 exceeds the budget: the planner reports, not raises
        plan = hbm.plan_block_ladder(
            [(4, 8, 3)], lanes=4, dim=3, itemsize=8, history=10,
            hbm_budget_bytes=5000)
        (b,) = plan.buckets
        assert b.lane_chunk == 1 and b.over_budget
        assert plan.over_budget

    def test_ladder_wide_chunk_is_tightest_bucket(self):
        # big bucket degrades to c=1, small one fits full K: the
        # all-at-once program runs at the min; passes is the max
        plan = hbm.plan_block_ladder(
            [(400, 64, 8), (4, 8, 3)], lanes=4, dim=8, itemsize=8,
            history=10,
            hbm_budget_bytes=3 * hbm.block_data_bytes(400, 64, 8, 8)
            + hbm.lane_state_bytes(400, 8, 8, 10))
        assert plan.buckets[0].lane_chunk == 1
        assert plan.buckets[1].lane_chunk == 4
        assert plan.lane_chunk == 1
        assert plan.passes == 4
        assert plan.degraded

    def test_budget_sources(self, monkeypatch):
        monkeypatch.delenv(hbm.ENV_BUDGET, raising=False)
        plan = hbm.plan_block_ladder(
            [(4, 8, 3)], lanes=2, dim=3, itemsize=8,
            hbm_budget_bytes=1 << 20)
        assert plan.budget_source == "override"
        monkeypatch.setenv(hbm.ENV_BUDGET, "123456")
        budget, source = hbm.default_hbm_budget_bytes()
        assert (budget, source) == (123456, "env")
        plan = hbm.plan_block_ladder([(4, 8, 3)], lanes=2, dim=3,
                                     itemsize=8)
        assert plan.budget_bytes == 123456
        assert plan.budget_source == "env"

    def test_rejects_degenerate_inputs(self):
        with pytest.raises(ValueError):
            hbm.plan_block_ladder([(4, 8, 3)], lanes=0, dim=3, itemsize=8)
        with pytest.raises(ValueError):
            hbm.plan_block_ladder([(4, 8, 3)], lanes=2, dim=3, itemsize=8,
                                  hbm_budget_bytes=0)

    def test_plan_for_dataset_matches_manual(self):
        coord, ds, _ = _coordinate(n=300, ents=30, max_buckets=3)
        plan = hbm.plan_for_dataset(ds, lanes=4, history=10,
                                    hbm_budget_bytes=1 << 30)
        shapes = [(b.num_rows, b.max_samples, b.features.values.shape[-1])
                  for b in ds.blocks]
        # the flat-order map (one int32 a flat row) is held beside every
        # bucket: n = 300 rows
        assert plan.resident_bytes == 4 * 300
        manual = hbm.plan_block_ladder(
            shapes, lanes=4, dim=ds.projected_dim, itemsize=8, history=10,
            hbm_budget_bytes=1 << 30, resident_bytes=4 * 300)
        assert [b.to_dict() for b in plan.buckets] == \
            [b.to_dict() for b in manual.buckets]
        assert plan.dtype == "float64"

    def test_resident_bytes_come_off_every_buckets_headroom(self):
        base, per_lane = 2 * 2064, 2064 + 2688
        kw = dict(lanes=4, dim=3, itemsize=8, history=10,
                  hbm_budget_bytes=base + 3 * per_lane + 100)
        assert hbm.plan_block_ladder([(4, 8, 3)], **kw).lane_chunk == 3
        plan = hbm.plan_block_ladder([(4, 8, 3)], resident_bytes=101, **kw)
        (b,) = plan.buckets
        assert b.lane_chunk == 2 and plan.to_dict()["resident_bytes"] == 101
        assert b.peak_bytes == 101 + base + 2 * per_lane

    def test_record_plan_feeds_run_report(self):
        from photon_tpu.obs.report import build_run_report, \
            validate_run_report

        hbm.reset_plan_stats()
        try:
            assert hbm.report_section() is None  # nothing planned yet
            plan = hbm.plan_block_ladder(
                [(4, 8, 3)], lanes=4, dim=3, itemsize=8,
                hbm_budget_bytes=2 * 2064 + (2064 + 2688))
            hbm.record_plan(plan)
            section = hbm.report_section()
            assert section["plans"] == 1
            assert section["buckets_degraded"] == 1
            assert section["last_plan"]["lane_chunk"] == 1
            report = build_run_report("test")
            assert report["re_plan"]["plans"] == 1
            assert validate_run_report(report) == []
        finally:
            hbm.reset_plan_stats()


# -- all-at-once sweep: bitwise lane-vs-scalar parity -----------------------


class TestSweptParity:
    def test_every_lane_bitwise_equals_sequential(self):
        coord, _ds, _ = _coordinate()
        refs, refs_it = _sequential_fits(coord, GRID)
        models = coord.update_model_swept(None, None, GRID)
        assert len(models) == len(GRID)
        for k in range(len(GRID)):
            np.testing.assert_array_equal(
                np.asarray(models[k].coefficients), refs[k])
            np.testing.assert_array_equal(
                np.asarray(coord.last_lane_trackers[k].iterations),
                refs_it[k])
        assert len(coord.last_lane_failed_entities) == len(GRID)

    def test_k1_bitwise_equals_update_model(self):
        coord, _ds, _ = _coordinate(seed=3)
        (ref,), (it_ref,) = _sequential_fits(coord, [2.0])
        (m,) = coord.update_model_swept(None, None, [2.0])
        np.testing.assert_array_equal(np.asarray(m.coefficients), ref)
        np.testing.assert_array_equal(
            np.asarray(coord.last_lane_trackers[0].iterations), it_ref)

    def test_sparse_blocks_bitwise(self):
        coord, _ds, _ = _coordinate(seed=11, n=600, d=12, ents=50, nnz=4)
        refs, _ = _sequential_fits(coord, GRID)
        models = coord.update_model_swept(None, None, GRID)
        for k in range(len(GRID)):
            np.testing.assert_array_equal(
                np.asarray(models[k].coefficients), refs[k])

    def test_padded_tail_chunk_bitwise(self):
        # force c=3 for K=4: the second chunk runs one real lane plus a
        # padded tail (repeated last λ) that must never be published
        coord, ds, _ = _coordinate()
        K = len(GRID)
        roomy = hbm.plan_for_dataset(ds, lanes=K, history=10,
                                     hbm_budget_bytes=1 << 30)
        budget = roomy.resident_bytes + max(
            2 * b.data_bytes + 3 * (b.data_bytes + b.lane_bytes)
            for b in roomy.buckets)
        plan = hbm.plan_for_dataset(ds, lanes=K, history=10,
                                    hbm_budget_bytes=budget)
        assert plan.lane_chunk == 3 and plan.degraded
        refs, _ = _sequential_fits(coord, GRID)
        models = coord.update_model_swept(None, None, GRID,
                                          hbm_budget_bytes=budget)
        assert coord.last_block_plan.lane_chunk == 3
        for k in range(K):
            np.testing.assert_array_equal(
                np.asarray(models[k].coefficients), refs[k])

    def test_single_lambda_degradation_identical(self):
        coord, ds, _ = _coordinate()
        full = [np.asarray(m.coefficients)
                for m in coord.update_model_swept(None, None, GRID)]
        tiny = max(3 * b.data_bytes + b.lane_bytes
                   for b in coord.last_block_plan.buckets)
        degraded = coord.update_model_swept(None, None, GRID,
                                            hbm_budget_bytes=tiny)
        plan = coord.last_block_plan
        assert plan.lane_chunk == 1 and plan.degraded
        # the binding bucket runs one λ per pass; small buckets may
        # still fit more lanes — the ladder program runs at the min
        assert hbm.STRATEGY_SINGLE in {b.strategy for b in plan.buckets}
        for k in range(len(GRID)):
            np.testing.assert_array_equal(
                np.asarray(degraded[k].coefficients), full[k])


# -- blocked sweep: one staging pass serves every λ -------------------------


class TestBlockedSwept:
    def test_bitwise_vs_sequential_blocked_and_staging_economics(self):
        coord, ds, _ = _coordinate()
        K, n_blocks = len(GRID), len(ds.blocks)
        refs, refs_it = _sequential_fits(coord, GRID, blocked=True)
        seq_stagings = K * n_blocks
        models = coord.update_model_blocked_swept(None, GRID)
        # the whole grid staged each bucket exactly once
        assert coord.last_blocks_staged == n_blocks
        assert coord.last_blocks_staged <= seq_stagings // K + n_blocks
        for k in range(K):
            np.testing.assert_array_equal(
                np.asarray(models[k].coefficients), refs[k])
            np.testing.assert_array_equal(
                np.asarray(coord.last_lane_trackers[k].iterations),
                refs_it[k])
        assert coord.last_block_overlap is not None

    def test_one_lane_blocked_fit_leaves_a_scalar_fits_side_effects(self):
        """``update_model_blocked`` is the blocked sweep at one lane, and
        what it publishes is a scalar fit's: no sweep run, no ``re_plan``,
        ``last_tracker`` with the buckets' rows (so
        ``obs.solver.lane_counts()`` works), host-resident coefficients."""
        from photon_tpu.optim import batched

        coord, ds, _ = _coordinate(seed=5)
        hbm.reset_plan_stats()
        sweeps_before = batched.report_section()
        try:
            m = coord.update_model_blocked(None)
            assert hbm.report_section() is None
        finally:
            hbm.reset_plan_stats()
        assert batched.report_section() == sweeps_before
        assert not hasattr(coord, "last_lane_trackers")
        assert isinstance(m.coefficients, np.ndarray)
        assert m.coefficients.shape == (ds.num_entities, ds.projected_dim)
        tracker = coord.last_tracker
        assert len(tracker.bucket_rows) == len(ds.blocks)
        counts = tracker.lane_counts()
        assert counts["trips"] > 0
        assert 0 < counts["sum"] <= counts["capacity"]
        assert coord.last_failed_entities == 0
        assert coord.last_failure is None
        # the resident fit's tracker says the same of the same entities
        ref = coord.update_model(None, None)
        np.testing.assert_array_equal(m.coefficients,
                                      np.asarray(ref.coefficients))
        assert coord.last_tracker.lane_counts() == counts

    def test_blocked_swept_matches_all_at_once(self):
        coord, _ds, _ = _coordinate(seed=3)
        flat = [np.asarray(m.coefficients)
                for m in coord.update_model_swept(None, None, GRID)]
        blocked = coord.update_model_blocked_swept(None, GRID)
        for k in range(len(GRID)):
            np.testing.assert_array_equal(
                np.asarray(blocked[k].coefficients), flat[k])

    def test_prefetch_off_is_bitwise(self):
        coord, _ds, _ = _coordinate()
        on = [np.asarray(m.coefficients)
              for m in coord.update_model_blocked_swept(None, GRID)]
        off = coord.update_model_blocked_swept(None, GRID, prefetch=False)
        assert coord.last_blocks_staged == len(_ds.blocks)
        for k in range(len(GRID)):
            np.testing.assert_array_equal(
                np.asarray(off[k].coefficients), on[k])

    def test_planner_peak_covers_measured(self):
        coord, _ds, _ = _coordinate()
        coord.update_model_blocked_swept(None, GRID)
        assert coord.last_block_measured
        for m in coord.last_block_measured:
            assert m["planned_peak_bytes"] >= m["measured_peak_bytes"], m

    def test_forced_budget_degrades_passes_not_results(self):
        coord, ds, _ = _coordinate()
        full = [np.asarray(m.coefficients)
                for m in coord.update_model_blocked_swept(None, GRID)]
        tiny = max(3 * b.data_bytes + b.lane_bytes
                   for b in coord.last_block_plan.buckets)
        degraded = coord.update_model_blocked_swept(
            None, GRID, hbm_budget_bytes=tiny)
        plan = coord.last_block_plan
        assert plan.degraded and plan.budget_source == "override"
        strategies = [m["strategy"] for m in coord.last_block_measured]
        assert any(s != hbm.STRATEGY_FULL for s in strategies)
        # degradation costs compute passes over the SAME staged copy —
        # staging traffic is unchanged
        assert coord.last_blocks_staged == len(ds.blocks)
        for k in range(len(GRID)):
            np.testing.assert_array_equal(
                np.asarray(degraded[k].coefficients), full[k])

    def test_per_lane_warm_start_shape_validated(self):
        coord, ds, _ = _coordinate(n=300, ents=30)
        bad = np.zeros((len(GRID) + 1, ds.num_entities,
                        ds.projected_dim))
        with pytest.raises(ValueError, match=r"\[K="):
            coord.update_model_blocked_swept(None, GRID, warm_start=bad)

    def test_resume_from_cursor_bitwise_k_lanes(self):
        """The v4 re_block_cursor contract at K>1: rebuild the [K, E, d]
        table from the buckets solved before the cut, resume at the
        cursor, and every lane reproduces the uninterrupted run bitwise
        (entities live in exactly one block)."""
        coord, ds, _ = _coordinate()
        K = len(GRID)
        full = np.stack([np.asarray(m.coefficients) for m in
                         coord.update_model_blocked_swept(None, GRID)])
        half = len(ds.blocks) // 2 or 1
        E = full.shape[1]
        tbl = np.zeros_like(full)
        for blk in ds.blocks[:half]:
            ents = np.asarray(blk.entity_rows)
            ok = (ents >= 0) & (ents < E)
            tbl[:, ents[ok]] = full[:, ents[ok]]
        resumed = coord.update_model_blocked_swept(
            None, GRID, warm_start=tbl, start_block=half)
        for k in range(K):
            np.testing.assert_array_equal(
                np.asarray(resumed[k].coefficients), full[k])


# -- chaos: staging faults and mid-sweep kills ------------------------------


class TestChaos:
    def test_read_delay_does_not_change_results(self):
        from photon_tpu.resilience import chaos

        coord, _ds, _ = _coordinate(n=400, ents=40)
        ref = [np.asarray(m.coefficients)
               for m in coord.update_model_blocked_swept(None, GRID)]
        chaos.install(chaos.ChaosConfig(re_block_read_delay_s=0.05,
                                        re_block_read_delays=2))
        try:
            got = coord.update_model_blocked_swept(None, GRID)
            assert chaos._active.re_block_read_delays_done == 2
        finally:
            chaos.uninstall()
        for k in range(len(GRID)):
            np.testing.assert_array_equal(
                np.asarray(got[k].coefficients), ref[k])

    def test_read_error_retried_results_identical(self):
        from photon_tpu.resilience import chaos

        coord, _ds, _ = _coordinate(n=400, ents=40)
        ref = [np.asarray(m.coefficients)
               for m in coord.update_model_blocked_swept(None, GRID)]
        chaos.install(chaos.ChaosConfig(re_block_read_errors=1))
        try:
            got = coord.update_model_blocked_swept(None, GRID)
            assert chaos._active.re_block_read_errors_done == 1
        finally:
            chaos.uninstall()
        for k in range(len(GRID)):
            np.testing.assert_array_equal(
                np.asarray(got[k].coefficients), ref[k])

    def test_kill_mid_swept_block_then_bitwise_resume(self):
        """Chaos kill fires AFTER bucket h's on_block checkpoint — the
        cursor and [K, E, d] table at the cut fully determine the rest;
        the resumed K-lane run is bitwise the uninterrupted one."""
        from photon_tpu.resilience import chaos

        coord, ds, _ = _coordinate()
        K = len(GRID)
        assert len(ds.blocks) >= 2
        full = np.stack([np.asarray(m.coefficients) for m in
                         coord.update_model_blocked_swept(None, GRID)])
        h = len(ds.blocks) // 2
        cursor = []
        chaos.install(chaos.ChaosConfig(re_block_kill_at=h))
        try:
            with pytest.raises(chaos.SimulatedKill):
                coord.update_model_blocked_swept(
                    None, GRID,
                    on_block=lambda b, nb: cursor.append((b, nb)))
        finally:
            chaos.uninstall()
        # checkpoint hook ran for every bucket up to and INCLUDING the
        # killed one — the cursor is durable before the kill
        assert cursor[-1] == (h + 1, len(ds.blocks))
        E = full.shape[1]
        tbl = np.zeros_like(full)
        for blk in ds.blocks[:h + 1]:
            ents = np.asarray(blk.entity_rows)
            ok = (ents >= 0) & (ents < E)
            tbl[:, ents[ok]] = full[:, ents[ok]]
        resumed = coord.update_model_blocked_swept(
            None, GRID, warm_start=tbl, start_block=h + 1)
        for k in range(K):
            np.testing.assert_array_equal(
                np.asarray(resumed[k].coefficients), full[k])


# -- spans: the checkpoint hook stays outside the timed solve span ----------


@pytest.fixture()
def obs():
    from photon_tpu import obs as obs_mod

    obs_mod.reset()
    obs_mod.configure(True)
    yield obs_mod
    obs_mod.reset()


class TestSpanNesting:
    def _assert_hook_outside_solve_span(self, obs_mod, run):
        from photon_tpu.obs import spans

        def hook(_b, _nb):
            with obs_mod.span("re/checkpoint"):
                pass

        run(hook)
        recs = spans.records()
        blocks = [r for r in recs if r["name"] == "re/solve_block"]
        hooks = [r for r in recs if r["name"] == "re/checkpoint"]
        assert blocks and hooks
        # per-bucket solves nest under the ladder span...
        assert all(r["parent"] == "re/solve_blocked" for r in blocks)
        # ...but the checkpoint hook fires AFTER the bucket's timed span
        # closes: a span opened inside on_block parents to the ladder,
        # never to re/solve_block (checkpoint I/O must not pollute the
        # per-bucket solve timings)
        assert all(r["parent"] == "re/solve_blocked" for r in hooks)

    def test_on_block_outside_timed_span_blocked(self, obs):
        coord, _ds, _ = _coordinate(n=300, ents=30)
        self._assert_hook_outside_solve_span(
            obs, lambda hook: coord.update_model_blocked(
                None, on_block=hook))

    def test_on_block_outside_timed_span_blocked_swept(self, obs):
        coord, _ds, _ = _coordinate(n=300, ents=30)
        self._assert_hook_outside_solve_span(
            obs, lambda hook: coord.update_model_blocked_swept(
                None, [0.5, 2.0], on_block=hook))


# -- planner -> lane sweep -> forced degradation, end to end ----------------
#
# A 4-point λ sweep over a 3-bucket ladder (2,500 rows, 80 Zipf-skewed
# entities) against 4 sequential blocked fits: staging passes, bitwise
# lanes on both the blocked and the all-at-once path, the planner's
# per-bucket bound, a forced small budget, and a second grid through the
# same programs. One run; each gate is one case.


@pytest.fixture(scope="module")
def re_sweep_quick_run():
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
    from photon_tpu.obs.metrics import registry
    from photon_tpu.types import TaskType

    n, d, ents, K = 2_500, 4, 80, 4
    grid = np.logspace(-1.0, 1.0, K)
    rng = np.random.default_rng(23)
    ent = rng.zipf(1.35, size=n) % ents
    idx = np.arange(d, dtype=np.int32)
    rows = [(idx, rng.normal(size=d)) for _ in range(n)]
    y = (rng.random(n) > 0.5).astype(np.float64)
    df = GameDataFrame(num_samples=n, response=y,
                       feature_shards={"u": FeatureShard(rows, d)},
                       id_tags={"userId": [str(e) for e in ent]})
    ds = build_random_effect_dataset(
        df, RandomEffectDataConfiguration("userId", "u",
                                          max_entity_buckets=3),
        EntityVocabulary(), dtype=np.float64)
    coord = RandomEffectCoordinate(
        ds, n, "userId", "u", TaskType.LOGISTIC_REGRESSION,
        GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(max_iterations=25, tolerance=1e-8),
            regularization=L2Regularization, regularization_weight=1.0))
    base = coord.config

    def at(w):
        coord.config = dataclasses.replace(base, regularization_weight=w)

    seq, seq_passes = [], 0
    for w in grid:
        at(float(w))
        seq.append(np.asarray(coord.update_model_blocked(None).coefficients))
        seq_passes += coord.last_blocks_staged
    swept = [np.asarray(m.coefficients)
             for m in coord.update_model_blocked_swept(None, grid)]
    swept_passes = coord.last_blocks_staged
    measured = list(coord.last_block_measured)
    plan = coord.last_block_plan
    flat_refs = []
    for w in grid:
        at(float(w))
        flat_refs.append(np.asarray(coord.update_model(None, None)
                                    .coefficients))
    flat = coord.update_model_swept(None, None, grid)

    # a budget that fits one staged bucket but not its K lanes at once
    tiny = max(3 * b.data_bytes + b.lane_bytes for b in plan.buckets)
    small = coord.update_model_blocked_swept(None, grid,
                                             hbm_budget_bytes=tiny)
    small_plan = coord.last_block_plan

    solvers = {coord._block_solve_swept_fn(bool(f))
               for f in set(coord._dense_local_blocks)}
    traces0 = sum(f._cache_size() for f in solvers)
    recompiles0 = registry.snapshot()["counters"].get(
        "jitcache.recompiles", 0)
    coord.update_model_blocked_swept(None, np.logspace(-2.0, 2.0, K))
    new_traces = sum(f._cache_size() for f in solvers) - traces0
    new_recompiles = registry.snapshot()["counters"].get(
        "jitcache.recompiles", 0) - recompiles0
    hbm.reset_plan_stats()
    return {
        "passes_within_1_over_k_plus_ladder":
            swept_passes <= seq_passes / K + len(ds.blocks),
        "bitwise_all_lanes": all(
            np.array_equal(swept[k], seq[k])
            and np.array_equal(np.asarray(flat[k].coefficients),
                               flat_refs[k]) for k in range(K)),
        "planned_ge_measured_all_buckets": bool(measured) and all(
            m["planned_peak_bytes"] >= m["measured_peak_bytes"]
            for m in measured),
        "forced_budget_degrades": bool(small_plan.degraded),
        "degraded_models_identical_to_full_k": all(
            np.array_equal(np.asarray(small[k].coefficients), swept[k])
            for k in range(K)),
        "zero_recompiles": new_traces == 0 and new_recompiles == 0,
    }


@pytest.mark.parametrize("gate", [
    "passes_within_1_over_k_plus_ladder", "bitwise_all_lanes",
    "planned_ge_measured_all_buckets", "forced_budget_degrades",
    "degraded_models_identical_to_full_k", "zero_recompiles"])
def test_planner_lanes_degradation(re_sweep_quick_run, gate):
    assert re_sweep_quick_run[gate] is True, re_sweep_quick_run
