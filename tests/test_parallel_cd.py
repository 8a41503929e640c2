"""Parallel coordinate descent: block-concurrent sweeps over
bounded-stale shared scores (game/parallel_cd.py scheduling +
game/descent.py parallel sweep mode).

Covers the parity gates (singleton groups bitwise-identical to
sequential; auto-grouping reaches the sequential validation metric
within 1e-4 relative), the group-granular validation cadence, the
staleness guard's sequential fallback (typed event, never an
exception), member-level failure isolation inside a group,
group-boundary preemption with bitwise-equal resume, the chaos
straggler injector, mesh placement planning, the v3 checkpoint schema,
and the host-sync lint extension. Faults are injected through
photon_tpu.resilience.chaos — no monkeypatching of library internals.
"""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.estimators.game_estimator import (
    CoordinateConfiguration,
    FixedEffectDataConfiguration,
    GameEstimator,
)
from photon_tpu.evaluation.evaluators import EvaluatorType
from photon_tpu.function.objective import L2Regularization
from photon_tpu.game import checkpoint as ckpt
from photon_tpu.game import parallel_cd
from photon_tpu.game.dataset import FeatureShard, GameDataFrame
from photon_tpu.game.descent import (
    CoordinateDescentConfig,
    run_coordinate_descent,
)
from photon_tpu.game.model import GameModel
from photon_tpu.game.random_effect import RandomEffectDataConfiguration
from photon_tpu.optim.problem import (
    GLMOptimizationConfiguration,
    OptimizerConfig,
)
from photon_tpu.resilience import chaos, failures, shutdown
from photon_tpu.resilience.failures import (
    CoordinateFailureError,
    PreemptionRequested,
)
from photon_tpu.types import TaskType


@pytest.fixture(autouse=True)
def _clean_state():
    """Process-wide resilience + parallel-CD statistics must not leak."""
    failures.clear()
    shutdown.reset()
    chaos.uninstall()
    parallel_cd.reset()
    yield
    failures.clear()
    shutdown.reset()
    chaos.uninstall()
    parallel_cd.reset()


# ---------------------------------------------------------------------------
# grouping (pure host-side scheduling, no JAX compute)
# ---------------------------------------------------------------------------


def _fake_coords(spec):
    """{cid: is_random_effect} -> duck-typed coordinate dict."""
    out = {}
    for cid, is_re in spec.items():
        c = types.SimpleNamespace()
        if is_re:
            c.random_effect_type = cid
        out[cid] = c
    return out


class TestGrouping:
    def test_auto_groups_merges_consecutive_random_effects(self):
        seq = ["fixed", "per_user", "per_item", "fixed2", "per_ctx"]
        coords = _fake_coords({"fixed": False, "per_user": True,
                               "per_item": True, "fixed2": False,
                               "per_ctx": True})
        assert parallel_cd.auto_groups(seq, coords) == [
            ["fixed"], ["per_user", "per_item"], ["fixed2"], ["per_ctx"]]

    def test_auto_groups_degenerates_without_adjacent_random_effects(self):
        seq = ["fixed", "per_user", "fixed2"]
        coords = _fake_coords({"fixed": False, "per_user": True,
                               "fixed2": False})
        assert parallel_cd.auto_groups(seq, coords) == [
            ["fixed"], ["per_user"], ["fixed2"]]

    def test_validate_groups_accepts_exact_partition(self):
        seq = ["a", "b", "c"]
        assert parallel_cd.validate_groups([["a"], ["b", "c"]], seq) \
            == [["a"], ["b", "c"]]

    def test_validate_groups_rejects_bad_partitions(self):
        seq = ["a", "b", "c"]
        with pytest.raises(ValueError, match="empty group"):
            parallel_cd.validate_groups([["a"], [], ["b", "c"]], seq)
        with pytest.raises(ValueError, match="partition"):
            parallel_cd.validate_groups([["b"], ["a", "c"]], seq)  # reorder
        with pytest.raises(ValueError, match="partition"):
            parallel_cd.validate_groups([["a"], ["b"]], seq)  # missing c

    def test_resolve_groups_spans_index_the_flat_sequence(self):
        cfg = CoordinateDescentConfig(
            update_sequence=["f", "u", "i"], parallel=True,
            parallel_groups=[["f"], ["u", "i"]])
        spans = parallel_cd.resolve_groups(cfg, _fake_coords(
            {"f": False, "u": True, "i": True}))
        assert spans == [(0, ["f"]), (1, ["u", "i"])]


# ---------------------------------------------------------------------------
# GLMix fixture: fixed effect + two adjacent random effects, so the
# auto-grouping produces one genuine concurrency group
# ---------------------------------------------------------------------------


def _make_frames(rng, n=2000, d=8, users=30, items=20, d_u=3):
    w_g = rng.normal(size=d)
    w_u = rng.normal(size=(users, d_u))
    w_i = rng.normal(size=(items, d_u))

    def build(n):
        Xg = rng.normal(size=(n, d))
        Xu = rng.normal(size=(n, d_u))
        Xi = rng.normal(size=(n, d_u))
        uid = rng.integers(0, users, size=n)
        iid = rng.integers(0, items, size=n)
        logits = (Xg @ w_g + np.einsum("nd,nd->n", Xu, w_u[uid])
                  + np.einsum("nd,nd->n", Xi, w_i[iid]))
        y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logits))).astype(np.float64)
        iu = np.arange(d_u, dtype=np.int32)
        return GameDataFrame(
            num_samples=n, response=y,
            feature_shards={
                "g": FeatureShard(Xg, d),
                "u": FeatureShard([(iu, x) for x in Xu], d_u),
                "i": FeatureShard([(iu, x) for x in Xi], d_u)},
            id_tags={"userId": [str(v) for v in uid],
                     "itemId": [str(v) for v in iid]})

    return build(n), build(n // 2)


SEQ_IDS = ["fixed", "per_user", "per_item"]


def _estimator(num_iterations=4, **kw):
    opt = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=60, tolerance=1e-9),
        regularization=L2Regularization, regularization_weight=1.0)
    return GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {"fixed": CoordinateConfiguration(
            FixedEffectDataConfiguration("g"), opt),
         "per_user": CoordinateConfiguration(
             RandomEffectDataConfiguration("userId", "u"), opt),
         "per_item": CoordinateConfiguration(
             RandomEffectDataConfiguration("itemId", "i"), opt)},
        update_sequence=SEQ_IDS, num_iterations=num_iterations,
        validation_evaluators=[EvaluatorType.AUC],
        dtype=jnp.float64, **kw)


@pytest.fixture(scope="module")
def frames():
    return _make_frames(np.random.default_rng(7))


@pytest.fixture(scope="module")
def fitted(frames):
    """One sequential and one parallel (auto-grouped) reference fit,
    shared by the parity tests."""
    train, val = frames
    seq = _estimator().fit(train, validation_df=val)[-1]
    parallel_cd.reset()
    par = _estimator(parallel_cd=True).fit(train, validation_df=val)[-1]
    stats = (parallel_cd.report_section() or {}).get("parallel", {})
    parallel_cd.reset()
    return {"seq": seq, "par": par, "par_stats": stats}


@pytest.fixture(scope="module")
def direct(frames):
    """Coordinates + a validation fn for driving run_coordinate_descent
    directly (cadence counting, locked-coordinate resume)."""
    train, val = frames
    est = _estimator(num_iterations=1)
    est.fit(train)
    vocab, _coords, re_datasets = est._prep_cache[2]
    scorer = est._build_scorer(val, vocab, re_datasets)
    return {"coords": est._coordinates, "n": train.num_samples,
            "vfn": est._validation_fn(scorer, val)}


def _means(model, cid):
    m = model[cid]
    return np.asarray(m.model.coefficients.means if cid == "fixed"
                      else m.coefficients)


def _assert_models_equal(a, b):
    for cid in SEQ_IDS:
        assert np.array_equal(_means(a, cid), _means(b, cid)), \
            f"{cid}: models diverged"


# ---------------------------------------------------------------------------
# parity gates
# ---------------------------------------------------------------------------


class TestParity:
    def test_singleton_groups_bitwise_identical_to_sequential(
            self, frames, fitted):
        train, val = frames
        single = _estimator(
            parallel_cd=True,
            parallel_groups=[[c] for c in SEQ_IDS],
        ).fit(train, validation_df=val)[-1]
        _assert_models_equal(fitted["seq"].model, single.model)

    def test_auto_grouping_reaches_sequential_metric(self, fitted):
        hs = fitted["seq"].descent.validation_history[-1]
        hp = fitted["par"].descent.validation_history[-1]
        rel = abs(hs["AUC"] - hp["AUC"]) / abs(hs["AUC"])
        assert rel <= 1e-4, f"AUC diverged: {hs['AUC']} vs {hp['AUC']}"

    def test_auto_grouping_ran_concurrent_groups_cleanly(self, fitted):
        stats = fitted["par_stats"]
        assert stats["groups"] == [["fixed"], ["per_user", "per_item"]]
        assert stats["concurrent_groups"] == 4   # the RE group, per sweep
        assert stats["stale_regressions"] == 0
        assert stats["fallbacks"] == 0
        assert stats["member_failures"] == 0


# ---------------------------------------------------------------------------
# validation cadence: sequential validates per coordinate update (the
# reference behavior, with the sweep boundary REUSING the final
# coordinate's metrics instead of re-validating the identical models);
# a concurrent group commits atomically and validates ONCE per group
# ---------------------------------------------------------------------------


class TestValidationCadence:
    def _count(self, direct, cfg):
        calls = {"n": 0}

        def counting_vfn(model):
            calls["n"] += 1
            return direct["vfn"](model)

        run_coordinate_descent(direct["coords"], cfg, direct["n"],
                               validation_fn=counting_vfn,
                               dtype=jnp.float64)
        return calls["n"]

    def test_sequential_validates_once_per_coordinate_update(self, direct):
        cfg = CoordinateDescentConfig(update_sequence=SEQ_IDS,
                                      num_iterations=2)
        # 3 coordinates x 2 sweeps; the sweep boundary adds NOTHING
        # (regression test for the redundant double validation)
        assert self._count(direct, cfg) == 6

    def test_parallel_validates_once_per_group(self, direct):
        cfg = CoordinateDescentConfig(update_sequence=SEQ_IDS,
                                      num_iterations=2, parallel=True)
        # per sweep: singleton [fixed] keeps the per-coordinate cadence
        # (1) + concurrent [per_user, per_item] validates once (1)
        assert self._count(direct, cfg) == 4


# ---------------------------------------------------------------------------
# locked coordinate at a mid-sweep resume boundary (satellite: the
# resume_coord_idx bookkeeping must skip completed AND locked
# coordinates identically on re-entry)
# ---------------------------------------------------------------------------


class TestLockedMidSweepResume:
    def test_locked_coordinate_midsweep_resume_is_bitwise(
            self, direct, tmp_path):
        coords, n = direct["coords"], direct["n"]
        warm = run_coordinate_descent(
            coords, CoordinateDescentConfig(update_sequence=SEQ_IDS),
            n, dtype=jnp.float64).model
        locked_model = GameModel({"per_user": warm["per_user"]})
        cfg = CoordinateDescentConfig(
            update_sequence=SEQ_IDS, num_iterations=3,
            locked_coordinates=frozenset({"per_user"}))

        full = run_coordinate_descent(
            coords, cfg, n, initial_model=locked_model,
            dtype=jnp.float64).model

        ckdir = str(tmp_path / "ck")
        with chaos.active(chaos.ChaosConfig(preempt_at=(1, "per_item"))):
            with pytest.raises(PreemptionRequested) as ei:
                run_coordinate_descent(
                    coords, cfg, n, initial_model=locked_model,
                    dtype=jnp.float64, checkpoint_dir=ckdir)
        state = ckpt.load_latest(ckdir)
        assert state is not None
        assert state.sweep_in_progress == 1
        assert state.next_coordinate == 2  # mid-sweep, past locked per_user
        assert ei.value.checkpoint_path is not None

        shutdown.reset()
        resumed = run_coordinate_descent(
            coords, cfg, n, initial_model=locked_model,
            dtype=jnp.float64, checkpoint_dir=ckdir, resume=True).model
        _assert_models_equal(full, resumed)
        # the locked coordinate only ever scored: its model IS the input
        assert np.array_equal(_means(resumed, "per_user"),
                              np.asarray(warm["per_user"].coefficients))


# ---------------------------------------------------------------------------
# resilience inside a concurrency group
# ---------------------------------------------------------------------------


class TestGroupFailureIsolation:
    def test_member_failure_rolls_back_only_that_member(self, frames):
        train, _val = frames
        with chaos.active(chaos.ChaosConfig(nan_solve=(("per_user", 1),))):
            res = _estimator(num_iterations=3, parallel_cd=True).fit(train)
        rollbacks = [e for e in failures.snapshot()
                     if e["kind"] == "coordinate_rollback"]
        assert [(e["coordinate"], e["sweep"]) for e in rollbacks] \
            == [("per_user", 1)]
        assert not any(e["kind"] == "coordinate_abort"
                       for e in failures.snapshot())
        stats = parallel_cd.report_section()["parallel"]
        assert stats["member_failures"] == 1
        # the sweep-1 RE group committed every OTHER member
        rec = next(r for r in stats["group_records"]
                   if r["sweep"] == 1 and r["size"] == 2)
        assert rec["committed"] == 1
        assert np.isfinite(_means(res[-1].model, "per_user")).all()
        assert np.isfinite(_means(res[-1].model, "per_item")).all()

    def test_member_abort_commits_others_and_checkpoints_group_boundary(
            self, frames, tmp_path):
        train, _val = frames
        ckdir = str(tmp_path / "ck")
        cfg = chaos.ChaosConfig(nan_solve=(
            ("per_user", 0), ("per_user", 1), ("per_user", 2)))
        with chaos.active(cfg):
            with pytest.raises(CoordinateFailureError) as ei:
                _estimator(parallel_cd=True).fit(train, checkpoint_dir=ckdir)
        assert ei.value.coordinate == "per_user"
        assert ei.value.consecutive == 3

        state = ckpt.load_latest(str(tmp_path / "ck" / "config_000"))
        assert state is not None
        assert state.group_boundary is True
        assert state.next_coordinate == 3  # END of the [per_user, per_item]
        assert state.scores is not None and state.full_score is not None
        # the abort sweep's OTHER group members committed before the raise
        assert "per_item" in state.models

        # with the fault gone, resume finishes from the group boundary
        res = _estimator(parallel_cd=True).fit(
            train, checkpoint_dir=ckdir, resume=True)
        for cid in SEQ_IDS:
            assert np.isfinite(_means(res[-1].model, cid)).all()

    def test_preemption_at_group_boundary_resumes_bitwise(
            self, frames, fitted, tmp_path):
        train, val = frames
        ckdir = str(tmp_path / "ck")
        with chaos.active(chaos.ChaosConfig(preempt_at=(1, "per_user"))):
            with pytest.raises(PreemptionRequested) as ei:
                _estimator(parallel_cd=True).fit(
                    train, validation_df=val, checkpoint_dir=ckdir)
        assert ei.value.checkpoint_path is not None
        state = ckpt.load_latest(str(tmp_path / "ck" / "config_000"))
        assert state.group_boundary is True
        assert state.sweep_in_progress == 1
        assert state.next_coordinate == 1  # the RE group hadn't started

        shutdown.reset()
        resumed = _estimator(parallel_cd=True).fit(
            train, validation_df=val, checkpoint_dir=ckdir,
            resume=True)[-1]
        _assert_models_equal(fitted["par"].model, resumed.model)


# ---------------------------------------------------------------------------
# staleness guard: forced regressions degrade to sequential sweeps via a
# typed event + counter — never an exception
# ---------------------------------------------------------------------------


class TestStalenessGuard:
    def test_forced_fallback_degrades_to_sequential(self, frames):
        from photon_tpu.obs.metrics import registry
        train, _val = frames
        # an unreachable required ratio makes EVERY concurrent group a
        # regression, so patience=1 trips the fallback on group one
        res = _estimator(num_iterations=3, parallel_cd=True,
                         staleness_ratio=1e6,
                         staleness_patience=1).fit(train)
        stats = parallel_cd.report_section()["parallel"]
        assert stats["fallbacks"] == 1
        assert stats["stale_regressions"] >= 1
        # after the trip, remaining RE groups run sequentialized
        assert stats["sequentialized_groups"] >= 2
        ev = [e for e in failures.snapshot()
              if e["kind"] == "parallel_staleness_fallback"]
        assert len(ev) == 1 and ev[0]["consecutive_regressions"] == 1
        counters = registry.snapshot()["counters"]
        assert any("cd.parallel.fallbacks" in k for k in counters)
        # degraded, not dead: the run still converges to a finite model
        for cid in SEQ_IDS:
            assert np.isfinite(_means(res[-1].model, cid)).all()

    def test_guard_is_quiet_on_healthy_defaults(self, fitted):
        assert fitted["par_stats"]["stale_regressions"] == 0
        assert fitted["par_stats"]["fallbacks"] == 0


class TestStragglerChaos:
    def test_straggler_member_lags_but_group_commits(self, frames):
        train, _val = frames
        delay = 0.3
        with chaos.active(chaos.ChaosConfig(
                straggler_at=("per_user", 0), straggler_delay_s=delay)):
            _estimator(num_iterations=2, parallel_cd=True).fit(train)
        stats = parallel_cd.report_section()["parallel"]
        assert stats["member_failures"] == 0
        recs = [r for r in stats["group_records"] if r["size"] == 2]
        assert recs[0]["sweep"] == 0 and recs[0]["committed"] == 2
        assert recs[0]["seconds"] >= delay  # the group waited it out
        # the injector fires once: sweep 1's group is back to speed
        assert recs[1]["seconds"] < recs[0]["seconds"]


# ---------------------------------------------------------------------------
# mesh placement plan
# ---------------------------------------------------------------------------


class TestPlacement:
    def _mesh(self):
        return jax.sharding.Mesh(np.array(jax.devices()), ("d",))

    def test_plan_is_disjoint_and_covers_the_mesh(self):
        from photon_tpu.parallel.mesh import plan_group_placement
        plan = plan_group_placement(["a", "b", "c"], self._mesh())
        seen = [d for cid in ["a", "b", "c"] for d in plan[cid]]
        assert len(seen) == len(set(seen)) == 8  # disjoint, full cover
        assert all(plan[cid] for cid in plan)

    def test_more_members_than_devices_timeslices(self):
        from photon_tpu.parallel.mesh import plan_group_placement
        members = [f"c{i}" for i in range(10)]
        plan = plan_group_placement(members, self._mesh())
        seen = [d for cid in members for d in plan[cid]]
        assert len(seen) == len(set(seen)) <= 8
        assert any(not plan[cid] for cid in members)  # some share by time


# ---------------------------------------------------------------------------
# checkpoint schema v3: group_boundary round-trips
# ---------------------------------------------------------------------------


class TestCheckpointSchemaV3:
    def _model(self, rng):
        from photon_tpu.game.model import FixedEffectModel
        from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
        return {"fixed": FixedEffectModel(
            GeneralizedLinearModel(Coefficients(jnp.asarray(rng.normal(size=4))),
                                   TaskType.LOGISTIC_REGRESSION), "g")}

    def test_group_boundary_round_trip(self, rng, tmp_path):
        d = str(tmp_path / "ck")
        ckpt.save_checkpoint(
            d, 0, self._model(rng), {"fixed": 1},
            sweep_in_progress=1, next_coordinate=3,
            scores={"fixed": np.zeros(5)}, full_score=np.zeros(5),
            group_boundary=True)
        state = ckpt.load_latest(d)
        assert state.group_boundary is True
        assert state.next_coordinate == 3

    def test_schema_version_and_default(self, rng, tmp_path):
        # v3 added group_boundary; v4 added re_block_cursor — both
        # default-off, so v3-era saves load unchanged
        assert ckpt.SCHEMA_VERSION == 4
        d = str(tmp_path / "ck")
        path = ckpt.save_checkpoint(d, 0, self._model(rng), {"fixed": 1})
        meta = json.load(open(os.path.join(path, "meta.json")))
        assert meta["schema"] == 4
        assert ckpt.load_latest(d).group_boundary is False
        assert ckpt.load_latest(d).re_block_cursor == {}


# ---------------------------------------------------------------------------
# host-sync lint covers the scheduler path (satellite: jax.device_get
# joined the banned set; game/ stays clean)
# ---------------------------------------------------------------------------


class TestHostSyncLint:
    def _lint(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "check_no_host_sync",
            os.path.join(os.path.dirname(__file__), os.pardir, "scripts",
                         "check_no_host_sync.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_scheduler_path_is_clean(self):
        assert self._lint().check() == []

    def test_device_get_is_flagged(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(
            "import jax\n"
            "def f(x):\n"
            "    return jax.device_get(x)\n"
            "def g(x):\n"
            "    return jax.device_get(x)  # host-sync-ok\n")
        out = self._lint().check(paths=(str(tmp_path),))
        assert len(out) == 1 and "device_get" in out[0]


# ---------------------------------------------------------------------------
# RunReport cd.parallel section
# ---------------------------------------------------------------------------


class TestRunReportSection:
    def test_parallel_run_lands_in_run_report(self, frames):
        from photon_tpu.obs.report import build_run_report, validate_run_report
        train, _val = frames
        _estimator(num_iterations=1, parallel_cd=True).fit(train)
        report = build_run_report("test")
        assert validate_run_report(report) == []
        sec = report["cd"]["parallel"]
        assert sec["runs"] == 1
        assert sec["groups"] == [["fixed"], ["per_user", "per_item"]]
        assert sec["groups_run"] == 2
        assert sec["group_records"]

    def test_sequential_only_process_has_no_cd_section(self):
        from photon_tpu.obs.report import build_run_report, validate_run_report
        report = build_run_report("test")
        assert "cd" not in report
        assert validate_run_report(report) == []


# ---------------------------------------------------------------------------
# one fixed effect + three random effects, sequential vs parallel sweeps
# ---------------------------------------------------------------------------
#
# The workload whose sequential sweep is the sum of four solves: the
# parallel mode groups the three random effects into one concurrency
# group (frozen-score solves, ordered reconciliation, staleness guard
# on), validating as it goes. One run of two sweeps each way; each gate
# is one case.


@pytest.fixture(scope="module")
def four_coordinate_sweeps():
    n, d_g, d_u = 1_200, 16, 4
    res = [("per_user", "userId", 24), ("per_item", "itemId", 18),
           ("per_ctx", "ctxId", 12)]
    rng = np.random.default_rng(7)
    theta = rng.normal(size=d_g)
    w_ents = {cid: rng.normal(size=(n_ent, d_u)) for cid, _t, n_ent in res}

    def frame(m):
        Xg = rng.normal(size=(m, d_g))
        logits = Xg @ theta
        shards, id_tags = {"g": FeatureShard(Xg, d_g)}, {}
        iu = np.arange(d_u, dtype=np.int32)
        for cid, tag, n_ent in res:
            Xe = rng.normal(size=(m, d_u))
            ent = rng.integers(0, n_ent, size=m)
            logits = logits + np.einsum("ij,ij->i", Xe, w_ents[cid][ent])
            shards[cid] = FeatureShard([(iu, Xe[i]) for i in range(m)], d_u)
            id_tags[tag] = [str(v) for v in ent]
        y = (rng.random(m) < 1.0 / (1.0 + np.exp(-logits))).astype(np.float64)
        return GameDataFrame(num_samples=m, response=y, feature_shards=shards,
                             id_tags=id_tags)

    df, val_df = frame(n), frame(n)
    opt = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(max_iterations=40, tolerance=1e-8),
        regularization=L2Regularization, regularization_weight=1.0)
    configs = {"fixed": CoordinateConfiguration(
        FixedEffectDataConfiguration("g"), opt)}
    for cid, tag, _n in res:
        configs[cid] = CoordinateConfiguration(
            RandomEffectDataConfiguration(tag, cid), opt)
    seq_ids = ["fixed"] + [cid for cid, _t, _n in res]
    est = GameEstimator(TaskType.LOGISTIC_REGRESSION, configs,
                        update_sequence=seq_ids, num_iterations=1)
    est.fit(df, validation_df=val_df)
    vocab, _c, re_datasets = est._prep_cache[2]
    vfn = est._validation_fn(est._build_scorer(val_df, vocab, re_datasets),
                             val_df)
    cfg = CoordinateDescentConfig(update_sequence=seq_ids, num_iterations=2)
    run_coordinate_descent(est._coordinates, cfg, n, validation_fn=vfn)
    parallel_cd.reset()
    run_coordinate_descent(
        est._coordinates, CoordinateDescentConfig(
            update_sequence=seq_ids, num_iterations=2, parallel=True),
        n, validation_fn=vfn)
    stats = (parallel_cd.report_section() or {}).get("parallel", {})
    parallel_cd.reset()
    return {
        "zero_staleness_fallbacks": int(stats.get("fallbacks", -1)) == 0,
        "random_effects_share_one_group": stats.get("groups") == [
            ["fixed"], ["per_user", "per_item", "per_ctx"]],
    }


@pytest.mark.parametrize("gate", [
    "zero_staleness_fallbacks", "random_effects_share_one_group"])
def test_four_coordinates_parallel_vs_sequential(four_coordinate_sweeps,
                                                 gate):
    assert four_coordinate_sweeps[gate] is True, four_coordinate_sweeps
