"""Chunk-local SDCA (optim/sdca.py): the single-pass stochastic arm.

The load-bearing invariants:
  * the duality gap is a real certificate — it decreases to the typed
    stopping threshold and the fitted coefficients land on the streamed
    L-BFGS optimum for every supported loss;
  * the whole solve is bitwise reproducible run-to-run, including
    through a mid-epoch chaos kill + crc-framed checkpoint resume and
    through injected transient chunk-read errors;
  * the refusal surface is TYPED and fires before anything compiles:
    Poisson (no conjugate step), bad example weights, L1 terms, warm
    starts, model-sharded features, random-effect coordinates;
  * on a mesh the chunk program contains ZERO collectives and the
    epoch-end merge is exactly ONE staged DCN psum (static oracle), with
    the CoCoA-style sigma = K local subproblem keeping the additive
    merge convergent;
  * the one-device staleness guard semantics: realized dual increase
    equals the prediction to FP, so an over-tight guard (> 1) trips the
    typed ``sdca_staleness_fallback`` + damping halving, and the default
    guard never does.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.dataset import DataBatch
from photon_tpu.data.ingest import generate_binary_classification
from photon_tpu.data.streaming import ChunkLoader, DenseSource, StreamConfig
from photon_tpu.function.objective import (
    GLMObjective,
    L1Regularization,
    L2Regularization,
)
from photon_tpu.ops import losses as L
from photon_tpu.optim import sdca
from photon_tpu.optim.base import ConvergenceReason, SolverConfig
from photon_tpu.optim.problem import (
    GLMOptimizationConfiguration,
    GlmOptimizationProblem,
    OptimizerConfig,
)
from photon_tpu.optim.sdca import (
    SdcaConfig,
    SdcaUnsupportedLossError,
    SdcaWeightError,
    minimize_sdca,
    validate_example_weights,
)
from photon_tpu.optim.streaming import StreamedProblem, minimize_streamed
from photon_tpu.parallel import mesh as M
from photon_tpu.resilience import chaos, failures
from photon_tpu.types import OptimizerType, TaskType

L2 = 4.0


def _logistic(rng, n=768, d=10):
    X, y, _ = generate_binary_classification(rng, n, d)
    return np.ascontiguousarray(X, np.float64), np.asarray(y, np.float64)


def _loader(X, y, chunk_rows=128, weights=None, mesh=None):
    return ChunkLoader(
        DenseSource(X, y, weights=weights),
        StreamConfig(chunk_rows=chunk_rows, dtype=np.float64), mesh=mesh)


def _fit(X, y, loss=L.LogisticLoss, l2=L2, chunk_rows=128, mesh=None,
         config=None, **kw):
    cfg = config or SdcaConfig(max_epochs=60, gap_tolerance=1e-6, seed=3)
    return minimize_sdca(GLMObjective(loss=loss),
                         _loader(X, y, chunk_rows, mesh=mesh),
                         l2_weight=l2, config=cfg, dim=X.shape[1],
                         dtype=np.float64, **kw)


# ==========================================================================
# Typed refusal surface
# ==========================================================================

class TestRefusals:
    def test_poisson_loss_refused_typed(self):
        with pytest.raises(SdcaUnsupportedLossError, match="poisson"):
            sdca.validate_loss("poisson")

    def test_poisson_solve_refused_before_compile(self, rng):
        X, y = _logistic(rng, n=64)
        with pytest.raises(SdcaUnsupportedLossError):
            _fit(X, np.abs(y), loss=L.PoissonLoss)

    def test_zero_l2_refused(self, rng):
        X, y = _logistic(rng, n=64)
        with pytest.raises(ValueError, match="l2_weight > 0"):
            _fit(X, y, l2=0.0)

    @pytest.mark.parametrize("bad", ["negative", "nan", "inf"])
    def test_bad_example_weights_refused(self, rng, bad):
        X, y = _logistic(rng, n=64)
        w = np.ones_like(y)
        w[17] = {"negative": -1.0, "nan": np.nan, "inf": np.inf}[bad]
        src = DenseSource(X, y, weights=w)
        with pytest.raises(SdcaWeightError):
            validate_example_weights(src)
        loader = ChunkLoader(src, StreamConfig(chunk_rows=32,
                                               dtype=np.float64))
        with pytest.raises(SdcaWeightError):
            minimize_sdca(GLMObjective(loss=L.LogisticLoss), loader,
                          l2_weight=L2, dim=X.shape[1], dtype=np.float64)

    def test_zero_weight_rows_pass_validation(self, rng):
        """Weight 0 is the pad-row convention, not an error."""
        X, y = _logistic(rng, n=64)
        w = np.ones_like(y)
        w[::7] = 0.0
        validate_example_weights(DenseSource(X, y, weights=w))

    def test_fixed_effect_coordinate_refuses_poisson_at_config_time(self):
        from photon_tpu.game.coordinate import FixedEffectCoordinate

        batch = DataBatch(features=jnp.zeros((8, 3)),
                          labels=jnp.ones((8,)))
        cfg = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(optimizer_type=OptimizerType.SDCA))
        with pytest.raises(SdcaUnsupportedLossError):
            FixedEffectCoordinate(batch, 3, "g",
                                  TaskType.POISSON_REGRESSION, cfg)

    def test_random_effect_coordinate_refuses_sdca(self, rng):
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

        n, d = 60, 3
        rows = [(np.arange(d, dtype=np.int32), rng.normal(size=d))
                for _ in range(n)]
        df = GameDataFrame(
            num_samples=n, response=(rng.random(n) < 0.5).astype(float),
            feature_shards={"u": FeatureShard(rows, d)},
            id_tags={"userId": [f"u{i % 4}" for i in range(n)]})
        ds = build_random_effect_dataset(
            df, RandomEffectDataConfiguration("userId", "u"),
            EntityVocabulary())
        coord = RandomEffectCoordinate(
            ds, n, "userId", "u", TaskType.LOGISTIC_REGRESSION,
            GLMOptimizationConfiguration(
                optimizer=OptimizerConfig(
                    optimizer_type=OptimizerType.SDCA)))
        with pytest.raises(ValueError, match="random-effect"):
            coord.update_model(None, None)

    def _sdca_problem(self, reg=L2Regularization, reg_weight=float(L2)):
        return GlmOptimizationProblem(
            TaskType.LOGISTIC_REGRESSION,
            GLMOptimizationConfiguration(
                optimizer=OptimizerConfig(optimizer_type=OptimizerType.SDCA,
                                          max_iterations=40,
                                          tolerance=1e-5),
                regularization=reg, regularization_weight=reg_weight))

    def test_run_streamed_refuses_l1(self, rng):
        X, y = _logistic(rng, n=64)
        with pytest.raises(ValueError, match="L1"):
            self._sdca_problem(reg=L1Regularization).run_streamed(
                _loader(X, y), dim=X.shape[1], dtype=np.float64)

    def test_run_streamed_refuses_warm_start(self, rng):
        X, y = _logistic(rng, n=64)
        with pytest.raises(ValueError, match="warm-start"):
            self._sdca_problem().run_streamed(
                _loader(X, y), initial=np.ones(X.shape[1]),
                dim=X.shape[1], dtype=np.float64)

    def test_run_resident_refuses_mesh(self, rng, devices8):
        X, y = _logistic(rng, n=64)
        batch = DataBatch(features=jnp.asarray(X), labels=jnp.asarray(y))
        with pytest.raises(ValueError, match="meshed ChunkLoader"):
            self._sdca_problem().run(batch, dim=X.shape[1],
                                     mesh=M.create_mesh(8))


# ==========================================================================
# Convergence + parity + determinism
# ==========================================================================

class TestConvergence:
    @pytest.mark.parametrize("loss", [L.LogisticLoss, L.SquaredLoss,
                                      L.SmoothedHingeLoss])
    def test_gap_decreases_to_typed_convergence(self, rng, loss):
        X, y = _logistic(rng, n=640, d=8)
        gaps = []
        # 200 epochs: squared loss is the slow arm here (its conjugate
        # step contracts per-row curvature 1+c|x|^2/l2, ~130 epochs to
        # 1e-5 relative); the others stop typed long before the cap
        res = _fit(X, y, loss=loss,
                   config=SdcaConfig(max_epochs=200, gap_tolerance=1e-5,
                                     seed=3),
                   on_epoch=lambda e, info: gaps.append(info["gap"]))
        assert int(res.reason) == int(
            ConvergenceReason.DUALITY_GAP_CONVERGED)
        assert gaps[0] > 0 and all(g >= -1e-9 * gaps[0] for g in gaps)
        assert gaps[-1] <= 1e-5 * gaps[0]
        # broad monotone decrease (per-epoch noise allowed, trend not)
        assert gaps[1] < gaps[0] and min(gaps[:3]) > gaps[-1]

    @pytest.mark.parametrize("loss", [L.LogisticLoss, L.SquaredLoss,
                                      L.SmoothedHingeLoss])
    def test_parity_with_streamed_lbfgs(self, rng, loss):
        """The gap certificate is honest: at gap <= 1e-6 * gap0 the
        coefficients coincide with the streamed L-BFGS optimum."""
        X, y = _logistic(rng, n=640, d=8)
        gaps = []
        res = _fit(X, y, loss=loss,
                   config=SdcaConfig(max_epochs=120, gap_tolerance=1e-7,
                                     seed=3),
                   on_epoch=lambda e, i: gaps.append(i["gap"]))
        ref = minimize_streamed(
            StreamedProblem(GLMObjective(loss=loss), _loader(X, y),
                            l2_weight=L2),
            np.zeros(X.shape[1]),
            config=SolverConfig(max_iterations=200, tolerance=1e-10))
        # the certificate IS the bar: gap >= P(w) - P(w*) and P is
        # l2-strongly convex, so |w - w*|_inf <= |w - w*|_2
        # <= sqrt(2 * gap / l2) (plus the reference's own tiny error)
        bound = float(np.sqrt(2.0 * max(gaps[-1], 0.0) / L2)) + 1e-6
        assert (np.max(np.abs(np.asarray(res.coef) - np.asarray(ref.coef)))
                <= bound)

    def test_value_is_primal_objective(self, rng):
        X, y = _logistic(rng, n=320, d=6)
        res = _fit(X, y)
        from photon_tpu.function.objective import Hyper
        batch = DataBatch(features=jnp.asarray(X), labels=jnp.asarray(y))
        f, _ = GLMObjective(loss=L.LogisticLoss).value_and_gradient(
            res.coef, batch, Hyper.of(L2, jnp.float64))
        # res.value is the entry-partial primal estimate: each chunk's
        # contribution is evaluated at the v the chunk SAW on entry, one
        # epoch behind the returned coef — by design (no extra pass), so
        # it matches f(coef) only to converged-gap precision
        assert abs(float(res.value) - float(f)) <= 1e-4 * abs(float(f))

    def test_bitwise_run_to_run(self, rng):
        X, y = _logistic(rng, n=640, d=8)
        a = _fit(X, y)
        b = _fit(X, y)
        assert np.array_equal(np.asarray(a.coef), np.asarray(b.coef))
        assert int(a.iterations) == int(b.iterations)

    def test_seed_changes_trajectory_not_optimum(self, rng):
        X, y = _logistic(rng, n=640, d=8)
        a = _fit(X, y, config=SdcaConfig(max_epochs=3, gap_tolerance=0.0,
                                         seed=3))
        b = _fit(X, y, config=SdcaConfig(max_epochs=3, gap_tolerance=0.0,
                                         seed=4))
        # different permutations visit rows in different order: the
        # 3-epoch iterates differ, the converged fits agree (parity test)
        assert not np.array_equal(np.asarray(a.coef), np.asarray(b.coef))

    def test_inner_epochs_speed_convergence(self, rng):
        """TPA-SCD's epochs-within-chunk: more local sweeps per byte
        streamed reaches a lower gap in the same number of storage
        passes."""
        X, y = _logistic(rng, n=640, d=8)
        gaps1, gaps3 = [], []
        _fit(X, y, config=SdcaConfig(max_epochs=4, gap_tolerance=0.0,
                                     seed=3, inner_epochs=1),
             on_epoch=lambda e, i: gaps1.append(i["gap"]))
        _fit(X, y, config=SdcaConfig(max_epochs=4, gap_tolerance=0.0,
                                     seed=3, inner_epochs=3),
             on_epoch=lambda e, i: gaps3.append(i["gap"]))
        assert gaps3[-1] < gaps1[-1]

    def test_weighted_rows_respected(self, rng):
        """Integer example weights == row replication (the SUM-convention
        objective contract), so SDCA on weights must match SDCA on the
        physically replicated rows at the optimum."""
        X, y = _logistic(rng, n=256, d=6)
        w = rng.integers(1, 4, size=y.shape[0]).astype(np.float64)
        loader = ChunkLoader(DenseSource(X, y, weights=w),
                             StreamConfig(chunk_rows=64, dtype=np.float64))
        res_w = minimize_sdca(
            GLMObjective(loss=L.LogisticLoss), loader, l2_weight=L2,
            config=SdcaConfig(max_epochs=120, gap_tolerance=1e-8, seed=3),
            dim=X.shape[1], dtype=np.float64)
        rep = np.repeat(np.arange(y.shape[0]), w.astype(int))
        res_r = _fit(np.ascontiguousarray(X[rep]), y[rep],
                     config=SdcaConfig(max_epochs=120, gap_tolerance=1e-8,
                                       seed=3))
        # both runs carry a <= ~2e-6 absolute gap, which certifies each
        # coef within sqrt(2*gap/l2) ~ 1e-3 of the (shared) optimum; the
        # two trajectories differ (different row multisets), so compare
        # at the certificate's resolution, not bitwise
        np.testing.assert_allclose(np.asarray(res_w.coef),
                                   np.asarray(res_r.coef),
                                   rtol=0, atol=5e-4)


# ==========================================================================
# Staleness guard (single-device semantics)
# ==========================================================================

class TestStalenessGuard:
    def test_default_guard_never_fires_on_one_device(self, rng):
        X, y = _logistic(rng, n=320, d=6)
        failures.clear()
        sdca.reset_sdca_stats()
        _fit(X, y)
        assert not [f for f in failures.snapshot()
                    if f["kind"] == "sdca_staleness_fallback"]
        assert sdca.report_section()["fallbacks"] == 0

    def test_overtight_guard_trips_typed_fallback(self, rng):
        """guard > 1 is unsatisfiable (realized == predicted to FP on one
        device), so the fallback must fire: typed failure record, halved
        damping bounded by min_damping, and NO exception."""
        X, y = _logistic(rng, n=320, d=6)
        failures.clear()
        sdca.reset_sdca_stats()
        res = _fit(X, y, config=SdcaConfig(max_epochs=8, gap_tolerance=0.0,
                                           seed=3, staleness_guard=1.5,
                                           min_damping=0.25))
        recs = [f for f in failures.snapshot()
                if f["kind"] == "sdca_staleness_fallback"]
        assert recs, "over-tight guard never fired"
        assert all(np.isfinite(r["realized"]) and r["predicted"] > 0
                   for r in recs)
        # halving sequence floors at min_damping
        assert min(r["damping"] for r in recs) >= 0.25 - 1e-12
        sec = sdca.report_section()
        assert sec["fallbacks"] == len(recs)
        assert np.all(np.isfinite(np.asarray(res.coef)))


# ==========================================================================
# Chaos: kill/resume + transient read errors (bitwise)
# ==========================================================================

class TestChaosAndResume:
    def test_kill_mid_epoch_bitwise_resume(self, rng, tmp_path):
        X, y = _logistic(rng, n=640, d=8)
        ckpt = str(tmp_path / "sdca.ckpt")
        cfg = SdcaConfig(max_epochs=6, gap_tolerance=0.0, seed=3)

        ref = _fit(X, y, config=cfg)
        with chaos.active(chaos.ChaosConfig(stream_kill_at=(2, 2))):
            with pytest.raises(chaos.SimulatedKill):
                _fit(X, y, config=cfg, checkpoint_path=ckpt,
                     checkpoint_every_chunks=1)
        assert os.path.exists(ckpt)
        meta, arrays = sdca.load_sdca_checkpoint(ckpt)
        assert meta["epoch"] == 2 and meta["next_pos"] == 3
        assert "st_alpha" in arrays and "acc" in arrays
        res = _fit(X, y, config=cfg, checkpoint_path=ckpt,
                   checkpoint_every_chunks=1)
        assert np.array_equal(np.asarray(ref.coef), np.asarray(res.coef))
        assert int(ref.iterations) == int(res.iterations)
        assert not os.path.exists(ckpt)  # removed on success

    def test_transient_chunk_read_errors_bitwise(self, rng):
        X, y = _logistic(rng, n=640, d=8)
        ref = _fit(X, y)
        with chaos.active(chaos.ChaosConfig(chunk_read_errors=3, seed=7)):
            res = _fit(X, y)
        assert np.array_equal(np.asarray(ref.coef), np.asarray(res.coef))

    def test_checkpoint_geometry_mismatch_refused(self, rng, tmp_path):
        X, y = _logistic(rng, n=256, d=6)
        ckpt = str(tmp_path / "sdca.ckpt")
        cfg = SdcaConfig(max_epochs=4, gap_tolerance=0.0, seed=3)
        with chaos.active(chaos.ChaosConfig(stream_kill_at=(1, 1))):
            with pytest.raises(chaos.SimulatedKill):
                _fit(X, y, config=cfg, checkpoint_path=ckpt,
                     checkpoint_every_chunks=1)
        with pytest.raises(ValueError, match="geometry"):
            _fit(X, y, chunk_rows=64, config=cfg, checkpoint_path=ckpt,
                 checkpoint_every_chunks=1)

    def test_checkpoint_decode_rejects_corruption(self, tmp_path):
        blob = sdca._encode_checkpoint(
            {"schema": sdca._SCHEMA, "epoch": 0},
            {"st_v": np.zeros(3)})
        meta, arrays = sdca._decode_checkpoint(blob)
        assert meta["epoch"] == 0 and arrays["st_v"].shape == (3,)
        with pytest.raises(ValueError, match="magic"):
            sdca._decode_checkpoint(b"NOTMAGIC" + blob[8:])
        torn = bytearray(blob)
        torn[-1] ^= 0xFF
        with pytest.raises(ValueError, match="crc"):
            sdca._decode_checkpoint(bytes(torn))


# ==========================================================================
# Meshed: CoCoA+ shards, one staged DCN psum per epoch
# ==========================================================================

class TestMeshed:
    def test_meshed_converges_with_gap_certificate(self, rng, devices8):
        X, y = _logistic(rng, n=1024, d=8)
        # sigma = K conservative local subproblems slow the per-epoch
        # rate ~K-fold vs the sequential arm (epoch ~130 reaches 1e-5
        # relative at these shapes) — the cap leaves headroom
        for mesh in (M.create_mesh(8), M.create_two_level_mesh(8, 2)):
            gaps = []
            res = _fit(X, y, chunk_rows=256, mesh=mesh,
                       config=SdcaConfig(max_epochs=300,
                                         gap_tolerance=1e-5, seed=3),
                       on_epoch=lambda e, i: gaps.append(i["gap"]))
            assert int(res.reason) == int(
                ConvergenceReason.DUALITY_GAP_CONVERGED), gaps
            # same optimum as the single-device fit (gap certifies it)
            ref = _fit(X, y, config=SdcaConfig(max_epochs=120,
                                               gap_tolerance=1e-5, seed=3))
            scale = max(float(np.max(np.abs(np.asarray(ref.coef)))), 1e-12)
            assert (np.max(np.abs(np.asarray(res.coef)
                                  - np.asarray(ref.coef)))
                    <= 5e-3 * scale)

    def test_meshed_bitwise_run_to_run(self, rng, devices8):
        X, y = _logistic(rng, n=512, d=6)
        mesh = M.create_two_level_mesh(8, 2)
        cfg = SdcaConfig(max_epochs=4, gap_tolerance=0.0, seed=3)
        a = _fit(X, y, chunk_rows=128, mesh=mesh, config=cfg)
        b = _fit(X, y, chunk_rows=128, mesh=mesh, config=cfg)
        assert np.array_equal(np.asarray(a.coef), np.asarray(b.coef))

    def test_one_dcn_psum_per_epoch_static_oracle(self, rng, devices8):
        """The chunk program has ZERO collectives on either axis; the
        epoch-end merge is exactly ONE staged DCN psum — counted on the
        lowered HLO, not inferred from timings."""
        X, y = _logistic(rng, n=512, d=6)
        mesh = M.create_two_level_mesh(8, 2)
        loader = _loader(X, y, chunk_rows=128, mesh=mesh)
        obj = GLMObjective(loss=L.LogisticLoss)
        progs = sdca._SdcaPrograms(obj, loader, SdcaConfig(), L2,
                                   X.shape[1], np.float64, c_max=4)
        state = progs.init_state()
        acc = progs.init_acc()
        first = None
        for chunk in loader.stream():  # drain fully; keep chunk 0's shape
            if first is None:
                first = (chunk.batch, chunk.rows)
        batch, rows = first
        args = (state["alpha"], state["vloc"], state["vg"], acc,
                batch, jnp.int32(rows), jnp.int32(0),
                jnp.int32(0), jnp.asarray(1.0, np.float64))
        assert M.count_axis_psums(progs._chunk_meshed, M.DCN_AXIS,
                                  *args) == 0
        assert M.count_axis_psums(progs._chunk_meshed, M.DATA_AXIS,
                                  *args) == 0
        assert M.count_axis_psums(progs._merge, M.DCN_AXIS,
                                  state["vloc"], state["vg"], acc) == 1

    def test_indivisible_chunk_rows_refused(self, rng, devices8):
        """chunk_rows is pow2-ceiled by the loader, so the reachable
        indivisible case is a chunk smaller than the shard count."""
        X, y = _logistic(rng, n=512, d=6)
        mesh = M.create_mesh(8)
        with pytest.raises(ValueError, match="divisible"):
            _fit(X, y, chunk_rows=4, mesh=mesh)


# ==========================================================================
# Dispatch + observability
# ==========================================================================

class TestDispatchAndObs:
    def test_problem_run_resident_dispatch(self, rng):
        """OptimizerType.SDCA through GlmOptimizationProblem.run wraps
        the resident batch in a chunk source and lands on the L-BFGS
        optimum; the result carries the typed gap reason."""
        X, y = _logistic(rng, n=512, d=8)
        batch = DataBatch(features=jnp.asarray(X), labels=jnp.asarray(y))
        cfg = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(optimizer_type=OptimizerType.SDCA,
                                      max_iterations=120, tolerance=1e-6),
            regularization=L2Regularization,
            regularization_weight=float(L2))
        model, res = GlmOptimizationProblem(
            TaskType.LOGISTIC_REGRESSION, cfg).run(batch, dim=X.shape[1])
        assert int(res.reason) == int(
            ConvergenceReason.DUALITY_GAP_CONVERGED)
        ref_cfg = GLMOptimizationConfiguration(
            regularization=L2Regularization,
            regularization_weight=float(L2))
        ref_model, _ = GlmOptimizationProblem(
            TaskType.LOGISTIC_REGRESSION, ref_cfg).run(batch,
                                                       dim=X.shape[1])
        np.testing.assert_allclose(
            np.asarray(model.coefficients.means),
            np.asarray(ref_model.coefficients.means), rtol=0, atol=2e-3)

    def test_run_streamed_dispatch(self, rng):
        X, y = _logistic(rng, n=512, d=8)
        cfg = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(optimizer_type=OptimizerType.SDCA),
            regularization=L2Regularization,
            regularization_weight=float(L2))
        model, res = GlmOptimizationProblem(
            TaskType.LOGISTIC_REGRESSION, cfg).run_streamed(
                _loader(X, y), dim=X.shape[1], dtype=np.float64,
                sdca_config=SdcaConfig(max_epochs=60, gap_tolerance=1e-5,
                                       seed=3))
        assert int(res.reason) == int(
            ConvergenceReason.DUALITY_GAP_CONVERGED)
        assert np.asarray(model.coefficients.means).shape == (X.shape[1],)

    def test_report_section_and_metrics(self, rng):
        from photon_tpu.obs.metrics import registry
        from photon_tpu.obs.report import build_run_report, validate_run_report

        X, y = _logistic(rng, n=256, d=6)
        sdca.reset_sdca_stats()
        assert sdca.report_section() is None  # idle module stays silent
        res = _fit(X, y)
        sec = sdca.report_section()
        assert sec["runs"] == 1
        assert sec["epochs"] == int(res.iterations)
        assert sec["converged"] == 1
        assert sec["last"]["loss"] == "logistic"
        snap = registry.snapshot()
        assert "sdca.duality_gap" in snap["gauges"]
        assert snap["counters"]["sdca.epochs"] >= int(res.iterations)
        report = build_run_report("test")
        assert report["sdca"]["runs"] == 1
        assert validate_run_report(report) == []
        sdca.reset_sdca_stats()
        assert sdca.report_section() is None


# ==========================================================================
# SDCA vs streamed L-BFGS off one mmap chunk store, end to end
# ==========================================================================
#
# An anisotropic f32 logistic problem (8,192 x 32, covariance condition
# ~1e3, equal signal a direction) is written once to the crc-verified
# chunk store and fit both ways. Storage passes to a shared AUC target
# are the unit: every L-BFGS objective evaluation is one pass, every SDCA
# epoch one. One run; each gate is one case.


@pytest.fixture(scope="module")
def sdca_vs_lbfgs(tmp_path_factory):
    from photon_tpu.data.streaming import MmapChunkSource
    from photon_tpu.evaluation.evaluators import auc
    from photon_tpu.io.data_store import write_data_store

    n, d, chunk_rows = 8192, 32, 2048
    rng = np.random.default_rng(23)
    scales = np.logspace(0.0, -1.5, d)
    X = rng.normal(size=(n, d)) * scales
    w_true = rng.normal(size=d) / scales * (3.0 / np.sqrt(d))
    y = (rng.random(n)
         < 1.0 / (1.0 + np.exp(-(X @ w_true)))).astype(np.float64)
    l2 = float(np.sum(scales ** 2))    # l2 ~ E||x||^2: row-norm ratio ~1
    store = str(tmp_path_factory.mktemp("sdca_q") / "store")
    write_data_store(store, y, x=X, dtype=np.float32, chunk_rows=chunk_rows)
    src = MmapChunkSource(store)

    def loader():
        return ChunkLoader(src, StreamConfig(chunk_rows=chunk_rows,
                                             num_buffers=2,
                                             dtype=np.float32))

    def auc_of(coef):
        s = jnp.asarray(X @ np.asarray(coef, np.float64))
        return float(np.asarray(auc(s, jnp.asarray(y))))

    obj = GLMObjective(loss=L.LogisticLoss)
    evals = []

    class Recording(StreamedProblem):
        def value_and_gradient(self, coef, **kw):
            evals.append(np.array(coef, np.float64, copy=True))
            return super().value_and_gradient(coef, **kw)

    minimize_streamed(Recording(obj, loader(), l2_weight=l2),
                      np.zeros(d, np.float32),
                      config=SolverConfig(max_iterations=60, tolerance=1e-7))
    lbfgs_aucs = [auc_of(c) for c in evals]
    cfg = SdcaConfig(max_epochs=20, gap_tolerance=1e-3, seed=5)
    epoch_aucs = []
    res = minimize_sdca(obj, loader(), l2_weight=l2, config=cfg, dim=d,
                        dtype=np.float32,
                        on_epoch=lambda _e, info: epoch_aucs.append(
                            auc_of(info["coef"])))
    repro = minimize_sdca(obj, loader(), l2_weight=l2, config=cfg, dim=d,
                          dtype=np.float32)
    src.store.close()

    target = max(lbfgs_aucs[-1], epoch_aucs[-1]) - 1e-3
    passes_to = lambda aucs: next(
        (i + 1 for i, a in enumerate(aucs) if a >= target), None)
    sdca_passes, lbfgs_passes = passes_to(epoch_aucs), passes_to(lbfgs_aucs)
    return {
        "passes_ge_2x": (sdca_passes is not None
                         and lbfgs_passes is not None
                         and lbfgs_passes >= 2 * sdca_passes),
        "auc_parity_le_1e3": abs(lbfgs_aucs[-1] - epoch_aucs[-1]) <= 1e-3,
        "bitwise_run_to_run": bool(np.array_equal(np.asarray(res.coef),
                                                  np.asarray(repro.coef))),
        "duality_gap_converged": int(np.asarray(res.reason))
        == int(ConvergenceReason.DUALITY_GAP_CONVERGED),
    }


@pytest.mark.parametrize("gate", [
    "passes_ge_2x", "auc_parity_le_1e3", "bitwise_run_to_run",
    "duality_gap_converged"])
def test_sdca_fewer_storage_passes_than_lbfgs(sdca_vs_lbfgs, gate):
    assert sdca_vs_lbfgs[gate] is True, sdca_vs_lbfgs
