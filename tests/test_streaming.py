"""Out-of-core streaming training: chunk loader, chunk-accumulated
objective parity, the host-loop streamed solvers, per-chunk validation,
chaos/retry/resume, and two flows end to end: streamed vs resident, and
LibSVM text -> mmap chunk store -> streamed fit.

The load-bearing invariants:
  * a streamed pass differs from the resident evaluation ONLY in FP
    summation order (parity to ~1e-12 in f64, asserted at 1e-9);
  * chunk order is deterministic and the whole streamed solve is bitwise
    reproducible run-to-run — including through a mid-epoch kill+resume
    via the chunk-cursor checkpoint;
  * per-chunk drop-invalid filtering assigns surviving rows to chunks
    exactly as filtering the resident dataset up front would.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.dataset import DataBatch
from photon_tpu.data.ingest import (
    chunk_source,
    generate_binary_classification,
    generate_linear,
    generate_poisson,
)
from photon_tpu.data.streaming import (
    ChunkLoader,
    CsrSource,
    DenseSource,
    StreamConfig,
    epoch_chunk_order,
)
from photon_tpu.data.validators import invalid_chunk_mask
from photon_tpu.function.objective import GLMObjective, Hyper
from photon_tpu.ops.losses import loss_for_task
from photon_tpu.optim import lbfgs, owlqn
from photon_tpu.optim.base import SolverConfig
from photon_tpu.optim.streaming import (
    StreamedProblem,
    load_stream_checkpoint,
    minimize_streamed,
)
from photon_tpu.parallel import mesh as M
from photon_tpu.resilience import chaos
from photon_tpu.types import TaskType

L2 = 0.1
F64 = jnp.float64


def _logistic_problem(rng, n=1000, d=16):
    X, y, _ = generate_binary_classification(rng, n, d)
    return np.ascontiguousarray(X, np.float64), np.asarray(y, np.float64)


def _objective(task=TaskType.LOGISTIC_REGRESSION):
    return GLMObjective(loss_for_task(task))


def _resident_vg(obj, X, y, coef, offsets=None, weights=None):
    batch = DataBatch(
        features=jnp.asarray(X), labels=jnp.asarray(y),
        offsets=None if offsets is None else jnp.asarray(offsets),
        weights=None if weights is None else jnp.asarray(weights))
    return obj.value_and_gradient(jnp.asarray(coef), batch, Hyper.of(L2, F64))


def _streamed_vg(obj, X, y, coef, chunk_rows, offsets=None, weights=None,
                 mesh=None):
    loader = ChunkLoader(
        DenseSource(X, y, offsets=offsets, weights=weights),
        StreamConfig(chunk_rows=chunk_rows, dtype=np.float64), mesh=mesh)
    return StreamedProblem(obj, loader, l2_weight=L2).value_and_gradient(coef)


class TestStreamedEvaluationParity:
    @pytest.mark.parametrize("chunk_rows", [100, 256, 300, 1000, 4096])
    def test_value_grad_parity_across_chunk_sizes(self, rng, chunk_rows):
        """Streamed == resident for divisible chunks, non-divisible tails
        (300 -> pow2 512 with a 488-row padded tail), and the 1-chunk
        degenerate case (4096 > n)."""
        X, y = _logistic_problem(rng)
        obj = _objective()
        coef = rng.normal(size=X.shape[1])
        fr, gr = _resident_vg(obj, X, y, coef)
        fs, gs = _streamed_vg(obj, X, y, coef, chunk_rows)
        assert abs(float(fr) - float(fs)) <= 1e-9 * max(abs(float(fr)), 1.0)
        np.testing.assert_allclose(np.asarray(gr), gs, rtol=0, atol=1e-9)

    def test_parity_with_offsets_and_weights(self, rng):
        X, y = _logistic_problem(rng)
        offsets = rng.normal(size=len(y))
        weights = rng.uniform(0.5, 2.0, size=len(y))
        obj = _objective()
        coef = rng.normal(size=X.shape[1])
        fr, gr = _resident_vg(obj, X, y, coef, offsets, weights)
        fs, gs = _streamed_vg(obj, X, y, coef, 256, offsets, weights)
        assert abs(float(fr) - float(fs)) <= 1e-9 * max(abs(float(fr)), 1.0)
        np.testing.assert_allclose(np.asarray(gr), gs, rtol=0, atol=1e-9)

    def test_sparse_csr_parity(self, rng):
        """CsrSource materializes per-chunk ELL blocks identical (up to
        summation order) to the resident from_csr_arrays batch."""
        from photon_tpu.ops.features import from_csr_arrays

        n, d, k = 900, 24, 6
        indptr = np.arange(0, (n + 1) * k, k, dtype=np.int64)
        cols = rng.integers(0, d, size=n * k).astype(np.int64)
        vals = rng.normal(size=n * k)
        y = (rng.random(n) < 0.5).astype(np.float64)
        obj = _objective()
        coef = rng.normal(size=d)

        feats = from_csr_arrays(indptr, cols, vals, max_nnz=8, dtype=F64)
        batch = DataBatch(features=feats, labels=jnp.asarray(y))
        fr, gr = obj.value_and_gradient(jnp.asarray(coef), batch,
                                        Hyper.of(L2, F64))
        src = CsrSource(indptr, cols, vals, y, dim=d, max_nnz=8,
                        dtype=np.float64)
        loader = ChunkLoader(src, StreamConfig(chunk_rows=200,
                                               dtype=np.float64))
        fs, gs = StreamedProblem(obj, loader,
                                 l2_weight=L2).value_and_gradient(coef)
        assert abs(float(fr) - float(fs)) <= 1e-9 * max(abs(float(fr)), 1.0)
        np.testing.assert_allclose(np.asarray(gr), gs, rtol=0, atol=1e-9)

    def test_chunk_source_adapter(self, rng):
        """ingest.chunk_source(LibSVMData) streams the same objective the
        resident to_batch materializes."""
        from photon_tpu.data.ingest import LibSVMData, to_batch

        n, d = 400, 12
        rows = []
        for _ in range(n):
            nnz = int(rng.integers(1, 5))
            rows.append((rng.choice(d, size=nnz, replace=False)
                         .astype(np.int32), rng.normal(size=nnz)))
        y = (rng.random(n) < 0.5).astype(np.float64)
        data = LibSVMData(labels=y, rows=rows, dim=d, max_nnz=4)
        obj = _objective()
        coef = rng.normal(size=d)

        batch = to_batch(data, dtype=np.float64)
        fr, gr = obj.value_and_gradient(jnp.asarray(coef), batch,
                                        Hyper.of(L2, F64))
        loader = ChunkLoader(chunk_source(data, dtype=np.float64),
                             StreamConfig(chunk_rows=128, dtype=np.float64))
        fs, gs = StreamedProblem(obj, loader,
                                 l2_weight=L2).value_and_gradient(coef)
        assert abs(float(fr) - float(fs)) <= 1e-9 * max(abs(float(fr)), 1.0)
        np.testing.assert_allclose(np.asarray(gr), gs, rtol=0, atol=1e-9)

    def test_meshed_streamed_parity(self, rng, devices8):
        """Shard-local carry + single pass-end staged psum == resident,
        on both the flat data mesh and the two-level (dcn, data) mesh."""
        X, y = _logistic_problem(rng, n=2048)
        obj = _objective()
        coef = rng.normal(size=X.shape[1])
        fr, gr = _resident_vg(obj, X, y, coef)
        for mesh in (M.create_mesh(8), M.create_two_level_mesh(8, 2)):
            fs, gs = _streamed_vg(obj, X, y, coef, 512, mesh=mesh)
            assert abs(float(fr) - float(fs)) <= 1e-9 * max(
                abs(float(fr)), 1.0)
            np.testing.assert_allclose(np.asarray(gr), gs, rtol=0,
                                       atol=1e-9)


class TestStreamedSolvers:
    @pytest.mark.parametrize("task,gen", [
        (TaskType.LOGISTIC_REGRESSION, generate_binary_classification),
        (TaskType.LINEAR_REGRESSION, generate_linear),
        (TaskType.POISSON_REGRESSION, generate_poisson),
    ])
    def test_lbfgs_fit_parity_on_seed_losses(self, rng, task, gen):
        """Full streamed L-BFGS fit lands on the resident lax solver's
        optimum (<=1e-6 coefficient gap) on each seed GLM loss."""
        n, d = 1200, 12
        X, y, _ = gen(rng, n, d)
        X = np.ascontiguousarray(X, np.float64)
        y = np.asarray(y, np.float64)
        obj = _objective(task)
        batch = DataBatch(features=jnp.asarray(X), labels=jnp.asarray(y))
        vg = lambda c: obj.value_and_gradient(c, batch, Hyper.of(L2, F64))
        ref = lbfgs.minimize(vg, jnp.zeros(d, F64), config=SolverConfig())

        loader = ChunkLoader(DenseSource(X, y),
                             StreamConfig(chunk_rows=256, dtype=np.float64))
        res = minimize_streamed(StreamedProblem(obj, loader, l2_weight=L2),
                                np.zeros(d))
        assert np.max(np.abs(np.asarray(ref.coef)
                             - np.asarray(res.coef))) <= 1e-6
        assert abs(float(ref.value) - float(res.value)) <= 1e-6 * max(
            abs(float(ref.value)), 1.0)

    def test_owlqn_fit_parity_and_sparsity(self, rng):
        """L1 regularization dispatches to the streamed OWL-QN port; the
        fit matches the resident OWL-QN (same orthant path => same zero
        pattern)."""
        X, y = _logistic_problem(rng, n=1200)
        d = X.shape[1]
        obj = _objective()
        batch = DataBatch(features=jnp.asarray(X), labels=jnp.asarray(y))
        vg = lambda c: obj.value_and_gradient(c, batch, Hyper.of(L2, F64))
        ref = owlqn.minimize(vg, jnp.zeros(d, F64), l1_weight=0.05,
                             config=SolverConfig())
        loader = ChunkLoader(DenseSource(X, y),
                             StreamConfig(chunk_rows=256, dtype=np.float64))
        res = minimize_streamed(StreamedProblem(obj, loader, l2_weight=L2),
                                np.zeros(d), l1_weight=0.05)
        assert np.max(np.abs(np.asarray(ref.coef)
                             - np.asarray(res.coef))) <= 1e-6
        assert np.array_equal(np.asarray(ref.coef) == 0,
                              np.asarray(res.coef) == 0)

    def test_bitwise_run_to_run(self, rng):
        """Deterministic chunk order + one compiled chunk program + a
        straight-line host solver => byte-identical re-runs."""
        X, y = _logistic_problem(rng)
        obj = _objective()

        def fit():
            loader = ChunkLoader(DenseSource(X, y),
                                 StreamConfig(chunk_rows=256,
                                              dtype=np.float64))
            return minimize_streamed(
                StreamedProblem(obj, loader, l2_weight=L2),
                np.zeros(X.shape[1]))

        a, b = fit(), fit()
        assert np.array_equal(np.asarray(a.coef), np.asarray(b.coef))
        assert int(a.iterations) == int(b.iterations)
        assert int(a.num_fun_evals) == int(b.num_fun_evals)

    def test_run_streamed_facade(self, rng):
        """problem.run_streamed mirrors problem.run on the same data (and
        rejects solvers that cannot stream)."""
        from photon_tpu.optim.problem import (
            GLMOptimizationConfiguration,
            GlmOptimizationProblem,
            OptimizerConfig,
        )
        from photon_tpu.function.objective import L2Regularization
        from photon_tpu.types import OptimizerType

        X, y = _logistic_problem(rng)
        cfg = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(optimizer_type=OptimizerType.LBFGS),
            regularization=L2Regularization, regularization_weight=L2)
        prob = GlmOptimizationProblem(TaskType.LOGISTIC_REGRESSION, cfg)
        batch = DataBatch(features=jnp.asarray(X), labels=jnp.asarray(y))
        model_ref, _ = prob.run(batch, dim=X.shape[1], dtype=F64)
        loader = ChunkLoader(DenseSource(X, y),
                             StreamConfig(chunk_rows=256, dtype=np.float64))
        model_str, res = prob.run_streamed(loader)
        assert np.max(np.abs(
            np.asarray(model_ref.coefficients.means)
            - np.asarray(model_str.coefficients.means))) <= 1e-6

        tron_cfg = GLMOptimizationConfiguration(
            optimizer=OptimizerConfig(optimizer_type=OptimizerType.TRON))
        tron_prob = GlmOptimizationProblem(TaskType.LOGISTIC_REGRESSION,
                                           tron_cfg)
        with pytest.raises(ValueError, match="LBFGS/OWLQN"):
            tron_prob.run_streamed(loader)


class TestChunkValidation:
    def test_chunked_filter_matches_resident_filter(self, rng):
        """Satellite regression: drop-invalid on the streaming path must
        assign surviving rows to chunks exactly as filtering the resident
        dataset up front would — survivors pack densely across chunk
        boundaries, not per-read-block."""
        n, d = 700, 8
        X, y = _logistic_problem(rng, n=n, d=d)
        bad = rng.choice(n, size=60, replace=False)
        y[bad[:30]] = np.nan           # finite-labels rule
        y[bad[30:]] = 2.0              # binary-labels rule
        task = TaskType.LOGISTIC_REGRESSION

        drop = invalid_chunk_mask(y, task)
        Xs, ys = X[~drop], y[~drop]
        loader = ChunkLoader(
            DenseSource(X, y),
            StreamConfig(chunk_rows=128, dtype=np.float64,
                         drop_invalid=True, task=task))
        seen_rows = 0
        for chunk in loader.stream():
            feats = np.asarray(chunk.batch.features)
            labels = np.asarray(chunk.batch.labels)
            w = np.asarray(chunk.batch.weights)
            r = chunk.rows
            lo = chunk.index * loader.chunk_rows
            np.testing.assert_array_equal(feats[:r], Xs[lo:lo + r])
            np.testing.assert_array_equal(labels[:r], ys[lo:lo + r])
            assert np.all(w[:r] == 1.0) and np.all(w[r:] == 0.0)
            seen_rows += r
        assert seen_rows == len(ys)
        assert loader.last_stats.rows_dropped == 60
        # second pass: the survivor-derived chunk count is now known
        assert loader.num_chunks == -(-len(ys) // loader.chunk_rows)

    def test_invalid_chunk_mask_rules(self):
        """The per-chunk mask applies the same named rules as
        validate_dataframe: non-finite labels/offsets/weights, Poisson
        negatives, non-binary classification labels, non-positive
        weights, non-finite feature values."""
        y = np.array([0.0, np.nan, 1.0, 2.0])
        drop = invalid_chunk_mask(y, TaskType.LOGISTIC_REGRESSION)
        np.testing.assert_array_equal(drop, [False, True, False, True])

        drop = invalid_chunk_mask(np.array([1.0, -1.0, 0.0]),
                                  TaskType.POISSON_REGRESSION)
        np.testing.assert_array_equal(drop, [False, True, False])

        drop = invalid_chunk_mask(
            np.array([1.0, 2.0, 3.0]), TaskType.LINEAR_REGRESSION,
            offsets=np.array([0.0, np.inf, 0.0]),
            weights=np.array([1.0, 1.0, 0.0]))
        np.testing.assert_array_equal(drop, [False, True, True])

        vals = np.ones((3, 4))
        vals[2, 1] = np.nan
        drop = invalid_chunk_mask(np.array([1.0, 2.0, 3.0]),
                                  TaskType.LINEAR_REGRESSION,
                                  feature_values=vals)
        np.testing.assert_array_equal(drop, [False, False, True])

    def test_filtered_solve_matches_prefiltered_resident(self, rng):
        """End-to-end: a streamed fit over drop-invalid data equals the
        resident fit over the pre-filtered arrays."""
        X, y = _logistic_problem(rng, n=600)
        y[::17] = np.nan
        task = TaskType.LOGISTIC_REGRESSION
        drop = invalid_chunk_mask(y, task)
        Xs, ys = X[~drop], y[~drop]
        obj = _objective()
        batch = DataBatch(features=jnp.asarray(Xs), labels=jnp.asarray(ys))
        ref = lbfgs.minimize(
            lambda c: obj.value_and_gradient(c, batch, Hyper.of(L2, F64)),
            jnp.zeros(X.shape[1], F64), config=SolverConfig())
        loader = ChunkLoader(
            DenseSource(X, y),
            StreamConfig(chunk_rows=128, dtype=np.float64,
                         drop_invalid=True, task=task))
        res = minimize_streamed(StreamedProblem(obj, loader, l2_weight=L2),
                                np.zeros(X.shape[1]))
        assert np.max(np.abs(np.asarray(ref.coef)
                             - np.asarray(res.coef))) <= 1e-6


class TestChaosAndResume:
    def test_slow_and_flaky_chunk_reads_retry_to_parity(self, rng):
        """slow_chunk_read delays and transient chunk_read_errors are
        absorbed by the retry policy; the result stays bitwise identical
        to the undisturbed run."""
        X, y = _logistic_problem(rng, n=600)
        obj = _objective()

        def fit():
            loader = ChunkLoader(DenseSource(X, y),
                                 StreamConfig(chunk_rows=128,
                                              dtype=np.float64))
            return minimize_streamed(
                StreamedProblem(obj, loader, l2_weight=L2),
                np.zeros(X.shape[1]))

        ref = fit()
        with chaos.active(chaos.ChaosConfig(chunk_read_errors=2,
                                            slow_chunk_read_s=0.005,
                                            slow_chunk_reads=3)):
            res = fit()
        assert np.array_equal(np.asarray(ref.coef), np.asarray(res.coef))

    def test_chunk_read_error_exhaustion_raises(self, rng):
        """More injected errors than retry attempts surfaces the IO error
        to the consumer (no silent chunk loss)."""
        from photon_tpu.resilience.retry import RetryPolicy

        X, y = _logistic_problem(rng, n=300)
        loader = ChunkLoader(
            DenseSource(X, y),
            StreamConfig(chunk_rows=128, dtype=np.float64,
                         retry=RetryPolicy(max_attempts=2,
                                           base_delay_s=0.001,
                                           max_delay_s=0.002,
                                           retry_on=(OSError,))))
        prob = StreamedProblem(_objective(), loader, l2_weight=L2)
        with chaos.active(chaos.ChaosConfig(chunk_read_errors=50)):
            with pytest.raises(chaos.ChaosIOError):
                prob.value_and_gradient(np.zeros(X.shape[1]))

    def test_kill_mid_epoch_bitwise_resume(self, rng, tmp_path):
        """Satellite: chaos kills the solve mid-pass AFTER a chunk-cursor
        checkpoint; the resumed run replays the interrupted iteration
        (completed evals from cache, in-flight pass from its cursor) and
        finishes bitwise identical to the uninterrupted run."""
        X, y = _logistic_problem(rng, n=800)
        obj = _objective()
        ckpt = str(tmp_path / "stream.ckpt")

        def fit(**kw):
            loader = ChunkLoader(DenseSource(X, y),
                                 StreamConfig(chunk_rows=128,
                                              dtype=np.float64))
            return minimize_streamed(
                StreamedProblem(obj, loader, l2_weight=L2),
                np.zeros(X.shape[1]), **kw)

        ref = fit()
        with chaos.active(chaos.ChaosConfig(stream_kill_at=(4, 3))):
            with pytest.raises(chaos.SimulatedKill):
                fit(checkpoint_path=ckpt, checkpoint_every_chunks=2)
        assert os.path.exists(ckpt)
        meta, _arrays = load_stream_checkpoint(ckpt)
        assert meta["pass_idx"] == 4 and meta["next_chunk"] == 4

        res = fit(checkpoint_path=ckpt, checkpoint_every_chunks=2)
        assert not os.path.exists(ckpt), "finished solve must clean up"
        assert np.array_equal(np.asarray(ref.coef), np.asarray(res.coef))
        assert int(ref.iterations) == int(res.iterations)
        assert int(ref.num_fun_evals) == int(res.num_fun_evals)

    def test_checkpoint_corruption_detected(self, rng, tmp_path):
        X, y = _logistic_problem(rng, n=400)
        ckpt = str(tmp_path / "stream.ckpt")
        with chaos.active(chaos.ChaosConfig(stream_kill_at=(1, 1))):
            with pytest.raises(chaos.SimulatedKill):
                loader = ChunkLoader(DenseSource(X, y),
                                     StreamConfig(chunk_rows=128,
                                                  dtype=np.float64))
                minimize_streamed(
                    StreamedProblem(_objective(), loader, l2_weight=L2),
                    np.zeros(X.shape[1]), checkpoint_path=ckpt,
                    checkpoint_every_chunks=1)
        blob = bytearray(open(ckpt, "rb").read())
        blob[-3] ^= 0xFF
        with open(ckpt, "wb") as f:
            f.write(bytes(blob))
        with pytest.raises(ValueError, match="crc"):
            load_stream_checkpoint(ckpt)


class TestOverlapGauges:
    def test_stream_overlap_utilization_math_and_gauges(self):
        from photon_tpu.obs.metrics import registry
        from photon_tpu.utils.flops import stream_overlap_utilization

        class V5e:           # a device the peaks table knows
            platform, device_kind = "tpu", "TPU v5 lite"

        rec = stream_overlap_utilization(
            reader_busy_s=2.0, consumer_stall_s=0.5, wall_s=4.0,
            bytes_h2d=10 * 2**20, device=V5e(), phase="on-chip")
        assert rec["hidden_s"] == pytest.approx(1.5)
        assert rec["overlap_efficiency"] == pytest.approx(0.75)
        assert rec["peak_h2d_bw"] == 32e9
        assert rec["h2d_bw_utilization"] == pytest.approx(
            10 * 2**20 / 4.0 / 32e9)
        # the CPU the tests run on has no peak: the overlap ratio (host
        # clocks) is still reported, the utilization figure is not
        cpu = stream_overlap_utilization(
            reader_busy_s=2.0, consumer_stall_s=0.5, wall_s=4.0,
            bytes_h2d=10 * 2**20, phase="on-cpu")
        assert cpu["overlap_efficiency"] == pytest.approx(0.75)
        assert cpu["h2d_bw_utilization"] is None
        assert cpu["peak_h2d_bw"] is None
        gauges = registry.snapshot()["gauges"]
        assert any("perf.stream_overlap" in k and "on-cpu" in k
                   for k in gauges)
        assert any("perf.h2d_bw_util" in k and "on-chip" in k
                   for k in gauges)
        assert not any("perf.h2d_bw_util" in k and "on-cpu" in k
                       for k in gauges)
        # an idle reader hid everything there was to hide
        assert stream_overlap_utilization(0.0, 0.0, 1.0, 0)[
            "overlap_efficiency"] == 1.0

    def test_loader_stats_populated(self, rng):
        X, y = _logistic_problem(rng, n=600)
        loader = ChunkLoader(DenseSource(X, y),
                             StreamConfig(chunk_rows=128, dtype=np.float64))
        StreamedProblem(_objective(), loader,
                        l2_weight=L2).value_and_gradient(np.zeros(16))
        st = loader.last_stats
        assert st.chunks == loader.num_chunks
        assert st.rows == 600
        assert st.bytes_h2d == st.chunks * loader.chunk_bytes()
        assert st.wall_s > 0 and st.reader_busy_s > 0


class TestHierInnerChunks:
    def test_inner_chunks_converges_with_one_dcn_psum(self, rng, devices8):
        """DANE rounds whose local solves read 1/inner of the shard per
        round still converge (safeguard absorbs chunk noise) and keep the
        one-staged-DCN-psum-per-round communication structure."""
        from photon_tpu.optim import hier

        n, d = 4096, 12
        X, y, _ = generate_binary_classification(rng, n, d)
        obj = _objective()
        batch = DataBatch(features=jnp.asarray(X, F64),
                          labels=jnp.asarray(y, F64))
        hyper = Hyper.of(L2, F64)
        x0 = jnp.zeros(d, F64)
        mesh = M.create_two_level_mesh(8, 2)

        ref = hier.minimize_hier(obj, batch, hyper, x0, mesh,
                                 config=hier.HierConfig(rounds=30))
        res = hier.minimize_hier(
            obj, batch, hyper, x0, mesh,
            config=hier.HierConfig(rounds=60, inner_chunks=4))
        assert res.value <= ref.value * 1.01 + 1e-6

        sharded = M.shard_batch(batch, mesh,
                                axis=(M.DCN_AXIS, M.DATA_AXIS))
        c = M.replicate(x0, mesh)
        rf = hier.build_round_fn(obj, mesh,
                                 hier.HierConfig(inner_chunks=4))
        assert M.count_axis_psums(
            rf, M.DCN_AXIS, jnp.asarray(0, jnp.int32), c, c, c,
            jnp.asarray(0.0, F64), hyper, sharded) == 1


class TestMmapSourceParity:
    """Satellite: the disk-native source must be indistinguishable from
    the in-RAM sources at the solver level — bitwise-identical fits, not
    just close ones — across chunk sizes, padded tails, drop-invalid
    filtering, and kill/resume."""

    def _sparse_store(self, rng, tmp_path, n=900, d=24, kmax=6):
        from photon_tpu.io.data_store import write_data_store

        indptr = np.zeros(n + 1, np.int64)
        indptr[1:] = np.cumsum(rng.integers(1, kmax + 1, n))
        cols = rng.integers(0, d, indptr[-1]).astype(np.int64)
        vals = rng.normal(size=indptr[-1])
        y = rng.integers(0, 2, n).astype(np.float64)
        p = str(tmp_path / "store")
        write_data_store(p, y, indptr=indptr, cols=cols, vals=vals,
                         dim=d, chunk_rows=64)
        return p, (indptr, cols, vals, y, d)

    @staticmethod
    def _fit(source, chunk_rows, d, **stream_kw):
        from photon_tpu.data.streaming import MmapChunkSource  # noqa: F401

        loader = ChunkLoader(
            source, StreamConfig(chunk_rows=chunk_rows, dtype=np.float64,
                                 **stream_kw))
        return minimize_streamed(
            StreamedProblem(_objective(), loader, l2_weight=L2),
            np.zeros(d))

    @pytest.mark.parametrize("chunk_rows", [128, 300])
    def test_fit_bitwise_vs_csr_source(self, rng, tmp_path, chunk_rows):
        """Same solver iterates off disk as off RAM — divisible chunks
        and the non-divisible case (300 -> pow2 512, padded tail)."""
        from photon_tpu.data.streaming import MmapChunkSource

        p, (indptr, cols, vals, y, d) = self._sparse_store(rng, tmp_path)
        ref = self._fit(CsrSource(indptr, cols, vals, y, dim=d,
                                  dtype=np.float64), chunk_rows, d)
        res = self._fit(MmapChunkSource(p), chunk_rows, d)
        assert np.array_equal(np.asarray(ref.coef), np.asarray(res.coef))
        assert int(ref.iterations) == int(res.iterations)
        assert int(ref.num_fun_evals) == int(res.num_fun_evals)

    def test_fit_bitwise_vs_dense_source(self, rng, tmp_path):
        from photon_tpu.data.streaming import MmapChunkSource
        from photon_tpu.io.data_store import write_data_store

        X, y = _logistic_problem(rng, n=700)
        p = str(tmp_path / "dense")
        write_data_store(p, y, x=X, chunk_rows=64)
        ref = self._fit(DenseSource(X, y), 256, X.shape[1])
        res = self._fit(MmapChunkSource(p), 256, X.shape[1])
        assert np.array_equal(np.asarray(ref.coef), np.asarray(res.coef))
        assert int(ref.iterations) == int(res.iterations)

    def test_drop_invalid_bitwise_vs_csr_source(self, rng, tmp_path):
        """NaN labels in the STORE (bitwise-preserved by the crc'd
        sections) filter identically to the in-RAM source — survivors
        pack into the same chunks, the fit stays bitwise."""
        from photon_tpu.data.streaming import MmapChunkSource
        from photon_tpu.io.data_store import write_data_store

        n, d, kmax = 700, 16, 5
        indptr = np.zeros(n + 1, np.int64)
        indptr[1:] = np.cumsum(rng.integers(1, kmax + 1, n))
        cols = rng.integers(0, d, indptr[-1]).astype(np.int64)
        vals = rng.normal(size=indptr[-1])
        y = rng.integers(0, 2, n).astype(np.float64)
        y[::13] = np.nan
        p = str(tmp_path / "store")
        write_data_store(p, y, indptr=indptr, cols=cols, vals=vals,
                         dim=d, chunk_rows=64)
        kw = dict(drop_invalid=True, task=TaskType.LOGISTIC_REGRESSION)
        ref = self._fit(CsrSource(indptr, cols, vals, y, dim=d,
                                  dtype=np.float64), 128, d, **kw)
        res = self._fit(MmapChunkSource(p), 128, d, **kw)
        assert np.array_equal(np.asarray(ref.coef), np.asarray(res.coef))
        assert int(ref.iterations) == int(res.iterations)

    def test_consumed_token_fence_trails_and_resets(self, rng, tmp_path):
        """RSS bounding on the alias path is token-fenced: ``consumed``
        releases pages only ``_CONSUME_LAG`` chunks behind the handed-in
        consumption tokens (a reader-side advise would be re-faulted by
        lagging async executions), and a backwards cursor (new pass)
        resets the watermark without fencing — those tokens were
        realized at the pass-end host read."""
        from photon_tpu.data.streaming import MmapChunkSource

        p, _ = self._sparse_store(rng, tmp_path, n=640)
        src = MmapChunkSource(p)
        calls = []
        src.store.advise_dontneed = lambda lo, hi: calls.append((lo, hi))
        lag = src._CONSUME_LAG
        for c in range(lag):   # fills the FIFO: nothing released yet
            src.consumed((c + 1) * 64, np.zeros(2))
        assert calls == [] and src._consumed_to == 0
        src.consumed((lag + 1) * 64, np.zeros(2))   # pops chunk 0
        assert calls == [(0, 64)] and src._consumed_to == 64
        src.consumed(64, np.zeros(2))   # backwards cursor: new pass
        assert src._consumed_to == 0
        assert len(src._pending) == 1   # only the new pass's first chunk
        assert calls == [(0, 64)]       # reset released nothing extra
        # advise_behind=False turns the whole path off
        src2 = MmapChunkSource(p, advise_behind=False)
        src2.store.advise_dontneed = lambda lo, hi: calls.append((lo, hi))
        for c in range(2 * lag):
            src2.consumed((c + 1) * 64, np.zeros(2))
        assert calls == [(0, 64)] and src2._pending == []

    def test_kill_mid_epoch_bitwise_resume_on_disk_path(self, rng,
                                                        tmp_path):
        """The chunk-cursor checkpoint machinery rides the disk-backed
        source unchanged: kill mid-pass, resume from the checkpoint,
        finish bitwise identical to the uninterrupted disk-backed run."""
        from photon_tpu.data.streaming import MmapChunkSource

        p, (_indptr, _cols, _vals, _y, d) = self._sparse_store(
            rng, tmp_path, n=800)
        ckpt = str(tmp_path / "stream.ckpt")

        def fit(**kw):
            loader = ChunkLoader(
                MmapChunkSource(p),
                StreamConfig(chunk_rows=128, dtype=np.float64))
            return minimize_streamed(
                StreamedProblem(_objective(), loader, l2_weight=L2),
                np.zeros(d), **kw)

        ref = fit()
        with chaos.active(chaos.ChaosConfig(stream_kill_at=(3, 2))):
            with pytest.raises(chaos.SimulatedKill):
                fit(checkpoint_path=ckpt, checkpoint_every_chunks=2)
        assert os.path.exists(ckpt)
        meta, _arrays = load_stream_checkpoint(ckpt)
        assert meta["pass_idx"] == 3 and meta["next_chunk"] == 3
        res = fit(checkpoint_path=ckpt, checkpoint_every_chunks=2)
        assert np.array_equal(np.asarray(ref.coef), np.asarray(res.coef))
        assert int(ref.iterations) == int(res.iterations)
        assert int(ref.num_fun_evals) == int(res.num_fun_evals)


# -- streamed vs resident fit, end to end -----------------------------------
#
# One f64 logistic problem (16,384 x 64) fit resident and streamed, with
# two 1/8-of-the-data chunk buffers: staging stays inside a quarter of
# the dataset, the streamed fit is bitwise run to run, and the streamed
# (f, g) equals the resident one at the fitted point.


@pytest.fixture(scope="module")
def stream_vs_resident():
    from photon_tpu.data.streaming import ensure_aligned
    from photon_tpu.ops.losses import LogisticLoss

    n, d = 16384, 64
    X, y, _ = generate_binary_classification(np.random.default_rng(11), n, d)
    X = ensure_aligned(np.ascontiguousarray(X, np.float64))
    y = ensure_aligned(np.ascontiguousarray(y, np.float64))
    obj = GLMObjective(loss=LogisticLoss)
    cfg = SolverConfig(max_iterations=100, tolerance=1e-9)
    stream_cfg = StreamConfig(chunk_rows=n // 8, num_buffers=2,
                              dtype=np.float64)

    def problem():
        return StreamedProblem(obj, ChunkLoader(DenseSource(X, y),
                                                stream_cfg), l2_weight=L2)

    batch = DataBatch(features=jnp.asarray(X), labels=jnp.asarray(y))
    vg = lambda c: obj.value_and_gradient(c, batch, Hyper.of(L2, F64))
    resident = lbfgs.minimize(vg, jnp.zeros(d, F64), config=cfg)
    run1 = minimize_streamed(problem(), np.zeros(d), config=cfg)
    run2 = minimize_streamed(problem(), np.zeros(d), config=cfg)
    coef = np.asarray(resident.coef)
    f_res, g_res = vg(jnp.asarray(coef))
    prob = problem()
    f_str, g_str = prob.value_and_gradient(coef)
    value_dev = abs(float(f_res) - float(f_str)) / max(abs(float(f_res)), 1)
    grad_dev = float(np.max(np.abs(np.asarray(g_res) - g_str))
                     / max(float(np.max(np.abs(np.asarray(g_res)))), 1e-30))
    staging = (stream_cfg.num_buffers * prob.loader.chunk_bytes()
               / (X.nbytes + y.nbytes))
    return {
        "grad_parity": grad_dev <= 1e-6 and value_dev <= 1e-6,
        "bitwise_run_to_run": bool(np.array_equal(np.asarray(run1.coef),
                                                  np.asarray(run2.coef))),
        "staging_within_a_quarter": staging <= 0.26,
    }


@pytest.mark.parametrize("gate", [
    "grad_parity", "bitwise_run_to_run", "staging_within_a_quarter"])
def test_streamed_fit_matches_resident(stream_vs_resident, gate):
    assert stream_vs_resident[gate] is True, stream_vs_resident


# -- LibSVM text -> mmap chunk store -> streamed fit, end to end -------------
#
# 16,384 rows of LibSVM text (8 nonzeros a row, 256 columns, two files)
# are converted once into the crc-verified chunk store; the same streamed
# L-BFGS fit then runs off the in-RAM parse and off the mmap store.


def _write_libsvm(dir_path, n, k, dim, files, seed):
    """k strictly increasing 1-based ids a row, %.17g values (the text
    round-trips bitwise) and labels in {-1, +1}."""
    rng = np.random.default_rng(seed)
    for fi in range(files):
        cols = np.sort(rng.integers(0, dim - k, (n // files, k)), axis=1)
        cols += np.arange(k)
        vals = rng.standard_normal((n // files, k))
        ys = rng.integers(0, 2, n // files) * 2 - 1
        with open(os.path.join(dir_path, f"part-{fi:04d}.txt"), "w") as f:
            f.write("".join(
                "%d %s\n" % (yy, " ".join("%d:%.17g" % (c + 1, v)
                                          for c, v in zip(cr, vr)))
                for yy, cr, vr in zip(ys.tolist(), cols.tolist(),
                                      vals.tolist())))


@pytest.fixture(scope="module")
def ingest_arms(tmp_path_factory):
    from photon_tpu.data import ingest
    from photon_tpu.data.streaming import MmapChunkSource
    from photon_tpu.io import data_store
    from photon_tpu.ops.losses import LogisticLoss

    chunk_rows, max_iter = 2048, 5
    root = tmp_path_factory.mktemp("ingest_q")
    raw, store = str(root / "libsvm"), str(root / "store")
    os.makedirs(raw)
    _write_libsvm(raw, 16384, 8, 256, 2, seed=29)
    data_store.convert_libsvm(raw, store, chunk_rows=chunk_rows,
                              dtype=np.float64)
    stream_cfg = StreamConfig(chunk_rows=chunk_rows, num_buffers=2,
                              dtype=np.float64)

    def fit(source):
        return minimize_streamed(
            StreamedProblem(GLMObjective(loss=LogisticLoss),
                            ChunkLoader(source, stream_cfg), l2_weight=L2),
            np.zeros(source.dim),
            config=SolverConfig(max_iterations=max_iter, tolerance=1e-9))

    ram = fit(chunk_source(ingest.read_libsvm(raw), dtype=np.float64))
    src = MmapChunkSource(store)
    mm1, mm2 = fit(src), fit(src)
    # a store opened afresh, as a new process would
    fresh_src = MmapChunkSource(store)
    fresh = fit(fresh_src)
    chunks = list(ChunkLoader(fresh_src, stream_cfg).stream())
    same = lambda a, b: (
        np.asarray(a.coef).tobytes() == np.asarray(b.coef).tobytes()
        and int(a.iterations) == int(b.iterations)
        and int(a.num_fun_evals) == int(b.num_fun_evals))
    gates = {
        "bitwise_vs_inram": same(ram, mm1),
        "bitwise_run_to_run": same(mm1, mm2),
        "fresh_open_bitwise_vs_inram": same(ram, fresh),
        "every_chunk_aliased": (len(chunks) == 16384 // chunk_rows
                                and not any(c.fenced for c in chunks)),
    }
    src.store.close()
    fresh_src.store.close()
    return gates


@pytest.mark.parametrize("gate", [
    "bitwise_vs_inram", "bitwise_run_to_run", "fresh_open_bitwise_vs_inram",
    "every_chunk_aliased"])
def test_libsvm_to_mmap_store_fit(ingest_arms, gate):
    assert ingest_arms[gate] is True, ingest_arms


class TestEpochChunkOrder:
    """Satellite regression: the counter-derived per-epoch chunk
    permutation the SDCA arm rides. Identity on epoch 0 (geometry is
    only learned on a completed ascending pass), splitmix64-keyed
    Fisher-Yates afterwards — bitwise stable across platforms and numpy
    releases, so the exact vectors are pinned."""

    def test_epoch0_is_identity(self):
        np.testing.assert_array_equal(epoch_chunk_order(9, 0, 6),
                                      np.arange(6))

    def test_degenerate_sizes(self):
        np.testing.assert_array_equal(epoch_chunk_order(3, 5, 0), [])
        np.testing.assert_array_equal(epoch_chunk_order(3, 5, 1), [0])
        with pytest.raises(ValueError, match="num_chunks"):
            epoch_chunk_order(3, 5, -1)

    def test_is_permutation_and_deterministic(self):
        for seed in (0, 3, 123456789):
            for epoch in (1, 2, 17):
                a = epoch_chunk_order(seed, epoch, 13)
                np.testing.assert_array_equal(np.sort(a), np.arange(13))
                np.testing.assert_array_equal(
                    a, epoch_chunk_order(seed, epoch, 13))

    def test_seed_and_epoch_key_the_stream(self):
        base = epoch_chunk_order(3, 1, 8)
        assert not np.array_equal(base, epoch_chunk_order(3, 2, 8))
        assert not np.array_equal(base, epoch_chunk_order(7, 1, 8))

    def test_pinned_regression_vectors(self):
        """Checkpoint resume replays the permutation from (seed, epoch)
        alone, so these exact orders are a forever contract."""
        np.testing.assert_array_equal(epoch_chunk_order(3, 1, 8),
                                      [2, 4, 7, 0, 1, 5, 6, 3])
        np.testing.assert_array_equal(epoch_chunk_order(3, 2, 8),
                                      [2, 1, 3, 5, 6, 4, 0, 7])
        np.testing.assert_array_equal(epoch_chunk_order(7, 1, 8),
                                      [2, 6, 1, 0, 4, 5, 7, 3])
        np.testing.assert_array_equal(epoch_chunk_order(0, 5, 5),
                                      [3, 2, 0, 1, 4])

    def test_stream_order_visits_canonical_chunks(self, rng):
        """stream(order=...) permutes WHICH chunk arrives when, never
        chunk composition: chunk_id c carries exactly the rows the
        ascending pass put in chunk c, and index is the visit position."""
        n, d = 640, 6
        X, y = _logistic_problem(rng, n=n, d=d)
        loader = ChunkLoader(DenseSource(X, y),
                             StreamConfig(chunk_rows=128,
                                          dtype=np.float64))
        ascending = {c.chunk_id: (np.asarray(c.batch.features).copy(),
                                  np.asarray(c.batch.labels).copy(),
                                  c.rows)
                     for c in loader.stream()}
        order = epoch_chunk_order(3, 1, loader.num_chunks)
        seen = []
        for pos, chunk in enumerate(loader.stream(order=order)):
            assert chunk.index == pos
            assert chunk.chunk_id == int(order[pos])
            ref_x, ref_y, ref_rows = ascending[chunk.chunk_id]
            assert chunk.rows == ref_rows
            np.testing.assert_array_equal(
                np.asarray(chunk.batch.features), ref_x)
            np.testing.assert_array_equal(
                np.asarray(chunk.batch.labels), ref_y)
            seen.append(chunk.chunk_id)
        assert seen == list(order)

    def test_stream_order_refuses_non_permutation(self, rng):
        X, y = _logistic_problem(rng, n=256, d=4)
        loader = ChunkLoader(DenseSource(X, y),
                             StreamConfig(chunk_rows=128,
                                          dtype=np.float64))
        with pytest.raises(ValueError, match="permutation"):
            list(loader.stream(order=[0, 0]))

    def test_geometry_roundtrip_enables_permuted_resume(self, rng):
        """A permuted pass with drop_invalid needs the survivor geometry
        of a completed ascending pass. geometry()/restore_geometry()
        moves that across a process boundary: a FRESH loader that never
        streamed ascending serves the identical permuted pass."""
        n, d = 700, 6
        X, y = _logistic_problem(rng, n=n, d=d)
        y[rng.choice(n, size=40, replace=False)] = np.nan
        cfg = StreamConfig(chunk_rows=128, dtype=np.float64,
                           drop_invalid=True,
                           task=TaskType.LOGISTIC_REGRESSION)

        loader = ChunkLoader(DenseSource(X, y), cfg)
        assert loader.geometry() is None  # unknown before a full pass
        for _ in loader.stream():
            pass
        geom = loader.geometry()
        assert geom is not None and "block_cum" in geom
        order = epoch_chunk_order(3, 1, geom["num_chunks"])
        ref = [(c.chunk_id, c.rows,
                np.asarray(c.batch.labels).copy())
               for c in loader.stream(order=order)]

        fresh = ChunkLoader(DenseSource(X, y), cfg)
        with pytest.raises(ValueError, match="ascending"):
            list(fresh.stream(order=order))  # no geometry yet
        fresh.restore_geometry(geom)
        got = [(c.chunk_id, c.rows,
                np.asarray(c.batch.labels).copy())
               for c in fresh.stream(order=order)]
        assert len(got) == len(ref)
        for (ri, rr, ry), (gi, gr, gy) in zip(ref, got):
            assert ri == gi and rr == gr
            np.testing.assert_array_equal(ry, gy)
