"""Hierarchical local-subproblem solver (photon_tpu/optim/hier.py).

The claims under test, in order of importance:

  1. communication structure: the round program contains exactly ONE
     DCN-stage psum no matter how many inner iterations run (static
     jaxpr oracle), and a full solve issues several-fold fewer DCN
     reductions than the reference data-parallel L-BFGS;
  2. parity: the safeguarded solve lands within 1e-5 relative loss of
     the reference optimum (f64 — the bar is below f32 round-off);
  3. the safeguard: a regressing round trips a typed ``hier_fallback``
     event + counter and the solve still converges to parity;
  4. refusal by construction: ``ModelShardedSparse`` batches raise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.dataset import DataBatch
from photon_tpu.function.objective import GLMObjective, Hyper
from photon_tpu.obs.metrics import registry
from photon_tpu.ops import features as F
from photon_tpu.ops.losses import LogisticLoss
from photon_tpu.optim import hier
from photon_tpu.optim.base import SolverConfig
from photon_tpu.parallel import mesh as M
from photon_tpu.resilience import failures


def _problem(n=2048, d=16, seed=7, spread=-2.5):
    """Ill-conditioned logistic design (column scales over 10^-spread
    with cross-correlation): hard enough that the reference pays many
    evaluations, which is the regime the round structure exists for."""
    rng = np.random.default_rng(seed)
    base = rng.normal(size=(n, d))
    mix = rng.normal(size=(d, d)) * 0.3 + np.eye(d)
    scales = np.logspace(0, spread, d)
    X = (base @ mix * scales).astype(np.float64)
    w = rng.normal(size=(d,)) * 2.0
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-X @ w))) \
        .astype(np.float64)
    return DataBatch(features=jnp.asarray(X), labels=jnp.asarray(y),
                     offsets=jnp.zeros(n, jnp.float64),
                     weights=jnp.ones(n, jnp.float64))


OBJ = GLMObjective(loss=LogisticLoss)
HYPER = Hyper.of(0.1, dtype=jnp.float64)


class TestRoundStructure:
    def test_round_fn_has_exactly_one_dcn_psum(self):
        """The static oracle behind the whole design: one DCN reduction
        per round, invariant to the inner-iteration budget."""
        batch = _problem(n=256)
        mesh = M.create_two_level_mesh(8, 2)
        sharded = M.shard_batch(batch, mesh, axis=(M.DCN_AXIS, M.DATA_AXIS))
        c = M.replicate(jnp.zeros(16, jnp.float64), mesh)
        mu = jnp.float64(0.0)
        for h in (1, 8, 50):
            round_fn = hier.build_round_fn(
                OBJ, mesh, hier.HierConfig(local_iterations=h))
            n_psums = M.count_axis_psums(
                round_fn, M.DCN_AXIS, c, c, c, mu, HYPER, sharded)
            assert n_psums == 1, (h, n_psums)

    def test_reference_vg_pays_one_dcn_psum_per_evaluation(self):
        batch = _problem(n=256)
        mesh = M.create_two_level_mesh(8, 2)
        sharded = M.shard_batch(batch, mesh, axis=(M.DCN_AXIS, M.DATA_AXIS))
        c = M.replicate(jnp.zeros(16, jnp.float64), mesh)
        global_vg = hier.build_global_vg(OBJ, mesh)
        assert M.count_axis_psums(
            global_vg, M.DCN_AXIS, c, HYPER, sharded) == 1


class TestParity:
    def test_parity_and_fewer_dcn_reductions(self):
        batch = _problem()
        mesh = M.create_two_level_mesh(8, 2)
        ref, ref_dcn = hier.minimize_reference(
            OBJ, batch, HYPER, jnp.zeros(16, jnp.float64), mesh,
            config=SolverConfig(max_iterations=500, tolerance=1e-10))
        hits0 = registry.counter(
            "parallel.dcn_stage_reductions", path="hier").value
        res = hier.minimize_hier(
            OBJ, batch, HYPER, jnp.zeros(16, jnp.float64), mesh,
            config=hier.HierConfig(rounds=60, local_iterations=25,
                                   tolerance=1e-10))
        gap = abs(res.value - float(ref.value)) / max(
            1.0, abs(float(ref.value)))
        assert gap <= 1e-5, (res.value, float(ref.value), gap)
        assert res.dcn_reductions * 3 <= ref_dcn, \
            (res.dcn_reductions, ref_dcn)
        # the observability counter tracks the result field exactly
        hits1 = registry.counter(
            "parallel.dcn_stage_reductions", path="hier").value
        assert hits1 - hits0 == res.dcn_reductions
        assert res.value <= min(res.history) + 1e-12  # monotone best-of

    def test_single_level_data_mesh(self):
        """No DCN axis: the solve still works, sharded over data only."""
        batch = _problem(n=1024)
        mesh = M.create_mesh(8, (M.DATA_AXIS,))
        ref, _ = hier.minimize_reference(
            OBJ, batch, HYPER, jnp.zeros(16, jnp.float64), mesh,
            config=SolverConfig(max_iterations=500, tolerance=1e-10))
        res = hier.minimize_hier(
            OBJ, batch, HYPER, jnp.zeros(16, jnp.float64), mesh,
            config=hier.HierConfig(rounds=40, local_iterations=25,
                                   tolerance=1e-10))
        gap = abs(res.value - float(ref.value)) / max(
            1.0, abs(float(ref.value)))
        assert gap <= 1e-5, gap

    def test_ell_sparse_batch(self):
        """ELL-sparse features ride the same data-parallel rounds."""
        rng = np.random.default_rng(3)
        n, d, k = 2048, 64, 8
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = (rng.normal(size=(n, k)) / np.sqrt(k)).astype(np.float64)
        w = rng.normal(size=d)
        margins = np.zeros(n)
        for j in range(k):
            margins += val[:, j] * w[idx[:, j]]
        y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margins))) \
            .astype(np.float64)
        batch = DataBatch(
            features=F.SparseFeatures(jnp.asarray(idx), jnp.asarray(val)),
            labels=jnp.asarray(y), offsets=jnp.zeros(n, jnp.float64),
            weights=jnp.ones(n, jnp.float64))
        mesh = M.create_two_level_mesh(8, 2)
        ref, _ = hier.minimize_reference(
            OBJ, batch, HYPER, jnp.zeros(d, jnp.float64), mesh,
            config=SolverConfig(max_iterations=500, tolerance=1e-10))
        res = hier.minimize_hier(
            OBJ, batch, HYPER, jnp.zeros(d, jnp.float64), mesh,
            config=hier.HierConfig(rounds=40, local_iterations=15,
                                   tolerance=1e-10))
        gap = abs(res.value - float(ref.value)) / max(
            1.0, abs(float(ref.value)))
        assert gap <= 1e-5, gap


class TestSafeguard:
    def test_fallback_is_typed_event_not_exception(self):
        """Overshooting rounds (harsh conditioning, deep local budget,
        no damping) must trip the safeguard: typed hier_fallback event,
        counter, reference step — and STILL land on parity."""
        failures.clear()
        batch = _problem(n=4096, d=32, spread=-4.0, seed=11)
        mesh = M.create_two_level_mesh(8, 2)
        fb0 = registry.counter("hier.fallbacks").value
        res = hier.minimize_hier(
            OBJ, batch, HYPER, jnp.zeros(32, jnp.float64), mesh,
            config=hier.HierConfig(rounds=60, local_iterations=50,
                                   tolerance=1e-10))
        assert res.fallbacks >= 1, res
        events = [e for e in failures.snapshot()
                  if e["kind"] == "hier_fallback"]
        assert len(events) >= 1
        assert {"round", "f_candidate", "f_best"} <= set(events[0])
        assert registry.counter("hier.fallbacks").value - fb0 \
            == res.fallbacks
        ref, _ = hier.minimize_reference(
            OBJ, batch, HYPER, jnp.zeros(32, jnp.float64), mesh,
            config=SolverConfig(max_iterations=800, tolerance=1e-10))
        gap = abs(res.value - float(ref.value)) / max(
            1.0, abs(float(ref.value)))
        assert gap <= 1e-5, gap


class TestRefusal:
    def test_model_sharded_sparse_is_refused(self):
        mesh = M.create_mesh(8, (M.DATA_AXIS,))
        ms = F.ModelShardedSparse(
            indices=jnp.zeros((1, 8, 2), jnp.int32),
            values=jnp.zeros((1, 8, 2), jnp.float32),
            shard_size=16, mesh=mesh)
        batch = DataBatch(features=ms, labels=jnp.zeros(8),
                          offsets=jnp.zeros(8), weights=jnp.ones(8))
        with pytest.raises(ValueError, match="ModelShardedSparse"):
            hier.minimize_hier(OBJ, batch, HYPER, jnp.zeros(16), mesh)
        with pytest.raises(ValueError, match="ModelShardedSparse"):
            hier.minimize_reference(OBJ, batch, HYPER, jnp.zeros(16), mesh)


# -- the acceptance bars at full depth: 8,192 x 64, 40 rounds of 50 --------


@pytest.fixture(scope="module")
def hier_vs_reference():
    batch = _problem(n=8192, d=64)
    mesh = M.create_two_level_mesh(8, 2)
    x0 = jnp.zeros(64, jnp.float64)
    ref, ref_dcn = hier.minimize_reference(
        OBJ, batch, HYPER, x0, mesh,
        config=SolverConfig(max_iterations=1000, tolerance=1e-10))
    res = hier.minimize_hier(
        OBJ, batch, HYPER, x0, mesh,
        config=hier.HierConfig(rounds=40, local_iterations=50,
                               tolerance=1e-10))
    ref_f = float(np.asarray(ref.value))
    return {
        "parity_le_1e5": abs(res.value - ref_f) / max(1.0, abs(ref_f))
        <= 1e-5,
        "dcn_reductions_5x_fewer": ref_dcn >= 5 * max(res.dcn_reductions, 1),
        "hier_converged": bool(res.converged),
    }


@pytest.mark.parametrize("gate", [
    "parity_le_1e5", "dcn_reductions_5x_fewer", "hier_converged"])
def test_hier_clears_the_acceptance_bars(hier_vs_reference, gate):
    assert hier_vs_reference[gate] is True, hier_vs_reference
