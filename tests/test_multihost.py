"""True multi-PROCESS distributed training (SURVEY §5.8).

The in-repo SPMD tests shard over virtual devices inside one process;
this test spawns TWO separate OS processes, each owning 4 CPU devices,
joined through ``initialize_distributed`` into one 8-device cluster —
the closest single-box analog of a multi-host TPU pod. Each worker
feeds only its own half of the data (``shard_process_local_batch``) and
runs the same public solve; the gradient all-reduces cross the process
boundary over the collective transport (Gloo here, ICI/DCN on a pod).
Parity vs a single-host solve of the identical problem is the oracle —
the reference's Spark-cluster/treeAggregate equivalence.
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_workers(out, mode=None):
    """Spawn the 2-process worker pair, bounded by communicate(timeout=420)
    (no pytest-timeout plugin in this image). Returns the workers' logs;
    only genuine distributed-runtime bring-up failures may skip — an
    ordinary worker traceback is a real regression and must FAIL."""
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"   # two workers, one machine: CPU devices
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PHOTON_TPU_NO_XLA_CACHE"] = "1"     # isolate from cache races
    workers = [
        subprocess.Popen(
            [sys.executable, os.path.join(HERE, "multihost_worker.py"),
             str(pid), "2", str(port), out]
            + ([mode] if mode else []),
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=os.path.dirname(HERE))
        for pid in (0, 1)
    ]
    _INIT_FAILURES = ("DEADLINE_EXCEEDED", "UNAVAILABLE",
                      "Failed to connect", "preemption",
                      "coordination service",
                      # jaxlib built without CPU cross-process collectives
                      # (no Gloo): the cluster forms but no multiprocess
                      # program can run — an environment limitation, not a
                      # code regression
                      "Multiprocess computations aren't implemented")
    logs = []
    try:
        for w in workers:
            stdout, _ = w.communicate(timeout=420)
            logs.append(stdout)
            if w.returncode != 0:
                if any(m in stdout for m in _INIT_FAILURES):
                    pytest.skip("distributed runtime unavailable in this "
                                f"environment:\n{stdout[-2000:]}")
                pytest.fail(f"multihost worker crashed:\n{stdout[-3000:]}")
    finally:
        for w in workers:
            if w.poll() is None:
                w.kill()
    return logs


def test_two_process_solve_matches_single_host(tmp_path):
    out = str(tmp_path / "coefs.npy")
    logs = _run_workers(out)

    assert any("devices 8" in l for l in logs), logs  # 2 procs x 4 devices
    multi = np.load(out)

    # single-host oracle on the identical global problem
    from photon_tpu.data.dataset import DataBatch
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        GlmOptimizationProblem,
        OptimizerConfig,
    )
    from photon_tpu.types import TaskType
    from tests.multihost_problem import make_global_problem

    Xg, yg, cfg_args = make_global_problem()
    cfg = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(**cfg_args),
        regularization=L2Regularization, regularization_weight=1.0)
    prob = GlmOptimizationProblem(TaskType.LOGISTIC_REGRESSION, cfg)
    model, _ = prob.run(
        DataBatch(jnp.asarray(Xg), jnp.asarray(yg), None, None),
        dim=Xg.shape[1], dtype=jnp.float32)
    single = np.asarray(model.coefficients.means)

    np.testing.assert_allclose(multi, single, rtol=5e-4, atol=5e-5)


def test_two_process_consistency_guard_detects_desync(tmp_path):
    """The sweep-boundary consistency guard (resilience/multihost.py)
    across a real 2-process cluster: bitwise-identical fixed-effect state
    passes; a one-host perturbation raises MultiHostDesyncError on every
    process, carrying all hosts' digests."""
    out = str(tmp_path / "consistency.npy")
    logs = _run_workers(out, mode="consistency")

    assert sum("consistency-ok" in l for l in logs) == 2, logs
    assert not any("desync-missed" in l for l in logs), logs
    assert sum("desync-detected sweep 1" in l for l in logs) == 2, logs


def test_two_process_sparse_tp_model_axis_spans_processes(tmp_path):
    """Sparse tensor parallelism composed with the multi-host runtime:
    a (data=4, model=2) mesh whose MODEL axis pairs one device from each
    OS process, so the hot path's theta-range collectives (margin psum
    over model, segment-sum gradient psum over data) cross the process
    boundary. Oracle is a single-host solve of the identical ELL problem
    on the plain (unsharded) path."""
    out = str(tmp_path / "coefs_tp.npy")
    logs = _run_workers(out, mode="sparse_tp")

    assert any("devices 8" in l for l in logs), logs
    # the mesh really did span: each model group held both processes
    assert any("model-axis-procs 2" in l for l in logs), logs
    multi = np.load(out)

    from photon_tpu.data.dataset import DataBatch
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.ops import features as F
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        GlmOptimizationProblem,
        OptimizerConfig,
    )
    from photon_tpu.types import TaskType
    from tests.multihost_problem import make_sparse_tp_problem

    idx, val, y, d, cfg_args = make_sparse_tp_problem()
    cfg = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(**cfg_args),
        regularization=L2Regularization, regularization_weight=1.0)
    prob = GlmOptimizationProblem(TaskType.LOGISTIC_REGRESSION, cfg)
    model, _ = prob.run(
        DataBatch(F.SparseFeatures(jnp.asarray(idx), jnp.asarray(val)),
                  jnp.asarray(y)),
        dim=d, dtype=jnp.float32)
    single = np.asarray(model.coefficients.means)

    np.testing.assert_allclose(multi, single, rtol=5e-4, atol=5e-4)


def test_two_process_hier_round_psum_crosses_dcn(tmp_path):
    """Hierarchical solver over a real 2-process cluster whose DCN mesh
    axis IS the process boundary: the round program carries exactly ONE
    DCN-stage psum (static oracle, checked in each worker under the
    multi-process mesh), the accept-always rounds land within 1e-5
    relative loss of the per-evaluation-DCN reference L-BFGS, and the
    round solve crossed the process boundary fewer times than the
    reference paid evaluations."""
    out = str(tmp_path / "hier.npy")
    logs = _run_workers(out, mode="hier")

    assert any("devices 8" in l for l in logs), logs
    assert sum("dcn-axis-procs 2" in l for l in logs) == 2, logs
    assert sum("round-psums 1" in l for l in logs) == 2, logs
    assert not any("hier-bad" in l for l in logs), logs
    assert sum("hier-ok" in l for l in logs) == 2, logs
