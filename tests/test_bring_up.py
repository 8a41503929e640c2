"""Start-up, device selection and launch code: nothing on the main path
hides the device (ISSUE 21).

Cheap by construction — tier-1 has no wall-clock to spare: one
subprocess, no compile-heavy body unmarked.
"""

import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), os.pardir))


# -- chip_smoke.py ------------------------------------------------------------


def test_chip_smoke_refuses_cpu_and_names_the_platform():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout        # no result line


@pytest.mark.slow
def test_chip_smoke_body_at_toy_size(monkeypatch):
    """The same phases main() runs on the chip, small, kernels
    interpreted. x64 off: the smoke is a float32 program."""
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke

    toy = chip_smoke.Sizes(
        n_train=4000, n_val=1000, d_global=16, n_users=40, d_user=4,
        n_requests=40, n_unknown=3, n_cli=600, kernel_rows=300,
        kernel_dense_dims=(16, 128), kernel_ragged_shape=(290, 200),
        kernel_product_shapes=((272, 200),),
        kernel_sparse_dim=200,
        kernel_ell_width=5, kernel_serving_rows=24,
        layout_relaid_shapes=((290, 200),), layout_plain_shape=(256, 128))
    jax.config.update("jax_enable_x64", False)
    try:
        out = chip_smoke.run(toy, kernel_interpret=True)
    finally:
        jax.config.update("jax_enable_x64", True)
    assert abs(out["train"]["auc"] - out["train"]["oracle_auc"]) <= 2e-3
    assert out["train"]["budget_source"] == "fallback"      # a CPU
    assert out["kernels"]["interpret"] is True
    assert set(out["layout"].values()) == {"default"}       # a CPU
    assert out["mesh"]["job"] == "default"
    assert "mesh" in out                    # 8 virtual devices >= 4


# -- compile cache placement ---------------------------------------------------


@pytest.fixture
def cache_config():
    """Restore the jax cache settings the session runs with."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def test_compile_cache_env_places_it_and_code_sets_no_directory(
        tmp_path, monkeypatch, cache_config):
    from photon_tpu.utils import compile_cache

    placed = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    dir_in_config = jax.config.jax_compilation_cache_dir
    assert compile_cache.maybe_enable() == placed
    assert os.path.isdir(placed)
    # jax reads the variable itself; the program set nothing over it
    assert jax.config.jax_compilation_cache_dir == dir_in_config


def test_compile_cache_default_is_the_checkout_and_does_not_move(
        monkeypatch, cache_config):
    from photon_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_compile_cache")
    assert compile_cache.maybe_enable() == want
    assert compile_cache.maybe_enable() == want
    assert jax.config.jax_compilation_cache_dir == want
    monkeypatch.setenv("PHOTON_TPU_NO_XLA_CACHE", "1")
    assert compile_cache.maybe_enable() is None


# -- peaks and budgets: never assumed -------------------------------------------


class _Device:
    def __init__(self, platform, kind, stats=None):
        self.platform, self.device_kind, self._stats = platform, kind, stats

    def memory_stats(self):
        return self._stats


def test_peaks_unknown_tpu_kind_raises_and_cpu_has_none():
    from photon_tpu.utils import flops

    assert flops.peak_h2d_bw(_Device("tpu", "TPU v5 lite"))[0] == 32e9
    assert flops.peak_h2d_bw(jax.devices()[0]) == (None, "cpu")
    with pytest.raises(ValueError, match="no peak figures"):
        flops.peak_h2d_bw(_Device("tpu", "TPU v9 mystery"))


def test_hbm_budget_is_never_assumed_for_an_accelerator(monkeypatch):
    from photon_tpu.parallel import memory

    monkeypatch.delenv(memory.ENV_BUDGET, raising=False)
    chip = _Device("tpu", "TPU v5 lite", {"bytes_limit": 16 << 30})
    assert memory.default_hbm_budget_bytes(chip) == (
        int((16 << 30) * 0.8), "backend")
    with pytest.raises(RuntimeError, match="bytes_limit"):
        memory.default_hbm_budget_bytes(_Device("tpu", "TPU v5 lite"))
    assert memory.default_hbm_budget_bytes(
        _Device("cpu", "cpu"))[1] == "fallback"


# -- one process per chip ---------------------------------------------------------


def test_shard_children_platform_is_explicit_or_refused(tmp_path,
                                                        monkeypatch):
    from photon_tpu.cli import fleet_serve

    assert fleet_serve.shard_child_platform("cpu", "tpu") == "cpu"
    assert fleet_serve.shard_child_platform(None, "cpu") is None
    with pytest.raises(fleet_serve.ShardSpawnRefused, match="one process"):
        fleet_serve.shard_child_platform(None, "tpu")

    spawned = {}

    class _Popen:
        stdout = ()

        def __init__(self, argv, **kw):
            spawned.update(argv=argv, **kw)

    monkeypatch.setattr(fleet_serve.subprocess, "Popen", _Popen)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    client = fleet_serve.PipeShardClient(
        3, "/fleet", ["--max-batch", "8"], platform="cpu",
        log_dir=str(tmp_path))
    assert spawned["argv"][1:7] == ["-m", "photon_tpu.cli.serve",
                                    "--fleet-manifest", "/fleet",
                                    "--shard-id", "3"]
    assert spawned["env"]["JAX_PLATFORMS"] == "cpu"
    assert client.stderr_path == str(tmp_path / "shard-3.stderr")
    assert spawned["stderr"].name == client.stderr_path   # a file, kept
    # no platform asked for: the child's environment is the parent's
    fleet_serve.PipeShardClient(0, "/fleet", platform=None,
                                log_dir=str(tmp_path))
    assert spawned["env"]["JAX_PLATFORMS"] == "tpu"
