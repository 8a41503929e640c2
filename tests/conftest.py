"""Test fixture: force an 8-device virtual CPU mesh before JAX initializes.

This plays the role of the reference's SparkTestUtils.sparkTest local-mode
fixture (photon-test-utils .../SparkTestUtils.scala:30-60): "distributed"
behavior — sharded batches, psum reductions, entity-sharded solves — is
exercised on host-platform virtual devices without TPU hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent XLA compilation cache (repo-local, gitignored): the suite is
# compile-bound on the 1-core CI host, and every run re-lowers the same
# HLO. Caching executables across processes/runs keeps tier-1 inside its
# wall budget without dropping tests. Semantics are untouched — the cache
# is keyed on the HLO hash (same executable bytes, bitwise-same results)
# and trace/compile COUNTS (jitcache, compile monitors) are unaffected;
# only backend-compile wall time shrinks. Env vars (not jax.config) so
# subprocess tests (cli/serve, the no-recompile script) inherit it too.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.normpath(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "..", ".jax_compile_cache")))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import jax  # noqa: E402

# Tests always run on the virtual 8-device CPU mesh, whatever the machine
# has: on a host with a chip, the suite (and the subprocesses it starts)
# must not take it — a chip belongs to one process at a time.
jax.config.update("jax_platforms", "cpu")

# Float64 on the CPU test mesh so optimizer convergence tests can assert
# tight tolerances against scipy oracles; production TPU runs use f32/bf16.
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'` (ROADMAP): compile-heavy rehearsals
    config.addinivalue_line(
        "markers", "slow: left out of tier-1 (run with -m slow)")


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def devices8():
    ds = jax.devices()
    assert len(ds) == 8, f"expected 8 virtual devices, got {len(ds)}"
    return ds
