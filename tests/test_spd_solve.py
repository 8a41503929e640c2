"""``optim/spd.py``: the one SPD solve NEWTON and DIRECT call.

Under the gate (``LANES_MAX_DIM``) a vmapped solve runs a Cholesky written as
elementwise steps with the batch on the minor axis; above it XLA's
``cho_factor`` / ``cho_solve``. Both keep one contract: the accuracy of a
float32 Cholesky solve, a non-finite result for a matrix that is not positive
definite, and a result that does not depend on the batch an entity sits in.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.optim import newton
from photon_tpu.optim.base import ConvergenceReason, SolverConfig
from photon_tpu.optim.spd import LANES_MAX_DIM, spd_solve


def _spd(rng, e, k, cond=None):
    """[e, k, k] float64 SPD matrices; ``cond`` fixes the condition number."""
    if cond is None:
        m = rng.normal(size=(e, k, 3 * k + 2))
        return m @ m.transpose(0, 2, 1) / (3 * k + 2) + 0.1 * np.eye(k)
    q = np.linalg.qr(rng.normal(size=(e, k, k)))[0]
    eig = np.logspace(0, -np.log10(cond), k) if k > 1 else np.ones(1)
    return (q * eig) @ q.transpose(0, 2, 1)


def _cho(h, g):
    return jax.scipy.linalg.cho_solve(jax.scipy.linalg.cho_factor(h), g)


def _worst_error(x, ref):
    x = np.asarray(x, np.float64)
    return float(np.max(np.linalg.norm(x - ref, axis=-1)
                        / np.linalg.norm(ref, axis=-1)))


@pytest.mark.parametrize("cond", [None, 1e4], ids=["well", "cond1e4"])
@pytest.mark.parametrize("e", [1, 7, 130])
@pytest.mark.parametrize("k", [1, 2, 8, 20, 32])
def test_float32_solve_is_as_close_to_float64_as_cho_solve(k, e, cond):
    rng = np.random.default_rng(1000 * k + e)
    h64, g64 = _spd(rng, e, k, cond), rng.normal(size=(e, k))
    h, g = jnp.asarray(h64, jnp.float32), jnp.asarray(g64, jnp.float32)
    # the reference solves the system the float32 operands state
    ref = np.linalg.solve(np.asarray(h, np.float64),
                          np.asarray(g, np.float64)[..., None])[..., 0]
    x = jax.vmap(spd_solve)(h, g)
    assert x.dtype == jnp.float32 and x.shape == (e, k)
    ours, theirs = _worst_error(x, ref), _worst_error(jax.vmap(_cho)(h, g), ref)
    # twice cho_solve's error on the same input; on one or seven matrices the
    # two differ by chance (over 4,096 their maxima and medians agree to a
    # third), so never asked to be under float32's own forward-error scale
    floor = np.finfo(np.float32).eps * float(np.max(np.linalg.cond(h64)))
    assert ours <= max(2.0 * theirs, floor), (ours, theirs, floor)


@pytest.mark.parametrize("batched", ["neither", "h", "g", "both"])
def test_either_operand_may_carry_the_batch(batched):
    rng = np.random.default_rng(2)
    e, k = 5, 6
    h = _spd(rng, e, k) if batched in ("h", "both") else _spd(rng, 1, k)[0]
    g = (rng.normal(size=(e, k)) if batched in ("g", "both")
         else rng.normal(size=k))
    ref = np.linalg.solve(h, g[..., None])[..., 0]
    if batched == "neither":
        x = spd_solve(jnp.asarray(h), jnp.asarray(g))
    else:
        axes = (0 if h.ndim == 3 else None, 0 if g.ndim == 2 else None)
        x = jax.vmap(spd_solve, in_axes=axes)(jnp.asarray(h), jnp.asarray(g))
        ref = np.broadcast_to(ref, (e, k))
    np.testing.assert_allclose(np.asarray(x), ref, rtol=1e-10)


def test_it_runs_in_a_while_loop_under_vmap_under_jit():
    """Where NEWTON has it: the loop's trip count differs an entity."""
    rng = np.random.default_rng(3)
    e, k = 9, 5
    h, g = jnp.asarray(_spd(rng, e, k)), jnp.asarray(rng.normal(size=(e, k)))
    trips = jnp.arange(e) % 3 + 1

    def one(h, g, n):
        def body(c):
            i, x = c
            return i + 1, x + spd_solve(h, g - h @ x)   # iterative refinement
        return jax.lax.while_loop(lambda c: c[0] < n, body,
                                  (0, jnp.zeros_like(g)))[1]

    x = jax.jit(jax.vmap(one))(h, g, trips)
    ref = np.linalg.solve(np.asarray(h), np.asarray(g)[..., None])[..., 0]
    np.testing.assert_allclose(np.asarray(x), ref, rtol=1e-10)


@pytest.mark.parametrize("k", [8, 20])
def test_an_entitys_result_does_not_depend_on_its_batch(k):
    rng = np.random.default_rng(4)
    h = jnp.asarray(_spd(rng, 130, k), jnp.float32)
    g = jnp.asarray(rng.normal(size=(130, k)), jnp.float32)
    solve = jax.jit(jax.vmap(spd_solve))
    wide = np.asarray(solve(h, g))
    np.testing.assert_array_equal(wide[:7], np.asarray(solve(h[:7], g[:7])))
    np.testing.assert_array_equal(wide[3], np.asarray(spd_solve(h[3], g[3])))


@pytest.mark.parametrize("k", [3, LANES_MAX_DIM + 1], ids=["lanes", "lapack"])
@pytest.mark.parametrize("bad", ["indefinite", "singular", "zero"])
def test_a_matrix_that_is_not_positive_definite_gives_a_non_finite_step(bad, k):
    rng = np.random.default_rng(5)
    h = _spd(rng, 4, k)
    if bad == "indefinite":
        q = np.linalg.qr(rng.normal(size=(k, k)))[0]
        h[2] = (q * np.r_[np.ones(k - 1), -1.0]) @ q.T
    else:
        h[2] = 1.0 if bad == "singular" else 0.0   # rank one; rank zero
    g = rng.normal(size=(4, k))
    x = np.asarray(jax.vmap(spd_solve)(jnp.asarray(h, jnp.float32),
                                       jnp.asarray(g, jnp.float32)))
    assert not np.all(np.isfinite(x[2]))
    # and its neighbours in the batch never notice
    good = [0, 1, 3]
    ref = np.linalg.solve(h[good], g[good][..., None])[..., 0]
    np.testing.assert_allclose(x[good], ref, rtol=2e-4)


def test_newton_reaches_the_optimum_of_a_rank_deficient_problem_by_fallback():
    """lambda = 0 and two identical columns: the Hessian is singular at every
    point, the solve is non-finite, ``optim/newton/direction`` takes steepest
    descent, and the solve still converges."""
    rng = np.random.default_rng(6)
    col = rng.normal(size=(40, 1))
    x = jnp.asarray(np.hstack([col, col]))
    y = jnp.asarray(2.0 * col[:, 0] + 0.01 * rng.normal(size=40))

    def vg(w):
        r = x @ w - y
        return 0.5 * jnp.dot(r, r), x.T @ r

    res = newton.minimize(vg, lambda w: x.T @ x, jnp.zeros(2),
                          SolverConfig(max_iterations=200, tolerance=1e-9))
    assert int(res.reason) != ConvergenceReason.NOT_CONVERGED
    assert int(res.failure) == 0
    best = 0.5 * float(np.sum((np.asarray(y) - np.asarray(col[:, 0])
                               * (col[:, 0] @ np.asarray(y))
                               / (col[:, 0] @ col[:, 0])) ** 2))
    assert float(res.value) <= best * (1 + 1e-4)


def _vmapped_newton_jaxpr(k):
    def vg(w):
        return 0.5 * jnp.dot(w, w) + jnp.sum(jnp.cos(w)), w - jnp.sin(w)

    def solve(w0):
        return newton.minimize(
            vg, lambda w: jnp.diag(1.0 - jnp.cos(w)) + jnp.eye(k), w0).coef

    # the jaxpr names the primitives whatever platform would lower them
    return str(jax.make_jaxpr(jax.vmap(solve))(jnp.ones((3, k))))


@pytest.mark.parametrize("k,path", [(LANES_MAX_DIM, "lanes"),
                                    (LANES_MAX_DIM + 1, "lapack")])
def test_the_gate_decides_what_a_vmapped_newton_lowers_to(k, path):
    equations = re.findall(r"= (cholesky|triangular_solve)\b",
                           _vmapped_newton_jaxpr(k))
    assert set(equations) == (set() if path == "lanes"
                              else {"cholesky", "triangular_solve"})


def test_the_gate_keeps_the_benchmarks_shapes_on_their_sides():
    """glmix-ml20m: per-user K = 20 and per-movie K = 8 batched, under it; the
    fixed effect's unbatched K = 128 above (PERF.md §5, the K x E table)."""
    assert 20 <= LANES_MAX_DIM < 128
