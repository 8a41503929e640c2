"""Streamed GLM solves: L-BFGS / OWL-QN over data that never fully
resides in HBM.

``StreamedProblem`` evaluates the objective by folding chunk after chunk
from a ``data.streaming.ChunkLoader`` into a device-resident carry
``(value_acc, grad_acc)``. Every chunk runs the SAME jitted partial (the
loader guarantees static chunk shapes), so a full pass is one compiled
program applied N times with zero recompiles and — critically — zero
host syncs inside the chunk loop: the single host crossing of a pass is
the ``np.asarray`` pull of ``(f, g)`` at the pass boundary.

On a mesh, the carry is kept SHARD-LOCAL ([n_shards] / [n_shards, dim])
through the whole pass and the per-chunk partial contains NO collectives;
the pass-end finalize issues exactly one staged ICI-then-DCN all-psum
(optim/hier._staged_all_psum) — the same reduction structure a resident
evaluation uses, issued once per pass instead of never needing it per
chunk.

The driving solvers (``minimize_streamed``) are host-loop ports of
optim/lbfgs.minimize and optim/owlqn.minimize with the same update rules,
line searches, tolerance semantics, convergence priorities and typed
non-finite failure handling — they must run on the host because each
objective evaluation is itself a host-driven loop over streamed chunks,
which cannot live inside a ``lax.while_loop``. Determinism is total: the
loader's chunk order is fixed, device arithmetic per chunk is one fixed
program, and all host arithmetic is straight-line numpy — two runs are
bitwise identical.

Mid-epoch preemption: with a ``checkpoint_path``, the solver persists a
chunk-cursor checkpoint (crc-framed npz via resilience/io atomic publish)
containing the iteration-start solver state, the ``(f, g)`` results of
evaluations already completed in the current iteration, and the in-flight
evaluation's device carry + next-chunk cursor. Resume replays the
iteration: completed evaluations are served from the checkpoint cache and
the in-flight pass continues from its cursor, so the resumed run is
bitwise identical to an uninterrupted one.
"""

from __future__ import annotations

import io
import json
import os
import struct
import zlib
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.function.objective import GLMObjective, Hyper
from photon_tpu.ops import pallas_glm
from photon_tpu.optim.base import (
    ConvergenceReason,
    FailureMode,
    SolverConfig,
    SolverResult,
    jit_donating,
)
from photon_tpu.resilience import chaos
from photon_tpu.resilience import io as rio


# =========================================================================
# Streamed objective evaluation
# =========================================================================

class StreamedProblem:
    """Full-pass ``(f, g)`` evaluation of a GLMObjective over a
    ChunkLoader's stream, with a device-resident accumulation carry.

    ``value_and_gradient`` is the solver-facing entry point; its
    ``resume=(carry, next_chunk)`` hook continues a partially-accumulated
    pass from a checkpoint cursor, and ``on_chunk`` fires after each
    chunk's accumulation (the checkpoint writer) — both off by default,
    leaving the hot path a bare dispatch loop.
    """

    def __init__(self, objective: GLMObjective, loader, l2_weight: float = 0.0,
                 dim: Optional[int] = None, dtype=None):
        self.objective = objective
        self.loader = loader
        self.mesh = loader.mesh
        self.dim = int(dim if dim is not None else loader.source.dim)
        self.dtype = np.dtype(dtype if dtype is not None else loader.dtype)
        self.l2_weight = float(l2_weight)
        self.passes = 0          # completed full evaluations (chaos cursor)
        self._l2_dev = jnp.asarray(self.l2_weight, self.dtype)
        if self.mesh is None:
            self._partial = jit_donating(
                objective.chunk_value_and_gradient, donate_argnums=(0,))
            self._finalize = jax.jit(
                lambda carry, coef, l2: objective.finalize_streamed(
                    carry, coef, Hyper(l2_weight=l2)))
            self._carry_shardings = None
        else:
            self._build_meshed()

    # -- meshed build: shard-local carry, no per-chunk collectives ----------

    def _build_meshed(self):
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from photon_tpu.optim.hier import (
            _num_shards,
            _sample_axes,
            _staged_all_psum,
        )
        from photon_tpu.parallel import mesh as M

        mesh, obj = self.mesh, self.objective
        sample_axes = _sample_axes(mesh)
        self._n_shards = _num_shards(mesh, sample_axes)
        spec_axis = sample_axes if len(sample_axes) > 1 else sample_axes[0]
        cv_spec, cg_spec = P(spec_axis), P(spec_axis, None)
        self._carry_shardings = (NamedSharding(mesh, cv_spec),
                                 NamedSharding(mesh, cg_spec))

        def partial_body(cv, cg, coef, batch):
            # shard-local accumulate: cv [1], cg [1, d] — NO collectives
            # (a mesh trace: the fused kernel carries no sharding)
            with pallas_glm.disabled():
                v, g = obj.chunk_value_and_gradient((cv[0], cg[0]), coef,
                                                    batch)
            return v[None], g[None]

        def finalize_body(cv, cg, coef, l2):
            # the pass's single reduction: one staged ICI-then-DCN psum
            packed = _staged_all_psum(jnp.concatenate([cg[0], cv]), mesh)
            carry = (packed[-1], packed[:-1])
            return obj.finalize_streamed(carry, coef, Hyper(l2_weight=l2))

        def partial(carry, coef, batch):
            specs = jax.tree.map(
                lambda a: P(spec_axis, *([None] * (a.ndim - 1))), batch)
            return M.shard_map(partial_body, mesh=mesh,
                               in_specs=(cv_spec, cg_spec, P(), specs),
                               out_specs=(cv_spec, cg_spec),
                               check_vma=False)(carry[0], carry[1], coef,
                                                batch)

        def finalize(carry, coef, l2):
            return M.shard_map(finalize_body, mesh=mesh,
                               in_specs=(cv_spec, cg_spec, P(), P()),
                               out_specs=(P(), P()),
                               check_vma=False)(carry[0], carry[1], coef, l2)

        self._partial = jit_donating(partial, donate_argnums=(0,))
        self._finalize = jax.jit(finalize)

    # -- carry plumbing -----------------------------------------------------

    def init_carry(self):
        if self.mesh is None:
            return self.objective.init_stream_carry(self.dim, self.dtype)
        cv = np.zeros((self._n_shards,), self.dtype)
        cg = np.zeros((self._n_shards, self.dim), self.dtype)
        return (jax.device_put(cv, self._carry_shardings[0]),
                jax.device_put(cg, self._carry_shardings[1]))

    def carry_to_host(self, carry) -> Tuple[np.ndarray, ...]:
        """Bitwise host snapshot of the carry (checkpoint boundary — the
        ONE deliberate device read outside the pass finalize)."""
        return tuple(np.asarray(leaf) for leaf in carry)

    def restore_carry(self, host_carry):
        if self.mesh is None:
            return tuple(jnp.asarray(leaf, self.dtype)
                         for leaf in host_carry)
        return tuple(jax.device_put(leaf, sh)
                     for leaf, sh in zip(host_carry, self._carry_shardings))

    def _put_coef(self, coef):
        if self.mesh is None:
            return jnp.asarray(coef, self.dtype)
        from photon_tpu.parallel import mesh as M
        return M.replicate(jnp.asarray(coef, self.dtype), self.mesh)

    # -- the streamed evaluation --------------------------------------------

    def value_and_gradient(
        self, coef, *, resume=None,
        on_chunk: Optional[Callable[[int, int, tuple], None]] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """One full streamed pass -> host ``(f, g)``.

        The per-chunk loop is pure async dispatch (no host syncs, no
        collectives on the mesh path); the pass's single host crossing is
        the np.asarray pull of the finalized pair. ``resume=(host_carry,
        next_chunk)`` continues a checkpointed pass mid-stream.
        """
        coef_dev = self._put_coef(coef)
        if resume is not None:
            carry = self.restore_carry(resume.carry)
            start = int(resume.next_chunk)
        else:
            carry = self.init_carry()
            start = 0
        pass_idx = self.passes
        for chunk in self.loader.stream(start_chunk=start):
            carry = self._partial(carry, coef_dev, chunk.batch)
            # zero-copy consumption token: the new carry's readiness
            # implies this chunk's reads are done, freeing its buffer
            self.loader.release(chunk, carry)
            if on_chunk is not None:
                on_chunk(pass_idx, chunk.index, carry)
        f_dev, g_dev = self._finalize(carry, coef_dev, self._l2_dev)
        self.passes = pass_idx + 1
        # pass boundary: the solver's host loop needs scalars — np.asarray
        # here is the single sync of the whole pass, by design
        return np.asarray(f_dev), np.asarray(g_dev)


# =========================================================================
# Chunk-cursor checkpoint (crc-framed npz, atomic publish)
# =========================================================================

_MAGIC = b"PTSTRMC1"
_SCHEMA = 1


def _encode_checkpoint(meta: dict, arrays: dict) -> bytes:
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    body = buf.getvalue()
    meta_b = json.dumps(meta, sort_keys=True).encode()
    return (_MAGIC + struct.pack("<II", zlib.crc32(body), len(meta_b))
            + meta_b + body)


def _decode_checkpoint(blob: bytes) -> Tuple[dict, dict]:
    if blob[:8] != _MAGIC:
        raise ValueError("not a stream checkpoint (bad magic)")
    crc, mlen = struct.unpack("<II", blob[8:16])
    meta = json.loads(blob[16:16 + mlen].decode())
    body = blob[16 + mlen:]
    if zlib.crc32(body) != crc:
        raise ValueError("stream checkpoint payload crc mismatch")
    if meta.get("schema") != _SCHEMA:
        raise ValueError(f"stream checkpoint schema {meta.get('schema')} "
                         f"!= {_SCHEMA}")
    with np.load(io.BytesIO(body), allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files}
    return meta, arrays


def load_stream_checkpoint(path: str) -> Tuple[dict, dict]:
    """(meta, arrays) of a chunk-cursor checkpoint; raises ValueError on
    torn/corrupt files (crc framed)."""
    return _decode_checkpoint(rio.read_bytes(path, op="stream.checkpoint"))


class _Resume(NamedTuple):
    carry: Tuple[np.ndarray, ...]
    next_chunk: int
    eval_x: np.ndarray


class _EvalDriver:
    """Evaluation boundary between the host solver and the streamed
    problem.

    Tracks the current iteration's completed ``(f, g)`` evaluations;
    after a resume it serves them back from the checkpoint cache (bitwise)
    and continues the in-flight evaluation from its chunk cursor. The
    per-chunk checkpoint hook persists: iteration-start solver state +
    completed evals + in-flight carry/cursor — everything iteration
    replay needs to be bitwise identical to the uninterrupted run.
    """

    def __init__(self, problem: StreamedProblem, path: Optional[str],
                 every: int):
        self.problem = problem
        self.path = path
        self.every = int(every or 0)
        self.completed: list = []
        self.serve_idx = 0
        self.iter_arrays: dict = {}
        self.iter_meta: dict = {}
        self.inflight: Optional[_Resume] = None
        self._restored: Optional[Tuple[dict, dict]] = None
        if path and os.path.exists(path):
            self._load()

    def _load(self) -> None:
        meta, arrays = load_stream_checkpoint(self.path)
        self._restored = (meta, arrays)
        self.iter_arrays = {k[3:]: arrays[k] for k in arrays
                            if k.startswith("st_")}
        self.iter_meta = {"mode": meta["mode"], "phase": meta["phase"]}
        self.completed = [(arrays["comp_f"][i], arrays["comp_g"][i])
                          for i in range(int(meta["n_completed"]))]
        self.serve_idx = 0
        carry = tuple(arrays[f"carry_{i}"]
                      for i in range(int(meta["n_carry"])))
        self.inflight = _Resume(carry=carry,
                                next_chunk=int(meta["next_chunk"]),
                                eval_x=arrays["eval_x"])
        self.problem.passes = int(meta["pass_idx"])

    def take_restored(self) -> Optional[Tuple[dict, dict]]:
        r, self._restored = self._restored, None
        return r

    def begin_iteration(self, arrays: dict, meta: dict) -> None:
        """Snapshot the solver state at the top of an iteration. While a
        resumed iteration still has cached evals to serve (or an
        in-flight pass), the restored snapshot stays canonical — the
        caller's freshly re-captured state is bitwise the same anyway."""
        if self.serve_idx < len(self.completed) or self.inflight is not None:
            return
        self.iter_arrays = {k: np.array(v) for k, v in arrays.items()}
        self.iter_meta = dict(meta)
        self.completed = []
        self.serve_idx = 0

    def evaluate(self, x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        if self.serve_idx < len(self.completed):
            f, g = self.completed[self.serve_idx]
            self.serve_idx += 1
            return f, g
        resume = self.inflight
        self.inflight = None
        if resume is not None and not np.array_equal(resume.eval_x, x):
            raise RuntimeError(
                "stream checkpoint resume mismatch: the replayed "
                "iteration requested an evaluation point different from "
                "the checkpointed in-flight one — checkpoint and run "
                "state have diverged")
        hook = None
        if self.path and (self.every > 0 or chaos.is_active()):
            hook = lambda p, c, carry: self._on_chunk(x, p, c, carry)  # noqa: E731
        f, g = self.problem.value_and_gradient(x, resume=resume,
                                               on_chunk=hook)
        self.completed.append((f, g))
        self.serve_idx += 1
        return f, g

    def _on_chunk(self, x, pass_idx: int, chunk_idx: int, carry) -> None:
        kill = chaos.should_kill_stream(pass_idx, chunk_idx)
        cadence = self.every > 0 and (chunk_idx + 1) % self.every == 0
        if not (kill or cadence):
            return
        self._save(x, pass_idx, chunk_idx + 1, carry)
        if kill:
            raise chaos.SimulatedKill(
                f"chaos: killed streamed solve at pass {pass_idx}, "
                f"chunk {chunk_idx} (checkpoint written)")

    def _save(self, eval_x, pass_idx: int, next_chunk: int, carry) -> None:
        arrays = {f"st_{k}": np.asarray(v)
                  for k, v in self.iter_arrays.items()}
        k = len(self.completed)
        if k:
            arrays["comp_f"] = np.stack(
                [np.asarray(f) for f, _ in self.completed])
            arrays["comp_g"] = np.stack(
                [np.asarray(g) for _, g in self.completed])
        else:
            d = int(np.shape(eval_x)[0])
            arrays["comp_f"] = np.zeros((0,), np.float64)
            arrays["comp_g"] = np.zeros((0, d), np.float64)
        host_carry = self.problem.carry_to_host(carry)
        for i, leaf in enumerate(host_carry):
            arrays[f"carry_{i}"] = leaf
        arrays["eval_x"] = np.asarray(eval_x)
        meta = {
            "schema": _SCHEMA,
            "mode": self.iter_meta.get("mode", "lbfgs"),
            "phase": self.iter_meta.get("phase", "loop"),
            "pass_idx": int(pass_idx),
            "next_chunk": int(next_chunk),
            "n_completed": int(k),
            "n_carry": len(host_carry),
        }
        rio.atomic_write_bytes(self.path, _encode_checkpoint(meta, arrays),
                               op="stream.checkpoint")
        try:
            from photon_tpu.obs.metrics import registry
            registry.counter("stream.checkpoints").inc()
        except Exception:   # hygiene-ok — telemetry is best-effort
            pass

    def finish(self) -> None:
        """Solve completed: the cursor checkpoint is obsolete (a leftover
        file would resume a FINISHED solve's final iteration)."""
        if self.path and os.path.exists(self.path):
            try:
                os.remove(self.path)
            except OSError:  # pragma: no cover — best-effort cleanup
                pass


# =========================================================================
# Host-loop solvers (ports of optim/lbfgs.minimize / optim/owlqn.minimize)
# =========================================================================

def _two_loop_host(g, s_hist, y_hist, rho, n_pairs, head, m):
    """Numpy port of lbfgs.two_loop_direction (same visit order)."""
    q = np.array(g)
    alphas = np.zeros(m, q.dtype)
    for j in range(n_pairs):
        idx = (head - 1 - j) % m
        a = rho[idx] * float(np.dot(s_hist[idx], q))
        alphas[idx] = a
        q = q - a * y_hist[idx]
    gamma = 1.0
    if n_pairs > 0:
        last = (head - 1) % m
        yy = float(np.dot(y_hist[last], y_hist[last]))
        if yy > 0:
            gamma = float(np.dot(s_hist[last], y_hist[last])) / yy
    r = gamma * q
    for j in range(n_pairs):
        idx = (head - n_pairs + j) % m
        beta = rho[idx] * float(np.dot(y_hist[idx], r))
        r = r + s_hist[idx] * (alphas[idx] - beta)
    return -r


def _zoom_candidate_host(a_lo, f_lo, d_lo, a_hi, f_hi):
    h = a_hi - a_lo
    denom = 2.0 * (f_hi - f_lo - d_lo * h)
    a_q = a_lo - d_lo * h * h / denom if denom != 0.0 else float("inf")
    mid = a_lo + 0.5 * h
    lo, hi = min(a_lo, a_hi), max(a_lo, a_hi)
    pad = 0.1 * (hi - lo)
    if not np.isfinite(a_q) or a_q <= lo + pad or a_q >= hi - pad:
        return mid
    return a_q


def _wolfe_host(evaluate, x, direction, f0, g0, *, initial_step=1.0,
                c1=1e-4, c2=0.9, max_evals=25, max_step=1e10):
    """Host port of linesearch.wolfe_linesearch: same bracket/zoom state
    machine, same approximate-Wolfe (Hager-Zhang flatness) acceptance,
    same never-uphill accepted-point contract. Returns
    (step, f, g, num_evals, success)."""
    f0 = float(f0)
    d0 = float(np.dot(g0, direction))
    slack = 8.0 * float(np.finfo(x.dtype).eps) * abs(f0)
    stage_bracket = True
    i = 0
    a_next = float(initial_step)
    a_lo, f_lo, d_lo, g_lo = 0.0, f0, d0, g0
    a_hi, f_hi, d_hi = 0.0, f0, d0
    a_prev, f_prev, d_prev, g_prev = 0.0, f0, d0, g0
    a_best, f_best, g_best = 0.0, f0, g0
    success = False
    while True:
        f_arr, g_a = evaluate(x + a_next * direction)
        f_a = float(f_arr)
        d_a = float(np.dot(g_a, direction))
        i += 1
        a = a_next

        if f_a < f_best and np.isfinite(f_a):
            a_best, f_best, g_best = a, f_a, g_a

        armijo_fail = (f_a > f0 + c1 * a * d0) or not np.isfinite(f_a)
        wolfe_ok = abs(d_a) <= -c2 * d0
        approx_conv = ((f_a <= f0 + slack) and (d_a >= c2 * d0)
                       and (d_a <= (2.0 * c1 - 1.0) * d0)
                       and np.isfinite(f_a))
        approx_take = approx_conv and f_a <= f0
        approx_stop = approx_conv and not approx_take

        grow = False
        entering_zoom = False
        if stage_bracket:
            to_zoom1 = armijo_fail or (i > 1 and f_a >= f_prev)
            accept = (not to_zoom1) and wolfe_ok
            to_zoom2 = (not to_zoom1) and (not wolfe_ok) and d_a >= 0
            grow = not (to_zoom1 or accept or to_zoom2)
            entering_zoom = to_zoom1 or to_zoom2
            if to_zoom1:
                n_lo = (a_prev, f_prev, d_prev, g_prev)
                n_hi = (a, f_a, d_a)
            else:
                n_lo = (a, f_a, d_a, g_a)
                n_hi = (a_prev, f_prev, d_prev)
        else:
            shrink_hi = armijo_fail or f_a >= f_lo
            accept = (not shrink_hi) and wolfe_ok
            flip = ((not shrink_hi) and (not wolfe_ok)
                    and d_a * (a_hi - a_lo) >= 0)
            if shrink_hi:
                n_lo = (a_lo, f_lo, d_lo, g_lo)
                n_hi = (a, f_a, d_a)
            else:
                n_lo = (a, f_a, d_a, g_a)
                n_hi = (a_lo, f_lo, d_lo) if flip else (a_hi, f_hi, d_hi)
        accept = accept or approx_take

        a_lo, f_lo, d_lo, g_lo = n_lo
        a_hi, f_hi, d_hi = n_hi

        interval_dead = (entering_zoom or not stage_bracket) and (
            abs(a_hi - a_lo) <= 1e-10 * max(abs(a_hi), 1.0))
        collapse_accept = interval_dead and not accept

        if accept:
            a_best, f_best, g_best = a, f_a, g_a
        elif collapse_accept:
            a_best, f_best, g_best = a_lo, f_lo, g_lo
        success = success or accept or approx_stop

        if accept or collapse_accept or approx_stop or i >= max_evals:
            return a_best, f_best, g_best, i, success

        if stage_bracket and grow:
            a_next = min(2.0 * a, max_step)
        else:
            a_next = _zoom_candidate_host(a_lo, f_lo, d_lo, a_hi, f_hi)
            stage_bracket = False
        a_prev, f_prev, d_prev, g_prev = a, f_a, d_a, g_a


def _nonfinite_code_host(f, g_finite: bool) -> int:
    if np.isfinite(f):
        return int(FailureMode.NONE if g_finite
                   else FailureMode.NON_FINITE_GRADIENT)
    return int(FailureMode.NON_FINITE_LOSS)


def _reason_host(it, f_old, f_new, gnorm, value_tol, gradient_tol,
                 max_iterations, improved) -> int:
    """Host port of base.convergence_reason's priority order."""
    if it >= max_iterations:
        return int(ConvergenceReason.MAX_ITERATIONS)
    if abs(f_old - f_new) <= value_tol and improved:
        return int(ConvergenceReason.FUNCTION_VALUES_CONVERGED)
    if gnorm <= gradient_tol:
        return int(ConvergenceReason.GRADIENT_CONVERGED)
    return int(ConvergenceReason.NOT_CONVERGED)


def _fresh_state(x0: np.ndarray, m: int) -> dict:
    d = x0.shape[0]
    dtype = x0.dtype
    return {
        "x": np.array(x0), "f": np.zeros((), np.float64),
        "g": np.zeros(d, dtype), "pg": np.zeros(d, dtype),
        "s_hist": np.zeros((m, d), dtype), "y_hist": np.zeros((m, d), dtype),
        "rho": np.zeros(m, dtype),
        "n_pairs": np.int32(0), "head": np.int32(0), "it": np.int32(0),
        "n_evals": np.int32(0), "ls_failed": np.bool_(False),
        "nf_count": np.int32(0),
        "reason": np.int32(ConvergenceReason.NOT_CONVERGED),
        "failure": np.int32(FailureMode.NONE),
        "value_tol": np.zeros((), np.float64),
        "gradient_tol": np.zeros((), np.float64),
    }


def _snapshot(S: dict) -> dict:
    return {k: np.array(v) for k, v in S.items()}


def _tolerances_host(f0, g0_norm, rel_tol, dtype) -> Tuple[float, float]:
    tiny = float(np.finfo(dtype).tiny)
    return (rel_tol * max(abs(float(f0)), tiny),
            rel_tol * max(float(g0_norm), tiny))


def _result_from_state(S: dict, dtype, gradient=None) -> SolverResult:
    g = S["g"] if gradient is None else gradient
    return SolverResult(
        coef=jnp.asarray(S["x"], dtype),
        value=jnp.asarray(float(S["f"]), dtype),
        gradient=jnp.asarray(g, dtype),
        iterations=jnp.asarray(int(S["it"]), jnp.int32),
        reason=jnp.asarray(int(S["reason"]), jnp.int32),
        num_fun_evals=jnp.asarray(int(S["n_evals"]), jnp.int32),
        failure=jnp.asarray(int(S["failure"]), jnp.int32),
    )


def minimize_streamed(
    problem: StreamedProblem,
    x0,
    *,
    config: SolverConfig = SolverConfig(),
    l1_weight=0.0,
    checkpoint_path: Optional[str] = None,
    checkpoint_every_chunks: int = 0,
) -> SolverResult:
    """L-BFGS (or OWL-QN when any l1 weight is positive) against a
    ``StreamedProblem``, mirroring optim/lbfgs.minimize /
    optim/owlqn.minimize semantics on a host loop.

    ``checkpoint_path`` + ``checkpoint_every_chunks`` enable the
    chunk-cursor checkpoint: every N accumulated chunks the solver
    persists enough state to resume bitwise after a kill; an existing
    file at the path is resumed from automatically (and removed once the
    solve completes).
    """
    if config.lower_bounds is not None or config.upper_bounds is not None:
        raise ValueError("box constraints are not supported on the "
                         "streamed path (use the resident solver)")
    x0 = np.asarray(x0)
    d = x0.shape[0]
    dtype = x0.dtype
    l1 = np.broadcast_to(np.asarray(l1_weight, dtype), (d,)).copy()
    if config.l1_mask is not None:
        l1 = l1 * np.asarray(config.l1_mask, dtype)
    driver = _EvalDriver(problem, checkpoint_path, checkpoint_every_chunks)
    if bool(np.any(l1 > 0)):
        result = _owlqn_streamed(driver, x0, l1, config)
    else:
        result = _lbfgs_streamed(driver, x0, config)
    driver.finish()
    return result


def _init_or_restore(driver: _EvalDriver, x0: np.ndarray, m: int,
                     mode: str) -> dict:
    restored = driver.take_restored()
    if restored is None:
        S = _fresh_state(x0, m)
        driver.begin_iteration(S, {"mode": mode, "phase": "init"})
        S["_phase"] = "init"
        return S
    meta, _ = restored
    if meta["mode"] != mode:
        raise ValueError(f"checkpoint solver mode {meta['mode']!r} != "
                         f"requested {mode!r}")
    S = {k: np.array(v) for k, v in driver.iter_arrays.items()}
    if S["x"].shape != x0.shape:
        raise ValueError("checkpoint dimension mismatch")
    S["_phase"] = meta["phase"]
    return S


def _lbfgs_streamed(driver: _EvalDriver, x0: np.ndarray,
                    config: SolverConfig) -> SolverResult:
    m = config.num_corrections
    dtype = x0.dtype
    S = _init_or_restore(driver, x0, m, "lbfgs")

    if S.pop("_phase") == "init":
        f0, g0 = driver.evaluate(S["x"])
        vt, gt = _tolerances_host(f0, np.linalg.norm(g0),
                                  config.tolerance, dtype)
        S["f"] = np.float64(float(f0))
        S["g"] = np.asarray(g0)
        S["value_tol"], S["gradient_tol"] = np.float64(vt), np.float64(gt)
        S["n_evals"] = np.int32(1)
        S["reason"] = np.int32(
            ConvergenceReason.GRADIENT_CONVERGED
            if float(np.linalg.norm(g0)) <= gt
            else ConvergenceReason.NOT_CONVERGED)
        S["failure"] = np.int32(_nonfinite_code_host(
            float(f0), bool(np.all(np.isfinite(g0)))))

    while (int(S["reason"]) == ConvergenceReason.NOT_CONVERGED
           and int(S["failure"]) == FailureMode.NONE):
        driver.begin_iteration(S, {"mode": "lbfgs", "phase": "loop"})
        x, f, g = S["x"], float(S["f"]), S["g"]
        n_pairs, head = int(S["n_pairs"]), int(S["head"])

        direction = _two_loop_host(g, S["s_hist"], S["y_hist"], S["rho"],
                                   n_pairs, head, m)
        if not float(np.dot(direction, g)) < 0:
            direction = -g
        gnorm = float(np.linalg.norm(g))
        init_step = (min(1.0, 1.0 / max(gnorm, 1e-12))
                     if n_pairs == 0 else 1.0)

        step, f_new, g_new, ls_evals, _ok = _wolfe_host(
            driver.evaluate, x, direction, f, g, initial_step=init_step,
            max_evals=config.linesearch_max_iterations)
        x_new = x + step * direction

        g_finite = bool(np.all(np.isfinite(g_new)))
        finite = bool(np.isfinite(f_new)) and g_finite
        decreased = finite and (f_new < f)
        if not decreased:        # reject non-decreasing steps entirely
            x_new, f_kept, g_kept = x, f, g
        else:
            f_kept, g_kept = f_new, g_new

        s = x_new - x
        yv = g_kept - g
        sy = float(np.dot(s, yv))
        store = decreased and sy > 1e-10 * max(float(np.dot(yv, yv)), 1e-30)
        if store:
            w = head % m
            S["s_hist"][w] = s
            S["y_hist"][w] = yv
            S["rho"][w] = 1.0 / sy
            S["head"] = np.int32((head + 1) % m)
            S["n_pairs"] = np.int32(min(n_pairs + 1, m))

        it = int(S["it"]) + 1
        reason = _reason_host(it, f, f_kept, float(np.linalg.norm(g_kept)),
                              float(S["value_tol"]),
                              float(S["gradient_tol"]),
                              config.max_iterations, decreased)
        if (reason == ConvergenceReason.NOT_CONVERGED
                and not decreased and bool(S["ls_failed"])):
            reason = int(ConvergenceReason.OBJECTIVE_NOT_IMPROVING)
        nf_count = 0 if finite else int(S["nf_count"]) + 1
        failure = (_nonfinite_code_host(f_new, g_finite)
                   if nf_count >= 2 else int(FailureMode.NONE))
        if failure != FailureMode.NONE:
            reason = int(ConvergenceReason.OBJECTIVE_NOT_IMPROVING)

        S["x"] = x_new
        S["f"] = np.float64(f_kept)
        S["g"] = np.asarray(g_kept)
        S["it"] = np.int32(it)
        S["reason"] = np.int32(reason)
        S["n_evals"] = np.int32(int(S["n_evals"]) + ls_evals)
        S["ls_failed"] = np.bool_(not decreased)
        S["nf_count"] = np.int32(nf_count)
        S["failure"] = np.int32(failure)

    return _result_from_state(S, dtype)


def _pseudo_gradient_host(x, g, l1):
    right = g + l1
    left = g - l1
    pg_zero = np.where(right < 0, right, np.where(left > 0, left, 0.0))
    return np.where(x > 0, right, np.where(x < 0, left, pg_zero))


def _project_orthant_host(x, orthant):
    return np.where(x * orthant > 0, x, 0.0)


def _owlqn_streamed(driver: _EvalDriver, x0: np.ndarray, l1: np.ndarray,
                    config: SolverConfig, c1: float = 1e-4) -> SolverResult:
    m = config.num_corrections
    dtype = x0.dtype
    eps = float(np.finfo(dtype).eps)
    S = _init_or_restore(driver, x0, m, "owlqn")

    def full_value(x, fx):
        return float(fx) + float(np.sum(l1 * np.abs(x)))

    if S.pop("_phase") == "init":
        f0s, g0 = driver.evaluate(S["x"])
        f0 = full_value(S["x"], f0s)
        pg0 = _pseudo_gradient_host(S["x"], np.asarray(g0), l1)
        vt, gt = _tolerances_host(f0, np.linalg.norm(pg0),
                                  config.tolerance, dtype)
        S["f"] = np.float64(f0)
        S["g"] = np.asarray(g0)
        S["pg"] = pg0
        S["value_tol"], S["gradient_tol"] = np.float64(vt), np.float64(gt)
        S["n_evals"] = np.int32(1)
        S["reason"] = np.int32(
            ConvergenceReason.GRADIENT_CONVERGED
            if float(np.linalg.norm(pg0)) <= gt
            else ConvergenceReason.NOT_CONVERGED)
        S["failure"] = np.int32(_nonfinite_code_host(
            float(f0), bool(np.all(np.isfinite(g0)))))

    while (int(S["reason"]) == ConvergenceReason.NOT_CONVERGED
           and int(S["failure"]) == FailureMode.NONE):
        driver.begin_iteration(S, {"mode": "owlqn", "phase": "loop"})
        x, f, g, pg = S["x"], float(S["f"]), S["g"], S["pg"]
        n_pairs, head = int(S["n_pairs"]), int(S["head"])

        direction = _two_loop_host(pg, S["s_hist"], S["y_hist"], S["rho"],
                                   n_pairs, head, m)
        direction = np.where(direction * (-pg) > 0, direction, 0.0)
        if not float(np.dot(direction, pg)) < 0:
            direction = -pg

        orthant = np.where(x != 0, np.sign(x), np.sign(-pg))
        pgnorm = float(np.linalg.norm(pg))
        step0 = (min(1.0, 1.0 / max(pgnorm, 1e-12))
                 if n_pairs == 0 else 1.0)
        slack = 8.0 * eps * abs(f)

        # orthant-projected backtracking Armijo with the same flat-exit
        # guard as owlqn.minimize's ls_body
        alpha = step0
        f_new, x_new, g_new = f, x, g
        k, ok = 0, False
        while k < config.linesearch_max_iterations:
            if k > 0:
                alpha *= 0.5
            x_new = _project_orthant_host(x + alpha * direction, orthant)
            f_s, g_new = driver.evaluate(x_new)
            f_new = full_value(x_new, f_s)
            k += 1
            ok = f_new <= f + c1 * float(np.dot(pg, x_new - x))
            if ok or (k >= 2 and abs(f_new - f) <= slack):
                break

        g_new = np.asarray(g_new)
        g_fin = bool(np.all(np.isfinite(g_new)))
        fin = bool(np.isfinite(f_new)) and g_fin
        failure = (int(FailureMode.NONE) if fin
                   else _nonfinite_code_host(f_new, g_fin))
        decreased = ok and (f_new < f) and fin
        if decreased:
            x_kept, f_kept, g_kept = x_new, f_new, g_new
        else:
            x_kept, f_kept, g_kept = x, f, g
        pg_new = _pseudo_gradient_host(x_kept, g_kept, l1)

        s = x_kept - x
        yv = g_kept - g
        sy = float(np.dot(s, yv))
        store = decreased and sy > 1e-10 * max(float(np.dot(yv, yv)), 1e-30)
        if store:
            w = head % m
            S["s_hist"][w] = s
            S["y_hist"][w] = yv
            S["rho"][w] = 1.0 / sy
            S["head"] = np.int32((head + 1) % m)
            S["n_pairs"] = np.int32(min(n_pairs + 1, m))

        it = int(S["it"]) + 1
        reason = _reason_host(it, f, f_kept, float(np.linalg.norm(pg_new)),
                              float(S["value_tol"]),
                              float(S["gradient_tol"]),
                              config.max_iterations, decreased)
        if reason == ConvergenceReason.NOT_CONVERGED and not decreased:
            reason = int(ConvergenceReason.OBJECTIVE_NOT_IMPROVING)
        if failure != FailureMode.NONE:
            reason = int(ConvergenceReason.OBJECTIVE_NOT_IMPROVING)

        S["x"] = np.asarray(x_kept)
        S["f"] = np.float64(f_kept)
        S["g"] = np.asarray(g_kept)
        S["pg"] = pg_new
        S["it"] = np.int32(it)
        S["reason"] = np.int32(reason)
        S["n_evals"] = np.int32(int(S["n_evals"]) + k)
        S["failure"] = np.int32(failure)

    return _result_from_state(S, dtype, gradient=S["pg"])
