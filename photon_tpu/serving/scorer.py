"""Compiled batch scorers for the serving engine.

One jitted program per (shape-signature, mode, bucket): EVERY model
parameter — the fixed-effect theta vectors and the random-effect gather
tables alike — is a program *argument*, never a closure. Tables had to
be arguments from the start, because the two-tier coefficient store
(serving/coeff_store.py) replaces a coordinate's hot table object on
every cold->hot transfer (the donated scatter produces a new array);
same-shape/dtype arguments re-dispatch the cached executable with zero
retraces, where a closure would either go stale or force a steady-state
recompile. The fixed-effect thetas now ride the same donation-safe
calling convention, which removes the last model-specific bake-in: the
jitcache key is ``model.shape_signature()`` (feature pads, theta
shapes, RE table shapes, dtypes, int8, mesh) instead of
``model.token``, so N same-shape tenants share ONE compiled bucket
ladder — tenant #2..N warm at near-zero compile cost, and a failed-over
replica can reuse an AOT-exported program bundle (serving/programs.py).

The math is the offline ``game/scoring.GameScorer`` expressions verbatim
— fixed effects as a gathered dot over padded (index, value) pairs,
random effects as an entity-row gather followed by a slot-aligned dot —
which is what makes serving-vs-offline parity exact rather than
approximate.

Two optional hot-path arms layer on top of the same programs:

* ``PHOTON_TPU_PALLAS_SERVING=1`` routes the fixed-effect margins
  through the fused gather+margin Pallas kernel
  (ops/pallas_glm.fused_gather_margin): every fixed shard's padded
  slots concatenate against one coefficient vector, so the whole
  fixed-effect term is ONE single-HBM-pass kernel per batch instead of
  a gather + multiply + reduce per shard. Read at program-build time;
  refusals fall back to the XLA expressions and tick
  ``kernels.xla_fallbacks{path="serving"}``.
* ``ServingConfig.int8_serving`` adds a third mode, ``"full_int8"``:
  full-resident random-effect tables arrive as (int8 rows, per-row f32
  scales) pairs and dequantize inside the gather — half the
  random-effect HBM bytes. The mode is warmed alongside the others and
  guarded by the swap ladder's int8 shadow gate (serving/swap.py).

Programs are shared through ``utils/jitcache`` so every bucket compiles
once per process; ``warmup_scorers`` dispatches each (mode, bucket)
program on dummy inputs inside ``compile_cache.warmup`` so the full
ladder is compiled at model-load time and steady-state traffic never
traces.

Inside every program the table look-ups run under ``jax.named_scope``
``serve/gather`` and the multiply-and-sum under ``serve/score`` (PERF.md §3;
the names are what a device trace's seconds are grouped by, and are an
interface).
"""

from __future__ import annotations

import os
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

from photon_tpu.serving.model_state import DeviceResidentModel
from photon_tpu.utils import compile_cache, jitcache

#: scoring modes; "fixed_only" is the SLO-shed ladder (random-effect
#: gathers skipped) and is warmed alongside "full" so entering shed mode
#: under load never triggers a compile
MODES = ("full", "fixed_only")

#: the opt-in quantized arm — only valid (and only warmed) for models
#: built with int8=True; its tables argument is
#: ``model.current_tables_int8()``
INT8_MODE = "full_int8"

#: the opt-in Thompson-sampling arm — only valid (and only warmed) for
#: models staged with thompson=True over posterior variances. Each
#: request row samples ``theta ~ N(mu, sigma^2)`` INSIDE the program
#: from its (seed_hi, seed_lo) counter pair: a murmur3-finalizer hash of
#: (request seed, coordinate tag, coefficient identity) feeds Box-Muller
#: so one coefficient gets ONE normal draw per request, duplicate
#: features agree, and a replay with the same seeds is bitwise. Extra
#: arguments beyond "full": seed_hi/seed_lo [B] uint32, the variance
#: mirrors (``current_var_thetas``/``current_var_tables``).
THOMPSON_MODE = "thompson"


def serving_modes(model: DeviceResidentModel) -> Tuple[str, ...]:
    """The modes this model warms and may dispatch: the base ladder,
    plus the int8 arm when the model carries quantized tables, plus the
    thompson arm when it carries posterior-variance mirrors."""
    modes = MODES
    if getattr(model, "int8_enabled", False):
        modes = modes + (INT8_MODE,)
    if getattr(model, "thompson_enabled", False):
        modes = modes + (THOMPSON_MODE,)
    return modes


_WARNED_REFUSED = False


def _warn_kernel_refused() -> None:
    """Warn ONCE when PHOTON_TPU_PALLAS_SERVING=1 asked for the fused
    gather+margin kernel and the scorer's shapes were refused — a silent
    downgrade the counters record and this makes audible."""
    global _WARNED_REFUSED
    if _WARNED_REFUSED:
        return
    _WARNED_REFUSED = True
    import warnings
    warnings.warn(
        "PHOTON_TPU_PALLAS_SERVING=1 requested the fused Pallas "
        "gather+margin kernel but the scorer's operands were refused "
        "(dtype/mesh/dimension gate); falling back to the XLA "
        "expressions. kernels.xla_fallbacks{path=serving} counts these.",
        RuntimeWarning, stacklevel=3)


def _fused_fixed_margin(mesh_local: bool, dtype, theta_dims, theta_dtypes,
                        fixed_pos, k_total: int):
    """Build-time routing for the fixed-effect term: returns a
    ``fn(fixed_idx, fixed_val, offsets, thetas) -> [B]`` using the fused
    Pallas gather+margin kernel when the env flag asks for it and the
    shapes qualify, else None (XLA expressions). Routing runs on static
    shape facts only (so the decision is a pure function of the scorer's
    shape key); the theta concatenation happens inside the trace, since
    thetas are now program arguments. Counted per compiled program into
    ``kernels.pallas_hits`` / ``kernels.xla_fallbacks`` with
    ``path="serving"`` — same telemetry contract as the training
    kernels (ops/aggregators.py)."""
    if os.environ.get("PHOTON_TPU_PALLAS_SERVING") != "1":
        return None
    import jax.numpy as jnp

    from photon_tpu.ops import pallas_glm
    from photon_tpu.ops.aggregators import _kernel_counter

    ok = (mesh_local and dtype == jnp.float32
          and len(theta_dims) > 0
          and all(dt == "float32" for dt in theta_dtypes)
          and sum(theta_dims) <= pallas_glm._MAX_SPARSE_DIM
          and k_total >= 1
          and not pallas_glm._TRACE_DISABLED.get())
    if not ok:
        _kernel_counter("xla_fallbacks", "serving")
        if not pallas_glm._TRACE_DISABLED.get():
            _warn_kernel_refused()
        return None
    _kernel_counter("pallas_hits", "serving")
    col_off = [0]
    for d in theta_dims[:-1]:
        col_off.append(col_off[-1] + d)

    def fn(fixed_idx, fixed_val, offsets, thetas):
        theta_all = jnp.concatenate(
            [t.astype(jnp.float32) for t in thetas])
        idx = jnp.concatenate(
            [fixed_idx[p] + col_off[j] for j, p in enumerate(fixed_pos)],
            axis=1)
        val = jnp.concatenate([fixed_val[p] for p in fixed_pos], axis=1)
        return pallas_glm.fused_gather_margin(
            idx, val, offsets, theta_all)

    return fn


def program_key(model: DeviceResidentModel, mode: str,
                bucket: int) -> tuple:
    """The jitcache key one (mode, bucket) scorer program lives under —
    shape-generic: equal for any model with the same
    ``shape_signature()``, so same-shape tenants resolve to one compiled
    program. The Pallas env flag is part of the key because it is read
    at build time and changes the traced computation."""
    return ("serving_scorer", mode, int(bucket), model.shape_signature(),
            os.environ.get("PHOTON_TPU_PALLAS_SERVING") == "1")


def build_scorer_fn(model: DeviceResidentModel, mode: str,
                    bucket: int) -> Callable:
    """Build a FRESH jitted scorer for (mode, bucket) — uncached. Normal
    callers want ``get_scorer`` (the process-wide shape-keyed cache);
    this entry exists for the AOT bundle exporter, which needs a
    lowerable jit function even when the cache slot holds a deserialized
    executable (a ``Compiled`` cannot be re-lowered or re-serialized)."""
    if mode not in serving_modes(model):
        raise ValueError(f"unknown serving mode {mode!r}")

    # static shape facts only — the builder must NOT capture the model
    # (a closure would pin every retired tenant's device arrays into the
    # process-wide cache for the program's lifetime)
    dtype = model.dtype
    mesh_local = model.mesh is None
    shard_pos = {sid: i for i, sid in enumerate(model.shard_order)}
    fixed_pos = tuple(shard_pos[f.feature_shard_id] for f in model.fixed)
    theta_dims = tuple(int(f.theta.shape[0]) for f in model.fixed)
    theta_dtypes = tuple(str(f.theta.dtype) for f in model.fixed)
    k_total = sum(int(model.shard_pad[model.shard_order[p]])
                  for p in fixed_pos)

    def builder():
        import jax
        import jax.numpy as jnp

        if mode == THOMPSON_MODE:
            # in-program posterior sampling. Randomness is a counter
            # hash, not a PRNG object: murmur3's 32-bit finalizer over
            # (per-request seed halves, a per-coordinate tag, the
            # coefficient's identity) yields the two uniforms Box-Muller
            # turns into ONE standard normal per (request, coefficient).
            # Keying on the coefficient identity (global column for
            # fixed effects, (entity row, slot) for random effects)
            # makes duplicate features sample the same theta-tilde draw
            # — this is sampling the PARAMETER, not per-slot noise — and
            # pad slots contribute nothing because their values are
            # zero. Everything is uint32/f32 inside the jit, so the
            # program runs without x64 and replays bitwise.
            M1 = jnp.uint32(0x85EBCA6B)
            M2 = jnp.uint32(0xC2B2AE35)
            S16, S13 = jnp.uint32(16), jnp.uint32(13)
            GOLD = jnp.uint32(0x9E3779B9)
            TWO_PI = 6.283185307179586
            INV_2_32 = 1.0 / 4294967296.0

            def _mix(x):
                x = x ^ (x >> S16)
                x = x * M1
                x = x ^ (x >> S13)
                x = x * M2
                return x ^ (x >> S16)

            @jax.jit
            def fn(fixed_idx, fixed_val, re_sidx, re_sval, re_ent,
                   offsets, seed_hi, seed_lo, thetas, var_thetas,
                   re_tables, re_var_tables):
                sh = seed_hi.astype(jnp.uint32)[:, None]
                sl = seed_lo.astype(jnp.uint32)[:, None]

                def z_normal(key, tag):
                    # key [B, P] uint32: coefficient identity
                    k = _mix(key ^ _mix(jnp.uint32(tag) ^ sl))
                    k = _mix(k ^ sh)
                    k2 = _mix(k ^ GOLD)
                    # +0.5 keeps both uniforms strictly inside (0, 1]
                    # after the f32 round, so log/sqrt never see 0
                    u1 = (k.astype(dtype) + 0.5) * INV_2_32
                    u2 = (k2.astype(dtype) + 0.5) * INV_2_32
                    return (jnp.sqrt(-2.0 * jnp.log(u1))
                            * jnp.cos(TWO_PI * u2))

                total = offsets.astype(dtype)
                for j, pos in enumerate(fixed_pos):
                    idx = fixed_idx[pos]
                    val = fixed_val[pos].astype(dtype)
                    with jax.named_scope("serve/gather"):
                        theta = thetas[j][idx].astype(dtype)
                        sigma = jnp.sqrt(var_thetas[j][idx].astype(dtype))
                    with jax.named_scope("serve/score"):
                        z = z_normal(idx.astype(jnp.uint32), 2 * j + 1)
                        total = total + jnp.sum(
                            val * (theta + sigma * z), axis=-1)
                for j, (coef, vcoef, sidx, sval, ent) in enumerate(
                        zip(re_tables, re_var_tables, re_sidx,
                            re_sval, re_ent)):
                    with jax.named_scope("serve/gather"):
                        rows = coef.at[ent].get(mode="fill", fill_value=0.0)
                        vrows = vcoef.at[ent].get(mode="fill",
                                                  fill_value=0.0)
                        mu = jnp.take_along_axis(
                            rows, sidx, axis=1).astype(dtype)
                        sigma = jnp.sqrt(jnp.take_along_axis(
                            vrows, sidx, axis=1).astype(dtype))
                    with jax.named_scope("serve/score"):
                        key = (_mix(ent.astype(jnp.uint32)[:, None])
                               ^ sidx.astype(jnp.uint32))
                        z = z_normal(key, 2 * j + 2)
                        total = total + jnp.sum(
                            sval.astype(dtype) * (mu + sigma * z), axis=-1)
                return total

            return fn

        with_random = mode != "fixed_only"
        fused_fixed = _fused_fixed_margin(
            mesh_local, dtype, theta_dims, theta_dtypes, fixed_pos, k_total)

        @jax.jit
        def fn(fixed_idx, fixed_val, re_sidx, re_sval, re_ent, offsets,
               thetas, re_tables):
            if fused_fixed is not None:
                with jax.named_scope("serve/score"):
                    total = fused_fixed(fixed_idx, fixed_val, offsets,
                                        thetas).astype(dtype)
            else:
                total = offsets.astype(dtype)
                for theta, pos in zip(thetas, fixed_pos):
                    # ops/features.matvec on the padded ELL layout: pad
                    # slots are (0, 0.0) so they contribute nothing
                    with jax.named_scope("serve/gather"):
                        picked = theta[fixed_idx[pos]]
                    with jax.named_scope("serve/score"):
                        total = total + jnp.sum(
                            fixed_val[pos].astype(dtype) * picked, axis=-1)
            if with_random:
                for coef, sidx, sval, ent in zip(re_tables, re_sidx,
                                                 re_sval, re_ent):
                    with jax.named_scope("serve/gather"):
                        if isinstance(coef, tuple):
                            # int8 arm: (quantized rows, per-row scales) —
                            # gather both and dequantize in-register; the
                            # unknown/zero rows quantize to (0, scale 1.0)
                            # so they still contribute exactly nothing
                            q, s = coef
                            rows = (q.at[ent].get(mode="fill", fill_value=0)
                                    .astype(dtype)
                                    * s.at[ent].get(mode="fill",
                                                    fill_value=0.0))
                        else:
                            rows = coef.at[ent].get(mode="fill",
                                                    fill_value=0.0)
                        picked = jnp.take_along_axis(rows, sidx, axis=1)
                    with jax.named_scope("serve/score"):
                        total = total + jnp.sum(
                            sval.astype(dtype) * picked, axis=-1)
            return total

        return fn

    return builder()


def get_scorer(model: DeviceResidentModel, mode: str,
               bucket: int) -> Callable:
    """Compiled scorer for one (shape-signature, mode, bucket); cached
    process-wide and shared by every same-shape model.

    Call as ``fn(*args, thetas, re_tables)`` where ``args`` is the
    assemble output, ``thetas`` is ``model.current_thetas()`` and
    ``re_tables`` is ``model.current_tables()`` — or
    ``model.current_tables_int8()`` for the "full_int8" mode — read
    inside the same ``model.transfer_lock`` hold as the assemble (the
    two-tier store's consistency contract). ``dispatch`` wraps the
    whole convention.
    """
    key = program_key(model, mode, bucket)
    return jitcache.get_or_build(
        key, lambda: build_scorer_fn(model, mode, bucket))


def tables_for_mode(model: DeviceResidentModel, mode: str) -> tuple:
    """The re_tables argument matching ``mode`` — int8 pairs for the
    quantized arm, f32 tables otherwise. Same lock contract as
    ``current_tables``."""
    if mode == INT8_MODE:
        return model.current_tables_int8()
    return model.current_tables()


def mode_args(model: DeviceResidentModel, mode: str, args,
              seeds: Optional[tuple] = None) -> tuple:
    """The FULL positional argument tuple for one (mode, batch): the
    assemble output plus the mode's parameter arguments, in program
    order. ``seeds`` is the thompson arm's (seed_hi, seed_lo) uint32
    pair; None falls back to all-zero seeds of the batch width (warmup /
    AOT lowering — shape-correct, values irrelevant). Same transfer_lock
    contract as ``current_tables``."""
    if mode == THOMPSON_MODE:
        if seeds is None:
            z = np.zeros(args[5].shape[0], np.uint32)
            seeds = (z, z)
        return args + (seeds[0], seeds[1], model.current_thetas(),
                       model.current_var_thetas(), model.current_tables(),
                       model.current_var_tables())
    return args + (model.current_thetas(), tables_for_mode(model, mode))


def dispatch(model: DeviceResidentModel, mode: str, bucket: int, args,
             seeds: Optional[tuple] = None):
    """One scorer call with the model's current parameter arguments
    appended — the full calling convention in one place. Caller holds
    ``model.transfer_lock`` around assemble + this call (two-tier
    consistency)."""
    return get_scorer(model, mode, bucket)(
        *mode_args(model, mode, args, seeds))


def warmup_scorers(model: DeviceResidentModel,
                   buckets: Sequence[int]) -> int:
    """Compile-and-dispatch every (mode, bucket) program under the warmup
    phase flag. Returns the number of programs warmed (dispatched) — for
    tenant #2..N of a shape, each dispatch is a jitcache hit and warms
    at zero compile cost."""
    warmed = 0
    modes = serving_modes(model)

    def one_bucket(bucket):
        nonlocal warmed
        args = model.dummy_args(bucket)
        for mode in modes:
            out = dispatch(model, mode, bucket, args)
            out.block_until_ready()  # host-sync-ok: warmup only
            warmed += 1

    compile_cache.warmup(buckets, one_bucket)
    return warmed
