"""AOT-exported serving program bundles: instant cold start.

A serving replica's cold start is compile-dominated: the warmup ladder
traces and XLA-compiles one program per (mode, bucket). The persistent
XLA cache (utils/compile_cache) removes the *compile* on a restart but
still pays the trace + lowering per program. This module removes both:
after warmup, ``export_program_bundle`` lowers each warmed scorer with
``jax.jit(...).lower().compile()`` and serializes the executables
(jax.experimental.serialize_executable) into a crc32-verified bundle
directory next to the model; on the next boot — same host, same model
shapes, same jax — ``load_program_bundle`` deserializes them and seeds
``utils/jitcache`` under the exact shape-generic keys ``get_scorer``
computes, so the warmup ladder performs ZERO traces and ZERO compiles
(all three compile monitors read zero) and the replica reaches
first-score in deserialization time.

Refusal is typed and total: any mismatch (schema, shape signature, jax
version, host fingerprint, Pallas env, crc of any program file) or any
deserialization error refuses the WHOLE bundle — counted under
``serving.program_bundle_refused{reason=...}`` — and the caller falls
back to the ordinary tracing warmup. A corrupt bundle can cost a
re-trace, never a wrong score: executables only enter the process when
every byte checks out, and the shape signature pins them to models
whose programs would have traced identically.

Same manifest discipline as the swap/fleet dirs (serving/swap.py,
io/fleet_store.py): versioned schema string, per-file crc32, atomic
manifest-last write order.
"""

from __future__ import annotations

import json
import logging
import os
import pickle
import tempfile
import zlib
from typing import Optional, Sequence

from photon_tpu.obs.metrics import registry as _metrics
from photon_tpu.serving.model_state import DeviceResidentModel
from photon_tpu.serving.scorer import (build_scorer_fn, get_scorer,
                                       mode_args, program_key,
                                       serving_modes)
from photon_tpu.utils import jitcache

_logger = logging.getLogger("photon_tpu.serving.programs")

BUNDLE_SCHEMA = "photon_tpu.programbundle.v1"
MANIFEST_NAME = "bundle-manifest.json"


def _refuse(reason: str, detail: str = "") -> dict:
    _metrics.counter("serving.program_bundle_refused", reason=reason).inc()
    _logger.warning("program bundle refused (%s): %s — falling back to "
                    "tracing warmup", reason, detail)
    return {"loaded": 0, "refused": reason, "detail": detail}


def _host_fingerprint() -> str:
    """Short token for (machine, CPU features): XLA's AOT loader will load
    an executable compiled for a different feature set with only a warning
    ('could lead to ... SIGILL'), so a bundle records the host it was
    built on."""
    import hashlib
    import platform

    bits = [platform.machine(), platform.processor() or ""]
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    bits.append(" ".join(sorted(line.split()[2:])))
                    break
    except OSError:
        pass
    return hashlib.sha256("|".join(bits).encode()).hexdigest()[:12]


def _jax_fingerprint() -> dict:
    """Everything an executable is pinned to besides model shapes: jax
    version, backend, device count, and the host CPU-feature fingerprint
    (XLA loads foreign-host executables with only a SIGILL warning)."""
    import jax

    return {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "host": _host_fingerprint(),
        "pallas_serving": os.environ.get("PHOTON_TPU_PALLAS_SERVING") == "1",
    }


def _signature_token(model: DeviceResidentModel) -> str:
    return repr(model.shape_signature())


def _prog_name(mode: str, bucket: int) -> str:
    return f"prog-{mode}-b{int(bucket)}.bin"


def bundle_dir_for(base_dir: str, model: DeviceResidentModel) -> str:
    """One bundle subdirectory per distinct shape signature — same-shape
    tenants naturally share one exported ladder, different shapes get
    their own without colliding."""
    tok = _signature_token(model)
    return os.path.join(base_dir, f"sig-{zlib.crc32(tok.encode()):08x}")


def _unwrap(fn):
    """Reach the jit function under the telemetry first-call timer. A
    jit fn itself carries ``__wrapped__`` (the plain python fn), so test
    for the AOT API instead of unwrapping unconditionally."""
    if hasattr(fn, "lower"):
        return fn
    return getattr(fn, "__wrapped__", fn)


def export_program_bundle(model: DeviceResidentModel,
                          buckets: Sequence[int],
                          bundle_dir: str) -> dict:
    """AOT-compile and serialize the full warmed (mode × bucket) ladder
    into ``bundle_dir``. Call after ``warmup_scorers`` (the jit programs
    must exist; with the persistent XLA cache on, the AOT re-compile
    below is a disk hit, not a second XLA compile). Never raises: a
    program that refuses to serialize (e.g. the Pallas arm) skips the
    export and reports itself in the returned dict."""
    from jax.experimental.serialize_executable import serialize

    os.makedirs(bundle_dir, exist_ok=True)
    programs = {}
    skipped = []
    for bucket in buckets:
        args = model.dummy_args(bucket)
        for mode in serving_modes(model):
            fn = _unwrap(get_scorer(model, mode, bucket))
            if not hasattr(fn, "lower"):
                # the cache slot holds a bundle-seeded Compiled, which
                # can be executed but not re-lowered or re-serialized
                # (XLA drops the symbol table) — trace a fresh jit for
                # the export; serving keeps using the seeded executable
                fn = build_scorer_fn(model, mode, bucket)
            name = _prog_name(mode, bucket)
            try:
                compiled = fn.lower(
                    *mode_args(model, mode, args)).compile()
                payload, in_tree, out_tree = serialize(compiled)
                blob = pickle.dumps((payload, in_tree, out_tree),
                                    protocol=pickle.HIGHEST_PROTOCOL)
            except Exception as e:  # noqa: BLE001 — export is an optimization
                skipped.append({"mode": mode, "bucket": int(bucket),
                                "error": repr(e)})
                _logger.warning("program bundle: skipping (%s, b%d): %r",
                                mode, bucket, e)
                continue
            with open(os.path.join(bundle_dir, name), "wb") as f:
                f.write(blob)
            programs[name] = {"mode": mode, "bucket": int(bucket),
                              "crc32": zlib.crc32(blob),
                              "bytes": len(blob)}
    manifest = {
        "schema": BUNDLE_SCHEMA,
        "signature": _signature_token(model),
        "env": _jax_fingerprint(),
        "buckets": [int(b) for b in buckets],
        "modes": list(serving_modes(model)),
        "programs": programs,
    }
    # manifest written last, atomically: a crash mid-export leaves a
    # manifest-less (hence refused) directory, never a half-trusted one
    fd, tmp = tempfile.mkstemp(dir=bundle_dir, suffix=".tmp")
    with os.fdopen(fd, "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
    os.replace(tmp, os.path.join(bundle_dir, MANIFEST_NAME))
    _metrics.gauge("serving.program_bundle_programs").set(len(programs))
    _logger.info("program bundle: exported %d programs (%d skipped) to %s",
                 len(programs), len(skipped), bundle_dir)
    return {"exported": len(programs), "skipped": skipped,
            "dir": bundle_dir}


def load_program_bundle(model: DeviceResidentModel,
                        buckets: Sequence[int],
                        bundle_dir: str) -> dict:
    """Verify and load a program bundle, seeding ``utils/jitcache`` so
    the subsequent warmup ladder dispatches without tracing. All-or-
    nothing: every expected (mode, bucket) must be present, byte-exact,
    and deserializable, or the whole bundle is refused and the caller
    warms by tracing."""
    manifest_path = os.path.join(bundle_dir, MANIFEST_NAME)
    if not os.path.exists(manifest_path):
        return _refuse("missing_manifest", bundle_dir)
    try:
        with open(manifest_path) as f:
            manifest = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return _refuse("unreadable_manifest", repr(e))
    if manifest.get("schema") != BUNDLE_SCHEMA:
        return _refuse("schema_mismatch", str(manifest.get("schema")))
    if manifest.get("signature") != _signature_token(model):
        return _refuse("signature_mismatch",
                       "model shapes differ from exported bundle")
    if manifest.get("env") != _jax_fingerprint():
        return _refuse("env_mismatch",
                       f"bundle env {manifest.get('env')}")
    if list(manifest.get("buckets", [])) != [int(b) for b in buckets]:
        return _refuse("bucket_mismatch", str(manifest.get("buckets")))
    if list(manifest.get("modes", [])) != list(serving_modes(model)):
        return _refuse("mode_mismatch", str(manifest.get("modes")))

    from jax.experimental.serialize_executable import deserialize_and_load

    # pass 1: verify every byte before ANY executable enters the process
    blobs = {}
    for bucket in buckets:
        for mode in serving_modes(model):
            name = _prog_name(mode, bucket)
            meta = manifest["programs"].get(name)
            if meta is None:
                return _refuse("missing_program", name)
            try:
                with open(os.path.join(bundle_dir, name), "rb") as f:
                    blob = f.read()
            except OSError as e:
                return _refuse("unreadable_program", f"{name}: {e!r}")
            if len(blob) != meta["bytes"] or \
                    zlib.crc32(blob) != meta["crc32"]:
                return _refuse("crc_mismatch", name)
            blobs[name] = blob

    # pass 2: deserialize + seed; any failure still refuses the bundle
    # (seeded keys from earlier iterations are evicted — all-or-nothing)
    seeded = []
    for bucket in buckets:
        for mode in serving_modes(model):
            name = _prog_name(mode, bucket)
            try:
                payload, in_tree, out_tree = pickle.loads(blobs[name])
                loaded = deserialize_and_load(payload, in_tree, out_tree)
            except Exception as e:  # noqa: BLE001 — refusal, not a crash
                _evict(seeded)
                return _refuse("deserialize_error", f"{name}: {e!r}")
            key = program_key(model, mode, bucket)
            if jitcache.seed(key, loaded):
                seeded.append(key)
    _metrics.gauge("serving.program_bundle_programs").set(len(seeded))
    _logger.info("program bundle: seeded %d programs from %s",
                 len(seeded), bundle_dir)
    return {"loaded": len(seeded), "refused": None, "dir": bundle_dir}


def _evict(keys) -> None:
    with jitcache._LOCK:
        for k in keys:
            jitcache._CACHE.pop(k, None)
        _metrics.gauge("jitcache.size").set(len(jitcache._CACHE))
