"""Mid-training checkpoint/resume for coordinate descent.

SURVEY §5.3: the reference delegates failure recovery to Spark lineage
(recompute lost partitions deterministically); the TPU-native answer is a
sweep-granular checkpoint of everything the continuation depends on —
per-coordinate model arrays, the sweep index, the per-coordinate
down-sampling counters (the PRNG fold-in state), and the best-model
bookkeeping — so a killed run resumes BITWISE-equal to an uninterrupted
one. At sweep boundaries scores are NOT persisted: they are pure
deterministic functions of the models and are recomputed on resume.
MID-sweep (preemption / coordinate-failure aborts) they MUST be: the
running ``full_score`` is an incremental sum whose last-ulp rounding
depends on the exact order of updates, and a recomputed sum would break
bitwise-equal continuation. Partial checkpoints therefore carry the score
container verbatim.

Layout (one directory per publish, atomic rename):

    <dir>/sweep_0007/                   # completed sweep 7
    <dir>/sweep_0007_part02/            # preempted DURING sweep 8, about
                                        # to update coordinate index 2
        meta.json              # schema, sweep, counters, best_*, history,
                               # per-file crc32 checksums, partial fields
        model__<coord>.npz     # arrays of that coordinate's model
        best__<coord>.npz      # arrays of the best-so-far model (if any)
        scores__<coord>.npz    # partial only: score container entry
        full_score.npz         # partial only: running sum, verbatim

Naming invariant: lexicographic order == resume order. A partial dir is
named by its LAST COMPLETED sweep, so ``sweep_0007_part02`` sorts after
``sweep_0007`` (strict prefix) and before ``sweep_0008``; a run that was
preempted in its very first sweep publishes ``sweep_-001_part..``, which
sorts before ``sweep_0000`` ('-' < '0').

Durability: every file is fsynced before the rename and the parent
directory after it (a rename is only atomic-durable once the directory
entry itself is on disk). meta.json carries a crc32 per sibling file;
``load_latest`` walks candidates newest-first and SKIPS (with a warning)
any directory whose checksums, JSON, or arrays fail to load — a torn
checkpoint costs one sweep of progress, never the run.
"""

from __future__ import annotations

import dataclasses
import io
import json
import logging
import os
import shutil
import tempfile
import zipfile
import zlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.game.model import FixedEffectModel, GameModel, RandomEffectModel
from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_tpu.resilience import chaos as _chaos
from photon_tpu.resilience import io as rio
from photon_tpu.resilience import retry as _retry
from photon_tpu.types import TaskType

Array = jax.Array

logger = logging.getLogger(__name__)

_SWEEP_PREFIX = "sweep_"
# v3: adds ``group_boundary`` — whether a partial checkpoint's
# ``next_coordinate`` is a parallel-mode concurrency-group boundary
# (game/parallel_cd.py) rather than an arbitrary coordinate boundary.
# Resume handles both (a mid-group index re-enters the group with
# sequential semantics); v2 checkpoints load unchanged (flag False).
# v4: adds ``re_block_cursor`` — per-coordinate next-block index for a
# random effect whose BLOCKED update (coordinate.update_model_blocked or
# update_model_blocked_swept: one host loop, cold-tier streaming) was
# mid-stream at preemption. The partial checkpoint's model arrays for
# that coordinate hold the host table as of the cursor (solved blocks
# fresh, later blocks still warm-start); resume re-enters the same method
# (start_block=cursor, warm_start=checkpointed coefficients, [K, E, d] for
# a sweep). v2/v3 checkpoints load unchanged (empty cursor map).
SCHEMA_VERSION = 4


class CheckpointCorruptError(RuntimeError):
    """A checkpoint directory failed checksum/parse validation."""

    def __init__(self, path: str, detail: str):
        self.path = path
        super().__init__(f"corrupt checkpoint at {path}: {detail}")


# -- model (de)serialization --------------------------------------------------

def _model_arrays(m) -> Tuple[dict, dict]:
    """(arrays, meta) for one coordinate model."""
    if isinstance(m, FixedEffectModel):
        c = m.model.coefficients
        arrays = {"means": np.asarray(c.means)}
        if c.variances is not None:
            arrays["variances"] = np.asarray(c.variances)
        return arrays, {"kind": "fixed", "task": m.model.task.value,
                        "feature_shard_id": m.feature_shard_id}
    if isinstance(m, RandomEffectModel):
        arrays = {"coefficients": np.asarray(m.coefficients)}
        if m.variances is not None:
            arrays["variances"] = np.asarray(m.variances)
        return arrays, {"kind": "random", "task": m.task.value,
                        "feature_shard_id": m.feature_shard_id,
                        "random_effect_type": m.random_effect_type}
    raise TypeError(f"unknown coordinate model type {type(m).__name__}")


def _model_from_arrays(arrays: dict, meta: dict):
    task = TaskType(meta["task"])
    if meta["kind"] == "fixed":
        coef = Coefficients(
            jnp.asarray(arrays["means"]),
            jnp.asarray(arrays["variances"]) if "variances" in arrays else None)
        return FixedEffectModel(GeneralizedLinearModel(coef, task),
                                meta["feature_shard_id"])
    return RandomEffectModel(
        coefficients=jnp.asarray(arrays["coefficients"]),
        random_effect_type=meta["random_effect_type"],
        feature_shard_id=meta["feature_shard_id"],
        task=task,
        variances=jnp.asarray(arrays["variances"]) if "variances" in arrays
        else None)


# -- checkpoint state ---------------------------------------------------------

@dataclasses.dataclass
class CheckpointState:
    sweep: int                              # last COMPLETED sweep index
    models: Dict[str, object]               # coordinate id -> model
    counters: Dict[str, int]                # coordinate id -> _update_count
    best_models: Optional[Dict[str, object]]
    best_metric: Optional[float]
    best_iteration: Optional[int]
    history: List[Dict[str, float]]
    # mid-sweep (partial) state; None/0 for sweep-boundary checkpoints
    sweep_in_progress: Optional[int] = None
    next_coordinate: int = 0
    scores: Optional[Dict[str, np.ndarray]] = None
    full_score: Optional[np.ndarray] = None
    # v3: next_coordinate is a parallel concurrency-group boundary
    group_boundary: bool = False
    # v4: coordinate id -> next block index of a mid-stream blocked
    # random-effect update (empty when no blocked update was in flight)
    re_block_cursor: Dict[str, int] = dataclasses.field(default_factory=dict)


def _npz_bytes(arrays: dict) -> bytes:
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    return buf.getvalue()


def save_checkpoint(
    directory: str,
    sweep: int,
    models: Dict[str, object],
    counters: Dict[str, int],
    best_models: Optional[Dict[str, object]] = None,
    best_metric: Optional[float] = None,
    best_iteration: Optional[int] = None,
    history: Optional[List[Dict[str, float]]] = None,
    sweep_in_progress: Optional[int] = None,
    next_coordinate: int = 0,
    scores: Optional[Dict[str, np.ndarray]] = None,
    full_score: Optional[np.ndarray] = None,
    group_boundary: bool = False,
    re_block_cursor: Optional[Dict[str, int]] = None,
) -> str:
    """Atomically publish one checkpoint; returns its path.

    ``sweep`` is the last COMPLETED sweep (-1 if none). Passing
    ``sweep_in_progress`` publishes a mid-sweep PARTIAL checkpoint (see
    module docstring for naming/resume semantics); partial checkpoints
    must also pass the score container (``scores`` + ``full_score``)
    verbatim for bitwise-equal continuation."""
    os.makedirs(directory, exist_ok=True)
    if sweep_in_progress is not None:
        name = f"{_SWEEP_PREFIX}{sweep:04d}_part{next_coordinate:02d}"
    else:
        name = f"{_SWEEP_PREFIX}{sweep:04d}"
    final = os.path.join(directory, name)

    def _publish() -> None:
        tmp = tempfile.mkdtemp(prefix=".ckpt_tmp_", dir=directory)
        try:
            checksums: Dict[str, int] = {}

            def put(fname: str, data: bytes) -> None:
                with open(os.path.join(tmp, fname), "wb") as f:
                    f.write(data)
                    f.flush()
                    os.fsync(f.fileno())
                checksums[fname] = zlib.crc32(data)

            model_meta = {}
            for cid, m in models.items():
                arrays, meta = _model_arrays(m)
                put(f"model__{cid}.npz", _npz_bytes(arrays))
                model_meta[cid] = meta
            best_meta = None
            if best_models is not None:
                best_meta = {}
                for cid, m in best_models.items():
                    arrays, meta = _model_arrays(m)
                    put(f"best__{cid}.npz", _npz_bytes(arrays))
                    best_meta[cid] = meta
            if scores is not None:
                for cid, s in scores.items():
                    put(f"scores__{cid}.npz",
                        _npz_bytes({"scores": np.asarray(s)}))
            if full_score is not None:
                put("full_score.npz",
                    _npz_bytes({"full_score": np.asarray(full_score)}))
            meta_doc = {"schema": SCHEMA_VERSION,
                        "sweep": sweep,
                        "counters": counters,
                        "models": model_meta,
                        "best_models": best_meta,
                        "best_metric": best_metric,
                        "best_iteration": best_iteration,
                        "history": history or [],
                        "checksums": checksums,
                        "sweep_in_progress": sweep_in_progress,
                        "next_coordinate": next_coordinate,
                        "group_boundary": group_boundary,
                        "re_block_cursor": re_block_cursor or {},
                        "score_coordinates":
                            None if scores is None else sorted(scores)}
            put("meta.json", json.dumps(meta_doc, indent=2).encode())
            rio.fsync_dir(tmp)
            _chaos.at_publish("checkpoint")
            if os.path.exists(final):
                shutil.rmtree(final)
            os.replace(tmp, final)
            rio.fsync_dir(directory)
        except _chaos.SimulatedKill:
            raise  # a real kill leaves the tmp dir behind; so does this one
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise

    _retry.with_retries(_publish, op="checkpoint")
    return final


def checkpoint_candidates(directory: str) -> List[str]:
    """All checkpoint directories, oldest first (lexicographic == resume
    order; see module docstring)."""
    if not os.path.isdir(directory):
        return []
    return [os.path.join(directory, d)
            for d in sorted(os.listdir(directory))
            if d.startswith(_SWEEP_PREFIX)
            and os.path.isfile(os.path.join(directory, d, "meta.json"))]


def latest_checkpoint(directory: str) -> Optional[str]:
    cands = checkpoint_candidates(directory)
    return cands[-1] if cands else None


def load_checkpoint(path: str) -> CheckpointState:
    try:
        with open(os.path.join(path, "meta.json"), "rb") as f:
            meta = json.loads(f.read().decode())
    except (OSError, ValueError) as e:
        raise CheckpointCorruptError(path, f"meta.json unreadable: {e}")

    checksums = meta.get("checksums")
    if meta.get("schema", 1) >= 2 and checksums is not None:
        for fname, want in checksums.items():
            fpath = os.path.join(path, fname)
            try:
                with open(fpath, "rb") as f:
                    got = zlib.crc32(f.read())
            except OSError as e:
                raise CheckpointCorruptError(path, f"{fname} unreadable: {e}")
            if got != int(want):
                raise CheckpointCorruptError(
                    path, f"{fname} checksum mismatch "
                          f"(want {int(want):#010x}, got {got:#010x})")

    def load_npz(fname: str) -> dict:
        try:
            with np.load(os.path.join(path, fname)) as z:
                return dict(z)
        except (OSError, ValueError, zipfile.BadZipFile) as e:
            raise CheckpointCorruptError(path, f"{fname} unreadable: {e}")

    def load_models(prefix: str, metas) -> Optional[Dict[str, object]]:
        if metas is None:
            return None
        return {cid: _model_from_arrays(load_npz(f"{prefix}__{cid}.npz"), m)
                for cid, m in metas.items()}

    scores = None
    if meta.get("score_coordinates"):
        scores = {cid: load_npz(f"scores__{cid}.npz")["scores"]
                  for cid in meta["score_coordinates"]}
    full_score = None
    if os.path.isfile(os.path.join(path, "full_score.npz")):
        full_score = load_npz("full_score.npz")["full_score"]

    return CheckpointState(
        sweep=int(meta["sweep"]),
        models=load_models("model", meta["models"]),
        counters={k: int(v) for k, v in meta["counters"].items()},
        best_models=load_models("best", meta.get("best_models")),
        best_metric=meta.get("best_metric"),
        best_iteration=meta.get("best_iteration"),
        history=meta.get("history") or [],
        sweep_in_progress=meta.get("sweep_in_progress"),
        next_coordinate=int(meta.get("next_coordinate") or 0),
        scores=scores,
        full_score=full_score,
        group_boundary=bool(meta.get("group_boundary", False)),
        re_block_cursor={k: int(v) for k, v in
                         (meta.get("re_block_cursor") or {}).items()},
    )


def load_latest(directory: str) -> Optional[CheckpointState]:
    """Newest loadable checkpoint, skipping corrupt/partial-write
    directories with a warning (a torn publish must never kill a
    resume — it costs at most one sweep of progress)."""
    for path in reversed(checkpoint_candidates(directory)):
        try:
            return load_checkpoint(path)
        except (CheckpointCorruptError, KeyError) as e:
            logger.warning("skipping unusable checkpoint %s: %s", path, e)
            try:
                from photon_tpu.resilience import failures
                failures.record_failure("checkpoint_corrupt", path=path,
                                        error=str(e))
            except Exception:  # pragma: no cover - telemetry must not fail
                logger.debug("failure-event emission failed", exc_info=True)
    return None
