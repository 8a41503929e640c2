"""Device-resident GAME model state + host-side batch assembly.

The load-once / serve-forever half of the serving engine: coefficient
arrays go to the accelerator exactly once at model load — the fixed-
effect vectors replicated, the per-entity random-effect blocks laid out
as gather tables (optionally sharded over the mesh's entity axis) — and
every request batch only ships its own [B, k] feature arrays. This is
the Snap ML resident-state discipline applied to GLMix: per-request work
is a gather + dot, never a model re-stage.

Host side, the model keeps the lookup tables that turn a request into
device arrays: per-shard feature IndexMaps (request (name, term) ->
column), per-coordinate entity vocabularies (REId string -> block row),
and the (entity, feature) -> local-slot tables that replay
``game/random_effect.project_for_scoring``'s projection per batch — the
same math as offline scoring, so serving scores are bitwise-comparable.

Two-tier placement: a random-effect coordinate loaded with a cold-store
file (io/cold_store.py) and a ``CoeffStoreConfig`` does NOT stage its
full table; it serves through a ``serving/coeff_store.TwoTierCoeffStore``
— a fixed-budget HBM hot set over the host-RAM cold tier, with the
entity->hot-slot map playing the role the full-resident path's
entity_rows dict plays. Assembly then resolves entities against the hot
map (HIT -> hot slot, COLD -> typed ``COLD_MISS`` + queued promotion,
UNKNOWN -> zero row), and the scorer receives the hot table as an
argument under ``transfer_lock`` so concurrent cold->hot transfers can
never tear a batch.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from photon_tpu.io.model_io import ServingGameModel
from photon_tpu.serving.types import (CoeffStoreConfig, Fallback,
                                      FallbackReason, ScoreRequest)

_model_counter = itertools.count()


@dataclasses.dataclass
class _FixedState:
    coordinate_id: str
    feature_shard_id: str
    theta: object                     # device [D_pad] (replicated on a mesh)
    # thompson arm: posterior variances aligned with theta ([D_pad],
    # zeros where the model carried none). None unless the model was
    # built with thompson=True and carries variances somewhere.
    var_theta: Optional[object] = None


@dataclasses.dataclass
class _RandomState:
    coordinate_id: str
    random_effect_type: str
    feature_shard_id: str
    coef: object                      # device [E_pad, K] gather table
    num_entities: int                 # E (pre-padding)
    unknown_row: int                  # index scoring as all-zeros
    slot_width: int                   # K
    entity_rows: Dict[str, int]       # REId -> row
    # (entity * D + global_col) -> local slot, as sorted parallel arrays
    # (the project_for_scoring lookup, built once at load)
    pkeys_sorted: np.ndarray          # [P] int64
    pslots_sorted: np.ndarray         # [P] int64
    # two-tier mode: the hot-set gather cache; coef/entity_rows/pkeys
    # are unused and the gather table is read via store.table instead
    store: Optional[object] = None    # TwoTierCoeffStore
    # full-resident nearline appends: reserve rows AFTER the zero row
    # (rows unknown_row+1 .. unknown_row+append_reserve). Appending an
    # entity takes the next reserve row, so existing rows, the zero row,
    # and the table shape (a compiled-program shape!) never change.
    append_reserve: int = 0
    append_used: int = 0
    # int8 serving arm (full-resident coordinates only): row-quantized
    # mirror of ``coef`` plus the per-row dequantization scales, staged
    # at load/publish time. None everywhere unless the model was built
    # with int8=True; two-tier coordinates never quantize.
    coef_q: Optional[object] = None      # device [E_pad, K] int8
    scales: Optional[object] = None      # device [E_pad, 1] float32
    # thompson arm: posterior-variance gather table mirroring ``coef``
    # row for row — real entities carry their Laplace variances (zeros
    # when the model has none for this coordinate), the unknown row
    # carries ``prior_variance`` (cold-start exploration; its MEAN row
    # stays zero), and the append reserve rows are zero until a nearline
    # publish hands them a variance row (appended-without-variance
    # entities serve the mean). None unless thompson staging is on.
    var_coef: Optional[object] = None    # device [E_pad, K] float32


class AssembledBatch(Tuple):
    pass


def quantize_rows(rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric per-row int8 quantization: ``q = round(row / scale)``
    with ``scale = max|row| / 127`` (all-zero rows get scale 1.0 so the
    dequantized row is exactly zero). Deterministic and row-local, so a
    row-level nearline publish can requantize only the touched rows and
    stay bitwise-consistent with a from-scratch staging."""
    rows = np.asarray(rows, np.float32)
    amax = np.abs(rows).max(axis=-1, keepdims=True)
    scales = np.where(amax > 0.0, amax / 127.0, np.float32(1.0))
    q = np.clip(np.rint(rows / scales), -127, 127).astype(np.int8)
    return q, scales.astype(np.float32)


def _pad_width(dim: int, requested: Optional[int]) -> int:
    if requested is not None:
        return max(int(requested), 1)
    p = 1
    while p < dim and p < 256:
        p *= 2
    return p


class DeviceResidentModel:
    """A ServingGameModel staged onto the accelerator, plus assembly."""

    def __init__(self, model: ServingGameModel, mesh=None,
                 feature_pad: Optional[int] = None, dtype=None,
                 coeff_store: Optional[CoeffStoreConfig] = None,
                 append_reserve: int = 0, int8: bool = False,
                 thompson: bool = False, prior_variance: float = 1.0):
        import jax
        import jax.numpy as jnp

        self.task = model.task
        self.index_maps = model.index_maps
        self.dtype = dtype or jnp.float32
        self.token = f"servmodel-{next(_model_counter)}"
        self.mesh = mesh
        self._shape_sig: Optional[tuple] = None
        #: int8 serving arm requested: full-resident coordinates carry a
        #: (coef_q, scales) mirror and "full_int8" programs are warmed
        self.int8_enabled = bool(int8)
        #: thompson arm: active only when it was REQUESTED and the model
        #: actually carries posterior variances somewhere — a var-less
        #: model under the flag stages nothing extra, keeps its pre-
        #: thompson shape signature, and serves the mean bitwise as
        #: before. When active, every coordinate gets a variance mirror
        #: (zeros where a coordinate has none) and "thompson" programs
        #: are warmed.
        self.prior_variance = float(prior_variance)
        has_var = (any(getattr(fe, "variances", None) is not None
                       for fe in model.fixed)
                   or any(getattr(re, "has_variances", False)
                          for re in model.random))
        self.thompson_enabled = bool(thompson) and has_var
        if self.thompson_enabled and coeff_store is not None and any(
                getattr(re, "cold_store_path", None) is not None
                for re in model.random):
            # the variance mirror must be a full-resident program
            # argument — a hot-set slice of it would explore with
            # whichever rows happen to be hot. Typed refusal, at load,
            # never a silent mean fallback.
            raise ValueError(
                "thompson serving requires full-resident random-effect "
                "tables; this model serves through a two-tier coeff_store "
                "— drop the CoeffStoreConfig or disable thompson_serving")
        # serializes batch assembly + scorer dispatch against the
        # two-tier stores' cold->hot transfer commits; recursive so the
        # engine can nest assemble inside its own hold. A model with no
        # stores pays one uncontended acquire per batch.
        self.transfer_lock = threading.RLock()
        self.coeff_store_config = coeff_store

        put_rep, put_ent = self._placers(mesh)

        # one request-feature column space per shard, shared by every
        # coordinate on that shard
        self.shard_order: Tuple[str, ...] = tuple(sorted(model.index_maps))
        self.shard_dims = {sid: m.feature_dimension
                           for sid, m in model.index_maps.items()}
        self.shard_pad = {sid: _pad_width(self.shard_dims[sid], feature_pad)
                          for sid in self.shard_order}

        self.fixed: List[_FixedState] = []
        for fe in model.fixed:
            theta = np.asarray(fe.coefficients, np.dtype(self.dtype.dtype.name
                               if hasattr(self.dtype, "dtype") else self.dtype))
            # gather indices are always < shard dim; pad the vector up so
            # a shard whose map grew (external index maps) still gathers
            dim = max(self.shard_dims.get(fe.feature_shard_id, 0), len(theta), 1)
            if len(theta) < dim:
                theta = np.concatenate([theta, np.zeros(dim - len(theta),
                                                        theta.dtype)])
            var_theta = None
            if self.thompson_enabled:
                v = getattr(fe, "variances", None)
                var = (np.zeros(dim, theta.dtype) if v is None
                       else np.asarray(v, theta.dtype))
                if len(var) < dim:
                    var = np.concatenate(
                        [var, np.zeros(dim - len(var), var.dtype)])
                var_theta = put_rep(var[:dim])
            self.fixed.append(_FixedState(
                fe.coordinate_id, fe.feature_shard_id, put_rep(theta),
                var_theta=var_theta))

        self.random: List[_RandomState] = []
        for re in model.random:
            cold_path = getattr(re, "cold_store_path", None)
            if coeff_store is not None and cold_path is not None:
                # two-tier: the table never fully materializes — a
                # fixed-budget hot set fronts the mmapped cold tier
                from photon_tpu.io.cold_store import ColdStore
                from photon_tpu.serving.coeff_store import TwoTierCoeffStore

                store = TwoTierCoeffStore(
                    ColdStore(cold_path), coeff_store,
                    lock=self.transfer_lock)
                self.random.append(_RandomState(
                    re.coordinate_id, re.random_effect_type,
                    re.feature_shard_id, None, store.cold.num_entities,
                    store.unknown_row, store.slot_width, {},
                    np.empty(0, np.int64), np.empty(0, np.int64),
                    store=store))
                continue
            coef = np.asarray(re.coefficients)
            E, K = coef.shape
            D = max(self.shard_dims.get(re.feature_shard_id, 1), 1)
            proj = np.asarray(re.projection)
            valid = proj >= 0
            pe, ps = np.nonzero(valid)
            pkeys = pe.astype(np.int64) * D + proj[pe, ps].astype(np.int64)
            order = np.argsort(pkeys, kind="stable")
            # one explicit zero row after the real entities: unknown
            # entities gather it and contribute exactly nothing. The
            # optional append reserve follows it — zero rows the nearline
            # publisher can hand to new entities without a table reshape.
            reserve = max(int(append_reserve), 0)
            coef = np.concatenate(
                [coef, np.zeros((1 + reserve, K), coef.dtype)])
            coef_q = scales = None
            if self.int8_enabled:
                q, s = quantize_rows(coef)
                coef_q, scales = put_ent(q), put_ent(s)
            var_coef = None
            if self.thompson_enabled:
                vtab = np.zeros((E + 1 + reserve, K), np.float32)
                rv = getattr(re, "variances", None)
                if rv is not None:
                    rv = np.asarray(rv, np.float32)
                    vtab[:E] = rv[:E]
                # the unknown row's MEAN stays zero but its VARIANCE is
                # the prior: cold-start entities explore instead of
                # silently scoring the mean. Reserve rows stay zero —
                # appended entities explore only once a publish hands
                # them a variance row.
                vtab[E] = self.prior_variance
                var_coef = put_ent(vtab)
            self.random.append(_RandomState(
                re.coordinate_id, re.random_effect_type, re.feature_shard_id,
                put_ent(coef.astype(np.float32) if self.dtype == jnp.float32
                        else coef),
                E, E, K, dict(re.entity_rows),
                pkeys[order], ps[order].astype(np.int64),
                append_reserve=reserve, coef_q=coef_q, scales=scales,
                var_coef=var_coef))

    # -- two-tier store plumbing --------------------------------------------

    @property
    def has_stores(self) -> bool:
        return any(rs.store is not None for rs in self.random)

    def current_tables(self) -> tuple:
        """The random-effect gather tables the scorer takes as arguments
        — the live hot table for two-tier coordinates, the static full
        table otherwise. Two-tier reads must happen under
        ``transfer_lock``, in the same hold as the assemble and the
        scorer dispatch that consume them (the donated transfer scatter
        invalidates superseded table objects)."""
        return tuple(rs.store.table if rs.store is not None else rs.coef
                     for rs in self.random)

    def current_tables_int8(self) -> tuple:
        """Gather tables for the "full_int8" programs: full-resident
        coordinates pass their ``(coef_q, scales)`` pair, two-tier
        coordinates pass the live f32 hot table (mixed-precision by
        design — the cold tier is the capacity story there). Same
        transfer_lock contract as ``current_tables``."""
        return tuple(rs.store.table if rs.store is not None
                     else (rs.coef_q, rs.scales)
                     for rs in self.random)

    def current_thetas(self) -> tuple:
        """The fixed-effect coefficient vectors the scorer takes as
        arguments — one device array per fixed coordinate, in coordinate
        order. Passing them as arguments (not closures) is what lets N
        same-shape tenants dispatch ONE compiled program: same
        shape/dtype arguments re-dispatch with zero retraces, exactly
        the random-effect tables' calling convention."""
        return tuple(f.theta for f in self.fixed)

    def current_var_thetas(self) -> tuple:
        """Posterior-variance vectors for the "thompson" programs, one
        per fixed coordinate (zeros where the model carried none). Only
        meaningful when ``thompson_enabled``."""
        return tuple(f.var_theta for f in self.fixed)

    def current_var_tables(self) -> tuple:
        """Posterior-variance gather tables for the "thompson" programs,
        one per random coordinate, row-aligned with ``current_tables()``
        (thompson is full-resident only, so these are static device
        arrays — nearline publishes scatter into them like the mean
        tables). Only meaningful when ``thompson_enabled``."""
        return tuple(rs.var_coef for rs in self.random)

    def shape_signature(self) -> tuple:
        """Canonical shape signature: everything a scorer trace depends
        on EXCEPT the parameter values — feature-shard pads, fixed
        coordinate positions and theta shapes/dtypes, random-effect
        table shapes (two-tier hot capacity or full-resident rows),
        int8 mirrors, compute dtype, and mesh layout. Two models with
        equal signatures produce bitwise-identical traces, so compiled
        (mode, bucket) programs are keyed by this signature instead of
        ``model.token`` and shared across tenants. Stable for a model's
        lifetime: two-tier transfers swap table *objects* at fixed
        shape, and nearline appends spend pre-reserved rows."""
        if self._shape_sig is not None:
            return self._shape_sig

        def _dt(x) -> str:
            return np.dtype(getattr(x, "dtype", x)).name

        mesh_tok = None
        if self.mesh is not None:
            mesh_tok = (tuple(str(a) for a in self.mesh.axis_names),
                        tuple(int(s) for s in self.mesh.devices.shape),
                        tuple(int(d.id) for d in self.mesh.devices.flat))
        shard_pos = {sid: i for i, sid in enumerate(self.shard_order)}
        fixed_sig = tuple(
            (shard_pos[f.feature_shard_id],
             tuple(int(s) for s in f.theta.shape), _dt(f.theta))
            for f in self.fixed)
        rand_sig = []
        for rs in self.random:
            table = rs.store.table if rs.store is not None else rs.coef
            entry = (shard_pos[rs.feature_shard_id], int(rs.slot_width),
                     tuple(int(s) for s in table.shape), _dt(table),
                     rs.store is not None)
            if rs.coef_q is not None:
                entry += (tuple(int(s) for s in rs.coef_q.shape),
                          _dt(rs.coef_q),
                          tuple(int(s) for s in rs.scales.shape))
            rand_sig.append(entry)
        sig = (
            "servshape", _dt(self.dtype), int(self.int8_enabled), mesh_tok,
            tuple(int(self.shard_pad[sid]) for sid in self.shard_order),
            fixed_sig, tuple(rand_sig))
        if self.thompson_enabled:
            # appended ONLY when variance mirrors are staged: a var-less
            # (or thompson-off) model keeps its pre-thompson signature
            # bitwise, so its compiled programs and AOT bundles stay
            # shared with pre-variance builds
            sig = sig + (("thompson",
                          tuple((tuple(int(s) for s in f.var_theta.shape),
                                 _dt(f.var_theta)) for f in self.fixed),
                          tuple((tuple(int(s) for s in rs.var_coef.shape),
                                 _dt(rs.var_coef)) for rs in self.random)),)
        self._shape_sig = sig
        return self._shape_sig

    def prefetch_request(self, request: ScoreRequest,
                         skip: frozenset = frozenset()) -> None:
        """Admission lookahead: queue cold->hot promotion for every
        two-tier entity this request names. Non-blocking. ``skip`` holds
        ``(random_effect_type, entity_id)`` pairs currently mid-publish —
        prefetching one of those could promote a half-published cold row
        into the hot tier, so they are deferred to the next natural miss
        after the publish commits (see engine._prefetch_lookahead)."""
        for rs in self.random:
            if rs.store is None:
                continue
            re_id = request.entity_ids.get(rs.random_effect_type)
            if re_id is not None and \
                    (rs.random_effect_type, re_id) not in skip:
                rs.store.prefetch(re_id)

    def coeff_store_stats(self) -> Optional[dict]:
        stats = {rs.coordinate_id: rs.store.stats()
                 for rs in self.random if rs.store is not None}
        return stats or None

    def drain_prefetch(self, timeout_s: float = 10.0) -> bool:
        """Flush every store's pending promotions (tests' phase
        boundaries — never the scoring path)."""
        ok = True
        for rs in self.random:
            if rs.store is not None:
                ok = rs.store.drain_prefetch(timeout_s) and ok
        return ok

    def close_stores(self) -> None:
        for rs in self.random:
            if rs.store is not None:
                rs.store.close()

    # -- device placement ---------------------------------------------------

    @staticmethod
    def _placers(mesh):
        """(replicate, entity-shard) placement functions. Without a mesh
        (or with a trivial one) both are a plain device transfer."""
        import jax
        import jax.numpy as jnp

        if mesh is None:
            return jnp.asarray, jnp.asarray
        from jax.sharding import NamedSharding, PartitionSpec as P

        from photon_tpu.parallel.mesh import ENTITY_AXIS, pad_to_multiple

        axis = ENTITY_AXIS if ENTITY_AXIS in mesh.axis_names else None
        n_ent = dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)

        def put_rep(a):
            return jax.device_put(a, NamedSharding(mesh, P()))

        def put_ent(a):
            if axis is None or n_ent <= 1:
                return put_rep(a)
            rows = pad_to_multiple(a.shape[0], n_ent)
            if rows != a.shape[0]:
                a = np.concatenate(
                    [a, np.zeros((rows - a.shape[0],) + a.shape[1:], a.dtype)])
            return jax.device_put(
                a, NamedSharding(mesh, P(axis, *([None] * (a.ndim - 1)))))

        return put_rep, put_ent

    # -- batch assembly (host) ----------------------------------------------

    def assemble(self, requests: Sequence[ScoreRequest], bucket: int,
                 shed_random: bool = False, explore_unknown: bool = False):
        """Pack <=bucket requests into the padded device arrays one scorer
        call consumes. Returns (args tuple, per-request fallback lists,
        counters dict). Pad rows beyond ``len(requests)`` carry zero
        features and the unknown-entity sentinel, so they score to their
        (zero) offset and are discarded by the engine.

        ``explore_unknown`` (thompson mode only): an unknown entity's
        request features are packed into its slot lanes against the
        unknown row — whose MEAN row is zero (no mean contribution, same
        score center as before) and whose VARIANCE row is the prior, so
        the thompson program draws prior-variance exploration noise for
        it. Typed EXPLORING_COLD_START instead of UNKNOWN_ENTITY."""
        n = len(requests)
        if n > bucket:
            raise ValueError(f"{n} requests > bucket {bucket}")
        fallbacks: List[List[Fallback]] = [[] for _ in range(n)]
        counters = {"unknown_features": 0, "truncated_features": 0,
                    "unknown_entities": 0, "cold_misses": 0,
                    "explored_cold_start": 0,
                    "padded_rows": bucket - n}

        offsets = np.zeros(bucket, np.float32)
        for i, r in enumerate(requests):
            offsets[i] = r.offset

        # per-shard global-column views, reused by every coordinate below
        shard_cols: Dict[str, List[np.ndarray]] = {}
        shard_vals: Dict[str, List[np.ndarray]] = {}
        for sid in self.shard_order:
            imap = self.index_maps[sid]
            cols_l, vals_l = [], []
            for i, r in enumerate(requests):
                feats = r.features.get(sid) or ()
                cols = np.fromiter(
                    (imap.index_of(name, term) for name, term, _ in feats),
                    np.int64, count=len(feats))
                vals = np.fromiter((v for _, _, v in feats), np.float64,
                                   count=len(feats))
                keep = cols >= 0
                dropped = int(len(cols) - keep.sum())
                if dropped:
                    counters["unknown_features"] += dropped
                    cols, vals = cols[keep], vals[keep]
                pad = self.shard_pad[sid]
                if len(cols) > pad:
                    counters["truncated_features"] += len(cols) - pad
                    fallbacks[i].append(Fallback(
                        FallbackReason.FEATURE_OVERFLOW, coordinate=sid,
                        detail=f"{len(cols)} features > pad {pad}"))
                    cols, vals = cols[:pad], vals[:pad]
                cols_l.append(cols)
                vals_l.append(vals)
            shard_cols[sid] = cols_l
            shard_vals[sid] = vals_l

        fixed_idx, fixed_val = [], []
        for sid in self.shard_order:
            pad = self.shard_pad[sid]
            idx = np.zeros((bucket, pad), np.int32)
            val = np.zeros((bucket, pad), np.float32)
            for i in range(n):
                c, v = shard_cols[sid][i], shard_vals[sid][i]
                idx[i, :len(c)] = c
                val[i, :len(c)] = v
            fixed_idx.append(idx)
            fixed_val.append(val)

        re_slot_idx, re_slot_val, re_ent = [], [], []
        for rs in self.random:
            ent = np.full(bucket, rs.unknown_row, np.int32)
            sidx = np.zeros((bucket, rs.slot_width), np.int32)
            sval = np.zeros((bucket, rs.slot_width), np.float32)
            if not shed_random and rs.store is not None:
                from photon_tpu.serving import coeff_store as _cs

                for i, r in enumerate(requests):
                    re_id = r.entity_ids.get(rs.random_effect_type)
                    if re_id is None:
                        counters["unknown_entities"] += 1
                        fallbacks[i].append(Fallback(
                            FallbackReason.UNKNOWN_ENTITY,
                            coordinate=rs.coordinate_id, detail="None"))
                        continue
                    slot, status = rs.store.lookup_locked(re_id)
                    if status == _cs.UNKNOWN:
                        counters["unknown_entities"] += 1
                        fallbacks[i].append(Fallback(
                            FallbackReason.UNKNOWN_ENTITY,
                            coordinate=rs.coordinate_id, detail=re_id))
                        continue
                    if status == _cs.COLD:
                        # rows still in the cold tier at pop time: typed
                        # degradation (the zero row scores this request
                        # fixed-effect-only for this coordinate); the
                        # lookup already queued the promotion
                        counters["cold_misses"] += 1
                        fallbacks[i].append(Fallback(
                            FallbackReason.COLD_MISS,
                            coordinate=rs.coordinate_id, detail=re_id))
                        continue
                    ent[i] = slot
                    cols = shard_cols[rs.feature_shard_id][i]
                    if not len(cols):
                        continue
                    # replay project_for_scoring against the hot slot's
                    # projection row (ascending global cols, -1 pad), a
                    # host mirror — the cold mmap is never touched here
                    prow = rs.store.proj_row_locked(slot)
                    pvalid = prow[prow >= 0]
                    if not len(pvalid):
                        continue
                    rank = np.searchsorted(pvalid, cols)
                    rank = np.minimum(rank, len(pvalid) - 1)
                    kept = pvalid[rank] == cols
                    k = int(kept.sum())
                    sidx[i, :k] = rank[kept]
                    sval[i, :k] = shard_vals[rs.feature_shard_id][i][kept]
            elif not shed_random:
                D = max(self.shard_dims.get(rs.feature_shard_id, 1), 1)
                for i, r in enumerate(requests):
                    re_id = r.entity_ids.get(rs.random_effect_type)
                    e = rs.entity_rows.get(re_id) if re_id is not None else None
                    if e is None:
                        if explore_unknown:
                            # cold-start exploration: pack this request's
                            # shard features into slots 0..k against the
                            # unknown row (zero mean, prior variance) —
                            # the slot ORDER is immaterial because every
                            # slot of that row shares the prior
                            counters["explored_cold_start"] += 1
                            fallbacks[i].append(Fallback(
                                FallbackReason.EXPLORING_COLD_START,
                                coordinate=rs.coordinate_id,
                                detail=str(re_id)))
                            cvals = shard_vals[rs.feature_shard_id][i]
                            k = min(len(cvals), rs.slot_width)
                            if k:
                                sidx[i, :k] = np.arange(k)
                                sval[i, :k] = cvals[:k]
                            continue
                        counters["unknown_entities"] += 1
                        fallbacks[i].append(Fallback(
                            FallbackReason.UNKNOWN_ENTITY,
                            coordinate=rs.coordinate_id,
                            detail=str(re_id)))
                        continue
                    ent[i] = e
                    cols = shard_cols[rs.feature_shard_id][i]
                    if not len(cols) or not len(rs.pkeys_sorted):
                        continue
                    # replay project_for_scoring: (e, g) -> local slot via
                    # binary search over the load-time sorted key table
                    keys = e * D + cols
                    rank = np.searchsorted(rs.pkeys_sorted, keys)
                    rank = np.minimum(rank, len(rs.pkeys_sorted) - 1)
                    kept = rs.pkeys_sorted[rank] == keys
                    k = int(kept.sum())
                    sidx[i, :k] = rs.pslots_sorted[rank[kept]]
                    sval[i, :k] = shard_vals[rs.feature_shard_id][i][kept]
            re_slot_idx.append(sidx)
            re_slot_val.append(sval)
            re_ent.append(ent)

        args = (tuple(fixed_idx), tuple(fixed_val), tuple(re_slot_idx),
                tuple(re_slot_val), tuple(re_ent), offsets)
        return args, fallbacks, counters

    def dummy_args(self, bucket: int):
        """Zero-filled arrays of the exact shapes/dtypes ``assemble``
        produces for this bucket — warmup dispatches these so steady-state
        calls hit the identical compiled program."""
        args, _, _ = self.assemble([], bucket)
        return args

    def describe(self) -> dict:
        return {
            "task": self.task.value,
            "fixed": [{"coordinate": f.coordinate_id,
                       "shard": f.feature_shard_id,
                       "dim": int(self.shard_dims.get(f.feature_shard_id, 0))}
                      for f in self.fixed],
            "random": [{"coordinate": r.coordinate_id,
                        "type": r.random_effect_type,
                        "shard": r.feature_shard_id,
                        "entities": r.num_entities,
                        "slot_width": r.slot_width,
                        "two_tier": r.store is not None,
                        **({"hot_capacity": r.store.capacity}
                           if r.store is not None else {})}
                       for r in self.random],
            "shard_pad": dict(self.shard_pad),
            "entity_sharded": self.mesh is not None,
            "int8": self.int8_enabled,
            "thompson": self.thompson_enabled,
        }
