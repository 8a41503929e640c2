"""Random-effect datasets: entity-blocked, size-bucketed, projected.

Reference: photon-api data/RandomEffectDataset.scala (activeData grouped
per-entity :46-55; build pipeline :207-340 — bounded groupBy via
deterministic reservoir sampling with byteswap64 ordering keys :212-215,
lower-bound filtering :319-340, Pearson feature selection :305, passive
split :264), data/LocalDataset.scala (Pearson correlation :122),
data/RandomEffectDataConfiguration (:68), projector/IndexMapProjectorRDD
.scala:19,24,156 (per-entity compact reindex of observed features),
data/MinHeapWithFixedCapacity.scala:29.

TPU re-design: the groupByKey shuffle becomes fully-vectorized numpy
grouping over a CSR view of the shard (no per-sample Python loops);
entities are bucketed by power-of-two active-sample count into a few
padded ELL blocks — a MovieLens-style power-law entity distribution no
longer pays S_max padding for every entity (SURVEY §7 risk (a)).
Per-entity index-map projection is a static [E, D_loc] gather table;
passive (score-only) samples are a flat gather-scored array. Reservoir
capping orders samples by splitmix64(uid) — deterministic under
recomputation exactly like the reference's byteswap64 trick.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import logging
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger(__name__)

from photon_tpu.game.dataset import (
    EntityVocabulary,
    GameDataFrame,
    count_placed,
)
from photon_tpu.obs.metrics import registry
from photon_tpu.ops import features as F
from photon_tpu.utils.timing import Timed

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class RandomEffectDataConfiguration:
    """Reference: RandomEffectDataConfiguration (CoordinateDataConfiguration
    .scala:68) incl. projectorType."""

    random_effect_type: str
    feature_shard_id: str
    active_data_lower_bound: Optional[int] = None   # min samples per entity
    active_data_upper_bound: Optional[int] = None   # reservoir cap
    features_to_samples_ratio: Optional[float] = None  # Pearson cap
    keep_passive_data: bool = True
    # ProjectorType.INDEX_MAP (default) | RANDOM | IDENTITY; RANDOM needs
    # projected_dimension (reference: ProjectorType.scala, RandomProjection)
    projector_type: str = "INDEX_MAP"
    projected_dimension: Optional[int] = None
    projection_seed: int = 0
    # cap on the number of padded size buckets: every distinct [E_b, S_b,
    # K_b] block shape is a separate XLA compile inside the one jitted
    # solve, so a long-tailed entity distribution must trade padding for
    # compile count (VERDICT r2 weak #8; no reference analog — Spark has
    # no compilation step). None/0 = uncapped.
    max_entity_buckets: Optional[int] = 16

    def random_projection(self, original_dim: int):
        from photon_tpu.game.projector import ProjectorType, RandomProjection

        if ProjectorType(self.projector_type) != ProjectorType.RANDOM:
            return None
        assert self.projected_dimension, \
            "RANDOM projector needs projected_dimension"
        return RandomProjection(original_dim, self.projected_dimension,
                                self.projection_seed)


def _lane_at(array: Array, index: Array, lanes: bool):
    """``array.at[index]`` on the first axis, or on the second under a
    leading lane axis."""
    return array.at[:, index] if lanes else array.at[index]


class EntityBlock(NamedTuple):
    """One size bucket of entities, padded to [E_b, S_b] / [E_b, S_b, K_b].
    All pads carry weight 0; ``entity_rows`` maps block rows to global
    entity rows (out-of-range = pad row). Read ``sample_rows`` and index by
    ``entity_rows`` through the mapping methods below, never directly."""

    features: F.SparseFeatures        # indices/values [E_b, S_b, K_b] LOCAL slots
    labels: Array                     # [E_b, S_b]
    offsets: Array                    # [E_b, S_b]
    weights: Array                    # [E_b, S_b] (0 on pads)
    sample_rows: Array                # [E_b, S_b] int32 row in flat frame (n on pads)
    entity_rows: Array                # [E_b] int32 global entity row

    @property
    def num_rows(self) -> int:
        return self.labels.shape[0]

    @property
    def max_samples(self) -> int:
        return self.labels.shape[1]

    # -- flat order <-> ladder order ------------------------------------
    # Four mapping methods: the three below take a flat vector or an
    # entity table into this bucket's order and a bucket's rows back to
    # the table; the fourth, ``RandomEffectDataset.rows_to_flat``, takes
    # every bucket's rows back to flat order at once (one gather through
    # ``flat_source``, the inverse map that ``flat_source_map`` derives
    # from every bucket's ``sample_rows`` at prepare). They are the ONLY
    # device-side readers of ``sample_rows`` / ``flat_source`` and the
    # only indexers by ``entity_rows``: the pad invariants above ("n on
    # pads", "out-of-range = pad row") and the ``mode="fill"`` /
    # ``mode="drop"`` that pair with them are stated here once
    # (tests/test_game.py holds coordinate.py and bayes/ to it). A
    # re-layout of the flat frame (ROADMAP S4: the frame ordered by one
    # coordinate's ladder) is a change to these and to
    # ``build_random_effect_dataset``, nowhere else. ``lanes=True`` means
    # a leading lane axis on the flat vector / the table.

    def rows_from_flat(self, flat: Array, lanes: bool = False) -> Array:
        """Flat ``[n]`` vector -> this bucket's ``[E_b, S_b]`` rows
        (``[c, n] -> [c, E_b, S_b]`` with lanes); pad slots read 0."""
        return _lane_at(flat, self.sample_rows, lanes).get(
            mode="fill", fill_value=0.0)

    def rows_from_table(self, table: Array, fill, lanes: bool = False
                        ) -> Array:
        """Entity table ``[E, ...]`` -> this bucket's ``[E_b, ...]`` rows
        (``[c, E, ...] -> [c, E_b, ...]`` with lanes); a pad row reads
        ``fill``."""
        return _lane_at(table, self.entity_rows, lanes).get(
            mode="fill", fill_value=fill)

    def set_rows_in_table(self, table: Array, rows: Array,
                          lanes: bool = False) -> Array:
        """This bucket's ``[E_b, ...]`` rows -> their places in the
        entity table; pad rows are dropped."""
        return _lane_at(table, self.entity_rows, lanes).set(
            rows, mode="drop")


class RandomEffectDataset(NamedTuple):
    """Device-resident bucketed entity blocks + passive split + projection."""

    blocks: Tuple[EntityBlock, ...]
    # passive (score-only) samples, in LOCAL slots
    passive_features: F.SparseFeatures  # [P, K]
    passive_entity: Array               # [P] int32 global entity row (E on pads)
    passive_rows: Array                 # [P] int32 flat row (n on pads)
    # projection table: local slot -> global feature index (-1 unused)
    projection: Array                 # [E, D_loc] int32
    # inverse of every ``sample_rows`` and ``passive_rows`` at once
    # (``flat_source_map``); read by ``rows_to_flat`` only
    flat_source: Array                # [n] int32 slot of each flat row

    @property
    def num_entities(self) -> int:
        return self.projection.shape[0]

    @property
    def num_flat_samples(self) -> int:
        return self.flat_source.shape[0]

    def rows_to_flat(self, block_values: Sequence[Array],
                     passive_values: Array, mesh=None) -> Array:
        """Every bucket's ``[E_b, S_b]`` values and the ``[P]`` passive
        values -> the flat ``[n]`` vector, by ONE gather: the buckets and
        the passive rows partition the flat frame, so the way back from
        ladder order is a permutation, read through its prepare-time
        inverse. Pad slots are never read; a flat row that no slot holds
        reads the trailing 0. Over all buckets this is
        ``EntityBlock.rows_from_flat``'s inverse. Over a ``mesh`` the
        entity-sharded values are made whole, each device gathers its share
        of the flat rows and the vector is made whole on every device
        (``parallel/mesh.made_whole``, ``gathered_whole``)."""
        pieces = [v.ravel() for v in block_values] + [passive_values]
        if mesh is not None:
            from photon_tpu.parallel import mesh as M
            axis = M.entity_axis(mesh)
            pieces = [M.made_whole(v, mesh, axis) for v in pieces]
        slots = jnp.concatenate(
            pieces + [jnp.zeros((1,), passive_values.dtype)])
        if mesh is not None:
            return M.gathered_whole(slots, self.flat_source, mesh)
        return slots.at[self.flat_source].get(mode="promise_in_bounds")

    @property
    def max_samples(self) -> int:
        return max((b.max_samples for b in self.blocks), default=0)

    @property
    def projected_dim(self) -> int:
        return self.projection.shape[1]

    def padding_waste(self) -> float:
        """(padded cells) / (real cells) over sample slots — the bucketing
        quality metric (SURVEY §7 risk (a))."""
        padded = sum(b.labels.size for b in self.blocks)
        xp = np if all(isinstance(b.weights, np.ndarray)
                       for b in self.blocks) else jnp
        real = sum(int(xp.sum(b.weights > 0)) for b in self.blocks)
        return padded / max(real, 1)


def flat_source_map(block_rows: Sequence[np.ndarray],
                    passive_rows: np.ndarray, n: int) -> np.ndarray:
    """``RandomEffectDataset.flat_source``: for every flat row its position
    in ``[bucket 0's slots raveled, bucket 1's, ..., the passive slots,
    one trailing 0.0]``, from the buckets' ``sample_rows`` and the
    ``passive_rows`` (host arrays; pads hold ``n`` or more). A row that no
    slot holds points at the trailing zero."""
    rows = np.concatenate([np.asarray(r).ravel() for r in block_rows]
                          + [np.asarray(passive_rows).ravel()])
    if len(rows) >= np.iinfo(np.int32).max:
        raise ValueError(f"{len(rows)} slots do not fit an int32 map")
    source = np.full(n, len(rows), np.int32)
    held = np.flatnonzero(rows < n)
    source[rows[held]] = held
    return source


def _splitmix64(x: np.ndarray) -> np.ndarray:
    """Deterministic sample-ordering hash (role of byteswap64(uid),
    RandomEffectDataset.scala:212-215)."""
    z = (x.astype(np.uint64) + np.uint64(0x9E3779B97F4A7C15))
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def _csr_of(rows) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Sparse rows -> (indptr [n+1], cols, vals); CSR-form rows from the
    native columnar ingest pass straight through; a dense [n, d] matrix
    is converted (vectorized) so dense feature shards work for random
    effects too."""
    from photon_tpu.game.dataset import CsrRows

    if isinstance(rows, CsrRows):
        return (rows.indptr, np.asarray(rows.cols, np.int64),
                np.asarray(rows.vals, np.float64))
    if isinstance(rows, np.ndarray):
        dense = np.asarray(rows, np.float64)
        r, cols = np.nonzero(dense)
        indptr = np.concatenate(
            [[0], np.cumsum(np.bincount(r, minlength=dense.shape[0]))])
        return indptr.astype(np.int64), cols.astype(np.int64), dense[r, cols]
    nnz = np.fromiter((len(r[0]) for r in rows), np.int64, len(rows))
    indptr = np.concatenate([[0], np.cumsum(nnz)])
    if len(rows):
        cols = np.concatenate([np.asarray(r[0], np.int64) for r in rows])
        vals = np.concatenate([np.asarray(r[1], np.float64) for r in rows])
    else:
        cols = np.zeros(0, np.int64)
        vals = np.zeros(0)
    return indptr, cols, vals


def _bucket_of(sizes: np.ndarray) -> np.ndarray:
    """Power-of-two size bucket id (sizes >= 1)."""
    return np.ceil(np.log2(np.maximum(sizes, 1))).astype(np.int64)


def pair_route(num_entities: int, dim: int, nonzeros: int) -> str:
    """How a shard's observed (entity, column) pairs find their local
    slots: ``"table"``, one int32 a key of the ``num_entities x dim`` key
    space, where that is no larger than the int64 sorted keys of its
    ``nonzeros`` active nonzeros (E x D <= 2 nnz); else ``"sort"``, the
    sorted distinct keys, searched (a wide vocabulary's sparse shard)."""
    return "table" if num_entities * dim <= 2 * nonzeros else "sort"


def _slot_lookup(route: str, uniq: np.ndarray, slot_of_pair: np.ndarray,
                 size: int):
    """key ``entity * D + column`` -> the pair's local slot, -1 where the
    pair is not kept; ``uniq`` holds the kept keys, ascending."""
    if route == "table":
        table = np.full(size, -1, np.int32)
        table[uniq] = slot_of_pair
        return table.take

    def search(keys: np.ndarray) -> np.ndarray:
        if not len(uniq):
            return np.full(len(keys), -1, np.int64)
        rank = np.minimum(np.searchsorted(uniq, keys), len(uniq) - 1)
        return np.where(uniq[rank] == keys, slot_of_pair[rank], -1)
    return search


def _read_nonzeros(rows, row_entity, row_cell, indptr, nnz, cols, D, slots,
                   counter):
    """The nonzeros of ``rows`` (of entities ``row_entity``), reached
    through their ``indptr`` ranges and looked up in ``slots``; ``counter``
    is ticked by the positions read. Returns ``K``, the most kept nonzeros
    of a row (at least 1), and of the kept nonzeros: where each lies in
    ``cols`` / ``vals``, its local slot, and its place ``row_cell[row] * K
    + its position among its row's kept nonzeros``."""
    lens = nnz[rows]
    ends = np.cumsum(lens)
    starts = ends - lens
    total = int(ends[-1]) if len(ends) else 0
    counter.inc(total)
    nz = np.repeat(indptr[rows] - starts, lens)
    nz += np.arange(total)                            # place in cols/vals
    key = np.repeat(row_entity * D, lens)
    key += cols[nz]
    slot = slots(key)
    del key
    if not total or slot.min() >= 0:                  # every one kept
        K = max(int(lens.max()) if total else 1, 1)
        place = np.repeat(row_cell * K - starts, lens)
        place += np.arange(total)
        return K, nz, slot, place
    kept = slot >= 0
    before = np.concatenate([[0], np.cumsum(kept)])   # kept ones before
    pos = before[:-1] - np.repeat(before[starts], lens)
    K = max(int(pos[kept].max()) + 1 if kept.any() else 1, 1)
    place = np.repeat(row_cell * K, lens)
    place += pos
    return K, nz[kept], slot[kept], place[kept]


def build_random_effect_dataset(
    df: GameDataFrame,
    config: RandomEffectDataConfiguration,
    vocab: EntityVocabulary,
    dtype=np.float32,
    scores_offsets: Optional[np.ndarray] = None,
    coordinate: Optional[str] = None,
    place: bool = True,
) -> RandomEffectDataset:
    """Fully-vectorized ingest: grouping, deterministic reservoir capping,
    Pearson feature selection, per-entity projection, bucketed ELL fill,
    passive split — no per-sample Python loops.

    Its host seconds are recorded, telemetry on or off, as ``Timed`` phases
    named for ``coordinate`` (the random-effect type when the caller gives
    none): ``ingest/prepare/<coordinate>/group`` (vocabulary, ordering,
    active/passive split, the observed pairs and their projection table,
    and the map from a pair's key ``entity * D + column`` to its local
    slot: an int32 table over the E x D keys or the sorted kept keys, as
    ``pair_route`` says from E, D and the active nonzeros, one tick of
    ``ingest.pair_route{coordinate, path=table|sort}``; no per-nonzero
    array of its own outlives it), ``.../bucket`` (the size
    ladder, and ONE stable sort of the active samples into bucket-major
    order), ``.../pad`` (one record a bucket: the padded fill of that
    bucket's block from a contiguous slice of its samples and, through
    their ``indptr`` ranges, their nonzeros, each looked up in the map
    where it is read; it reads no other bucket's, and ticks the counter
    ``ingest.pad_nonzeros{coordinate}`` by the nonzero positions it reads:
    over the buckets, the nonzeros of the active samples, once) and
    ``.../passive`` (the same for the passive samples' nonzeros alone,
    ticking ``ingest.passive_nonzeros{coordinate}``);
    ``ingest/h2d/<coordinate>`` around each placement (what the host
    spends in ``jnp.asarray``: nothing waits for the copy), the placed
    bytes going to the counter ``ingest.h2d_bytes{coordinate}``;
    ``ingest/stats`` around the padding-waste count, which compiles a
    tiny program a bucket shape.

    ``place=False`` leaves every array on the host, for a caller that
    places them itself (a mesh: ``parallel/mesh.shard_entity_blocks``):
    no ``ingest/h2d`` phase, nothing counted as placed, and the
    padding-waste count taken on the host."""
    return _build_random_effect_dataset(df, config, vocab, dtype,
                                        scores_offsets, coordinate,
                                        on_device=place)


def _build_random_effect_dataset(df, config, vocab, dtype=np.float32,
                                 scores_offsets=None, coordinate=None,
                                 route=pair_route, on_device=True):
    """``build_random_effect_dataset``; ``route`` is ``pair_route`` or, in
    the tests alone, a function that forces one."""
    re_type = config.random_effect_type
    coordinate = coordinate or re_type
    prepare, h2d = f"ingest/prepare/{coordinate}", f"ingest/h2d/{coordinate}"
    phase = functools.partial(Timed, level=logging.DEBUG)
    if on_device:
        put, placing = jnp.asarray, functools.partial(phase, h2d)
    else:
        put, placing = (lambda a: a), contextlib.nullcontext
    with phase(f"{prepare}/group"):
        shard = df.feature_shards[config.feature_shard_id]
        # sparse row lists, columnar CsrRows, and dense [n, d] matrices all
        # funnel through _csr_of into the same columnar pipeline
        shard = _maybe_random_project(shard, config)
        n = df.num_samples
        D = shard.dim

        entity_idx = vocab.build(re_type, df.id_tags[re_type]).astype(np.int64)
        E = vocab.size(re_type)
        base_offsets = np.zeros(n) if df.offsets is None else np.asarray(df.offsets, np.float64)
        if scores_offsets is not None:
            base_offsets = base_offsets + np.asarray(scores_offsets, np.float64)
        weights = np.ones(n) if df.weights is None else np.asarray(df.weights, np.float64)
        resp = np.asarray(df.response, np.float64)

        indptr, cols, vals = _csr_of(shard.rows)
        nnz = np.diff(indptr)

        # -- deterministic ordering within entities + active/passive split -------
        counts = np.bincount(entity_idx, minlength=E)
        keys = _splitmix64(np.arange(n, dtype=np.uint64))
        order = np.lexsort((keys, entity_idx))           # by (entity, hash)
        starts = np.concatenate([[0], np.cumsum(counts)])
        pos = np.arange(n) - np.repeat(starts[:-1], counts)  # rank within entity

        e_sorted = entity_idx[order]
        active_sorted = np.ones(n, bool)
        if config.active_data_lower_bound is not None:
            active_sorted &= counts[e_sorted] >= config.active_data_lower_bound
        if config.active_data_upper_bound is not None:
            active_sorted &= pos < config.active_data_upper_bound
        passive_sorted = ~active_sorted
        if config.active_data_upper_bound is not None and not config.keep_passive_data:
            # over-cap samples are dropped entirely; below-lower-bound samples
            # stay passive (they are scored, just never trained on)
            over_cap = pos >= config.active_data_upper_bound
            if config.active_data_lower_bound is not None:
                over_cap &= counts[e_sorted] >= config.active_data_lower_bound
            passive_sorted &= ~over_cap

        active = np.zeros(n, bool)
        active[order] = active_sorted
        passive = np.zeros(n, bool)
        passive[order] = passive_sorted
        act_counts = np.bincount(entity_idx[active], minlength=E)

        # -- observed (entity, feature) pairs over ACTIVE data -------------------
        pair = np.repeat(entity_idx * D, nnz)
        pair += cols                                      # int64 composite key
        keep_nz = None if active.all() else np.repeat(active, nnz)
        if keep_nz is not None:
            pair = pair[keep_nz]
        path = route(E, D, len(pair))
        registry.counter("ingest.pair_route", coordinate=coordinate,
                         path=path).inc()
        if path == "table":
            # the sorted distinct keys, and (below) a key's rank among them,
            # by direct address: no sort, no search
            present = np.zeros(E * D, bool)
            present[pair] = True
            uniq = np.flatnonzero(present)
        else:
            uniq = np.unique(pair)

        # -- optional Pearson feature selection (reference: LocalDataset:122) ----
        if config.features_to_samples_ratio is not None and len(uniq):
            ratio = config.features_to_samples_ratio
            k_per_entity = np.maximum((ratio * act_counts).astype(np.int64), 1)
            if path == "table":   # exclusive count of present keys below
                rank = (np.cumsum(present) - present)[pair]
            else:
                rank = np.searchsorted(uniq, pair)
            v, y = vals, np.repeat(resp, nnz)
            if keep_nz is not None:
                v, y = v[keep_nz], y[keep_nz]
            scores = _pearson_scores_vectorized(
                uniq, rank, v, y, entity_idx, resp, weights, active, E, D)
            del rank, v, y
            u_e = uniq // D
            sel_order = np.lexsort((-scores, u_e))
            u_starts = np.searchsorted(u_e[sel_order], np.arange(E))
            sel_pos = np.arange(len(uniq)) - u_starts[u_e[sel_order]]
            need_cap = k_per_entity[u_e[sel_order]]
            keep_pair = np.zeros(len(uniq), bool)
            keep_pair[sel_order[sel_pos < need_cap]] = True
            # entities whose feature count is within bound keep everything
            feat_counts = np.bincount(u_e, minlength=E)
            within = feat_counts[u_e] <= np.maximum(
                (ratio * act_counts[u_e]).astype(np.int64), 1)
            keep_pair |= within
            uniq = uniq[keep_pair]
        del keep_nz, pair

        # -- projection table ----------------------------------------------------
        u_e = uniq // D
        u_f = uniq % D
        d_loc_per_entity = np.bincount(u_e, minlength=E) if len(uniq) else np.zeros(E, np.int64)
        D_loc = max(int(d_loc_per_entity.max()) if E else 1, 1)
        u_starts = np.searchsorted(u_e, np.arange(E + 1))
        slot_of_pair = np.arange(len(uniq)) - u_starts[u_e]
        projection = np.full((E, D_loc), -1, np.int32)
        if len(uniq):
            projection[u_e, slot_of_pair] = u_f.astype(np.int32)

        # -- local slot of a kept pair, looked up where a nonzero is read -------
        slots = _slot_lookup(path, uniq, slot_of_pair, E * D)

    # -- bucketed active blocks ---------------------------------------------
    with phase(f"{prepare}/bucket"):
        has_active = act_counts > 0
        bucket_id = np.where(has_active, _bucket_of(act_counts), -1)
        uniq_buckets = np.unique(bucket_id[bucket_id >= 0])
        cap = config.max_entity_buckets
        if cap and len(uniq_buckets) > cap:
            # coarsen: merge adjacent pow-2 buckets into at most `cap` groups
            # (each group pads to its largest member's S_b) — bounded compile
            # count at the cost of extra padding, both reported below
            groups = np.array_split(uniq_buckets, cap)
            lut = np.arange(int(uniq_buckets.max()) + 1)
            for g in groups:
                lut[g] = g[-1]
            bucket_id = np.where(bucket_id >= 0, lut[np.maximum(bucket_id, 0)], -1)
        blocks: List[EntityBlock] = []
        block_rows: List[np.ndarray] = []     # host sample_rows a bucket

        # active samples sorted by (entity, hash) and within cap
        act_idx_sorted = order[active_sorted]             # flat rows, grouped
        act_pos = pos[active_sorted]                      # rank within entity
        act_entity = entity_idx[act_idx_sorted]

        # bucket-major order, ONCE for all buckets: a bucket's entities in
        # ascending global row are its block rows, and a stable sort of the
        # active samples by their entity's bucket keeps them in (entity,
        # hash) order inside it, so each bucket below is a contiguous slice
        live = np.flatnonzero(bucket_id >= 0)             # entities in a bucket
        buckets = np.unique(bucket_id[live])
        ents_sorted = live[np.argsort(bucket_id[live], kind="stable")]
        ent_bounds = np.append(
            np.searchsorted(bucket_id[ents_sorted], buckets), len(live))
        row_of_entity = np.full(E, -1, np.int64)          # block row, any bucket
        row_of_entity[ents_sorted] = (
            np.arange(len(ents_sorted))
            - np.repeat(ent_bounds[:-1], np.diff(ent_bounds)))
        # pow-2 bucket ids are under 64: an int8 key sorts by counting
        act_bucket = bucket_id[act_entity].astype(np.int8)
        by_bucket = np.argsort(act_bucket, kind="stable")
        act_bounds = np.append(
            np.searchsorted(act_bucket[by_bucket], buckets.astype(np.int8)),
            len(by_bucket))
        rows_sorted = act_idx_sorted[by_bucket]           # flat sample rows
        r_sorted = row_of_entity[act_entity[by_bucket]]   # block row
        c_sorted = act_pos[by_bucket]                     # block column
        pad_nonzeros = registry.counter("ingest.pad_nonzeros",
                                        coordinate=coordinate)

    for i in range(len(buckets)):
        with phase(f"{prepare}/pad"):
            # everything below is as long as THIS bucket's entities, samples
            # or nonzeros: nothing of the other buckets is read or built
            ents = ents_sorted[ent_bounds[i]:ent_bounds[i + 1]]
            E_b = len(ents)
            S_b = int(act_counts[ents].max())
            in_b = slice(act_bounds[i], act_bounds[i + 1])
            rows_flat = rows_sorted[in_b]
            cell = r_sorted[in_b] * S_b + c_sorted[in_b]  # slot in [E_b * S_b]

            labels_b = np.zeros((E_b, S_b), dtype)
            offsets_b = np.zeros((E_b, S_b), dtype)
            weights_b = np.zeros((E_b, S_b), dtype)
            rows_b = np.full((E_b, S_b), n, np.int32)
            labels_b.reshape(-1)[cell] = resp[rows_flat]
            offsets_b.reshape(-1)[cell] = base_offsets[rows_flat]
            weights_b.reshape(-1)[cell] = weights[rows_flat]
            rows_b.reshape(-1)[cell] = rows_flat
            block_rows.append(rows_b)

            # ELL features: the nonzeros of this bucket's samples, gathered
            # through their ``indptr`` ranges (each is visited by one bucket)
            K_b, nz, nz_slot, place = _read_nonzeros(   # in [E_b * S_b * K_b]
                rows_flat, ents[r_sorted[in_b]], cell, indptr, nnz, cols, D,
                slots, pad_nonzeros)
            f_idx = np.zeros((E_b, S_b, K_b), np.int32)
            f_val = np.zeros((E_b, S_b, K_b), dtype)
            f_idx.reshape(-1)[place] = nz_slot
            f_val.reshape(-1)[place] = vals[nz]

        with placing():
            blocks.append(EntityBlock(
                features=F.SparseFeatures(put(f_idx), put(f_val)),
                labels=put(labels_b),
                offsets=put(offsets_b),
                weights=put(weights_b),
                sample_rows=put(rows_b),
                entity_rows=put(ents.astype(np.int32)),
            ))

    # -- passive block (projected through each entity's local map) -----------
    with phase(f"{prepare}/passive"):
        pas_rows = np.flatnonzero(passive)
        P = max(len(pas_rows), 1)
        K_p, pas_nz, pas_slot, place = _read_nonzeros(
            pas_rows, entity_idx[pas_rows], np.arange(len(pas_rows)), indptr,
            nnz, cols, D, slots,
            registry.counter("ingest.passive_nonzeros", coordinate=coordinate))
        p_idx = np.zeros((P, K_p), np.int32)
        p_val = np.zeros((P, K_p), dtype)
        p_entity = np.full(P, E, np.int32)
        p_rows = np.full(P, n, np.int32)
        if len(pas_rows):
            p_entity[: len(pas_rows)] = entity_idx[pas_rows]
            p_rows[: len(pas_rows)] = pas_rows
            p_idx.reshape(-1)[place] = pas_slot
            p_val.reshape(-1)[place] = vals[pas_nz]
        flat_source = flat_source_map(block_rows, p_rows, n)

    with placing():
        ds = RandomEffectDataset(
            blocks=tuple(blocks),
            passive_features=F.SparseFeatures(put(p_idx), put(p_val)),
            passive_entity=put(p_entity),
            passive_rows=put(p_rows),
            projection=put(projection),
            flat_source=put(flat_source),
        )
    if on_device:
        count_placed(coordinate, ds)
    # ingest telemetry (VERDICT r2 weak #8): block count == distinct XLA
    # compiles for this coordinate's solve; padding_waste == padded/real
    # sample cells
    with phase("ingest/stats"):
        waste = ds.padding_waste()
    logger.info(
        "random-effect %r ingest: %d entities, %d block(s) (bucket cap %s), "
        "padding waste %.3f, shapes %s",
        re_type, E, len(ds.blocks), cap, waste,
        [(b.num_rows, b.max_samples, b.features.values.shape[-1])
         for b in ds.blocks])
    return ds


def _maybe_random_project(shard, config: RandomEffectDataConfiguration):
    """RANDOM projector: replace the shard with dense rows in the shared
    Gaussian-projected space (the pipeline then treats every projected dim
    as observed for every entity)."""
    from photon_tpu.game.dataset import FeatureShard

    rp = config.random_projection(shard.dim)
    if rp is None:
        return shard
    dense = (rp.project_dense(np.asarray(shard.rows, np.float64))
             if shard.is_dense else rp.project_rows(shard.rows))
    from photon_tpu.game.dataset import CsrRows

    # columnar handover (every projected dim is observed for every row):
    # no per-row Python tuples — _csr_of passes CsrRows straight through
    return FeatureShard(CsrRows.from_dense(dense), rp.projected_dim)


def _pearson_scores_vectorized(uniq, rank, v, y, entity_idx, resp, weights,
                               active, E, D) -> np.ndarray:
    """|Pearson corr(feature, label)| per observed (entity, feature) pair
    over active samples (reference: LocalDataset.computePearsonCorrelation
    Score :122; constant nonzero columns — intercepts — score 1). ``rank``,
    ``v``, ``y``: each active nonzero's pair's index in ``uniq``, its value
    and its sample's label, in the shard's order."""
    act_counts = np.bincount(entity_idx[active], minlength=E).astype(np.float64)
    # per-entity label stats over active samples
    lab_sum = np.bincount(entity_idx[active], weights=resp[active], minlength=E)
    lab_sq = np.bincount(entity_idx[active], weights=resp[active] ** 2, minlength=E)
    with np.errstate(invalid="ignore", divide="ignore"):
        lab_mean = lab_sum / act_counts
        lab_var = lab_sq / act_counts - lab_mean ** 2
    lab_sd = np.sqrt(np.maximum(lab_var, 0))

    nfeat = len(uniq)
    sums = np.bincount(rank, weights=v, minlength=nfeat)
    sqs = np.bincount(rank, weights=v * v, minlength=nfeat)
    u_e = uniq // D
    ly = y - lab_mean[u_e[rank]]
    xy = np.bincount(rank, weights=v * ly, minlength=nfeat)

    cnt = act_counts[u_e]
    with np.errstate(invalid="ignore", divide="ignore"):
        mean = sums / cnt
        var = sqs / cnt - mean ** 2
        corr = np.abs(xy / cnt) / np.sqrt(np.maximum(var, 0)) / np.maximum(
            lab_sd[u_e], 1e-12)
    corr[~np.isfinite(corr)] = 0.0
    is_const = (var <= 1e-12) & (np.abs(mean) > 0)
    corr[is_const] = 1.0
    return corr


def project_for_scoring(
    df: GameDataFrame,
    config: RandomEffectDataConfiguration,
    vocab: EntityVocabulary,
    projection: np.ndarray,
    dtype=np.float32,
) -> Tuple[F.SparseFeatures, Array]:
    """Project an evaluation frame into each sample's entity-local feature
    space (reference: IndexMapProjector applied to scoring data). Unseen
    entities -> entity index E (out of range => zero score); unmapped
    features are dropped. Fully vectorized."""
    shard = df.feature_shards[config.feature_shard_id]
    shard = _maybe_random_project(shard, config)
    n = df.num_samples
    D = shard.dim
    proj_np = np.asarray(projection)
    E, d_loc = proj_np.shape

    entity_idx = vocab.lookup(config.random_effect_type,
                              df.id_tags[config.random_effect_type]).astype(np.int64)
    ent_out = np.where(entity_idx < 0, E, entity_idx).astype(np.int32)

    # (entity, feature) -> slot lookup table, rebuilt from the projection
    valid = proj_np >= 0
    pe, ps = np.nonzero(valid)
    pkeys = pe.astype(np.int64) * D + proj_np[pe, ps]
    # projection rows are slot-ordered by ascending feature id, so pkeys
    # is sorted within each entity and across entities
    porder = np.argsort(pkeys, kind="stable")
    pkeys_sorted = pkeys[porder]
    pslots_sorted = ps[porder].astype(np.int64)

    indptr, cols, vals = _csr_of(shard.rows)
    nnz = np.diff(indptr)
    s_nz = np.repeat(np.arange(n), nnz)
    e_nz = entity_idx[s_nz]
    in_vocab = e_nz >= 0
    key_nz = np.where(in_vocab, e_nz, 0) * D + cols
    rank = np.searchsorted(pkeys_sorted, key_nz)
    rank = np.minimum(rank, max(len(pkeys_sorted) - 1, 0))
    kept = in_vocab & (len(pkeys_sorted) > 0)
    if len(pkeys_sorted):
        kept &= pkeys_sorted[rank] == key_nz
    slot_nz = pslots_sorted[rank] if len(pkeys_sorted) else np.zeros(len(cols), np.int64)

    if len(cols):
        kept_i = kept.astype(np.int64)
        c = np.cumsum(kept_i)
        excl = c - kept_i
        base = np.repeat(excl[np.minimum(indptr[:-1], len(excl) - 1)], nnz)
        k_pos = excl - base
    else:
        k_pos = np.zeros(0, np.int64)

    K = max(int(k_pos[kept].max()) + 1 if kept.any() else 1, 1)
    out_idx = np.zeros((n, K), np.int32)
    out_val = np.zeros((n, K), dtype)
    out_idx[s_nz[kept], k_pos[kept]] = slot_nz[kept].astype(np.int32)
    out_val[s_nz[kept], k_pos[kept]] = vals[kept]
    return (F.SparseFeatures(jnp.asarray(out_idx), jnp.asarray(out_val)),
            jnp.asarray(ent_out))


# -- cold-tier warm starts ----------------------------------------------------

def replay_cold_rows(ds_proj: np.ndarray, cold_proj: np.ndarray,
                     cold_coef: np.ndarray) -> np.ndarray:
    """Map cold-store coefficient rows into this dataset's local slot
    layout by global column id.

    Both layouts are slot-sorted ascending with -1 padding (the dataset
    by construction, the cold store normalized at write —
    io/cold_store.py), but the two column SETS can differ: the cold model
    may have been trained on a different sample of each entity's
    features. Columns present in both carry their cold value; dataset
    slots with no cold counterpart warm-start at zero."""
    if ds_proj.shape[0] != cold_proj.shape[0]:
        raise ValueError(
            f"row count mismatch: {ds_proj.shape[0]} dataset rows vs "
            f"{cold_proj.shape[0]} cold rows")
    # pairwise column match per entity; slot widths are small, so the
    # [E_b, D, K] broadcast stays cheap relative to the mmap read itself
    eq = ((ds_proj[:, :, None] == cold_proj[:, None, :])
          & (ds_proj[:, :, None] >= 0))
    hit = eq.any(axis=2)
    pos = eq.argmax(axis=2)
    vals = np.take_along_axis(
        np.asarray(cold_coef, np.float32), pos, axis=1)
    return np.where(hit, vals, np.float32(0.0))


def warm_start_from_cold_store(cold, entity_names: Sequence[str],
                               projection, *,
                               block_rows: int = 262144) -> np.ndarray:
    """Stream a ``ColdStore`` into a host-RAM warm-start block aligned to
    this dataset's entity rows and slot layout.

    ``entity_names[r]`` is the entity id of dataset row ``r`` (the ingest
    vocabulary's ordering). Entities absent from the cold store — new
    since the warm model was written — start at zero. Peak memory is the
    host [E, K] output plus one streamed block; nothing touches the
    device."""
    proj = np.asarray(projection)
    out = np.zeros(proj.shape, np.float32)
    row_of = {str(name): r for r, name in enumerate(entity_names)}
    for _lo, ids, coef_b, proj_b in cold.iter_blocks(block_rows):
        rows = np.fromiter((row_of.get(str(i), -1) for i in ids),
                           np.int64, count=len(ids))
        sel = rows >= 0
        if not sel.any():
            continue
        ds_rows = rows[sel]
        out[ds_rows] = replay_cold_rows(proj[ds_rows], proj_b[sel],
                                        np.asarray(coef_b)[sel])
    return out
