"""Columnar native ingest: Avro feature bags -> CSR arrays, no per-feature
Python objects.

The generic path (io/avro.py + io/data_io.py) builds a dict per record and
a (indices, values) pair per row — fine for fixtures, too slow to feed
chips (SURVEY §7 risk (e)). This path decodes feature bags INSIDE the C
extension (photon_tpu/native) straight into growable id/value buffers with
an interned name-term vocabulary, then assembles the same ``GameDataFrame``
with ``CsrRows`` shards. Everything non-bag still decodes generically, and
any unsupported schema shape falls back to the generic path.

Semantics mirror records_to_game_dataframe exactly: duplicate (name, term)
within a record keep the LAST value; keys unseen by a supplied index map
are dropped; an intercept slot is appended to every row unless the data
already carries one.
"""

from __future__ import annotations

import io
import logging
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from photon_tpu.game.dataset import CsrRows, FeatureShard, GameDataFrame
from photon_tpu.io.avro import AvroFileReader, SchemaError, list_avro_files
from photon_tpu.io.data_io import (
    METADATA_COLUMN,
    OFFSET_COLUMN,
    RESPONSE_COLUMNS,
    WEIGHT_COLUMN,
    FeatureShardConfiguration,
)
from photon_tpu.io.index_map import DELIMITER, INTERCEPT_KEY, IndexMap

logger = logging.getLogger(__name__)


def _bag_spec(root_program, schema, bag_name: str) -> Optional[Tuple]:
    """(field_index, role_name, role_term, role_value, union_branch) for a
    top-level field holding array<record{name, term, value}> (optionally
    behind ["null", array]); None when the shape doesn't match."""
    fields = schema.get("fields", [])
    for fi, f in enumerate(fields):
        if f["name"] != bag_name:
            continue
        t = f["type"]
        branch = -1
        if isinstance(t, list):
            arr = [i for i, b in enumerate(t)
                   if isinstance(b, dict) and b.get("type") == "array"]
            nulls = [i for i, b in enumerate(t) if b == "null"]
            if len(arr) != 1 or len(nulls) + len(arr) != len(t):
                return None
            branch = arr[0]
            t = t[branch]
        if not isinstance(t, dict) or t.get("type") != "array":
            return None
        item = t["items"]
        if not isinstance(item, dict) or item.get("type") != "record":
            return None
        ifields = item.get("fields", [])
        if len(ifields) != 3:
            return None
        roles = {}
        for pos, itf in enumerate(ifields):
            ft = itf["type"]
            if itf["name"] == "name" and ft == "string":
                roles["name"] = pos
            elif itf["name"] == "term" and ft == "string":
                roles["term"] = pos
            elif itf["name"] == "value" and ft == "double":
                roles["value"] = pos
        if set(roles) != {"name", "term", "value"}:
            return None
        total = 1 if branch < 0 else len(f["type"])
        return (fi, roles["name"], roles["term"], roles["value"], branch,
                total)
    return None


class _BagAccumulator:
    """Merges per-block columnar outputs; block-local ids -> global ids."""

    def __init__(self):
        self.vocab: Dict[str, int] = {}
        self.ids: List[np.ndarray] = []
        self.vals: List[np.ndarray] = []
        self.row_nnz: List[np.ndarray] = []

    def add_block(self, rowptr_b: bytes, ids_b: bytes, vals_b: bytes,
                  keys: List[str]) -> None:
        lut = np.empty(len(keys), np.int32)
        vocab = self.vocab
        for i, k in enumerate(keys):
            g = vocab.get(k)
            if g is None:
                g = len(vocab)
                vocab[k] = g
            lut[i] = g
        ids = np.frombuffer(ids_b, "<i4")
        rowptr = np.frombuffer(rowptr_b, "<i8")
        self.ids.append(lut[ids] if len(keys) else ids)
        self.vals.append(np.frombuffer(vals_b, "<f8"))
        self.row_nnz.append(np.diff(rowptr))

    def csr(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        nnz = (np.concatenate(self.row_nnz) if self.row_nnz
               else np.zeros(0, np.int64))
        indptr = np.concatenate([[0], np.cumsum(nnz)]).astype(np.int64)
        cols = (np.concatenate(self.ids) if self.ids
                else np.zeros(0, np.int32))
        vals = (np.concatenate(self.vals) if self.vals else np.zeros(0))
        return indptr, cols, vals


def _dedup_last_wins(indptr, cols, vals, dim):
    """Within each row keep the LAST value per column id (the generic
    path's duplicate semantics; order within a row is irrelevant to every
    consumer — margins are sums)."""
    n = len(indptr) - 1
    nnz = np.diff(indptr)
    if nnz.sum() == 0:
        return indptr, cols, vals
    row_of = np.repeat(np.arange(n, dtype=np.int64), nnz)
    key = row_of * np.int64(dim) + cols.astype(np.int64)
    order = np.arange(len(key))
    # stable sort by key; within a key, original order ascends -> take last
    perm = np.lexsort((order, key))
    k_sorted = key[perm]
    is_last = np.concatenate([k_sorted[1:] != k_sorted[:-1], [True]])
    keep = perm[is_last]
    keep.sort()
    new_cols = cols[keep]
    new_vals = vals[keep]
    new_row = row_of[keep]
    new_nnz = np.bincount(new_row, minlength=n).astype(np.int64)
    new_indptr = np.concatenate([[0], np.cumsum(new_nnz)])
    return new_indptr, new_cols, new_vals


def read_game_frame(
    input_dirs: Sequence[str],
    shard_configs: Dict[str, FeatureShardConfiguration],
    index_maps: Optional[Dict[str, IndexMap]] = None,
    id_tag_columns: Sequence[str] = (),
    response_columns: Sequence[str] = RESPONSE_COLUMNS,
    return_records: bool = False,
) -> Optional[Tuple]:
    """Columnar read of Avro dirs -> (GameDataFrame, index maps), or None
    when the native decoder / schema shape is unavailable (caller falls
    back to read_records + records_to_game_dataframe). With
    ``return_records`` the (bag-free) record dicts ride along as a third
    element — drivers use them for uid passthrough and late id-tag
    discovery."""
    from photon_tpu import native

    if native._load() is None:
        return None
    # v1 scope: single-bag shards (multi-bag merges fall back)
    for cfg in shard_configs.values():
        if len(cfg.feature_bags) != 1:
            return None

    bag_names = sorted({cfg.feature_bags[0]
                        for cfg in shard_configs.values()})
    accs = {b: _BagAccumulator() for b in bag_names}
    records: List[dict] = []

    paths = [p for d in input_dirs for p in list_avro_files(d)]
    if not paths:
        raise FileNotFoundError(f"no avro files under {list(input_dirs)}")
    from photon_tpu.resilience import io as rio

    for path in paths:
        with io.BytesIO(rio.read_bytes(path, op="ingest_read")) as f:
            reader = AvroFileReader(f)
            specs = tuple(_bag_spec(None, reader.schema, b)
                          for b in bag_names)
            if any(s is None for s in specs):
                logger.info("fast ingest: bag shape unsupported in %s — "
                            "falling back", path)
                return None
            prog = reader._native   # compiled once by AvroFileReader
            if not prog:
                return None
            mod = native._load()
            import zlib
            dec = reader._body
            while not dec.eof():
                count = dec.read_long()
                nbytes = dec.read_long()
                raw = dec.read(nbytes)
                if reader.codec == "deflate":
                    raw = zlib.decompress(raw, -15)
                elif reader.codec != "null":
                    raise SchemaError(f"unsupported codec {reader.codec}")
                recs, bags_out = mod.decode_columnar(
                    prog._program, raw, count, specs, DELIMITER)
                records.extend(recs)
                for b, out in zip(bag_names, bags_out):
                    accs[b].add_block(*out)
                sync = dec.read(16)
                if sync != reader._sync:
                    raise SchemaError("sync marker mismatch")

    n = len(records)
    if n == 0:
        # match read_records' contract: empty partitions error clearly
        # instead of yielding a degenerate 0-sample frame
        raise ValueError(f"no Avro records under {list(input_dirs)}")
    # scalar columns (cheap Python loop: one dict access per column)
    response = np.zeros(n)
    offsets = np.zeros(n)
    weights = np.ones(n)
    any_offset = any_weight = False
    id_tags: Dict[str, List[str]] = {c: [None] * n for c in id_tag_columns}
    for i, rec in enumerate(records):
        for col in response_columns:
            if rec.get(col) is not None:
                response[i] = float(rec[col])
                break
        else:
            raise KeyError(f"record {i} has none of {response_columns}")
        if rec.get(OFFSET_COLUMN) is not None:
            offsets[i] = float(rec[OFFSET_COLUMN])
            any_offset = True
        if rec.get(WEIGHT_COLUMN) is not None:
            weights[i] = float(rec[WEIGHT_COLUMN])
            any_weight = True
        if id_tag_columns:
            meta = rec.get(METADATA_COLUMN) or {}
            for col in id_tag_columns:
                v = rec.get(col, meta.get(col))
                if v is None:
                    raise KeyError(f"record {i} missing id tag column {col!r}")
                id_tags[col][i] = str(v)

    # index maps + per-shard CSR in final index space
    built_maps: Dict[str, IndexMap] = {}
    shards: Dict[str, FeatureShard] = {}
    for sid, cfg in shard_configs.items():
        bag = cfg.feature_bags[0]
        acc = accs[bag]
        indptr, cols, vals = acc.csr()
        if index_maps is None:
            imap = IndexMap.from_keys(acc.vocab.keys(),
                                      add_intercept=cfg.has_intercept)
        else:
            imap = index_maps[sid]
        built_maps[sid] = imap
        # vocabulary id -> final index (-1 drops, matching the generic path)
        lut = np.full(max(len(acc.vocab), 1), -1, np.int32)
        for k, gid in acc.vocab.items():
            lut[gid] = imap.get_index(k)
        mapped = lut[cols] if len(cols) else cols.astype(np.int32)
        keep = mapped >= 0
        if not keep.all():
            row_of = np.repeat(np.arange(n, dtype=np.int64),
                               np.diff(indptr))[keep]
            new_nnz = np.bincount(row_of, minlength=n).astype(np.int64)
            indptr = np.concatenate([[0], np.cumsum(new_nnz)])
            mapped = mapped[keep]
            vals = vals[keep]
        dim = imap.feature_dimension
        if cfg.has_intercept:
            j = imap.get_index(INTERCEPT_KEY)
            if j >= 0:
                # PREPEND one intercept slot per row; rows that carry an
                # explicit intercept keep the data value (last wins)
                nnz0 = np.diff(indptr)
                new_indptr = np.concatenate(
                    [[0], np.cumsum(nnz0 + 1)]).astype(np.int64)
                total = int(new_indptr[-1])
                new_cols = np.empty(total, mapped.dtype if len(mapped)
                                    else np.int32)
                new_vals = np.empty(total, vals.dtype if len(vals)
                                    else np.float64)
                head = new_indptr[:-1]
                new_cols[head] = j
                new_vals[head] = 1.0
                is_data = np.ones(total, bool)
                is_data[head] = False
                new_cols[is_data] = mapped
                new_vals[is_data] = vals
                indptr, mapped, vals = new_indptr, new_cols, new_vals
        indptr, mapped, vals = _dedup_last_wins(indptr, mapped, vals, dim)
        shards[sid] = FeatureShard(
            CsrRows(indptr, mapped.astype(np.int32), vals), dim)

    frame = GameDataFrame(
        num_samples=n,
        response=response,
        feature_shards=shards,
        offsets=offsets if any_offset else None,
        weights=weights if any_weight else None,
        id_tags=id_tags,
    )
    if return_records:
        return frame, built_maps, records
    return frame, built_maps


def read_frame_with_fallback(
    input_dirs: Sequence[str],
    shard_configs: Dict[str, FeatureShardConfiguration],
    index_maps: Optional[Dict[str, IndexMap]] = None,
    id_tag_columns: Sequence[str] = (),
    return_records: bool = False,
):
    """The drivers' shared ingest ladder: columnar native path first,
    generic record path on any unsupported shape or non-fatal failure.
    Genuine data errors (missing files, empty partitions, corruption)
    raise identically on BOTH arms — behavior must never depend on
    whether the C extension compiled."""
    from photon_tpu.io.data_io import (
        build_index_maps,
        read_records,
        records_to_game_dataframe,
    )

    out = None
    try:
        out = read_game_frame(input_dirs, shard_configs,
                              index_maps=index_maps,
                              id_tag_columns=id_tag_columns,
                              return_records=return_records)
    except (OSError, KeyError, ValueError):
        raise
    except Exception as e:  # noqa: BLE001 — the fast path must never be fatal
        logger.warning("fast ingest failed (%r), using generic path", e)
    # which arm ran is observable (chip_smoke.py prints it)
    from photon_tpu.obs.metrics import registry
    registry.counter("ingest.frames",
                     path="native" if out is not None else "python").inc()
    if out is not None:
        return out
    records = read_records(list(input_dirs))  # raises on empty, both arms
    maps = index_maps if index_maps is not None else build_index_maps(
        records, shard_configs)
    frame = records_to_game_dataframe(records, shard_configs, maps,
                                      id_tag_columns=id_tag_columns)
    if return_records:
        return frame, maps, records
    return frame, maps
