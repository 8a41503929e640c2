"""Seeded generators: every input of every cell comes from a seed.

numpy only, no JAX and no photon_tpu, so the same seed gives the same bytes
on the sandbox's CPU and on the chip's host, whatever the thread count.

The Zipf rank and the arrival gap are the arithmetic of
``photon_tpu/serving/replay.py`` (``_zipf_rank``, the exponential gap in
``generate``), vectorised; ``benchmark/tests/test_rehearse.py`` holds them
to the original on the same uniforms. The bounded Zipf of the training rows
is ``bench.py::zipf_assign``'s distribution, drawn by inverse CDF.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import os
import zlib
from typing import Dict, Optional

import numpy as np

BLOCK_ROWS = 1 << 16        # one child stream per block of rows


def stream(seed: int, *names) -> np.random.SeedSequence:
    """The seed sequence of one named stream under ``seed``."""
    return np.random.SeedSequence(
        [int(seed)] + [zlib.crc32(str(n).encode()) for n in names])


def _rng(seq: np.random.SeedSequence) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64(seq))


def _threads() -> int:
    return max(1, min(8, (os.cpu_count() or 2) - 1))


def normal_f32(rows: int, width: int, seq: np.random.SeedSequence,
               scale: float = 1.0, unit_rows: bool = False) -> np.ndarray:
    """[rows, width] float32 standard normals times ``scale``, or with
    every row scaled to unit length. Drawn by row block, one child stream
    a block, so the bytes do not depend on how many threads drew them."""
    out = np.empty((rows, width), np.float32)
    n_blocks = -(-rows // BLOCK_ROWS)
    children = seq.spawn(n_blocks)

    def fill(i: int) -> None:
        block = out[i * BLOCK_ROWS:(i + 1) * BLOCK_ROWS]
        _rng(children[i]).standard_normal(out=block, dtype=np.float32)
        if unit_rows:
            block /= np.sqrt(np.einsum("nk,nk->n", block, block))[:, None]
        elif scale != 1.0:
            block *= np.float32(scale)

    with concurrent.futures.ThreadPoolExecutor(_threads()) as pool:
        list(pool.map(fill, range(n_blocks)))
    return out


# --------------------------------------------------------------------------
# which entity a row belongs to
# --------------------------------------------------------------------------

def zipf_bounded_shares(count: int, a: float) -> np.ndarray:
    """p_k proportional to k^-a over k = 1..count (entity 0 the hottest)."""
    p = 1.0 / np.arange(1, count + 1, dtype=np.float64) ** a
    return p / p.sum()


def min_power_law_shares(count: int, rows_per_entity: float, min_rows: int,
                         tail: float, tail_cap: float,
                         seq: np.random.SeedSequence) -> np.ndarray:
    """Every entity's expected share of the rows when each has
    ``min_rows`` and the rest follow a Lomax(``tail``) draw truncated at
    ``tail_cap``, scaled so the mean is ``rows_per_entity``."""
    u = _rng(seq).random(count)
    extra = np.minimum((1.0 - u) ** (-1.0 / tail) - 1.0, tail_cap)
    per_entity = min_rows + (rows_per_entity - min_rows) * extra / extra.mean()
    return per_entity / per_entity.sum()


def inverse_cdf(shares: np.ndarray, u: np.ndarray) -> np.ndarray:
    cdf = np.cumsum(shares)
    return np.minimum(np.searchsorted(cdf / cdf[-1], u),
                      len(shares) - 1).astype(np.int32)


def assign_entities(shares: np.ndarray, rows: int, min_rows: int,
                    seq: np.random.SeedSequence) -> np.ndarray:
    """[rows] int32 entity of every row. With ``min_rows`` every entity
    gets exactly that many first and the rest of its share by draw; rows
    end up in random order."""
    rng = _rng(seq)
    count = len(shares)
    if not min_rows or rows < count * min_rows:
        return inverse_cdf(shares, rng.random(rows))
    rest = np.maximum(shares * rows - min_rows, 0.0)
    ids = np.concatenate([
        np.repeat(np.arange(count, dtype=np.int32), min_rows),
        inverse_cdf(rest, rng.random(rows - count * min_rows))])
    return rng.permutation(ids)


def zipf_folded(u: np.ndarray, a: float, count: int) -> np.ndarray:
    """replay.py's entity draw: rank = int(u ** (-1 / (a - 1))) over an
    unbounded rank space, folded into ``count`` entities by modulus. One
    Python ``**`` a draw, as in the original: a rank can exceed what int64
    holds (a = 1.1 gives u ** -10), and numpy's vectorised power differs
    from it in the last place, which the modulus turns into another
    entity."""
    e = -1.0 / (a - 1.0)
    return np.fromiter(((int(x ** e) - 1) % count for x in u.tolist()),
                       np.int64, len(u))


def arrival_times(u: np.ndarray, rate: float) -> np.ndarray:
    """Cumulative arrival times of a Poisson process: exponential gaps
    ``-log(u) / rate``, as replay.py draws them."""
    return np.cumsum(-np.log(u) / rate)


# --------------------------------------------------------------------------
# rows of a GAME problem with a planted model
# --------------------------------------------------------------------------

@dataclasses.dataclass
class GameRows:
    x: Dict[str, np.ndarray]          # feature shard -> [n, width] float32
    ids: Dict[str, np.ndarray]        # entity type -> [n] int32
    y: np.ndarray                     # [n] float32 in {0, 1}
    logits: np.ndarray                # [n] float32 under the planted model


def entity_shares(cfg: dict, seed: int) -> Dict[str, np.ndarray]:
    out = {}
    for etype, spec in cfg.get("entities", {}).items():
        if spec["rows"] == "zipf":
            out[etype] = zipf_bounded_shares(spec["count"], spec["a"])
        elif spec["rows"] == "min_power_law":
            out[etype] = min_power_law_shares(
                spec["count"], spec["rows_per_entity"], spec["min_rows"],
                spec["tail"], spec["tail_cap"],
                stream(seed, "shares", etype))
        else:
            raise ValueError(f"unknown row distribution {spec['rows']!r}")
    return out


def planted_model(cfg: dict, seed: int) -> Dict[str, np.ndarray]:
    """The model the labels are drawn from: coordinate id -> [width] for a
    fixed effect, [entities, width] for a random effect, float32."""
    out = {}
    for c in cfg["coordinates"]:
        rng = _rng(stream(seed, "planted", c["id"]))
        shape = ((c["width"],) if c["kind"] == "fixed"
                 else (cfg["entities"][c["entity"]]["count"], c["width"]))
        out[c["id"]] = (rng.standard_normal(shape, dtype=np.float32)
                        * np.float32(c["planted_scale"]))
    return out


def feature_scale(coordinate: dict) -> float:
    """What a coordinate's standard normal features are multiplied by."""
    return (coordinate["width"] ** -0.5
            if coordinate["feature_scale"] == "inv_sqrt_width" else 1.0)


def game_rows(cfg: dict, rows: int, seed: int, split: str,
              planted: Optional[Dict[str, np.ndarray]] = None) -> GameRows:
    """``rows`` rows of the configuration's problem under ``seed``.
    ``split`` names the stream ("train", "validation-3", ...): the same
    planted model and entity shares, fresh rows."""
    planted = planted if planted is not None else planted_model(cfg, seed)
    shares = entity_shares(cfg, seed)
    ids = {}
    for etype, spec in cfg.get("entities", {}).items():
        min_rows = spec.get("min_rows", 0) if split == "train" else 0
        ids[etype] = assign_entities(shares[etype], rows, min_rows,
                                     stream(seed, split, "ids", etype))
    x, logits = {}, np.zeros(rows, np.float32)
    for c in cfg["coordinates"]:
        xs = x[c["shard"]] = normal_f32(
            rows, c["width"], stream(seed, split, "x", c["shard"]),
            scale=feature_scale(c),
            unit_rows=c["feature_scale"] == "unit_rows")
        w = planted[c["id"]]
        if c["kind"] == "fixed":
            logits += xs @ w
        else:
            logits += np.einsum("nk,nk->n", xs, w[ids[c["entity"]]])
    u = _rng(stream(seed, split, "labels")).random(rows, dtype=np.float32)
    y = (u < 1.0 / (1.0 + np.exp(-logits))).astype(np.float32)
    return GameRows(x, ids, y, logits)


def auc(y: np.ndarray, score: np.ndarray) -> float:
    """Area under the ROC curve by ranks (ties get their mean rank)."""
    order = np.argsort(score, kind="stable")
    s = np.asarray(score)[order]
    ranks = np.empty(len(s), np.float64)
    ranks[order] = np.arange(1, len(s) + 1)
    # mean rank over each run of ties
    starts = np.flatnonzero(np.concatenate([[True], s[1:] != s[:-1]]))
    ends = np.concatenate([starts[1:], [len(s)]])
    mean_rank = (starts + 1 + ends) / 2.0
    ranks[order] = np.repeat(mean_rank, ends - starts)
    pos = np.asarray(y) > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2)
                 / (n_pos * n_neg))
