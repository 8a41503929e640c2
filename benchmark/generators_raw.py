"""Seeded generator of RAW dense rows: features in their own units, plus
Photon's intercept feature.

``benchmark/generators.py`` draws what the LIBSVM collection distributes:
epsilon AFTER its preprocessing ("every feature standardised and every row
then scaled to unit length"). A job that does not inherit that
preprocessing sees features in raw units, and this file undoes it by
assumption (the source gives no statistics of the raw features):

    x_j = m_j + s_j * z_j            j < width - 1
    x_intercept = 1                  the last column

with ``z`` the configuration's unit rows from ``generators.game_rows`` under
the SAME streams (so the labels, the planted margins and ``logits`` are those
of the configuration without the intercept, ``fe-epsilon``'s), ``s_j``
log-uniform on ``raw_scale`` and ``m_j = r_j * s_j / sqrt(width - 1)`` with
``r_j`` uniform on ``raw_mean_over_std``: a unit row's entry has standard
deviation ``1 / sqrt(width - 1)``, so ``r_j`` is the feature's mean in its
own standard deviations. numpy only, as ``generators.py``.
"""

from __future__ import annotations

import concurrent.futures
import copy
from typing import Dict, Optional, Tuple

import numpy as np

from benchmark import generators as G


def unit_cfg(cfg: dict) -> dict:
    """The configuration without its intercept: what ``generators`` draws."""
    out = copy.deepcopy(cfg)
    (c,) = out["coordinates"]
    c["width"] -= 1
    return out


def raw_statistics(cfg: dict, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """(m, s), float32 [width - 1]: the assumed location and scale of every
    raw feature, from ``seed`` alone."""
    (c,) = cfg["coordinates"]
    d = c["width"] - 1
    lo, hi = np.log(cfg["raw_scale"])
    s = np.exp(G._rng(G.stream(seed, "raw", "scale")).uniform(lo, hi, d))
    r = G._rng(G.stream(seed, "raw", "mean")).uniform(
        *cfg["raw_mean_over_std"], d)
    return ((r * s / np.sqrt(d)).astype(np.float32), s.astype(np.float32))


def planted_model(cfg: dict, seed: int) -> Dict[str, np.ndarray]:
    """The unit rows' planted model in RAW space, float64 [width]:
    ``z . theta = sum_j (x_j - m_j) / s_j * theta_j``, so a raw feature's
    coefficient is ``theta_j / s_j`` and the intercept ``-sum_j theta_j m_j /
    s_j``: the same margins, exactly."""
    (c,) = cfg["coordinates"]
    theta = G.planted_model(unit_cfg(cfg), seed)[c["id"]].astype(np.float64)
    m, s = (a.astype(np.float64) for a in raw_statistics(cfg, seed))
    return {c["id"]: np.concatenate([theta / s, [-np.sum(theta * m / s)]])}


def game_rows(cfg: dict, rows: int, seed: int, split: str,
              planted: Optional[Dict[str, np.ndarray]] = None) -> G.GameRows:
    """``generators.game_rows`` of the configuration without its intercept,
    its features moved to raw units and the column of ones appended.
    ``planted`` is the UNIT rows' model (``generators.planted_model`` of
    ``unit_cfg``), as ``generators.game_rows`` takes it."""
    (c,) = cfg["coordinates"]
    unit = G.game_rows(unit_cfg(cfg), rows, seed, split, planted)
    z = unit.x[c["shard"]]
    m, s = raw_statistics(cfg, seed)
    x = np.empty((rows, c["width"]), np.float32)

    def fill(lo: int) -> None:
        block = x[lo:lo + G.BLOCK_ROWS]
        np.multiply(z[lo:lo + G.BLOCK_ROWS], s, out=block[:, :-1])
        block[:, :-1] += m
        block[:, -1] = 1.0

    with concurrent.futures.ThreadPoolExecutor(G._threads()) as pool:
        list(pool.map(fill, range(0, rows, G.BLOCK_ROWS)))
    return G.GameRows({c["shard"]: x}, unit.ids, unit.y, unit.logits)
