"""The comparisons that decide ``correct``, against ``benchmark/reference/``.

Training: the reference's gradient of the configuration's own regularised
objective at the fitted model, coordinate by coordinate, relative to the
objective; and validation AUC against the planted model's, both scored by
the reference. Serving: every response against the reference score off
the saved arrays, inside the float32 forward-error bound of that sum.

The reference sees the data in row blocks, so the check adds one block to
the device's memory and not a second copy of the data.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Dict, Tuple

import numpy as np

from benchmark import generators as G

HERE = os.path.dirname(os.path.abspath(__file__))
EPS32 = float(np.finfo(np.float32).eps)
BLOCK_BYTES = 1 << 28


def load_reference(config_name: str):
    path = os.path.join(HERE, "reference", f"{config_name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_reference_{config_name.replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _block_rows(rows: G.GameRows) -> int:
    width = sum(x.shape[1] for x in rows.x.values())
    return 1 << int(np.log2(BLOCK_BYTES / (4 * width)))


def _blocks(rows: G.GameRows):
    """(x, ids, y, weight) per block, every block the same shape: the last
    is padded with rows of weight 0."""
    n, b = len(rows.y), _block_rows(rows)
    b = min(b, 1 << int(np.ceil(np.log2(n))))
    for lo in range(0, n, b):
        hi = min(lo + b, n)

        def cut(a):
            out = a[lo:hi]
            if hi - lo < b:
                out = np.concatenate(
                    [out, np.zeros((b - (hi - lo),) + a.shape[1:], a.dtype)])
            return out

        weight = np.zeros(b, np.float32)
        weight[:hi - lo] = 1.0
        yield ({k: cut(v) for k, v in rows.x.items()},
               {k: cut(v) for k, v in rows.ids.items()}, cut(rows.y), weight)


def objective_and_gradient(ref, params: Dict[str, np.ndarray],
                           rows: G.GameRows, l2: float
                           ) -> Tuple[float, Dict[str, np.ndarray]]:
    """The reference's regularised objective and its gradient, summed over
    row blocks in float64 on the host."""
    import jax

    step = jax.jit(ref.loss_and_gradient)
    dev = jax.device_put(params)
    value = float(ref.regulariser(dev, l2))
    grad = {k: np.asarray(v, np.float64)
            for k, v in ref.regulariser_gradient(dev, l2).items()}
    with jax.default_matmul_precision("highest"):
        for x, ids, y, weight in _blocks(rows):
            v, g = step(dev, x, ids, y, weight)
            value += float(v)
            for k in grad:
                grad[k] += np.asarray(g[k], np.float64)
    return value, grad


def reference_scores(ref, params: Dict[str, np.ndarray],
                     rows: G.GameRows) -> np.ndarray:
    import jax

    step = jax.jit(ref.score)
    dev = jax.device_put(params)
    out = []
    with jax.default_matmul_precision("highest"):
        for x, ids, _, weight in _blocks(rows):
            out.append(np.asarray(step(dev, x, ids))[weight > 0])
    return np.concatenate(out)


def training(cfg: dict, ref, fitted: Dict[str, np.ndarray],
             train: G.GameRows, validation: G.GameRows) -> Tuple[bool, dict]:
    """(holds, what was measured) for one fitted model; the planted model's
    AUC is that of the margins the validation labels were drawn from."""
    limits = cfg["correct"]
    objective, grad = objective_and_gradient(ref, fitted, train, cfg["l2"])
    relative = {k: float(np.sqrt(np.sum(g * g)) / objective)
                for k, g in grad.items()}
    auc_fit = G.auc(validation.y, reference_scores(ref, fitted, validation))
    auc_planted = G.auc(validation.y, validation.logits)
    finite = all(np.isfinite(v).all() for v in fitted.values())
    holds = (finite
             and all(relative[k] <= limits["gradient_over_objective"][k]
                     for k in relative)
             and auc_planted - auc_fit <= limits["auc_margin"])
    return bool(holds), {
        "objective": objective, "gradient_over_objective": relative,
        "auc": auc_fit, "auc_planted": auc_planted, "finite": finite}


def serving(cfg: dict, ref, tables: Dict[str, np.ndarray], x: Dict[str, np.ndarray],
            ids: Dict[str, np.ndarray], served: np.ndarray) -> Tuple[bool, dict]:
    """(holds, what was measured) for a sample of served scores: request
    ``r`` carried the feature row ``x[shard][r]`` and the entities
    ``ids[type][r]`` (-1 = unknown to the model: that effect adds nothing).

    A served score and the reference's are float32 sums of the same
    ``terms`` products in some order, so each is within
    ``(terms + 2) * eps32 * sum|term|`` of the exact sum (the + 2 covers
    rounding each float64 request value to float32) and the two within
    twice that of each other."""
    params, row_ids = {}, {}
    for c in cfg["coordinates"]:
        params[c["id"]] = tables[c["id"]]
        if c["kind"] == "random":
            # one zero row after the real ones, for the unknown entity
            params[c["id"]] = np.concatenate(
                [tables[c["id"]], np.zeros((1, c["width"]), np.float32)])
            row_ids[c["entity"]] = np.where(
                ids[c["entity"]] < 0, len(tables[c["id"]]),
                ids[c["entity"]]).astype(np.int32)
    rows = G.GameRows({k: v.astype(np.float32) for k, v in x.items()},
                      row_ids, np.zeros(len(served), np.float32),
                      np.zeros(len(served), np.float32))
    want = reference_scores(ref, params, rows).astype(np.float64)
    magnitude = np.zeros(len(served))
    terms = 0
    for c in cfg["coordinates"]:
        w = (params[c["id"]] if c["kind"] == "fixed"
             else params[c["id"]][row_ids[c["entity"]]]).astype(np.float64)
        magnitude += np.abs(x[c["shard"]] * w).sum(axis=1)
        terms += c["width"]
    bound = 2 * (terms + 2) * EPS32 * magnitude
    gap = np.abs(served - want)
    finite = bool(np.isfinite(served).all())
    return finite and bool((gap <= bound).all()), {
        "sample": len(served), "max_gap": float(gap.max()),
        "largest_share_of_bound": float((gap / bound).max()), "finite": finite}
