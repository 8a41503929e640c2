"""Operations and bytes a kernel's algorithm needs, from its shapes, and
the least time the chip could take for them. The peaks are the device's
row of ``benchmark/peaks.json``."""

from __future__ import annotations

from typing import Tuple


def dense_value_gradient(rows: int, width: int, itemsize: int = 4
                         ) -> Tuple[float, float]:
    """(operations, bytes) of one value-and-gradient pass of a GLM over a
    dense [rows, width] design matrix: margins ``X theta`` (2 ops a cell)
    and gradient ``X^T dz`` (2 ops a cell); the least traffic is ONE read
    of X plus the per-row vectors (labels, offsets, margins) and theta
    and the gradient. A pass that reads X twice can reach at most half of
    this roofline."""
    cells = float(rows) * width
    return 4.0 * cells, itemsize * (cells + 3.0 * rows + 2.0 * width)


def least_seconds(ops: float, bytes_: float, peaks: dict) -> Tuple[float, str]:
    """(seconds, which peak bounds it)."""
    by_compute = ops / peaks["bf16_flops_per_s"]
    by_bandwidth = bytes_ / peaks["hbm_bytes_per_s"]
    return ((by_compute, "compute") if by_compute > by_bandwidth
            else (by_bandwidth, "bandwidth"))
