"""Operations and bytes of a LANE-batched value-and-gradient pass, and how
many such passes a traced window ran, counted from the trace itself. Beside
``roofline.py``, whose ``least_seconds`` turns the first into a time."""

from __future__ import annotations

from typing import Optional, Tuple

from benchmark import scope_reader, trace_reader


def lanes_value_gradient(rows: int, width: int, lanes: int,
                         itemsize: int = 4) -> Tuple[float, float]:
    """(operations, bytes) of one value-and-gradient pass of K = ``lanes``
    GLMs over ONE dense [rows, width] design matrix: margins ``X Theta^T``
    and gradients ``dZ^T X``, 2 operations a cell a lane each; the least
    traffic is ONE read of X for all the lanes plus each lane's per-row
    vectors (margins, dz, and the labels read again) and its theta and
    gradient. A pass that reads X twice reaches at most half of it."""
    cells = float(rows) * width
    return (4.0 * lanes * cells,
            itemsize * (cells + lanes * (3.0 * rows + 2.0 * width)))


def passes(path: str, window: Tuple[float, float],
           scope: str = "agg/margins") -> Optional[int]:
    """Executions, inside the window, of the contractions under ``scope``:
    of the operations whose ``tf_op`` holds the scope, those that take at
    least half of what the longest of them takes an execution (the first
    pass over the design matrix, which the solver's first evaluation and
    its loop each compile as an operation of their own; what else runs
    under the name is a broadcast or an add over a vector), counted on the
    busiest chip. Joined by metadata id, as ``scope_reader.read`` does.
    None where no operation carries the scope."""
    lo, hi = window
    space = scope_reader._xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    best = None
    for plane in space.planes:
        if not plane.name.startswith(trace_reader.DEVICE_PLANE):
            continue
        stat_name = {e.key: e.value.name for e in plane.stat_metadata}
        scoped = {e.key for e in plane.event_metadata
                  if scope in scope_reader._SCOPE.findall(
                      scope_reader._described(e.value, stat_name)[0])}
        seconds, count = {}, {}
        for line in plane.lines:
            if line.name != trace_reader.OPS_LINE:
                continue
            for ev in line.events:
                start = line.timestamp_ns + ev.offset_ps / 1000.0
                end = start + ev.duration_ps / 1000.0
                if ev.metadata_id in scoped and end > lo and start < hi:
                    key = ev.metadata_id
                    seconds[key] = seconds.get(key, 0.0) + end - start
                    count[key] = count.get(key, 0) + 1
        if not seconds:
            continue
        longest = max(seconds[k] / count[k] for k in seconds)
        big = [k for k in seconds if seconds[k] / count[k] >= longest / 2]
        total = sum(seconds[k] for k in big)
        if best is None or total > best[0]:
            best = (total, sum(count[k] for k in big))
    return None if best is None else best[1]
