"""The LANE-batched value-and-gradient's share of its roofline, in a
configuration whose fit is one dense fixed-effect solve over a grid of
regularisation weights (``l2_grid``): the passes the traced window ran,
counted FROM THE TRACE (executions of the contraction under
``agg/margins``: the batched loop runs one pass a trip for all K lanes,
riding lanes included), times the least seconds the chip could take for one
pass of K lanes (ONE read of the design matrix for all of them;
``benchmark/lane_roofline.py``, ``roofline.least_seconds``, bandwidth-bound
until K is some hundreds), over the seconds the device was busy in the
window. A pass reads X at least once, so it cannot pass 100%; a pass that
reads X twice (margins, then gradients) reaches at most 50%."""

import os

from benchmark import lane_roofline, roofline, trace_reader

LAYER = "aggregators"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"


def read(run):
    coords = run.cfg["coordinates"]
    if (run.trace is None or not run.trace.ops or run.peaks is None
            or len(coords) != 1 or "l2_grid" not in run.cfg):
        return None
    path = trace_reader.find_xplane(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "out",
        run.cell["name"], "trace"))
    passes = lane_roofline.passes(path, run.trace.window)
    if not passes:
        return None
    seconds, _ = roofline.least_seconds(
        *lane_roofline.lanes_value_gradient(
            run.cfg["rows"], coords[0]["width"], len(run.cfg["l2_grid"])),
        run.peaks)
    return 100.0 * passes * seconds / trace_reader.busy_s(run.trace)
