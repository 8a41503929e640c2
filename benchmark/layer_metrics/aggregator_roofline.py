"""The value-and-gradient aggregator's share of its roofline, in a
configuration whose fit is one dense fixed-effect solve: the least seconds
the chip could take for the traced fits' objective evaluations (ONE read of
the design matrix each; ``benchmark/roofline.py``) over the seconds the
device was busy in the traced window. Bandwidth bounds it: 1 operation a
byte against the chip's 240. ISSUE 22 called this ``agg_hbm_share``."""

from benchmark import roofline, trace_reader

LAYER = "aggregators"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"


def read(run):
    coords = run.cfg["coordinates"]
    if (run.trace is None or not run.trace.ops or run.peaks is None
            or len(coords) != 1 or run.cfg["sweeps"] != 1):
        return None
    evaluations = sum(sum(f["evaluations"].values())
                      for f in run.traced["fits"] if "error" not in f)
    ops, bytes_ = roofline.dense_value_gradient(
        run.cfg["rows"], coords[0]["width"])
    seconds, _ = roofline.least_seconds(ops, bytes_, run.peaks)
    return 100.0 * evaluations * seconds / trace_reader.busy_s(run.trace)
