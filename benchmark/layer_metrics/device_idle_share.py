"""1 - (union of the device's operation intervals) / (traced window)."""

from benchmark import trace_reader

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    return 100.0 * trace_reader.idle_share(run.trace)
