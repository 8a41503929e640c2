"""Device program launches in the traced window over the fits in it."""

from benchmark import trace_reader

LAYER = "cd_solver"
UNIT = "programs"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"


def read(run):
    if run.trace is None or not run.traced["fits"]:
        return None
    return trace_reader.launches(run.trace) / len(run.traced["fits"])
