"""What the tracing costs when it is on: the median seconds of a fit in the
traced tail of the window (the program's telemetry AND the profiler on)
over the median in the part before it (telemetry on, profiler off), less
one. The end-to-end run has both off; its fits are the ``--trace 0``
line's."""

import numpy as np

LAYER = "obs"
UNIT = "%"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "train_rows_per_s"


def _fit_seconds(samples):
    return [f["end"] - f["start"] for f in samples["fits"]
            if "error" not in f]


def read(run):
    if run.traced is None:
        return None
    before, traced = _fit_seconds(run.window), _fit_seconds(run.traced)
    if not before or not traced:
        return None
    return 100.0 * (float(np.median(traced)) / float(np.median(before)) - 1.0)
