"""Training rows over the median seconds of one complete fit, over the
fits that ended inside the window (a count of fits a window would move in
steps of one fit)."""

import numpy as np

UNIT = "rows/s"
BETTER = "higher"
SOURCE = "host_clock"


def fit_seconds(window):
    return [f["end"] - f["start"] for f in window["fits"]
            if "error" not in f and f["end"] <= window["end"]]


def read(run):
    seconds = fit_seconds(run.window)
    if not seconds:
        return None
    return run.cfg["rows"] / float(np.median(seconds))
