"""99th percentile of seconds from submit to response in the closed loop."""

import numpy as np

LAYER = "load_generator"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "serve_rps"


def read(run):
    samples = run.window["latency"]
    if len(samples) < 1000:
        return None
    return float(np.percentile(samples, 99)) * 1e3
