"""99th percentile of how late the sender thread released a request after it
was due: a starved generator must not be read as a fast server."""

import numpy as np

LAYER = "load_generator"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "serve_p99_ms"


def read(run):
    samples = run.window["late"]
    if len(samples) < 1000:
        return None
    return float(np.percentile(samples, 99)) * 1e3
