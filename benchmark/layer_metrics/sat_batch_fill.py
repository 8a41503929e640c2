"""``batch_fill`` in the saturated cell."""

from benchmark.layer_metrics import _serving

LAYER = "serving_host"
UNIT = "share"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_rps"


def read(run):
    return _serving.batch_fill(run)
