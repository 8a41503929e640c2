"""``serve_assemble_ms`` in the saturated cell, where it bounds the
completed rate."""

from benchmark.layer_metrics import _serving

LAYER = "serving_host"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "serve_rps"


def read(run):
    return _serving.stage_mean_ms(run, "assemble")
