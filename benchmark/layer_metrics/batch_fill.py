"""Responses over the sum of the bucket sizes they were scored in: 1 is no
padding."""

from benchmark.layer_metrics import _serving

LAYER = "serving_host"
UNIT = "share"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "serve_p50_ms"


def read(run):
    return _serving.batch_fill(run)
