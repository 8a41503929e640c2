"""Mean seconds a request waited in the micro-batcher before its batch was
popped (the engine's own stage histogram)."""

from benchmark.layer_metrics import _serving

LAYER = "serving_host"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "serve_p50_ms"


def read(run):
    return _serving.stage_mean_ms(run, "queue")
