"""Mean seconds a batch spent from dispatch to the end of the blocking
device-to-host copy (the engine's own stage histogram)."""

from benchmark.layer_metrics import _serving

LAYER = "scorer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "serve_p99_ms"


def read(run):
    return _serving.stage_mean_ms(run, "score")
