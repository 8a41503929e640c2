"""``serve_score_ms`` in the saturated cell."""

from benchmark.layer_metrics import _serving

LAYER = "scorer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "serve_rps"


def read(run):
    return _serving.stage_mean_ms(run, "score")
