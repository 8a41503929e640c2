"""Mean host seconds a batch spent in ``model.assemble`` (the engine's own
stage histogram), over the untraced window."""

from benchmark.layer_metrics import _serving

LAYER = "serving_host"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "serve_p50_ms"


def read(run):
    return _serving.stage_mean_ms(run, "assemble")
