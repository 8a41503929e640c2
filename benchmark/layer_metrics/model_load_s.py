"""Seconds in ``ServingEngine.from_model_dir`` and the engine's own
``warmup()``."""

LAYER = "ingest"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run):
    return run.state.get("model_load_s")
