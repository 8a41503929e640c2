"""Frame, dataset preparation, lowering, compile or cache load, and one
fit: the program has no boundary between them yet."""

LAYER = "ingest"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run):
    return run.state.get("first_fit_s")
