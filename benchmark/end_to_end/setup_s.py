"""Process start to the first measured operation: imports, reaching the
device, generation, ingest, compile or cache load, warm-up and the
``correct`` check."""

UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
    return run.setup_s
