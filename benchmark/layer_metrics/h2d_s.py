"""Host seconds in the placements of set-up: the sum of the program's
phases ``ingest/h2d/<coordinate id>`` (``jnp.asarray`` of every block of
every coordinate; ``game/random_effect.py``, ``game/dataset.py``). What the
host spends in the call: nothing waits for the copy to land, so a transfer
the runtime hides behind the next block's preparation is not in it."""

from benchmark.layer_metrics import _ingest

LAYER = "ingest"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    return _ingest.phase_seconds("ingest/h2d/")
