"""Host seconds of dataset preparation in set-up: the sum of the program's
phases ``ingest/prepare/<coordinate id>/<step>`` (grouping rows by entity,
the size ladder, the padded fill of every bucket, the coordinate objects;
``game/random_effect.py``, ``estimators/game_estimator.py``). Placement
(``h2d_s``) and the padding-waste count (``ingest/stats``) are apart."""

from benchmark.layer_metrics import _ingest

LAYER = "ingest"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    return _ingest.phase_seconds("ingest/prepare/")
