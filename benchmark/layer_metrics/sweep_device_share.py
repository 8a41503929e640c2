"""Share of the traced window's busy device seconds in operations whose
innermost ``jax.named_scope`` is the coordinate-descent sweep's own
(``fe/*``, ``re/*``, ``cd/*``: scoring, the bucket ladder's gathers and
scatters, a bucket's glue; ``photon_tpu/game/coordinate.py``). With
``aggregators_``, ``solver_`` and ``unscoped_device_share`` it sums to 100
(``benchmark/scope_reader.py``)."""

from benchmark import scope_reader

LAYER = "cd_solver"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"


def read(run):
    ops = scope_reader.of(run)
    return (None if ops is None
            else scope_reader.share(ops, "fe/", "re/", "cd/"))
