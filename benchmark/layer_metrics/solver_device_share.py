"""Share of the traced window's busy device seconds in operations whose
innermost ``jax.named_scope`` is a solver step's own (``optim/*``:
``photon_tpu/optim/``): direction, factorisation, line-search bookkeeping,
history. What a step spends inside an aggregator is the aggregator's. With
``aggregators_``, ``sweep_`` and ``unscoped_device_share`` it sums to 100
(``benchmark/scope_reader.py``)."""

from benchmark import scope_reader

LAYER = "cd_solver"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"


def read(run):
    ops = scope_reader.of(run)
    return None if ops is None else scope_reader.share(ops, "optim/")
