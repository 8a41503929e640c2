"""Share of the traced window's busy device seconds in operations whose
innermost ``jax.named_scope`` is an aggregator's (``agg/*``:
``photon_tpu/ops/aggregators.py``): the passes over the data themselves.
With ``solver_``, ``sweep_`` and ``unscoped_device_share`` it sums to 100
(``benchmark/scope_reader.py``)."""

from benchmark import scope_reader

LAYER = "aggregators"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"


def read(run):
    ops = scope_reader.of(run)
    return None if ops is None else scope_reader.share(ops, "agg/")
