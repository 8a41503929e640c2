"""Share of the traced window's busy device seconds in operations under no
scope of the program's: eager one-operation programs (the score algebra,
``jit(convert_element_type)``) and what the compiler inserts and names after
nothing (a re-layout ``copy``). With ``aggregators_``, ``solver_`` and
``sweep_device_share`` it sums to 100 (``benchmark/scope_reader.py``)."""

from benchmark import scope_reader

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"


def read(run):
    ops = scope_reader.of(run)
    return (None if ops is None
            else scope_reader.share(ops, scope_reader.UNSCOPED))
