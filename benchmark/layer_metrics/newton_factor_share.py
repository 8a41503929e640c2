"""Share of the traced window's busy device seconds in the NEWTON solver's
``optim/newton/factor_solve``: the batched Cholesky factorisation of the
per-entity Hessians and its two triangular solves (``optim/newton.py``)."""

from benchmark import scope_reader

LAYER = "cd_solver"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"


def read(run):
    ops = scope_reader.of(run)
    return None if ops is None else scope_reader.share(
        ops, "optim/newton/factor_solve")
