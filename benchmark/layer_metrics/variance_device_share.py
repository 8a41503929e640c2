"""Share of the traced window's busy device seconds in operations with
``optim/variance/`` ANYWHERE in their scope path: the curvature weights, the
Gram, the factorisation, the inverse and the diagonal of every coefficient
variance computed (``photon_tpu/optim/problem.py::coefficient_variances``),
the aggregators nested in it included. How much of a fit the mechanism is.
``None`` on a program without the scope."""

from benchmark import variance_roofline

LAYER = "cd_solver"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"


def read(run):
    ops = variance_roofline.traced_ops(run)
    if ops is None:
        return None
    return (100.0 * variance_roofline.seconds_under(ops)
            / sum(op.seconds for op in ops))
