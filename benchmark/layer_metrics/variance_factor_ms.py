"""Device milliseconds a fit under ``optim/variance/factor_solve``: the
Cholesky factorisation of the ``[width, width]`` Hessian and the inverse
taken from it (``photon_tpu/optim/problem.py::coefficient_variances``), over
the fits of the traced window. Latency-bound: 2000^3 operations are
0.04 ms at the chip's peak. ``None`` on a program without the scope."""

from benchmark import scope_reader, variance_roofline

LAYER = "cd_solver"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"


def read(run):
    ops = variance_roofline.traced_ops(run)
    fits = [f for f in (run.traced or {}).get("fits", ())
            if "error" not in f]
    if ops is None or not fits:
        return None
    return 1e3 * scope_reader.under(
        ops, variance_roofline.FACTOR_SOLVE) / len(fits)
