"""The FULL variance as a share of its roofline, in a configuration whose
fit is one dense fixed-effect solve and its variances: the variances the
PROGRAM counted in the traced fits (``variance.computed{type=FULL}``, not
operation names) x the least seconds of ONE, whatever implements it (the
longer of one read of X and the symmetric ``X^T D X`` at the bfloat16 peak:
``benchmark/variance_roofline.py``; 10.77 ms at epsilon's shape; the
factorisation's width^3 operations, 0.04 ms at that peak, are left out)
over the busy seconds under ``optim/variance/``. A Gram in several bfloat16
passes, a second read of X and a latency-bound factorisation read as the
share they cost; it cannot pass 100%."""

from benchmark import variance_roofline

LAYER = "aggregators"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"


def read(run):
    coords = run.cfg["coordinates"]
    ops = variance_roofline.traced_ops(run)
    count = variance_roofline.computed(run)
    if ops is None or not count or run.peaks is None or len(coords) != 1:
        return None
    least = variance_roofline.least_seconds(
        run.cfg["rows"], coords[0]["width"], run.peaks)
    return 100.0 * count * least / variance_roofline.seconds_under(ops)
