"""The value-and-gradient aggregator's share of its roofline, in a
configuration whose fit is one dense fixed-effect solve: the least seconds
the chip could take for the traced fits' objective evaluations (ONE read of
the design matrix each; ``benchmark/roofline.py``, bandwidth-bound) over the
device seconds of the operations under ``agg/value_and_gradient`` (the
margins pass nested in it included) or, where a margin-resident line search
evaluates, ``agg/margin_value_and_gradient`` and ``agg/margin_trial``.
Unlike ``aggregator_roofline``, whose denominator is ALL busy seconds, a copy
or a scorer outside the kernel does not move it."""

from benchmark import roofline, scope_reader

LAYER = "aggregators"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"


def read(run):
    coords = run.cfg["coordinates"]
    ops = scope_reader.of(run)
    if (ops is None or run.peaks is None or len(coords) != 1
            or run.cfg["sweeps"] != 1):
        return None
    kernel = scope_reader.under(
        ops, "agg/value_and_gradient", "agg/margin_value_and_gradient",
        "agg/margin_trial")
    if not kernel:
        return None
    evaluations = sum(sum(f["evaluations"].values())
                      for f in run.traced["fits"] if "error" not in f)
    seconds, _ = roofline.least_seconds(
        *roofline.dense_value_gradient(run.cfg["rows"], coords[0]["width"]),
        run.peaks)
    return 100.0 * evaluations * seconds / kernel
