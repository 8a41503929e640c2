"""``benchmark/lane_roofline.py`` and the reader on it: the lane pass's
operations and bytes against ``roofline.py``'s at one lane, and the count of
passes on the traces recorded on the chip (testdata/README-scopes.md: three
fits of 14 evaluations each, 13 in the solver's loop and its first, which
is an operation of its own)."""

import os
import types

from benchmark import lane_roofline, roofline, trace_reader
from benchmark import run as R
from benchmark.layer_metrics import lanes_value_gradient_roofline as reader

TESTDATA = os.path.join(R.HERE, "testdata")
SCOPED = os.path.join(TESTDATA, "fe-epsilon.refit.scopes.xplane.pb")
UNSCOPED = os.path.join(TESTDATA, "fe-epsilon.refit.xplane.pb")


def test_one_lane_is_the_dense_pass_and_lanes_share_the_read_of_x():
    rows, width = 530_000, 2_000
    assert lane_roofline.lanes_value_gradient(rows, width, 1) == \
        roofline.dense_value_gradient(rows, width)
    ops1, bytes1 = lane_roofline.lanes_value_gradient(rows, width, 1)
    ops4, bytes4 = lane_roofline.lanes_value_gradient(rows, width, 4)
    assert ops4 == 4 * ops1
    assert bytes4 - bytes1 == 4 * 3 * (3 * rows + 2 * width)
    peaks = R.load_json("peaks.json")["TPU v5 lite"]
    assert roofline.least_seconds(ops4, bytes4, peaks)[1] == "bandwidth"


def test_passes_are_counted_from_the_trace():
    window = trace_reader.read(SCOPED).window
    assert lane_roofline.passes(SCOPED, window) == 3 * 14
    lo, hi = window
    assert lane_roofline.passes(SCOPED, (lo, lo + (hi - lo) / 3)) == 14
    assert lane_roofline.passes(SCOPED, window, "agg/no_such_scope") is None
    # a program from before it named anything has nothing to count
    assert lane_roofline.passes(
        UNSCOPED, trace_reader.read(UNSCOPED).window) is None


def test_the_reader_reads_nothing_without_a_trace_or_a_grid():
    cfg = R.load_json("configs", "fe-epsilon-l2grid.json")
    run = types.SimpleNamespace(cfg=cfg, trace=None, peaks={}, cell={})
    assert reader.read(run) is None
    trace = trace_reader.read(SCOPED)
    one_weight = {k: v for k, v in cfg.items() if k != "l2_grid"}
    run = types.SimpleNamespace(cfg=one_weight, trace=trace, cell={},
                                peaks=R.load_json("peaks.json")["TPU v5 lite"])
    assert reader.read(run) is None
