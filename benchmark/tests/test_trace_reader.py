"""The reduction from a profiler trace to busy seconds, idle share, top
operations and labelled idle gaps: on intervals made by hand, and on a
small trace recorded on the chip (benchmark/testdata/README.md)."""

import os

import pytest

from benchmark import trace_reader as T

RECORDED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "fe-epsilon.refit.xplane.pb")


def made_by_hand():
    """A 100 ns window; one chip busy in [10, 30) (a ``while`` enclosing two
    operations), [50, 60) and [60, 70); the host in ``fit`` over [5, 80),
    with ``cd/update`` [8, 40) inside it and ``fe/solve`` [9, 20) inside
    that."""
    ops = [(10.0, 30.0, "while"), (12.0, 18.0, "fusion.1"),
           (20.0, 29.0, "fusion.2"), (50.0, 60.0, "fusion.1"),
           (60.0, 70.0, "copy")]
    annotations = [(5.0, 80.0, "fit"), (8.0, 40.0, "cd/update"),
                   (9.0, 20.0, "fe/solve"), (6.0, 7.0, "PjitFunction(solve)")]
    return T.Trace((0.0, 100.0), {"/device:TPU:0": sorted(ops)},
                   {"/device:TPU:0": [(10.0, 30.0, "jit_solve"),
                                      (50.0, 70.0, "jit_score")]},
                   sorted(annotations))


def test_busy_is_the_union_of_the_operation_intervals():
    trace = made_by_hand()
    assert T.merged(trace.ops["/device:TPU:0"]) == [(10.0, 30.0), (50.0, 70.0)]
    assert T.busy_s(trace) == pytest.approx(40e-9)
    assert T.idle_share(trace) == pytest.approx(0.6)
    assert T.launches(trace) == 2


def test_an_operation_counts_its_self_time():
    top = dict(T.top_ops(made_by_hand()))
    # the while: 20 ns less the 6 + 9 nested in it
    assert top == pytest.approx({"while": 5e-9, "fusion.1": 16e-9,
                                 "fusion.2": 9e-9, "copy": 10e-9})
    assert T.top_ops(made_by_hand(), 1) == [["fusion.1", pytest.approx(16e-9)]]


def test_an_idle_gap_goes_to_the_innermost_label_open_on_the_host():
    gaps = dict(T.idle_gaps(made_by_hand(), ("fit", "cd/", "fe/")))
    # idle: [0, 10) [30, 50) [70, 100). Of [0, 10): 5 before `fit`, 3 in
    # fit alone, 1 in cd/update, 1 in fe/solve. Of [30, 50): 10 in
    # cd/update, 10 in fit. Of [70, 100): 10 in fit, 20 after it.
    assert gaps == pytest.approx({
        T.UNATTRIBUTED: 25e-9, "fit": 23e-9, "cd/update": 11e-9,
        "fe/solve": 1e-9})
    # an annotation that is not a label (the runtime's own) names nothing
    assert "PjitFunction(solve)" not in gaps
    assert sum(gaps.values()) == pytest.approx(60e-9)


def test_a_chip_that_ran_nothing():
    trace = T.Trace((0.0, 100.0), {}, {}, [])
    assert T.busy_s(trace) == 0.0 and T.launches(trace) == 0
    assert T.idle_gaps(trace, ("fit",)) == [[T.UNATTRIBUTED,
                                              pytest.approx(100e-9)]]


@pytest.fixture(scope="module")
def recorded():
    return T.read(RECORDED)


def test_recorded_trace_planes_and_window(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    assert list(recorded.launches) == ["/device:TPU:0"]
    lo, hi = recorded.window
    assert all(lo <= s and e <= hi for s, e, _ in recorded.ops["/device:TPU:0"])
    assert {"fit", "cd/sweep", "cd/update", "fe/solve"} <= {
        name for _, _, name in recorded.annotations}


def test_recorded_trace_busy_and_idle(recorded):
    """The numbers the traced run itself printed for this window
    (benchmark/testdata/README.md): busy_s 0.605075756, window_s
    0.616921465, 28 programs over 3 fits."""
    assert recorded.window_s == pytest.approx(0.616921465, abs=1e-9)
    assert T.busy_s(recorded) == pytest.approx(0.605075756, abs=1e-8)
    assert T.idle_share(recorded) == pytest.approx(0.0192013, abs=1e-6)
    assert T.launches(recorded) == 28
    names = [n.split("(")[0] for _, _, n in recorded.launches["/device:TPU:0"]]
    assert names.count("jit_solve") == 3 and names.count("jit__fixed_score") == 3
    fits = [a for a in recorded.annotations if a[2] == "fit"]
    assert len(fits) == 3


def test_recorded_trace_top_operations(recorded):
    """Nothing overlaps on one chip, so self times add up to busy time; the
    two passes over the design matrix lead, 0.254 s and 0.253 s, and the
    copy of the matrix into row-major order follows."""
    ops = recorded.ops["/device:TPU:0"]
    assert len(ops) == 4896
    assert sum(T.self_times(ops).values()) == pytest.approx(
        T.busy_s(recorded), rel=1e-6)
    top = T.top_ops(recorded, 3)
    assert [name.split(" = ")[0] for name, _ in top] == [
        "%multiply_reduce_fusion.37", "%multiply_reduce_fusion.38", "%copy"]
    assert [s for _, s in top] == pytest.approx(
        [0.253784549, 0.253256569, 0.044518656], abs=1e-8)
    assert all("f32[600000,2000]" in name for name, _ in top)


def test_recorded_trace_gaps_carry_the_programs_span_names(recorded):
    gaps = T.idle_gaps(recorded, ("cd/", "fe/", "re/", "fit"))
    assert sum(s for _, s in gaps) == pytest.approx(
        recorded.window_s - T.busy_s(recorded), rel=1e-6)
    assert gaps[0] == ["cd/update", pytest.approx(0.005976766, abs=1e-8)]
    assert dict(gaps) == pytest.approx({
        "cd/update": 0.005976766, "fit": 0.00231506, "cd/sweep": 0.00118368,
        "fe/solve": 0.001079053, T.UNATTRIBUTED: 0.0009205,
        "fe/score": 0.00037065}, abs=1e-8)
    # with fewer labels the same idle time moves outward, none is lost
    outer = dict(T.idle_gaps(recorded, ("fit",)))
    assert set(outer) == {"fit", T.UNATTRIBUTED}
    assert sum(outer.values()) == pytest.approx(
        recorded.window_s - T.busy_s(recorded), rel=1e-6)
