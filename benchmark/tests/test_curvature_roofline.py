"""``benchmark/curvature_roofline.py`` and the two readers on it: the
operations and bytes of TRON's curvature work against ``roofline.py``'s
value-and-gradient pass, which peak bounds each at the cells' shapes, what
the solver's counts become, and that a program with no counts (another
solver's, or one from before TRON counted) reads nothing and raises
nothing."""

import os
import types

import pytest

from benchmark import curvature_roofline as C
from benchmark import roofline, trace_reader
from benchmark import run as R
from benchmark.layer_metrics import tron_cg_steps, tron_curvature_roofline

PEAKS = R.load_json("peaks.json")["TPU v5 lite"]
SCOPED = os.path.join(R.HERE, "testdata", "fe-epsilon.refit.scopes.xplane.pb")


def test_a_product_is_a_value_and_gradient_pass_and_a_build_is_symmetric():
    rows, width = 530_000, 2_000
    ops, bytes_ = C.hessian_vector(rows, width)
    vg_ops, vg_bytes = roofline.dense_value_gradient(rows, width)
    assert ops == vg_ops                       # X v and back: 4 a cell
    assert vg_bytes - bytes_ == 4 * 2 * rows   # one per-row vector, not three
    g_ops, g_bytes = C.weighted_gram(rows, width)
    # half of the full 2 n d^2 contraction, plus the diagonal's half
    assert g_ops == rows * width * (width + 1)
    assert g_ops < 0.51 * 2 * rows * width * width
    assert g_bytes == 4 * (rows * width + rows + width * width)


@pytest.mark.parametrize("rows,width,bound", [
    (530_000, 2_000, "compute"),        # 10.8 ms of MXU over a 5.2 ms read
    (2_000_000, 512, "bandwidth"),
    (4_000_000, 128, "bandwidth")])
def test_what_bounds_a_build_at_the_tables_shapes(rows, width, bound):
    seconds, which = roofline.least_seconds(*C.weighted_gram(rows, width),
                                            PEAKS)
    assert which == bound
    read = 4.0 * rows * width / PEAKS["hbm_bytes_per_s"]
    assert seconds >= read
    assert roofline.least_seconds(*C.hessian_vector(rows, width),
                                  PEAKS)[1] == "bandwidth"


def test_least_seconds_counts_builds_or_products_by_the_path():
    rows, width = 530_000, 2_000
    build = roofline.least_seconds(*C.weighted_gram(rows, width), PEAKS)[0]
    product = roofline.least_seconds(*C.hessian_vector(rows, width),
                                     PEAKS)[0]
    assert C.least_seconds("explicit", 6, 30, rows, width, PEAKS) == \
        6 * build
    assert C.least_seconds("matrix_free", 6, 30, rows, width, PEAKS) == \
        30 * product
    assert 0.0107 < build < 0.0109 and 0.0051 < product < 0.0053
    with pytest.raises(ValueError):
        C.least_seconds("no_such_path", 1, 1, rows, width, PEAKS)


class _Coordinate:
    def __init__(self, counts):
        self._counts = counts

    def tron_counts(self):
        return self._counts


def _run(coordinate, trace=None, fits=3):
    cfg = R.load_json("configs", "fe-epsilon-tron.json")
    est = types.SimpleNamespace(_coordinates={"fixed": coordinate})
    return types.SimpleNamespace(
        cfg=cfg, cell={}, peaks=PEAKS, trace=trace, state={"est": est},
        traced={"fits": [{"start": 0.0, "end": 1.0}] * fits})


def test_the_readers_read_the_solvers_counts():
    counts = {"cg_steps": 30, "hessian_builds": 6, "rejected_steps": 1}
    run = _run(_Coordinate(counts))
    assert C.solver_counts(run) == counts
    assert tron_cg_steps.read(run) == 30
    assert tron_curvature_roofline.read(run) is None       # no trace


def test_the_roofline_reader_is_builds_over_busy_seconds(monkeypatch):
    counts = {"cg_steps": 30, "hessian_builds": 6, "rejected_steps": 0}
    trace = trace_reader.read(SCOPED)
    run = _run(_Coordinate(counts), trace=trace, fits=3)
    busy = trace_reader.busy_s(trace)
    for path, n in (("explicit", 6), ("matrix_free", 30)):
        monkeypatch.setattr(C, "traced_path", lambda path=path: path)
        one = C.least_seconds(path, 6, 30, 530_000, 2_000, PEAKS)
        assert tron_curvature_roofline.read(run) == pytest.approx(
            100.0 * 3 * one / busy)
    monkeypatch.setattr(C, "traced_path", lambda: None)
    assert tron_curvature_roofline.read(run) is None


def test_a_program_without_the_counts_reads_nothing():
    """The parent of PR 33 (no ``tron_counts``), another solver (None), a
    state with no estimator: every reader returns None and none raises."""
    trace = trace_reader.read(SCOPED)
    for coordinate in (object(), _Coordinate(None)):
        run = _run(coordinate, trace=trace)
        assert C.solver_counts(run) is None
        assert tron_cg_steps.read(run) is None
        assert tron_curvature_roofline.read(run) is None
    run = _run(None, trace=trace)
    run.state = {}
    assert tron_cg_steps.read(run) is None


def test_the_traced_path_is_the_one_label_that_ticked():
    from photon_tpu.obs.metrics import registry

    registry.clear()
    assert C.traced_path() is None
    registry.counter("kernels.tron_hessian", path="matrix_free").inc()
    assert C.traced_path() == "matrix_free"
    registry.counter("kernels.tron_hessian", path="explicit").inc()
    assert C.traced_path() is None              # both: no one answer
    registry.clear()
