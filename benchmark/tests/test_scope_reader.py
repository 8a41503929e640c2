"""The reduction from a profiler trace to device seconds by the program's
named scopes: on a file made by hand, on the trace PR 22 recorded (a
program with no scopes), and on the traces recorded on the chip after the
scopes went in (benchmark/testdata/README-scopes.md). And the per-layer
readers that stand on it, which no cell lists yet."""

import importlib
import json
import os
import re
import shutil
import subprocess
import sys
import types

import pytest

from benchmark import run as R
from benchmark import scope_reader as S
from benchmark import trace_reader as T

TESTDATA = os.path.join(R.HERE, "testdata")
OLD = os.path.join(TESTDATA, "fe-epsilon.refit.xplane.pb")
READERS = ("aggregators_device_share", "solver_device_share",
           "sweep_device_share", "unscoped_device_share",
           "newton_factor_share", "value_gradient_roofline", "prepare_s",
           "h2d_s", "h2d_gb", "traced_fit_overhead")
SHARES = READERS[:4]


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}")


# --------------------------------------------------------------------------
# a file made by hand
# --------------------------------------------------------------------------

LINESEARCH = ("jit(solve)/optim/lbfgs/loop/while/body/optim/lbfgs/linesearch"
              "/optim/linesearch/loop/while/body/optim/linesearch/trial")
PATHS = {
    1: ("%while.1", "jit(solve)/optim/lbfgs/loop/while:", "while", 999),
    2: ("%fusion.1", LINESEARCH + "/agg/value_and_gradient/agg/margins"
        "/dot_general:", "loop fusion", 600),
    3: ("%fusion.2", LINESEARCH + "/agg/value_and_gradient/dot_general:",
        "loop fusion", 400),
    4: ("%custom-call.7", "jit(solve_all)/re/b3/vmap(optim/newton/"
        "factor_solve)/jit(_cholesky)/cholesky:", "custom-call", 50),
    5: ("%copy", "", "data formatting", 80),          # compiler-inserted
    6: ("%copy", "jit(solve_all)/re/b3/re/gather/gather:", "data formatting",
        8),                                           # same name, other program
    7: ("%add", "jit(add)/add:", "non-fusion elementwise", 4),
}


def made_by_hand(path):
    """One chip, a 100 ns window. ``while.1`` [10, 30) encloses ``fusion.1``
    [12, 18) and ``fusion.2`` [20, 29); then the Cholesky [40, 50), the
    compiler's copy [50, 60), the other program's ``%copy`` [60, 64) twice
    ([60, 64) and [70, 74)), an eager add [80, 82), and one event that
    straddles the window's end, [95, 105)."""
    space = S._xspace_class()()
    plane = space.planes.add(name="/device:TPU:0")
    for i, name in enumerate(("hlo_category", "tf_op", "bytes_accessed",
                              "program_id"), start=1):
        entry = plane.stat_metadata.add(key=i)
        entry.value.id, entry.value.name = i, name
    for key, (name, path_, category, nbytes) in PATHS.items():
        entry = plane.event_metadata.add(key=key)
        entry.value.id, entry.value.name = key, name
        entry.value.stats.add(metadata_id=1, str_value=category)
        if path_:
            entry.value.stats.add(metadata_id=2, str_value=path_)
        entry.value.stats.add(metadata_id=3, int64_value=nbytes)
        entry.value.stats.add(metadata_id=4, uint64_value=100 + (key == 6))
    plane.lines.add(name="XLA Modules")
    line = plane.lines.add(name="XLA Ops", timestamp_ns=1000)
    for key, start, end in ((1, 10, 30), (2, 12, 18), (3, 20, 29),
                            (4, 40, 50), (5, 50, 60), (6, 60, 64),
                            (6, 70, 74), (7, 80, 82), (7, 95, 105)):
        line.events.add(metadata_id=key, offset_ps=start * 1000,
                        duration_ps=(end - start) * 1000)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())
    return (1000.0, 1100.0)


@pytest.fixture()
def by_hand(tmp_path):
    path = str(tmp_path / "hand.xplane.pb")
    window = made_by_hand(path)
    return {(op.name, S.scope_of(op.path)): op for op in S.read(path, window)}


def test_scope_is_the_innermost_and_bucket_stands_anywhere():
    assert S.scope_of(PATHS[2][1]) == "agg/margins"
    assert S.scope_of(PATHS[3][1]) == "agg/value_and_gradient"
    assert S.scope_of(PATHS[1][1]) == "optim/lbfgs/loop"
    assert S.scope_of(PATHS[4][1]) == "optim/newton/factor_solve"
    assert S.scope_of(PATHS[6][1]) == "re/gather"
    assert S.scope_of("jit(solve_all)/re/b12/select_n:") == "re/b12"
    assert S.scope_of("jit(add)/add:") == S.UNSCOPED == S.scope_of("")
    # a word that ends in a prefix is not a scope
    assert S.scope_of("jit(core/share)/more/x:") == S.UNSCOPED
    assert S.bucket_of(PATHS[4][1]) == S.bucket_of(PATHS[6][1]) == 3
    assert S.bucket_of("jit(solve_all)/re/b12/select_n:") == 12
    assert S.bucket_of(PATHS[2][1]) is None


def test_self_seconds_join_on_the_metadata_not_the_name(by_hand):
    seconds = {k: op.seconds for k, op in by_hand.items()}
    assert seconds == pytest.approx({
        ("%while.1", "optim/lbfgs/loop"): 5e-9,     # 20 less 6 + 9
        ("%fusion.1", "agg/margins"): 6e-9,
        ("%fusion.2", "agg/value_and_gradient"): 9e-9,
        ("%custom-call.7", "optim/newton/factor_solve"): 10e-9,
        ("%copy", S.UNSCOPED): 10e-9,
        ("%copy", "re/gather"): 8e-9,               # two executions
        ("%add", S.UNSCOPED): 7e-9}, abs=1e-15)     # 2 + the 5 inside
    # bytes: per execution, and none for what encloses other operations
    assert by_hand[("%while.1", "optim/lbfgs/loop")].bytes == 0
    assert by_hand[("%copy", "re/gather")].bytes == 16
    assert by_hand[("%add", S.UNSCOPED)].bytes == 8
    assert by_hand[("%copy", S.UNSCOPED)].category == "data formatting"


def test_reductions_over_the_hand_made_file(by_hand):
    ops = list(by_hand.values())
    scopes = S.by_scope(ops)
    assert list(scopes)[0] == "unscoped"             # most seconds first
    assert scopes["unscoped"] == pytest.approx([17e-9, 88])
    # each ladder program numbers its own buckets
    assert S.by_bucket(ops) == {(100, 3): pytest.approx([10e-9, 50]),
                                (101, 3): pytest.approx([8e-9, 16])}
    # a scope with everything nested in it
    assert S.under(ops, "agg/value_and_gradient") == pytest.approx(15e-9)
    assert S.under(ops, "agg/margins", "re/gather") == pytest.approx(14e-9)
    shares = [S.share(ops, "agg/"), S.share(ops, "optim/"),
              S.share(ops, "fe/", "re/", "cd/"), S.share(ops, S.UNSCOPED)]
    assert shares == pytest.approx([100 * 15 / 55, 100 * 15 / 55,
                                    100 * 8 / 55, 100 * 17 / 55])
    assert sum(shares) == pytest.approx(100.0)
    assert S.share(ops, "optim/newton/factor_solve") == pytest.approx(
        100 * 10 / 55)


# --------------------------------------------------------------------------
# PR 22's trace: a program from before it named anything
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def old():
    return T.read(OLD), S.read(OLD)


def test_old_trace_is_all_unscoped_and_nothing_raises(old):
    trace, ops = old
    assert {S.scope_of(op.path) for op in ops} == {S.UNSCOPED}
    assert S.by_bucket(ops) == {}
    # the same seconds as trace_reader's: self times add up to busy time
    assert sum(op.seconds for op in ops) == pytest.approx(
        T.busy_s(trace), rel=1e-6)
    top = T.top_ops(trace, 3)
    assert [op.name for op in ops[:3]] == [name for name, _ in top]
    assert [op.seconds for op in ops[:3]] == pytest.approx(
        [s for _, s in top], rel=1e-6)
    # the two passes over X, and the compiler's copy, which has no tf_op
    assert ops[0].path == "jit(solve)/while/body/while/body/dot_general:"
    assert ops[2].name.startswith("%copy = ") and ops[2].path == ""
    assert ops[2].bytes == pytest.approx(3 * 9.6e9)


def fake_run(trace, ops, **kw):
    trace.scoped_ops = ops           # what scope_reader.of() keeps on it
    return types.SimpleNamespace(trace=trace, **kw)


def test_on_an_unscoped_program_the_share_readers_report_nothing(old):
    trace, ops = old
    run = fake_run(trace, ops, cfg=R.load_json("configs", "fe-epsilon.json"),
                   peaks=R.load_json("peaks.json")["TPU v5 lite"],
                   traced={"fits": []})
    for name in SHARES + ("newton_factor_share", "value_gradient_roofline"):
        assert reader(name).read(run) is None, name
    # and without a trace at all
    run.trace = None
    for name in READERS[:6]:
        assert reader(name).read(run) is None, name


# --------------------------------------------------------------------------
# PR 23's trace: the program with its scopes (testdata/README-scopes.md)
# --------------------------------------------------------------------------

SCOPED = os.path.join(TESTDATA, "fe-epsilon.refit.scopes.xplane.pb")


@pytest.fixture(scope="module")
def scoped():
    trace = T.read(SCOPED)
    return trace, S.read(SCOPED, trace.window)


def test_recorded_scopes_are_what_the_run_printed(scoped):
    trace, ops = scoped
    assert trace.window_s == pytest.approx(0.546071767, abs=1e-9)
    assert sum(op.seconds for op in ops) == pytest.approx(
        T.busy_s(trace), rel=1e-6) == pytest.approx(0.534816996, rel=1e-6)
    scopes = {k: v[0] for k, v in S.by_scope(ops).items()}
    assert scopes == pytest.approx({
        "agg/margins": 0.240998, "agg/value_and_gradient": 0.240678,
        "unscoped": 0.040355, "fe/score": 0.011212,
        "optim/lbfgs/direction": 0.001219, "optim/lbfgs/update": 0.000158,
        "optim/linesearch/loop": 0.000077, "optim/linesearch/trial": 0.000069,
        "optim/linesearch/init": 0.000026, "optim/lbfgs/converged": 0.000019,
        "optim/lbfgs/init": 0.000005, "optim/linesearch/zoom": 0.000001},
        abs=1e-6)
    assert S.by_bucket(ops) == {}
    # the first pass is nested in the whole evaluation; the scorer is not
    assert S.under(ops, "agg/value_and_gradient") == pytest.approx(
        0.240998 + 0.240678, abs=2e-6)
    # what has no scope: the compiler's copy of X, which no JAX operation
    # asked for, the `while`s themselves, eager one-operation programs
    unscoped = [op for op in ops if S.scope_of(op.path) == S.UNSCOPED]
    assert unscoped[0].name.startswith("%copy = f32[530000,2000]")
    assert unscoped[0].path == "" and unscoped[0].seconds == pytest.approx(
        0.03937, abs=1e-5)
    assert {op.category for op in unscoped[1:5]} == {"while"}
    assert all(op.path.startswith(("jit(add)", "jit(broadcast_in_dim)"))
               for op in unscoped if op.path)
    # bytes: the second pass reads X once an execution, 4.24 GB
    second = next(op for op in ops if "multiply_reduce_fusion.38" in op.name)
    assert S.scope_of(second.path) == "agg/value_and_gradient"
    assert second.bytes / 4.24e9 == pytest.approx(
        second.seconds / 0.00575, rel=0.05)


def test_recorded_readers_are_what_the_run_printed(scoped):
    trace, ops = scoped
    run = fake_run(trace, ops, cfg=R.load_json("configs", "fe-epsilon.json"),
                   peaks=R.load_json("peaks.json")["TPU v5 lite"],
                   traced={"fits": [{"evaluations": {"fixed": 14}}] * 3})
    printed = {"aggregators_device_share": 90.0637,
               "solver_device_share": 0.2942, "sweep_device_share": 2.0965,
               "unscoped_device_share": 7.5456,
               "value_gradient_roofline": 45.2094}
    got = {name: reader(name).read(run) for name in printed}
    assert got == pytest.approx(printed, abs=1e-3)
    assert sum(got[name] for name in SHARES) == pytest.approx(100.0)
    assert reader("newton_factor_share").read(run) == 0.0    # no NEWTON here
    # two coordinates, or more sweeps than one: not this roofline's cell
    run.cfg = {**run.cfg, "sweeps": 2}
    assert reader("value_gradient_roofline").read(run) is None


# --------------------------------------------------------------------------
# the other readers, and what registering them will take
# --------------------------------------------------------------------------

def test_traced_fit_overhead_is_a_ratio_of_medians():
    fits = lambda *seconds: {"fits": [{"start": 0.0, "end": s}
                                      for s in seconds]}
    run = types.SimpleNamespace(window=fits(1.0, 1.0, 1.1, 5.0),
                                traced=fits(1.071, 1.07, 9.0))
    assert reader("traced_fit_overhead").read(run) == pytest.approx(
        100 * (1.071 / 1.05 - 1))
    run.traced = {"fits": [{"start": 0.0, "end": 1.0, "error": "x"}]}
    assert reader("traced_fit_overhead").read(run) is None
    run.traced = None
    assert reader("traced_fit_overhead").read(run) is None


def test_set_up_readers_read_the_programs_phases_and_counter():
    from photon_tpu.obs.metrics import registry
    from photon_tpu.utils import timing

    timing.clear_timings()
    registry.clear()
    assert [reader(n).read(None) for n in ("prepare_s", "h2d_s", "h2d_gb")
            ] == [None, None, None]      # a program with none of them
    with timing._TIMINGS_LOCK:
        timing._TIMINGS.extend([
            ("ingest/prepare/a/group", 2.0), ("ingest/prepare/a/pad", 0.5),
            ("ingest/prepare/b/coordinate", 0.25), ("ingest/h2d/a", 0.125),
            ("ingest/h2d/b", 0.125), ("ingest/stats", 7.0),
            ("read training data", 11.0)])
    registry.counter("ingest.h2d_bytes", coordinate="a").inc(3e9)
    registry.counter("ingest.h2d_bytes", coordinate="b").inc(1.5e9)
    registry.counter("other.bytes").inc(1e12)
    assert reader("prepare_s").read(None) == 2.75
    assert reader("h2d_s").read(None) == 0.25
    assert reader("h2d_gb").read(None) == 4.5
    timing.clear_timings()
    registry.clear()


def test_the_new_readers_keep_the_contract():
    """What BENCHMARK.json will repeat when a benchmark PR lists them."""
    with open(os.path.join(os.path.dirname(R.HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    ends = {m["name"] for m in bench["end_to_end"]}
    taken = ends | {m["name"] for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]} | {"obs"}
    for name in READERS:
        r = reader(name)
        assert re.fullmatch(r"[A-Za-z0-9][A-Za-z0-9_.-]{0,63}", name)
        assert name not in taken          # registering is appending
        assert r.LAYER in layers and r.MOVES in ends
        assert r.BETTER in ("lower", "higher")
        assert r.SOURCE in ("device_trace", "program_span",
                            "program_counter", "host_clock")
        assert 1 <= len(r.UNIT) <= 16 and " " not in r.UNIT
        assert r.__doc__ and callable(r.read)
    assert reader("value_gradient_roofline").UNIT == "%"   # <kernel>_roofline


def test_a_cell_that_lists_them_rehearses(tmp_path):
    """The two refit cells with the new names appended to ``per_layer``, as a
    benchmark PR would leave them: the harness runs them without another
    edit, and a CPU run prints the one count among them."""
    shutil.copytree(R.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(os.path.join(os.path.dirname(R.HERE), "photon_tpu"),
               tmp_path / "photon_tpu")
    spec = R.load_json("workloads", "fe-epsilon.refit.json")
    spec["per_layer"] += [n for n in READERS if n != "newton_factor_share"]
    with open(tmp_path / "benchmark" / "workloads" / "fe-epsilon.refit.json",
              "w") as f:
        json.dump(spec, f)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "fe-epsilon.refit",
         "--seed", "1", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=tmp_path, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    cfg = R.overlaid(*[R.load_json("configs", "fe-epsilon.json")] * 2)
    rows = cfg["rehearse"]["rows"]
    width = cfg["coordinates"][0]["width"]
    # X, and labels (offsets and weights are not placed where a frame has
    # none): float32
    assert line["metrics"]["h2d_gb"] == {
        "value": pytest.approx(4 * rows * (width + 1) / 1e9), "unit": "GB"}
