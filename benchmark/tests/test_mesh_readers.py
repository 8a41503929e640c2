"""The mesh's two readers on synthetic inputs: ``collective_device_share``
on a list of operations as ``scope_reader`` gives them (names and
categories as a TPU trace writes them), ``mesh_padding_share`` on the
program's counters.

    python -m pytest benchmark/tests/test_mesh_readers.py -q
"""

import types

import pytest

from benchmark import scope_reader as S
from benchmark.layer_metrics import collective_device_share as C
from benchmark.layer_metrics import mesh_padding_share as PAD

WHOLE = "jit(score)/re/score/cd/whole_score/shard_map/all_gather"
NEWTON = "jit(solve)/optim/newton/loop/while/body/optim/newton/hessian/agg/"


def _op(name, path, category, seconds, program=1):
    return S.Op(name, path, category, program, seconds, 0.0)


OPS = [
    # the score's one gather, and the whole step around it
    _op("%gather.3 = f32[5000066]{0} gather(f32[28000001]{0} %slots, "
        "s32[5000066,1]{1,0} %index)", "jit(score)/re/score/gather",
        "non-fusion elementwise", 0.040),
    _op("%all-gather-start.2 = (f32[5000066]{0}, f32[20000264]{0}) "
        "all-gather-start(f32[5000066]{0} %gather.3)", WHOLE,
        "all-gather-start", 0.001),
    _op("%all-gather-done.2 = f32[20000264]{0} all-gather-done("
        "(f32[5000066]{0}, f32[20000264]{0}) %all-gather-start.2)", WHOLE,
        "all-gather-done", 0.004),
    # a consumer of a collective's result is not a collective
    _op("%fusion.7 = f32[20000263]{0} fusion(f32[20000264]{0} "
        "%all-gather-done.2), kind=kLoop", "jit(score)/re/score/slice",
        "loop fusion", 0.002),
    # the fixed effect's Hessian, all-reduced, its category the opcode's
    _op("%all-reduce.5 = f32[128,128]{1,0} all-reduce(f32[128,128]{1,0} "
        "%fusion.9), channel_id=3", NEWTON + "hessian_matrix/reduce_sum",
        "all-reduce", 0.003, program=2),
    _op("%convolution.1 = f32[128,128]{1,0} convolution(f32[5000066,128]"
        "{1,0:T(8,128)} %x, f32[5000066,128]{1,0} %xw)",
        NEWTON + "hessian_matrix/dot_general", "convolution", 0.050,
        program=2),
    # an all-gather the compiler made an all-reduce, its name dropped
    _op("%all-reduce.14 = f32[20000264]{0:T(1024)} all-reduce(f32[20000264]"
        "{0:T(1024)} %dynamic-update-slice.20)", "", "all-reduce", 0.006),
]


def _run(ops):
    trace = types.SimpleNamespace(ops={"/device:TPU:0": [(0, 1, "x")]},
                                  scoped_ops=ops)
    return types.SimpleNamespace(trace=trace)


def test_collectives_are_found_by_opcode_not_by_operand():
    assert [C.is_collective(op) for op in OPS] == [
        False, True, True, False, True, False, True]


def test_the_share_and_its_split_by_scope(capsys):
    busy = sum(op.seconds for op in OPS)
    assert C.read(_run(OPS)) == pytest.approx(100 * 0.014 / busy)
    assert C.by_scope(OPS) == pytest.approx(
        {"cd/whole_score": 0.005, "agg/hessian_matrix": 0.003,
         "unscoped in re/score": 0.006})
    assert "cd/whole_score" in capsys.readouterr().out


def test_no_collective_reads_zero_and_no_trace_reads_nothing():
    assert C.read(_run([OPS[0], OPS[5]])) == 0.0
    assert C.read(types.SimpleNamespace(trace=None)) is None


def test_the_padding_share_reads_the_counter(monkeypatch):
    import importlib

    metrics = importlib.import_module("photon_tpu.obs.metrics")
    registry = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "registry", registry)
    assert PAD.read(None) is None           # no mesh: nothing ticked it
    for coordinate, real, pad in (("userId", 28_000_000, 200_000),
                                  ("movieId", 28_700_000, 14_400_000)):
        registry.counter("mesh.entity_slots", coordinate=coordinate,
                         kind="real").inc(real)
        registry.counter("mesh.entity_slots", coordinate=coordinate,
                         kind="pad").inc(pad)
    assert PAD.read(None) == pytest.approx(
        100 * 14_600_000 / (56_700_000 + 14_600_000))
