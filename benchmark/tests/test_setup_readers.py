"""The readers of the compile account and of the process's start (PR 36),
which no cell lists yet: the account's stages (``retrace_s``, ``lower_s``,
``cache_load_s``), the traces of repeat fits (``repeat_fit_traces``) and the
seconds before the program's first import (``start_s``). On hand-fed
counters, a hand-made buffer and a hand-set ``_PROCESS_START``; on a program
without the account; and a cell that lists them, in a scratch copy,
rehearsed."""

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

READERS = ("retrace_s", "lower_s", "cache_load_s", "repeat_fit_traces",
           "start_s")


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}")


def test_the_readers_keep_the_contract():
    """What BENCHMARK.json will repeat when a benchmark PR lists them."""
    with open(os.path.join(os.path.dirname(R.HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    ends = {m["name"] for m in bench["end_to_end"]}
    taken = ends | {m["name"] for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    for name in READERS:
        r = reader(name)
        assert re.fullmatch(r"[A-Za-z0-9][A-Za-z0-9_.-]{0,63}", name)
        assert name not in taken          # registering is appending
        assert r.LAYER in layers and r.MOVES in ends
        assert r.BETTER == "lower"
        assert 1 <= len(r.UNIT) <= 16 and " " not in r.UNIT
        assert r.__doc__ and callable(r.read)
    assert {n: (reader(n).LAYER, reader(n).MOVES, reader(n).SOURCE)
            for n in READERS} == {
        "retrace_s": ("compile", "setup_s", "program_span"),
        "lower_s": ("compile", "setup_s", "program_span"),
        "cache_load_s": ("compile", "setup_s", "program_span"),
        # a count: the one of the five a CPU rehearsal prints
        "repeat_fit_traces": ("compile", "train_rows_per_s",
                              "program_counter"),
        "start_s": ("device", "setup_s", "host_clock")}
    # no cell lists one: listing is a benchmark PR's (B1)
    for cell in os.listdir(os.path.join(R.HERE, "workloads")):
        spec = R.load_json("workloads", cell)
        assert not set(READERS) & set(spec.get("per_layer", [])), cell


def fits(*pairs, end):
    return {"fits": [{"start": s, "end": e} for s, e in pairs], "end": end}


def test_on_a_program_without_the_account_they_report_nothing(monkeypatch):
    from photon_tpu.obs.metrics import registry
    from photon_tpu.utils import compile_cache

    registry.clear()
    # the parent: it counts jitcache builds and keeps no account
    registry.counter("compile_cache.compiles", phase="steady_state",
                     what="solve").inc(3)
    monkeypatch.delattr(compile_cache, "account_compiles")
    monkeypatch.delattr(compile_cache, "programs")
    run = types.SimpleNamespace(window=fits((1.0, 2.0), end=2.0), traced=None)
    assert [reader(n).read(run) for n in READERS[:4]] == [None] * 4
    registry.clear()


def test_the_stage_readers_sum_the_programs_counter():
    from photon_tpu.obs.metrics import registry

    registry.clear()
    names = READERS[:3]
    # the account is kept and nothing matched: a true zero
    assert [reader(n).read(None) for n in names] == [0.0, 0.0, 0.0]
    for stage, during, seconds in (
            ("trace", "none", 9.0), ("trace", "ingest/stats", 0.5),
            ("trace", "ingest/prepare", 0.25), ("lower", "none", 2.0),
            ("lower", "ingest/stats", 0.125), ("backend", "none", 70.0)):
        registry.counter("compile.seconds", stage=stage,
                         during=during).inc(seconds)
        registry.counter("compile.programs", stage=stage,
                         during=during).inc(7)
    registry.counter("compile.cache", outcome="miss").inc(5)
    assert [reader(n).read(None) for n in names] == [9.75, 2.125, 0.0]
    registry.counter("compile.seconds", stage="cache_load",
                     during="none").inc(3.0)
    registry.counter("compile.seconds", stage="cache_load",
                     during="ingest/stats").inc(0.5)
    assert [reader(n).read(None) for n in names] == [9.75, 2.125, 3.5]
    registry.clear()


def test_repeat_fit_traces_counts_the_buffers_traces_inside_a_window():
    """One event before the window, one inside it and one after; the traced
    tail has one more; a ``lower`` event inside a window is no trace."""
    from photon_tpu.obs import spans
    from photon_tpu.utils import compile_cache

    def unix(perf):                      # the span epoch, the other way
        return perf - spans._EPOCH_PERF + spans._EPOCH_UNIX

    r = reader("repeat_fit_traces")
    compile_cache.clear_programs()
    window = fits((100.0, 101.0), (101.0, 102.5), end=102.0)
    traced = fits((103.0, 104.0), end=104.0)
    run = types.SimpleNamespace(window=window, traced=traced)
    assert r.read(run) == 0.0            # kept, and nothing in the windows
    for fun, stage, at in (("solve", "trace", 99.5),     # the set-up fit's
                           ("solve", "trace", 100.5),
                           ("jit(solve)", "lower", 100.6),
                           ("late", "trace", 102.4),     # the last fit ran on
                           ("verify", "trace", 102.75),  # between the two
                           ("solve", "trace", 103.5),
                           ("after", "trace", 104.5)):
        compile_cache._PROGRAMS.append((fun, stage, 0.125, unix(at), "none"))
    assert r.read(run) == 3.0
    assert r.read(types.SimpleNamespace(window=window, traced=None)) == 2.0
    # a kind whose samples are no fits has nothing for it to read
    serving = types.SimpleNamespace(window={"requests": []}, traced=None)
    assert r.read(serving) is None
    compile_cache.clear_programs()


def test_start_s_is_the_epoch_less_the_process_start(monkeypatch):
    from photon_tpu.obs import spans

    r = reader("start_s")
    main = types.ModuleType("__main__")
    monkeypatch.setitem(sys.modules, "__main__", main)
    assert r.read(None) is None          # the harness imported, not run
    main._PROCESS_START = spans._EPOCH_PERF - 12.5
    assert r.read(None) == 12.5


def test_a_cell_that_lists_them_rehearses(tmp_path):
    """``fe-epsilon.refit`` with the five appended to ``per_layer``, as a
    benchmark PR would leave it: the harness runs it without another edit,
    and a CPU run prints the one count among them, which reads 0."""
    shutil.copytree(R.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(os.path.join(os.path.dirname(R.HERE), "photon_tpu"),
               tmp_path / "photon_tpu")
    spec = R.load_json("workloads", "fe-epsilon.refit.json")
    listed = len(spec["per_layer"])
    spec["per_layer"] += list(READERS)
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
    assert line["metrics"]["repeat_fit_traces"] == {
        "value": 0.0, "unit": "programs"}
    # counts only on a CPU: none of the four others
    assert not (set(READERS) - {"repeat_fit_traces"}) & set(line["metrics"])
    assert len(spec["per_layer"]) == listed + 5
