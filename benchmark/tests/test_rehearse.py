"""Every cell end to end on the CPU at a tiny size; the references against
a float64 one-hot oracle; the generators against the originals they copy;
``BENCHMARK.json`` against the files it points at.

    python -m pytest benchmark/tests -q
"""

import copy
import glob
import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import correct
from benchmark import generators as G
from benchmark import run as R

ROOT = os.path.dirname(R.HERE)
CELLS = sorted(os.path.basename(p)[:-len(".json")]
               for p in glob.glob(os.path.join(R.HERE, "workloads", "*.json")))
CONFIGS = sorted(os.path.basename(p)[:-len(".json")]
                 for p in glob.glob(os.path.join(R.HERE, "configs", "*.json")))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def reader(package, name):
    return importlib.import_module(f"benchmark.{package}.{name}")


def rehearsal_cfg(name):
    cfg = R.load_json("configs", f"{name}.json")
    return R.overlaid(cfg, cfg["rehearse"])


# --------------------------------------------------------------------------
# every cell runs, and its line keeps the contract
# --------------------------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses(cell, trace):
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "2", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(line) == RESULT_KEYS           # no breakdown off the chip
    assert line["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    spec = R.load_json("workloads", f"{cell}.json")
    package, names = (("layer_metrics", spec["per_layer"]) if trace
                      else ("end_to_end", spec["end_to_end"]))
    assert set(line["metrics"]) <= set(names)
    for name, m in line["metrics"].items():
        # a CPU run prints counts only, never a time, a rate or a share
        assert reader(package, name).SOURCE == "program_counter"
        assert m["unit"] == reader(package, name).UNIT


def test_no_accelerator_no_result():
    """Without --rehearse a CPU is refused: exit 2 and no result line."""
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 2
    assert not any(l.startswith("{") for l in out.stdout.splitlines())


# --------------------------------------------------------------------------
# `correct` is tight: a cut-short or lower-precision fit fails it
# --------------------------------------------------------------------------

def _fitted(cfg, frame, **kw):
    from benchmark.systems import training

    est = training.estimator(cfg, **kw)
    model = est.fit(frame)[-1].model
    return training.model_tables(cfg, est, model), \
        training.solver_iterations(cfg, est)


@pytest.fixture(scope="module", params=CONFIGS)
def toy(request):
    from benchmark.systems import training

    cfg = rehearsal_cfg(request.param)
    planted = G.planted_model(cfg, 1)
    train = G.game_rows(cfg, cfg["rows"], 1, "train", planted)
    validation = G.game_rows(cfg, cfg["validation_rows"], 1, "validation-1",
                             planted)
    frame = training.frame(cfg, train)
    tables, iterations = _fitted(cfg, frame)
    ref = correct.load_reference(cfg["name"])

    def holds(tables_, cfg_=cfg):
        return correct.training(cfg_, ref, tables_, train, validation)

    return cfg, frame, tables, iterations, holds


def test_proper_fit_is_correct(toy):
    _, _, tables, _, holds = toy
    ok, measured = holds(tables)
    assert ok, measured


def test_one_sweep_fewer_fails(toy):
    cfg, frame, _, _, holds = toy
    if cfg["sweeps"] == 1:
        pytest.skip("a one-sweep configuration has no sweep to drop")
    ok, measured = holds(_fitted(cfg, frame, sweeps=cfg["sweeps"] - 1)[0])
    assert not ok, measured


def test_half_the_iterations_fails(toy):
    cfg, frame, _, iterations, holds = toy
    cut = copy.deepcopy(cfg)
    for c in cut["coordinates"]:
        c["optimizer"]["max_iterations"] = max(1, iterations[c["id"]] // 2)
    ok, measured = holds(_fitted(cut, frame)[0])
    assert not ok, measured


def test_bfloat16_features_fail(toy):
    """Where the fixed effect is the last coordinate updated. In a GLMix
    fit it is the first, and what the later coordinates move (1e-3 of the
    objective) hides a bfloat16 design matrix (1e-5): the cell that shares
    its aggregators with it, fe-epsilon, is the one that catches that."""
    import jax.numpy as jnp

    cfg, frame, _, _, holds = toy
    if cfg["coordinates"][-1]["kind"] != "fixed":
        pytest.skip("the fixed effect is not the last coordinate updated")
    ok, measured = holds(_fitted(cfg, frame, feature_dtype=jnp.bfloat16)[0])
    assert not ok, measured


def test_a_zeroed_coordinate_fails_the_auc_margin(toy):
    cfg, _, tables, _, holds = toy
    for cid in tables:
        zeroed = {**tables, cid: np.zeros_like(tables[cid])}
        _, measured = holds(zeroed)
        assert (measured["auc_planted"] - measured["auc"]
                > cfg["correct"]["auc_margin"]), (cid, measured)


# --------------------------------------------------------------------------
# each reference against a float64 numpy one-hot oracle
# --------------------------------------------------------------------------

def _one_hot_design(cfg, rows):
    """[n, total] float64: a fixed effect's block as it is, a random
    effect's block one-hot by entity; and each coordinate's column slice."""
    blocks, slices, at = [], {}, 0
    for c in cfg["coordinates"]:
        x = rows.x[c["shard"]].astype(np.float64)
        if c["kind"] == "random":
            count = cfg["entities"][c["entity"]]["count"]
            wide = np.zeros((len(x), count * c["width"]))
            cols = (rows.ids[c["entity"]][:, None] * c["width"]
                    + np.arange(c["width"])[None, :])
            np.put_along_axis(wide, cols, x, axis=1)
            x = wide
        blocks.append(x)
        slices[c["id"]] = slice(at, at + x.shape[1])
        at += x.shape[1]
    return np.concatenate(blocks, axis=1), slices


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_against_float64_oracle(name):
    cfg = rehearsal_cfg(name)
    rows = G.game_rows(cfg, 1500, 5, "train")
    params = {k: (v * 0.3).astype(np.float32)
              for k, v in G.planted_model(cfg, 6).items()}
    ref = correct.load_reference(name)
    design, slices = _one_hot_design(cfg, rows)
    theta = np.concatenate([params[c["id"]].astype(np.float64).ravel()
                            for c in cfg["coordinates"]])
    z = design @ theta
    y = rows.y.astype(np.float64)
    l2 = 0.7
    want_f = np.sum(np.logaddexp(0.0, z) - y * z) + 0.5 * l2 * theta @ theta
    want_g = design.T @ (1.0 / (1.0 + np.exp(-z)) - y) + l2 * theta

    np.testing.assert_allclose(correct.reference_scores(ref, params, rows),
                               z, rtol=0, atol=2e-5)
    got_f, got_g = correct.objective_and_gradient(ref, params, rows, l2)
    assert abs(got_f - want_f) <= 1e-5 * want_f
    for c in cfg["coordinates"]:
        np.testing.assert_allclose(
            got_g[c["id"]].ravel(), want_g[slices[c["id"]]],
            rtol=0, atol=1e-5 * np.abs(want_g).max())


# --------------------------------------------------------------------------
# the generators reproduce the arithmetic they copy
# --------------------------------------------------------------------------

def test_zipf_and_arrivals_match_replay():
    from photon_tpu.serving import replay

    u = np.random.default_rng(0).random(5000)
    u = np.concatenate([u, [1e-9, 1e-30, 1.0 - 1e-12]])
    for a, count in ((1.5, 138_493), (1.1, 27_278)):
        want = [(replay._zipf_rank(float(x), a) - 1) % count for x in u]
        assert G.zipf_folded(u, a, count).tolist() == want
    rate = 3210.0
    t, want = 0.0, []
    for x in u:
        t += -np.log(x) / rate          # replay.generate's gap
        want.append(t)
    np.testing.assert_allclose(G.arrival_times(u, rate), want, rtol=1e-12)


def test_bounded_zipf_is_zipf_assign():
    """bench.py::zipf_assign's distribution, drawn by inverse CDF."""
    shares = G.zipf_bounded_shares(1000, 1.1)
    p = 1.0 / np.arange(1, 1001) ** 1.1
    np.testing.assert_allclose(shares, p / p.sum())
    ids = G.inverse_cdf(shares, np.random.default_rng(1).random(200_000))
    np.testing.assert_allclose(np.bincount(ids, minlength=1000)[:5] / 2e5,
                               shares[:5], rtol=0.05)


def test_same_seed_same_rows():
    cfg = rehearsal_cfg("glmix-ml20m")
    rows = G.BLOCK_ROWS + 1234
    cfg["entities"]["userId"]["count"] = 400
    a, b = (G.game_rows(cfg, rows, 9, "train") for _ in range(2))
    c = G.game_rows(cfg, rows, 10, "train")
    d = G.game_rows(cfg, rows, 9, "validation-1")
    for k in a.x:
        assert np.array_equal(a.x[k], b.x[k])
        assert not np.array_equal(a.x[k], c.x[k])
        assert not np.array_equal(a.x[k], d.x[k])
    assert np.array_equal(a.y, b.y)
    assert np.array_equal(a.ids["userId"], b.ids["userId"])
    counts = np.bincount(a.ids["userId"])
    assert counts.min() >= cfg["entities"]["userId"]["min_rows"]


# --------------------------------------------------------------------------
# BENCHMARK.json says what the files say
# --------------------------------------------------------------------------

def test_benchmark_json_agrees_with_the_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["command"] == ["python3", "benchmark/run.py"]
    configs = {c["name"]: c for c in bench["configs"]}
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for c in bench["configs"]:
        cfg = R.load_json("configs", f"{c['name']}.json")
        assert os.path.join("benchmark", "configs", f"{c['name']}.json") == c["file"]
        assert cfg["source"] == c["source"]
        assert set(c["reduced"]) == {k.split(".")[0] for k in cfg["reduced"]
                                     if k != "why"}
    for w in bench["workloads"]:
        cell = R.load_json("workloads", f"{w['name']}.json")
        assert (cell["config"], cell["chips"], cell["why"]) == \
            (w["config"], w["chips"], w["why"])
        assert w["config"] in configs and len(w["why"]) <= 200
        assert w["traffic"] == w["name"].split(".", 1)[1]
        for package, key in (("end_to_end", "end_to_end"),
                             ("layer_metrics", "per_layer")):
            for name in cell[key]:
                m, r = metrics[name], reader(package, name)
                assert (m["unit"], m["better"], m["source"]) == \
                    (r.UNIT, r.BETTER, r.SOURCE), name
                assert w["name"] in m.get("workloads", [w["name"]]), name
                if key == "per_layer":
                    assert (m["layer"], m["moves"]) == (r.LAYER, r.MOVES)
                    assert r.MOVES in cell["end_to_end"], (w["name"], name)
    for m in metrics.values():
        for cell in m.get("workloads", []):
            spec = R.load_json("workloads", f"{cell}.json")
            assert m["name"] in spec["end_to_end"] + spec["per_layer"]


def test_benchmark_json_keeps_the_contracts_limits():
    """The limits the driver checks before any run (it refused PR 22's first
    file over a layer named "entry points / compile")."""
    import re

    path = os.path.join(ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        bench = json.load(f)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"[A-Za-z0-9][A-Za-z0-9_.-]{0,63}\Z")
    layer = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
    plain = re.compile(r"[A-Za-z0-9_./-]{1,200}\Z")
    width = re.compile(r"hidden|intermediate|latent|state|proj|head|expan"
                       r"|experts_per|width|_dim\Z|_rank\Z")
    sources = {"device_trace", "program_span", "program_counter", "host_clock"}

    assert 1 <= len(bench["paths"]) <= 16
    assert all(plain.match(p) and not p.startswith("/") and ".." not in p
               for p in bench["paths"])
    assert len(bench["command"]) <= 32
    assert not any(a.startswith("/") or ".." in a for a in bench["command"])
    for top, _, files in os.walk(R.HERE):
        if "/out" in top[len(R.HERE):] or "__pycache__" in top:
            continue
        assert all(plain.match(f) for f in files), (top, files)

    groups = [bench[k] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer")]
    for group in groups:
        names = [e["name"] for e in group]
        assert all(name.match(n) for n in names), names
        assert len(set(names)) == len(names)
        assert all(len(e.get("why", "")) <= 200 for e in group)
    configs, cells, e2e, layers = groups
    assert 1 <= len(configs) <= 24 and 2 <= len(cells) <= 24
    assert 1 <= len(e2e) <= 16 and 1 <= len(layers) <= 128

    files = [c["file"] for c in configs]
    assert len(set(files)) == len(files)
    for c in configs:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert not any(width.search(k) for k in c["reduced"]), c["reduced"]
        assert any(w["config"] == c["name"] for w in cells)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)

    ends = {m["name"]: m for m in e2e}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert ends["setup_s"]["bound"] == 0.1
    for m in layers:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert layer.match(m["layer"]), m["layer"]
        assert m["source"] in sources and m["moves"] in ends
    for m in e2e + layers:
        assert m["better"] in ("lower", "higher")
        assert 1 <= len(m["unit"]) <= 16 and " " not in m["unit"]
        assert set(m.get("workloads", [])) <= {w["name"] for w in cells}

    def reports(metric, cell):
        return cell in metric.get("workloads", [cell])

    for w in cells:
        mine = [m["name"] for m in e2e if reports(m, w["name"])]
        assert "setup_s" in mine and len(mine) >= 2
        moved = [m["moves"] for m in layers if reports(m, w["name"])]
        assert moved and set(moved) <= set(mine)

    seconds = bench["run_seconds"]
    assert isinstance(seconds, int) and 1 <= seconds <= 51
    assert (2 + 14 * 24) * (seconds + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_a_new_cell_is_only_a_file(tmp_path):
    """benchmark/README.md's walk-through: a fifth cell is one added data
    file, and the harness runs it without an edit."""
    import shutil

    shutil.copytree(R.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(os.path.join(ROOT, "photon_tpu"), tmp_path / "photon_tpu")
    spec = R.load_json("workloads", "fe-epsilon.refit.json")
    spec["name"] = "fe-epsilon.refit-again"
    with open(tmp_path / "benchmark" / "workloads"
              / "fe-epsilon.refit-again.json", "w") as f:
        json.dump(spec, f)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "fe-epsilon.refit-again", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--rehearse"],
        cwd=tmp_path, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1])["correct"] is True
