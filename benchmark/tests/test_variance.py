"""``fe-epsilon-variance`` (PR 40): its reference against a float64 numpy
oracle, the cell rehearsed end to end with the program's own count of the
variances it computed, the three variance readers on recorded and hand-made
operations, and the kind's ``_fit`` and ``verify`` catching a timed fit that
does not wait on its variances and a last fit whose variances differ.

    python -m pytest benchmark/tests/test_variance.py -q
"""

import importlib
import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import correct
from benchmark import generators as G
from benchmark import run as R
from benchmark import scope_reader as S
from benchmark import trace_reader as T
from benchmark import variance_roofline
from benchmark.systems import training_variance
from benchmark.traffic import refit_variance as K

ROOT = os.path.dirname(R.HERE)
CONFIG, CELL = "fe-epsilon-variance", "fe-epsilon-variance.refit"
READERS = ("variance_device_share", "variance_roofline", "variance_factor_ms")


def reader(name):
    return importlib.import_module(f"benchmark.layer_metrics.{name}")


def rehearsal_cfg():
    cfg = R.load_json("configs", f"{CONFIG}.json")
    return R.overlaid(cfg, cfg["rehearse"])


# --------------------------------------------------------------------------
# the reference against a float64 numpy oracle
# --------------------------------------------------------------------------

def test_reference_variances_against_float64_oracle():
    """The means' gradient and ``diag(H^-1)``, at a model that is nobody's
    optimum, on rows read in blocks (1,500 rows in blocks of 1,024 and a
    padded remainder, by ``correct._blocks``)."""
    cfg = rehearsal_cfg()
    rows = G.game_rows(cfg, 1500, 5, "train")
    params = {k: (v * 0.3).astype(np.float32)
              for k, v in G.planted_model(cfg, 6).items()}
    ref = correct.load_reference(CONFIG)
    x = rows.x["features"].astype(np.float64)
    theta = params["fixed"].astype(np.float64)
    y, l2 = rows.y.astype(np.float64), 0.7
    s = 1.0 / (1.0 + np.exp(-(x @ theta)))
    want_g = x.T @ (s - y) + l2 * theta
    want_v = np.diag(np.linalg.inv(
        x.T @ ((s * (1 - s))[:, None] * x) + l2 * np.eye(len(theta))))

    _, got_g = correct.objective_and_gradient(ref, params, rows, l2)
    np.testing.assert_allclose(got_g["fixed"], want_g, rtol=0,
                               atol=1e-5 * np.abs(want_g).max())
    got_v = K.reference_variances(ref, params, rows, l2)["fixed"]
    assert got_v.shape == want_v.shape
    # float32 products summed 1,024 rows at a time in float32, the blocks
    # in float64, a float64 inverse: read 7e-8 (a float32 inverse read 7e-7)
    assert np.max(np.abs(got_v - want_v) / want_v) <= 1e-6
    # and it is the inverse's diagonal, not the diagonal's inverse
    simple = 1.0 / (np.einsum("ij,ij,i->j", x, x, s * (1 - s)) + l2)
    assert np.max(np.abs(simple - want_v) / want_v) > 1e-3


# --------------------------------------------------------------------------
# the cell, rehearsed: correct, and the program counted what it computed
# --------------------------------------------------------------------------

def test_the_cell_rehearses_and_the_program_counts_a_variance_a_fit():
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3000000019", "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["window_compiles"]["value"] == 0
    said = re.search(r"the program counted (\d+) variance computations in "
                     r"(\d+) fits", out.stdout)
    # the set-up fit and every fit of the two windows
    assert said and int(said[1]) == int(said[2]) == line["attempted"] + 1
    assert "the fit True, the variances True" in out.stdout


# --------------------------------------------------------------------------
# the three readers
# --------------------------------------------------------------------------

SCOPED = os.path.join(R.HERE, "testdata", "fe-epsilon.refit.scopes.xplane.pb")
VARIANCE = "jit(variances)/optim/variance/"


@pytest.fixture()
def recorded():
    """PR 23's trace of ``fe-epsilon.refit`` on the chip: three fits, no
    variance in it."""
    trace = T.read(SCOPED)
    return trace, S.read(SCOPED, trace.window)


def fake_run(trace, ops, fits):
    trace.scoped_ops = ops           # what scope_reader.of() keeps on it
    return types.SimpleNamespace(
        trace=trace, cfg=R.load_json("configs", f"{CONFIG}.json"),
        cell=R.load_json("workloads", f"{CELL}.json"),
        peaks=R.load_json("peaks.json")["TPU v5 lite"],
        traced={"fits": fits})


def test_on_a_program_without_the_scope_the_readers_report_nothing(recorded):
    """The parent's side of a comparison: no ``optim/variance`` anywhere,
    whatever the kind's samples say; and with no trace at all."""
    trace, ops = recorded
    run = fake_run(trace, ops, [{"variances": 0}] * 3)
    for name in READERS:
        assert reader(name).read(run) is None, name
    run.trace = None
    for name in READERS:
        assert reader(name).read(run) is None, name


def test_the_readers_on_a_window_with_three_variances(recorded):
    """The recorded solve's operations and, made by hand, three variances
    of 30 ms each: the weights' pass over X nested two scopes deep, the
    Gram, the factorisation and inverse, the diagonal."""
    trace, ops = recorded
    solve = sum(op.seconds for op in ops)
    made = [("hessian/agg/hessian_weights/agg/margins/dot_general:", 0.0171),
            ("hessian/agg/hessian_matrix/dot_general:", 0.0519),
            ("factor_solve/jit(_cholesky)/cholesky:", 0.0150),
            ("factor_solve/jit(_cho_solve)/triangular_solve:", 0.0057),
            ("diagonal/jit(_diag)/gather:", 0.0003)]
    ops = ops + [S.Op(f"%made.{i}", VARIANCE + path, "fusion", 7, s, 0.0)
                 for i, (path, s) in enumerate(made)]
    run = fake_run(trace, ops, [{"variances": 1}] * 3)
    assert variance_roofline.seconds_under(ops) == pytest.approx(0.09)
    assert reader("variance_device_share").read(run) == pytest.approx(
        100 * 0.09 / (solve + 0.09))
    assert reader("variance_factor_ms").read(run) == pytest.approx(
        1e3 * 0.0207 / 3)
    # ONE variance's floor: the symmetric half of X^T D X at the bfloat16
    # peak, 530,000 x 2,000 x 2,001 / 197e12 = 10.77 ms, over one read of
    # X (5.18 ms): three of them over 90 ms
    least = variance_roofline.least_seconds(530000, 2000, run.peaks)
    assert least == pytest.approx(530000 * 2000 * 2001 / 197e12)
    assert least == pytest.approx(0.01077, abs=1e-5)
    assert reader("variance_roofline").read(run) == pytest.approx(
        100 * 3 * least / 0.09)
    # counted by the PROGRAM: a kind or program that counts none reads none
    run.traced = {"fits": [{}] * 3}
    assert reader("variance_roofline").read(run) is None
    # a failed fit's sample is no fit
    run.traced = {"fits": [{"variances": 1}] * 3 + [{"error": "x"}]}
    assert reader("variance_factor_ms").read(run) == pytest.approx(
        1e3 * 0.0207 / 3)


@pytest.mark.parametrize("name", READERS)
def test_the_readers_keep_the_contract(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}[name]
    r = reader(name)
    assert (listed["unit"], listed["better"], listed["source"],
            listed["layer"], listed["moves"], listed["workloads"]) == \
        (r.UNIT, r.BETTER, r.SOURCE, r.LAYER, r.MOVES, [CELL])
    assert r.__doc__ and name in R.load_json(
        "workloads", f"{CELL}.json")["per_layer"]


# --------------------------------------------------------------------------
# the kind: a fit is waited on its variances, and verify holds them
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    cell = R.load_json("workloads", f"{CELL}.json")
    ctx = R.Context(cell, rehearsal_cfg(), 7, True,
                    str(tmp_path_factory.mktemp("out")), say=lambda m: None)
    state = K.setup(ctx)
    return ctx, state, K.measure(ctx, state, 0.5)


def test_a_rehearsed_window_verifies(rehearsed):
    ctx, state, window = rehearsed
    assert window["fits"] and all(
        f["waited_on"] == ["fixed.means", "fixed.variances"] and f["ready"]
        and f["variances"] == 1 for f in window["fits"])
    assert K.verify(ctx, state, [window]) == (True, len(window["fits"]), 0)


def test_a_timed_fit_that_does_not_wait_on_the_variances_is_caught(
        rehearsed, monkeypatch):
    """``refit``'s wait, on the means alone: the fit's clock would stop
    with the variance's program still running."""
    import jax

    from benchmark.systems import training

    ctx, state, window = rehearsed

    def means_only(cfg, model):
        jax.block_until_ready(training.coefficient_arrays(cfg, model))
        return ["fixed.means"]

    monkeypatch.setattr(training_variance, "wait", means_only)
    unwaited = K._fit(ctx, state)
    assert "error" not in unwaited
    ok, attempted, failed = K.verify(
        ctx, state, [{"fits": window["fits"] + [unwaited]}])
    assert not ok and (attempted, failed) == (len(window["fits"]) + 1, 0)


def test_verify_fails_when_the_last_variances_differ_from_the_first(
        rehearsed):
    ctx, state, window = rehearsed
    moved = dict(state, variances={
        k: np.nextafter(v, np.float32(np.inf))
        for k, v in state["variances"].items()})
    assert not K.verify(ctx, moved, [window])[0]
    # and when the last fit published none at all
    bare = training_variance.estimator(ctx.cfg, variance="NONE")
    model = bare.fit(state["frame"])[-1].model
    assert not K.verify(ctx, dict(state, model=model), [window])[0]


def test_a_lesser_job_fails_the_variance_limit(rehearsed):
    """bfloat16 features and SIMPLE in FULL's place, at toy size on the
    CPU, through the reading the cell takes (on the chip: the
    configuration's ``correct_variance.why``)."""
    import jax.numpy as jnp

    from benchmark.systems import training

    ctx, state, _ = rehearsed
    cfg, frame = ctx.cfg, state["frame"]
    train = G.game_rows(cfg, cfg["rows"], cfg["data_seed"], "train",
                        G.planted_model(cfg, cfg["data_seed"]))
    ref = correct.load_reference(CONFIG)

    def gap(**kw):
        est = training_variance.estimator(cfg, **kw)
        model = est.fit(frame)[-1].model
        return K.variance_gap(
            cfg, ref, training.model_tables(cfg, est, model),
            training_variance.variance_tables(cfg, model), train)

    assert gap()[0]
    for kw in ({"feature_dtype": jnp.bfloat16}, {"variance": "SIMPLE"}):
        ok, measured = gap(**kw)
        assert not ok, (kw, measured)
    ok, measured = gap(variance="NONE")
    assert not ok and np.isnan(measured["relative_gap"]["fixed"])
