"""``fe-epsilon-standardized`` (PR 38): the raw-rows generator, the plain
reference BOUND to its own statistics against a float64 oracle that
standardises the matrix first, the two readers' contract, and the cell's
limits at toy size (a cut-short fit fails the fit's reading, lower-precision
arithmetic the planted point's, through the kind's own functions).

    python -m pytest benchmark/tests/test_standardized.py -q
"""

import json
import os
import types

import numpy as np
import pytest

from benchmark import correct
from benchmark import generators as G
from benchmark import generators_raw as raw
from benchmark import roofline, trace_reader
from benchmark import run as R
from benchmark.layer_metrics import (
    aggregator_roofline,
    feature_stats_s,
    standardized_value_gradient_roofline,
)

NAME = "fe-epsilon-standardized"
PEAKS = R.load_json("peaks.json")["TPU v5 lite"]
RECORDED = os.path.join(R.HERE, "testdata", "fe-epsilon.refit.xplane.pb")


def config(rehearse=True):
    cfg = R.load_json("configs", f"{NAME}.json")
    return R.overlaid(cfg, cfg["rehearse"]) if rehearse else cfg


# --------------------------------------------------------------------------
# the generator
# --------------------------------------------------------------------------

def test_same_seed_same_raw_rows():
    cfg = config()
    rows = G.BLOCK_ROWS + 77            # two blocks, the second ragged
    a, b = (raw.game_rows(cfg, rows, 9, "train") for _ in range(2))
    c = raw.game_rows(cfg, rows, 10, "train")
    d = raw.game_rows(cfg, rows, 9, "validation-1")
    assert a.x["features"].dtype == np.float32
    assert a.x["features"].shape == (rows, 2001)
    assert np.array_equal(a.x["features"], b.x["features"])
    assert np.array_equal(a.y, b.y)
    assert not np.array_equal(a.x["features"], c.x["features"])
    assert not np.array_equal(a.x["features"], d.x["features"])
    assert (a.x["features"][:, -1] == 1.0).all()     # the intercept, last


def test_the_labels_and_planted_margins_are_fe_epsilons():
    """Same streams: the unit rows under the raw ones, the labels and the
    planted margins are what ``fe-epsilon`` draws from the same seed, and
    the planted model mapped to raw space scores the RAW rows the same."""
    cfg, base = config(), R.load_json("configs", "fe-epsilon.json")
    rows = 4000
    got = raw.game_rows(cfg, rows, 2008, "train")
    want = G.game_rows(base, rows, 2008, "train")
    assert np.array_equal(got.y, want.y)
    assert np.array_equal(got.logits, want.logits)
    m, s = raw.raw_statistics(cfg, 2008)
    x = got.x["features"].astype(np.float64)
    np.testing.assert_allclose((x[:, :-1] - m) / s, want.x["features"],
                               atol=2e-6)
    theta = raw.planted_model(cfg, 2008)["fixed"]
    assert theta.shape == (2001,)
    np.testing.assert_allclose(x @ theta, want.logits, atol=2e-4)


def test_the_raw_statistics_are_as_assumed():
    cfg = config(rehearse=False)
    m, s = raw.raw_statistics(cfg, cfg["data_seed"])
    assert m.shape == s.shape == (2000,)
    lo, hi = cfg["raw_scale"]
    assert lo <= s.min() < 2 * lo and hi / 2 < s.max() <= hi
    # log-uniform: a quarter of the features a decade
    decades = np.histogram(np.log10(s), bins=[-2, -1, 0, 1, 2])[0]
    assert (np.abs(decades / 2000 - 0.25) < 0.04).all(), decades
    r = m / (s / np.sqrt(2000))          # the mean in standard deviations
    assert -3 <= r.min() < -2.9 and 2.9 < r.max() <= 3
    assert abs(r.mean()) < 0.15 and abs(r.std() - np.sqrt(3)) < 0.08
    # and the rows have them: a feature's sample mean and deviation
    rows = raw.game_rows(config(), 20_000, cfg["data_seed"], "train")
    x = rows.x["features"][:, :-1].astype(np.float64)
    np.testing.assert_allclose(x.std(0) * np.sqrt(2000) / s, 1.0, atol=0.05)
    np.testing.assert_allclose((x.mean(0) - m) / x.std(0), 0.0, atol=0.05)


# --------------------------------------------------------------------------
# the bound reference against a float64 oracle that standardises first
# --------------------------------------------------------------------------

def test_bound_reference_against_float64_oracle():
    cfg = config()
    rows = raw.game_rows(cfg, 1500, 5, "train")
    # another seed's unit-row model in THESE rows' raw units, and an
    # intercept of its own: margins of a few units, no optimum of anything
    m, s = (a.astype(np.float64) for a in raw.raw_statistics(cfg, 5))
    unit = G.planted_model(raw.unit_cfg(cfg), 6)["fixed"] * 0.3
    theta = np.concatenate([unit / s, [0.4 - np.sum(unit * m / s)]]
                           ).astype(np.float32)
    params = {"fixed": theta}
    ref = correct.load_reference(NAME).bind(rows.x["features"])

    # the oracle: standardise the matrix (its own float64 statistics, the
    # intercept column kept), then plain logistic + L2 on the transformed
    # coefficients, and the chain rule back to the original ones
    x = rows.x["features"].astype(np.float64)
    mean, std = x.mean(0), x.std(0, ddof=1)
    mean[-1], std[-1] = 0.0, 1.0
    xs = (x - mean) / std
    t = theta.astype(np.float64)
    tt = t * std
    tt[-1] = t[-1] + t @ mean
    z = xs @ tt
    y = rows.y.astype(np.float64)
    l2 = 0.7
    want_f = np.sum(np.logaddexp(0.0, z) - y * z) + 0.5 * l2 * tt @ tt
    g_t = xs.T @ (1.0 / (1.0 + np.exp(-z)) - y) + l2 * tt
    want_g = g_t * std + g_t[-1] * mean
    want_g[-1] = g_t[-1]

    np.testing.assert_allclose(x @ t, z, rtol=0, atol=1e-9)   # invariance
    np.testing.assert_allclose(correct.reference_scores(ref, params, rows),
                               z, rtol=0, atol=5e-5)
    got_f, got_g = correct.objective_and_gradient(ref, params, rows, l2)
    assert abs(got_f - want_f) <= 1e-5 * want_f
    np.testing.assert_allclose(got_g["fixed"], want_g, rtol=0,
                               atol=1e-5 * np.abs(want_g).max())


def test_the_references_statistics_are_float64_and_its_own():
    x = raw.game_rows(config(), 40_000, 3, "train").x["features"]
    ref = correct.load_reference(NAME)
    mean, std = ref.statistics(x)
    assert mean.dtype == std.dtype == np.float64
    wide = x.astype(np.float64)
    np.testing.assert_allclose(mean[:-1], wide.mean(0)[:-1], rtol=1e-12)
    np.testing.assert_allclose(std[:-1], wide.std(0, ddof=1)[:-1],
                               rtol=1e-12)
    assert (mean[-1], std[-1]) == (0.0, 1.0)
    # unbound: identity statistics, fe-epsilon's functions
    base = correct.load_reference("fe-epsilon")
    params = {"fixed": np.linspace(-1, 1, 2001).astype(np.float32)}
    rows = {"features": x[:64]}
    np.testing.assert_array_equal(ref.score(params, rows, {}),
                                  base.score(params, rows, {}))
    assert float(ref.regulariser(params, 0.7)) == \
        float(base.regulariser(params, 0.7))


# --------------------------------------------------------------------------
# the cell's limits, through the kind's own functions
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def toy():
    from benchmark.systems import training, training_standardized as system
    from benchmark.traffic import refit_standardized as kind

    cfg = config()
    planted = G.planted_model(raw.unit_cfg(cfg), cfg["data_seed"])
    train = raw.game_rows(cfg, cfg["rows"], cfg["data_seed"], "train",
                          planted)
    validation = raw.game_rows(cfg, cfg["validation_rows"], cfg["data_seed"],
                               "validation-1", planted)
    frame = training.frame(cfg, train)
    contexts, intercepts = system.normalization(cfg, frame)
    ref = correct.load_reference(NAME).bind(train.x["features"])
    limits = {**cfg, "correct": cfg["correct_standardized"]}

    def fitted(contexts=contexts, **kw):
        """(the fit holds, the planted point holds, what was measured,
        the model, the solver's iterations) of one fit."""
        est = system.estimator(cfg, contexts, intercepts, **kw)
        tables = training.model_tables(cfg, est, est.fit(frame)[-1].model)
        fit, measured = correct.training(limits, ref, tables, train,
                                         validation)
        point, measured["at_planted"] = kind.at_planted(cfg, est, ref, train)
        return (fit, point, measured, tables,
                training.solver_iterations(cfg, est))

    def holds(tables):
        return correct.training(limits, ref, tables, train, validation)

    return cfg, fitted, holds, contexts


def test_the_cells_rehearsal_limits(toy):
    """The fit's reading catches a solve cut short; the planted point's
    catches arithmetic of a lower precision, wherever the solve stops."""
    import jax.numpy as jnp

    cfg, fitted, holds, _ = toy
    fit, point, measured, tables, iterations = fitted()
    assert fit and point, measured
    fit, point, cut, _, _ = fitted(max_iterations=iterations["fixed"] // 2)
    assert not fit, cut
    # the planted point is no function of the solve: the same reading
    assert point and cut["at_planted"] == measured["at_planted"]
    fit, point, measured, _, _ = fitted(feature_dtype=jnp.bfloat16)
    assert not point, measured
    from benchmark.traffic.refit_standardized import GAPS

    gaps, limits = (measured["at_planted"],
                    cfg["correct_standardized"]["at_planted"])
    assert all(gaps[k] > 5 * limits[k] for k in GAPS)
    _, measured = holds({"fixed": np.zeros_like(tables["fixed"])})
    assert (measured["auc_planted"] - measured["auc"]
            > cfg["correct_standardized"]["auc_margin"])


@pytest.mark.parametrize("rounded", ["margin_shift", "sum_w_dz"])
def test_the_planted_point_sees_one_rounded_piece(rounded, toy, monkeypatch):
    """What a fitted model's reading hides: the margin shift ``-e . shift``
    on its way into the margins, or the sum ``sum(w dz)`` that multiplies
    the shifts on the gradient's way out, rounded to bfloat16 with
    everything else float32. (A context rounded CONSISTENTLY is another
    standardisation, not an error: margins are invariant, and only the L2
    term moves.)"""
    import jax.numpy as jnp

    from photon_tpu.ops import aggregators

    _, fitted, _, _ = toy
    lower = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    if rounded == "margin_shift":
        effective = aggregators.effective_coefficients

        def rounded_shift(coef, norm):
            e, shift = effective(coef, norm)
            return e, lower(shift)

        monkeypatch.setattr(aggregators, "effective_coefficients",
                            rounded_shift)
    else:
        apply = aggregators._apply_factor_and_shift
        monkeypatch.setattr(
            aggregators, "_apply_factor_and_shift",
            lambda v, prefactor, norm: apply(v, lower(prefactor), norm))
    _, point, measured, _, _ = fitted()
    assert not point, measured


def test_objective_at_is_the_original_space_objective(toy):
    """The system's evaluation at a point against the float64 oracle of
    ``test_bound_reference_against_float64_oracle``'s algebra through the
    bound reference: value and original-space gradient, at the planted
    model, to float32."""
    from benchmark.systems import training, training_standardized as system

    cfg, _, _, contexts = toy
    planted = G.planted_model(raw.unit_cfg(cfg), cfg["data_seed"])
    train = raw.game_rows(cfg, cfg["rows"], cfg["data_seed"], "train",
                          planted)
    frame = training.frame(cfg, train)
    est = system.estimator(cfg, contexts, {"features": 2000})
    est.fit(frame)
    theta = raw.planted_model(cfg, cfg["data_seed"])["fixed"].astype(
        np.float32)
    value, gradient = system.objective_at(cfg, est, theta)
    assert gradient.dtype == np.float64 and gradient.shape == (2001,)
    ref = correct.load_reference(NAME).bind(train.x["features"])
    want, want_g = correct.objective_and_gradient(
        ref, {"fixed": theta}, train, cfg["l2"])
    assert abs(value - want) <= 2e-6 * want
    both = [ref.gradient_in_transformed_space(g)
            for g in (gradient, want_g["fixed"])]
    np.testing.assert_allclose(both[0], both[1], rtol=0,
                               atol=2e-5 * np.abs(both[1]).max())
    # the map is to_transformed's inverse transpose: g . theta is kept
    t = np.asarray(ref.to_transformed(theta), np.float64)
    assert both[1] @ t == pytest.approx(want_g["fixed"] @ theta, rel=1e-4)


# --------------------------------------------------------------------------
# the two readers
# --------------------------------------------------------------------------

def test_the_new_readers_keep_the_contract():
    with open(os.path.join(os.path.dirname(R.HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for r, name in ((standardized_value_gradient_roofline,
                     "standardized_value_gradient_roofline"),
                    (feature_stats_s, "feature_stats_s")):
        m = metrics[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (r.UNIT, r.BETTER, r.SOURCE, r.LAYER, r.MOVES)
        assert m["workloads"] == [f"{NAME}.refit"]
        assert r.__doc__ and callable(r.read)
    cell = R.load_json("workloads", f"{NAME}.refit.json")
    assert {"standardized_value_gradient_roofline", "feature_stats_s"} <= \
        set(cell["per_layer"])
    assert cell["traffic"] == {"kind": "refit_standardized"}


def _run(cfg, trace, evaluations=14, fits=3):
    return types.SimpleNamespace(
        cfg=cfg, cell={}, peaks=PEAKS, trace=trace, state={},
        traced={"fits": [{"evaluations": {"fixed": evaluations}}] * fits
                + [{"error": "x"}]})


def test_the_roofline_reader_is_aggregator_rooflines_arithmetic():
    """On the trace recorded on the chip: evaluations counted by the solver
    x the least seconds of ONE read of X over ALL busy seconds; the accepted
    reader's value at the same shape; nothing without a trace, without a
    normalization, or on a configuration of another shape; never over
    100% for what a chip can do in the busy time."""
    trace = trace_reader.read(RECORDED)
    cfg = config(rehearse=False)
    got = standardized_value_gradient_roofline.read(_run(cfg, trace))
    seconds, bound = roofline.least_seconds(
        *roofline.dense_value_gradient(530_000, 2_001), PEAKS)
    assert bound == "bandwidth" and 0.0051 < seconds < 0.0053
    assert got == pytest.approx(
        100.0 * 3 * 14 * seconds / trace_reader.busy_s(trace))
    assert got == pytest.approx(aggregator_roofline.read(_run(cfg, trace)))
    read = standardized_value_gradient_roofline.read
    assert read(_run(cfg, None)) is None
    plain = R.load_json("configs", "fe-epsilon.json")
    assert read(_run(plain, trace)) is None
    assert read(_run({**cfg, "sweeps": 2}, trace)) is None
    # the most evaluations the busy seconds could hold reads 100%
    most = trace_reader.busy_s(trace) / seconds
    assert read(_run(cfg, trace, evaluations=most, fits=1)) == \
        pytest.approx(100.0)


def test_feature_stats_s_reads_the_phase_or_nothing():
    from photon_tpu.utils import timing

    timing.clear_timings()
    assert feature_stats_s.read(None) is None       # a program without it
    with timing.Timed("ingest/feature_stats/features"):
        pass
    with timing.Timed("ingest/feature_stats/other"):
        pass
    with timing.Timed("ingest/stats"):
        pass
    records = dict(timing.timing_records())
    assert feature_stats_s.read(None) == pytest.approx(
        records["ingest/feature_stats/features"]
        + records["ingest/feature_stats/other"])
    timing.clear_timings()
