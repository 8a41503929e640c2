"""The compile pipeline counted inside the program (PERF.md §3, layer
``compile``): JAX's trace / lower / cache-load / compile stages counted
where JAX reports them (``utils/compile_cache.account_compiles``) and booked
under the ``Timed`` phase the host stood in (``utils/timing.current_phase``).

What these tests hold is what the benchmark's readers and the next
``perf_opt`` on ``setup_s`` stand on: a first fit books its stages, the
programs of ``ingest/stats`` under that phase; nested trace events are
booked once, so the stages sum to wall time; a repeat fit reaches no
listener at all; a program the persistent cache served is ``cache_load``,
not ``backend``; and nothing stands on the fit's path."""

import logging
import threading
import time

import jax
import jax.monitoring as monitoring
import jax.numpy as jnp
import pytest

from photon_tpu import obs
from photon_tpu.obs import spans
from photon_tpu.obs.metrics import registry
from photon_tpu.utils import compile_cache, jitcache, timing

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
STAGES = ("trace", "lower", "cache_load", "backend")
KINDS = ("scalar", "event", "span", "duration")


def account():
    """{(counter, stage, during): value} of the compile account."""
    return {(what, labels["stage"], labels["during"]): value
            for what in ("seconds", "programs")
            for labels, value in registry.series(f"compile.{what}")}


def added(before, after):
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)}


def stage_event(event, seconds, fun="f", inside=()):
    """One stage event as JAX sends it: a scalar when it begins, a time
    span when it ends, the events ``inside`` in between."""
    start = time.time()
    monitoring.record_scalar(event, start, fun_name=fun)
    for send in inside:
        send()
    monitoring.record_event_time_span(event, start, start + seconds,
                                      fun_name=fun)


class Listening:
    """Every ``jax.monitoring`` call while it is open: how many of each
    kind, every stage event's time span and the plain sum of the stage
    events' durations."""

    def __enter__(self):
        self.calls = dict.fromkeys(KINDS, 0)
        self.spans, self.durations = [], 0.0
        monitoring.register_scalar_listener(self._scalar)
        monitoring.register_event_listener(self._event)
        monitoring.register_event_time_span_listener(self._span)
        monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc):
        monitoring.unregister_scalar_listener(self._scalar)
        monitoring.unregister_event_listener(self._event)
        monitoring.unregister_event_time_span_listener(self._span)
        monitoring.unregister_event_duration_listener(self._duration)

    def _scalar(self, event, value, **kw):
        self.calls["scalar"] += 1

    def _event(self, event, **kw):
        self.calls["event"] += 1

    def _span(self, event, start, end, **kw):
        self.calls["span"] += 1
        if event in (TRACE, LOWER, BACKEND):
            self.spans.append((start, end))

    def _duration(self, event, seconds, **kw):
        self.calls["duration"] += 1
        if event in (TRACE, LOWER, BACKEND):
            self.durations += seconds

    def union(self):
        """Seconds some stage event was open (one thread)."""
        covered, upto = 0.0, float("-inf")
        for start, end in sorted(self.spans):
            covered += max(end - max(start, upto), 0.0)
            upto = max(upto, end)
        return covered


@pytest.fixture(scope="module")
def two_fits():
    """A toy GLMix estimator fitted twice on one frame, telemetry off,
    nothing traced before: what each fit added to the account, and every
    ``jax.monitoring`` call JAX made during each."""
    from tests.test_game import glmix_estimator
    from tests.test_scopes import _skewed_frame

    obs.reset()
    jitcache.clear()
    jax.clear_caches()
    compile_cache.account_compiles()
    frame = _skewed_frame()
    est = glmix_estimator(num_iterations=1)
    before = account()
    with Listening() as heard_first:
        est.fit(frame)
    first = added(before, account())
    between = account()
    with Listening() as heard_second:
        est.fit(frame)
    second = added(between, account())
    return first, second, heard_first, heard_second


@pytest.mark.parametrize("stage", ["trace", "lower"])
def test_a_first_fit_ticks_its_stages(two_fits, stage):
    """The solves are traced in no ``Timed`` phase: ``during`` is ``none``
    (nothing marks a fit; the fit's path is the parent's)."""
    first = two_fits[0]
    assert first.get(("programs", stage, "none"), 0) >= 2, first
    assert first.get(("seconds", stage, "none"), 0) > 0, first


def test_every_lowered_program_was_loaded_or_compiled(two_fits):
    """``backend`` where XLA compiled, ``cache_load`` where the persistent
    cache served the program (the suite's cache is on): one or the other
    for every program that was lowered, under the same phase."""
    first = two_fits[0]
    for during in ("none", "ingest/stats"):
        lowered = first.get(("programs", "lower", during), 0)
        ended = (first.get(("programs", "backend", during), 0)
                 + first.get(("programs", "cache_load", during), 0))
        assert lowered >= 1 and ended == lowered, (during, first)


def test_a_phase_of_the_set_up_books_its_own(two_fits):
    """``padding_waste()``'s tiny programs are traced inside the ``Timed``
    phase ``ingest/stats``: they are booked there."""
    first = two_fits[0]
    assert first.get(("programs", "trace", "ingest/stats"), 0) >= 1, first
    assert {during for _, _, during in first} <= {
        "none", "ingest/stats", "ingest/prepare", "ingest/h2d"}, first


def test_the_stages_own_seconds_sum_to_the_union_of_the_spans(two_fits):
    """Trace events nest, and each reports its whole span: the plain sum of
    durations counts an inner trace once for every trace around it. The
    account's seconds are the time some stage was open, within 1%."""
    first, _, heard, _ = two_fits
    booked = sum(v for (what, _, _), v in first.items() if what == "seconds")
    assert heard.calls["span"] > sum(
        v for (what, _, _), v in first.items() if what == "programs")
    assert booked == pytest.approx(heard.union(), rel=0.01)
    assert heard.durations > 1.01 * booked


def test_a_repeat_fit_ticks_nothing_and_calls_no_listener(two_fits):
    _, second, _, heard = two_fits
    assert second == {}
    assert heard.calls == dict.fromkeys(KINDS, 0)


def test_nothing_stands_on_the_fits_path():
    """One Python frame more between the caller and the solves' traces was
    read to cost ``glmix-ml20m-lbfgs.refit``'s first fit seconds of tracing
    (PERF.md §6): ``fit`` / ``fit_swept`` are the functions their ``def``
    made, and a repeat fit's traces are found by time stamp."""
    from photon_tpu.estimators.game_estimator import GameEstimator

    for name in ("fit", "fit_swept"):
        method = getattr(GameEstimator, name)
        assert not hasattr(method, "__wrapped__")
        assert method.__code__.co_name == name
        assert "current_phase" not in method.__code__.co_names
    assert not hasattr(timing, "mark_fit")


def test_registering_twice_registers_once():
    from jax._src import monitoring as m

    compile_cache.account_compiles()
    compile_cache.account_compiles()
    assert m.get_scalar_listeners().count(compile_cache._on_scalar) == 1
    assert m.get_event_listeners().count(compile_cache._on_event) == 1
    assert m.get_event_time_span_listeners().count(
        compile_cache._on_time_span) == 1
    assert compile_cache._on_time_span not in m.get_event_duration_listeners()


def test_the_jitcache_registers_for_a_library_user(monkeypatch):
    """A process that never calls ``maybe_enable`` is covered from its
    first jitcache build."""
    called = []
    monkeypatch.setattr(compile_cache, "account_compiles",
                        lambda: called.append(1))
    jitcache.get_or_build(("test_compile_account", object()),
                          lambda: (lambda: None))
    assert called


def test_nested_stage_events_are_booked_once():
    """The account books an event's OWN seconds, so the stage sums to the
    outermost span, and counts one program."""
    compile_cache.account_compiles()
    compile_cache.clear_programs()
    before = account()

    def inner():
        stage_event(TRACE, 0.25, fun="inner")

    def middle():
        stage_event(TRACE, 0.5, fun="middle", inside=(inner, inner))

    stage_event(TRACE, 2.0, fun="outer", inside=(middle, inner))
    got = added(before, account())
    assert got[("programs", "trace", "none")] == 1
    assert got[("seconds", "trace", "none")] == pytest.approx(2.0)
    kept = compile_cache.programs()
    assert [(r["fun"], r["stage"], r["during"]) for r in kept] == [
        ("outer", "trace", "none")]
    assert kept[0]["seconds"] == pytest.approx(2.0)


@pytest.mark.parametrize("outcome,stage", [(HIT, "cache_load"),
                                           (MISS, "backend"),
                                           (None, "backend")])
def test_a_cache_hit_books_the_backend_event_as_cache_load(outcome, stage):
    compile_cache.account_compiles()
    before = account()
    cache_before = registry.snapshot()["counters"]
    inside = () if outcome is None else (
        lambda: monitoring.record_event(outcome),)
    stage_event(BACKEND, 0.5, fun="jit(f)", inside=inside)
    # the outcome does not outlive its program
    stage_event(BACKEND, 0.25, fun="jit(g)")
    got = added(before, account())
    want = {("programs", "backend", "none"): 1.0,
            ("seconds", "backend", "none"): 0.25}
    want["programs", stage, "none"] = want.get(
        ("programs", stage, "none"), 0) + 1.0
    want["seconds", stage, "none"] = want.get(
        ("seconds", stage, "none"), 0) + 0.5
    assert got == pytest.approx(want)
    if outcome is not None:
        key = 'compile.cache{outcome="%s"}' % (
            "hit" if outcome == HIT else "miss")
        assert (registry.snapshot()["counters"][key]
                - cache_before.get(key, 0.0)) == 1


def test_the_during_stack_survives_an_exception():
    assert timing.current_phase() == "none"
    with pytest.raises(ValueError):
        with timing.Timed("ingest/prepare/per_user/pad",
                          level=logging.DEBUG):
            assert timing.current_phase() == "ingest/prepare"
            with timing.Timed("ingest/h2d/per_user", level=logging.DEBUG):
                assert timing.current_phase() == "ingest/h2d"
                raise ValueError("inside a phase")
    assert timing.current_phase() == "none"
    # a driver's log line is no phase
    with timing.Timed("train 3 configuration(s)", level=logging.DEBUG):
        assert timing.current_phase() == "none"
        with timing.Timed("ingest/stats", level=logging.DEBUG):
            assert timing.current_phase() == "ingest/stats"
            before = account()
            stage_event(LOWER, 0.5)
            assert added(before, account()) == pytest.approx({
                ("programs", "lower", "ingest/stats"): 1.0,
                ("seconds", "lower", "ingest/stats"): 0.5})
        assert timing.current_phase() == "none"


def test_the_during_stack_is_per_thread():
    seen = {}
    inside, leave = threading.Event(), threading.Event()

    def other():
        seen["before"] = timing.current_phase()
        with timing.Timed("ingest/h2d/per_item", level=logging.DEBUG):
            seen["inside"] = timing.current_phase()
            inside.set()
            leave.wait(timeout=30)
        seen["after"] = timing.current_phase()

    with timing.Timed("ingest/prepare/per_user/group", level=logging.DEBUG):
        worker = threading.Thread(target=other)
        worker.start()
        assert inside.wait(timeout=30)
        assert timing.current_phase() == "ingest/prepare"
        leave.set()
        worker.join(timeout=30)
        assert not worker.is_alive()
    assert seen == {"before": "none", "inside": "ingest/h2d",
                    "after": "none"}
    assert timing.current_phase() == "none"


def test_timed_records_what_it_did_before():
    """The phase stack adds nothing to ``Timed``'s records."""
    timing.clear_timings()
    with timing.Timed("ingest/stats", level=logging.DEBUG) as phase:
        pass
    with timing.Timed("a log line", level=logging.DEBUG):
        pass
    assert [label for label, _ in timing.timing_records()] == [
        "ingest/stats", "a log line"]
    assert timing.timing_records()[0][1] == phase.seconds
    timing.clear_timings()


def test_the_buffer_holds_the_newest_256_and_reset_empties_it():
    compile_cache.account_compiles()
    compile_cache.clear_programs()
    for i in range(compile_cache.MAX_PROGRAMS + 44):
        stage_event(LOWER, 0.001 * (i + 1), fun=f"jit(p{i})")
    kept = compile_cache.programs()
    assert compile_cache.MAX_PROGRAMS == 256 and len(kept) == 256
    assert kept[0]["fun"] == "jit(p44)" and kept[-1]["fun"] == "jit(p299)"
    assert set(kept[0]) == {"fun", "stage", "seconds", "start_unix", "during"}
    slowest = compile_cache.report_section()["slowest"]
    assert [r["fun"] for r in slowest] == [
        f"jit(p{i})" for i in range(299, 289, -1)]
    obs.reset()
    assert compile_cache.programs() == []
    assert account() == {}


def test_with_telemetry_on_a_compile_is_a_span_and_the_report_validates():
    compile_cache.account_compiles()
    obs.reset()
    obs.configure(enabled=True)
    try:
        with obs.span("warm"):
            # a function no cache has seen: traced, lowered, compiled
            salt = time.time()
            jax.jit(lambda x: x * salt + 1.0)(jnp.arange(3.0))
        records = spans.records()
        mine = [r for r in records if r["name"].startswith("compile/")]
        names = {r["name"] for r in mine}
        assert {"compile/trace", "compile/lower"} <= names, names
        assert names & {"compile/backend", "compile/cache_load"}, names
        for r in mine:
            assert r["args"]["fun"] and r["args"]["during"] == "none"
            assert r["parent"] == "warm" and r["dur_us"] >= 0
            # on the Chrome trace's clock: inside the span that was open
            assert abs(r["start_unix"] - spans._EPOCH_UNIX
                       - r["ts_us"] * 1e-6) < 1e-6
        warm = next(r for r in records if r["name"] == "warm")
        for r in mine:
            assert warm["ts_us"] - 5e4 <= r["ts_us"] <= (
                warm["ts_us"] + warm["dur_us"] + 5e4)
        assert any(e["name"] == "compile/trace" and e["args"]["fun"]
                   for e in obs.chrome_trace_events())
        report = obs.build_run_report("test")
        assert obs.validate_run_report(report) == []
        section = report["compile"]
        assert set(section["seconds"]) <= set(STAGES)
        assert section["programs"]["trace"]["none"] >= 1
        assert section["seconds"]["trace"]["none"] > 0
        assert 1 <= len(section["slowest"]) <= 10
        assert any(k.startswith("compile.seconds{")
                   for k in report["metrics"]["counters"])
        broken = dict(report, compile={"seconds": {}})
        assert any("compile missing" in e
                   for e in obs.validate_run_report(broken))
    finally:
        obs.reset()
    assert compile_cache.programs() == []


def test_with_telemetry_off_a_compile_is_no_span():
    obs.reset()
    salt = time.time()
    jax.jit(lambda x: x * salt + 2.0)(jnp.arange(3.0))
    assert spans.records() == []
    assert compile_cache.programs()         # the account is kept all the same


def test_a_train_job_reports_its_compile_account(tmp_path):
    """The operator's use: ``cli/train --telemetry`` leaves the account in
    ``runreport.json`` (``metrics`` by stage and phase, ``compile`` with the
    slowest programs by name) and every program's stages in ``trace.json``."""
    import json
    import os

    from photon_tpu.cli import train
    from tests.test_drivers import FIXED_COORD, _write_game_records

    obs.reset()
    jitcache.clear()
    jax.clear_caches()
    data = str(tmp_path / "data" / "train.avro")
    _write_game_records(data, n=300, seed=11)
    out = str(tmp_path / "out")
    try:
        train.run(train.build_arg_parser().parse_args([
            "--input-data-directories", os.path.dirname(data),
            "--root-output-directory", out,
            "--training-task", "LOGISTIC_REGRESSION",
            "--feature-shard-configuration",
            "name=global,feature.bags=features",
            "--coordinate-configuration", FIXED_COORD,
            "--coordinate-update-sequence", "fixed",
            "--telemetry",
        ]))
        with open(os.path.join(out, "runreport.json")) as f:
            report = json.load(f)
        assert obs.validate_run_report(report) == []
        section = report["compile"]
        programs = {stage: sum(by_phase.values())
                    for stage, by_phase in section["programs"].items()}
        assert programs["trace"] >= 1
        assert programs["lower"] == (programs.get("backend", 0)
                                     + programs.get("cache_load", 0))
        assert section["slowest"] and all(
            r["stage"] in STAGES and r["fun"] for r in section["slowest"])
        assert any(k.startswith("compile.seconds{")
                   for k in report["metrics"]["counters"])
        with open(os.path.join(out, "trace.json")) as f:
            events = json.load(f)["traceEvents"]
        assert any(e["name"] == "compile/trace" and e["args"]["fun"]
                   and "during" in e["args"] for e in events)
    finally:
        obs.reset()
