"""The program's own account of where its device seconds and set-up seconds
go (PERF.md §3): every solver step, aggregator and score program lowers with
its ``jax.named_scope``; dataset preparation records its ``Timed`` phases
and placed bytes once, telemetry off; a span's attributes reach its
profiler annotation; nothing samples memory or pins device arrays because
a fit happened to run with telemetry on.

The names are an interface: the per-layer readers in ``benchmark/`` group a
device trace by them. A renamed or lost scope fails here, on the CPU, before
it blinds a metric on the chip."""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.data.dataset import DataBatch
from photon_tpu.function.objective import L1Regularization, L2Regularization
from photon_tpu.ops import features as F
from photon_tpu.optim.problem import (
    GLMOptimizationConfiguration,
    GlmOptimizationProblem,
    OptimizerConfig,
)
from photon_tpu.types import OptimizerType, TaskType

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCOPE = re.compile(r"(?<![A-Za-z0-9_])(?:optim/[a-z0-9_]+/[a-z0-9_]+"
                    r"|(?:agg|fe|re|cd|serve)/[a-z0-9_]+)")

LINESEARCH = {f"optim/linesearch/{s}" for s in ("init", "trial", "zoom",
                                                 "loop")}


def _steps(solver, *steps):
    return {f"optim/{solver}/{s}" for s in steps}


# solver -> (task, regularisation, its own steps, the aggregators it calls
# on dense features, and on sparse ones where they differ)
SOLVERS = {
    "LBFGS": (TaskType.LOGISTIC_REGRESSION, L2Regularization,
              _steps("lbfgs", "init", "direction", "linesearch", "update",
                     "converged", "loop") | LINESEARCH,
              {"agg/value_and_gradient", "agg/margins"}, None),
    "NEWTON": (TaskType.LOGISTIC_REGRESSION, L2Regularization,
               _steps("newton", "init", "hessian", "factor_solve",
                      "direction", "linesearch", "update", "converged",
                      "loop"),
               {"agg/value_and_gradient", "agg/margins",
                "agg/hessian_weights", "agg/hessian_matrix"}, None),
    # dense and small: the explicit Gauss-Newton matrix; sparse: matrix-free;
    # either way from the weights the evaluations hand out, and no
    # ``agg/hessian_weights`` pass of its own
    "TRON": (TaskType.LOGISTIC_REGRESSION, L2Regularization,
             _steps("tron", "init", "hessian", "direction", "trial",
                    "update", "converged", "loop"),
             {"agg/value_and_gradient", "agg/margins", "agg/hessian_matrix"},
             {"agg/value_and_gradient", "agg/margins", "agg/hessian_vector"}),
    "OWLQN": (TaskType.LOGISTIC_REGRESSION, L1Regularization,
              _steps("owlqn", "init", "direction", "linesearch", "update",
                     "converged", "loop"),
              {"agg/value_and_gradient", "agg/margins"}, None),
    "DIRECT": (TaskType.LINEAR_REGRESSION, L2Regularization,
               _steps("direct", "init", "hessian", "factor_solve", "update"),
               {"agg/value_and_gradient", "agg/margins",
                "agg/hessian_weights", "agg/hessian_matrix"}, None),
}


def scopes_in(lowered_text):
    return set(_SCOPE.findall(lowered_text))


def _batch(sparse, n=64, d=6):
    rng = np.random.default_rng(3)
    x = rng.normal(size=(n, d))
    feats = (F.SparseFeatures(
        jnp.asarray(np.tile(np.arange(d, dtype=np.int32), (n, 1))),
        jnp.asarray(x)) if sparse else jnp.asarray(x))
    return DataBatch(feats, jnp.asarray((rng.random(n) < 0.5).astype(float)),
                     jnp.zeros(n), jnp.ones(n)), d


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
@pytest.mark.parametrize("solver", sorted(SOLVERS))
def test_a_solve_lowers_with_every_step_and_aggregator_named(solver, sparse):
    task, reg, steps, dense_aggs, sparse_aggs = SOLVERS[solver]
    batch, d = _batch(sparse)
    problem = GlmOptimizationProblem(task, GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(optimizer_type=OptimizerType[solver],
                                  max_iterations=5),
        regularization=reg, regularization_weight=1.0))
    one = jnp.asarray(1.0)
    text = problem._solve_fn.lower(jnp.zeros(d), batch, one, one).as_text(
        debug_info=True)
    found = scopes_in(text)
    want = steps | ((sparse_aggs if sparse else None) or dense_aggs)
    assert want <= found, sorted(want - found)
    if "agg/hessian_weights" not in want:
        assert "agg/hessian_weights" not in found
    # and nothing of another solver's leaked in under this one's name
    others = {s for name, spec in SOLVERS.items() if name != solver
              for s in spec[2]} - steps
    assert not found & others, sorted(found & others)


@pytest.mark.parametrize("solver", ["LBFGS", "OWLQN", "NEWTON", "TRON"])
def test_the_fused_kernel_lowers_under_value_and_gradient(solver,
                                                          monkeypatch):
    """On a TPU a dense solve of an admitted width holds the ONE fused
    kernel wherever the objective is evaluated, and every call of it is
    located under ``agg/value_and_gradient`` (so ``benchmark/
    scope_reader.py`` keeps charging its seconds to ``aggregators``);
    TRON's matrix-free program holds it once more, for the CG step's
    product, under ``agg/hessian_vector`` (PR 34). The program is lowered
    FOR a TPU from here (Mosaic serialises the kernel into the custom
    call; nothing is compiled or run)."""
    from photon_tpu.ops import pallas_glm
    from photon_tpu.utils import jitcache

    task, reg, steps, _, _ = SOLVERS[solver]
    monkeypatch.setattr(pallas_glm, "_on_tpu", lambda: True)
    monkeypatch.setattr(pallas_glm, "_default_interpret", lambda: False)
    jitcache.clear()
    n, d = 300, 2000
    batch = DataBatch(jnp.zeros((n, d), jnp.float32),
                      jnp.zeros(n, jnp.float32), jnp.zeros(n, jnp.float32),
                      jnp.ones(n, jnp.float32))
    problem = GlmOptimizationProblem(task, GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(optimizer_type=OptimizerType[solver],
                                  max_iterations=5),
        regularization=reg, regularization_weight=1.0))
    one = jnp.float32(1.0)
    try:
        text = problem._solve_fn.trace(
            jnp.zeros(d, jnp.float32), batch, one, one).lower(
                lowering_platforms=("tpu",)).as_text(debug_info=True)
    finally:
        jitcache.clear()
    assert "tpu_custom_call" in text
    locs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, flags=re.M))
    calls = [locs[ref] for ref in re.findall(
        r"call @_fused\w*\(.*loc\((#loc\d+)\)\s*$", text, flags=re.M)]
    assert calls
    # the innermost scope of each call is the aggregator's
    under = [_SCOPE.findall(loc)[-1] for loc in calls]
    for loc, scope in zip(calls, under):
        assert re.search(scope + r"/jit\(_fused\)", loc), loc
    products = ["agg/hessian_vector"] if solver == "TRON" else []
    assert sorted(set(under)) == sorted(
        {"agg/value_and_gradient", *products}), under
    assert under.count("agg/hessian_vector") == len(products), under
    assert steps <= scopes_in(text), sorted(steps - scopes_in(text))
    if solver in ("LBFGS", "OWLQN"):
        # X theta is inside the kernel: no first pass of its own
        assert "agg/margins" not in scopes_in(text)


def test_the_kernel_flag_is_gone():
    """An environment flag selected the fused kernel by hand until PR 32;
    the kernel is chosen by backend and shape now, and no file a user or
    a driver runs reads the flag's name."""
    paths = [os.path.join(REPO, "README.md"),
             os.path.join(REPO, "chip_smoke.py")]
    for top in ("photon_tpu", "scripts", "benchmark"):
        paths += glob.glob(os.path.join(REPO, top, "**", "*.*"),
                           recursive=True)
    paths = [p for p in paths if os.path.isfile(p)
             and not p.endswith((".pyc", ".so", ".pb", ".gz", ".avro"))]
    assert len(paths) > 100
    flag = "PHOTON_TPU_" + "PALLAS_GLM"
    holders = []
    for path in paths:
        with open(path, errors="ignore") as f:
            if flag in f.read():
                holders.append(os.path.relpath(path, REPO))
    assert not holders, holders


def _vmapped_solve_names(solver, k):
    """The named locations of a vmapped NEWTON or DIRECT solve of ``k``
    coefficients, as lowered."""
    from photon_tpu.optim import direct, newton

    def vg(w):
        return 0.5 * jnp.dot(w, w) + jnp.sum(jnp.cos(w)), w - jnp.sin(w)

    def hess(w):
        return jnp.diag(1.0 - jnp.cos(w)) + jnp.eye(k)

    minimize = {"newton": newton.minimize, "direct": direct.minimize}[solver]
    text = jax.jit(jax.vmap(lambda w0: minimize(vg, hess, w0).coef)).lower(
        jnp.ones((3, k))).as_text(debug_info=True)
    return set(re.findall(r'loc\("(jit\([^"]+)"', text))


def _spd_path_ticks():
    from photon_tpu.obs.metrics import registry

    counters = registry.snapshot()["counters"]
    return {path: counters.get(f'kernels.spd_solve{{path="{path}"}}', 0.0)
            for path in ("lanes", "lapack")}


@pytest.mark.parametrize("path", ["lanes", "lapack"])
@pytest.mark.parametrize("solver", ["newton", "direct"])
def test_the_spd_solve_lowers_under_factor_solve_and_counts_its_path(
        solver, path, monkeypatch):
    """What the solve adds to a program, against the same program with the
    solve stubbed out, is named ``optim/<solver>/factor_solve`` to the last
    operation (the ``custom_vmap`` rule runs at batching time and must still
    inherit the scope), and ``kernels.spd_solve{path}`` ticks once a traced
    program with the path the static shape chose."""
    import importlib

    from photon_tpu.optim.spd import LANES_MAX_DIM

    k = LANES_MAX_DIM if path == "lanes" else LANES_MAX_DIM + 1
    before = _spd_path_ticks()
    with_solve = _vmapped_solve_names(solver, k)
    ticks = {p: n - before[p] for p, n in _spd_path_ticks().items()}
    assert ticks == {p: float(p == path) for p in ("lanes", "lapack")}
    module = importlib.import_module(f"photon_tpu.optim.{solver}")
    # (the stub reads h, or the Hessian's operations would go with the solve)
    monkeypatch.setattr(module, "spd_solve", lambda h, g: g + 0.0 * h[0])
    added = with_solve - _vmapped_solve_names(solver, k)
    scope = f"optim/{solver}/factor_solve"
    assert added and all(scope in name for name in added), sorted(
        name for name in added if scope not in name)


VARIANCE = {
    # variance type -> the steps it runs, the aggregators nested in them
    "FULL": ({"optim/variance/hessian", "optim/variance/factor_solve",
              "optim/variance/diagonal"},
             {"agg/hessian_weights", "agg/hessian_matrix", "agg/margins"}),
    "SIMPLE": ({"optim/variance/hessian", "optim/variance/diagonal"},
               {"agg/hessian_diagonal", "agg/margins"}),
}


@pytest.mark.parametrize("per_entity", [False, True],
                         ids=["fixed", "per_entity"])
@pytest.mark.parametrize("variance", sorted(VARIANCE))
def test_a_variance_program_lowers_with_every_step_named(variance,
                                                         per_entity):
    """PR 40: SIMPLE and FULL, the fixed effect's program
    (``GlmOptimizationProblem._variance_fns``) and the per-entity one's
    body under ``vmap`` (``RandomEffectCoordinate._variance_fn`` calls the
    same ``coefficient_variances``): every operation of the curvature, the
    factorisation and the inverse sits under ``optim/variance/<step>``, the
    aggregators keeping their names nested in ``hessian``."""
    from photon_tpu.function.objective import Hyper
    from photon_tpu.optim.problem import coefficient_variances
    from photon_tpu.types import VarianceComputationType

    vtype = VarianceComputationType[variance]
    steps, aggs = VARIANCE[variance]
    batch, d = _batch(sparse=per_entity)
    if per_entity:
        objective = GlmOptimizationProblem(
            TaskType.LOGISTIC_REGRESSION).objective
        one = lambda i, v, y, o, w, c: coefficient_variances(
            objective, c, DataBatch(F.SparseFeatures(i, v), y, o, w),
            Hyper(l2_weight=1.0), vtype)
        lanes = lambda a: jnp.stack([a, a, a])
        text = jax.jit(jax.vmap(one)).lower(
            lanes(batch.features.indices), lanes(batch.features.values),
            lanes(batch.labels), lanes(batch.offsets), lanes(batch.weights),
            jnp.zeros((3, d))).as_text(debug_info=True)
    else:
        simple, full = GlmOptimizationProblem(
            TaskType.LOGISTIC_REGRESSION)._variance_fns
        text = (full if variance == "FULL" else simple).lower(
            jnp.zeros(d), batch, jnp.asarray(1.0)).as_text(debug_info=True)
    found = scopes_in(text)
    assert steps | aggs <= found, sorted((steps | aggs) - found)
    assert not found & (VARIANCE["FULL"][0] - steps)
    # nothing of a variance program runs outside the variance's scope
    named = set(re.findall(r'loc\("(jit\([^"]+)"', text))
    outside = sorted(n for n in named if "optim/variance/" not in n
                     and "/" in n.split(")", 1)[-1].strip("/"))
    assert not outside, outside


def _skewed_frame():
    """Users with 4, 12, 40 and 130 rows: four size buckets."""
    from photon_tpu.game.dataset import FeatureShard, GameDataFrame

    rng = np.random.default_rng(11)
    users = np.repeat(np.arange(8), [4, 4, 12, 12, 40, 40, 130, 130])
    n = len(users)
    return GameDataFrame(
        num_samples=n, response=(rng.random(n) < 0.5).astype(np.float64),
        feature_shards={"global": FeatureShard(rng.normal(size=(n, 8)), 8),
                        "user_feats": FeatureShard(rng.normal(size=(n, 4)),
                                                   4)},
        id_tags={"userId": [f"u{u}" for u in users]})


@pytest.fixture(scope="module")
def glmix_fit():
    """A GLMix estimator fitted twice on one frame, telemetry off, with the
    phases and counters each fit added."""
    from photon_tpu import obs
    from photon_tpu.obs.metrics import registry
    from photon_tpu.utils import timing
    from tests.test_game import glmix_estimator

    obs.reset()
    frame = _skewed_frame()
    est = glmix_estimator(num_iterations=1)

    def counters():
        return {k: v for k, v in registry.snapshot()["counters"].items()
                if k.startswith("ingest.h2d_bytes")}

    before = counters()
    timing.clear_timings()
    est.fit(frame)
    first = timing.timing_records()
    placed = {k: v - before.get(k, 0.0) for k, v in counters().items()}
    timing.clear_timings()
    after_first = counters()
    est.fit(frame)
    second = timing.timing_records()
    return est, first, second, placed, after_first == counters()


@pytest.mark.parametrize("program", ["ladder", "swept_ladder", "block"])
def test_the_ladder_program_names_every_bucket(glmix_fit, program):
    """Every per-entity solve program is the one bucket body
    (``RandomEffectCoordinate._make_bucket_solver``) under a wrapper: the
    scalar ladder and the lane ladder put each bucket under its
    ``re/b<index>``; the blocked program serves every bucket of its flavour
    with one executable, so it carries the body's names and no bucket's."""
    est = glmix_fit[0]
    coord = est._coordinates["per-user"]
    ds = coord.dataset
    assert len(ds.blocks) == 4
    E, K = ds.num_entities, ds.projected_dim
    one, residual = jnp.asarray(1.0), jnp.zeros(coord.n)
    if program == "ladder":
        lowered = coord._solve_fn.lower(
            ds, residual, jnp.zeros((E, K), jnp.float64), one, one)
    elif program == "swept_ladder":
        lowered = coord._solve_swept_fn.lower(
            ds, residual, jnp.zeros((2, E, K), jnp.float64),
            jnp.ones(2), jnp.ones(2))
    else:
        blk, dense = ds.blocks[1], coord._dense_local_blocks[1]
        lowered = coord._block_solve_swept_fn(dense).lower(
            blk, residual, jnp.zeros((1, blk.num_rows, K), jnp.float64),
            jnp.ones(1), jnp.ones(1))
    found = scopes_in(lowered.as_text(debug_info=True))
    buckets = {s for s in found if re.fullmatch(r"re/b\d+", s)}
    if program == "block":
        assert not buckets, sorted(buckets)
    else:
        assert buckets == {f"re/b{i}" for i in range(len(ds.blocks))}
    assert {"re/gather", "re/scatter", "agg/value_and_gradient",
            "optim/lbfgs/linesearch"} <= found


def test_a_ladder_solved_by_lbfgs_names_its_steps_under_its_buckets(glmix_fit):
    """The solver the configuration names is the solver that runs: every
    ``optim/`` operation of an L-BFGS ladder is an L-BFGS or line-search
    step, stands under its bucket's ``re/b<index>``, and no other solver's
    step is in the program (``glmix-ml20m-lbfgs.refit``'s scope table by
    bucket, and its check that no NEWTON ran, read these names)."""
    coord = glmix_fit[0]._coordinates["per-user"]
    assert coord.config.optimizer.optimizer_type == OptimizerType.LBFGS
    ds = coord.dataset
    coef0 = jnp.zeros((ds.num_entities, ds.projected_dim), jnp.float64)
    one = jnp.asarray(1.0)
    text = coord._solve_fn.lower(
        ds, jnp.zeros(coord.n), coef0, one, one).as_text(debug_info=True)
    steps = SOLVERS["LBFGS"][2]
    assert steps <= scopes_in(text), sorted(steps - scopes_in(text))
    others = {s for name, spec in SOLVERS.items() if name != "LBFGS"
              for s in spec[2]} - steps
    assert not scopes_in(text) & others, sorted(scopes_in(text) & others)
    named = [loc for loc in re.findall(r'loc\("(jit\([^"]+)"', text)
             if "optim/" in loc]
    assert named
    for loc in named:
        bucket = re.search(r"re/b(\d+)/", loc)
        assert bucket and loc.index("optim/") > bucket.start(), loc
    buckets = {int(re.search(r"re/b(\d+)/", loc).group(1)) for loc in named}
    assert buckets == set(range(len(ds.blocks)))


def test_the_score_programs_are_named(glmix_fit):
    est = glmix_fit[0]
    fe, re_ = est._coordinates["fixed"], est._coordinates["per-user"]
    from photon_tpu.game.coordinate import _fixed_score

    text = _fixed_score.lower(
        fe.batch.features, jnp.zeros(fe.dim)).as_text(debug_info=True)
    assert "fe/score" in scopes_in(text)
    ds = re_.dataset
    text = re_._score_fn.lower(
        ds, jnp.zeros((ds.num_entities, ds.projected_dim))).as_text(
            debug_info=True)
    assert "re/score" in scopes_in(text)


def test_a_meshed_score_is_made_whole_under_its_scope(devices8):
    """Over a mesh each coordinate's score program ends in the cross-chip
    step, the flat score made whole on every device for the next residual:
    every all-gather of the fixed and the random effect's score programs
    stands under ``cd/whole_score`` (``parallel/mesh.made_whole``), where
    ``collective_device_share``'s split by scope finds it."""
    from photon_tpu.game.coordinate import _fixed_score_whole
    from photon_tpu.parallel import mesh as M
    from tests.test_game import glmix_estimator, make_glmix_frame

    frame, _, _ = make_glmix_frame(np.random.default_rng(4), n=300,
                                   n_users=6)
    est = glmix_estimator(num_iterations=1)
    est.mesh = M.create_mesh(4)
    est.fit(frame)
    fe, re_ = est._coordinates["fixed"], est._coordinates["per-user"]
    ds = re_.dataset
    texts = [
        _fixed_score_whole.lower(fe.batch.features, jnp.zeros(fe.dim),
                                 fe._n_orig, fe.mesh).compile().as_text(),
        re_._score_fn.lower(ds, jnp.zeros(
            (ds.num_entities, ds.projected_dim))).compile().as_text()]
    for text in texts:
        gathers = [line for line in text.splitlines()
                   if " all-gather(" in line]
        assert gathers
        for line in gathers:
            assert "/cd/whole_score/" in line, line


def test_preparation_records_its_phases_once_with_telemetry_off(glmix_fit):
    _, first, second, _, _ = glmix_fit
    labels = [label for label, _ in first]
    for cid in ("fixed", "per-user"):
        assert f"ingest/h2d/{cid}" in labels
        assert f"ingest/prepare/{cid}/coordinate" in labels
    for step in ("group", "bucket", "pad", "passive"):
        assert labels.count(f"ingest/prepare/per-user/{step}") >= 1
    assert labels.count("ingest/prepare/per-user/group") == 1
    assert "ingest/stats" in labels
    assert all(seconds >= 0 for _, seconds in first)
    # _prepare_cached holds the frame: a second fit prepares nothing
    assert [l for l, _ in second if l.startswith("ingest/")] == []


def test_the_placed_bytes_are_counted_once(glmix_fit):
    est, _, _, placed, unchanged_by_second_fit = glmix_fit
    fe = est._coordinates["fixed"]
    want = {
        'ingest.h2d_bytes{coordinate="fixed"}': sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(fe.batch)),
        'ingest.h2d_bytes{coordinate="per-user"}': sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(
                est._re_datasets["per-user"])),
    }
    assert placed == want
    assert unchanged_by_second_fit


def test_a_spans_attributes_reach_its_profiler_annotation(tmp_path):
    from jax.profiler import ProfileData

    from photon_tpu import obs

    obs.reset()
    obs.configure(enabled=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with obs.span("cd/update", coordinate="x", iteration=3):
            with obs.annotate("re/args", coordinate="x"):
                pass
    finally:
        jax.profiler.stop_trace()
        obs.reset()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    events = {ev.name: dict(ev.stats)
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name in ("cd/update", "re/args")}
    # the name stays what idle gaps are labelled by; the attributes ride
    assert events == {"cd/update": {"coordinate": "x", "iteration": 3},
                      "re/args": {"coordinate": "x"}}


def test_telemetry_off_annotations_are_the_shared_no_op():
    from photon_tpu import obs
    from photon_tpu.obs import spans

    obs.reset()
    os.environ.pop("PHOTON_TPU_TELEMETRY", None)
    assert obs.annotate("re/args", coordinate="x") is spans._NULL_CONTEXT


def test_an_outermost_library_span_samples_no_memory():
    """``cd/sweep`` is outermost when a library user (the benchmark's traced
    window) fits with telemetry on; only a driver's root is a phase
    boundary."""
    from photon_tpu import obs

    obs.reset()
    obs.configure(enabled=True)
    try:
        with obs.span("cd/sweep", iteration=0):
            pass
        assert obs.memory.watermarks() == {}
        obs.memory.record_phase("train")
        assert set(obs.memory.watermarks()) == {"train"}
    finally:
        obs.reset()


def test_solver_telemetry_is_bounded_by_the_fit_not_the_process():
    from photon_tpu import obs
    from photon_tpu.obs import solver
    from tests.test_game import glmix_estimator, make_glmix_frame

    obs.reset()
    obs.configure(enabled=True)
    try:
        frame, _, _ = make_glmix_frame(np.random.default_rng(5), n=300,
                                       n_users=6)
        est = glmix_estimator(num_iterations=2)
        est.fit(frame)
        # the random effect's tracker, a sweep (the fixed effect has one
        # only where the configuration tracks states)
        assert solver.pending() == 2
        est.fit(frame)
        est.fit(frame)
        assert solver.pending() == 2
        drained = obs.drain_solver_telemetry()["random_effects"]
        assert [(d["coordinate"], d["sweep"]) for d in drained] == [
            ("per-user", 0), ("per-user", 1)]
        assert solver.pending() == 0
    finally:
        obs.reset()


def test_the_timed_registry_keeps_only_the_newest_records():
    import logging

    from photon_tpu.utils import timing

    timing.clear_timings()
    for i in range(timing._MAX_TIMINGS + 10):
        with timing.Timed(f"p{i}", level=logging.DEBUG):
            pass
    records = timing.timing_records()
    assert len(records) == timing._MAX_TIMINGS
    assert records[0][0] == "p10" and records[-1][0].endswith(
        str(timing._MAX_TIMINGS + 9))
    timing.clear_timings()


def test_perf_md_lists_every_scope_and_phase_the_tests_hold():
    """PERF.md §3 is where a later session looks the names up."""
    with open(os.path.join(REPO, "PERF.md")) as f:
        text = f.read()
    # `optim/lbfgs/{init,loop}` lists `optim/lbfgs/init` and `optim/lbfgs/loop`
    for stem, leaves in re.findall(r"([a-z_/]+)/\{([a-z_,]+)\}", text):
        text += " " + " ".join(f"{stem}/{leaf}" for leaf in leaves.split(","))
    names = {"fe/score", "re/score", "re/gather", "re/scatter", "re/b<index>",
             "serve/gather", "serve/score", "ingest/prepare/", "ingest/h2d/",
             "ingest/stats", "ingest.h2d_bytes", "fe/args", "re/args",
             "fe/outcome", "re/outcome", "cd/score", "cd/commit",
             "cd/record", "fe/solve_swept", "fe/score_lanes",
             # the compile account (PR 36): its spans, counters and readers
             "compile/<stage>", "compile.seconds", "compile.programs",
             "compile.cache", "account_compiles", "current_phase",
             "retrace_s", "lower_s", "cache_load_s", "repeat_fit_traces",
             "start_s",
             # a normalised objective (PR 38): the statistics pass's phase,
             # the kernel's labels under a context, the two readers
             "ingest/feature_stats/", "dense_norm", "dense_hv_norm",
             "feature_stats_s", "standardized_value_gradient_roofline",
             # the padded fill (PR 39): what the ``pad`` phase read; the
             # pair map's route and what the ``passive`` phase read (PR 42)
             "ingest.pad_nonzeros", "ingest.pair_route",
             "ingest.passive_nonzeros",
             # the variances (PR 40): the host spans, the counters, the
             # three readers
             "fe/variance", "re/variance", "variance.computed",
             "kernels.variance_gram", "variance_device_share",
             "variance_roofline", "variance_factor_ms",
             # the mesh: the cross-chip step's scope, the
             # counters, the two readers
             "cd/whole_score", "mesh.staged_bytes", "mesh.entity_slots",
             "collective_device_share", "mesh_padding_share",
             } | LINESEARCH
    for _, _, steps, dense, sparse in SOLVERS.values():
        names |= steps | dense | (sparse or set())
    for steps, aggs in VARIANCE.values():
        names |= steps | aggs
    missing = sorted(n for n in names if n not in text)
    assert not missing, missing


def test_perf_md_lists_the_lane_counts_and_their_readers():
    """What counts (``obs/solver.py``, ``optim/tracking.py``) and what reads
    it (``benchmark/layer_metrics/``) are looked up in PERF.md §3 too."""
    with open(os.path.join(REPO, "PERF.md")) as f:
        text = f.read()
    for name in ("obs.solver.lane_counts", "`sum`", "`capacity`", "`trips`",
                 "re_lane_occupancy", "re_solver_trips",
                 "sweep_lane_occupancy", "last_lane_result"):
        assert name in text, name
