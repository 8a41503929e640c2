#!/usr/bin/env python3
"""chip_smoke.py — does today's tree train, save and serve a GLMix model on
the TPU it is given?

One process (the chip belongs to it), the entry points a user would call,
the full width of the supported GLMix headline shape (100,000 rows, 256
global features, 1,000 users of 4 features each), random weights from a
seed. It

  1. trains through ``GameEstimator.fit`` (2 coordinate-descent sweeps,
     then a second fit warm-started from the first) and checks the model
     against a float64 one-hot oracle of the same objective;
  2. saves with ``persistable_artifacts`` + ``save_game_model``, serves
     through ``ServingEngine.from_model_dir`` and checks every score
     against a numpy oracle off the saved arrays;
  3. runs ``photon_tpu.cli.train.main`` in-process on a few thousand of
     the same rows written as Avro, and serves its output;
  4. compiles each Pallas kernel with ``interpret=False`` and compares it
     with the XLA path;
  5. stores dense fixed-effect matrices as ``GameEstimator._prepare`` does
     (``store_rows_major``: rows-major where the chip's own layout is not),
     checks L-BFGS, TRON and four-lane swept fits on them against the same
     fits on a plain ``jnp.asarray``, and fits, validates and transforms
     one estimator on a ragged-width frame;
  6. when the host has four devices, repeats one sweep on a (2, 2) mesh,
     checks that all four took part and agree with one device, and runs
     the ragged-width job on the mesh.

Any failed check raises: no phase is wrapped in try/except, and the exit
code is non-zero. ``main()`` refuses to run unless ``jax.devices()`` is a
TPU; the phases themselves are plain functions of ``Sizes`` so tier-1 can
rehearse them small on CPU (tests/test_bring_up.py).

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import tempfile
import time
from typing import List, Tuple

import numpy as np

L2 = 1.0
CD_SWEEPS = 2
SOLVER_TOLERANCE = 1e-5      # the reference's TRON tolerance (TRON.scala:256)
EPS32 = float(np.finfo(np.float32).eps)

# validation AUC must be within this of the oracle's (ISSUE 21, item 1)
AUC_TOLERANCE = 2e-3


def stopping_radius(rows: int) -> float:
    """How far apart two converged NEWTON solves of one L2-regularised
    logistic problem over ``rows`` samples may stop. A solve ends when
    the objective's relative change falls under SOLVER_TOLERANCE (and
    could not resolve it below eps32 anyway: a step is accepted only if
    the float32 objective does not rise), so it is within
    tol * f of the minimum in objective, and since
    f(theta + e) - f* >= (L2 / 2) |e|^2, within
    |e| <= sqrt(2 * tol * f / L2) in coefficients; f never exceeds its
    value at theta = 0, rows * log 2. Two device layouts sum in
    different orders and may stop an iteration apart inside that ball.
    The bound is the per-user coordinate's (its curvature is little more
    than L2, its rows those of the busiest user); the fixed effect's
    curvature is n * p(1-p) / d, some 60 times L2, and its ball
    correspondingly smaller — it is held to the same radius, which a
    dropped or double-counted shard (an O(0.1) shift and more) exceeds.
    Measured gaps: README "Precision"."""
    tol = max(SOLVER_TOLERANCE, EPS32)
    return float(np.sqrt(2.0 * tol * rows * np.log(2.0) / L2))


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every size the smoke runs at. The defaults are the full width of
    the supported GLMix headline shape."""

    n_train: int = 100_000
    n_val: int = 20_000
    d_global: int = 256
    n_users: int = 1_000
    d_user: int = 4
    n_requests: int = 300        # over known users
    n_unknown: int = 8           # requests for users the model never saw
    n_cli: int = 5_000           # rows written as Avro for cli.train
    kernel_rows: int = 8_192
    kernel_dense_dims: Tuple[int, ...] = (256, 1024)
    # epsilon's width: no multiple of 128, and rows that are no multiple
    # of the 256-row tile the kernel picks there (<= kernel_rows)
    kernel_ragged_shape: Tuple[int, int] = (8_100, 2_000)
    # the Hessian-vector product's further shapes: a small X that XLA places
    # in VMEM (PR 32's wrong answer showed only at such shapes, only on the
    # chip) and fe-epsilon-tron's own
    kernel_product_shapes: Tuple[Tuple[int, int], ...] = (
        (8_016, 2_000), (530_000, 2_000))
    kernel_sparse_dim: int = 4_096
    kernel_ell_width: int = 16
    kernel_serving_rows: int = 128
    # dense fixed effects whose placement the chip re-lays rows-major (a
    # small X and fe-epsilon's own), and one whose default is rows-major
    layout_relaid_shapes: Tuple[Tuple[int, int], ...] = (
        (8_100, 2_000), (530_000, 2_000))
    layout_plain_shape: Tuple[int, int] = (8_192, 128)


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    say(f"ok   {what}")


class CompileClock:
    """XLA's own account of compilation, read from jax.monitoring: seconds
    spent in backend compiles (a persistent-cache hit costs only its
    retrieval) and how many of them the cache served — so the cold fit's
    seconds can be read apart from the compiler's."""

    def __enter__(self):
        import jax.monitoring as monitoring

        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as monitoring

        monitoring.unregister_event_duration_listener(self._duration)
        monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def lap(self, what: str) -> None:
        say(f"{what}: {self.seconds:.2f}s in {self.programs} backend "
            f"compiles so far, {self.cache_hits} served by the persistent "
            f"cache")


def _g_name(j: int) -> str:
    return f"g{j:04d}"


def _u_name(j: int) -> str:
    return f"u{j:04d}"


# --------------------------------------------------------------------------
# data, seeded: a dense global block, one per-user block, logistic labels
# --------------------------------------------------------------------------

def make_rows(sizes: Sizes, n: int, seed: int):
    rng = np.random.default_rng(99)
    w_g = rng.normal(size=sizes.d_global)
    w_u = rng.normal(size=(sizes.n_users, sizes.d_user)) * 1.5
    r = np.random.default_rng(seed)
    xg = (r.normal(size=(n, sizes.d_global)).astype(np.float32)
          / np.sqrt(sizes.d_global))
    users = r.integers(0, sizes.n_users, size=n)
    xu = r.normal(size=(n, sizes.d_user)).astype(np.float32)
    logits = xg @ w_g + np.einsum("nk,nk->n", xu, w_u[users])
    y = (r.random(n) < 1.0 / (1.0 + np.exp(-logits))).astype(np.float64)
    return xg, xu, users, y


def glmix_frame(xg, xu, users, y):
    from photon_tpu.game.dataset import CsrRows, FeatureShard, GameDataFrame

    return GameDataFrame(
        num_samples=len(y), response=y,
        feature_shards={
            "global": FeatureShard(xg, xg.shape[1]),
            "per_user": FeatureShard(CsrRows.from_dense(xu), xu.shape[1])},
        id_tags={"userId": [str(u) for u in users]})


def auc(y, s) -> float:
    from scipy.stats import rankdata

    ranks = rankdata(s)
    pos = y > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2)
                 / (n_pos * n_neg))


def oracle_auc(sizes: Sizes, train, val) -> float:
    """float64 host oracle of the SAME objective: sum of logistic losses
    + (L2/2)|theta|^2 over [global | user one-hot x user features], no
    intercept — sklearn's C = 1/L2 (the formulation GLMix replaces)."""
    import scipy.sparse as sp
    from sklearn.linear_model import LogisticRegression

    def design(xg, xu, users):
        n, d = xu.shape
        cols = (users[:, None] * d + np.arange(d)[None, :]).ravel()
        onehot = sp.csr_matrix(
            (xu.ravel().astype(np.float64),
             (np.repeat(np.arange(n), d), cols)),
            shape=(n, sizes.n_users * d))
        return sp.hstack([sp.csr_matrix(xg.astype(np.float64)), onehot],
                         format="csr")

    xg, xu, users, y = train
    clf = LogisticRegression(C=1.0 / L2, fit_intercept=False,
                             solver="lbfgs", max_iter=200, tol=1e-7)
    clf.fit(design(xg, xu, users), y)
    xg_v, xu_v, users_v, y_v = val
    return auc(y_v, clf.decision_function(design(xg_v, xu_v, users_v)))


# --------------------------------------------------------------------------
# phase 1: train
# --------------------------------------------------------------------------

def build_estimator(sweeps: int = CD_SWEEPS, mesh=None):
    import jax.numpy as jnp

    from photon_tpu.estimators.game_estimator import (
        CoordinateConfiguration,
        FixedEffectDataConfiguration,
        GameEstimator,
    )
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.game.random_effect import RandomEffectDataConfiguration
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        OptimizerConfig,
    )
    from photon_tpu.types import OptimizerType, TaskType

    opt = GLMOptimizationConfiguration(
        optimizer=OptimizerConfig(optimizer_type=OptimizerType.NEWTON,
                                  max_iterations=100,
                                  tolerance=SOLVER_TOLERANCE),
        regularization=L2Regularization, regularization_weight=L2)
    return GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {"fixed": CoordinateConfiguration(
            FixedEffectDataConfiguration("global"), opt),
         "per_user": CoordinateConfiguration(
             RandomEffectDataConfiguration("userId", "per_user"), opt)},
        update_sequence=["fixed", "per_user"],
        num_iterations=sweeps, dtype=jnp.float32, mesh=mesh)


def _coefficients(model):
    return (model["fixed"].model.coefficients.means,
            model["per_user"].coefficients)


def _fit_seconds(est, df, **kw):
    import jax

    t0 = time.perf_counter()
    results = est.fit(df, **kw)
    jax.block_until_ready(_coefficients(results[-1].model))
    return results, time.perf_counter() - t0


def _check_solves(est, which: str) -> None:
    """The last sweep's solver outcomes. NEWTON's Cholesky guard (a
    non-finite step falls back to steepest descent) leaves no record of
    its own; a solve that needed it crawls to max_iterations instead of
    converging in a handful of steps."""
    from photon_tpu.optim.base import ConvergenceReason

    coords = est._coordinates
    check(coords["per_user"].last_failed_entities == 0,
          f"{which}: zero failed entities in the random-effect coordinate")
    fe = coords["fixed"].last_result
    re_track = coords["per_user"].last_tracker
    re_iters = np.asarray(re_track.iterations)
    say(f"{which}, last sweep: NEWTON fixed effect {int(fe.iterations)} "
        f"iterations, {ConvergenceReason(int(fe.reason)).name}; per-user "
        f"max {int(re_iters.max())} iterations, {re_track.reason_counts()}")
    check(int(fe.failure) == 0 and int(fe.iterations) < 100,
          f"{which}: fixed-effect NEWTON converged, no non-finite guard "
          f"trip")
    check(int(re_iters.max()) < 100,
          f"{which}: no per-user NEWTON solve ran into max_iterations")


def train_phase(sizes: Sizes) -> dict:
    from photon_tpu.estimators.game_estimator import GameTransformer
    from photon_tpu.parallel import memory as hbm
    from photon_tpu.resilience import failures

    train = make_rows(sizes, sizes.n_train, 0)
    val = make_rows(sizes, sizes.n_val, 1)
    t0 = time.perf_counter()
    want_auc = oracle_auc(sizes, train, val)
    say(f"oracle (float64 one-hot, host): validation AUC {want_auc:.5f} "
        f"in {time.perf_counter() - t0:.1f}s")

    df, dfv = glmix_frame(*train), glmix_frame(*val)
    failures.clear()
    est = build_estimator()
    results, cold_s = _fit_seconds(est, df)
    say(f"train cold (ingest + compile + {CD_SWEEPS} sweeps): {cold_s:.2f}s")
    _check_solves(est, "cold fit")
    # the warm-started second fit is what reaches the donated-buffer paths
    results, warm_s = _fit_seconds(est, df, initial_model=results[-1].model)
    say(f"train warm-started second fit ({CD_SWEEPS} sweeps): {warm_s:.2f}s")
    model = results[-1].model

    fixed, per_user = (np.asarray(a) for a in _coefficients(model))
    check(fixed.shape == (sizes.d_global,),
          f"fixed-effect coefficients have shape ({sizes.d_global},)")
    check(per_user.shape[1] == sizes.d_user,
          f"per-user coefficients have width {sizes.d_user}")
    check(np.isfinite(fixed).all() and np.isfinite(per_user).all(),
          "every coefficient is finite")

    recorded = failures.snapshot()
    check(not recorded, f"zero recorded failures (got {recorded[:3]})")
    _check_solves(est, "warm-started fit")

    budget, source = hbm.default_hbm_budget_bytes()
    say(f"random-effect planner budget: {budget} bytes, source {source!r}")

    scores = np.asarray(GameTransformer(model, est).transform(dfv))
    got_auc = auc(val[3], scores)
    say(f"validation AUC {got_auc:.5f} vs oracle {want_auc:.5f} "
        f"(gap {got_auc - want_auc:+.5f})")
    check(np.isfinite(scores).all() and scores.shape == (sizes.n_val,),
          f"{sizes.n_val} finite validation scores")
    check(abs(got_auc - want_auc) <= AUC_TOLERANCE,
          f"validation AUC within {AUC_TOLERANCE} of the oracle")
    return {"est": est, "model": model, "result": results[-1], "df": df,
            "train": train, "cold_s": cold_s, "warm_s": warm_s,
            "auc": got_auc, "oracle_auc": want_auc,
            "budget_source": source}


# --------------------------------------------------------------------------
# phase 2: save -> serve
# --------------------------------------------------------------------------

def index_maps(sizes: Sizes) -> dict:
    from photon_tpu.io.index_map import IndexMap, feature_key

    return {
        "global": IndexMap({feature_key(_g_name(j)): j
                            for j in range(sizes.d_global)}),
        "per_user": IndexMap({feature_key(_u_name(j)): j
                              for j in range(sizes.d_user)}),
    }


def save_model(est, result, imaps, out_dir: str) -> None:
    """The same calls cli/train.save_models makes."""
    from photon_tpu.estimators.game_estimator import persistable_artifacts
    from photon_tpu.io.model_io import save_game_model

    model, projections = persistable_artifacts(est, result.model)
    save_game_model(out_dir, model, imaps, vocab=est._vocab,
                    projections=projections,
                    coordinate_configs=result.config)


def make_requests(sizes: Sizes, seed: int = 7):
    """(requests, is_unknown): dense rows over every feature of both
    shards, known users first, then users the model never saw."""
    from photon_tpu.serving import ScoreRequest

    r = np.random.default_rng(seed)
    n = sizes.n_requests + sizes.n_unknown
    xg = r.normal(size=(n, sizes.d_global)) / np.sqrt(sizes.d_global)
    xu = r.normal(size=(n, sizes.d_user))
    users = [str(u) for u in r.integers(0, sizes.n_users,
                                        size=sizes.n_requests)]
    users += [f"never-seen-{i}" for i in range(sizes.n_unknown)]
    reqs = [ScoreRequest(
        f"r{i}",
        {"global": [(_g_name(j), "", float(xg[i, j]))
                    for j in range(sizes.d_global)],
         "per_user": [(_u_name(j), "", float(xu[i, j]))
                      for j in range(sizes.d_user)]},
        {"userId": users[i]}, float(r.normal() * 0.1))
        for i in range(n)]
    return reqs, [i >= sizes.n_requests for i in range(n)]


def oracle_scores(model_dir: str, requests) -> Tuple[np.ndarray, np.ndarray]:
    """(score, bound) per request from the SAVED arrays, in float64:
    offset + theta . x + sum_k coef[row, k] * x[proj[row, k]]. ``bound``
    is the float32 forward-error bound of that sum in any order,
    (terms + 2) * eps32 * sum |term| — the +2 covers rounding each
    float64 request value and the offset to float32. The served score is
    a float32 gather-multiply-sum of the same terms (no matmul unit, so
    no reduced-precision pass), and must sit inside it."""
    from photon_tpu.io.model_io import load_for_serving

    m = load_for_serving(model_dir)
    scores, bounds = [], []
    for req in requests:
        terms = [req.offset]
        dense = {}
        for sid, feats in req.features.items():
            x = np.zeros(m.index_maps[sid].feature_dimension)
            for name, term, v in feats:
                col = m.index_maps[sid].index_of(name, term)
                if col >= 0:
                    x[col] = v
            dense[sid] = x
        for f in m.fixed:
            terms.extend(f.coefficients.astype(np.float64)
                         * dense[f.feature_shard_id])
        for re in m.random:
            row = re.entity_rows.get(req.entity_ids[re.random_effect_type])
            if row is None:
                continue
            proj = re.projection[row]
            live = proj >= 0
            terms.extend(re.coefficients[row][live].astype(np.float64)
                         * dense[re.feature_shard_id][proj[live]])
        terms = np.asarray(terms, np.float64)
        scores.append(terms.sum())
        bounds.append((len(terms) + 2) * EPS32 * np.abs(terms).sum())
    return np.asarray(scores), np.asarray(bounds)


def serve_and_check(model_dir: str, requests, unknown: List[bool]) -> dict:
    from photon_tpu.serving import FallbackReason, ServingEngine
    from photon_tpu.utils import compile_cache

    engine = ServingEngine.from_model_dir(model_dir)    # cli/serve.py:204
    info = engine.warmup()
    say(f"serving: warmed {info['programs']} programs over buckets "
        f"{info['buckets']} in {info['seconds']:.2f}s")
    steady0 = compile_cache.compile_counts()["steady_state"]
    t0 = time.perf_counter()
    resps = engine.serve(requests)
    serve_s = time.perf_counter() - t0
    steady1 = compile_cache.compile_counts()["steady_state"]
    engine.shutdown()

    check(len(resps) == len(requests),
          f"{len(requests)} requests answered in {serve_s:.3f}s")
    known = [r for r, u in zip(resps, unknown) if not u]
    cold = [r for r, u in zip(resps, unknown) if u]
    check(all(not r.degraded and not r.fallbacks for r in known),
          f"all {len(known)} known-entity responses undegraded, "
          f"no fallbacks")
    check(all(r.degraded and FallbackReason.UNKNOWN_ENTITY
              in {f.reason for f in r.fallbacks} for r in cold),
          f"all {len(cold)} unknown-entity responses carry the typed "
          f"UNKNOWN_ENTITY")
    got = np.asarray([r.score for r in resps], np.float64)
    want, bound = oracle_scores(model_dir, requests)
    gap = np.abs(got - want)
    say(f"serving: max |score - float64 oracle| = {gap.max():.3e}, "
        f"largest share of the float32 bound = {(gap / bound).max():.3f}")
    check(np.isfinite(got).all(), "every served score is finite")
    check((gap <= bound).all(),
          "every served score inside the float32 forward-error bound "
          "of its oracle")
    check(steady1 - steady0 == 0,
          "compile_counts()['steady_state'] did not move after warm-up")
    return {"max_score_gap": float(gap.max()), "serve_s": serve_s}


def serve_phase(sizes: Sizes, trained: dict, tmp: str) -> dict:
    model_dir = os.path.join(tmp, "model")
    save_model(trained["est"], trained["result"], index_maps(sizes),
               model_dir)
    requests, unknown = make_requests(sizes)
    return serve_and_check(model_dir, requests, unknown)


# --------------------------------------------------------------------------
# phase 3: the CLI, in-process (the chip belongs to this process)
# --------------------------------------------------------------------------

def _cli_schema() -> dict:
    """TrainingExampleAvro plus a second feature bag: GLMix rows carry
    the per-user features beside the global ones, and the entity id in
    ``metadataMap`` (io.data_io.write_training_examples writes neither,
    so the rows go through io.write_avro)."""
    import copy

    from photon_tpu.io.schemas import FEATURE_AVRO, TRAINING_EXAMPLE_AVRO

    schema = copy.deepcopy(TRAINING_EXAMPLE_AVRO)
    # an inline record (a named type is defined once per schema, and the
    # columnar native reader wants the record's shape, not a reference)
    schema["fields"].append({
        "name": "userFeatures",
        "type": {"type": "array",
                 "items": {**FEATURE_AVRO, "name": "UserFeatureAvro"}}})
    return schema


def cli_phase(sizes: Sizes, trained: dict, tmp: str) -> dict:
    from photon_tpu.cli import train as cli_train
    from photon_tpu.io import write_avro
    from photon_tpu.obs.metrics import registry

    xg, xu, users, y = (a[:sizes.n_cli] for a in trained["train"])
    data_dir = os.path.join(tmp, "cli-data")
    os.makedirs(data_dir)
    write_avro(os.path.join(data_dir, "part-00000.avro"), _cli_schema(), (
        {"uid": f"s{i}", "label": float(y[i]),
         "features": [{"name": _g_name(j), "term": "",
                       "value": float(xg[i, j])}
                      for j in range(sizes.d_global)],
         "metadataMap": {"userId": str(users[i])},
         "weight": None, "offset": None,
         "userFeatures": [{"name": _u_name(j), "term": "",
                           "value": float(xu[i, j])}
                          for j in range(sizes.d_user)]}
        for i in range(len(y))))
    out_dir = os.path.join(tmp, "cli-out")
    coord = (f"optimizer=NEWTON,tolerance={SOLVER_TOLERANCE},max.iter=100,"
             f"regularization=L2,reg.weights={L2}")
    t0 = time.perf_counter()
    cli_train.main([
        "--input-data-directories", data_dir,
        "--root-output-directory", out_dir,
        "--training-task", "LOGISTIC_REGRESSION",
        "--feature-shard-configuration",
        "name=global,feature.bags=features,intercept=false",
        "--feature-shard-configuration",
        "name=per_user,feature.bags=userFeatures,intercept=false",
        "--coordinate-configuration",
        f"name=fixed,feature.shard=global,{coord}",
        "--coordinate-configuration",
        "name=per_user,random.effect.type=userId,feature.shard=per_user,"
        + coord,
        "--coordinate-update-sequence", "fixed,per_user",
        "--coordinate-descent-iterations", str(CD_SWEEPS),
    ])
    say(f"cli.train.main on {len(y)} Avro rows: "
        f"{time.perf_counter() - t0:.2f}s")
    paths = {k.split('path="')[1].rstrip('"}'): int(v)
             for k, v in registry.snapshot()["counters"].items()
             if k.startswith("ingest.frames")}
    say(f"ingest ran: {paths} (native = C Avro decoder, python = pure "
        f"Python fallback)")
    check(sum(paths.values()) >= 1, "the CLI read its data through "
          "io.fast_ingest.read_frame_with_fallback")
    requests, unknown = make_requests(
        dataclasses.replace(sizes, n_requests=1, n_unknown=0), seed=11)
    # a user the CLI's rows contain, so the request is a known entity
    requests = [dataclasses.replace(
        requests[0], entity_ids={"userId": str(users[0])})]
    return serve_and_check(os.path.join(out_dir, "best"), requests, unknown)


# --------------------------------------------------------------------------
# phase 4: the Pallas kernels, compiled
# --------------------------------------------------------------------------

def kernel_phase(sizes: Sizes, interpret: bool) -> dict:
    """Each kernel at a realistic shape against the XLA path AND a
    float64 oracle. With ``interpret=False`` Mosaic compiles them; a
    kernel that cannot compile raises here."""
    import jax.numpy as jnp

    from photon_tpu.obs.metrics import registry
    from photon_tpu.ops import aggregators, pallas_glm
    from photon_tpu.ops.features import SparseFeatures
    from photon_tpu.ops.losses import LogisticLoss
    from photon_tpu.ops.normalization import no_normalization

    rng = np.random.default_rng(5)
    n = sizes.kernel_rows
    y = (rng.random(n) > 0.4).astype(np.float32)
    off = (rng.normal(size=n) * 0.2).astype(np.float32)
    w = (rng.random(n) + 0.1).astype(np.float32)
    out = {}

    def logistic_oracle(margins64, x_t_times):
        """float64 value and gradient from float64 margins (of the
        first ``len(margins64)`` rows)."""
        m = len(margins64)
        z = margins64 + off[:m]
        value = float(np.sum(w[:m] * (np.logaddexp(0.0, z) - y[:m] * z)))
        dz = w[:m] * (1.0 / (1.0 + np.exp(-z)) - y[:m])
        return value, x_t_times(dz)

    def compare(name, got, xla, want):
        """Kernel and XLA path each against the float64 oracle, relative
        to the oracle's largest entry. The XLA path's f32 matmuls run at
        the TPU's default (reduced) precision, so the kernel is held to
        the oracle no worse than twice the XLA path's own distance plus
        float32 round-off — not to the XLA path's digits."""
        scale = max(float(np.abs(want).max()), 1e-30)
        k_err = float(np.abs(np.asarray(got, np.float64) - want).max()) / scale
        x_err = float(np.abs(np.asarray(xla, np.float64) - want).max()) / scale
        say(f"kernel {name}: rel err vs float64 oracle — pallas "
            f"{k_err:.2e}, xla {x_err:.2e}")
        check(np.isfinite(np.asarray(got)).all(), f"{name}: finite")
        check(k_err <= 2.0 * x_err + 1e-4,
              f"{name}: pallas no further from the oracle than "
              f"2x the XLA path + 1e-4")
        out[name] = {"pallas_rel_err": k_err, "xla_rel_err": x_err}

    def dense_oracle(x, coef, v, ym, offm, wm, chunk=65_536):
        """float64 value, gradient, curvature weights, product
        ``X^T (d2 * Xv)`` and ``sum(w dz)`` at ``coef``, the rows a chunk at
        a time (530,000 x 2,000 in float64 is 8.5 GB whole)."""
        value, g, hv = 0.0, np.zeros(x.shape[1]), np.zeros(x.shape[1])
        dz_sum = 0.0
        d2 = np.empty(x.shape[0])
        coef64, v64 = coef.astype(np.float64), v.astype(np.float64)
        for s in range(0, x.shape[0], chunk):
            rows = slice(s, s + chunk)
            x64 = x[rows].astype(np.float64)
            z = x64 @ coef64 + offm[rows]
            p = 1.0 / (1.0 + np.exp(-z))
            value += float(np.sum(wm[rows] * (np.logaddexp(0.0, z)
                                              - ym[rows] * z)))
            g += x64.T @ (wm[rows] * (p - ym[rows]))
            dz_sum += float(np.sum(wm[rows] * (p - ym[rows])))
            d2[rows] = wm[rows] * p * (1.0 - p)
            hv += x64.T @ (d2[rows] * (x64 @ v64))
        return value, g, d2, hv, dz_sum

    # every dense shape takes the evaluation AND the Hessian-vector product
    # (the same kernel at another per-row function); the product's shapes
    # add the small ones where XLA places X in VMEM and the cell's own
    for m, d in ([(n, d) for d in sizes.kernel_dense_dims]
                 + [sizes.kernel_ragged_shape]
                 + list(sizes.kernel_product_shapes)):
        x = rng.standard_normal(size=(m, d), dtype=np.float32)
        x /= np.float32(np.sqrt(d))
        coef = (rng.normal(size=d) * 0.4).astype(np.float32)
        v = rng.normal(size=d).astype(np.float32)
        ym = (rng.random(m) > 0.4).astype(np.float32)
        offm = (rng.normal(size=m) * 0.2).astype(np.float32)
        wm = (rng.random(m) + 0.1).astype(np.float32)
        value, g, d2, hv, dz_sum = dense_oracle(x, coef, v, ym, offm, wm)
        xd, d2d, vd = jnp.asarray(x), jnp.asarray(d2, jnp.float32), jnp.asarray(v)
        args = (LogisticLoss, xd, jnp.asarray(ym), jnp.asarray(offm),
                jnp.asarray(wm), jnp.asarray(coef))
        v1, g1 = pallas_glm.fused_dense_value_grad(*args,
                                                   interpret=interpret)
        q1, hv1 = pallas_glm.fused_dense_hessian_vector(
            xd, d2d, vd, interpret=interpret)
        with pallas_glm.disabled():     # XLA's two passes, at any width
            v0, g0 = aggregators.value_and_gradient(*args,
                                                    no_normalization())
            hv0 = aggregators.hessian_vector_from_weights(
                xd, d2d, vd, no_normalization(), d)
        compare(f"fused_dense_value_grad[{m}x{d}] value",
                np.asarray([v1]), np.asarray([v0]), np.asarray([value]))
        compare(f"fused_dense_value_grad[{m}x{d}] grad", g1, g0, g)
        compare(f"fused_dense_hessian_vector[{m}x{d}] product", hv1, hv0, hv)
        compare(f"fused_dense_hessian_vector[{m}x{d}] quadratic form",
                np.asarray([q1]), np.asarray([0.5 * jnp.dot(vd, hv0)]),
                np.asarray([0.5 * float(v.astype(np.float64) @ hv)]))
        # the three-result program a normalised objective with shifts runs
        # (PR 38): the same value and gradient, and sum(w dz) beside them
        v2, g2, s2 = pallas_glm.fused_dense_value_grad(
            *args, interpret=interpret, with_dz_sum=True)
        _, dz0 = LogisticLoss.loss_and_dz(
            aggregators.compute_margins(xd, args[5], args[3],
                                        no_normalization()), args[2])
        compare(f"fused_dense_value_grad[{m}x{d}] grad beside sum(w dz)",
                g2, g0, g)
        compare(f"fused_dense_value_grad[{m}x{d}] sum(w dz)",
                np.asarray([s2]), np.asarray([jnp.sum(args[4] * dz0)]),
                np.asarray([dz_sum]))
        del x, xd, args

    d, k = sizes.kernel_sparse_dim, sizes.kernel_ell_width
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    coef = (rng.normal(size=d) * 0.4).astype(np.float32)
    feats = SparseFeatures(jnp.asarray(idx), jnp.asarray(val))
    args = (LogisticLoss, feats, jnp.asarray(y), jnp.asarray(off),
            jnp.asarray(w), jnp.asarray(coef))
    v1, g1 = pallas_glm.fused_sparse_value_grad(*args, interpret=interpret)
    v0, g0 = aggregators.value_and_gradient(*args, no_normalization())
    val64, coef64 = val.astype(np.float64), coef.astype(np.float64)

    def scatter(dz):
        g = np.zeros(d)
        np.add.at(g, idx.ravel(), (val64 * dz[:, None]).ravel())
        return g

    v, g = logistic_oracle((val64 * coef64[idx]).sum(axis=1), scatter)
    compare(f"fused_sparse_value_grad[{n}xELL{k}, d={d}] value",
            np.asarray([v1]), np.asarray([v0]), np.asarray([v]))
    compare(f"fused_sparse_value_grad[{n}xELL{k}, d={d}] grad", g1, g0, g)

    b = sizes.kernel_serving_rows
    m1 = pallas_glm.fused_gather_margin(
        jnp.asarray(idx[:b]), jnp.asarray(val[:b]), jnp.asarray(off[:b]),
        jnp.asarray(coef), interpret=interpret)
    m0 = jnp.asarray(off[:b]) + jnp.sum(
        jnp.asarray(val[:b]) * jnp.asarray(coef)[jnp.asarray(idx[:b])],
        axis=-1)
    compare(f"fused_gather_margin[{b}xK{k}, d={d}]", m1, m0,
            off[:b] + (val64[:b] * coef64[idx[:b]]).sum(axis=1))

    counters = registry.snapshot()["counters"]
    routed = {key: int(v) for key, v in counters.items()
              if key.startswith(("kernels.pallas_hits",
                                 "kernels.xla_fallbacks"))}
    say("kernels.pallas_hits / kernels.xla_fallbacks on the main path: "
        + (str(routed) if routed else "none (no dense fixed effect of an "
           "admitted width was solved above, and the serving kernel is "
           "opt-in)"))
    out["interpret"] = interpret
    return out


# --------------------------------------------------------------------------
# phase 5: where a dense fixed effect's matrix lies
# --------------------------------------------------------------------------

def _logistic_rows(rng, m: int, d: int):
    x = rng.standard_normal(size=(m, d), dtype=np.float32)
    x /= np.float32(np.sqrt(d))
    beta = (rng.normal(size=d) * 4.0).astype(np.float32)
    y = (rng.random(m) < 1.0 / (1.0 + np.exp(-(x @ beta)))).astype(
        np.float32)
    return x, y


def ragged_width_job(sizes: Sizes, mesh=None) -> str:
    """One estimator on a dense fixed effect of ragged width (the smallest
    relaid shape), with or without a mesh: ``fit`` with a validation frame,
    then ``GameTransformer.transform``. The training X is the estimator's
    to store (rows-major on one chip, the mesh's own on four); the
    validation and transform X are placed plainly, uncommitted, and must
    score against coefficients that lie wherever the solve left them.
    Returns the training X's ``ingest.row_major`` outcome."""
    import jax.numpy as jnp

    from photon_tpu.estimators.game_estimator import (
        CoordinateConfiguration,
        FixedEffectDataConfiguration,
        GameEstimator,
        GameTransformer,
    )
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.game.dataset import FeatureShard, GameDataFrame
    from photon_tpu.obs.metrics import registry
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        OptimizerConfig,
    )
    from photon_tpu.types import TaskType

    m, d = sizes.layout_relaid_shapes[0]
    tag = f"ragged-{m}x{d}" + ("" if mesh is None else "-mesh")
    rng = np.random.default_rng(29)
    x, y = _logistic_rows(rng, m + m // 4, d)
    frames = [GameDataFrame(num_samples=len(y[rows]), response=y[rows],
                            feature_shards={"g": FeatureShard(x[rows], d)})
              for rows in (slice(0, m), slice(m, None))]
    est = GameEstimator(
        TaskType.LOGISTIC_REGRESSION,
        {tag: CoordinateConfiguration(
            FixedEffectDataConfiguration("g"), GLMOptimizationConfiguration(
                optimizer=OptimizerConfig(max_iterations=100,
                                          tolerance=1e-6),
                regularization=L2Regularization, regularization_weight=L2))},
        update_sequence=[tag], num_iterations=1, dtype=jnp.float32,
        mesh=mesh, validation_evaluators=["AUC"])
    (result,) = est.fit(frames[0], validation_df=frames[1])
    (outcome,) = [key.split('outcome="')[1].split('"')[0]
                  for key in registry.snapshot()["counters"]
                  if key.startswith(f'ingest.row_major{{coordinate="{tag}"')]
    auc = result.evaluation["AUC"]
    scores = np.asarray(GameTransformer(result.model, est).transform(
        frames[1]))
    coef = np.asarray(result.model[tag].model.coefficients.means)
    want = x[m:] @ coef
    gap = float(np.abs(scores - want).max() / np.abs(want).max())
    say(f"{tag}: training X {outcome}, validation AUC {auc:.4f}, "
        f"transform within {gap:.2e} of the largest X @ theta on the host")
    check(0.6 < auc <= 1.0, f"{tag}: the validation frame was scored")
    # a dot at the chip's default precision is a bfloat16 pass
    check(gap <= 2e-2, f"{tag}: transform scores the plain X against the "
          f"fitted coefficients")
    return outcome


def layout_phase(sizes: Sizes) -> dict:
    """``store_rows_major`` (``GameEstimator._prepare``'s step after
    ``fixed_effect_batch``) stores a dense X rows-major wherever the
    device's own layout of the shape is not (on the chip: the relaid
    shapes; on a CPU: none), and an L-BFGS fit, a TRON fit and a
    four-lane swept fit on the placed matrix are the fits on a plain
    ``jnp.asarray`` of the same rows: counts exactly, coefficients to 2e-6
    of the largest. Then ``ragged_width_job`` on this one device. Returns
    each shape's ``ingest.row_major`` outcome, the job's under ``job``."""
    import jax.numpy as jnp

    from photon_tpu.data.dataset import DataBatch
    from photon_tpu.function.objective import L2Regularization
    from photon_tpu.game.dataset import (
        ROW_MAJOR,
        FeatureShard,
        GameDataFrame,
        store_rows_major,
    )
    from photon_tpu.obs.metrics import registry
    from photon_tpu.optim.problem import (
        GLMOptimizationConfiguration,
        GlmOptimizationProblem,
        OptimizerConfig,
    )
    from photon_tpu.types import OptimizerType, TaskType

    def problem(**optimizer):
        return GlmOptimizationProblem(
            TaskType.LOGISTIC_REGRESSION, GLMOptimizationConfiguration(
                optimizer=OptimizerConfig(**optimizer),
                regularization=L2Regularization, regularization_weight=L2))

    lbfgs = problem(max_iterations=100, tolerance=1e-6)
    tron = problem(optimizer_type=OptimizerType.TRON, max_iterations=15,
                   tolerance=1e-5)

    def fits(batch, d):
        """{fit: (counts, coefficients)} on the host, nothing kept on the
        device."""
        out = {}
        for name, prob in (("lbfgs", lbfgs), ("tron", tron)):
            model, res = prob.run(batch, dim=d, dtype=jnp.float32)
            counts = [int(res.iterations), int(res.num_fun_evals)]
            if res.cg_steps is not None:
                counts.append(int(res.cg_steps))
            out[name] = (counts, np.asarray(model.coefficients.means))
        swept = lbfgs.solve_swept(batch, [0.1, 1.0, 10.0, 100.0], dim=d,
                                  dtype=jnp.float32)
        out["swept"] = (
            np.asarray(swept.stacked.iterations).tolist()
            + np.asarray(swept.stacked.num_fun_evals).tolist(),
            np.asarray(swept.coefs))
        return out

    def outcomes(tag):
        return {key.split('outcome="')[1].split('"')[0]: int(v)
                for key, v in registry.snapshot()["counters"].items()
                if key.startswith(f'ingest.row_major{{coordinate="{tag}"')}

    rng = np.random.default_rng(17)
    out = {}
    for m, d in sizes.layout_relaid_shapes + (sizes.layout_plain_shape,):
        tag = f"layout-{m}x{d}"
        x, y = _logistic_rows(rng, m, d)
        df = GameDataFrame(num_samples=m, response=y,
                           feature_shards={"g": FeatureShard(x, d)})
        t0 = time.perf_counter()
        placed = df.fixed_effect_batch("g", coordinate=tag)
        check(not placed.features.committed,
              f"{tag}: the frame's own placement is uncommitted")
        placed = placed._replace(
            features=store_rows_major(placed.features, tag))
        placed.features.block_until_ready()
        seconds = time.perf_counter() - t0
        (outcome,) = outcomes(tag)
        layout = placed.features.format.layout
        say(f"{tag}: placed in {seconds:.2f} s, outcome {outcome}, "
            f"{layout}, committed {placed.features.committed}")
        check(layout is None or layout.major_to_minor == ROW_MAJOR,
              f"{tag}: the placed matrix lies rows-major")
        check(placed.features.committed == (outcome == "relaid"),
              f"{tag}: committed exactly where it was relaid")
        got = fits(placed, d)
        del placed
        want = fits(DataBatch(jnp.asarray(x), jnp.asarray(y)), d)
        for name, (counts, coef) in got.items():
            gap = float(np.abs(coef - want[name][1]).max()
                        / max(np.abs(want[name][1]).max(), 1e-30))
            say(f"{tag} {name}: counts {counts}, coefficients {gap:.2e} "
                f"of the largest from the default layout's")
            check(counts == want[name][0],
                  f"{tag} {name}: the default layout's counts "
                  f"{want[name][0]}")
            check(gap <= 2e-6, f"{tag} {name}: coefficients within 2e-6")
        out[tag] = outcome
        del x, df
    out["job"] = ragged_width_job(sizes)
    return out


# --------------------------------------------------------------------------
# phase 6: four devices
# --------------------------------------------------------------------------

def mesh_phase(sizes: Sizes, trained: dict) -> dict:
    """One sweep on a (data=2, model=2) mesh in this same process, then
    the ragged-width job on the mesh."""
    import jax
    import jax.numpy as jnp

    from photon_tpu.parallel import mesh as M

    devices = jax.devices()[:4]
    before = [d.memory_stats() for d in devices]
    mesh = M.create_mesh(4, (M.DATA_AXIS, M.MODEL_AXIS), (2, 2))
    est = build_estimator(sweeps=1, mesh=mesh)
    results, secs = _fit_seconds(est, trained["df"])
    say(f"mesh (2, 2): one sweep in {secs:.2f}s (with compile)")

    coord = est._coordinates["fixed"]
    theta0 = M.shard_coef_model_parallel(
        jnp.zeros((coord.dim,), jnp.float32), mesh,
        padded_dim=coord._dim_padded)
    from photon_tpu.ops import pallas_glm
    with pallas_glm.disabled():      # as ``problem.run`` traces a mesh solve
        hlo = coord.problem._solve_fn_for(False).lower(
            theta0, coord.batch, jnp.asarray(L2, jnp.float32),
            jnp.asarray(0.0, jnp.float32)).compile().as_text()
    check("all-reduce" in hlo, "the meshed fixed-effect solve's HLO has "
          "an all-reduce")

    def holders(a):
        return {s.device.id for s in a.addressable_shards}

    want = {d.id for d in devices}
    check(holders(coord.batch.features) == want,
          "the batch has addressable shards on all four devices")
    blocks = est._coordinates["per_user"].dataset.blocks
    check(all(holders(b.features.values) == want for b in blocks),
          f"all {len(blocks)} entity blocks have addressable shards on "
          f"all four devices")
    after = [d.memory_stats() for d in devices]
    mem_checked = all(s is not None for s in before + after)
    if mem_checked:
        grew = [a["bytes_in_use"] - b["bytes_in_use"]
                for a, b in zip(after, before)]
        say(f"bytes_in_use growth per device: {grew}")
        check(all(g > 0 for g in grew),
              "memory_stats()['bytes_in_use'] grew on every device")
    else:
        say("memory_stats() is not reported by this backend: per-device "
            "growth not checked")

    single, _ = _fit_seconds(build_estimator(sweeps=1), trained["df"])
    atol = stopping_radius(int(np.bincount(trained["train"][2]).max()))
    gaps = {}
    for name, a, b in zip(("fixed", "per_user"),
                          _coefficients(results[-1].model),
                          _coefficients(single[-1].model)):
        a, b = np.asarray(a), np.asarray(b)
        check(np.isfinite(a).all(), f"meshed {name} coefficients finite")
        gaps[name] = float(np.abs(a - b).max())
        say(f"mesh vs single device, {name}: max |gap| {gaps[name]:.3e} "
            f"(max |theta| {np.abs(b).max():.3f})")
    check(max(gaps.values()) <= atol,
          f"mesh agrees with one device inside the solver's stopping "
          f"radius {atol:.2e}")
    return {"gaps": gaps, "mem_checked": mem_checked,
            "job": ragged_width_job(sizes, mesh)}


# --------------------------------------------------------------------------

def run(sizes: Sizes, kernel_interpret: bool) -> dict:
    """Every phase, in order; raises on the first failed check."""
    import jax

    from photon_tpu.utils import compile_cache

    say(f"compile cache: {compile_cache.maybe_enable()}")
    out = {}
    with CompileClock() as clock, \
            tempfile.TemporaryDirectory(prefix="chip_smoke-") as tmp:
        trained = train_phase(sizes)
        out["train"] = {k: trained[k] for k in
                        ("cold_s", "warm_s", "auc", "oracle_auc",
                         "budget_source")}
        clock.lap("after train")
        out["serve"] = serve_phase(sizes, trained, tmp)
        out["cli"] = cli_phase(sizes, trained, tmp)
        clock.lap("after serve + cli")
        out["kernels"] = kernel_phase(sizes, interpret=kernel_interpret)
        out["layout"] = layout_phase(sizes)
        if jax.device_count() >= 4:
            out["mesh"] = mesh_phase(sizes, trained)
        else:
            say(f"{jax.device_count()} device(s): the four-device mesh "
                f"phase does not apply")
        clock.lap("at the end")
    out["compile"] = {"seconds": clock.seconds, "programs": clock.programs,
                      "cache_hits": clock.cache_hits}
    return out


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but jax.devices() returned "
              f"{devices} (platform {dev.platform!r}); not run",
              file=sys.stderr)
        return 2
    import jaxlib
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not importable"
    say(f"platform {dev.platform}, device_kind {dev.device_kind!r}, "
        f"{len(devices)} device(s); jax {jax.__version__}, jaxlib "
        f"{jaxlib.__version__}, libtpu {libtpu_version}")

    out = run(Sizes(), kernel_interpret=False)
    # what only the chip can promise
    check(out["train"]["budget_source"] == "backend",
          "the random-effect planner's budget comes from the backend's "
          "bytes_limit")
    plain = "layout-%dx%d" % Sizes().layout_plain_shape
    check(all(outcome == ("default" if tag == plain else "relaid")
              for tag, outcome in out["layout"].items()),
          "the chip re-lays the ragged-width matrices rows-major and "
          "leaves the 128-wide one as placed")
    if "mesh" in out:
        check(out["mesh"]["job"] == "mesh",
              "a meshed estimator leaves its ragged-width X to the mesh")
        check(out["mesh"]["mem_checked"],
              "per-device memory growth was checked")
    say("summary: " + json.dumps(out, sort_keys=True))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
