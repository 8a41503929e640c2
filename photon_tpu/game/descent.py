"""Coordinate descent over GAME coordinates with score algebra.

Reference: photon-lib algorithm/CoordinateDescent.scala:38 (run :93,
descend :119): outer loop over update sequence x iterations; each
coordinate trains against ``fullScore - ownScore`` (partial score,
:197-204); score container updated incrementally (:223-234); validation
after every coordinate update (:257-288); best model tracked by the primary
validation metric over FULL sweeps only (:162-171, :292-325); locked
coordinates (partial retraining) score but never train
(coordinatesToTrain :45).

TPU re-design: DataScores RDDs with +/- joins become flat [n] arrays with
elementwise arithmetic; the persist/unpersist choreography disappears
(arrays are device-resident); everything else keeps the reference's
semantics exactly.

Parallel sweeps (no reference analog — the Scala walks coordinates
strictly one at a time): with ``CoordinateDescentConfig.parallel`` the
update sequence is partitioned into CONTIGUOUS concurrency groups
(game/parallel_cd.py; default: fixed effect alone, consecutive random
effects together). Every member of a group solves against the SAME
partial score frozen at group entry — the solves become data-independent
and are dispatched from worker threads as overlapping async JAX
computations (host prep of one member overlaps device execution of
another; on a mesh, parallel/mesh.plan_group_placement names disjoint
device subsets per member). After the group, the score container is
reconciled in ONE canonical ordered pass, so sweep boundaries stay
bitwise-reproducible. Bounded staleness (arXiv 1811.01564, 1611.02101)
is policed by a convergence guard: the realized objective decrease
(fresh residuals) is compared against the solver-predicted decrease
(frozen residuals); regression beyond ``staleness_tol`` for
``staleness_patience`` consecutive groups degrades the rest of the run
to sequential mode — a typed obs event + counter, never an exception.
Singleton groups run the exact sequential arithmetic, so
``parallel_groups=[[c] for c in seq]`` is bitwise-identical to
sequential mode.

Resilience (no reference analog — Spark lineage recovery doesn't exist
here): every coordinate update is a fault boundary. A solve that trips a
device-side non-finite guard (optim.base.FailureMode) rolls the
coordinate back to its previous model and the sweep continues; the same
coordinate failing ``max_consecutive_failures`` times aborts with a
resumable mid-sweep checkpoint. In a parallel group the same isolation
holds per member: a failed member rolls back alone while the group's
other members commit. SIGTERM/SIGINT (resilience/shutdown.py) is honored
at the next coordinate boundary — GROUP boundary in parallel mode — with
an emergency partial checkpoint whose resume is bitwise-equal to the
uninterrupted run — which is why partial checkpoints persist the score
container verbatim instead of recomputing it (incremental score
arithmetic is order-sensitive in the last ulp). Sweep boundaries run the
multi-host consistency guard (resilience/multihost.py).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.game.model import GameModel
from photon_tpu.obs import solver as _obs_solver
from photon_tpu.obs import spans as _obs_spans
from photon_tpu.resilience import chaos as _chaos
from photon_tpu.resilience import failures as _failures
from photon_tpu.resilience import multihost as _multihost
from photon_tpu.resilience import shutdown as _shutdown
from photon_tpu.resilience.failures import (
    CoordinateFailureError,
    PreemptionRequested,
)

Array = jax.Array

logger = logging.getLogger(__name__)

# validation callback: GameModel -> {metric name: value}; first metric is primary
ValidationFn = Callable[[GameModel], Dict[str, float]]


@dataclasses.dataclass(frozen=True)
class CoordinateDescentConfig:
    update_sequence: List[str]
    num_iterations: int = 1
    locked_coordinates: frozenset = frozenset()  # partial retraining
    # abort (with a resumable checkpoint) after this many CONSECUTIVE
    # failed solves of the same coordinate; isolated failures roll back
    # and the sweep continues
    max_consecutive_failures: int = 3
    # parallel sweep mode: solve concurrency groups of coordinates
    # against bounded-stale frozen scores (module docstring; game/
    # parallel_cd.py). parallel_groups overrides the auto-grouping and
    # must partition update_sequence in order; singleton groups are
    # bitwise-identical to the sequential sweep.
    parallel: bool = False
    parallel_groups: Optional[List[List[str]]] = None
    # staleness guard: simultaneous solves legitimately realize LESS
    # than the sum of their independently-predicted decreases (Jacobi
    # vs Gauss-Seidel sub-additivity), so the guard polices the ratio: a
    # group regresses when realized decrease <
    # staleness_ratio * predicted - staleness_tol * (|predicted| + 1).
    # staleness_patience consecutive regressions degrade the rest of the
    # run to sequential (<= 0 disables the guard).
    staleness_tol: float = 1e-3
    staleness_ratio: float = 0.5
    staleness_patience: int = 2


@dataclasses.dataclass
class CoordinateDescentResult:
    model: GameModel
    best_model: GameModel
    validation_history: List[Dict[str, float]]
    best_iteration: Optional[int] = None


def run_coordinate_descent(
    coordinates: Dict[str, object],
    config: CoordinateDescentConfig,
    num_samples: int,
    initial_model: Optional[GameModel] = None,
    validation_fn: Optional[ValidationFn] = None,
    primary_metric_bigger_is_better: bool = True,
    dtype=jnp.float32,
    checkpoint_dir: Optional[str] = None,
    resume: bool = False,
) -> CoordinateDescentResult:
    """Run GAME coordinate descent.

    ``coordinates`` maps coordinate id -> FixedEffectCoordinate /
    RandomEffectCoordinate (game/coordinate.py); locked ids must come with
    their model inside ``initial_model`` (they only score).

    With ``checkpoint_dir``, every completed sweep is atomically published
    there; ``resume=True`` restarts from the latest one — the continuation
    is bitwise-equal to an uninterrupted run (SURVEY §5.3: checkpoint +
    restart replaces Spark lineage recovery; scores are recomputed from
    the models at sweep boundaries, restored verbatim from mid-sweep
    partial checkpoints, down-sampling PRNG counters are restored).
    """
    to_train = [c for c in config.update_sequence
                if c not in config.locked_coordinates]
    if not to_train:
        raise ValueError("no coordinates to train (all locked)")
    for cid in config.update_sequence:
        if cid not in coordinates:
            raise KeyError(f"coordinate {cid!r} missing from coordinates")
    for cid in config.locked_coordinates:
        if initial_model is None or cid not in initial_model:
            raise ValueError(f"locked coordinate {cid!r} needs an initial model")

    parallel_spans = None
    if config.parallel:
        from photon_tpu.game import parallel_cd as _pcd
        parallel_spans = _pcd.resolve_groups(config, coordinates)
        mesh = next((getattr(coordinates[c], "mesh", None)
                     for c in config.update_sequence
                     if getattr(coordinates[c], "mesh", None) is not None),
                    None)
        placement = {}
        if mesh is not None:
            from photon_tpu.parallel import mesh as M
            for _g_start, members in parallel_spans:
                if len(members) > 1:
                    placement.update(M.plan_group_placement(members, mesh))
        _pcd.begin_run(parallel_spans, placement or None)

    models: Dict[str, object] = dict(initial_model.models) if initial_model else {}
    best_model: Optional[GameModel] = None
    best_metric: Optional[float] = None
    best_iter: Optional[int] = None
    history: List[Dict[str, float]] = []
    start_iter = 0
    resume_coord_idx = 0
    restored_scores: Optional[Dict[str, Array]] = None
    restored_full: Optional[Array] = None

    if checkpoint_dir and resume:
        from photon_tpu.game import checkpoint as ckpt
        state = ckpt.load_latest(checkpoint_dir)
        if state is not None:
            models = dict(state.models)
            if state.sweep_in_progress is not None:
                # mid-sweep partial checkpoint (preemption / coordinate
                # abort): re-enter the interrupted sweep at the exact
                # coordinate boundary, score container verbatim
                start_iter = state.sweep_in_progress
                resume_coord_idx = state.next_coordinate
                restored_scores = {cid: jnp.asarray(v) for cid, v
                                   in (state.scores or {}).items()}
                restored_full = (None if state.full_score is None
                                 else jnp.asarray(state.full_score))
            else:
                start_iter = state.sweep + 1
            best_model = (GameModel(dict(state.best_models))
                          if state.best_models else None)
            best_metric = state.best_metric
            best_iter = state.best_iteration
            history = list(state.history)
            for cid, count in state.counters.items():
                if cid in coordinates and hasattr(coordinates[cid],
                                                  "_update_count"):
                    coordinates[cid]._update_count = count
            logger.info(
                "resumed from %s (sweep %d complete%s)", checkpoint_dir,
                state.sweep,
                "" if state.sweep_in_progress is None
                else f", re-entering sweep {start_iter}"
                     f" at coordinate index {resume_coord_idx}")

    scores: Dict[str, Array] = {}
    full_score = jnp.zeros((num_samples,), dtype)

    if restored_scores is not None:
        scores = restored_scores
        if restored_full is not None:
            full_score = restored_full
    else:
        # initial scores for any pre-existing models (warm start / locked /
        # checkpoint-resumed — at sweep boundaries scores are pure
        # functions of the models)
        for cid in config.update_sequence:
            if cid in models:
                s = coordinates[cid].score(models[cid])
                scores[cid] = s
                full_score = full_score + s

    def _counters() -> Dict[str, int]:
        return {cid: coordinates[cid]._update_count
                for cid in config.update_sequence
                if hasattr(coordinates[cid], "_update_count")}

    def save_partial(sweep_in_progress: int, next_k: int,
                     group_boundary: bool = False) -> Optional[str]:
        """Emergency mid-sweep checkpoint at a coordinate boundary
        (a GROUP boundary in parallel mode sets ``group_boundary``)."""
        if not checkpoint_dir:
            return None
        from photon_tpu.game import checkpoint as ckpt
        return ckpt.save_checkpoint(
            checkpoint_dir, sweep_in_progress - 1, models, _counters(),
            best_models=None if best_model is None else best_model.models,
            best_metric=best_metric, best_iteration=best_iter,
            history=history,
            sweep_in_progress=sweep_in_progress, next_coordinate=next_k,
            scores={cid: np.asarray(s) for cid, s in scores.items()},
            full_score=np.asarray(full_score),
            group_boundary=group_boundary)

    consecutive: Dict[str, int] = {}
    # the last validation_fn result for the CURRENT models, or None when
    # models changed since — lets the sweep boundary reuse the final
    # coordinate's post-update validation instead of scoring the
    # identical model a second time
    metrics_current: Optional[Dict[str, float]] = None
    # staleness-guard state (parallel mode): consecutive regressed
    # groups, and the sticky degraded-to-sequential flag
    stale_streak = 0
    fallback_active = False

    def _record_solver_obs(cid: str, coord, it: int) -> None:
        tracker = getattr(coord, "last_tracker", None)
        if tracker is not None:
            # telemetry keeps a REFERENCE (device arrays and all);
            # the host transfer happens at drain time, not here
            _obs_solver.record(cid, tracker, sweep=it)
            if logger.isEnabledFor(logging.DEBUG):
                # summary() forces a device->host sync; never pay it
                # unless debug logging actually consumes it
                logger.debug("coord %s solver: %s", cid, tracker.summary())
        n_failed_entities = getattr(coord, "last_failed_entities", 0)
        if n_failed_entities:
            # isolated per-entity failures: those entities kept their
            # warm start inside the solve; the coordinate is still good
            _failures.record_failure(
                "entity_solve_failures", coordinate=cid, sweep=it,
                entities=int(n_failed_entities))

    def _commit(cid: str, it: int, new_model, new_score,
                validate: bool = True) -> None:
        """``validate=False`` is the concurrent-group path: members commit
        atomically at reconciliation, so the models between member commits
        are mixtures that never existed as trajectory states — the group
        runs ONE validation at its boundary instead (sequential mode keeps
        the reference per-coordinate cadence)."""
        nonlocal full_score, metrics_current
        consecutive[cid] = 0
        models[cid] = new_model
        own = scores.get(cid)
        full_score = (full_score - own + new_score) if own is not None \
            else (full_score + new_score)
        scores[cid] = new_score
        metrics_current = None
        if validate and validation_fn is not None:
            metrics = validation_fn(GameModel(dict(models)))
            metrics_current = metrics
            history.append({"iteration": it, "coordinate": cid, **metrics})
            logger.info("CD iter %d coord %s: %s", it, cid, metrics)

    def _rollback(cid: str, it: int, failure) -> bool:
        """Discard the failed solve, keep the previous model + score;
        True when the consecutive-failure budget is exhausted (abort)."""
        consecutive[cid] = consecutive.get(cid, 0) + 1
        _failures.record_failure(
            "coordinate_rollback", coordinate=cid, sweep=it,
            failure=failure.name, consecutive=consecutive[cid])
        logger.warning(
            "coordinate %s failed (%s) at sweep %d; rolled back "
            "(%d consecutive)", cid, failure.name, it, consecutive[cid])
        return consecutive[cid] >= config.max_consecutive_failures

    def _train_one(k: int, cid: str, it: int) -> bool:
        """One sequential-semantics coordinate update against the LIVE
        score container; ``k`` is the coordinate's index in the update
        sequence (the checkpoint boundary on abort). Returns True when
        the new model committed, False on rollback."""
        coord = coordinates[cid]
        if _chaos.is_active() and _chaos.should_poison_nan(cid, it):
            coord._chaos_poison_once = True
        own = scores.get(cid)
        partial = full_score - own if own is not None else full_score
        residual = partial if len(config.update_sequence) > 1 else None
        with _obs_spans.span("cd/update", coordinate=cid):
            new_model = coord.update_model(models.get(cid), residual)
        with _obs_spans.annotate("cd/record", coordinate=cid):
            _record_solver_obs(cid, coord, it)
        failure = getattr(coord, "last_failure", None)
        if failure is not None:
            # coordinate-level failure: discard the new model, keep the
            # previous one and its score — the sweep continues on the
            # other coordinates
            if _rollback(cid, it, failure):
                path = save_partial(it, k + 1)
                _failures.record_failure(
                    "coordinate_abort", coordinate=cid, sweep=it,
                    consecutive=consecutive[cid], checkpoint=path)
                raise CoordinateFailureError(
                    cid, it, consecutive[cid], checkpoint_path=path)
            return False
        with _obs_spans.annotate("cd/score", coordinate=cid):
            new_score = coord.score(new_model)
        with _obs_spans.annotate("cd/commit", coordinate=cid):
            _commit(cid, it, new_model, new_score)
        return True

    def _run_group(it: int, gi: int, g_start: int, members: List[str],
                   train: List[str]) -> None:
        """One concurrent group: freeze the score container, dispatch all
        members' solves from worker threads against the same frozen
        partial scores, then reconcile in ONE canonical ordered pass and
        run the staleness guard (one host read, at the group boundary)."""
        nonlocal stale_streak, fallback_active, metrics_current
        from photon_tpu.game import parallel_cd as _pcd
        t0 = time.perf_counter()
        with _obs_spans.span("cd/group", iteration=it, group=gi,
                             size=len(train)):
            # every member sees the container AS OF group entry
            frozen = full_score
            resids = {}
            for cid in train:
                own = scores.get(cid)
                resids[cid] = frozen - own if own is not None else frozen
            old_models = {cid: models.get(cid) for cid in train}
            old_scores = {cid: scores.get(cid) for cid in train}

            def _solve_member(cid: str):
                coord = coordinates[cid]
                delay = _chaos.straggler_delay(cid, it)
                if delay:
                    time.sleep(delay)  # injected straggler inside the group
                if _chaos.is_active() and _chaos.should_poison_nan(cid, it):
                    coord._chaos_poison_once = True
                with _obs_spans.span("cd/update", coordinate=cid, group=gi):
                    new_model = coord.update_model(old_models[cid],
                                                   resids[cid])
                failure = getattr(coord, "last_failure", None)
                # scoring in-thread too: score VALUES are order-free (only
                # the container arithmetic is order-sensitive, and that
                # happens in the canonical pass below)
                new_score = coord.score(new_model) if failure is None else None
                return new_model, new_score, failure

            # run-level pool: worker threads are reused across groups and
            # sweeps (per-group executor churn would cost ~0.1 ms each)
            solved = dict(zip(train, group_pool.map(_solve_member, train)))

            aborted: Optional[str] = None
            committed: List[str] = []
            for cid in train:  # canonical ordered reconciliation pass
                new_model, new_score, failure = solved[cid]
                _record_solver_obs(cid, coordinates[cid], it)
                if failure is not None:
                    # member-level isolation: this member rolls back; the
                    # group's other members still commit below
                    _pcd.record_member_failure(cid, it)
                    if _rollback(cid, it, failure):
                        aborted = cid
                    continue
                _commit(cid, it, new_model, new_score, validate=False)
                committed.append(cid)

            if aborted is not None:
                # healthy members committed above — the group END is the
                # resumable boundary
                path = save_partial(it, g_start + len(members),
                                    group_boundary=True)
                _failures.record_failure(
                    "coordinate_abort", coordinate=aborted, sweep=it,
                    consecutive=consecutive[aborted], checkpoint=path)
                raise CoordinateFailureError(
                    aborted, it, consecutive[aborted], checkpoint_path=path)

            if committed and validation_fn is not None:
                # group-granular validation cadence (see _commit)
                metrics = validation_fn(GameModel(dict(models)))
                metrics_current = metrics
                history.append({"iteration": it,
                                "coordinate": f"group:{gi}", **metrics})
                logger.info("CD iter %d group %d: %s", it, gi, metrics)

            # convergence guard in SCORE SPACE: objective_value(m, resid)
            # == data_loss(resid + score(m)) + reg(m), and reconciliation
            # already materialized every score vector involved — so the
            # predicted loss decrease of member m against its frozen
            # residual is L(frozen) - L(frozen + new_score_m -
            # old_score_m), and the realized group decrease is L(frozen) -
            # L(reconciled container). The guard therefore costs O(n)
            # elementwise evals, never feature passes. Per-member reg
            # deltas appear identically in predicted and realized and drop
            # out of both sides. Everything stays on device until the
            # single boundary read.
            predicted = realized = None
            regressed = False
            if config.staleness_patience > 0 and len(committed) >= 2:
                lp = coordinates[committed[0]]
                L0 = lp.data_loss_at(frozen)
                pred = None
                for cid in committed:
                    own = old_scores[cid]
                    delta = (scores[cid] - own if own is not None
                             else scores[cid])
                    d = L0 - lp.data_loss_at(frozen + delta)
                    pred = d if pred is None else pred + d
                real = L0 - lp.data_loss_at(full_score)
                if pred is not None:
                    thresh = (config.staleness_ratio * pred
                              - config.staleness_tol * (jnp.abs(pred) + 1.0))
                    # ONE device->host transfer per group, at the boundary
                    h = np.asarray(jnp.stack([pred, real, thresh]))
                    predicted, realized = float(h[0]), float(h[1])
                    regressed = bool(h[1] < h[2])
                    if regressed:
                        stale_streak += 1
                        logger.warning(
                            "parallel CD group %d (sweep %d): stale "
                            "regression — realized decrease %.3e < "
                            "predicted %.3e (streak %d)", gi, it,
                            realized, predicted, stale_streak)
                        if (stale_streak >= config.staleness_patience
                                and not fallback_active):
                            fallback_active = True
                            _pcd.record_fallback(it, gi, stale_streak)
                            logger.warning(
                                "parallel CD: staleness guard tripped %d "
                                "consecutive groups — degrading to "
                                "sequential sweeps", stale_streak)
                    else:
                        stale_streak = 0
        _pcd.record_group(sweep=it, group=gi, size=len(train),
                          committed=len(committed),
                          seconds=time.perf_counter() - t0,
                          predicted=predicted, realized=realized,
                          regressed=regressed)

    # one worker pool for the whole run: concurrent-group members are
    # dispatched from threads so their host-side work and device waits
    # interleave; reusing the pool across groups and sweeps avoids
    # per-group executor churn
    group_pool: Optional[ThreadPoolExecutor] = None
    if parallel_spans is not None:
        widest = max((len(m) for _g, m in parallel_spans), default=0)
        if widest > 1:
            group_pool = ThreadPoolExecutor(max_workers=widest,
                                            thread_name_prefix="cd-group")
    try:
        for it in range(start_iter, config.num_iterations):
          with _obs_spans.span("cd/sweep", iteration=it):
            if parallel_spans is not None:
                from photon_tpu.game import parallel_cd as _pcd
                for gi, (g_start, members) in enumerate(parallel_spans):
                    if it == start_iter and g_start + len(members) <= resume_coord_idx:
                        continue  # re-entered sweep: group fully ran pre-restart
                    for cid in members:
                        _chaos.maybe_preempt(it, cid)
                    if _shutdown.requested():
                        # preemption lands on the GROUP boundary
                        path = save_partial(it, g_start, group_boundary=True)
                        _failures.record_failure(
                            "preemption", sweep=it, coordinate=members[0],
                            reason=_shutdown.reason(), checkpoint=path)
                        raise PreemptionRequested(checkpoint_path=path, sweep=it,
                                                  coordinate=members[0])
                    midgroup = it == start_iter and g_start < resume_coord_idx
                    pending = (members[resume_coord_idx - g_start:] if midgroup
                               else members)
                    train = [cid for cid in pending
                             if cid not in config.locked_coordinates]
                    if not train:
                        continue
                    if fallback_active or len(train) == 1 or midgroup:
                        # sequential semantics: staleness fallback, degenerate
                        # group, or re-entry MID-group from a coordinate-
                        # boundary checkpoint (the restored container's
                        # incremental arithmetic must continue exactly)
                        t0 = time.perf_counter()
                        n_committed = 0
                        with _obs_spans.span("cd/group", iteration=it, group=gi,
                                             size=len(train), mode="sequential"):
                            for cid in train:
                                if _train_one(g_start + members.index(cid),
                                              cid, it):
                                    n_committed += 1
                        _pcd.record_group(sweep=it, group=gi, size=len(train),
                                          committed=n_committed,
                                          seconds=time.perf_counter() - t0,
                                          sequentialized=True)
                        continue
                    _run_group(it, gi, g_start, members, train)
            else:
                for k, cid in enumerate(config.update_sequence):
                    if it == start_iter and k < resume_coord_idx:
                        continue  # re-entered sweep: these already ran pre-restart
                    _chaos.maybe_preempt(it, cid)
                    if _shutdown.requested():
                        path = save_partial(it, k)
                        _failures.record_failure(
                            "preemption", sweep=it, coordinate=cid,
                            reason=_shutdown.reason(), checkpoint=path)
                        raise PreemptionRequested(checkpoint_path=path, sweep=it,
                                                  coordinate=cid)
                    if cid in config.locked_coordinates:
                        continue
                    _train_one(k, cid, it)

            resume_coord_idx = 0  # only the re-entered sweep skips coordinates

            # best-model bookkeeping over FULL sweeps (reference :162-171).
            # The final coordinate's post-update validation already scored
            # exactly these models — reuse it instead of a second identical
            # validation pass; metrics_current is None whenever models
            # changed without a fresh validation (or none ran this sweep)
            if validation_fn is not None:
                metrics = (metrics_current if metrics_current is not None
                           else validation_fn(GameModel(dict(models))))
                metrics_current = metrics
                primary = next(iter(metrics.values()))
                is_better = (best_metric is None
                             or (primary > best_metric if primary_metric_bigger_is_better
                                 else primary < best_metric))
                if is_better:
                    best_metric = primary
                    best_model = GameModel(dict(models))
                    best_iter = it

            # canonicalize the running sum at sweep boundaries: a resume
            # rebuilds full_score as a FRESH ordered sum over the models, and
            # bitwise-equal continuation requires the uninterrupted run to
            # hold the same value (incremental "full - own + new" arithmetic
            # drifts in the last ulp)
            full_score = jnp.zeros((num_samples,), dtype)
            for cid in config.update_sequence:
                if cid in scores:
                    full_score = full_score + scores[cid]

            # sweep boundary = the one place replicated state is compared
            # across hosts (collective; every process reaches it together)
            _multihost.check_consistency(models, it)

            ckpt_path = None
            if checkpoint_dir:
                from photon_tpu.game import checkpoint as ckpt
                ckpt_path = ckpt.save_checkpoint(
                    checkpoint_dir, it, models, _counters(),
                    best_models=None if best_model is None else best_model.models,
                    best_metric=best_metric, best_iteration=best_iter,
                    history=history)
            if _shutdown.requested():
                # the sweep-boundary checkpoint just published IS the
                # emergency checkpoint — stop before starting another sweep
                _failures.record_failure("preemption", sweep=it,
                                         reason=_shutdown.reason(),
                                         checkpoint=ckpt_path)
                raise PreemptionRequested(checkpoint_path=ckpt_path, sweep=it)
    finally:
        if group_pool is not None:
            group_pool.shutdown(wait=False)

    final = GameModel(dict(models))
    return CoordinateDescentResult(
        model=final,
        best_model=best_model if best_model is not None else final,
        validation_history=history,
        best_iteration=best_iter,
    )
