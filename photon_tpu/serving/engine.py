"""The serving engine: admission, SLO ladder, dispatch, telemetry.

Request lifecycle::

    submit() ── draining? ──────────> typed SHUTTING_DOWN response
        │  ── breaker open? ────────> typed BREAKER_REJECTED response
        │  ── deadline infeasible? ─> typed DEADLINE_EXCEEDED response
        │  ── depth > reject? ──────> typed SLO_REJECTED response
        ▼ queue (MicroBatcher, deadline-aware release)
    pump() ── batch ready? ──> expire overdue ──> typed DEADLINE_EXCEEDED
        │                          │ survivors: assemble (host pack, pad)
        ▼                          ▼ depth > shed / breaker shed? fixed_only
    responses <── unpad <── compiled scorer (one dispatch per batch)
                                │ stage latency + ok ──> circuit breaker
                                └ breaker trip in probation? ──> rollback

Everything observable lands in the process metrics registry under the
``serving.*`` namespace; ``stats()`` folds the registry snapshot plus
compile-phase accounting into the dict that becomes the RunReport's
``serving`` section.

Model state is versioned: ``publish_model`` atomically installs a staged
:class:`~photon_tpu.serving.model_state.DeviceResidentModel` between
micro-batches (serving/swap.py runs the validation gates first) and
keeps the prior version for ``rollback_model`` — which the engine calls
itself when the breaker trips inside the post-swap probation window.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from photon_tpu.obs.metrics import registry as _metrics
from photon_tpu.obs.timeseries import series as _series
from photon_tpu.resilience import chaos as _chaos
from photon_tpu.resilience.failures import record_failure
from photon_tpu.serving.batching import (
    BucketLadder,
    MicroBatcher,
    Pending,
    QueueClosedError,
)
from photon_tpu.serving.breaker import (
    OPEN,
    SHED,
    STATE_LEVELS,
    CircuitBreaker,
)
from photon_tpu.serving.model_state import DeviceResidentModel
from photon_tpu.serving.scorer import (INT8_MODE, THOMPSON_MODE,
                                       dispatch, serving_modes,
                                       warmup_scorers)
from photon_tpu.serving.types import (
    Fallback,
    FallbackReason,
    ScoreRequest,
    ScoreResponse,
    ServingConfig,
)
from photon_tpu.utils import compile_cache

# serving latencies live well under the DEFAULT_BUCKETS floor (5ms);
# ~1.3x geometric steps from 50us to ~5s keep the interpolated
# p50/p95/p99 honest at sub-millisecond scale
LATENCY_BUCKETS = tuple(50e-6 * 1.3 ** i for i in range(36))


class ServingEngine:
    """Online scorer over a device-resident GAME model."""

    def __init__(self, model: DeviceResidentModel,
                 config: Optional[ServingConfig] = None,
                 clock=None, obs_labels: Optional[dict] = None):
        self.model = model
        self.config = config or ServingConfig()
        # metric labels distinguishing this engine in a multi-engine
        # process (tenant=... in a MultiTenantEngine, shard=... in a
        # fleet) — without them every engine overwrites the same plain
        # warmup gauges; with them the per-engine values survive
        # ``obs.merge_snapshots`` as distinct labeled keys
        self.obs_labels = dict(obs_labels or {})
        self.ladder = BucketLadder(self.config.max_batch,
                                   self.config.min_bucket)
        self.batcher = MicroBatcher(
            self.ladder, self.config.max_wait_s, clock=clock,
            deadline_headroom_s=self.config.deadline.score_headroom_s,
            on_admit=self._prefetch_lookahead)
        self.clock = self.batcher.clock
        self.breaker = CircuitBreaker(self.config.breaker, clock=self.clock,
                                      on_transition=self._on_breaker)
        self._warmed = False
        self._warmup_seconds = 0.0
        self._warmup_programs = 0
        # model versioning (live swap): the lock orders publish/rollback
        # against batch dispatch; reads of self.model are a single
        # attribute load, so a swap lands exactly between micro-batches
        self._model_lock = threading.Lock()
        self.model_version = 1
        self.model_label = "initial"
        self._prior: Optional[Tuple[DeviceResidentModel, int, str]] = None
        self._probation_until: Optional[float] = None
        self.swap_history: List[dict] = []
        _metrics.gauge("serving.model_version").set(self.model_version)
        # shadow capture: the most recent admitted requests, the sample a
        # candidate model is validated against before publish
        self._capture: deque = deque(maxlen=self.config.swap.capture_size)
        # rows currently mid-delta-publish, as {(re_type, entity_id)}.
        # Swapped atomically (one attribute store of an immutable set) by
        # the nearline publisher; the admission lookahead consults it so
        # a request racing a publish never prefetches a half-published
        # entity — the publish stays atomic per batch boundary.
        self.pending_publish_rows: frozenset = frozenset()
        # drain state
        self._draining = False
        self._drain_reason: Optional[str] = None
        self._drain_info: Optional[dict] = None

    @classmethod
    def from_model_dir(cls, model_dir: str,
                       config: Optional[ServingConfig] = None,
                       mesh=None, clock=None,
                       coordinates_to_load=None,
                       obs_labels: Optional[dict] = None) -> "ServingEngine":
        from photon_tpu.io.model_io import load_for_serving

        serving_model = load_for_serving(
            model_dir, coordinates_to_load=coordinates_to_load)
        model = DeviceResidentModel(serving_model, mesh=mesh,
                                    feature_pad=(config.feature_pad
                                                 if config else None),
                                    coeff_store=(config.coeff_store
                                                 if config else None),
                                    append_reserve=(config.append_reserve
                                                    if config else 0),
                                    int8=(config.int8_serving
                                          if config else False),
                                    thompson=(config.thompson_serving
                                              if config else False),
                                    prior_variance=(config.prior_variance
                                                    if config else 1.0))
        return cls(model, config=config, clock=clock, obs_labels=obs_labels)

    def _prefetch_lookahead(self, request: ScoreRequest) -> None:
        """MicroBatcher ``on_admit`` hook: resolve the request's entities
        against the two-tier stores at admission so their cold->hot
        uploads are usually done by batch-pop time.

        Consults the pending-publish row set first: an entity whose cold
        row is mid-delta-publish must NOT be prefetched — the promotion
        could read a half-written cold row, or hoist a pre-publish row
        into the hot tier an instant before the commit remaps it. Those
        entities skip the lookahead (counted) and promote on their next
        natural miss after the publish commits."""
        model = self.model
        if not model.has_stores:
            return
        pending = self.pending_publish_rows
        if pending and any(
                (re_type, re_id) in pending
                for re_type, re_id in request.entity_ids.items()):
            _metrics.counter("serving.prefetch_publish_deferred").inc()
            model.prefetch_request(request, skip=pending)
            return
        model.prefetch_request(request)

    # -- warmup --------------------------------------------------------------

    def warmup(self) -> dict:
        """Compile-and-dispatch the whole (mode x bucket) ladder. After
        this returns, steady-state serving performs zero compiles — the
        contract ``scripts/check_serving_no_recompile.py`` enforces."""
        t0 = time.perf_counter()
        self._warmup_programs = warmup_scorers(self.model,
                                               self.ladder.buckets)
        self._warmup_seconds = time.perf_counter() - t0
        self._warmed = True
        _metrics.gauge("serving.warmup_seconds",
                       **self.obs_labels).set(self._warmup_seconds)
        _metrics.gauge("serving.warmup_programs",
                       **self.obs_labels).set(self._warmup_programs)
        return {"programs": self._warmup_programs,
                "buckets": list(self.ladder.buckets),
                "modes": list(serving_modes(self.model)),
                "seconds": self._warmup_seconds,
                "compile_counts": compile_cache.compile_counts()}

    # -- admission -----------------------------------------------------------

    def _refuse(self, request: ScoreRequest, reason: FallbackReason,
                detail: str = "") -> ScoreResponse:
        _metrics.counter("serving.degraded", reason=reason.value).inc()
        # windowed + labeled: per-engine typed-degradation rate over time
        # (the cumulative counter above stays as the run-total shim)
        _series.counter("serving.degraded", reason=reason.value,
                        **self.obs_labels).inc(self.clock())
        return ScoreResponse(
            request.uid, score=None, degraded=True,
            fallbacks=(Fallback(reason, detail=detail),))

    def submit(self, request: ScoreRequest) -> Optional[ScoreResponse]:
        """Admit one request. Returns an immediate typed refusal when the
        engine cannot serve it (draining, breaker open, infeasible
        deadline, queue past the reject threshold), else None (the
        response arrives from a later ``pump``)."""
        _metrics.counter("serving.requests").inc()
        _series.counter("serving.requests",
                        **self.obs_labels).inc(self.clock())
        if self._draining:
            return self._refuse(request, FallbackReason.SHUTTING_DOWN,
                                detail=self._drain_reason or "draining")
        if not self.breaker.admit():
            return self._refuse(request, FallbackReason.BREAKER_REJECTED,
                                detail="circuit breaker open")
        now = self.clock()
        timeout = (request.timeout_s if request.timeout_s is not None
                   else self.config.deadline.default_timeout_s)
        deadline = None
        if timeout is not None:
            if timeout < self.config.deadline.min_service_s:
                return self._refuse(
                    request, FallbackReason.DEADLINE_EXCEEDED,
                    detail=f"budget {timeout * 1e3:.1f}ms below service "
                           f"floor "
                           f"{self.config.deadline.min_service_s * 1e3:.1f}ms")
            deadline = now + timeout
        depth = self.batcher.depth()
        if depth >= self.config.slo.reject_queue_depth:
            return self._refuse(request, FallbackReason.SLO_REJECTED,
                                detail=f"queue depth {depth}")
        try:
            self.batcher.submit(request, deadline=deadline)
        except QueueClosedError:
            # drain began between the flag check and the enqueue (signal
            # handlers land anywhere): still a typed response, never a
            # raised exception to the client
            return self._refuse(request, FallbackReason.SHUTTING_DOWN,
                                detail=self._drain_reason or "draining")
        self._capture.append(request)
        _metrics.gauge("serving.queue_depth").set(self.batcher.depth())
        return None

    def recent_requests(self, n: Optional[int] = None) -> List[ScoreRequest]:
        """The newest admitted requests (shadow-scoring sample for swap)."""
        items = list(self._capture)
        return items if n is None else items[-n:]

    # -- dispatch ------------------------------------------------------------

    def pump(self, flush: bool = False) -> List[ScoreResponse]:
        """Form and score at most one batch; [] when none is ready.
        Drain loops call this repeatedly; ``flush`` overrides the
        coalescing deadline (stream end / synchronous serve)."""
        depth_before = self.batcher.depth()
        popped = self.batcher.next_batch(flush=flush)
        if popped is None:
            return []
        items, _bucket = popped
        # deadline enforcement at the queue->score boundary: requests that
        # can no longer make their deadline are refused instead of
        # occupying a slot; the rest of the batch still scores (in the
        # smallest covering bucket, which warmup has compiled)
        now = self.clock()
        headroom = self.config.deadline.score_headroom_s
        responses: List[ScoreResponse] = []
        live: List[Pending] = []
        for p in items:
            if p.deadline is not None and now > p.deadline - headroom:
                responses.append(self._refuse(
                    p.request, FallbackReason.DEADLINE_EXCEEDED,
                    detail=f"expired in queue after "
                           f"{(now - p.t_submit) * 1e3:.1f}ms"))
            else:
                live.append(p)
        if live:
            bucket = self.ladder.bucket_for(len(live))
            shed = depth_before > self.config.slo.shed_queue_depth
            t_start = self.clock()
            responses.extend(self._score_batch(live, bucket, shed, t_start))
        _metrics.gauge("serving.queue_depth").set(self.batcher.depth())
        return responses

    def _score_batch(self, items: Sequence[Pending], bucket: int,
                     shed: bool, t_start: float) -> List[ScoreResponse]:
        requests = [p.request for p in items]
        full_ok, probe = self.breaker.allow_full()
        breaker_shed = not full_ok
        shed_any = shed or breaker_shed
        model = self.model    # one read: a concurrent publish lands on
        # the next batch, never mid-batch
        if shed_any:
            mode = "fixed_only"
        elif getattr(model, "thompson_enabled", False):
            # explore/exploit IS the healthy-path program for a
            # variance-carrying model under thompson_serving; sheds
            # still drop to fixed_only above (no exploration under
            # pressure), and it outranks int8 (sampling needs f32 vars)
            mode = THOMPSON_MODE
        elif getattr(model, "int8_enabled", False):
            mode = INT8_MODE  # quantized arm IS the healthy-path program
        else:
            mode = "full"
        seeds = None
        if mode == THOMPSON_MODE:
            # per-request sampling keys from the uid alone: bitwise
            # replay-stable no matter how requests batch or arrive
            from photon_tpu.utils.seeds import request_key, split32

            hi = np.zeros(bucket, np.uint32)
            lo = np.zeros(bucket, np.uint32)
            for i, r in enumerate(requests):
                hi[i], lo[i] = split32(
                    request_key(self.config.thompson_seed, r.uid))
            seeds = (hi, lo)

        # two-tier consistency contract: assemble (slot lookups against the
        # host-side hot maps), the table read, and the scorer DISPATCH all
        # happen in ONE transfer_lock hold, so the transfer thread cannot
        # donate a table or remap a slot between the lookup and the gather
        # that consumes it. Only the dispatch is inside the lock — the
        # blocking np.asarray materialization happens after release, so
        # transfers overlap device compute. Full-resident models share the
        # same (uncontended) lock, keeping one code path.
        scorer_ok = True
        scores = None
        raw = None
        with model.transfer_lock:
            t0 = time.perf_counter()
            args, fallbacks, counters = model.assemble(
                requests, bucket, shed_random=shed_any,
                explore_unknown=(mode == THOMPSON_MODE))
            t_assemble = time.perf_counter() - t0

            t0 = time.perf_counter()
            try:
                delay = _chaos.scorer_delay()
                if delay > 0:
                    time.sleep(delay)
                raw = dispatch(model, mode, bucket, args, seeds=seeds)
            except Exception as e:  # device/dispatch fault: typed, counted
                scorer_ok = False
                record_failure("serving_scorer_error", error=repr(e),
                               bucket=bucket, mode=mode)
        if scorer_ok:
            try:
                scores = np.asarray(raw)
            except Exception as e:
                scorer_ok = False
                record_failure("serving_scorer_error", error=repr(e),
                               bucket=bucket, mode=mode)
        t_score = time.perf_counter() - t0

        n = len(requests)
        if scores is not None and not np.all(np.isfinite(scores[:n])):
            scorer_ok = False
            record_failure("serving_nonfinite_scores", bucket=bucket,
                           mode=mode,
                           count=int(np.sum(~np.isfinite(scores[:n]))))
        self.breaker.record(t_score, scorer_ok, probe=probe)
        self._check_probation()

        if not scorer_ok:
            _metrics.counter("serving.responses").inc(n)
            _metrics.counter("serving.batches", bucket=str(bucket),
                             mode=mode).inc()
            return [self._refuse(r, FallbackReason.SCORER_FAILURE,
                                 detail="scorer raised" if scores is None
                                 else "non-finite score")
                    for r in requests]

        if shed:
            for fb in fallbacks:
                fb.append(Fallback(FallbackReason.SLO_SHED_RANDOM_EFFECTS,
                                   detail=f"batch mode {mode}"))
        elif breaker_shed:
            for fb in fallbacks:
                fb.append(Fallback(
                    FallbackReason.BREAKER_SHED_RANDOM_EFFECTS,
                    detail="circuit breaker shed"))

        responses = []
        lat_series = _series.quantile("serving.latency", mode=mode,
                                      **self.obs_labels)
        resp_series = _series.counter("serving.responses", **self.obs_labels)
        for i, (pending, req) in enumerate(zip(items, requests)):
            fbs = tuple(fallbacks[i])
            responses.append(ScoreResponse(
                req.uid, score=float(scores[i]),
                degraded=bool(fbs), fallbacks=fbs))
            # queue time from the injected clock (deterministic in tests);
            # total = queue + host assemble + device score
            q = max(t_start - pending.t_submit, 0.0)
            _metrics.histogram("serving.latency_seconds", LATENCY_BUCKETS,
                               stage="queue").observe(q)
            _metrics.histogram("serving.latency_seconds", LATENCY_BUCKETS,
                               stage="total").observe(q + t_assemble + t_score)
            # per-label windowed quantiles: THIS engine's latency in THIS
            # window, so tenant/shard tails never pollute each other the
            # way the process-global histograms above do
            lat_series.observe(t_start, q + t_assemble + t_score)
            resp_series.inc(t_start)

        _metrics.counter("serving.responses").inc(len(responses))
        _metrics.counter("serving.batches", bucket=str(bucket),
                         mode=mode).inc()
        _metrics.counter("serving.padded_rows").inc(counters["padded_rows"])
        if counters["truncated_features"]:
            _metrics.counter("serving.degraded",
                             reason=FallbackReason.FEATURE_OVERFLOW.value
                             ).inc(counters["truncated_features"])
        if counters["unknown_entities"]:
            _metrics.counter("serving.degraded",
                             reason=FallbackReason.UNKNOWN_ENTITY.value
                             ).inc(counters["unknown_entities"])
        if counters.get("cold_misses"):
            _metrics.counter("serving.degraded",
                             reason=FallbackReason.COLD_MISS.value
                             ).inc(counters["cold_misses"])
        if counters.get("explored_cold_start"):
            _metrics.counter(
                "serving.degraded",
                reason=FallbackReason.EXPLORING_COLD_START.value
                ).inc(counters["explored_cold_start"])
        if shed:
            _metrics.counter(
                "serving.degraded",
                reason=FallbackReason.SLO_SHED_RANDOM_EFFECTS.value
                ).inc(len(responses))
        elif breaker_shed:
            _metrics.counter(
                "serving.degraded",
                reason=FallbackReason.BREAKER_SHED_RANDOM_EFFECTS.value
                ).inc(len(responses))
        _metrics.histogram("serving.latency_seconds", LATENCY_BUCKETS,
                           stage="assemble").observe(t_assemble)
        _metrics.histogram("serving.latency_seconds", LATENCY_BUCKETS,
                           stage="score").observe(t_score)
        return responses

    # -- circuit breaker wiring ----------------------------------------------

    def _on_breaker(self, frm: str, to: str, why: str) -> None:
        _metrics.gauge("serving.breaker_state").set(STATE_LEVELS[to])
        _metrics.counter("serving.breaker_transitions", to=to).inc()
        if to in (SHED, OPEN):
            record_failure("serving_breaker_trip", from_state=frm,
                           to_state=to, why=why)

    def _check_probation(self) -> None:
        """Post-swap guard: a breaker trip inside the probation window
        rolls the swap back automatically."""
        until = self._probation_until
        if until is None:
            return
        if self.clock() > until:
            self._probation_until = None
            return
        if self.breaker.state() in (SHED, OPEN):
            self.rollback_model("breaker tripped in post-swap probation")

    # -- live model swap (publish/rollback; gates live in serving/swap.py) ---

    def publish_model(self, staged: DeviceResidentModel,
                      label: str) -> dict:
        """Atomically install a staged (already warmed) model between
        micro-batches. The prior version is retained for rollback; the
        breaker watches the new model for ``swap.probation_s``."""
        with self._model_lock:
            self._prior = (self.model, self.model_version, self.model_label)
            self.model = staged
            self.model_version += 1
            self.model_label = label
            version = self.model_version
            if self.config.swap.probation_s > 0:
                self._probation_until = (self.clock()
                                         + self.config.swap.probation_s)
        _metrics.gauge("serving.model_version").set(version)
        _metrics.counter("serving.swap_published").inc()
        return {"version": version, "label": label}

    def rollback_model(self, why: str) -> bool:
        """Restore the pre-swap model (bitwise: the prior
        DeviceResidentModel object and its compiled programs are reused
        untouched). Returns False when there is nothing to roll back."""
        with self._model_lock:
            if self._prior is None:
                return False
            rolled_from = (self.model_version, self.model_label)
            self.model, self.model_version, self.model_label = self._prior
            self._prior = None
            self._probation_until = None
            version = self.model_version
        _metrics.gauge("serving.model_version").set(version)
        _metrics.counter("serving.swap_rollbacks").inc()
        record_failure("serving_swap_rollback", why=why,
                       from_version=rolled_from[0], from_label=rolled_from[1],
                       to_version=version, to_label=self.model_label)
        self.swap_history.append({
            "outcome": "rolled_back", "why": why,
            "from_version": rolled_from[0], "from_label": rolled_from[1],
            "to_version": version, "to_label": self.model_label,
            "gates": {},
        })
        return True

    # -- graceful drain ------------------------------------------------------

    def begin_drain(self, reason: str = "drain requested") -> None:
        """Flip to draining: admission refuses with typed SHUTTING_DOWN,
        queued work stays poppable. Lock-free flag flips only — safe from
        a signal handler."""
        if self._draining:
            return
        self._draining = True
        self._drain_reason = reason
        self.batcher.close()
        _metrics.gauge("serving.draining").set(1)

    @property
    def draining(self) -> bool:
        return self._draining

    def shutdown(self, drain_budget_s: Optional[float] = None,
                 reason: str = "shutdown") -> List[ScoreResponse]:
        """Graceful drain to completion: flush in-flight micro-batches
        within the drain budget, refuse the remainder with typed
        SHUTTING_DOWN, record the drain outcome for stats/RunReport.
        Returns every response produced (flushed + refused)."""
        self.begin_drain(reason)
        budget = (self.config.drain_budget_s if drain_budget_s is None
                  else drain_budget_s)
        t0 = self.clock()
        out: List[ScoreResponse] = []
        flushed = 0
        while self.batcher.depth() and (self.clock() - t0) < budget:
            got = self.pump(flush=True)
            flushed += sum(1 for r in got if r.score is not None
                           or FallbackReason.SHUTTING_DOWN not in
                           {f.reason for f in r.fallbacks})
            out.extend(got)
        refused = 0
        for p in self.batcher.pop_all():  # budget exhausted
            refused += 1
            out.append(self._refuse(
                p.request, FallbackReason.SHUTTING_DOWN,
                detail=f"drain budget {budget:.3f}s exhausted"))
        seconds = self.clock() - t0
        self._drain_info = {"reason": self._drain_reason or reason,
                            "budget_s": budget, "seconds": seconds,
                            "flushed": flushed, "refused": refused}
        _metrics.gauge("serving.drain_seconds").set(seconds)
        if refused:
            _metrics.counter("serving.drain_refused").inc(refused)
        # stop two-tier transfer threads with the drain: a drained engine
        # must not keep background threads uploading to the device
        self.model.close_stores()
        if self._prior is not None:
            self._prior[0].close_stores()
        return out

    # -- synchronous convenience --------------------------------------------

    def serve(self, requests: Sequence[ScoreRequest]) -> List[ScoreResponse]:
        """Score a request sequence synchronously, preserving input order.
        Rejected requests still get (typed) responses."""
        # FIFO queue per uid: duplicate uids stay well-defined because
        # batches pop in submission order
        by_uid: Dict[str, List[ScoreResponse]] = {}
        for r in requests:
            rejected = self.submit(r)
            if rejected is not None:
                by_uid.setdefault(r.uid, []).append(rejected)
            while True:
                got = self.pump(flush=self.batcher.depth()
                                >= self.ladder.max_batch)
                if not got:
                    break
                for resp in got:
                    by_uid.setdefault(resp.uid, []).append(resp)
        while self.batcher.depth():
            for resp in self.pump(flush=True):
                by_uid.setdefault(resp.uid, []).append(resp)
        return [by_uid[r.uid].pop(0) for r in requests]

    def drain(self) -> List[ScoreResponse]:
        """Flush every queued request to completion (stream end)."""
        out: List[ScoreResponse] = []
        while self.batcher.depth():
            out.extend(self.pump(flush=True))
        return out

    # -- reporting -----------------------------------------------------------

    def swap_stats(self) -> dict:
        """The ``swap`` section: versions, attempt history (gate outcomes,
        shadow deviations), rollback count — RunReport satellite."""
        hist = list(self.swap_history)
        return {
            "version": self.model_version,
            "label": self.model_label,
            "attempts": sum(1 for h in hist
                            if h.get("outcome") != "rolled_back"),
            "published": sum(1 for h in hist
                             if h.get("outcome") == "published"),
            "rejected": sum(1 for h in hist
                            if h.get("outcome") == "rejected"),
            "rollbacks": sum(1 for h in hist
                             if h.get("outcome") == "rolled_back"),
            "probation_active": self._probation_until is not None,
            "history": hist,
        }

    def stats(self) -> dict:
        """The serving section for the RunReport: model shape,
        ladder, compile-phase accounting, and the latency quantiles."""
        snap = _metrics.snapshot()
        latencies = {}
        for key, h in snap["histograms"].items():
            if key.startswith("serving.latency_seconds{"):
                stage = key.split('stage="')[1].split('"')[0]
                latencies[stage] = {
                    k: h.get(k) for k in ("count", "sum", "p50", "p95", "p99")}
        counters = {k: v for k, v in snap["counters"].items()
                    if k.startswith("serving.")}
        out = {
            "model": self.model.describe(),
            "model_version": self.model_version,
            "model_label": self.model_label,
            "buckets": list(self.ladder.buckets),
            "modes": list(serving_modes(self.model)),
            "warmed": self._warmed,
            "warmup_seconds": self._warmup_seconds,
            "warmup_programs": self._warmup_programs,
            "compile_counts": compile_cache.compile_counts(),
            "queue_depth": self.batcher.depth(),
            "counters": counters,
            "latency_seconds": latencies,
            "slo": {"shed_queue_depth": self.config.slo.shed_queue_depth,
                    "reject_queue_depth": self.config.slo.reject_queue_depth},
            "deadline": {
                "default_timeout_s": self.config.deadline.default_timeout_s,
                "min_service_s": self.config.deadline.min_service_s,
                "score_headroom_s": self.config.deadline.score_headroom_s},
            "breaker": self.breaker.snapshot(),
            "draining": self._draining,
            "swap": self.swap_stats(),
        }
        cs = self.model.coeff_store_stats()
        if cs is not None:
            out["coeff_store"] = cs
        if self._drain_info is not None:
            out["drain"] = dict(self._drain_info)
        return out
