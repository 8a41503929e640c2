"""Multi-tenant serving: many GAME models behind one compiled ladder.

Photon deployments are inherently multi-model — per-market, per-surface,
per-experiment GLMix variants served side by side. The scorer refactor
(serving/scorer.py) made the compiled (mode × bucket) programs
shape-keyed, so hosting N same-shape tenants costs ONE warmup ladder:
tenant #2..N warm at near-zero compile cost.

``MultiTenantEngine`` hosts one ``ServingEngine`` per tenant under a
single shared bucket-ladder configuration and routes by the request's
``tenant`` field (the JSONL protocol's ``"tenant"`` key). Per-tenant
engines are the isolation boundary, deliberately: each tenant keeps its
OWN admission queue, SLO depths, circuit breaker, shadow capture, and
swap/probation state, so one tenant's breaker trip, SLO shed, or noisy
hot loop can never degrade a neighbor's scores — per-tenant scores stay
bitwise-equal to a dedicated single-tenant engine (the isolation test's
contract). What is shared is exactly what is safe to share: the
compiled programs (shape-keyed, parameters are arguments) and the
ladder geometry. Mixed-tenant micro-batches are impossible by
construction — a batch's gather tables belong to one model — so
"one MicroBatcher ladder" means one ladder shape with per-tenant
queues, not one queue.

On top of routing:

* **Admission budgets** — an optional per-tenant cap on queued depth
  (``admission_budget``), checked before the tenant's own engine sees
  the request: a flooding tenant gets typed TENANT_BUDGET_EXCEEDED
  refusals once ITS queue is full, bounding the device work it can put
  in front of its neighbors' batches (the ``tenant_hot_loop`` chaos
  test measures exactly this). The engine's own SLO shed/reject depths
  still apply underneath.
* **Canary / A-B splitting** — ``start_canary`` runs serving/swap.py's
  FULL gate ladder (finite, staging, shadow, int8, zero-compile) via
  ``swap_staged(..., publish=False)`` and, on pass, hosts the candidate
  in a canary arm that receives a deterministic hash-based fraction of
  the tenant's traffic: ``crc32("tenant:uid") % 10000 < fraction·10000``
  — stable per uid across processes, no RNG. Responses carry typed
  per-arm attribution (``arm="live"|"canary"``); ``promote_canary``
  publishes the canary model into the live engine (normal swap
  semantics: prior retained, probation armed), ``abort_canary`` drops it.
* **Per-tenant observability** — engines get ``tenant=...`` obs labels
  (warmup gauges become ``serving.warmup_seconds{tenant=...}`` etc. and
  survive ``obs.merge_snapshots`` as distinct keys), and routing emits
  ``serving.tenant_requests/responses/refused`` counters.
"""

from __future__ import annotations

import threading
import zlib
from typing import Dict, List, Optional, Sequence

from photon_tpu.obs.metrics import registry as _metrics
from photon_tpu.resilience import chaos as _chaos
from photon_tpu.serving.engine import ServingEngine
from photon_tpu.serving.model_state import DeviceResidentModel
from photon_tpu.serving.types import (Fallback, FallbackReason,
                                      ScoreRequest, ScoreResponse,
                                      ServingConfig)
from photon_tpu.utils import compile_cache

#: flood requests injected by the tenant_hot_loop chaos hook carry this
#: uid prefix; their responses are dropped (counted), never emitted
_FLOOD_PREFIX = "__chaos_flood__"

#: the two traffic arms a tenant can serve from
ARMS = ("live", "canary")


class TenantState:
    """One hosted tenant: its live engine, optional canary arm, and
    routing counters. Internal to MultiTenantEngine."""

    def __init__(self, name: str, engine: ServingEngine,
                 admission_budget: Optional[int]):
        self.name = name
        self.engine = engine
        self.admission_budget = admission_budget
        self.canary_engine: Optional[ServingEngine] = None
        self.canary_label: Optional[str] = None
        self.canary_fraction: float = 0.0
        self.split_counts = {"live": 0, "canary": 0}

    def depth(self) -> int:
        d = self.engine.batcher.depth()
        if self.canary_engine is not None:
            d += self.canary_engine.batcher.depth()
        return d


class MultiTenantEngine:
    """N tenants, one compiled bucket ladder, per-tenant isolation."""

    def __init__(self, config: Optional[ServingConfig] = None,
                 clock=None, default_tenant: Optional[str] = None):
        #: the shared ladder geometry; per-tenant configs may override
        #: SLO/breaker/swap knobs but MUST keep the same bucket ladder
        #: (max_batch / min_bucket / feature_pad) — those are compiled-
        #: program shapes, and one ladder is the point
        self.config = config or ServingConfig()
        self._clock = clock
        self.tenants: Dict[str, TenantState] = {}
        self.default_tenant = default_tenant
        self._lock = threading.Lock()

    # -- tenant lifecycle ----------------------------------------------------

    def _check_ladder(self, cfg: ServingConfig) -> None:
        host = self.config
        if (cfg.max_batch, cfg.min_bucket, cfg.feature_pad) != \
                (host.max_batch, host.min_bucket, host.feature_pad):
            raise ValueError(
                "tenant config must share the host bucket ladder "
                f"(max_batch={host.max_batch}, min_bucket={host.min_bucket}, "
                f"feature_pad={host.feature_pad}) — those are compiled-"
                "program shapes")

    def add_tenant(self, name: str, model: DeviceResidentModel,
                   config: Optional[ServingConfig] = None,
                   admission_budget: Optional[int] = None,
                   warm: bool = True) -> dict:
        """Host ``model`` as tenant ``name`` (its engine is built with
        ``tenant=name`` obs labels). The first tenant becomes the default
        route for tenant-less requests unless a default was configured.
        With ``warm=True`` the tenant's ladder is warmed immediately —
        a jitcache hit per program when a same-shape tenant (or a loaded
        program bundle) already populated the shape's programs."""
        cfg = config or self.config
        self._check_ladder(cfg)
        with self._lock:
            if name in self.tenants:
                raise ValueError(f"tenant {name!r} already hosted")
            engine = ServingEngine(model, config=cfg, clock=self._clock,
                                   obs_labels={"tenant": name})
            self.tenants[name] = TenantState(name, engine, admission_budget)
            if self.default_tenant is None:
                self.default_tenant = name
        _metrics.gauge("serving.tenants").set(len(self.tenants))
        info = engine.warmup() if warm else {}
        return {"tenant": name, "warmup": info}

    def add_tenant_from_dir(self, name: str, model_dir: str,
                            config: Optional[ServingConfig] = None,
                            admission_budget: Optional[int] = None,
                            mesh=None, warm: bool = True) -> dict:
        from photon_tpu.io.model_io import load_for_serving

        cfg = config or self.config
        serving_model = load_for_serving(model_dir)
        model = DeviceResidentModel(
            serving_model, mesh=mesh, feature_pad=cfg.feature_pad,
            coeff_store=cfg.coeff_store, append_reserve=cfg.append_reserve,
            int8=cfg.int8_serving)
        return self.add_tenant(name, model, config=cfg,
                               admission_budget=admission_budget, warm=warm)

    def remove_tenant(self, name: str, drain_budget_s: float = 0.0) -> None:
        with self._lock:
            st = self.tenants.pop(name, None)
            if st is None:
                raise KeyError(f"tenant {name!r} not hosted")
            if self.default_tenant == name:
                self.default_tenant = next(iter(self.tenants), None)
        if st.canary_engine is not None:
            st.canary_engine.model.close_stores()
        st.engine.shutdown(drain_budget_s=drain_budget_s,
                           reason=f"tenant {name} removed")
        _metrics.gauge("serving.tenants").set(len(self.tenants))

    def _get(self, name: str) -> TenantState:
        st = self.tenants.get(name)
        if st is None:
            raise KeyError(f"tenant {name!r} not hosted")
        return st

    # -- warmup & program bundles -------------------------------------------

    def warmup(self) -> dict:
        """Warm every tenant's ladder. Same-shape tenants after the first
        are pure jitcache hits — the aggregate compile_counts show one
        shape's worth of builds, not N."""
        infos = {}
        for name, st in list(self.tenants.items()):
            infos[name] = st.engine.warmup()
        return {"tenants": infos,
                "programs": sum(i.get("programs", 0) for i in infos.values()),
                "compile_counts": compile_cache.compile_counts()}

    def load_program_bundles(self, base_dir: str) -> dict:
        """Seed the jitcache from AOT bundles under ``base_dir`` (one
        subdirectory per distinct shape signature) so the subsequent
        ``warmup`` performs zero traces. Refusals fall back silently —
        the tenant just warms by tracing."""
        from photon_tpu.serving import programs as _programs

        out = {}
        done = {}
        buckets = _ladder_buckets(self.config)
        for name, st in self.tenants.items():
            d = _programs.bundle_dir_for(base_dir, st.engine.model)
            if d in done:  # same shape signature: already seeded
                out[name] = {**done[d], "shared_with": done[d]["tenant"]}
                continue
            got = _programs.load_program_bundle(st.engine.model, buckets, d)
            done[d] = {**got, "tenant": name}
            out[name] = got
        return out

    def export_program_bundles(self, base_dir: str) -> dict:
        """Export each distinct shape signature's warmed ladder (one
        bundle subdirectory per signature — same-shape tenants share)."""
        from photon_tpu.serving import programs as _programs

        out = {}
        done = set()
        buckets = _ladder_buckets(self.config)
        for name, st in self.tenants.items():
            d = _programs.bundle_dir_for(base_dir, st.engine.model)
            if d in done:
                continue
            done.add(d)
            out[name] = _programs.export_program_bundle(
                st.engine.model, buckets, d)
        return out

    # -- routing -------------------------------------------------------------

    def _refuse(self, request: ScoreRequest, tenant: str,
                reason: FallbackReason, detail: str) -> ScoreResponse:
        _metrics.counter("serving.tenant_refused", tenant=tenant,
                         reason=reason.value).inc()
        return ScoreResponse(
            request.uid, score=None, degraded=True,
            fallbacks=(Fallback(reason, detail=detail),),
            tenant=tenant if tenant != "?" else None)

    @staticmethod
    def canary_pick(tenant: str, uid: str, fraction: float) -> bool:
        """Deterministic traffic split: stable per (tenant, uid), no RNG,
        identical across processes and restarts — crc32 of "tenant:uid"
        against a 10000-slot wheel."""
        if fraction <= 0.0:
            return False
        return (zlib.crc32(f"{tenant}:{uid}".encode()) % 10000
                < int(round(fraction * 10000)))

    def submit(self, request: ScoreRequest) -> Optional[ScoreResponse]:
        """Route one request to its tenant's live or canary arm. Returns
        an immediate typed refusal (unknown tenant, tenant budget, or
        the engine's own admission refusals) or None (queued; response
        arrives from ``pump``)."""
        name = request.tenant or self.default_tenant
        if name is None or name not in self.tenants:
            return self._refuse(
                request, name or "?", FallbackReason.UNKNOWN_TENANT,
                f"tenant {name!r} not hosted")
        st = self.tenants[name]
        _metrics.counter("serving.tenant_requests", tenant=name).inc()

        # noisy-neighbor chaos: this tenant's submit fans out into flood
        # duplicates that go through the SAME budget gate — the flood
        # lands on this tenant's queue or gets refused here, never on a
        # neighbor's queue
        for k in range(_chaos.tenant_flood_burst(name)):
            flood = ScoreRequest(
                f"{_FLOOD_PREFIX}{k}-{request.uid}", request.features,
                request.entity_ids, request.offset, request.timeout_s,
                tenant=name)
            _metrics.counter("serving.tenant_flood_injected",
                             tenant=name).inc()
            self._submit_to(st, flood)  # refusals/responses are dropped

        return self._submit_to(st, request)

    def _submit_to(self, st: TenantState,
                   request: ScoreRequest) -> Optional[ScoreResponse]:
        flood = request.uid.startswith(_FLOOD_PREFIX)
        if st.admission_budget is not None \
                and st.depth() >= st.admission_budget:
            resp = self._refuse(request, st.name,
                                FallbackReason.TENANT_BUDGET_EXCEEDED,
                                f"queued depth >= budget "
                                f"{st.admission_budget}")
            return None if flood else resp
        arm = "live"
        engine = st.engine
        if st.canary_engine is not None and not flood and \
                self.canary_pick(st.name, request.uid, st.canary_fraction):
            arm = "canary"
            engine = st.canary_engine
        if not flood:
            st.split_counts[arm] += 1
        rejected = engine.submit(request)
        if rejected is not None:
            if flood:
                _metrics.counter("serving.tenant_flood_dropped",
                                 tenant=st.name).inc()
                return None
            rejected.tenant = st.name
            rejected.arm = arm
            return rejected
        return None

    def pump(self, flush: bool = False) -> List[ScoreResponse]:
        """Pump every tenant's arms once; responses come back tagged with
        typed (tenant, arm) attribution. Chaos flood responses are
        dropped here (counted), so callers only ever see real traffic."""
        out: List[ScoreResponse] = []
        for name, st in list(self.tenants.items()):
            arms = [("live", st.engine)]
            if st.canary_engine is not None:
                arms.append(("canary", st.canary_engine))
            for arm, engine in arms:
                for resp in engine.pump(flush=flush):
                    if resp.uid.startswith(_FLOOD_PREFIX):
                        _metrics.counter("serving.tenant_flood_dropped",
                                         tenant=name).inc()
                        continue
                    resp.tenant = name
                    resp.arm = arm
                    _metrics.counter("serving.tenant_responses",
                                     tenant=name, arm=arm).inc()
                    out.append(resp)
        return out

    def serve(self, requests: Sequence[ScoreRequest]) -> List[ScoreResponse]:
        """Synchronous convenience mirroring ``ServingEngine.serve``:
        responses in request order, every degradation typed."""
        by_uid: Dict[str, List[ScoreResponse]] = {}
        for r in requests:
            rejected = self.submit(r)
            if rejected is not None:
                by_uid.setdefault(r.uid, []).append(rejected)
            for resp in self.pump(flush=any(
                    st.depth() >= self.config.max_batch
                    for st in self.tenants.values())):
                by_uid.setdefault(resp.uid, []).append(resp)
        while any(st.depth() for st in self.tenants.values()):
            got = self.pump(flush=True)
            if not got:
                break
            for resp in got:
                by_uid.setdefault(resp.uid, []).append(resp)
        return [by_uid[r.uid].pop(0) for r in requests]

    # -- canary / A-B --------------------------------------------------------

    def start_canary(self, tenant: str, serving_model, label: str,
                     fraction: float, mesh=None):
        """Gate-validate a candidate for ``tenant`` (the FULL swap
        ladder, publish withheld) and, on pass, open a canary arm that
        receives ``fraction`` of the tenant's traffic. Returns the
        SwapResult; ``accepted=False`` means no canary was opened and
        the reason names the failing gate."""
        from photon_tpu.serving.swap import swap_staged

        if not 0.0 < fraction <= 1.0:
            raise ValueError("canary fraction must be in (0, 1]")
        st = self._get(tenant)
        if st.canary_engine is not None:
            raise RuntimeError(f"tenant {tenant!r} already has a canary "
                               f"({st.canary_label!r}); promote or abort it")
        result = swap_staged(st.engine, serving_model, label, mesh=mesh,
                             publish=False)
        if not result.accepted:
            return result
        canary = ServingEngine(result.staged_model, config=st.engine.config,
                               clock=self._clock,
                               obs_labels={"tenant": tenant, "arm": "canary"})
        canary.warmup()  # programs already compiled: pure jitcache hits
        st.canary_engine = canary
        st.canary_label = label
        st.canary_fraction = float(fraction)
        st.split_counts = {"live": 0, "canary": 0}
        _metrics.counter("serving.canary_started", tenant=tenant).inc()
        return result

    def promote_canary(self, tenant: str) -> dict:
        """Publish the canary model as the tenant's live model (normal
        swap semantics: prior retained for rollback, probation armed)
        and close the canary arm."""
        st = self._get(tenant)
        if st.canary_engine is None:
            raise RuntimeError(f"tenant {tenant!r} has no canary")
        # flush whatever the canary arm still has queued before its
        # engine wrapper is discarded (the model itself lives on)
        st.canary_engine.drain()
        published = st.engine.publish_model(st.canary_engine.model,
                                            st.canary_label or "canary")
        splits = dict(st.split_counts)
        st.canary_engine = None
        st.canary_label = None
        st.canary_fraction = 0.0
        _metrics.counter("serving.canary_promoted", tenant=tenant).inc()
        return {**published, "splits": splits}

    def abort_canary(self, tenant: str) -> dict:
        """Drop the canary arm; its model's stores are closed. The live
        model never changed, so there is nothing to roll back."""
        st = self._get(tenant)
        if st.canary_engine is None:
            raise RuntimeError(f"tenant {tenant!r} has no canary")
        st.canary_engine.drain()
        st.canary_engine.model.close_stores()
        splits = dict(st.split_counts)
        label = st.canary_label
        st.canary_engine = None
        st.canary_label = None
        st.canary_fraction = 0.0
        _metrics.counter("serving.canary_aborted", tenant=tenant).inc()
        return {"label": label, "splits": splits}

    # -- lifecycle / stats ---------------------------------------------------

    def begin_drain(self, reason: str = "drain requested") -> None:
        for st in self.tenants.values():
            st.engine.begin_drain(reason)
            if st.canary_engine is not None:
                st.canary_engine.begin_drain(reason)

    @property
    def draining(self) -> bool:
        return any(st.engine.draining for st in self.tenants.values())

    def drain(self) -> List[ScoreResponse]:
        """Flush every tenant's queued requests to completion (stream
        end) — tagged like ``pump`` output."""
        out: List[ScoreResponse] = []
        while any(st.depth() for st in self.tenants.values()):
            got = self.pump(flush=True)
            if not got:
                break
            out.extend(got)
        return out

    def shutdown(self, drain_budget_s: Optional[float] = None,
                 reason: str = "shutdown") -> List[ScoreResponse]:
        """Drain every tenant within the budget; mirrors
        ``ServingEngine.shutdown`` (flat tagged response list) so the CLI
        driver treats both engine kinds identically."""
        out: List[ScoreResponse] = []
        for name, st in list(self.tenants.items()):
            if st.canary_engine is not None:
                for resp in st.canary_engine.shutdown(drain_budget_s=0.0,
                                                      reason=reason):
                    if resp.uid.startswith(_FLOOD_PREFIX):
                        continue
                    resp.tenant = name
                    resp.arm = "canary"
                    out.append(resp)
                st.canary_engine.model.close_stores()
                st.canary_engine = None
            for resp in st.engine.shutdown(drain_budget_s=drain_budget_s,
                                           reason=reason):
                if resp.uid.startswith(_FLOOD_PREFIX):
                    continue
                resp.tenant = name
                resp.arm = "live"
                out.append(resp)
        return out

    def stats(self) -> dict:
        out = {"tenants": {}, "default_tenant": self.default_tenant}
        for name, st in self.tenants.items():
            entry = {"live": st.engine.stats(),
                     "admission_budget": st.admission_budget,
                     "splits": dict(st.split_counts)}
            if st.canary_engine is not None:
                entry["canary"] = {"label": st.canary_label,
                                   "fraction": st.canary_fraction,
                                   "stats": st.canary_engine.stats()}
            out["tenants"][name] = entry
        return out


def _ladder_buckets(config: ServingConfig):
    from photon_tpu.serving.batching import BucketLadder

    return BucketLadder(config.max_batch, config.min_bucket).buckets
