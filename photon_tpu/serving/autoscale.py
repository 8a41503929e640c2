"""Gauge-driven hot-shard autoscaling for the elastic serving fleet.

A ``HotShardAutoscaler`` closes the feedback loop PR 18 left open: it
polls the per-shard windowed response counters the router already
stamps (``fleet.shard.responses{shard=N}`` in `obs/timeseries`),
decides whether the fleet's load is skewed enough to act, and drives
the `serving/migrate.BucketMigrator` machinery:

* **split** — the hottest shard's share exceeds ``hot_factor`` × the
  mean: provision a fresh shard (empty per-coordinate cold stores, a
  manifest bump adding the shard entry, a warmed engine — warmed via
  jit-cache HITS, the scorer programs are shape-keyed so a same-shape
  shard engine compiles nothing new), then migrate the hot shard's
  top-load buckets onto it.
* **drain** — the coldest shard's share falls below ``cold_factor`` ×
  the mean: migrate its buckets to the least-loaded survivor, then
  decommission the shard (router removal + manifest bump).

Execution is two-phase on purpose: ``step()`` starts the work (shard
provisioning, bucket copy, double-read window open) and ``finish()``
completes it (reconcile, bitwise-parity cutover, decommission) — the
window in between is where live traffic flows through the double-read
comparison, which is the whole point. A deterministic replay
(``tests/test_elastic.py::test_elastic_lifecycle_under_replay``)
schedules ``step``/``finish`` as virtual-clock actions mid-burst.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional

import numpy as np

from photon_tpu.io.cold_store import write_cold_store
from photon_tpu.io.fleet_store import (
    FLEET_MANIFEST_SCHEMA_V2,
    read_fleet_manifest,
    shard_dir,
    shard_store_path,
    write_fleet_manifest,
)
from photon_tpu.obs import timeseries as _tsmod
from photon_tpu.serving.fleet import LocalShardClient, build_shard_engine
from photon_tpu.serving.migrate import BucketMigrator, MigrationError

__all__ = [
    "AutoscaleConfig",
    "HotShardAutoscaler",
    "decommission_shard",
    "provision_shard",
]


@dataclasses.dataclass(frozen=True)
class AutoscaleConfig:
    """Controller thresholds. Shares are sums of each shard's last
    ``lookback_windows`` response-counter windows."""

    #: split when the hottest shard's share > hot_factor * mean share
    hot_factor: float = 1.75
    #: drain when the coldest shard's share < cold_factor * mean share
    cold_factor: float = 0.25
    min_shards: int = 1
    max_shards: int = 8
    #: buckets migrated off the hot shard per split step
    buckets_per_step: int = 1
    #: response-counter windows summed per shard
    lookback_windows: int = 3
    #: below this fleet-wide total the controller holds (no signal)
    min_total: float = 1.0

    def __post_init__(self):
        if self.hot_factor <= 1.0:
            raise ValueError("hot_factor must be > 1")
        if not (0.0 <= self.cold_factor < 1.0):
            raise ValueError("cold_factor must be in [0, 1)")
        if self.min_shards < 1 or self.max_shards < self.min_shards:
            raise ValueError("need 1 <= min_shards <= max_shards")
        if self.buckets_per_step < 1:
            raise ValueError("buckets_per_step must be >= 1")


def provision_shard(fleet, shard_id: int, serving=None) -> dict:
    """Grow the fleet by one EMPTY shard: zero-row updatable cold
    stores for every routed coordinate, a manifest bump adding the
    shard entry (durable first — a kill after the bump leaves an idle
    shard, harmless), then a warmed engine registered with the router.
    Returns the new manifest document."""
    fleet_dir = fleet.fleet_dir
    if fleet_dir is None:
        raise MigrationError("fleet has no fleet_dir; cannot provision")
    doc = read_fleet_manifest(fleet_dir)
    if doc["schema"] != FLEET_MANIFEST_SCHEMA_V2:
        raise MigrationError(
            "provisioning needs the v2 virtual-bucket layout; this "
            f"fleet dir carries {doc['schema']!r} (rebuild with "
            "build_fleet_dir(num_buckets=...))")
    sid = int(shard_id)
    if any(sh["shard_id"] == sid for sh in doc["shards"]):
        raise MigrationError(f"shard {sid} already in manifest")
    os.makedirs(shard_dir(fleet_dir, sid), exist_ok=True)
    stores: Dict[str, dict] = {}
    for cid, meta in doc["coordinates"].items():
        k = int(meta["slot_width"])
        out = shard_store_path(fleet_dir, sid, cid)
        write_cold_store(out, cid, meta["random_effect_type"],
                         meta["feature_shard_id"],
                         np.zeros((0, k), np.float32),
                         np.zeros((0, k), np.int32), [],
                         updatable=True)
        stores[cid] = {"path": os.path.relpath(out, fleet_dir),
                       "entities": 0,
                       "bytes_at_split": int(os.path.getsize(out))}
    doc["shards"] = sorted(
        doc["shards"] + [{"shard_id": sid, "stores": stores}],
        key=lambda sh: sh["shard_id"])
    doc["num_shards"] = len(doc["shards"])
    doc["version"] = int(doc["version"]) + 1
    write_fleet_manifest(fleet_dir, doc)
    engine = build_shard_engine(
        fleet_dir, sid, serving or fleet.config.serving, manifest=doc,
        model_dir=getattr(fleet, "_model_dir", None), clock=fleet.clock)
    client = LocalShardClient(sid, engine)
    client.warmup()    # shape-keyed jit-cache hits: zero new compiles
    fleet.add_shard(client)
    fleet.manifest = doc
    return doc


def decommission_shard(fleet, shard_id: int) -> dict:
    """Shrink the fleet by one (already-drained) shard: router removal
    first (refuses typed while the shard still owns buckets), then the
    manifest bump dropping the entry."""
    fleet_dir = fleet.fleet_dir
    if fleet_dir is None:
        raise MigrationError("fleet has no fleet_dir; cannot decommission")
    sid = int(shard_id)
    fleet.remove_shard(sid)
    doc = read_fleet_manifest(fleet_dir)
    doc["shards"] = [sh for sh in doc["shards"]
                     if sh["shard_id"] != sid]
    if not doc["shards"]:
        raise MigrationError("refusing to decommission the last shard")
    doc["num_shards"] = len(doc["shards"])
    doc["version"] = int(doc["version"]) + 1
    write_fleet_manifest(fleet_dir, doc)
    fleet.manifest = doc
    return doc


class HotShardAutoscaler:
    """Two-phase feedback controller over the per-shard windowed
    gauges. ``step()`` makes one decision and starts it; ``finish()``
    completes the migrations it opened. At most one plan is in flight
    at a time (the controller never races its own cutovers)."""

    def __init__(self, fleet, config: Optional[AutoscaleConfig] = None,
                 registry=None, serving=None):
        self.fleet = fleet
        self.config = config or AutoscaleConfig()
        self.registry = registry or _tsmod.series
        self.serving = serving
        self._plan: Optional[dict] = None

    # --------------------------------------------------------- observe

    def shard_shares(self) -> Dict[int, float]:
        """Per-shard response counts summed over the last
        ``lookback_windows`` windows of
        ``fleet.shard.responses{shard=N}``."""
        snap = self.registry.snapshot()
        shares = {c.shard_id: 0.0 for c in self.fleet.clients}
        for key, s in snap.get("timeseries", {}).items():
            if not key.startswith("fleet.shard.responses{"):
                continue
            sh = s.get("labels", {}).get("shard")
            try:
                sid = int(sh)
            except (TypeError, ValueError):
                continue
            if sid not in shares:
                continue
            wins = s.get("windows", [])[-self.config.lookback_windows:]
            shares[sid] = float(sum(w["value"] for w in wins))
        return shares

    # ---------------------------------------------------------- decide

    def decide(self) -> Optional[dict]:
        """One control decision off the current gauges, or None (hold).
        Pure read — ``step`` executes it."""
        cfg = self.config
        fleet = self.fleet
        shares = self.shard_shares()
        if not shares:
            return None
        total = sum(shares.values())
        if total < cfg.min_total:
            return None
        mean = total / len(shares)
        hot = max(shares, key=lambda s: (shares[s], -s))
        cold = min(shares, key=lambda s: (shares[s], s))
        if (shares[hot] > cfg.hot_factor * mean
                and fleet.num_shards < cfg.max_shards
                and len(fleet.bucket_map.buckets_on(hot)) > 1):
            return {"action": "split", "shard": hot,
                    "share": shares[hot], "mean": mean}
        if (fleet.num_shards > cfg.min_shards
                and shares[cold] < cfg.cold_factor * mean):
            return {"action": "drain", "shard": cold,
                    "share": shares[cold], "mean": mean}
        return None

    # --------------------------------------------------------- execute

    def step(self, decision: Optional[dict] = None) -> Optional[dict]:
        """Execute the start half of one decision: provision/choose the
        destination, copy the chosen buckets, open their double-read
        windows. Returns the in-flight plan (None = held)."""
        if self._plan is not None:
            raise MigrationError(
                "previous autoscale step not finished; call finish()")
        decision = decision or self.decide()
        if decision is None:
            return None
        if decision["action"] == "split":
            plan = self._start_split(int(decision["shard"]))
        else:
            plan = self._start_drain(int(decision["shard"]))
        plan.update(share=decision.get("share"),
                    mean=decision.get("mean"))
        self._plan = plan
        return plan

    def _start_split(self, hot: int) -> dict:
        fleet = self.fleet
        new_id = max(c.shard_id for c in fleet.clients) + 1
        provision_shard(fleet, new_id, serving=self.serving)
        loads = dict(fleet.bucket_loads())
        owned = fleet.bucket_map.buckets_on(hot)
        # hottest buckets first; never take the LAST bucket off a shard
        ranked = sorted(owned, key=lambda b: (-loads.get(b, 0), b))
        take = ranked[:min(self.config.buckets_per_step,
                           len(ranked) - 1)]
        migrators: List[BucketMigrator] = []
        for b in take:
            m = BucketMigrator(fleet, b, new_id)
            m.copy()
            m.open_double_read()
            migrators.append(m)
        return {"action": "split", "shard": hot, "new_shard": new_id,
                "buckets": list(take), "migrators": migrators}

    def _start_drain(self, cold: int) -> dict:
        fleet = self.fleet
        shares = self.shard_shares()
        dst = min((s for s in shares if s != cold),
                  key=lambda s: (shares[s], s))
        owned = fleet.bucket_map.buckets_on(cold)
        migrators: List[BucketMigrator] = []
        for b in owned:
            m = BucketMigrator(fleet, b, dst)
            m.copy()
            m.open_double_read()
            migrators.append(m)
        return {"action": "drain", "shard": cold, "dst": dst,
                "buckets": list(owned), "migrators": migrators}

    def finish(self) -> Optional[dict]:
        """Complete the in-flight plan: reconcile + bitwise-parity
        cutover for every opened migration, then decommission on a
        drain. Returns the completed plan (None = nothing in flight).
        A poisoned double-read window raises typed and leaves the old
        map serving (callers abort the plan's migrators)."""
        plan, self._plan = self._plan, None
        if plan is None:
            return None
        results = []
        for m in plan["migrators"]:
            m.reconcile()
            results.append(m.cutover())
        if plan["action"] == "drain":
            decommission_shard(self.fleet, plan["shard"])
        plan["results"] = results
        return plan

    def abort(self) -> None:
        """Abort the in-flight plan: roll back every opened migration
        (bitwise restore) and close windows. The provisioned shard, if
        any, stays registered but idle."""
        plan, self._plan = self._plan, None
        if plan is None:
            return
        for m in plan["migrators"]:
            m.abort("autoscale abort")
