"""The nearline loop: poll -> delta-train -> row-publish -> checkpoint.

One :class:`NearlinePipeline` drives one serving engine.  Each round:

1. ``EventLogReader.poll`` pulls the new events past the watermark
   (deduplicated, re-ordered, torn tails left for the writer to finish).
2. ``DeltaTrainer.train`` re-solves ONLY the entities those events
   touch, warm-started from the live coefficients.
3. ``DeltaPublisher.publish`` pushes the changed rows into the live
   serving tables behind its gate ladder, landing a durable versioned
   manifest (which carries the watermark).
4. ``save_checkpoint`` advances the durable offset watermark.

The manifest-before-checkpoint order is the exactly-once handshake: a
crash between 3 and 4 leaves ``manifest.version > ckpt.published_version``
and recovery adopts the manifest's watermark instead of re-publishing the
same delta (re-running step 3 would double-apply nothing — publishes are
idempotent per row — but would re-consume capacity gates and re-trip
probation; adopting the watermark is both cheaper and exact).

Freshness is the pipeline's north-star metric: the histogram
``nearline.freshness_seconds`` measures event timestamp -> the moment the
entity's new row is scoreable (the publish commit), per touched entity.

Run it inline round by round (``run_round``, what the tests do), or as a long-lived loop (``run``) with the shared shutdown hook
providing graceful drain: finish the in-flight round, land the final
checkpoint, exit.  ``cli/nearline`` wraps ``run`` for operators.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import time
from typing import Any, Dict, Optional

from photon_tpu.nearline.delta_trainer import DeltaTrainConfig, DeltaTrainer
from photon_tpu.nearline.events import (
    EventLogReader,
    load_checkpoint,
    save_checkpoint,
)
from photon_tpu.nearline.publisher import DeltaPublisher, NearlinePublishConfig
from photon_tpu.obs.metrics import registry as _metrics
from photon_tpu.resilience import shutdown as _shutdown

_FRESHNESS_BUCKETS = tuple(0.01 * 2.0 ** i for i in range(28))
_ROUND_BUCKETS = tuple(1e-3 * 1.8 ** i for i in range(30))


@dataclasses.dataclass(frozen=True)
class NearlineConfig:
    """Loop cadence and sub-stage configuration."""

    #: idle sleep between polls that found nothing
    poll_interval_s: float = 1.0
    #: stop after this many rounds; 0 = run until shutdown
    max_rounds: int = 0
    #: cap on events consumed per round (None = drain the log)
    max_events_per_round: Optional[int] = None
    #: durable watermark checkpoint; None derives <state_dir>/checkpoint.json
    checkpoint_path: Optional[str] = None
    #: manifest/checkpoint directory; None derives <model_dir>/nearline
    state_dir: Optional[str] = None
    train: DeltaTrainConfig = dataclasses.field(
        default_factory=DeltaTrainConfig)
    publish: NearlinePublishConfig = dataclasses.field(
        default_factory=NearlinePublishConfig)

    def __post_init__(self) -> None:
        if self.poll_interval_s < 0:
            raise ValueError("poll_interval_s must be >= 0")
        if self.max_rounds < 0:
            raise ValueError("max_rounds must be >= 0")
        if (self.max_events_per_round is not None
                and self.max_events_per_round <= 0):
            raise ValueError("max_events_per_round must be positive")


class NearlinePipeline:
    """Poll -> train -> publish -> checkpoint against one engine."""

    def __init__(self, engine, log_dir: str,
                 model_dir: Optional[str] = None,
                 config: Optional[NearlineConfig] = None):
        self.engine = engine
        self.log_dir = log_dir
        self.model_dir = model_dir
        self.config = config or NearlineConfig()
        state_dir = self.config.state_dir
        if state_dir is None and model_dir is not None:
            state_dir = os.path.join(model_dir, "nearline")
        self.state_dir = state_dir
        self.checkpoint_path = self.config.checkpoint_path
        if self.checkpoint_path is None and state_dir is not None:
            self.checkpoint_path = os.path.join(state_dir, "checkpoint.json")
        self.reader = EventLogReader(log_dir)
        self.trainer = DeltaTrainer(engine, model_dir, self.config.train)
        self.publisher = DeltaPublisher(engine, model_dir, state_dir,
                                        self.config.publish)
        self.rounds = 0
        self.recovered = False
        self.totals: Dict[str, int] = {
            "events": 0, "rows_updated": 0, "rows_appended": 0,
            "publishes": 0, "rejected": 0, "rollbacks": 0,
            "fixed_refreshes": 0}
        self.last_round: Dict[str, Any] = {}
        self._recover()
        set_active(self)

    # ---------------------------------------------------------- recovery

    def _recover(self) -> None:
        """Adopt the durable watermark; reconcile a publish that landed
        its manifest but died before the checkpoint advanced."""
        published_version = 0
        ckpt = (load_checkpoint(self.checkpoint_path)
                if self.checkpoint_path else None)
        if ckpt is not None:
            self.reader.restore(ckpt["state"])
            published_version = int(ckpt.get("published_version", 0))
        manifest = self.publisher.last_manifest
        if manifest is not None and \
                int(manifest["version"]) > published_version:
            # the exactly-once seam: rows are already live (and durable
            # in the cold tier) — adopt the manifest watermark, do NOT
            # re-train/re-publish the same events
            if manifest.get("watermark"):
                self.reader.restore(manifest["watermark"])
            self._checkpoint()
            self.recovered = True
            _metrics.counter("nearline.pipeline.recovered_publishes").inc()

    def _checkpoint(self) -> None:
        if self.checkpoint_path is None:
            return
        os.makedirs(os.path.dirname(self.checkpoint_path) or ".",
                    exist_ok=True)
        save_checkpoint(self.checkpoint_path, self.reader.state(),
                        published_version=self.publisher.version)

    # ------------------------------------------------------------ rounds

    def run_round(self) -> Dict[str, Any]:
        """One poll -> train -> publish -> checkpoint round (no sleep)."""
        t0 = time.perf_counter()
        self.publisher.check_probation()
        events = self.reader.poll(self.config.max_events_per_round)
        summary: Dict[str, Any] = {"round": self.rounds,
                                   "events": len(events)}
        if not events:
            self.last_round = summary
            return summary
        self.rounds += 1
        summary["round"] = self.rounds
        self.totals["events"] += len(events)

        delta = self.trainer.train(events)
        summary["entities"] = delta.num_rows
        summary["train_stats"] = dict(delta.stats)

        if delta.num_rows:
            label = f"nearline-r{self.rounds:05d}"
            res = self.publisher.publish(delta, label,
                                         watermark=self.reader.state())
            summary["publish"] = res.to_json()
            if res.accepted:
                self.totals["publishes"] += 1
                self.totals["rows_updated"] += res.rows_updated
                self.totals["rows_appended"] += res.rows_appended
                # event -> scoreable: the commit is the moment the new
                # rows gather into scores
                now = time.time()
                hist = _metrics.histogram("nearline.freshness_seconds",
                                          buckets=_FRESHNESS_BUCKETS)
                for cd in delta.coordinates.values():
                    for ts in cd.event_ts.values():
                        hist.observe(max(now - float(ts), 0.0))
            else:
                self.totals["rejected"] += 1
                if res.rolled_back:
                    self.totals["rollbacks"] += 1

        swap = self.trainer.maybe_refresh_fixed()
        if swap is not None:
            summary["fixed_refresh"] = swap.to_json()
            if swap.accepted:
                self.totals["fixed_refreshes"] += 1

        # watermark advances only after the publish (and its manifest)
        # landed — crash anywhere above replays this round's events
        self._checkpoint()
        dt = time.perf_counter() - t0
        summary["seconds"] = dt
        _metrics.histogram("nearline.round_seconds",
                           buckets=_ROUND_BUCKETS).observe(dt)
        _metrics.gauge("nearline.rounds").set(float(self.rounds))
        self.last_round = summary
        return summary

    def run(self) -> Dict[str, Any]:
        """Loop until shutdown (or ``max_rounds``); graceful drain lands
        a final checkpoint before returning the run summary."""
        cfg = self.config
        while not _shutdown.requested():
            if cfg.max_rounds and self.rounds >= cfg.max_rounds:
                break
            got = self.run_round()
            if got["events"] == 0:
                # idle: nap in small slices so shutdown stays responsive
                deadline = time.monotonic() + cfg.poll_interval_s
                while (time.monotonic() < deadline
                       and not _shutdown.requested()):
                    time.sleep(min(0.05, cfg.poll_interval_s or 0.05))
        self._checkpoint()
        return self.describe()

    # --------------------------------------------------------------- obs

    def describe(self) -> Dict[str, Any]:
        return {
            "log_dir": self.log_dir,
            "rounds": self.rounds,
            "recovered": self.recovered,
            "watermark": self.reader.max_seq,
            "published_version": self.publisher.version,
            "totals": dict(self.totals),
            "reader_stats": dict(self.reader.stats),
            "last_round": dict(self.last_round),
        }


# -- RunReport integration ---------------------------------------------------

_ACTIVE: Optional[NearlinePipeline] = None


def set_active(pipeline: Optional[NearlinePipeline]) -> None:
    """Register the pipeline the obs RunReport should describe."""
    global _ACTIVE
    _ACTIVE = pipeline


def report_section() -> Optional[Dict[str, Any]]:
    """The ``nearline`` RunReport section (None when no pipeline ran)."""
    if _ACTIVE is None:
        return None
    return _ACTIVE.describe()
