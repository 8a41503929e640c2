"""Incremental per-entity retraining over the dirty-entity set.

A nearline round only touches the entities that actually received new
events.  For each random-effect coordinate the trainer builds a *tiny*
GAME dataset over just those events, warm-starts each entity's solve
from the live model's coefficients (the cold store when one backs the
coordinate, the resident table otherwise), and runs the exact per-entity
solve program offline training uses (``RandomEffectCoordinate.
update_model_blocked``: the blocked sweep's per-bucket program at one lane —
size-bucketed, jitted, warm-started, failed entities keep their warm
start).  The output is a per-coordinate set of
*candidate rows* — ``{entity_id: (coef_row, proj_row)}`` in the delta
dataset's projected space — which the publisher normalizes into the
serving layout and pushes behind its gate ladder.

Residualization follows GAME score algebra: each event's solve offset is
its logged offset plus the host-computed margins of every *other*
coordinate (fixed thetas and other coordinates' current entity rows), so
the per-entity solve sees the same residual it would in a full
coordinate-descent sweep over that data.

Fixed effects change on a much slower cadence and their thetas are
closed over by the compiled scorers, so a fixed refresh cannot be a
row-level publish — ``maybe_refresh_fixed`` re-fits the fixed coordinate
on the accumulated event buffer (warm-started from the live theta) and
routes the result through the full validated swap (``serving/swap.py``).
Two-tier coordinates survive the swap with their nearline deltas intact
because the publisher keeps the on-disk cold stores current; a
full-resident coordinate re-stages whatever ``model_dir`` holds, so pair
fixed refresh with two-tier serving when nearline deltas must persist.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from photon_tpu.obs.metrics import registry as _metrics
from photon_tpu.optim.problem import GLMOptimizationConfiguration


@dataclasses.dataclass(frozen=True)
class DeltaTrainConfig:
    """Knobs for the per-round delta solves.

    ``max_entity_buckets`` is deliberately tiny: a delta round touches
    few entities with few samples each, and every distinct bucket shape
    is an XLA compile.  ``fixed_refresh_every`` = 0 disables the fixed
    refresh; N > 0 refreshes every N rounds via a full validated swap.
    """

    max_entity_buckets: int = 4
    fixed_refresh_every: int = 0
    fixed_buffer: int = 8192           # events retained for fixed refresh
    glm: GLMOptimizationConfiguration = dataclasses.field(
        default_factory=GLMOptimizationConfiguration)


@dataclasses.dataclass
class CoordinateDelta:
    """Candidate rows for one random-effect coordinate."""

    coordinate_id: str
    random_effect_type: str
    feature_shard_id: str
    # entity_id -> (coef_row [K_ds] f32, proj_row [K_ds] i32) in the
    # delta dataset's projected space (ascending global cols, -1 pad)
    rows: Dict[str, Tuple[np.ndarray, np.ndarray]]
    event_ts: Dict[str, float]         # entity_id -> newest event ts
    num_events: int = 0
    # entity_id -> posterior-variance row [K_ds] f32 aligned with
    # ``rows`` (same projected space, same slot order).  Populated only
    # when the serving coordinate carries variances (Thompson models) —
    # a delta-trained mean must republish its uncertainty in the SAME
    # round or the scorer would explore with stale noise.
    var_rows: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class DeltaTrainResult:
    coordinates: Dict[str, CoordinateDelta]
    num_events: int
    stats: Dict[str, int]

    @property
    def num_rows(self) -> int:
        return sum(len(c.rows) for c in self.coordinates.values())


def _parse_features(event: Dict[str, Any], sid: str, imap,
                    stats: Dict[str, int]) -> Tuple[np.ndarray, np.ndarray]:
    """(global cols int64, values f64) for one event on one shard,
    unknown (name, term) pairs dropped."""
    feats = (event.get("features") or {}).get(sid) or ()
    cols = np.fromiter((imap.index_of(f[0], f[1]) for f in feats),
                       np.int64, count=len(feats))
    vals = np.fromiter((float(f[2]) for f in feats), np.float64,
                       count=len(feats))
    keep = cols >= 0
    dropped = int(len(cols) - keep.sum())
    if dropped:
        stats["unknown_features"] += dropped
    return cols[keep], vals[keep]


def current_entity_row(rs, entity_id: str,
                       shard_dim: int) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """The LIVE (coef_row, proj_row) of ``entity_id`` in serving layout,
    host-side.  Two-tier coordinates read the authoritative cold tier
    (the hot set is a cache of it); full-resident coordinates gather the
    device row and reconstruct its projection from the load-time sorted
    (entity * D + col) -> slot table.  None = unknown entity."""
    if rs.store is not None:
        cold = rs.store.cold
        r = cold.entity_row(entity_id)
        if r is None:
            return None
        return (np.array(cold.coef[r], np.float32),
                np.array(cold.proj[r], np.int32))
    e = rs.entity_rows.get(entity_id)
    if e is None:
        return None
    coef = np.asarray(rs.coef[e], np.float32)
    D = max(int(shard_dim), 1)
    lo = int(np.searchsorted(rs.pkeys_sorted, e * D))
    hi = int(np.searchsorted(rs.pkeys_sorted, (e + 1) * D))
    proj = np.full(rs.slot_width, -1, np.int32)
    proj[rs.pslots_sorted[lo:hi]] = (rs.pkeys_sorted[lo:hi] - e * D).astype(
        np.int32)
    return coef, proj


def _row_margin(cols: np.ndarray, vals: np.ndarray,
                coef_row: np.ndarray, proj_row: np.ndarray) -> float:
    """Host replay of one entity-row margin: sum of vals over the
    features its projection covers."""
    if not len(cols):
        return 0.0
    pvalid = proj_row >= 0
    pcols = proj_row[pvalid].astype(np.int64)
    pcoef = coef_row[pvalid].astype(np.float64)
    rank = np.searchsorted(pcols, cols)
    rank = np.minimum(rank, max(len(pcols) - 1, 0))
    if not len(pcols):
        return 0.0
    hit = pcols[rank] == cols
    return float(np.dot(pcoef[rank[hit]], vals[hit]))


class DeltaTrainer:
    """Builds candidate rows for the publisher from a batch of events."""

    def __init__(self, engine, model_dir: Optional[str] = None,
                 config: Optional[DeltaTrainConfig] = None):
        self.engine = engine
        self.model_dir = model_dir
        self.config = config or DeltaTrainConfig()
        self._rounds = 0
        self._fixed_events: List[Dict[str, Any]] = []

    # ------------------------------------------------------------ helpers

    def _cold_for(self, rs):
        """The ColdStore backing a coordinate, if any (two-tier store's
        cold tier, else the model_dir cold-store file)."""
        if rs.store is not None:
            return rs.store.cold
        if self.model_dir is not None:
            import os

            from photon_tpu.io.cold_store import ColdStore, cold_store_path

            p = cold_store_path(self.model_dir, rs.coordinate_id)
            if os.path.exists(p):
                return ColdStore(p)
        return None

    def _fixed_margin(self, model, ev: Dict[str, Any],
                      thetas: Dict[str, np.ndarray],
                      stats: Dict[str, int]) -> float:
        m = 0.0
        for fs in model.fixed:
            cols, vals = _parse_features(ev, fs.feature_shard_id,
                                         model.index_maps[fs.feature_shard_id],
                                         stats)
            if len(cols):
                m += float(np.dot(thetas[fs.coordinate_id][cols], vals))
        return m

    def _re_margin(self, model, ev: Dict[str, Any], exclude: str,
                   stats: Dict[str, int]) -> float:
        """Margins of every random-effect coordinate except ``exclude``."""
        m = 0.0
        for rs in model.random:
            if rs.coordinate_id == exclude:
                continue
            re_id = (ev.get("entities") or {}).get(rs.random_effect_type)
            if re_id is None:
                continue
            row = current_entity_row(
                rs, str(re_id), model.shard_dims.get(rs.feature_shard_id, 1))
            if row is None:
                continue
            cols, vals = _parse_features(
                ev, rs.feature_shard_id,
                model.index_maps[rs.feature_shard_id], stats)
            m += _row_margin(cols, vals, row[0], row[1])
        return m

    # ------------------------------------------------------------- train

    def train(self, events: Sequence[Dict[str, Any]]) -> DeltaTrainResult:
        """One delta round: per-coordinate warm-started solves over the
        entities ``events`` touch.  Pure training — nothing is published."""
        from photon_tpu.game.coordinate import RandomEffectCoordinate
        from photon_tpu.game.dataset import (EntityVocabulary, FeatureShard,
                                             GameDataFrame)
        from photon_tpu.game.random_effect import (
            RandomEffectDataConfiguration, build_random_effect_dataset,
            warm_start_from_cold_store)

        model = self.engine.model
        stats: Dict[str, int] = {
            "events": len(events), "entities": 0,
            "unknown_features": 0, "nonfinite_rows": 0,
        }
        self._rounds += 1
        if self.config.fixed_refresh_every > 0:
            self._fixed_events.extend(events)
            if len(self._fixed_events) > self.config.fixed_buffer:
                self._fixed_events = \
                    self._fixed_events[-self.config.fixed_buffer:]
        thetas = {fs.coordinate_id: np.asarray(fs.theta, np.float64)
                  for fs in model.fixed}
        out: Dict[str, CoordinateDelta] = {}
        for rs in model.random:
            evs = [ev for ev in events
                   if (ev.get("entities") or {}).get(rs.random_effect_type)
                   is not None]
            if not evs:
                continue
            sid = rs.feature_shard_id
            imap = model.index_maps[sid]
            rows, ids = [], []
            resp = np.empty(len(evs), np.float64)
            wts = np.empty(len(evs), np.float64)
            offs = np.empty(len(evs), np.float64)
            for i, ev in enumerate(evs):
                cols, vals = _parse_features(ev, sid, imap, stats)
                rows.append((cols.astype(np.int32), vals))
                ids.append(str(ev["entities"][rs.random_effect_type]))
                resp[i] = float(ev.get("response", 0.0))
                wts[i] = float(ev.get("weight", 1.0))
                # residual offset: logged offset + every other
                # coordinate's margin on this event (GAME score algebra)
                offs[i] = (float(ev.get("offset", 0.0))
                           + self._fixed_margin(model, ev, thetas, stats)
                           + self._re_margin(model, ev, rs.coordinate_id,
                                             stats))
            df = GameDataFrame(
                num_samples=len(evs), response=resp,
                feature_shards={sid: FeatureShard(rows, imap.feature_dimension)},
                offsets=offs, weights=wts,
                id_tags={rs.random_effect_type: ids})
            vocab = EntityVocabulary()
            ds = build_random_effect_dataset(
                df,
                RandomEffectDataConfiguration(
                    rs.random_effect_type, sid,
                    max_entity_buckets=self.config.max_entity_buckets),
                vocab)
            names = vocab.names(rs.random_effect_type)
            proj = np.asarray(ds.projection)
            cold = self._cold_for(rs)
            if cold is not None:
                warm = warm_start_from_cold_store(cold, names, proj)
            else:
                warm = np.zeros(proj.shape, np.float32)
                for r, name in enumerate(names):
                    live = current_entity_row(
                        rs, name, model.shard_dims.get(sid, 1))
                    if live is None:
                        continue
                    from photon_tpu.game.random_effect import replay_cold_rows
                    warm[r] = replay_cold_rows(
                        proj[r:r + 1], live[1][None, :], live[0][None, :])[0]
            coord = RandomEffectCoordinate(
                ds, df.num_samples, rs.random_effect_type, sid, model.task,
                config=self.config.glm)
            rem = coord.update_model_blocked(None, warm_start=warm)
            coef = np.asarray(rem.coefficients, np.float32)[:len(names)]
            # Thompson coordinates republish uncertainty WITH the means:
            # a diagonal-Hessian Laplace pass at the freshly solved rows
            # (bayes/laplace), gated on the target actually serving
            # variances and the loss having a Hessian (typed skip — the
            # mean delta still publishes, existing variance bytes stay).
            var: Optional[np.ndarray] = None
            serves_var = (getattr(rs, "var_coef", None) is not None
                          or (cold is not None
                              and getattr(cold, "has_variances", False)))
            if serves_var:
                if coord.objective.loss.has_hessian:
                    from photon_tpu.bayes.laplace import \
                        entity_variances_blocked
                    var = np.asarray(
                        entity_variances_blocked(coord, rem.coefficients),
                        np.float32)[:len(names)]
                else:
                    stats["variance_skips"] = stats.get(
                        "variance_skips", 0) + 1
                    _metrics.counter(
                        "nearline.train.variance_skipped",
                        reason="no_hessian").inc()
            delta_rows: Dict[str, Tuple[np.ndarray, np.ndarray]] = {}
            var_rows: Dict[str, np.ndarray] = {}
            ev_ts: Dict[str, float] = {}
            for r, name in enumerate(names):
                if not np.isfinite(coef[r]).all():
                    stats["nonfinite_rows"] += 1
                    _metrics.counter("nearline.train.nonfinite_rows").inc()
                    continue
                delta_rows[name] = (coef[r].copy(), proj[r].astype(np.int32))
                if var is not None:
                    if np.isfinite(var[r]).all() and (var[r] >= 0).all():
                        var_rows[name] = var[r].copy()
                    else:
                        stats["nonfinite_var_rows"] = stats.get(
                            "nonfinite_var_rows", 0) + 1
                        _metrics.counter(
                            "nearline.train.nonfinite_var_rows").inc()
            for ev, name in zip(evs, ids):
                ts = ev.get("ts")
                if ts is not None and name in delta_rows:
                    ev_ts[name] = max(ev_ts.get(name, float(ts)), float(ts))
            stats["entities"] += len(delta_rows)
            out[rs.coordinate_id] = CoordinateDelta(
                rs.coordinate_id, rs.random_effect_type, sid,
                delta_rows, ev_ts, num_events=len(evs),
                var_rows=var_rows)
        _metrics.counter("nearline.train.events").inc(len(events))
        _metrics.counter("nearline.train.entities").inc(stats["entities"])
        return DeltaTrainResult(out, len(events), stats)

    # ------------------------------------------------------ fixed refresh

    def maybe_refresh_fixed(self, label: str = "nearline-fixed"):
        """Low-cadence fixed-effect re-fit through the full validated
        swap.  Returns the ``SwapResult`` when a refresh ran, else None.
        Requires ``model_dir`` (thetas are closed over by the compiled
        scorers, so this is a whole-model publish, not a row publish)."""
        cfg = self.config
        if (cfg.fixed_refresh_every <= 0 or self.model_dir is None
                or self._rounds == 0
                or self._rounds % cfg.fixed_refresh_every != 0
                or not self._fixed_events):
            return None
        import dataclasses as _dc

        import jax.numpy as jnp

        from photon_tpu.game.coordinate import FixedEffectCoordinate
        from photon_tpu.game.dataset import FeatureShard, GameDataFrame
        from photon_tpu.game.model import FixedEffectModel
        from photon_tpu.io.model_io import load_for_serving
        from photon_tpu.models.glm import Coefficients, GeneralizedLinearModel
        from photon_tpu.serving.swap import swap_staged

        engine = self.engine
        model = engine.model
        stats: Dict[str, int] = {"unknown_features": 0}
        thetas = {fs.coordinate_id: np.asarray(fs.theta, np.float64)
                  for fs in model.fixed}
        evs = self._fixed_events
        new_thetas: Dict[str, np.ndarray] = {}
        for fs in model.fixed:
            sid = fs.feature_shard_id
            imap = model.index_maps[sid]
            dim = imap.feature_dimension
            rows = []
            resp = np.empty(len(evs), np.float64)
            wts = np.empty(len(evs), np.float64)
            offs = np.empty(len(evs), np.float64)
            for i, ev in enumerate(evs):
                cols, vals = _parse_features(ev, sid, imap, stats)
                rows.append((cols.astype(np.int32), vals))
                resp[i] = float(ev.get("response", 0.0))
                wts[i] = float(ev.get("weight", 1.0))
                # residual: everything except THIS fixed coordinate
                other_fixed = sum(
                    float(np.dot(thetas[f2.coordinate_id][c2], v2))
                    for f2 in model.fixed if f2.coordinate_id
                    != fs.coordinate_id
                    for c2, v2 in [_parse_features(
                        ev, f2.feature_shard_id,
                        model.index_maps[f2.feature_shard_id], stats)]
                    if len(c2))
                offs[i] = (float(ev.get("offset", 0.0)) + other_fixed
                           + self._re_margin(model, ev, "", stats))
            df = GameDataFrame(
                num_samples=len(evs), response=resp,
                feature_shards={sid: FeatureShard(rows, dim)},
                offsets=offs, weights=wts)
            coord = FixedEffectCoordinate(
                df.fixed_effect_batch(sid), dim, sid, model.task,
                config=cfg.glm)
            theta0 = thetas[fs.coordinate_id][:dim].astype(np.float32)
            prev = FixedEffectModel(
                GeneralizedLinearModel(
                    Coefficients(jnp.asarray(theta0)), model.task), sid)
            fem = coord.update_model(prev, None)
            theta_new = np.asarray(fem.model.coefficients.means, np.float32)
            if not np.isfinite(theta_new).all():
                _metrics.counter("nearline.fixed.nonfinite_refresh").inc()
                return None
            new_thetas[fs.coordinate_id] = theta_new
        sm = load_for_serving(self.model_dir)
        sm = _dc.replace(sm, fixed=[
            _dc.replace(fe, coefficients=new_thetas.get(
                fe.coordinate_id, fe.coefficients))
            for fe in sm.fixed])
        _metrics.counter("nearline.fixed.refreshes").inc()
        return swap_staged(engine, sm, label)
