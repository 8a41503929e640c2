"""A GLMix estimator over a mesh places every training array from the host
straight onto its shards (``parallel/mesh.shard_batch`` /
``shard_entity_blocks`` on host arrays): no device holds a whole one, the
always-on counter ``mesh.staged_bytes`` says so, ``mesh.entity_slots``
counts the rows ``pad_entities`` adds, and the one-device path compiles the
programs it always did. The benchmark's ``glmix-ml20m-mesh4.refit`` kind is
held to its reference and to the one-device fit here, at its rehearsal
sizes on four of the eight virtual devices."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import generators as G
from benchmark import run as R
from benchmark.systems import training
from photon_tpu.game.dataset import EntityVocabulary, FeatureShard, GameDataFrame
from photon_tpu.game.random_effect import (
    RandomEffectDataConfiguration,
    build_random_effect_dataset,
)
from photon_tpu.obs.metrics import registry
from photon_tpu.parallel import mesh as M

CONFIG = "glmix-ml20m-mesh4"


def _counters(prefix):
    return {k: v for k, v in registry.snapshot()["counters"].items()
            if k.startswith(prefix)}


def _delta(before, prefix):
    return {k: v - before.get(k, 0.0) for k, v in _counters(prefix).items()
            if v != before.get(k, 0.0) or k not in before}


def _rehearsal_cfg(dtype="float32"):
    cfg = R.load_json("configs", f"{CONFIG}.json")
    cfg = R.overlaid(cfg, cfg["rehearse"])
    return {**cfg, "mesh": {"data": 4}, "dtype": dtype}


@pytest.fixture(scope="module")
def rehearsal_frame():
    cfg = _rehearsal_cfg()
    rows = G.game_rows(cfg, cfg["rows"], cfg["data_seed"], "train",
                       G.planted_model(cfg, cfg["data_seed"]))
    return cfg, training.frame(cfg, rows)


def test_the_mesh_kind_is_correct_and_is_the_one_device_fit(devices8,
                                                            tmp_path):
    """The kind's set-up on a 4-device mesh passes ``correct`` against the
    configuration's reference (both readings), and its model is the
    one-device fit's (float64, at ``test_game_estimator_mesh_parity``'s
    tolerance)."""
    from benchmark.traffic import refit_mesh

    cfg = _rehearsal_cfg("float64")
    cell = R.load_json("workloads", f"{CONFIG}.refit.json")
    state = refit_mesh.setup(R.Context(cell, cfg, 1, True, str(tmp_path)))
    assert state["holds"]
    assert state["est"].mesh.devices.size == 4
    one = training.estimator(cfg)
    want = training.model_tables(cfg, one, one.fit(state["frame"])[-1].model)
    assert set(want) == set(state["fitted"])
    for k, v in want.items():
        np.testing.assert_allclose(state["fitted"][k], v, rtol=1e-6,
                                   atol=1e-8)


def test_a_meshed_prepare_stages_nothing_on_one_device(devices8,
                                                       rehearsal_frame):
    """Every training array of a meshed ``_prepare`` goes from the host to
    its four shards: ``mesh.staged_bytes`` reads 0 for every coordinate, no
    array the preparation left alive sits on one device, and each device
    holds a quarter of the fixed effect's rows."""
    cfg, frame = rehearsal_frame
    from benchmark.traffic import refit_mesh

    est = refit_mesh.estimator(cfg)
    before = {id(a) for a in jax.live_arrays()}
    staged = _counters("mesh.staged_bytes")
    coordinates, datasets = est._prepare(frame, EntityVocabulary())
    after = _counters("mesh.staged_bytes")
    for c in ("global", "userId", "movieId"):
        key = f'mesh.staged_bytes{{coordinate="{c}"}}'
        assert after[key] == staged.get(key, 0.0), (key, after)
    new = [a for a in jax.live_arrays() if id(a) not in before]
    assert new
    on_one = [(a.shape, a.nbytes) for a in new
              if len(a.sharding.device_set) == 1 and a.nbytes > 64]
    assert not on_one, on_one
    x = coordinates["fixed"].batch.features
    assert x.shape == (cfg["rows"], 128) and cfg["rows"] % 4 == 0
    assert {s.data.shape for s in x.addressable_shards} == {
        (cfg["rows"] // 4, 128)}
    for cid in ("per_user", "per_movie"):
        assert datasets[cid] is coordinates[cid].dataset
        for blk in datasets[cid].blocks:
            assert len(blk.labels.sharding.device_set) == 4


def test_arrays_on_one_device_are_counted_as_staged(devices8,
                                                    rehearsal_frame):
    """The parent's way, where it survives: a dataset already on one
    device is re-placed, and every byte of it is counted."""
    cfg, frame = rehearsal_frame
    c = cfg["coordinates"][1]
    ds = build_random_effect_dataset(
        frame, RandomEffectDataConfiguration(c["entity"], c["shard"]),
        EntityVocabulary(), coordinate="staged")
    before = _counters("mesh.staged_bytes")
    M.shard_entity_blocks(ds, M.create_mesh(4), coordinate="staged")
    assert _delta(before, "mesh.staged_bytes") == {
        'mesh.staged_bytes{coordinate="staged"}': float(sum(
            a.nbytes for a in jax.tree_util.tree_leaves(ds)))}


def test_entity_slots_are_the_pad_rows_counted_by_hand(devices8):
    """A skewed toy: entities with 9, 5, 5, 1, 1, 1 rows fall in three
    power-of-two buckets of 1, 2 and 3 entities, 9, 5 and 1 slots a row;
    over four devices each is padded to 4 rows, so the pad is 3 x 9 + 2 x 5
    + 1 x 1 = 38 slots beside 9 + 10 + 3 = 22 real ones."""
    counts = {"a": 9, "b": 5, "c": 5, "d": 1, "e": 1, "f": 1}
    ids = [e for e, k in counts.items() for _ in range(k)]
    n = len(ids)
    frame = GameDataFrame(
        num_samples=n, response=np.zeros(n),
        feature_shards={"u": FeatureShard(np.ones((n, 2), np.float32), 2)},
        id_tags={"userId": ids})
    ds = build_random_effect_dataset(
        frame, RandomEffectDataConfiguration("userId", "u"),
        EntityVocabulary(), place=False)
    assert sorted((b.num_rows, b.max_samples) for b in ds.blocks) == [
        (1, 9), (2, 5), (3, 1)]
    before = _counters("mesh.entity_slots")
    padded = M.pad_entities(ds, 4, "skewed")
    assert _delta(before, "mesh.entity_slots") == {
        'mesh.entity_slots{coordinate="skewed",kind="real"}': 22.0,
        'mesh.entity_slots{coordinate="skewed",kind="pad"}': 38.0}
    added = sum((p.num_rows - b.num_rows) * b.max_samples
                for p, b in zip(padded.blocks, ds.blocks))
    assert added == 38
    # padded on the host, while the arrays are host arrays
    assert all(isinstance(a, np.ndarray)
               for a in jax.tree_util.tree_leaves(padded))


def test_a_meshed_fit_compiles_each_solve_once(devices8, rehearsal_frame):
    """Every update of a meshed fit hands its solve the same placements (the
    first sweep's warm starts and offsets are placed as the later sweeps'
    are), so the fixed effect's solve and each random effect's ladder are
    traced and compiled once, not once for the first sweep and again for
    the rest."""
    cfg, frame = rehearsal_frame
    from benchmark.traffic import refit_mesh

    est = refit_mesh.estimator(cfg)
    _, coordinates, _ = est._prepare_cached(frame)
    # the jitted solves are shared process-wide: count what these fits add
    solves = {cid: (coordinates[cid].problem._solve_fn_for(False)
                    if cid == "fixed" else coordinates[cid]._solve_fn)
              for cid in ("fixed", "per_user", "per_movie")}
    before = {cid: fn._cache_size() for cid, fn in solves.items()}
    est.fit(frame)
    est.fit(frame)
    for cid, fn in solves.items():
        assert fn._cache_size() - before[cid] <= 1, cid


@pytest.mark.parametrize("rows", ["all", "one shard"])
def test_the_solved_reading_sees_a_solve_that_missed_rows(devices8, rows):
    """``refit_mesh.solved`` runs the fixed effect's own solve and reads the
    reference's gradient at its result: a solve that saw one shard's rows
    alone (the other shards' weights 0: what a solve whose sums were not
    all-reduced over the mesh sees on each chip) fails the limit a proper
    solve meets, where the fitted model's gradient need not."""
    from benchmark import correct
    from benchmark.traffic import refit_mesh

    cfg = _rehearsal_cfg()
    train = G.game_rows(cfg, cfg["rows"], cfg["data_seed"], "train",
                        G.planted_model(cfg, cfg["data_seed"]))
    est = refit_mesh.estimator(cfg)
    est.fit(training.frame(cfg, train))
    fe = est._coordinates["fixed"]
    if rows == "one shard":
        w = np.array(fe.batch.weights)
        w[len(w) // 4:] = 0
        fe.batch = fe.batch._replace(
            weights=jax.device_put(w, fe.batch.weights.sharding))
    holds, measured = refit_mesh.solved(
        cfg, est, correct.load_reference(cfg["name"]), train)
    assert holds == (rows == "all"), measured


# sha256 of the lowered text (no debug info) of the programs a one-device
# GLMix fit compiles where placement or scoring could reach them, as the
# parent of the mesh's host placement lowered them: a change here is a
# change to every one-chip cell's programs
ONE_DEVICE_PROGRAMS = {
    "fixed_score": "52eaa2d994854666",
    "re_score": "8eb9a41b73b3469b",
    "re_solve": "d81e0d8f664c2fa2",
}


def test_the_one_device_programs_are_unchanged():
    from photon_tpu.game.coordinate import _fixed_score
    from tests.test_game import glmix_estimator, make_glmix_frame

    frame, _, _ = make_glmix_frame(np.random.default_rng(11), n=400,
                                   n_users=9)
    est = glmix_estimator(num_iterations=1)
    est.fit(frame)
    fe, re_ = est._coordinates["fixed"], est._coordinates["per-user"]
    ds = re_.dataset
    table = jnp.zeros((ds.num_entities, ds.projected_dim))
    texts = {
        "fixed_score": _fixed_score.lower(fe.batch.features,
                                          jnp.zeros(fe.dim)),
        "re_score": re_._score_fn.lower(ds, table),
        "re_solve": re_._solve_fn.lower(ds, jnp.zeros(re_.n), table,
                                        jnp.asarray(1.0), jnp.asarray(0.0)),
    }
    got = {k: hashlib.sha256(v.as_text().encode()).hexdigest()[:16]
           for k, v in texts.items()}
    assert got == ONE_DEVICE_PROGRAMS
