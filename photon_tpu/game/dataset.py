"""GAME data structures: host-side columnar frame -> device datasets.

Reference: photon-lib data/GameDatum.scala:40-68 (response/offset/weight,
per-shard feature vectors, id-tag map), photon-api data/GameConverters
.scala:28 (DataFrame row -> GameDatum), data/FixedEffectDataset.scala:31,
data/InputColumnsNames.scala:25.

TPU re-design: the RDD[(uid, GameDatum)] becomes a host-side columnar
``GameDataFrame`` (numpy struct-of-arrays + per-shard sparse rows) from
which static-shape device views are built: a flat uid-major DataBatch per
fixed-effect coordinate, entity-blocked padded arrays per random-effect
coordinate (game/random_effect.py). Sample identity is the row position —
uids never leave the host.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout

from photon_tpu.data.dataset import DataBatch
from photon_tpu.obs.metrics import registry
from photon_tpu.ops import features as F
from photon_tpu.utils import compile_cache
from photon_tpu.utils.timing import Timed

SparseRows = List[Tuple[np.ndarray, np.ndarray]]  # per-row (indices, values)


def count_placed(coordinate: str, tree) -> None:
    """Add the bytes of the device arrays in ``tree``, just placed for
    ``coordinate``, to the always-on counter ``ingest.h2d_bytes``."""
    registry.counter("ingest.h2d_bytes", coordinate=coordinate).inc(
        sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree)))


ROW_MAJOR = (0, 1)      # ``Layout.major_to_minor`` with the rows outermost
_LANES = 128            # a TPU tiles the minor dimension in 128 lanes
# Stored rows-major, a row is padded to whole lane tiles and the excess is
# read on EVERY pass over X, 14-20 a solve; left as the compiler lays it, a
# solve begins with a re-layout copy, one read and one write of X, some 2.3
# passes. So the stored layout wins while the excess is under 2.3 / 16 =
# 0.14 of a row: an eighth (2,000 -> 2,048 is 2.4%; 130 -> 256 is not
# admitted). Arithmetic, not a setting. Read once on the chip (PERF.md §6,
# PR 37): at 1,000,000 x 1,000 the stored layout wins a fit by 13.6%; at
# 130 wide the two are within 3% either way (the compiler's own copy pads
# the rows the same), so past the bound the rule saves no time and spares
# the device a stored matrix of twice its values.
_MAX_ROW_PADDING = 1.125


def row_major_outcome(width: int, default_major_to_minor,
                      on_mesh: bool = False) -> str:
    """What ``store_rows_major`` does with a dense ``[rows, width]`` matrix
    whose device lays it ``default_major_to_minor`` when nothing is stated
    (``None``: the backend reports no layout), and the label it counts
    under ``ingest.row_major``: ``default`` (the rows are outermost
    already: every CPU array, a TPU's where the width is a multiple of
    128), ``mesh`` (a mesh re-places the batch), ``padding`` (whole lane
    tiles would cost over an eighth more bytes), else ``relaid``."""
    if default_major_to_minor in (None, ROW_MAJOR):
        return "default"
    if on_mesh:
        return "mesh"
    if -(-width // _LANES) * _LANES > _MAX_ROW_PADDING * width:
        return "padding"
    return "relaid"


def _major_to_minor(x: jax.Array):
    """The layout ``x`` lies in on its device (``None``: not reported)."""
    layout = x.format.layout
    return None if layout is None else layout.major_to_minor


def _rows_major(x: jax.Array) -> jax.Array:
    """The relayout program's body. A function of this module's own, not
    ``jax.device_put(x, Format(...))``: that one compiles JAX's
    ``_identity_fn``, under whose name a persistent cache may already hold
    an entry that mislabels its output (``compiled_in_this_process``)."""
    return x


def store_rows_major(x, coordinate: str, on_mesh: bool = False):
    """A placed dense feature matrix, ONCE in the layout every solve reads.
    For the layer that holds X for MANY solves and knows its mesh
    (``GameEstimator._prepare``); a matrix that is read once (a validation
    or transform X, statistics) stays as ``shard_features`` placed it.

    Where ``row_major_outcome`` says ``relaid`` the array is stored
    rows-major (one identity program; the device holds X and the copy for
    its duration, what each solve program held until now) and comes back
    COMMITTED: a jitted function that states no layout is compiled for a
    committed argument's own, so no solve, score or lane program begins
    with the compiler's re-layout copy of X. Everywhere else ``x`` comes
    back as it is (every CPU array; a TPU's 128-wide matrix; anything but
    a dense rank-2 array, uncounted). A relaid array whose label does not
    read rows-major (a stale executable: ``compiled_in_this_process``) is
    dropped for ``x``, outcome ``mislabelled``: a fit is then slower, not
    refused. One tick of ``ingest.row_major{coordinate, outcome}`` a dense
    matrix."""
    if not isinstance(x, jax.Array) or x.ndim != 2:
        return x
    outcome = row_major_outcome(x.shape[1], _major_to_minor(x), on_mesh)
    if outcome == "relaid":
        # compiled here, never served from the persistent cache: a served
        # program mislabels a stated output layout (compile_cache)
        with compile_cache.compiled_in_this_process():
            relaid = jax.jit(_rows_major, out_shardings=Format(
                Layout(ROW_MAJOR), x.sharding))(x)
        if _major_to_minor(relaid) in (None, ROW_MAJOR):
            x = relaid
        else:
            outcome = "mislabelled"
    registry.counter("ingest.row_major", coordinate=coordinate,
                     outcome=outcome).inc()
    return x


class CsrRows:
    """Columnar sparse rows (CSR): the zero-Python-object counterpart of
    ``SparseRows`` produced by the native ingest path (io/fast_ingest.py).
    Duck-types the row-list protocol (len / [i] / iteration) so generic
    consumers keep working; hot paths branch on isinstance for the
    vectorized form."""

    __slots__ = ("indptr", "cols", "vals")

    def __init__(self, indptr: np.ndarray, cols: np.ndarray,
                 vals: np.ndarray):
        self.indptr = np.asarray(indptr, np.int64)
        self.cols = np.asarray(cols)
        self.vals = np.asarray(vals)

    def __len__(self) -> int:
        return len(self.indptr) - 1

    def __getitem__(self, i) -> Tuple[np.ndarray, np.ndarray]:
        n = len(self)
        if i < 0:
            i += n
        if not 0 <= i < n:
            raise IndexError(i)
        s, e = self.indptr[i], self.indptr[i + 1]
        return self.cols[s:e], self.vals[s:e]

    @staticmethod
    def from_dense(x: np.ndarray) -> "CsrRows":
        """Dense [n, d] -> fully-populated CsrRows (every slot observed,
        explicit zeros kept): the columnar handover for dense blocks."""
        n, d = x.shape
        return CsrRows(np.arange(n + 1, dtype=np.int64) * d,
                       np.tile(np.arange(d, dtype=np.int32), n),
                       np.asarray(x, np.float64).reshape(-1))

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def row_nnz(self) -> np.ndarray:
        return np.diff(self.indptr)


@dataclasses.dataclass
class FeatureShard:
    """One feature space: sparse rows (list- or CSR-form) or a dense
    matrix, plus its dim."""

    rows: Union[SparseRows, CsrRows, np.ndarray]
    dim: int

    @property
    def is_dense(self) -> bool:
        return isinstance(self.rows, np.ndarray)

    def max_nnz(self) -> int:
        if self.is_dense:
            return self.dim
        if isinstance(self.rows, CsrRows):
            nnz = self.rows.row_nnz()
            return int(nnz.max()) if len(nnz) else 0
        return max((len(r[0]) for r in self.rows), default=0)


@dataclasses.dataclass
class GameDataFrame:
    """Host-side columnar GAME dataset (the RDD[(uid, GameDatum)] stand-in).

    ``id_tags[re_type][i]`` is sample i's entity id string for that
    random-effect type (reference: GameDatum.idTagToValueMap).
    """

    num_samples: int
    response: np.ndarray                       # [n]
    feature_shards: Dict[str, FeatureShard]
    offsets: Optional[np.ndarray] = None       # [n]
    weights: Optional[np.ndarray] = None       # [n]
    id_tags: Dict[str, Sequence[str]] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        n = self.num_samples
        assert len(self.response) == n
        for tag, vals in self.id_tags.items():
            assert len(vals) == n, f"id tag {tag} length mismatch"

    def shard_features(self, shard_id: str, dtype=np.float32) -> F.FeatureMatrix:
        """One shard's features on the device. A dense matrix is the plain
        uncommitted ``jnp.asarray``, in the device's DEFAULT layout for
        its shape (on a TPU column-major where the width is no multiple of
        128): right for a matrix that is read once (a validation or
        transform X, statistics) and free to move to any device or mesh.
        The layer that solves on it again and again stores it rows-major
        (``store_rows_major``, ``GameEstimator._prepare``)."""
        shard = self.feature_shards[shard_id]
        if shard.is_dense:
            return jnp.asarray(shard.rows, dtype)
        if isinstance(shard.rows, CsrRows):
            return F.from_csr_arrays(shard.rows.indptr, shard.rows.cols,
                                     shard.rows.vals, dtype=dtype)
        return F.from_rows(shard.rows, shard.dim, dtype=dtype)

    def fixed_effect_batch(self, shard_id: str, dtype=np.float32,
                           feature_dtype=None,
                           coordinate: Optional[str] = None,
                           place: bool = True) -> DataBatch:
        """Reference: FixedEffectDataset — flat uid-major batch over one
        feature shard.

        ``feature_dtype`` stores X narrower than the solve dtype (e.g.
        bfloat16 under an f32 solve): matvec/rmatvec promote to the
        accumulation dtype in-register, so a bandwidth-bound solve reads
        half the HBM bytes while the optimizer math stays full-precision.

        The host seconds of the placement are the ``Timed`` phase
        ``ingest/h2d/<coordinate>`` (the shard id when the caller gives no
        coordinate; a sparse shard's padded fill, which ``ops/features``
        does in the same call, is inside it), and the placed bytes go to
        the counter ``ingest.h2d_bytes{coordinate}``.

        The layout contract is ``shard_features``': X is placed plainly, in
        the device's default layout, uncommitted. ``GameEstimator._prepare``
        passes a dense X it will solve on many times, on one device,
        through ``store_rows_major``: it is then a COMMITTED rows-major
        array whose layout the solves compile for, and
        ``ingest.row_major{coordinate, outcome}`` says which way it went.

        ``place=False`` leaves a dense X and the per-row vectors on the
        host, for a caller that places them itself (a mesh:
        ``parallel/mesh.shard_batch`` sends each device its shard); no
        ``ingest/h2d`` phase, nothing counted. A sparse X is placed as
        ever."""
        coordinate = coordinate or shard_id
        if not place and self.feature_shards[shard_id].is_dense:
            def host(a, dt=dtype):
                return None if a is None else np.asarray(a, dt)
            return DataBatch(
                features=host(self.feature_shards[shard_id].rows,
                              feature_dtype or dtype),
                labels=host(self.response), offsets=host(self.offsets),
                weights=host(self.weights))
        with Timed(f"ingest/h2d/{coordinate}", level=logging.DEBUG):
            batch = DataBatch(
                features=self.shard_features(shard_id, feature_dtype or dtype),
                labels=jnp.asarray(self.response, dtype),
                offsets=None if self.offsets is None else jnp.asarray(self.offsets, dtype),
                weights=None if self.weights is None else jnp.asarray(self.weights, dtype),
            )
        count_placed(coordinate, batch)
        return batch


class EntityVocabulary:
    """String REId <-> dense entity index, per random-effect type.

    Built from training data; evaluation data maps unseen entities to -1
    (zero score contribution — matching the reference, where a missing
    per-entity model contributes nothing).
    """

    def __init__(self):
        self._maps: Dict[str, Dict[str, int]] = {}
        self._names: Dict[str, List[str]] = {}

    def build(self, re_type: str, ids: Sequence[str]) -> np.ndarray:
        m = self._maps.setdefault(re_type, {})
        names = self._names.setdefault(re_type, [])
        out = np.empty(len(ids), np.int32)
        for i, s in enumerate(ids):
            j = m.get(s)
            if j is None:
                j = len(names)
                m[s] = j
                names.append(s)
            out[i] = j
        return out

    def lookup(self, re_type: str, ids: Sequence[str]) -> np.ndarray:
        m = self._maps.get(re_type, {})
        return np.asarray([m.get(s, -1) for s in ids], np.int32)

    def names(self, re_type: str) -> List[str]:
        return list(self._names.get(re_type, []))

    def size(self, re_type: str) -> int:
        return len(self._names.get(re_type, []))
