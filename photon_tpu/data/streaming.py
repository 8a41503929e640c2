"""Out-of-core streaming ingest: double-buffered host->device chunk pipeline.

Photon ML's Spark runtime streams training data from disk through
executors, so dataset size never bounds a fit; the TPU rebuild held every
shard in device memory. This module removes that assumption with the
pipeline shape of Snap ML (PAPERS.md): a fixed pool of pow2-shaped host
staging buffers filled by a reader thread, with the device transfer of
chunk k+1 dispatched while the consumer computes on chunk k.

Invariants the rest of the system builds on:

- **Static chunk shape.** Every chunk is exactly ``chunk_rows`` rows
  (rounded up to a power of two); the tail is zero-padded with weight-0
  rows. One jitted per-chunk program therefore serves the entire stream.
- **Deterministic chunk order.** Chunks are emitted in ascending raw-row
  order, always — there is no shuffling and no reader-side reordering, so
  two runs over the same source produce bitwise-identical chunk
  sequences (the foundation of the streamed solver's run-to-run and
  kill/resume bitwise guarantees).
- **Filter-stable chunk assignment.** With ``drop_invalid``, rows are
  filtered per raw block by ``validators.invalid_chunk_mask`` (the same
  row-local rules the resident validator applies) and survivors are
  packed densely across chunk boundaries — surviving row i lands in
  chunk i // chunk_rows exactly as it would after filtering the resident
  dataset up front.
- **Bounded staging memory.** Host-side memory is ``num_buffers`` staging
  buffers plus one raw block; device-side memory is at most the chunks
  in flight through the bounded queue. Neither scales with dataset size.
- **Safe buffer recycling.** A staging buffer is reused only after the
  reader has fenced the consumer out of it — on the reader thread,
  never the consumer's per-chunk path. In copy mode (any accelerator,
  or any meshed run) the fence is ``block_until_ready`` on the prior
  device arrays: once the DMA copy lands, the staging memory is free.
  On unmeshed CPU backends the loader instead *aliases* the staging
  buffers into device arrays via dlpack (zero-copy — ``device_put`` on
  CPU is a slow single-threaded memcpy that would triple host traffic),
  and the fence becomes a **consumption token**: an async consumer
  calls ``loader.release(chunk, token)`` with an output of the
  computation that read the chunk (the streamed solver passes the new
  carry), and the reader blocks on that token before refilling the
  buffer. Consumers that read chunks synchronously need nothing — the
  generator auto-releases a chunk when the next one is requested.

Chaos hooks: ``chaos.chunk_read_delay`` (slow disk) and
``chaos.chunk_read_error`` (transient read failure, retried under the
``resilience/retry`` env knobs) fire inside the reader thread, so fault
injection exercises the real overlap path.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Iterator, List, NamedTuple, Optional

import numpy as np

from photon_tpu.data.dataset import DataBatch
from photon_tpu.ops import features as F
from photon_tpu.resilience import chaos
from photon_tpu.resilience.retry import RetryPolicy, with_retries
from photon_tpu.types import TaskType


class RawBlock(NamedTuple):
    """One raw block read from a ChunkSource (host numpy, row-major).

    Dense sources fill ``x`` [rows, dim]; sparse sources fill the
    padded-ELL pair ``idx``/``val`` [rows, ell_width]. ``weights`` and
    ``offsets`` are optional per-row columns.
    """

    labels: np.ndarray
    x: Optional[np.ndarray] = None
    idx: Optional[np.ndarray] = None
    val: Optional[np.ndarray] = None
    offsets: Optional[np.ndarray] = None
    weights: Optional[np.ndarray] = None

    @property
    def rows(self) -> int:
        return int(self.labels.shape[0])


class DenseSource:
    """Dense [n, dim] design matrix (ndarray or np.memmap) as a chunk
    source. ``read_block`` returns views; the loader either copies them
    into its staging buffers or (zero-copy mode, full aligned chunks)
    publishes the views directly, so a memmapped X streams from disk
    without ever materializing in RAM beyond one block. The source
    arrays are assumed immutable for the lifetime of the stream."""

    def __init__(self, X, labels, offsets=None, weights=None):
        if X.ndim != 2 or X.shape[0] != np.shape(labels)[0]:
            raise ValueError(f"X {X.shape} does not match labels "
                             f"{np.shape(labels)}")
        self.X = X
        self.labels = labels
        self.offsets = offsets
        self.weights = weights
        self.num_rows, self.dim = X.shape
        self.ell_width: Optional[int] = None   # dense

    def read_block(self, start: int, stop: int) -> RawBlock:
        sl = slice(start, stop)
        return RawBlock(
            labels=np.asarray(self.labels[sl]),
            x=np.asarray(self.X[sl]),
            offsets=None if self.offsets is None
            else np.asarray(self.offsets[sl]),
            weights=None if self.weights is None
            else np.asarray(self.weights[sl]),
        )


class CsrSource:
    """CSR rows streamed as fixed-width padded-ELL blocks. ``max_nnz`` is
    a global static so every chunk lowers to the same compiled program;
    rows wider than it are rejected up front (silent truncation would
    corrupt margins, same contract as ops/features.from_csr_arrays)."""

    def __init__(self, indptr, cols, vals, labels, dim: int,
                 max_nnz: Optional[int] = None, offsets=None, weights=None,
                 dtype=np.float32):
        self.indptr = np.asarray(indptr, np.int64)
        self.cols = np.asarray(cols)
        self.vals = np.asarray(vals)
        self.labels = labels
        self.offsets = offsets
        self.weights = weights
        self.num_rows = len(self.indptr) - 1
        self.dim = int(dim)
        self.dtype = np.dtype(dtype)
        row_nnz = np.diff(self.indptr)
        widest = int(row_nnz.max()) if self.num_rows else 0
        k = int(max_nnz) if max_nnz is not None else widest
        if widest > k:
            raise ValueError(f"row has {widest} nonzeros > max_nnz={k}; "
                             "refusing to silently truncate features")
        self.ell_width = k

    def read_block(self, start: int, stop: int) -> RawBlock:
        indptr = self.indptr[start:stop + 1]
        r = stop - start
        k = self.ell_width
        row_nnz = np.diff(indptr)
        idx = np.zeros((r, k), np.int32)
        val = np.zeros((r, k), self.dtype)
        if r and k:
            slot = np.arange(k)[None, :]
            mask = slot < row_nnz[:, None]
            src = indptr[:-1, None] + slot
            idx[mask] = self.cols[src[mask]]
            val[mask] = self.vals[src[mask]]
        sl = slice(start, stop)
        return RawBlock(
            labels=np.asarray(self.labels[sl]), idx=idx, val=val,
            offsets=None if self.offsets is None
            else np.asarray(self.offsets[sl]),
            weights=None if self.weights is None
            else np.asarray(self.weights[sl]),
        )


class EllSource:
    """In-RAM padded-ELL rows as a chunk source — the layout the mmap
    store carries on disk and ``ops/features.SparseFeatures`` holds on
    device. ``read_block`` returns plain row slices (zero-copy views),
    so a resident sparse batch can be re-streamed through the chunk
    pipeline (the SDCA passthrough wraps a coordinate's ELL batch this
    way) without a CSR round-trip."""

    def __init__(self, idx, val, labels, dim: int, offsets=None,
                 weights=None):
        idx = np.asarray(idx)
        val = np.asarray(val)
        if idx.ndim != 2 or idx.shape != val.shape:
            raise ValueError(f"idx {idx.shape} / val {val.shape} must be "
                             "matching [rows, ell_width] ELL arrays")
        if idx.shape[0] != np.shape(labels)[0]:
            raise ValueError(f"ELL rows {idx.shape[0]} do not match labels "
                             f"{np.shape(labels)}")
        self.idx = idx
        self.val = val
        self.labels = labels
        self.offsets = offsets
        self.weights = weights
        self.num_rows = int(idx.shape[0])
        self.dim = int(dim)
        self.ell_width = int(idx.shape[1])

    def read_block(self, start: int, stop: int) -> RawBlock:
        sl = slice(start, stop)
        return RawBlock(
            labels=np.asarray(self.labels[sl]),
            idx=np.asarray(self.idx[sl]),
            val=np.asarray(self.val[sl]),
            offsets=None if self.offsets is None
            else np.asarray(self.offsets[sl]),
            weights=None if self.weights is None
            else np.asarray(self.weights[sl]),
        )


class MmapChunkSource:
    """Disk-native chunk source over an ``io/data_store.py`` columnar
    store: ``read_block`` is a zero-copy mmap slice per section — no
    parse, no row assembly — so a fit streams straight off storage while
    host RAM holds only the OS page-cache window.

    The store carries sparse rows PRE-ASSEMBLED as padded ELL, bitwise
    identical to what ``CsrSource.read_block`` materializes, and every
    section file is page-aligned, so interior full chunks satisfy the
    loader's 64-byte alias contract (any chunk boundary at a multiple of
    16 rows is aligned for every section dtype) and flow through the
    same zero-copy dlpack path as the in-RAM sources — a streamed
    L-BFGS/OWL-QN fit off this source is bitwise identical to one off
    ``CsrSource``/``DenseSource`` on the same rows.

    ``shard_id`` restricts the source to the chunks the store's manifest
    assigns to that mesh shard (crc32 partitioner, see
    ``parallel/partition.entity_shard``); the shard's chunk spans are
    remapped to a dense [0, num_rows) row space so the loader needs no
    shard awareness. ``advise_behind`` (default on) drops clean resident
    pages behind the consumption cursor via madvise(DONTNEED) — purely
    an RSS bound; the pages re-fault identically if re-read, so repeated
    passes stay correct and a full pass's resident high-water is a small
    window instead of the dataset. Two release paths cover the loader's
    two modes: ``read_block`` advises behind the *read* cursor (safe in
    copy mode, where the reader's staging memcpy has already consumed
    the pages synchronously), and ``consumed`` advises behind realized
    *consumption tokens* (the loader hands over each source-aliased
    chunk's token) — in alias mode the async dispatch queue lets XLA
    executions lag the read cursor, so a reader-side advise alone gets
    quietly re-faulted by the lagging reads and a full pass ends with
    most of the store resident.
    """

    #: consumption-token lag (chunks) before a fenced page release:
    #: small enough to bound the resident window, large enough to keep
    #: chunk dispatch running ahead of execution
    _CONSUME_LAG = 4

    def __init__(self, path: str, *, shard_id: Optional[int] = None,
                 verify: bool = True, advise_behind: bool = True):
        # deferred: io.data_store imports resilience/io; keep streaming's
        # import graph free of the io package until a store is opened
        from photon_tpu.io.data_store import DataStore
        self.store = DataStore(path, verify=verify)
        man = self.store.manifest
        self.dtype = np.dtype(man["dtype"])
        self.dim = int(man["dim"])
        self.ell_width: Optional[int] = (
            None if man["ell_width"] is None else int(man["ell_width"]))
        n = int(man["n_rows"])
        cr = int(man["chunk_rows"])
        if shard_id is None:
            spans = [(0, n)] if n else []
        else:
            if not 0 <= int(shard_id) < int(man["num_shards"]):
                raise ValueError(f"shard_id={shard_id} outside the "
                                 f"store's {man['num_shards']} shards")
            spans = []
            for c, s in enumerate(man["chunk_shards"]):
                if int(s) != int(shard_id):
                    continue
                lo, hi = c * cr, min(n, (c + 1) * cr)
                if spans and spans[-1][1] == lo:
                    spans[-1] = (spans[-1][0], hi)
                else:
                    spans.append((lo, hi))
        self._spans = spans
        self.num_rows = int(sum(hi - lo for lo, hi in spans))
        self._cum = np.cumsum([0] + [hi - lo for lo, hi in spans])
        self.labels = self.store.section("labels")
        self.offsets = (self.store.section("offsets")
                        if man["has_offsets"] else None)
        self.weights = (self.store.section("weights")
                        if man["has_weights"] else None)
        if self.ell_width is None:
            self._x = self.store.section("x")
        else:
            self._idx = self.store.section("idx")
            self._val = self.store.section("val")
        self._advise = bool(advise_behind)
        self._advised_to = 0   # logical row watermark already released
        self._pending: List[tuple] = []   # (row_stop, token) FIFO
        self._consumed_to = 0  # logical row watermark token-fence-released

    def _pieces(self, start: int, stop: int) -> List[tuple]:
        """Logical row range -> physical (lo, hi) spans in the store."""
        out = []
        i = int(np.searchsorted(self._cum, start, side="right")) - 1
        while start < stop and i < len(self._spans):
            lo, hi = self._spans[i]
            p_lo = lo + (start - int(self._cum[i]))
            take = min(stop - start, hi - p_lo)
            out.append((p_lo, p_lo + take))
            start += take
            i += 1
        return out

    def _gather(self, arr: np.ndarray, pieces: List[tuple]) -> np.ndarray:
        if len(pieces) == 1:
            lo, hi = pieces[0]
            return arr[lo:hi]           # zero-copy mmap slice
        return np.concatenate([arr[lo:hi] for lo, hi in pieces])

    def _release_behind(self, start: int, stop: int) -> None:
        """madvise(DONTNEED) rows more than ~4 blocks behind the cursor
        (new pass detected by a backwards cursor => watermark reset)."""
        if start < self._advised_to:
            self._advised_to = 0
        behind = start - 4 * (stop - start)
        if behind - self._advised_to < (stop - start):
            return
        for lo, hi in self._pieces(self._advised_to, behind):
            self.store.advise_dontneed(lo, hi)
        self._advised_to = behind

    def consumed(self, row_stop: int, token) -> None:
        """Token-fenced page release for the zero-copy alias path. The
        loader calls this with every source-aliased chunk's consumption
        token (the streamed solver's new carry); the carry chain means
        token k's readiness fences every chunk <= k's reads, so pages
        advised after the wait can never be re-faulted by a lagging
        async execution. The wait itself trails ``_CONSUME_LAG`` chunks
        behind dispatch and lands on an almost-always-realized token —
        compute, not this fence, stays the critical path."""
        if not self._advise:
            return
        if self._pending and row_stop <= self._pending[-1][0]:
            # backwards cursor = new pass; its tokens were realized at
            # the pass-end (f, g) host read, nothing left to fence
            self._pending.clear()
            self._consumed_to = 0
        self._pending.append((row_stop, token))
        if len(self._pending) <= self._CONSUME_LAG:
            return
        stop, tok = self._pending.pop(0)
        import jax
        jax.block_until_ready(tok)   # host-sync-ok — trailing RSS fence,
        # _CONSUME_LAG chunks behind dispatch, NOT the per-chunk path
        for lo, hi in self._pieces(self._consumed_to, stop):
            self.store.advise_dontneed(lo, hi)
        self._consumed_to = stop

    def read_block(self, start: int, stop: int) -> RawBlock:
        pieces = self._pieces(start, stop)
        g = lambda a: self._gather(a, pieces)   # noqa: E731
        block = RawBlock(
            labels=g(self.labels),
            x=g(self._x) if self.ell_width is None else None,
            idx=g(self._idx) if self.ell_width is not None else None,
            val=g(self._val) if self.ell_width is not None else None,
            offsets=None if self.offsets is None else g(self.offsets),
            weights=None if self.weights is None else g(self.weights),
        )
        if self._advise:
            self._release_behind(start, stop)
        return block


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Knobs for the streaming chunk loader.

    ``chunk_rows`` is rounded UP to a power of two (static shapes; one
    compiled per-chunk program). ``num_buffers=2`` is classic double
    buffering: one buffer in flight to the device while the reader fills
    the other; raise it to deepen prefetch when reads are bursty.
    ``drop_invalid`` applies the resident validator's row-local rules
    per chunk (``task`` required). ``retry`` defaults to the env-tunable
    ``RetryPolicy.from_env()`` (PHOTON_TPU_IO_RETRIES / _RETRY_BASE_S /
    _RETRY_MAX_S), the same knobs the checkpoint/cold-store I/O uses.
    """

    chunk_rows: int = 8192
    num_buffers: int = 2
    dtype: object = np.float32
    drop_invalid: bool = False
    task: Optional[TaskType] = None
    retry: Optional[RetryPolicy] = None
    # None = auto: alias staging buffers into device arrays (dlpack,
    # zero-copy) on unmeshed CPU backends, DMA-copy everywhere else.
    # False forces copy mode (e.g. a consumer that dispatches async
    # compute on chunks but cannot provide release tokens).
    zero_copy: Optional[bool] = None


class DeviceChunk(NamedTuple):
    index: int          # position in the deterministic chunk order
    rows: int           # real rows (tail chunks: < chunk_rows; rest pad)
    batch: DataBatch    # device-resident, chunk_rows rows, weight-0 pads
    # True when the chunk occupies a recycled staging buffer and so needs
    # a consumption token before reuse; False for chunks aliased straight
    # off the (immutable, never-recycled) source arrays
    fenced: bool = True
    # stable chunk identity: which chunk of the CANONICAL ascending order
    # this is. Equal to ``index`` on ascending streams; under
    # ``stream(order=...)`` the visit position (``index``) permutes while
    # ``chunk_id`` names the same rows every epoch — the key consumers
    # with per-chunk state (SDCA's dual slots) key on. -1 = unset
    # (legacy constructions), meaning "same as index".
    chunk_id: int = -1


@dataclasses.dataclass
class StreamStats:
    """Wall-clock accounting of one pass, read by the overlap gauges
    (utils/flops.stream_overlap_utilization). ``reader_busy_s`` is the
    hideable work (read + validate + stage + transfer dispatch);
    ``consumer_stall_s`` is how much of it was NOT hidden (consumer sat
    in q.get); ``transfer_wait_s`` is reader-side backpressure waiting to
    recycle a buffer still in flight."""

    chunks: int = 0
    rows: int = 0
    rows_dropped: int = 0
    bytes_h2d: int = 0
    reader_busy_s: float = 0.0
    transfer_wait_s: float = 0.0
    consumer_stall_s: float = 0.0
    wall_s: float = 0.0


class _EndOfPass(NamedTuple):
    num_chunks: int


class _ReaderError(NamedTuple):
    error: BaseException


def _pow2_ceil(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length() if n > 1 else 1


_ALIGN = 64   # XLA:CPU requires 64-byte alignment to alias a host buffer


def _aligned_zeros(shape, dtype) -> np.ndarray:
    """Zeroed ndarray whose data pointer is ``_ALIGN``-byte aligned, so
    dlpack import of the staging buffer is a true alias (an unaligned
    buffer silently degrades to a copy and the whole zero-copy path
    loses its point)."""
    dt = np.dtype(dtype)
    n = int(np.prod(shape, dtype=np.int64)) * dt.itemsize
    raw = np.zeros(n + _ALIGN, np.uint8)
    off = (-raw.ctypes.data) % _ALIGN
    return raw[off:off + n].view(dt).reshape(shape)


def ensure_aligned(a: np.ndarray) -> np.ndarray:
    """Return ``a`` if its buffer is 64-byte aligned and C-contiguous,
    else a one-time aligned copy. XLA:CPU only aliases aligned host
    buffers, and numpy's default allocator gives 16 — so an in-RAM dense
    source built straight from ``rng.normal``/``np.load`` silently loses
    the source-alias fast path on every chunk of every pass. Memmapped
    and freshly materialized large arrays are page-aligned already; this
    is for the in-RAM case, where one copy is affordable and amortizes
    over the whole fit."""
    a = np.ascontiguousarray(a)
    if a.ctypes.data % _ALIGN == 0:
        return a
    out = _aligned_zeros(a.shape, a.dtype)
    np.copyto(out, a)
    return out


_U64 = (1 << 64) - 1


def _splitmix64(x: int) -> int:
    """One splitmix64 step (pure-int, platform/numpy-version independent —
    the permutation below must be bitwise stable forever, so it cannot
    ride numpy's Generator, whose stream is only stable per release
    line)."""
    x = (x + 0x9E3779B97F4A7C15) & _U64
    z = x
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _U64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _U64
    return (z ^ (z >> 31)) & _U64


def epoch_chunk_order(seed: int, epoch: int, num_chunks: int) -> np.ndarray:
    """Deterministic chunk visit order for outer epoch ``epoch``.

    Counter-derived (splitmix64-keyed Fisher-Yates on ``(seed, epoch)``)
    so two runs — and a kill/resume replay — produce bitwise-identical
    orders with no wall-clock or global-RNG entropy. Epoch 0 is the
    IDENTITY by contract: the first pass must ascend because chunk
    geometry is only learned on a completed ascending pass (with
    ``drop_invalid`` the survivor-packed chunk count and composition are
    unknown before it). Later epochs shuffle.

    Stable under drop-invalid filtering: the permutation is a function of
    ``num_chunks`` alone and chunk *composition* never changes with visit
    order (survivors pack ascending into chunk ``i // chunk_rows`` slots
    regardless of the order those chunks are later visited in), so
    enabling the filter permutes exactly the same chunk ids it packs.
    """
    n = int(num_chunks)
    if n < 0:
        raise ValueError(f"num_chunks must be >= 0, got {num_chunks}")
    order = np.arange(n, dtype=np.int64)
    if int(epoch) == 0 or n <= 1:
        return order
    # key the stream on (seed, epoch) via two absorb steps
    state = _splitmix64((int(seed) & _U64) ^ 0xD6E8FEB86659FD93)
    state = _splitmix64(state ^ (int(epoch) & _U64))
    for i in range(n - 1, 0, -1):
        state = _splitmix64(state)
        j = state % (i + 1)
        order[i], order[j] = order[j], order[i]
    return order


class ChunkLoader:
    """Async prefetching chunk loader over a ChunkSource.

    ``stream(start_chunk=k)`` yields DeviceChunks in deterministic
    ascending order; one stream may be active per loader at a time. The
    reader thread owns the staging pool and all raw I/O; the consumer
    only ever touches device arrays, so its per-chunk path stays free of
    host syncs.
    """

    def __init__(self, source, config: StreamConfig = StreamConfig(),
                 mesh=None):
        if config.drop_invalid and config.task is None:
            raise ValueError("drop_invalid requires StreamConfig.task")
        if config.num_buffers < 2:
            raise ValueError("need >= 2 staging buffers to double-buffer")
        self.source = source
        self.config = config
        self.mesh = mesh
        self.dtype = np.dtype(config.dtype)
        self.chunk_rows = _pow2_ceil(config.chunk_rows)
        if mesh is not None:
            from photon_tpu.parallel import mesh as M
            self._axes = ((M.DCN_AXIS, M.DATA_AXIS)
                          if M.DCN_AXIS in mesh.axis_names else M.DATA_AXIS)
            names = (self._axes if isinstance(self._axes, tuple)
                     else (self._axes,))
            shards = int(np.prod([mesh.shape[a] for a in names]))
            if self.chunk_rows % shards:
                raise ValueError(f"chunk_rows={self.chunk_rows} not "
                                 f"divisible by {shards} sample shards")
        import jax
        cpu = jax.devices()[0].platform == "cpu"
        # Zero-copy alias mode: on an unmeshed CPU backend the "device"
        # is the host, so publishing a chunk is a dlpack import of the
        # staging buffer (~0 cost) instead of device_put's slow
        # single-threaded memcpy. Recycling then fences on consumption
        # tokens (see release()). Anywhere a real transfer happens
        # (accelerators, meshed runs) we copy, and fence on the copy.
        self._alias = (cpu and mesh is None) if config.zero_copy is None \
            else bool(config.zero_copy)
        # Copy mode on CPU: device_put may itself alias host memory, so
        # leaves are defensively copied at put time.
        self._copy_on_put = cpu and not self._alias
        self._policy = config.retry or RetryPolicy.from_env()
        self._buffers = [self._alloc_buffer()
                         for _ in range(config.num_buffers)]
        # shared all-ones weights column for source-aliased full chunks
        # (immutable once built, so it needs no fence either)
        self._ones = _aligned_zeros(self.chunk_rows, self.dtype)
        self._ones[:] = 1
        self._inflight: List[Optional[DataBatch]] = \
            [None] * config.num_buffers
        self._release_q: queue.Queue = queue.Queue()
        self._released_idx = -1
        self._streaming = False
        self._num_chunks: Optional[int] = None
        # cumulative survivor counts per raw block, cached by the first
        # COMPLETE ascending pass with drop_invalid; permuted streams use
        # it to find which raw blocks feed chunk k without a full rescan
        self._block_cum: Optional[np.ndarray] = None
        self._ordered = False
        self.last_stats = StreamStats()

    # -- geometry -----------------------------------------------------------

    @property
    def num_chunks(self) -> Optional[int]:
        """Chunks per pass. Known a priori without filtering; with
        ``drop_invalid`` it depends on the survivor count and is cached
        after the first complete pass (None before that)."""
        if not self.config.drop_invalid:
            n = self.source.num_rows
            return max(1, -(-n // self.chunk_rows))
        return self._num_chunks

    def chunk_bytes(self) -> int:
        """Host bytes of one staged chunk (= device bytes per chunk)."""
        return sum(a.nbytes for a in self._buffers[0].values())

    def geometry(self) -> Optional[dict]:
        """Snapshot of the learned pass geometry (chunk count and, with
        ``drop_invalid``, the per-raw-block survivor cumsum), for
        checkpoint consumers: a killed permuted-epoch run resumes in a
        fresh process whose loader never streamed ascending, so the
        geometry must travel with the checkpoint. None until a first
        complete pass has learned it."""
        if self.num_chunks is None:
            return None
        g: dict = {"num_chunks": int(self.num_chunks)}
        if self._block_cum is not None:
            g["block_cum"] = np.array(self._block_cum)
        return g

    def restore_geometry(self, g: Optional[dict]) -> None:
        """Install a :meth:`geometry` snapshot taken from the SAME
        (immutable) source + config — permuted streams become available
        without re-paying the ascending discovery pass."""
        if g is None:
            return
        self._num_chunks = int(g["num_chunks"])
        if g.get("block_cum") is not None:
            self._block_cum = np.asarray(g["block_cum"], np.int64)

    # -- staging pool -------------------------------------------------------

    def _alloc_buffer(self) -> dict:
        c, dt = self.chunk_rows, self.dtype
        buf = {"labels": _aligned_zeros(c, dt),
               "weights": _aligned_zeros(c, dt)}
        if getattr(self.source, "offsets", None) is not None:
            buf["offsets"] = _aligned_zeros(c, dt)
        if self.source.ell_width is None:
            buf["x"] = _aligned_zeros((c, self.source.dim), dt)
        else:
            buf["idx"] = _aligned_zeros((c, self.source.ell_width), np.int32)
            buf["val"] = _aligned_zeros((c, self.source.ell_width), dt)
        return buf

    def _acquire(self, b: int, stop: threading.Event,
                 stats: StreamStats) -> dict:
        """Fence the consumer out of buffer ``b`` before the reader
        refills it. Runs on the reader thread only — the consumer's
        per-chunk path never blocks on device state. Copy mode fences on
        the chunk's own device arrays (transfer landed => staging free);
        alias mode pops the next consumption token (chunk order equals
        recycle order, so one token frees exactly one buffer)."""
        import jax
        prev = self._inflight[b]
        self._inflight[b] = None
        if prev is None:
            return self._buffers[b]
        t0 = time.perf_counter()
        fence = prev
        if self._alias:
            fence = None
            while not stop.is_set():
                try:
                    fence = self._release_q.get(timeout=0.1)
                    break
                except queue.Empty:
                    continue
        if fence is not None:
            for leaf in jax.tree_util.tree_leaves(fence):
                leaf.block_until_ready()  # host-sync-ok: reader-side buffer-recycle fence
        stats.transfer_wait_s += time.perf_counter() - t0
        return self._buffers[b]

    def _pack(self, buf: dict, fill: int, block: RawBlock,
              pos: int, take: int) -> None:
        end, bsl = fill + take, slice(pos, pos + take)
        buf["labels"][fill:end] = block.labels[bsl]
        if block.weights is not None:
            buf["weights"][fill:end] = block.weights[bsl]
        else:
            buf["weights"][fill:end] = 1.0
        if "offsets" in buf:
            buf["offsets"][fill:end] = block.offsets[bsl]
        if "x" in buf:
            buf["x"][fill:end] = block.x[bsl]
        else:
            buf["idx"][fill:end] = block.idx[bsl]
            buf["val"][fill:end] = block.val[bsl]

    def _zero_tail(self, buf: dict, fill: int) -> None:
        for a in buf.values():
            a[fill:] = 0

    def _alias_put(self, buf: dict) -> Optional[dict]:
        """Publish staging arrays as zero-copy device aliases. Returns
        None (and permanently downgrades to copy mode) if this backend
        will not alias — the pointer check catches a silent dlpack copy,
        which would reintroduce the triple host traffic AND break the
        token fence's assumption that the device reads staging memory."""
        import jax.numpy as jnp
        try:
            out = {}
            for k, a in buf.items():
                d = jnp.from_dlpack(a)
                if d.unsafe_buffer_pointer() != a.ctypes.data:
                    return None
                out[k] = d
            return out
        except Exception:   # noqa: BLE001 — alias is an optimization only
            return None

    @staticmethod
    def _to_batch(buf: dict, sparse: bool) -> DataBatch:
        if sparse:
            feats = F.SparseFeatures(indices=buf["idx"], values=buf["val"])
        else:
            feats = buf["x"]
        return DataBatch(features=feats, labels=buf["labels"],
                         offsets=buf.get("offsets"),
                         weights=buf["weights"])

    def _put(self, buf: dict) -> DataBatch:
        import jax
        if self._alias:
            aliased = self._alias_put(buf)
            if aliased is None:
                self._alias = False
                self._copy_on_put = True
            else:
                return self._to_batch(aliased,
                                      self.source.ell_width is not None)
        batch = self._to_batch(buf, self.source.ell_width is not None)
        if self._copy_on_put:
            batch = jax.tree_util.tree_map(np.copy, batch)
        if self.mesh is not None:
            from photon_tpu.parallel import mesh as M
            return M.shard_batch(batch, self.mesh, axis=self._axes)
        return jax.device_put(batch)

    def _alias_block(self, block: RawBlock) -> Optional[DataBatch]:
        """Source-alias fast path: a full chunk whose block arrays
        already have the exact staged layout (shape, dtype, row-major,
        64-byte aligned) is published without touching the staging pool
        at all — for a dense source these are views of the (immutable)
        design matrix, for CSR the block's freshly materialized ELL
        arrays, so no buffer is ever recycled and no fence is needed.
        This halves host memory traffic, which is the whole cost of
        streaming a memory-bound objective on CPU. Returns None when any
        array misses the layout contract (the staging path handles it)."""
        arrs = {"labels": block.labels,
                "weights": self._ones if block.weights is None
                else block.weights}
        if "offsets" in self._buffers[0]:
            arrs["offsets"] = block.offsets
        if self.source.ell_width is None:
            arrs["x"] = block.x
        else:
            arrs["idx"] = block.idx
            arrs["val"] = block.val
        proto = self._buffers[0]
        for k, a in arrs.items():
            if (a is None or a.shape != proto[k].shape
                    or a.dtype != proto[k].dtype
                    or not a.flags["C_CONTIGUOUS"]
                    or a.ctypes.data % _ALIGN):
                return None
        aliased = self._alias_put(arrs)
        if aliased is None:
            return None
        return self._to_batch(aliased, self.source.ell_width is not None)

    # -- reader thread ------------------------------------------------------

    def _read_raw(self, start: int, stop: int) -> RawBlock:
        chaos.chunk_read_error()
        d = chaos.chunk_read_delay()
        if d > 0:
            time.sleep(d)
        return self.source.read_block(start, stop)

    def _filter(self, block: RawBlock, stats: StreamStats) -> RawBlock:
        # deferred: validators reaches game.dataset, which itself imports
        # this package — a module-level import would be circular
        from photon_tpu.data import validators

        fv = block.x if block.x is not None else block.val
        bad = validators.invalid_chunk_mask(
            block.labels, self.config.task, offsets=block.offsets,
            weights=block.weights, feature_values=fv)
        n_bad = int(bad.sum())
        if not n_bad:
            return block
        stats.rows_dropped += n_bad
        keep = ~bad
        return RawBlock(*(None if a is None else a[keep] for a in block))

    def _produce(self, q: queue.Queue, stop: threading.Event,
                 start_chunk: int, stats: StreamStats) -> None:
        try:
            c, n = self.chunk_rows, self.source.num_rows
            # staged_i rotates the staging pool independently of the
            # global chunk index: source-aliased chunks consume no buffer
            emitted, staged_i, fill = 0, 0, 0
            survivors: List[int] = []
            buf = self._acquire(0, stop, stats)
            for s in range(0, n, c):
                if stop.is_set():
                    return
                t0 = time.perf_counter()
                block = with_retries(self._read_raw, s, min(s + c, n),
                                     op="stream.chunk_read",
                                     policy=self._policy)
                if self.config.drop_invalid:
                    block = self._filter(block, stats)
                    survivors.append(block.rows)
                if (self._alias and fill == 0 and block.rows == c
                        and not self.config.drop_invalid):
                    dev = (None if emitted < start_chunk
                           else self._alias_block(block))
                    if dev is not None or emitted < start_chunk:
                        self._emit_aliased(q, stop, emitted, c, dev, stats,
                                           t0)
                        emitted += 1
                        if stop.is_set():
                            return
                        continue
                pos, remaining = 0, block.rows
                while remaining:
                    take = min(c - fill, remaining)
                    self._pack(buf, fill, block, pos, take)
                    fill += take
                    pos += take
                    remaining -= take
                    if fill == c:
                        self._emit(q, stop, emitted,
                                   staged_i % self.config.num_buffers, c,
                                   start_chunk, stats, t0)
                        emitted += 1
                        staged_i += 1
                        fill = 0
                        if stop.is_set():
                            return
                        buf = self._acquire(
                            staged_i % self.config.num_buffers, stop, stats)
                        t0 = time.perf_counter()  # recycle wait != work
                stats.reader_busy_s += time.perf_counter() - t0
            if fill > 0 or emitted == 0:
                t0 = time.perf_counter()
                self._zero_tail(buf, fill)
                self._emit(q, stop, emitted,
                           staged_i % self.config.num_buffers, fill,
                           start_chunk, stats, t0)
                emitted += 1
            if self.config.drop_invalid:
                # complete ascending pass: cache the survivor geometry
                # permuted epochs need to locate chunk k's raw blocks
                self._block_cum = np.cumsum([0] + survivors,
                                            dtype=np.int64)
            self._q_put(q, stop, _EndOfPass(emitted))
        except BaseException as e:  # noqa: BLE001 — surfaced to consumer
            self._q_put(q, stop, _ReaderError(e))

    def _emit_aliased(self, q: queue.Queue, stop: threading.Event,
                      index: int, rows: int, dev: Optional[DataBatch],
                      stats: StreamStats, t0: float,
                      chunk_id: Optional[int] = None) -> None:
        stats.reader_busy_s += time.perf_counter() - t0
        if dev is None:   # resume fast-forward: nothing to publish
            return
        stats.chunks += 1
        stats.rows += rows
        stats.bytes_h2d += self.chunk_bytes()
        self._q_put(q, stop, DeviceChunk(
            index=index, rows=rows, batch=dev, fenced=False,
            chunk_id=index if chunk_id is None else chunk_id))

    def _emit(self, q: queue.Queue, stop: threading.Event, index: int,
              b: int, rows: int, start_chunk: int, stats: StreamStats,
              t0: float, chunk_id: Optional[int] = None) -> None:
        if index < start_chunk:
            # resume fast-forward: the raw read/pack had to happen (chunk
            # packing state carries across chunks) but the transfer is
            # skipped — the consumer restarts at its checkpointed cursor
            stats.reader_busy_s += time.perf_counter() - t0
            return
        dev = self._put(self._buffers[b])
        self._inflight[b] = dev
        stats.chunks += 1
        stats.rows += rows
        stats.bytes_h2d += self.chunk_bytes()
        stats.reader_busy_s += time.perf_counter() - t0
        self._q_put(q, stop, DeviceChunk(
            index=index, rows=rows, batch=dev,
            chunk_id=index if chunk_id is None else chunk_id))

    def _produce_ordered(self, q: queue.Queue, stop: threading.Event,
                         order: np.ndarray, start_pos: int,
                         stats: StreamStats) -> None:
        """Reader loop for ``stream(order=...)``: visit chunks of the
        canonical ascending composition in an arbitrary order. Without
        filtering, chunk k IS raw block k, so a visit is one direct
        block read (resume positions are skipped without any I/O —
        unlike the ascending path there is no cross-chunk packing
        state). With ``drop_invalid``, the cached survivor geometry maps
        chunk k's survivor-index span to the raw blocks that feed it;
        each visit reads and re-filters just those blocks, reproducing
        the ascending pass's packing bitwise."""
        try:
            c, n = self.chunk_rows, self.source.num_rows
            cum = self._block_cum
            emitted, staged_i = 0, 0
            buf = self._acquire(0, stop, stats)
            for pos in range(int(start_pos), len(order)):
                if stop.is_set():
                    return
                cid = int(order[pos])
                t0 = time.perf_counter()
                if cum is None:
                    lo, hi = cid * c, min(n, (cid + 1) * c)
                    block = with_retries(self._read_raw, lo, hi,
                                         op="stream.chunk_read",
                                         policy=self._policy)
                    rows = block.rows
                    if self._alias and rows == c:
                        dev = self._alias_block(block)
                        if dev is not None:
                            self._emit_aliased(q, stop, pos, rows, dev,
                                               stats, t0, chunk_id=cid)
                            emitted += 1
                            continue
                    self._pack(buf, 0, block, 0, rows)
                else:
                    # survivor-index span of chunk cid -> raw blocks
                    total = int(cum[-1])
                    lo, hi = cid * c, min(total, (cid + 1) * c)
                    b0 = int(np.searchsorted(cum, lo, side="right")) - 1
                    fill = 0
                    for b in range(b0, len(cum) - 1):
                        if int(cum[b]) >= hi:
                            break
                        block = with_retries(
                            self._read_raw, b * c, min(n, (b + 1) * c),
                            op="stream.chunk_read", policy=self._policy)
                        block = self._filter(block, stats)
                        if block.rows != int(cum[b + 1]) - int(cum[b]):
                            raise RuntimeError(
                                "survivor geometry changed between "
                                "passes: cached block survivor count "
                                f"{int(cum[b + 1]) - int(cum[b])} != "
                                f"refiltered {block.rows} (block {b}) — "
                                "the source must be immutable for the "
                                "lifetime of the stream")
                        p_lo = max(lo - int(cum[b]), 0)
                        p_hi = min(hi - int(cum[b]), block.rows)
                        take = p_hi - p_lo
                        self._pack(buf, fill, block, p_lo, take)
                        fill += take
                    rows = fill
                if rows < c:
                    self._zero_tail(buf, rows)
                self._emit(q, stop, pos,
                           staged_i % self.config.num_buffers, rows,
                           0, stats, t0, chunk_id=cid)
                emitted += 1
                staged_i += 1
                if stop.is_set():
                    return
                buf = self._acquire(staged_i % self.config.num_buffers,
                                    stop, stats)
            # the pass covers len(order) chunk positions even when a
            # resume skipped the leading ones (ascending-path parity)
            self._q_put(q, stop, _EndOfPass(len(order)))
        except BaseException as e:  # noqa: BLE001 — surfaced to consumer
            self._q_put(q, stop, _ReaderError(e))

    @staticmethod
    def _q_put(q: queue.Queue, stop: threading.Event, item) -> None:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return
            except queue.Full:
                continue

    # -- consumer -----------------------------------------------------------

    def release(self, chunk: DeviceChunk, token) -> None:
        """Hand buffer ``chunk`` back to the reader. ``token`` is any
        device pytree whose readiness implies every read of the chunk
        has completed — the streamed solver passes the carry its chunk
        partial produced. Required (per chunk, in order) by consumers
        that dispatch async compute on zero-copy chunks; a no-op in copy
        mode. Consumers that read chunks synchronously may skip it: the
        generator auto-releases when the next chunk is requested."""
        if (self._alias and self._streaming
                and chunk.index > self._released_idx):
            self._released_idx = chunk.index
            if chunk.fenced:
                self._release_q.put(token)
            elif not self._ordered:
                # source-aliased chunk: no buffer to recycle, but a
                # disk-backed source can use the token to fence page
                # release behind the consumption cursor. Skipped on
                # permuted streams — the source's release watermark
                # assumes a monotone row cursor, which only the
                # ascending order provides (permuted epochs trade the
                # RSS bound for random visit order).
                consumed = getattr(self.source, "consumed", None)
                if consumed is not None:
                    consumed(chunk.index * self.chunk_rows + chunk.rows,
                             token)

    def stream(self, start_chunk: int = 0,
               order=None) -> Iterator[DeviceChunk]:
        """Yield DeviceChunks in deterministic ascending order, chunk
        k+1's staging overlapping chunk k's compute. ``start_chunk``
        resumes mid-pass (chunks before it are read but not transferred).
        Stats for the pass land in ``self.last_stats`` on close.

        ``order`` (a permutation of ``range(num_chunks)``, e.g. from
        :func:`epoch_chunk_order`) visits the SAME ascending-composition
        chunks in that order: ``DeviceChunk.index`` is the visit
        position, ``DeviceChunk.chunk_id`` the stable chunk identity,
        and ``start_chunk`` counts positions in ``order``. With
        ``drop_invalid`` a permuted pass needs the survivor geometry a
        completed ascending pass caches — stream ascending once first.

        A new pass reuses the staging pool unfenced, so in zero-copy
        mode all chunks of the previous pass must be fully consumed
        before the next ``stream()`` begins — the streamed solver's
        per-pass host read of (f, g) guarantees exactly that."""
        if self._streaming:
            raise RuntimeError("one active stream per ChunkLoader")
        if order is not None:
            order = np.asarray(order, np.int64)
            if self.config.drop_invalid:
                if self._block_cum is None or self._num_chunks is None:
                    raise ValueError(
                        "stream(order=...) with drop_invalid needs the "
                        "survivor geometry of a completed ascending "
                        "pass — stream() once without order first")
                expect = self._num_chunks
            else:
                expect = self.num_chunks
            if (order.ndim != 1 or len(order) != expect
                    or not np.array_equal(np.sort(order),
                                          np.arange(expect))):
                raise ValueError(
                    f"order must be a permutation of range({expect}), "
                    f"got shape {order.shape}")
        self._streaming = True
        self._ordered = order is not None
        q: queue.Queue = queue.Queue(maxsize=self.config.num_buffers)
        stop = threading.Event()
        stats = StreamStats()
        self._inflight = [None] * self.config.num_buffers
        self._release_q = queue.Queue()
        self._released_idx = -1
        if order is not None:
            reader = threading.Thread(
                target=self._produce_ordered,
                args=(q, stop, order, start_chunk, stats),
                daemon=True, name="photon-stream-reader")
        else:
            reader = threading.Thread(
                target=self._produce, args=(q, stop, start_chunk, stats),
                daemon=True, name="photon-stream-reader")
        wall0 = time.perf_counter()
        reader.start()
        try:
            while True:
                t0 = time.perf_counter()
                item = q.get()
                stats.consumer_stall_s += time.perf_counter() - t0
                if isinstance(item, _ReaderError):
                    raise item.error
                if isinstance(item, _EndOfPass):
                    self._num_chunks = item.num_chunks
                    break
                yield item
                # consumer came back without releasing: it consumed the
                # chunk synchronously, so its own arrays are the fence
                self.release(item, item.batch)
        finally:
            stop.set()
            while reader.is_alive():
                try:
                    q.get_nowait()
                except queue.Empty:
                    pass
                reader.join(timeout=0.05)
            stats.wall_s = time.perf_counter() - wall0
            self.last_stats = stats
            self._streaming = False
            self._ordered = False
            try:
                from photon_tpu.obs.metrics import registry
                registry.counter("stream.chunks").inc(stats.chunks)
                if stats.rows_dropped:
                    registry.counter("stream.rows_dropped").inc(
                        stats.rows_dropped)
            except Exception:   # hygiene-ok — telemetry is best-effort
                pass
