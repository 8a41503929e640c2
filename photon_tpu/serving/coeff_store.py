"""Two-tier per-coordinate coefficient store: HBM hot set over a
host-RAM cold tier.

Serving previously required every random-effect gather table fully
resident in device memory, capping entity count at HBM. This module puts
a fixed-capacity device gather table (the HOT tier) in front of an
``io/cold_store.ColdStore`` (the COLD tier: all N rows, mmapped host
RAM, sorted by entity id) so a 10M+-entity coordinate serves from a
fixed HBM budget with a traffic-adaptive LRU hot set — the photon_tpu
analog of Photon ML's PalDB off-heap coefficient index, with the
memory-hierarchy placement story of Snap ML / DuHL.

Hot-table layout (leading dim is a compiled-program shape, so capacity
is a power of two and never changes after construction)::

    rows 0..C-1   hot slots (LRU over entity traffic)
    row  C        the unknown/cold zero row — UNKNOWN_ENTITY and
                  COLD_MISS requests gather it, contributing exactly 0
    row  C+1      scratch row absorbing the padding writes of the
                  fixed-shape transfer scatter

Concurrency contract (the part that keeps "zero steady-state compiles"
AND "no hot-path stalls" true at once):

- Scoring threads hold the owning model's ``transfer_lock`` across
  assemble + scorer DISPATCH (not execution): lookups, the table
  reference read, and the jit call happen against one consistent
  (maps, table) snapshot.
- The background transfer thread reads cold rows and stages them on
  device OUTSIDE the lock (this is the only path allowed to touch the
  host), then under the lock commits: ONE donated fixed-shape scatter,
  table-reference swap, and slot-map updates — atomically, so a scorer
  can never see new maps with an old table or vice versa, and the
  donated buffer can never be consumed between a scorer's table read
  and its dispatch.
- A request whose entity is still cold at pop time gathers the zero row
  and gets typed ``COLD_MISS`` degradation; the miss (and the admission
  lookahead before it) promotes the rows for next time. The scoring
  path never performs a synchronous host->device upload.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from photon_tpu.io.cold_store import ColdStore
from photon_tpu.obs.metrics import registry as _metrics
from photon_tpu.serving.types import CoeffStoreConfig
from photon_tpu.utils import compile_cache, jitcache

#: lookup outcomes (status strings double as metrics labels)
HIT = "hit"
COLD = "cold_miss"
UNKNOWN = "unknown"

_PREFETCH_BUCKETS = tuple(50e-6 * 1.6 ** i for i in range(32))


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def _build_scatter(shape: Tuple[int, int], batch: int, dtype) -> object:
    """Fixed-shape donated row scatter: the one program every cold->hot
    transfer reuses. Keyed by (table shape, batch, dtype) in the
    process-wide jitcache so a swapped-in model with the same geometry
    shares the compiled executable."""
    import jax

    def build():
        def scatter(table, idx, rows):
            return table.at[idx].set(rows)

        return jax.jit(scatter, donate_argnums=0)

    return jitcache.get_or_build(
        ("coeff_scatter", shape[0], shape[1], batch, str(np.dtype(dtype))),
        build)


class TwoTierCoeffStore:
    """One coordinate's hot-set gather cache over its cold tier.

    All ``*_locked`` methods require the caller to hold ``lock`` (the
    owning model's transfer lock, shared by every store of that model so
    one critical section covers a whole multi-coordinate batch).
    """

    def __init__(self, cold: ColdStore, config: CoeffStoreConfig,
                 lock: Optional[threading.RLock] = None,
                 start_thread: bool = True, dtype=np.float32):
        import jax

        self.cold = cold
        self.config = config
        self.coordinate_id = cold.coordinate_id
        self.slot_width = cold.slot_width
        self.dtype = np.dtype(dtype)
        row_bytes = self.slot_width * self.dtype.itemsize
        cap = (config.hot_capacity if config.hot_capacity is not None
               else config.hbm_budget_bytes // row_bytes)
        if cap < 1:
            raise ValueError(
                f"hot budget below one row ({row_bytes}B) for coordinate "
                f"{self.coordinate_id!r}")
        self.capacity = _pow2_floor(cap)
        self.unknown_row = self.capacity           # explicit zero row
        self._scratch_row = self.capacity + 1      # absorbs scatter padding
        self.transfer_batch = min(config.transfer_batch, self.capacity)
        self.lock = lock if lock is not None else threading.RLock()

        # hot-tier host mirrors (mirroring model_state's host-side
        # (entity,feature)->slot maps): entity id -> hot slot in LRU
        # order, slot -> (entity id, cold row), and the per-slot
        # projection rows so assemble's slot replay never touches the
        # cold mmap for a hot entity
        self._hot: "collections.OrderedDict[str, int]" = \
            collections.OrderedDict()
        self._slot_info: List[Optional[Tuple[str, int]]] = \
            [None] * self.capacity
        self._free: List[int] = list(range(self.capacity - 1, -1, -1))
        self._hot_proj = np.full((self.capacity, self.slot_width), -1,
                                 dtype=np.int32)
        # pending promotions: entity id -> cold row, insertion-ordered
        self._pending: "collections.OrderedDict[str, int]" = \
            collections.OrderedDict()

        self._table = jax.device_put(
            np.zeros((self.capacity + 2, self.slot_width), self.dtype))
        # build AND warm the transfer program at store construction —
        # both inside the warmup phase, so the first real promotion is
        # compile-free and nothing here counts as a steady-state compile
        # (padding writes target the scratch row; the zero row stays zero)
        self._scatter = None
        compile_cache.warmup((self.transfer_batch,), self._warm_scatter)

        # held across one whole transfer cycle (all three phases) and by
        # the nearline publisher across an entire delta publish — pausing
        # the transfer thread at a cycle boundary without ever blocking
        # the scoring path, which only needs ``lock``. Acquire order is
        # always publish_lock -> lock, never the reverse.
        self._publish_lock = threading.Lock()

        self._stats_lock = threading.Lock()
        self._counts = {"hits": 0, "misses": 0, "cold_misses": 0,
                        "unknown": 0, "promotes": 0, "evictions": 0,
                        "transfers": 0}
        self._wakeup = threading.Event()
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        if start_thread:
            self._thread = threading.Thread(
                target=self._transfer_loop, daemon=True,
                name=f"coeff-transfer-{self.coordinate_id}")
            self._thread.start()

    # -- scoring-path API (caller holds self.lock) --------------------------

    @property
    def table(self):
        """Current device gather table [capacity + 2, slot_width]. Read
        under ``lock`` and used for the dispatch inside the same hold —
        the commit path swaps it atomically with the slot maps."""
        return self._table

    def lookup_locked(self, entity_id: str) -> Tuple[int, str]:
        """(gather row, status) for one request's entity.

        HIT: the hot slot (LRU-touched). COLD: the zero row now, plus a
        queued promotion so the next request finds the entity hot.
        UNKNOWN: the zero row, entity not in the model at all.
        """
        slot = self._hot.get(entity_id)
        if slot is not None:
            self._hot.move_to_end(entity_id)
            self._bump("hits")
            return slot, HIT
        row = self._pending.get(entity_id)
        if row is None:
            row = self.cold.entity_row(entity_id)
            if row is None:
                self._bump("unknown")
                return self.unknown_row, UNKNOWN
            self._pending[entity_id] = row
            self._wakeup.set()
        self._bump("misses")
        self._bump("cold_misses")
        return self.unknown_row, COLD

    def proj_row_locked(self, slot: int) -> np.ndarray:
        """Projection row (global col per local slot, -1 padded) for a
        HIT slot — host mirror, no cold-tier touch."""
        return self._hot_proj[slot]

    # -- admission lookahead ------------------------------------------------

    def prefetch(self, entity_id: str) -> None:
        """Admission-time lookahead: resolve the entity and queue its
        cold->hot upload so the rows are usually resident by batch-pop
        time. Cheap, non-blocking, safe from any thread."""
        if not self.config.prefetch:
            return
        with self.lock:
            if entity_id in self._hot:
                self._hot.move_to_end(entity_id)
                return
            if entity_id in self._pending:
                return
            row = self.cold.entity_row(entity_id)
            if row is None:
                return
            self._pending[entity_id] = row
        self._wakeup.set()

    # -- transfer thread ----------------------------------------------------

    def _warm_scatter(self, batch: int) -> None:
        import jax

        if self._scatter is None:
            self._scatter = _build_scatter(
                (self.capacity + 2, self.slot_width), batch, self.dtype)
        idx = jax.device_put(
            np.full(batch, self._scratch_row, dtype=np.int32))
        rows = jax.device_put(np.zeros((batch, self.slot_width),
                                       self.dtype))
        self._table = self._scatter(self._table, idx, rows)
        self._table.block_until_ready()  # host-sync-ok: warmup only

    def _transfer_loop(self) -> None:
        while not self._stop:
            self._wakeup.wait(timeout=0.05)
            self._wakeup.clear()
            if self._stop:
                return
            try:
                while self.drain_once():
                    pass
            except Exception:  # noqa: BLE001 — prefetch must never kill
                # the process; a failed transfer just leaves entities
                # cold (typed COLD_MISS), and the next cycle retries
                _metrics.counter("serving.coeff_store.transfer_errors",
                                 coordinate=self.coordinate_id).inc()

    def drain_once(self) -> int:
        """Run one coalesced transfer cycle; returns rows promoted.

        Phase 1 (locked): reserve up to ``transfer_batch`` pending
        entities and their slots — free slots first, then LRU eviction.
        An evicted victim disappears from the maps immediately (requests
        for it degrade to COLD_MISS until re-promoted; its stale device
        rows are unreachable because nothing maps to the slot).
        Phase 2 (unlocked): cold mmap read + ONE ``jax.device_put`` of
        the padded row block. Phase 3 (locked): one donated fixed-shape
        scatter + atomic map/table commit.

        The whole cycle runs under ``publish_lock`` so a nearline delta
        publish holding it sees a quiescent store: no cold-file read and
        no donated scatter can interleave with its staged-table build,
        cold rewrite, or commit.
        """
        with self._publish_lock:
            return self._drain_cycle()

    def _drain_cycle(self) -> int:
        import jax

        t0 = time.perf_counter()
        batch: List[Tuple[str, int, int]] = []  # (entity, cold row, slot)
        with self.lock:
            while self._pending and len(batch) < self.transfer_batch:
                entity_id, row = self._pending.popitem(last=False)
                if entity_id in self._hot:
                    continue
                if self._free:
                    slot = self._free.pop()
                else:
                    victim, slot = self._hot.popitem(last=False)
                    self._slot_info[slot] = None
                    self._bump("evictions")
                    _metrics.counter("serving.coeff_store.evictions",
                                     coordinate=self.coordinate_id).inc()
                batch.append((entity_id, row, slot))
        if not batch:
            return 0

        rows_idx = np.asarray([r for _, r, _ in batch], dtype=np.int64)
        coef_rows = self.cold.read_rows(rows_idx)
        proj_rows = self.cold.read_proj_rows(rows_idx)
        m = len(batch)
        buf = np.zeros((self.transfer_batch, self.slot_width), self.dtype)
        buf[:m] = coef_rows
        idx = np.full(self.transfer_batch, self._scratch_row,
                      dtype=np.int32)
        idx[:m] = [s for _, _, s in batch]
        dev_rows = jax.device_put(buf)
        dev_idx = jax.device_put(idx)

        with self.lock:
            self._table = self._scatter(self._table, dev_idx, dev_rows)
            for i, (entity_id, row, slot) in enumerate(batch):
                self._hot[entity_id] = slot
                self._hot.move_to_end(entity_id)
                self._slot_info[slot] = (entity_id, row)
                self._hot_proj[slot] = proj_rows[i]
            occupancy = len(self._hot)
        self._bump("promotes", m)
        self._bump("transfers")
        _metrics.counter("serving.coeff_store.promotes",
                         coordinate=self.coordinate_id).inc(m)
        _metrics.gauge("serving.coeff_store.hot_occupancy",
                       coordinate=self.coordinate_id).set(occupancy)
        _metrics.histogram("serving.coeff_store.prefetch_seconds",
                           buckets=_PREFETCH_BUCKETS,
                           coordinate=self.coordinate_id).observe(
            time.perf_counter() - t0)
        return m

    def drain_prefetch(self, timeout_s: float = 10.0) -> bool:
        """Block until every queued promotion has landed (tests' phase
        boundaries — never the scoring path). True on quiescence."""
        deadline = time.monotonic() + timeout_s
        while True:
            moved = self.drain_once()
            with self.lock:
                pending = len(self._pending)
            if moved == 0 and pending == 0:
                return True
            if time.monotonic() > deadline:
                return False

    # -- nearline delta publish --------------------------------------------

    @property
    def publish_lock(self) -> threading.Lock:
        """Cycle-granular transfer pause for the nearline publisher.
        Hold it (before ``lock``) across staging + commit so the staged
        table copy can never race a donated transfer scatter. The
        scoring path is untouched — it only takes ``lock``."""
        return self._publish_lock

    def hot_slot_locked(self, entity_id: str) -> Optional[int]:
        """Hot slot of ``entity_id`` without an LRU touch (publisher
        bookkeeping is not traffic), or None when not resident."""
        return self._hot.get(entity_id)

    def set_hot_proj_locked(self, slot: int, proj_row: np.ndarray) -> None:
        """Update the host projection mirror of a hot slot after its
        device row was republished."""
        self._hot_proj[slot] = np.asarray(proj_row, dtype=np.int32)

    def commit_table_locked(self, table) -> None:
        """Swap in a republished gather table (same shape; built by the
        publisher's non-donated scatter-copy)."""
        self._table = table

    def evict_locked(self, entity_id: str) -> bool:
        """Drop one entity from the hot tier (rollback of a published
        append). Its stale device rows become unreachable, exactly like
        an LRU eviction."""
        slot = self._hot.pop(entity_id, None)
        self._pending.pop(entity_id, None)
        if slot is None:
            return False
        self._slot_info[slot] = None
        self._hot_proj[slot] = -1
        self._free.append(slot)
        return True

    def refresh_cold_locked(self) -> int:
        """Reopen the cold file and remap every cached cold-row index by
        entity id — required after ``apply_cold_store_delta`` /
        ``upgrade_cold_store`` / rollback replaced or mutated the file
        (the old mmap may see a replaced inode). v2 storage rows are
        append-stable so remaps are usually identity; entities absent
        from the refreshed file (a rolled-back append) are evicted.
        Returns the number of entities dropped. Caller holds both
        ``publish_lock`` and ``lock``."""
        new_cold = ColdStore(self.cold.path)
        dropped = 0
        for slot, info in enumerate(self._slot_info):
            if info is None:
                continue
            entity_id, _old_row = info
            row = new_cold.entity_row(entity_id)
            if row is None:
                if self.evict_locked(entity_id):
                    dropped += 1
            else:
                self._slot_info[slot] = (entity_id, row)
        for entity_id in list(self._pending):
            row = new_cold.entity_row(entity_id)
            if row is None:
                del self._pending[entity_id]
                dropped += 1
            else:
                self._pending[entity_id] = row
        self.cold = new_cold
        return dropped

    # -- accounting ---------------------------------------------------------

    def _bump(self, key: str, n: int = 1) -> None:
        with self._stats_lock:
            self._counts[key] += n
        if key in ("hits", "misses"):
            _metrics.counter(f"serving.coeff_store.{key}",
                             coordinate=self.coordinate_id).inc(n)

    def stats(self) -> dict:
        with self._stats_lock:
            counts = dict(self._counts)
        with self.lock:
            occupancy = len(self._hot)
            pending = len(self._pending)
        lookups = counts["hits"] + counts["misses"] + counts["unknown"]
        return {
            "coordinate_id": self.coordinate_id,
            "capacity": self.capacity,
            "occupancy": occupancy,
            "pending": pending,
            "slot_width": self.slot_width,
            "hot_bytes": int((self.capacity + 2) * self.slot_width
                             * self.dtype.itemsize),
            "cold_bytes": self.cold.file_bytes,
            "num_entities": self.cold.num_entities,
            "hit_rate": (counts["hits"] / lookups) if lookups else None,
            **counts,
        }

    def close(self) -> None:
        self._stop = True
        self._wakeup.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
