"""Double-buffered entity-block staging for blocked random-effect training.

The blocked fits (``RandomEffectCoordinate.update_model_blocked_swept``, and
``update_model_blocked``, its one-lane case: one host loop,
``_solve_blocked``) used to stream buckets strictly sequentially:
host→device copy of bucket b, solve, host copy-back, repeat — the
staging time of every bucket sat on the critical path. This module moves
staging onto a prefetch thread with the consumption-token fence pattern
of ``data/streaming.ChunkLoader``: while bucket b solves on device, the
reader stages bucket b+1 from host RAM (or wherever the dataset's block
pytree lives — on real hardware this is the H2D DMA the solve hides).

Fence protocol (the part that keeps a lagging async solve from ever
seeing a recycled buffer):

- the reader holds ``depth`` staging tokens; it stages a bucket only
  after acquiring one, so at most ``depth`` buckets are in flight —
  host+device staging memory is bounded by the planner's
  double-buffered footprint (parallel/memory), never by ladder length;
- the reader fences its OWN transfer (``block_until_ready`` on the
  staged pytree, reader thread only — never the consumer's solve path)
  before publishing, so the consumer dequeues fully-landed arrays;
- the consumer returns the token via :meth:`BlockPrefetcher.release`
  only after the bucket's results are back on the host, which is the
  proof the solve consumed the staged arrays.

Chaos hooks ``chaos.re_block_read_delay`` / ``chaos.re_block_read_error``
fire inside the reader (the error path retried under the
``resilience/retry`` env knobs), so fault injection exercises the real
overlap path. The reader also keeps the busy/stall clocks that
``utils/flops.re_block_overlap`` turns into the pipeline's overlap
gauges.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Optional, Sequence

import jax

from photon_tpu.resilience import chaos
from photon_tpu.resilience.retry import RetryPolicy, with_retries

_SENTINEL = object()


def staged_bytes(tree) -> int:
    """Total array bytes of a staged block pytree (the measured side of
    the planner's ``data_bytes``)."""
    return sum(int(getattr(leaf, "nbytes", 0))
               for leaf in jax.tree_util.tree_leaves(tree))


class BlockPrefetcher:
    """Stage entity blocks ``start_block..`` onto the device one bucket
    ahead of the solve loop.

    The consumer calls :meth:`get` (blocking) once per bucket, in
    ascending order, and :meth:`release` after copying that bucket's
    results back to the host; :meth:`close` joins the thread (idempotent
    — call it in a ``finally``)."""

    def __init__(self, blocks: Sequence, *, start_block: int = 0,
                 depth: int = 2, device=None,
                 policy: Optional[RetryPolicy] = None):
        self._blocks = blocks
        self._start = int(start_block)
        self._device = device
        self._policy = policy or RetryPolicy.from_env()
        self._out: "queue.Queue" = queue.Queue()
        self._tokens: "queue.Queue" = queue.Queue()
        for _ in range(max(1, int(depth))):
            self._tokens.put(None)
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        # pipeline clocks for flops.re_block_overlap
        self.reader_busy_s = 0.0
        self.consumer_stall_s = 0.0
        self.bytes_staged = 0
        self.blocks_staged = 0
        self._t0 = time.perf_counter()
        self._thread = threading.Thread(
            target=self._run, name="re-block-prefetch", daemon=True)
        self._thread.start()

    # -- reader side ---------------------------------------------------

    def _stage(self, bi: int):
        def read():
            chaos.re_block_read_error()
            delay = chaos.re_block_read_delay()
            if delay:
                time.sleep(delay)
            staged = jax.device_put(self._blocks[bi], self._device)
            # buffer-recycle fence on the READER thread (the streaming
            # loader's pattern): the consumer must dequeue fully-landed
            # arrays, and the solve path itself stays sync-free
            jax.block_until_ready(staged)  # host-sync-ok: reader-side staging fence
            return staged

        return with_retries(read, op="re.block_read", policy=self._policy)

    def _run(self) -> None:
        try:
            for bi in range(self._start, len(self._blocks)):
                # consumption-token fence: wait for a free staging slot
                while True:
                    if self._stop.is_set():
                        return
                    try:
                        self._tokens.get(timeout=0.1)
                        break
                    except queue.Empty:
                        continue
                t0 = time.perf_counter()
                staged = self._stage(bi)
                self.reader_busy_s += time.perf_counter() - t0
                self.bytes_staged += staged_bytes(staged)
                self.blocks_staged += 1
                self._out.put((bi, staged))
            self._out.put(_SENTINEL)
        except BaseException as e:  # surfaces on the consumer's get()
            self._error = e
            self._out.put(_SENTINEL)

    # -- consumer side -------------------------------------------------

    def get(self, bi: int):
        """Blocking dequeue of bucket ``bi``'s staged block (buckets are
        produced in order; time spent here is consumer stall — the part
        of staging the pipeline failed to hide)."""
        t0 = time.perf_counter()
        item = self._out.get()
        self.consumer_stall_s += time.perf_counter() - t0
        if item is _SENTINEL:
            if self._error is not None:
                raise self._error
            raise RuntimeError(
                f"block prefetcher exhausted before bucket {bi}")
        got, staged = item
        if got != bi:
            raise RuntimeError(
                f"block prefetcher out of order: wanted {bi}, got {got}")
        return staged

    def release(self) -> None:
        """Return one staging token — the consumer's proof that the
        bucket's results are back on the host and its staged arrays are
        consumable."""
        self._tokens.put(None)

    @property
    def wall_s(self) -> float:
        return time.perf_counter() - self._t0

    def close(self) -> None:
        """Stop and join the reader (idempotent; safe mid-stream — e.g.
        a ``SimulatedKill`` unwinding the solve loop)."""
        self._stop.set()
        # unblock a reader parked on a token or let a finished one exit
        try:
            while True:
                self._out.get_nowait()
        except queue.Empty:
            pass
        self._tokens.put(None)
        self._thread.join(timeout=5.0)
