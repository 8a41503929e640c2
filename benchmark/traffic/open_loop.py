"""Traffic kind ``open_loop``: requests released on a Poisson schedule at a
fixed rate, whether or not earlier ones have been answered.

Independent users behind a ranking front end. A sender thread sleeps to
each request's due time (exponential gaps at ``rate_rps``, drawn from the
seed) and hands it over a queue; the main thread does what
``photon_tpu/cli/serve.run`` does with a line from its reader thread:
take one (waiting at most its 50 ms tick), submit it, pump once, and pump
on an idle tick. A request is timed from when it was DUE to when its
response left ``pump``, so a stall is charged to every request it delayed;
how late the sender released each one is kept beside it.

Samples: ``sent``, ``latency`` and ``late`` ([n] seconds, by request),
``responses`` (request number -> response), ``seconds``, ``stages`` (the
engine's own stage sums and counts over the window).
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

from benchmark import generators as G
from benchmark.systems import serving

GAP_LABELS = ("submit+pump", "idle:")
TICK_S = 0.05                     # cli/serve.py's _TICK_S


setup = serving.setup
verify = serving.verify


def _sender(due: np.ndarray, first: int, t0: float, out: queue.Queue,
            late: np.ndarray) -> None:
    for k, t in enumerate(due):
        while True:
            wait = t0 + t - time.perf_counter()
            if wait <= 0:
                break
            time.sleep(wait)
        late[k] = -wait
        out.put(first + k)
    out.put(None)


def measure(ctx, state, seconds: float) -> dict:
    import jax

    engine, mix = state["engine"], state["mix"]
    rate = ctx.cell["traffic"]["rate_rps"]
    first = state["next"]
    rng = np.random.Generator(np.random.PCG64(
        G.stream(ctx.seed, "arrivals", first)))
    due = G.arrival_times(1.0 - rng.random(int(rate * seconds * 1.2) + 16), rate)
    due = due[due < seconds]
    state["next"] += len(due)
    late = np.zeros(len(due))
    done = np.full(len(due), np.nan)
    responses = {}
    before = serving.histogram_totals()
    released: queue.Queue = queue.Queue()
    t0 = time.perf_counter() + 0.05
    sender = threading.Thread(target=_sender, name="bench-sender",
                              args=(due, first, t0, released, late))
    sender.start()

    def take(got) -> None:
        now = time.perf_counter()
        for r in got:
            responses[int(r.uid)] = r
            done[int(r.uid) - first] = now

    try:
        while True:
            try:
                with jax.profiler.TraceAnnotation("idle:no-request"):
                    i = released.get(timeout=TICK_S)
            except queue.Empty:
                take(engine.pump())
                continue
            if i is None:
                break
            with jax.profiler.TraceAnnotation("submit+pump"):
                refused = engine.submit(mix.request(i))
                if refused is not None:
                    take([refused])
                take(engine.pump())
        take(engine.drain())
    finally:
        sender.join()
    return {"sent": len(due), "responses": responses,
            "latency": done - (t0 + due), "late": late, "seconds": seconds,
            "stages": serving.totals_since(before)}
