"""Traffic kind ``closed_loop``: ``callers`` callers, each sending its next
request when the reply to its last one arrives.

A bounded-concurrency upstream, or a batch scoring job: the system is
offered as much as it completes, so nothing is refused or shed and the
completed rate is its capacity. One thread: it submits, pumps, and for
every response submits that caller's next request.

Samples: ``sent``, ``latency`` ([n] seconds from submit to the response
leaving ``pump``), ``responses`` (request number -> response), ``seconds``,
``stages`` (the engine's own stage sums and counts over the window).
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.systems import serving

GAP_LABELS = ("submit+pump",)


setup = serving.setup
verify = serving.verify


def measure(ctx, state, seconds: float) -> dict:
    import jax

    engine, mix = state["engine"], state["mix"]
    before = serving.histogram_totals()
    submitted, latency, responses = {}, [], {}
    t_end = time.perf_counter() + seconds

    def send() -> None:
        i = state["next"]
        state["next"] += 1
        submitted[i] = time.perf_counter()
        refused = engine.submit(mix.request(i))
        if refused is not None:
            responses[i] = refused

    for _ in range(ctx.cell["traffic"]["callers"]):
        send()
    while time.perf_counter() < t_end:
        with jax.profiler.TraceAnnotation("submit+pump"):
            got = engine.pump()
            now = time.perf_counter()
            for r in got:
                i = int(r.uid)
                responses[i] = r
                latency.append(now - submitted[i])
                send()
    done_in_window = len(latency)
    for r in engine.drain():           # the callers' last requests
        responses[int(r.uid)] = r
    return {"sent": len(submitted), "responses": responses,
            "completed": done_in_window, "latency": np.asarray(latency),
            "seconds": seconds, "stages": serving.totals_since(before)}
