"""Median over the window's requests of: due time to the response leaving
``pump``, on the benchmark's clock. A request left unanswered counts as
infinitely late."""

import numpy as np

UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"


def latency_ms(window, q):
    """The q-th percentile, or None when fewer than 10 samples lie beyond
    it; an unanswered request is later than any answered one."""
    lat = np.where(np.isnan(window["latency"]), np.inf, window["latency"])
    if len(lat) * min(q, 100 - q) / 100.0 < 10:
        return None
    return float(np.percentile(lat, q, method="higher")) * 1e3


def read(run):
    return latency_ms(run.window, 50)
