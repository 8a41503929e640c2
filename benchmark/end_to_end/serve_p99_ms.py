"""99th percentile over the window's requests of: due time to the response
leaving ``pump``; reported only with at least 10 samples beyond it."""

from benchmark.end_to_end.serve_p50_ms import latency_ms

UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
    return latency_ms(run.window, 99)
