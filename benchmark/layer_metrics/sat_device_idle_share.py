"""``device_idle_share`` in the saturated serving cell."""

from benchmark.layer_metrics.device_idle_share import read  # noqa: F401

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "serve_rps"
