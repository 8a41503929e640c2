"""Bytes the program placed on the device for its coordinates' datasets:
its always-on counter ``ingest.h2d_bytes{coordinate}``, summed over the
coordinates (``nbytes`` of every array placed; ``game/dataset.py``)."""

LAYER = "ingest"
UNIT = "GB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    from photon_tpu.obs.metrics import registry

    found = [value for key, value in registry.snapshot()["counters"].items()
             if key.startswith("ingest.h2d_bytes")]
    return sum(found) / 1e9 if found else None
