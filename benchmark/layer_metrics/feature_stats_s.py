"""Seconds of the feature-statistics pass in set-up: the sum of the
program's phases ``ingest/feature_stats/<shard>`` (``photon_tpu/cli/
train.py::compute_shard_statistics``: placing the shard's matrix, the
statistics program, and the wait for its result, so that the pass's copy of
the matrix is gone before the estimator places its own). ``None`` on a
program without the phase, and in a job that normalises nothing."""

from benchmark.layer_metrics import _ingest

LAYER = "ingest"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    return _ingest.phase_seconds("ingest/feature_stats/")
