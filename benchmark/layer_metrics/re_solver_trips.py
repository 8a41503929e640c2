"""Trips of the vmapped per-entity solver loops in one fit: each size
bucket's largest per-entity iteration count, summed over buckets,
random-effect coordinates and sweeps. A fit's loop seconds follow this
(every trip runs the whole bucket), not ``solver_iterations``' last-sweep
maximum. The program's ``obs.solver.lane_counts()``, stat ``trips``
(``benchmark/layer_metrics/_lanes.py``): the last fit of the window."""

from benchmark.layer_metrics import _lanes

LAYER = "cd_solver"
UNIT = "iterations"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "train_rows_per_s"


def read(run):
    return _lanes.lane_iterations("trips")
