"""Solver iterations of the last sweep, summed over the coordinates: a
fixed effect's own count, a random effect's largest per-entity count.
Repeats exactly from fit to fit, or the run is not ``correct``."""

LAYER = "cd_solver"
UNIT = "iterations"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "train_rows_per_s"


def read(run):
    return sum(run.state["first"]["iterations"].values())
