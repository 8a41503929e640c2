"""Share of a lane-batched grid fit's lane-iterations that a lane still
needed: the lambda lanes' own iteration counts over (lanes x the largest
lane's count). The K lanes of ``GameEstimator.fit_swept`` run as ONE
vmapped loop that trips until the slowest lane is done; the rest ran on
lanes that were already done and only rode along. The program's
``obs.solver.lane_counts()`` (``benchmark/layer_metrics/_lanes.py``), where
a fixed effect's lambda lanes are one loop's bucket; ONE fit's, the
window's last. A program whose swept fit records no lane counts reads
nothing."""

from benchmark.layer_metrics import re_lane_occupancy

LAYER = "cd_solver"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "train_rows_per_s"

read = re_lane_occupancy.read       # the same sum over capacity
