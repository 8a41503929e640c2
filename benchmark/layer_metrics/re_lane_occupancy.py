"""Share of the vmapped per-entity loops' lane-iterations that an entity
still needed: the entities' own iteration counts over (entities of a bucket
x the bucket's largest count), summed over the buckets, coordinates and
sweeps of one fit. The rest ran on lanes whose entity was already done and
only rode along to the bucket's slowest. The program's
``obs.solver.lane_counts()`` (``benchmark/layer_metrics/_lanes.py``)."""

from benchmark.layer_metrics import _lanes

LAYER = "cd_solver"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "train_rows_per_s"


def read(run):
    needed, ran = (_lanes.lane_iterations(s) for s in ("sum", "capacity"))
    return 100.0 * needed / ran if needed is not None and ran else None
