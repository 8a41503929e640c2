"""TRON's curvature work as a share of its roofline, in a configuration
whose fit is one dense fixed-effect solve: the least seconds the chip could
take for the curvature work the SOLVER counted in the traced fits (operator
builds x one symmetric ``X^T D X`` where the Hessian is explicit, CG steps
x one product over ONE read of X where it is matrix-free;
``benchmark/curvature_roofline.py``, ``roofline.least_seconds``) over ALL
the seconds the device was busy in the traced window, value-and-gradient
evaluations and re-layout copies included: ``aggregator_roofline``'s
convention. The symmetric half at the bfloat16 peak bounds a build at
epsilon's shape (10.8 ms against 5.2 ms for the read)."""

from benchmark import curvature_roofline, trace_reader

LAYER = "aggregators"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"


def read(run):
    coords = run.cfg["coordinates"]
    if (run.trace is None or not run.trace.ops or run.peaks is None
            or len(coords) != 1 or run.cfg["sweeps"] != 1):
        return None
    counts = curvature_roofline.solver_counts(run)
    path = curvature_roofline.traced_path()
    if counts is None or path is None:
        return None
    fits = sum(1 for f in run.traced["fits"] if "error" not in f)
    seconds = curvature_roofline.least_seconds(
        path, counts["hessian_builds"], counts["cg_steps"],
        run.cfg["rows"], coords[0]["width"], run.peaks)
    return 100.0 * fits * seconds / trace_reader.busy_s(run.trace)
