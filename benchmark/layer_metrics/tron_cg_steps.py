"""Conjugate-gradient steps of ONE fit under TRON, the window's last: the
operator products the truncated CG ran, summed over the fit's outer
iterations (``solver_iterations`` counts those). The program's own count
(``SolverResult.cg_steps`` through
``FixedEffectCoordinate.tron_counts()``; ``benchmark/curvature_roofline.py``
reads it after the window, nothing inside). Matrix-free a step is a product
over X; explicit it is a ``[width, width]`` product and the build is what
costs. Repeats exactly from fit to fit."""

from benchmark import curvature_roofline

LAYER = "cd_solver"
UNIT = "iterations"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "train_rows_per_s"


def read(run):
    counts = curvature_roofline.solver_counts(run)
    return None if counts is None else counts["cg_steps"]
