"""What the three variance readers share: the device seconds a traced
window spent computing coefficient variances, how many variances the
PROGRAM says it computed in the traced fits, and the least seconds the chip
could take for one. Beside ``curvature_roofline.py``, whose explicit-build
arithmetic this imports.

The program puts everything a variance runs under ``optim/variance/
{hessian,factor_solve,diagonal}`` (``photon_tpu/optim/problem.py::
coefficient_variances``); the aggregators it calls keep their own names
nested in ``hessian``, so an operation belongs to the variance when
``optim/variance/`` stands ANYWHERE in its scope path, not only innermost.
A program from before it named them has no such path and every reader
here returns ``None``.

The least seconds of ONE FULL variance, whatever implements it: the longer
of one read of X and the SYMMETRIC half of ``X^T D X``, rows x width x
(width + 1) operations, at the chip's bfloat16 peak
(``curvature_roofline.weighted_gram``, ``roofline.least_seconds``): 10.77 ms
at 530,000 x 2,000 on a TPU v5e, compute-bound. The margins' read of X
(it could ride in the same pass), and the factorisation and inverse
(width^3 operations a triangle: 0.04 ms at that peak) are left out, so a
multi-pass precision, a second read of X or a latency-bound factorisation
read as the share they cost and nothing can read over 100%.
"""

from __future__ import annotations

from typing import Optional, Sequence

from benchmark import curvature_roofline, roofline, scope_reader

SCOPE = "optim/variance/"
FACTOR_SOLVE = "optim/variance/factor_solve"


def seconds_under(ops: Sequence[scope_reader.Op]) -> float:
    """Self seconds of the operations with the variance's scope anywhere in
    their path."""
    return sum(op.seconds for op in ops if SCOPE in op.path)


def computed(run) -> float:
    """FULL variances the program counted during the traced fits (the
    kind's samples carry the ticks of ``variance.computed{type=FULL}`` a
    fit); 0 where the kind or the program counts none."""
    if not run.traced:
        return 0
    return sum(f.get("variances", 0) for f in run.traced["fits"]
               if "error" not in f)


def least_seconds(rows: int, width: int, peaks: dict) -> float:
    return roofline.least_seconds(
        *curvature_roofline.weighted_gram(rows, width), peaks)[0]


def traced_ops(run) -> Optional[Sequence[scope_reader.Op]]:
    """The traced window's operations where any of them ran under the
    variance's scope, else None."""
    ops = scope_reader.of(run)
    return ops if ops and seconds_under(ops) > 0 else None
