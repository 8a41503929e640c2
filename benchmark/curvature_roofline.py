"""Operations and bytes of TRON's curvature work over ONE dense
[rows, width] design matrix, what a fit's solver counted of it, and the
least seconds the chip could take for that. Beside ``roofline.py``, whose
``least_seconds`` turns operations and bytes into a time.

The solver keeps its Hessian ``X^T D X + l2 I`` one of two ways
(``photon_tpu/optim/problem.py::tron_explicit_hessian`` chooses; the
program's counter ``kernels.tron_hessian{path}`` says which a process
traced): ``explicit``, one contraction an operator build and no pass over
X a CG step, or ``matrix_free``, a product over X a CG step. Either way the
least is counted: the SYMMETRIC half of the contraction and ONE read of X,
so no implementation reads over 100% and the number means the same work
whichever side of the gate runs.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from benchmark import roofline

PATHS = ("explicit", "matrix_free")


def weighted_gram(rows: int, width: int, itemsize: int = 4
                  ) -> Tuple[float, float]:
    """(operations, bytes) of one ``X^T diag(d2) X``: the upper triangle
    with its diagonal, ``width (width + 1) / 2`` entries of 2 operations a
    row each (the scaling by d2 rides on one operand: ``rows x width``
    multiplies more, left out); the least traffic is ONE read of X, the
    curvature weights and the [width, width] result."""
    return (float(rows) * width * (width + 1.0),
            itemsize * (float(rows) * width + rows + float(width) * width))


def hessian_vector(rows: int, width: int, itemsize: int = 4
                   ) -> Tuple[float, float]:
    """(operations, bytes) of one matrix-free product ``X^T (d2 * (X v))``:
    2 operations a cell for ``X v`` and 2 for the way back; the least
    traffic is ONE read of X (a row's two uses can share it), the curvature
    weights, v and the result. A product that reads X twice reaches at most
    half of this."""
    cells = float(rows) * width
    return 4.0 * cells, itemsize * (cells + rows + 2.0 * width)


def least_seconds(path: str, hessian_builds: int, cg_steps: int, rows: int,
                  width: int, peaks: dict) -> float:
    """Least seconds of one solve's curvature work: its operator builds
    (``explicit``: one symmetric contraction each) or its CG steps
    (``matrix_free``: one product over X each)."""
    if path == "explicit":
        return hessian_builds * roofline.least_seconds(
            *weighted_gram(rows, width), peaks)[0]
    if path == "matrix_free":
        return cg_steps * roofline.least_seconds(
            *hessian_vector(rows, width), peaks)[0]
    raise ValueError(f"unknown Hessian path {path!r}: one of {PATHS}")


def solver_counts(run) -> Optional[Dict[str, int]]:
    """``{"cg_steps", "hessian_builds", "rejected_steps"}`` of ONE fit, the
    window's last (every fit repeats its counts, or the run is not
    ``correct``), summed over the fixed-effect coordinates that ran TRON:
    the program's own ``FixedEffectCoordinate.tron_counts()``, read here,
    after the window. None where no coordinate has any: another solver, or
    a program from before it counted them."""
    total: Dict[str, int] = {}
    est = run.state.get("est")
    for c in run.cfg["coordinates"]:
        coord = getattr(est, "_coordinates", {}).get(c["id"])
        counts = getattr(coord, "tron_counts", lambda: None)()
        for key, n in (counts or {}).items():
            total[key] = total.get(key, 0) + n
    return total or None


def traced_path() -> Optional[str]:
    """Which operator this process's TRON solves were traced with: the one
    label of ``kernels.tron_hessian`` that ticked. None where none or both
    did."""
    from photon_tpu.obs.metrics import registry

    ticked = [path for path in PATHS if registry.snapshot()["counters"].get(
        f'kernels.tron_hessian{{path="{path}"}}')]
    return ticked[0] if len(ticked) == 1 else None
