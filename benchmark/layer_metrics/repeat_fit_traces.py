"""Programs JAX TRACED while the window's fits ran, every one of them a
repeat fit on a frame the estimator holds prepared: the ``trace`` events in
the program's buffer of outermost stage events (``_compile.py``) whose start
lies between the first fit's ``start`` and the window's ``end`` (or its last
fit's, where that is later), in ``run.window`` and in ``run.traced``. Found
by time stamp: nothing marks a fit. Must read 0: the program's own form of
``window_compiles``, one level up; it also sees a trace that ends in jit's
lowering cache and never reaches the backend. The buffer keeps the newest
256 events, so a window that traced more reads 256 at most."""

from benchmark.layer_metrics import _compile

LAYER = "compile"
UNIT = "programs"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "train_rows_per_s"


def read(run):
    windows = [(w["fits"][0]["start"], max(w["end"], w["fits"][-1]["end"]))
               for w in (run.window, run.traced) if w and w.get("fits")]
    if not windows:
        return None
    found = _compile.traces_between(windows)
    return None if found is None else float(found)
