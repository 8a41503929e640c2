"""Programs compiled inside the measured window. Must read 0: a run that
compiles in its window is not ``correct``."""

LAYER = "compile"
UNIT = "programs"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    return run.compile["window_compiles"]
