"""Seconds XLA spent in backend compiles during set-up (a program served
by the persistent cache costs only its retrieval)."""

LAYER = "compile"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(run):
    return run.compile["compile_s"]
