"""Host seconds JAX spent LOWERING the job's jaxprs to MLIR modules, over
the process: the program's counter ``compile.seconds{stage=lower}`` summed
over its phases (``_compile.py``). Paid on a warm cache too."""

from benchmark.layer_metrics import _compile

LAYER = "compile"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    return _compile.seconds("lower")
