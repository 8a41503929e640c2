"""Host seconds JAX spent TRACING the job's programs to jaxprs, over the
process: the program's counter ``compile.seconds{stage=trace}`` summed over
its phases (``_compile.py``). A warm persistent cache saves none of it:
what an exported or ahead-of-time-loaded program would skip, and where a
rule re-run in a ``vmap`` fixpoint shows (PERF.md §6, PR 25)."""

from benchmark.layer_metrics import _compile

LAYER = "compile"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    return _compile.seconds("trace")
