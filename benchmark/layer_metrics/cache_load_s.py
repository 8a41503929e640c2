"""Host seconds spent in backend-compile events that the persistent cache
SERVED (the key's hashing, the read, the deserialisation; no compile), over
the process: the program's counter ``compile.seconds{stage=cache_load}``
summed over its phases (``_compile.py``). With ``stage=backend`` (events that
compiled) it adds up to what ``compile_s`` reads from outside; 0 on an empty
cache."""

from benchmark.layer_metrics import _compile

LAYER = "compile"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    return _compile.seconds("cache_load")
