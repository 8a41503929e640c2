"""Peak device memory of the process, ``memory_stats()["peak_bytes_in_use"]``
on the fullest chip."""

LAYER = "device"
UNIT = "GB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9
