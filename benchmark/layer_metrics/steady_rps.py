"""Responses completed over the window's seconds in the steady cell: the
offered rate, unless the server fell behind."""

LAYER = "load_generator"
UNIT = "requests/s"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = "serve_p99_ms"


def read(run):
    return len(run.window["responses"]) / run.window["seconds"]
