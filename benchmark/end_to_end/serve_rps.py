"""Responses that left ``pump`` inside the window, over its seconds. A
response that is not whole (a refusal, a shed or failed score) counts under
``failed``, and the closed loop's traffic is chosen so that there is none."""

UNIT = "requests/s"
BETTER = "higher"
SOURCE = "host_clock"


def read(run):
    w = run.window
    return w["completed"] / w["seconds"]
