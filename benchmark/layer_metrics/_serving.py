"""What the serving readers share: the engine's own stage histograms
(``serving.latency_seconds{stage}``; bucketed, so only sum and count are
read) and its batch counters (``serving.batches{bucket}``), over the
untraced part of the window."""


def stage_mean_ms(run, stage):
    total, count = run.window["stages"].get(stage, (0.0, 0))
    return 1e3 * total / count if count else None


def batch_fill(run):
    rows, batches = run.window["stages"]["batches"]
    responses = run.window["stages"].get("total", (0.0, 0))[1]
    return responses / rows if rows else None
