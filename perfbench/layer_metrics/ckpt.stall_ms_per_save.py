"""Milliseconds the training loop stood still per staged save: the
checkpoint engine's own ``inloop_pause_seconds_total`` over
``saves_staged_total``, differences over the window."""

LAYER = "checkpoint"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    c = run["counters"]
    n = c.get("ckpt.saves_staged_total", 0)
    return c["ckpt.inloop_pause_seconds_total"] / n * 1e3 if n else None
