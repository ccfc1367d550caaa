"""Seconds the writer thread took per committed save (device to host copy
plus the copy into shared memory), beside training:
``commit_seconds_total`` over ``saves_committed_total``, differences over
the window and the final flush."""

LAYER = "checkpoint"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    c = run["counters"]
    n = c.get("ckpt.saves_committed_total", 0)
    return c["ckpt.commit_seconds_total"] / n if n else None
