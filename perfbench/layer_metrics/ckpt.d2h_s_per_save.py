"""Seconds per committed save that the writer thread spent bringing the
snapshot's bytes from the device to the host (dispatching the copies and
waiting for them): the engine's ``d2h_seconds_total`` over
``saves_committed_total``, differences over the window and the final
flush.  A part of ``ckpt.commit_s_per_save``; a program without the
counter reports nothing."""

LAYER = "checkpoint"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    c = run["counters"]
    n = c.get("ckpt.saves_committed_total", 0)
    total = c.get("ckpt.d2h_seconds_total")
    return total / n if n and total is not None else None
