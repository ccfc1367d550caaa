"""Seconds of set-up spent running every program of the cell once (compile,
or load from the persistent cache): the trainer's first steps, the
engine's ``warmup()`` and the warm requests through the router."""

LAYER = "entry points"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
    return run["setup"].get("warmup_s")
