"""Seconds of set-up spent making the model's weights and state on the
device (the program's jitted init for training, perfbench/weights.py for
serving), compile or cache load of that program included."""

LAYER = "entry points"
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
    return run["setup"].get("weights_s")
