"""Bytes of the window layers' rings and kept prefix ends over all the
bytes the engine keeps sequences in, percent (``InferenceEngine.
cache_nbytes_by_kind``, as the driver read it into the run's shapes: the
pools are made once, in set-up).  ``serve-mixed-window``: three window
layers in 0.28 GB beside three full layers' 1.63 GB of blocks; the same
rows under the full layers' table would be 60 %.  A program that does not
split its cache bytes by kind (the parent of PR 47) reports nothing."""

LAYER = "engine"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    sh = run.get("shapes", {})
    if not sh.get("window_cache_nbytes") or not sh.get("cache_nbytes"):
        return None
    return 100.0 * sh["window_cache_nbytes"] / sh["cache_nbytes"]
