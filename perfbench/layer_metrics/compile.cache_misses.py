"""Programs this process had to compile because the persistent cache did
not hold them, counted by the program's ``cache_counts()`` at the end of
set-up.  0 on every run but the first in a checkout."""

LAYER = "compile cache"
UNIT = "programs"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    return run["setup"].get("cache_misses")
