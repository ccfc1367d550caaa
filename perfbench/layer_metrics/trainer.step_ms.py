"""Median wall milliseconds of one ``train_step`` ending in
``block_until_ready``, over the window's steps that had no save due."""

LAYER = "trainer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
    import statistics

    s = run["samples"]
    plain = [t for t, saved in zip(s["step_s"], s["step_had_save"])
             if not saved]
    return statistics.median(plain) * 1e3 if plain else None
