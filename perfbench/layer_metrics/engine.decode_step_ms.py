"""Wall milliseconds per decode forward: the engine's own ``decode_seconds``
(host clock around each decode-chunk dispatch, which ends in a sync) over
``decode_forwards``, differences over the window.

One body for every ``engine.decode_step_ms.<suffix>``: the suffix only says
which end-to-end metric the entry in BENCHMARK.json ``moves``."""

LAYER = "engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    c = run["counters"]
    n = c.get("engine.decode_forwards", 0)
    return c["engine.decode_seconds"] / n * 1e3 if n else None
