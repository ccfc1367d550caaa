"""Milliseconds from a request's admission to the read of its first
token: the median ``prefill_ms`` of the ``dlrover.request.first_token``
events of the traced window (``serving/engine.py _deliver_firsts``; what
``EngineStats.prefill_wall_seconds`` sums).  The WALL of a prefill, not
its programs' time: a chunked prompt takes one chunk an engine step, and
every step also runs the other slots' chunks and a decode chunk
(``steps`` on the event says how many).

A latency, filed under the one serving end-to-end metric there is: the
third and, in the document cells, largest part of the first-token tail
(``router.first_token_ms``).  Fewer than 3 first tokens in the window, or
a program that writes no such event (the parent of PR 52), report
nothing."""

import statistics

LAYER = "engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"

EVENT, FIELD = "dlrover.request.first_token", "prefill_ms"


def read(run):
    from perfbench import program_spans as ps

    parsed = ps.of_run(run)
    values = [float(a[FIELD]) for _, _, _, a in ps.named(parsed, EVENT)
              if FIELD in a] if parsed else []
    return statistics.median(values) if len(values) >= 3 else None
