"""Milliseconds a request waited in the ROUTER's queue before a replica
took it: the median ``queue_wait_ms`` of the ``dlrover.request.placed``
events of the traced window (``serving/router/router.py``, beside the
``serving_queue_wait_seconds`` histogram's sample: the start of the router
step that placed it less the start of this stay in the queue).  The router
places a request only where a slot is free, so the clients a cell has
beyond its slots wait here and not in the engine.

A latency, filed under the one serving end-to-end metric there is: it is
the first part of the first-token tail (``router.first_token_ms``) that an
open-loop cell will bound.  Fewer than 3 placements in the window, or a
program that writes no such event (the parent of PR 52), report nothing."""

import statistics

LAYER = "router"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"

EVENT, FIELD = "dlrover.request.placed", "queue_wait_ms"


def read(run):
    from perfbench import program_spans as ps

    parsed = ps.of_run(run)
    values = [float(a[FIELD]) for _, _, _, a in ps.named(parsed, EVENT)
              if FIELD in a] if parsed else []
    return statistics.median(values) if len(values) >= 3 else None
