"""Milliseconds from a request's submission to its first token as the
router stamps it: the median ``ttft_ms`` of the
``dlrover.request.first_delivery`` events of the traced window
(``serving/router/gateway.py ServingRequest.mark_first_token``:
``first_token_at`` less ``submitted_at``, the sample of
``serving_ttft_seconds``; for an in-process replica the engine's own read
of the program that sampled the token).  Queue wait + slot wait + prefill
wall of the same request, to within one router step
(``router.queue_wait_ms``, the ``slot_wait_ms`` of
``dlrover.request.admitted``, which has no reader of its own,
``engine.prefill_wall_ms``; the medians are of different requests where
the window cuts a request's life).

A latency, filed under the one serving end-to-end metric there is: the
median of what an open-loop cell will bound as ``ttft_p95_ms``.  Fewer
than 3 first deliveries in the window, or a program that writes no such
event (the parent of PR 52), report nothing."""

import statistics

LAYER = "router"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"

EVENT, FIELD = "dlrover.request.first_delivery", "ttft_ms"


def read(run):
    from perfbench import program_spans as ps

    parsed = ps.of_run(run)
    values = [float(a[FIELD]) for _, _, _, a in ps.named(parsed, EVENT)
              if FIELD in a] if parsed else []
    return statistics.median(values) if len(values) >= 3 else None
