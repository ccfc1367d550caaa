"""The 90th percentile of what ``engine.token_gap_ms`` gives the median
of: over the ``dlrover.engine.deliver`` events of the traced window, the
value under which nine tenths of the gaps lie, each event's mean gap
(``gap_ms_sum / gaps``) standing for its ``gaps``.  A decoding slot's gap
is one engine step, and a step is as long as the prompt chunks in front
of its decode chunk: where one chunk a prefilling slot is dispatched
(a latent-attention model's), the tail is ``prefilling slots x one
chunk``.  The mean within a delivery hides nothing of that: every lane of
one read waited for the same step.

A latency, filed under the one serving end-to-end metric there is: the
nearest reading to the ``gap_p95_ms`` an open-loop cell will bound.
Under 100 gaps in the window a ninth decile is a handful of events:
nothing then (but in a ``--rehearse`` run, whose second of four clients
proves the path and prints no value), and nothing from a program whose
``.deliver`` spans say nothing (the parent of PR 52)."""

LAYER = "engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"

EVENT = "dlrover.engine.deliver"
QUANTILE, MIN_GAPS = 0.9, 100


def read(run):
    from perfbench import program_spans as ps

    parsed = ps.of_run(run)
    if parsed is None:
        return None
    events = sorted(
        (float(a["gap_ms_sum"]) / int(a["gaps"]), int(a["gaps"]))
        for _, _, _, a in ps.named(parsed, EVENT)
        if int(a.get("gaps", 0)) > 0)
    gaps = sum(n for _, n in events)
    rehearsal = any(d["plane"].startswith("/device:CPU-rehearsal")
                    for d in run["trace"]["devices"])
    if len(events) < 3 or gaps < (3 if rehearsal else MIN_GAPS):
        return None
    # the value under which QUANTILE of the gaps lie, each event's mean
    # standing for its gaps
    below = 0
    for value, n in events:
        below += n
        if below >= QUANTILE * gaps:
            return value
