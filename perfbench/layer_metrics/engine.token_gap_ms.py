"""Milliseconds between two deliveries of tokens to one request, as the
engine reads them: over the ``dlrover.engine.deliver`` events of the
traced window (``serving/engine.py``: one a program read, after its
tokens are handed on), the median of each event's mean gap
(``gap_ms_sum / gaps``), an event counting as often as it has ``gaps``
(what ``EngineStats.token_gap_seconds / token_gaps`` is the mean of).  A
gap is the read of a decode chunk less the same request's last read: one
engine step where nothing but the step's own programs lies between, so
near the cell's median ``bench.router_step``; a request's first delivery
has none.

A latency, filed under the one serving end-to-end metric there is: the
median of what an open-loop cell will bound as ``gap_p95_ms`` (a chunk's
tokens arrive together: the gap of a token is this over the chunk's
length, for all but the chunk's first).  Fewer than 3 deliveries with a
gap in the window, or a program whose ``.deliver`` spans say nothing
(the parent of PR 52), report nothing."""

LAYER = "engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"

EVENT = "dlrover.engine.deliver"
QUANTILE, MIN_GAPS = 0.5, 3


def read(run):
    from perfbench import program_spans as ps

    parsed = ps.of_run(run)
    events = sorted(
        (float(a["gap_ms_sum"]) / int(a["gaps"]), int(a["gaps"]))
        for _, _, _, a in ps.named(parsed, EVENT)
        if int(a.get("gaps", 0)) > 0) if parsed else []
    gaps = sum(n for _, n in events)
    if len(events) < 3 or gaps < MIN_GAPS:
        return None
    # the value under which QUANTILE of the gaps lie, each event's mean
    # standing for its gaps
    below = 0
    for value, n in events:
        below += n
        if below >= QUANTILE * gaps:
            return value
