"""Percent of the traced window the engine spent inside prefill dispatches
(``dlrover.engine.prefill_chunk``: a prompt of 512-4 096 tokens in chunks
of 512, a slot a dispatch; ``dlrover.engine.prefill`` never runs in this
cell: a model with recurrent state has no bucketed prefill), from the
first dispatch of a step through the sync on the last: time in which no
slot decodes.  ``engine.prefill_share.reason``'s quantity in the cell
whose prompts are twice as long and whose answers are a third as long:
the chunk programs are judged here too."""

LAYER = "engine"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"

PREFILLS = ("dlrover.engine.prefill", "dlrover.engine.prefill_chunk")


def read(run):
    from perfbench import program_spans as ps

    parsed = ps.of_run(run)
    spans = ps.totals(parsed) if parsed else {}
    if "dlrover.engine.step" not in spans:
        return None
    prefill = sum(spans[n]["seconds"] for n in PREFILLS if n in spans)
    return 100.0 * prefill / ps.window_s(parsed)
