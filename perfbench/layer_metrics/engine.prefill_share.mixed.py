"""Percent of the traced window the engine spent inside prefill dispatches
(``dlrover.engine.prefill_chunk``: one chunk of a LONG request's tail
behind its cached document or of a SHORT request's prompt, a slot a
dispatch; ``dlrover.engine.prefill`` never runs in ``serve-mixed-window``), from the first dispatch of a step through the sync on the
last: time in which no slot decodes."""

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
