"""Percent of the traced window the engine's step spent outside its
dispatch-and-sync children: ``dlrover.engine.step`` less the prefill-chunk
and decode-chunk spans (``prefill`` and ``verify`` never run in this cell).
Host work of the engine itself: admission against the prefix cache,
block-table pushes, the warm starts' and kept prefix ends' copies, the
selection's and the windows' books, handing tokens to requests
(``serve-mixed-window``)."""

LAYER = "engine"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"

DEVICE_WAITS = ("dlrover.engine.prefill", "dlrover.engine.prefill_chunk",
                "dlrover.engine.decode_chunk", "dlrover.engine.verify")


def read(run):
    from perfbench import program_spans as ps

    parsed = ps.of_run(run)
    spans = ps.totals(parsed) if parsed else {}
    if "dlrover.engine.step" not in spans:
        return None
    host = spans["dlrover.engine.step"]["seconds"] - sum(
        spans[n]["seconds"] for n in DEVICE_WAITS if n in spans)
    return 100.0 * host / ps.window_s(parsed)
