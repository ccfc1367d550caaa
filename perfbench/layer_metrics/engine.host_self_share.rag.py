"""Percent of the traced window the engine's step spent outside its
dispatch-and-sync children: ``dlrover.engine.step`` less the prefill-chunk
and decode-chunk spans (``engine.host_self_share.reason``'s quantity).
Host work of the engine itself: admission, block-table pushes, the books
of the kernels, handing the tokens of 128 slots to their requests.  At
depth 10 of 40 the host's share is larger than a deployment's."""

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
