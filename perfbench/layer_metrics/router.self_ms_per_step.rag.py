"""Milliseconds of a router step that are the router's own: the
``dlrover.router.step`` spans less the ``dlrover.engine.step`` spans under
them, per router step of the traced window
(``router.self_ms_per_step.reason``'s quantity): expiry, placement of
192 clients' requests against the block ledger, delivery to 128 running
requests, the pump's bookkeeping, gauges."""

LAYER = "router"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"


def read(run):
    from perfbench import program_spans as ps

    parsed = ps.of_run(run)
    spans = ps.totals(parsed) if parsed else {}
    steps = spans.get("dlrover.router.step")
    if not steps:
        return None
    engine = spans.get("dlrover.engine.step", {"seconds": 0.0})
    return (steps["seconds"] - engine["seconds"]) / steps["count"] * 1e3
