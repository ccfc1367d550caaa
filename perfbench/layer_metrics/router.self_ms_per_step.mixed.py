"""Milliseconds of a router step that are the router's own: the
``dlrover.router.step`` spans less the ``dlrover.engine.step`` spans under
them (the in-process replica's engine runs inside the router's pump), per
router step of the traced window.  Expiry, placement of 0.4-32 k-token
requests against the block ledger, delivery, the pump's bookkeeping, gauges
(``serve-mixed-window``)."""

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
