"""Share of the decode batch that produced a token a request kept:
``generated_tokens`` over ``decode_forwards`` x ``max_slots``, differences
over the window.  Empty slots, slots still prefilling their tail behind a
cached document and the forwards of a chunk past a request's last token all
count against it (``serve-mixed-window``: 32 slots, half of them behind a
document of 16-31 k, which the router's ledger charges whole)."""

LAYER = "engine"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"


def read(run):
    c = run["counters"]
    n = c.get("engine.decode_forwards", 0) * run["shapes"]["max_slots"]
    return 100.0 * c["engine.generated_tokens"] / n if n else None
