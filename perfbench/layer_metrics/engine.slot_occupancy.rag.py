"""Share of the decode batch that produced a token a request kept:
``generated_tokens`` over ``decode_forwards`` x ``max_slots``, differences
over the window (``engine.slot_occupancy.reason``'s quantity).  Empty
slots, slots still prefilling their prompt and the forwards of a chunk
past a request's last token all count against it; 128 slots here."""

LAYER = "engine"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"


def read(run):
    c = run["counters"]
    n = c.get("engine.decode_forwards", 0) * run["shapes"]["max_slots"]
    return 100.0 * c["engine.generated_tokens"] / n if n else None
