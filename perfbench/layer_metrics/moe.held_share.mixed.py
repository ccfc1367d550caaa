"""Share of the router's picks that fell on the experts this chip HOLDS,
percent, over the window: the engine's ``moe_picks_held`` / ``moe_picks``
(``EngineStats``; a device reduction over the sparse layers, carried in
the cache beside the pools).  12.5 where 32 of 256 experts are held and
the routing is even (``serve-mixed-window``): the guard that routing still
spans all 256."""

LAYER = "engine"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"


def read(run):
    c = run.get("counters", {})
    picks = c.get("engine.moe_picks")
    if not picks:
        return None
    return 100.0 * c["engine.moe_picks_held"] / picks
