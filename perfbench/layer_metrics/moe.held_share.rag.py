"""Share of the router's picks that fell on the experts this chip HOLDS,
percent, over the window: the engine's ``moe_picks_held`` / ``moe_picks``
(``moe.held_share.reason``'s quantity).  25 where 18 of 72 experts are
held and the routing is even: the guard that routing still spans all
72."""

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
