"""Share of all the router's picks that fell on experts this chip HOLDS,
summed over the sparse layers, median over the window's steps: the step's
``moe_held_share`` metric (``models/moe.py routing_stats``).  12.5 % where
32 of 256 experts are held and the routing is even; it is also the share
of the sorted buffer's ``T x top_k`` rows that are live, the rest being
what the deployment's exchange would fill."""

LAYER = "trainer"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"


def read(run):
    share = run.get("counters", {}).get("moe.held_share_median")
    return None if share is None else 100.0 * share
