"""Key rows attended per key row live, percent, over every query of the
window (decode forwards and prefill chunks): the engine's
``attn_rows_selected`` / ``dsa_rows_live`` (``EngineStats``; host
arithmetic from positions, one layer's rows).  ``index_topk`` / context:
6-12 at this cell's depths; 100 would mean that contexts fit the selection
and the cell no longer measures the mechanism."""

LAYER = "engine"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    c = run.get("counters", {})
    live = c.get("engine.dsa_rows_live")
    if not live:
        return None
    return 100.0 * c["engine.attn_rows_selected"] / live
