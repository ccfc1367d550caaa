"""Latent rows the window layers' decode attention read per row inside
their queries' windows, over the window: the engine's
``window_rows_streamed`` / ``window_rows_in_window`` (``EngineStats``; host
arithmetic at each decode dispatch, summed over the window layers, also on
the ``dlrover.engine.decode_chunk`` span).  1.00 = a decode reads the
window and no more; the blocks a window of 513 touches, whole, are 640
rows (1.25 past the window, more for a sequence shorter than it); the
whole ring would be 2.0, the slot's whole context ``length / 513``.  A
program that books no window rows (the parent of PR 47) reports
nothing."""

LAYER = "engine"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    c = run.get("counters", {})
    inside = c.get("engine.window_rows_in_window")
    if not inside:
        return None
    return c["engine.window_rows_streamed"] / inside
