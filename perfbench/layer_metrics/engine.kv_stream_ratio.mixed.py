"""Latent rows the decode kernel copied per row its slots could see, over
the window: the engine's ``kv_rows_streamed`` / ``kv_rows_live``
(``EngineStats``; host arithmetic at each decode dispatch, one layer's
rows, also on the ``dlrover.engine.decode_chunk`` span).  1.00 is a stream
with no dead row (``serve-mixed-window``: the FULL layers' rows; the window
layers' are ``engine.window_stream_ratio.mixed``); whole page groups up to each slot's length read a few
hundred rows past 24 000; a gather of every table's width would read
``slots x table rows / live``."""

LAYER = "engine"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    c = run.get("counters", {})
    live = c.get("engine.kv_rows_live")
    if not live:
        return None
    return c["engine.kv_rows_streamed"] / live
