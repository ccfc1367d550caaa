"""Recurrent-state bytes the decode forwards moved per byte of the slots
that decoded, over the window: the engine's ``state_bytes_streamed`` /
``state_bytes_live`` (``EngineStats``; host arithmetic at each decode
dispatch, also on the ``dlrover.engine.decode_chunk`` span).  1.00 when
the kernel's grid (``ssm_decode_step``'s here) walks the active slots
alone; a step that read and
wrote every slot's state would read ``max_slots / active``."""

LAYER = "engine"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    c = run.get("counters", {})
    live = c.get("engine.state_bytes_live")
    if not live:
        return None
    return c["engine.state_bytes_streamed"] / live
