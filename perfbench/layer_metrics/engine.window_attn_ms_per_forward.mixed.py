"""Device milliseconds a decode forward spends in the WINDOW layers'
attention blocks (``swa_proj`` + ``swa_attn`` in ``serving/latent.py
_window_layer``, in the engine's decode-chunk program: the projections in
the window layers' own geometry, the ``mla_window_decode_attn`` kernel over
the blocks of each slot's ring that its window touches, the value
up-projection, the head gate and ``W_o``, the three window layers of
``serve-mixed-window``): self time under the scopes over the program's
executions x the chunk's forwards (``perfbench/device_scopes.py``).  A
program with no such scope (the parent of PR 47) reports nothing."""

LAYER = "engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"


PROGRAM = "decode_chunk"
SCOPES = ("swa_proj", "swa_attn")
SPAN = "dlrover.engine.decode_chunk"


def read(run):
    from perfbench.device_scopes import ms_per_execution

    forwards = run["shapes"].get("chunk")      # of one decode chunk
    return forwards and ms_per_execution(run, PROGRAM, SCOPES, SPAN,
                                         per_execution=forwards)
