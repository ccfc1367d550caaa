"""Device milliseconds a decode forward spends choosing the keys each query
attends to (``dsa_select`` in ``serving/latent.py``, in the engine's
decode-chunk program: the threshold over the index scores and the mask of
the chosen rows, ``serve-mixed-window``'s three FULL layers; a window layer
selects nothing): self time under the scope over the program's
executions x the chunk's forwards (``perfbench/device_scopes.py``)."""

LAYER = "engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"


PROGRAM = "decode_chunk"
SCOPES = ("dsa_select",)
SPAN = "dlrover.engine.decode_chunk"


def read(run):
    from perfbench.device_scopes import ms_per_execution

    forwards = run["shapes"].get("chunk")      # of one decode chunk
    return forwards and ms_per_execution(run, PROGRAM, SCOPES, SPAN,
                                         per_execution=forwards)
