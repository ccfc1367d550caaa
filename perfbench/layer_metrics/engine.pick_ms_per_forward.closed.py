"""Device milliseconds a decode forward spends in the output head and the
choice of the token (``head`` in ``serving/model.py _lm_head`` and ``pick``
around ``select_token`` in ``serving/engine.py``, in the engine's
decode-chunk program; the compiler fuses the greedy pick into the head's
matmul): self time under the two scopes over the program's executions x
the chunk's forwards (``perfbench/device_scopes.py``)."""

LAYER = "engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"


PROGRAM = "decode_chunk"
SCOPES = ("head", "pick")
SPAN = "dlrover.engine.decode_chunk"


def read(run):
    from perfbench.device_scopes import ms_per_execution

    forwards = run["shapes"].get("chunk")      # of one decode chunk
    return forwards and ms_per_execution(run, PROGRAM, SCOPES, SPAN,
                                         per_execution=forwards)
