"""Device milliseconds a decode forward spends in the power-retention
blocks (``ret_proj``, ``ret_scan`` and ``ret_out`` in ``serving/linear.py``,
in the engine's decode-chunk program: the fused projection, the head
norms, the rotation and the gate; the ``retention_decode_step`` kernel over
the active slots' states; ``W_o``; every layer): self time under the
scopes over the program's executions x the chunk's forwards
(``perfbench/device_scopes.py``).  Beside ``engine.decode_step_ms`` it
says what share of a forward the mechanism is."""

LAYER = "engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"


PROGRAM = "decode_chunk"
SCOPES = ("ret_proj", "ret_scan", "ret_out")
SPAN = "dlrover.engine.decode_chunk"


def read(run):
    from perfbench.device_scopes import ms_per_execution

    forwards = run["shapes"].get("chunk")      # of one decode chunk
    return forwards and ms_per_execution(run, PROGRAM, SCOPES, SPAN,
                                         per_execution=forwards)
