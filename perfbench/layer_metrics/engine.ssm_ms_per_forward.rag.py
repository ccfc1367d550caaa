"""Device milliseconds a decode forward spends in the Mamba-2 layers
(``ssm_proj``, ``ssm_scan`` and ``ssm_out`` in ``serving/linear.py``, in
the engine's decode-chunk program: ``W_in`` and the convolution, the
``ssm_decode_step`` kernel over the active slots' states, the gated norm
and ``W_out``, the nine Mamba-2 layers): self time under the scopes over
the program's executions x the chunk's forwards
(``perfbench/device_scopes.py``).  Beside ``engine.decode_step_ms.rag`` it
says what share of a forward the new mechanism is."""

LAYER = "engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"


PROGRAM = "decode_chunk"
SCOPES = ("ssm_proj", "ssm_scan", "ssm_out")
SPAN = "dlrover.engine.decode_chunk"


def read(run):
    from perfbench.device_scopes import ms_per_execution

    forwards = run["shapes"].get("chunk")      # of one decode chunk
    return forwards and ms_per_execution(run, PROGRAM, SCOPES, SPAN,
                                         per_execution=forwards)
