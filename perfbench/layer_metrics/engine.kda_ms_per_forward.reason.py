"""Device milliseconds a decode forward spends in the linear-attention
(KDA) layers (``kda_proj``, ``kda_scan`` and ``kda_out`` in
``serving/linear.py``, in the engine's decode-chunk program: the fused
q/k/v projection and its convolution, the ``kda_decode_step`` kernel over
the active slots' states, the gated norm and ``W_o``, the nine KDA
layers): self time under the scopes over the program's executions x the
chunk's forwards (``perfbench/device_scopes.py``).  Beside
``engine.decode_step_ms.reason`` it says what share of a forward the new
mechanism is."""

LAYER = "engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"


PROGRAM = "decode_chunk"
SCOPES = ("kda_proj", "kda_scan", "kda_out")
SPAN = "dlrover.engine.decode_chunk"


def read(run):
    from perfbench.device_scopes import ms_per_execution

    forwards = run["shapes"].get("chunk")      # of one decode chunk
    return forwards and ms_per_execution(run, PROGRAM, SCOPES, SPAN,
                                         per_execution=forwards)
