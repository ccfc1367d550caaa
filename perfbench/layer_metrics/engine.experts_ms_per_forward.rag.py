"""Device milliseconds a decode forward spends in the held experts
(``moe_experts`` in ``serving/latent.py sparse_mlp``, in the engine's
decode-chunk program; ``engine.experts_ms_per_forward.reason``'s
quantity): self time under the scope over the program's executions x the
chunk's forwards (``perfbench/device_scopes.py``).  18 held experts at 18
rows each a forward, ten layers: the grouped matmuls stream every held
expert's 19 MB."""

LAYER = "engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"


PROGRAM = "decode_chunk"
SCOPES = ("moe_experts",)
SPAN = "dlrover.engine.decode_chunk"


def read(run):
    from perfbench.device_scopes import ms_per_execution

    forwards = run["shapes"].get("chunk")      # of one decode chunk
    return forwards and ms_per_execution(run, PROGRAM, SCOPES, SPAN,
                                         per_execution=forwards)
