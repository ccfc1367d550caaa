"""Device milliseconds a decode forward spends in latent attention
(``mla_attn`` in ``serving/latent.py``, in the engine's decode-chunk
program: the ``mla_decode_attn`` kernel over every slot's live pages, the
value up-projection of the attended latent and ``W_o``, the three MLA
layers): self time under the scope over the program's executions x the
chunk's forwards (``engine.attn_ms_per_forward.longctx``'s quantity)."""

LAYER = "engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"


PROGRAM = "decode_chunk"
SCOPES = ("mla_attn",)
SPAN = "dlrover.engine.decode_chunk"


def read(run):
    from perfbench.device_scopes import ms_per_execution

    forwards = run["shapes"].get("chunk")      # of one decode chunk
    return forwards and ms_per_execution(run, PROGRAM, SCOPES, SPAN,
                                         per_execution=forwards)
