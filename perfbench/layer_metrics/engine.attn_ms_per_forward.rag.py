"""Device milliseconds a decode forward spends in grouped-query
attention (``attn_proj``, ``kv_write`` and ``paged_attn`` in
``serving/latent.py _gqa_layer``, in the engine's decode-chunk program:
the fused ``W_qkv``, the row written to the pools, the
``paged_decode_attention`` kernel over every slot's live pages and
``W_o``, the one attention layer): self time under the scopes over the
program's executions x the chunk's forwards
(``engine.attn_ms_per_forward.reason``'s quantity)."""

LAYER = "engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"


PROGRAM = "decode_chunk"
SCOPES = ("attn_proj", "kv_write", "paged_attn")
SPAN = "dlrover.engine.decode_chunk"


def read(run):
    from perfbench.device_scopes import ms_per_execution

    forwards = run["shapes"].get("chunk")      # of one decode chunk
    return forwards and ms_per_execution(run, PROGRAM, SCOPES, SPAN,
                                         per_execution=forwards)
