"""Device milliseconds one prefill chunk of one slot spends in attention,
both kinds (``mla_attn``, the three full layers' latent attention under
the selection's mask, and ``swa_attn``, the three window layers' run of
queries over the blocks of the slot's ring in position order: the
``mla_window_prefill_attn`` kernel, the value up-projection, the gate and
``W_o``; ``serving/latent.py``, in the engine's prefill-chunk program):
self time under the scopes over the program's executions in the traced
window (``perfbench/device_scopes.py``).  ``.docqa``'s quantity in
``serve-mixed-window``, where a prompt chunk is a LONG request's tail
behind a document of 16-31 k or a SHORT prompt's 256-2 048."""

LAYER = "engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"


PROGRAM = "prefill_chunk"
SCOPES = ("mla_attn", "swa_attn")
SPAN = "dlrover.engine.prefill_chunk"


def read(run):
    from perfbench.device_scopes import ms_per_execution

    return ms_per_execution(run, PROGRAM, SCOPES, SPAN)
