"""Device milliseconds a decode forward spends in latent attention
(``mla_attn`` in ``serving/latent.py``, in the engine's decode-chunk
program: the ``mla_decode_attn`` kernel over every slot's live pages,
under the mask of the rows the slot chose where the model has a learned
selection, then the value up-projection of the attended latent and
``W_o``, every layer): self time under the scope over the program's
executions x the chunk's forwards (``perfbench/device_scopes.py``).

The reader of ``engine.attn_ms_per_forward.<suffix>`` for a cell with no
file of its own (``.longctx`` and ``.reason`` have theirs, the same
reading): ``.docqa``, where since PR 44 the time that left
``engine.select_ms_per_forward.docqa`` is read."""

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
