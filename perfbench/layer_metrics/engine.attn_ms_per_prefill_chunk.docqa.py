"""Device milliseconds one prefill chunk of one slot spends in latent
attention under the selection's mask (``mla_attn`` in ``serving/latent.py``,
in the engine's prefill-chunk program): self time under the scope over the
program's executions in the traced window
(``perfbench/device_scopes.py``)."""

LAYER = "engine"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"


PROGRAM = "prefill_chunk"
SCOPES = ("mla_attn",)
SPAN = "dlrover.engine.prefill_chunk"


def read(run):
    from perfbench.device_scopes import ms_per_execution

    return ms_per_execution(run, PROGRAM, SCOPES, SPAN)
