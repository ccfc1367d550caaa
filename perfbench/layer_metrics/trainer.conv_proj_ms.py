"""Device milliseconds a traced step in the projections around a gated
short convolution (``conv_proj`` in ``models/llama.py``: ``W_in`` to ``[B |
C | u]``, ``W_out``, and the conv layers' two norms), forward, recomputed
and backward; the gates and taps between them are ``trainer.conv_mix_ms``.
Self time by the program's own scopes (``perfbench/device_scopes.py``),
mean over the chips.  A program that never entered the scope gives
nothing."""

LAYER = "trainer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"


SCOPES = ("conv_proj",)


def read(run):
    from perfbench.device_scopes import ms_per_step

    return ms_per_step(run, SCOPES)
