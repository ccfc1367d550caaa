"""Device milliseconds a traced step in the gates and taps of the gated
short convolutions (``conv_mix`` in ``models/llama.py``: ``B * u``, the
causal depthwise convolution over it, ``C *`` that), forward, recomputed
and backward: memory-bound work between two matmuls.  Self time by the
program's own scopes (``perfbench/device_scopes.py``), mean over the
chips.  A program that never entered the scope gives nothing."""

LAYER = "trainer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"


SCOPES = ("conv_mix",)


def read(run):
    from perfbench.device_scopes import ms_per_step

    return ms_per_step(run, SCOPES)
