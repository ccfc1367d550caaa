"""Device milliseconds a traced step in the dense SwiGLU MLPs (``mlp`` in
``models/llama.py``), forward, recomputed and backward.  Self time by the
program's own scopes (``perfbench/device_scopes.py``), mean over the
chips; a sparse layer's time is ``moe.*``'s."""

LAYER = "trainer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"


SCOPES = ("mlp",)


def read(run):
    from perfbench.device_scopes import ms_per_step

    return ms_per_step(run, SCOPES)
