"""Device milliseconds a traced step in what surrounds the attention kernels
(``attn_proj`` in ``models/llama.py``: the q / k / v / o and gate
projections, QK-norm, RoPE and its tables, the layer's two norms), forward,
recomputed and backward; the kernels themselves are the ``kernel.*``
readers'.  Self time by the program's own scopes
(``perfbench/device_scopes.py``), mean over the chips."""

LAYER = "trainer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"


SCOPES = ("attn_proj",)


def read(run):
    from perfbench.device_scopes import ms_per_step

    return ms_per_step(run, SCOPES)
