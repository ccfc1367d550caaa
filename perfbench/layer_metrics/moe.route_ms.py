"""Device milliseconds a traced step in the sparse layers' routing
(``moe_route`` in ``models/moe.py``: the router matmul, scores, top-k, the
picks' counts and the auxiliary terms; a held share's test of its picks),
forward, recomputed and backward.  Self time by the program's own scopes
(``perfbench/device_scopes.py``)."""

LAYER = "trainer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"


SCOPES = ("moe_route",)


def read(run):
    from perfbench.device_scopes import ms_per_step

    return ms_per_step(run, SCOPES)
