"""Device milliseconds a traced step moving rows into and out of expert
order (``moe_dispatch`` and ``moe_combine`` in ``models/moe.py``: the sort
of the picks, the row gathers over the ``T x top_k`` buffer, the selects
behind a held share's groups, the weighted sum back), forward, recomputed
and backward.  Self time by the program's own scopes
(``perfbench/device_scopes.py``)."""

LAYER = "trainer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"


SCOPES = ("moe_dispatch", "moe_combine")


def read(run):
    from perfbench.device_scopes import ms_per_step

    return ms_per_step(run, SCOPES)
