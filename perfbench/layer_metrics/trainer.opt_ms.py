"""Device milliseconds a traced step under the optimizer's scopes of the
compiled train step (``accel/accelerate.py``: ``optimizer`` around
``apply_gradients``, ``clip`` around the chain's clipping link and, with
clipping on, the global norm the step's metrics report, ``grad_norm``
where there is no clipping): every instruction's SELF time, joined with its
scope through the program's own text (``perfbench/device_scopes.py``), mean
over the chips.  Nothing where the program has no such reduction."""

LAYER = "trainer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"


SCOPES = ("optimizer", "clip", "grad_norm")


def read(run):
    from perfbench.device_scopes import ms_per_step

    return ms_per_step(run, SCOPES)
