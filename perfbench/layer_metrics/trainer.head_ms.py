"""Device milliseconds a traced step at the two ends of the model, forward
and backward: ``embed`` (the token embedding, ``models/llama.py``) and
``head`` (the final norm, ``lm_head`` and the loss, ``models/llama.py`` and
``accel/accelerate.py``).  Self time by the program's own scopes
(``perfbench/device_scopes.py``), mean over the chips."""

LAYER = "trainer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"


SCOPES = ("embed", "head")


def read(run):
    from perfbench.device_scopes import ms_per_step

    return ms_per_step(run, SCOPES)
