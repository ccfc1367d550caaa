"""The worst layer's largest expert group over the mean group (T x top_k /
experts), median over the window's steps: the step's ``moe_load_max``
metric (``models/moe.py routing_stats``, exported by ``accelerate()``'s
``train_step``).  1.0 is an even spread; a capacity mask at factor 1.25
would have dropped every pick above 1.25."""

LAYER = "trainer"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"


def read(run):
    return run.get("counters", {}).get("moe.load_max_median")
