"""The grouped expert matmuls' share of the chip's bf16 peak where the
chip holds a SHARE of each layer's experts: the FLOPs the picks on HELD
experts of the traced steps need (``perfbench/kernels_hybrid.py``: 6 x
picks x hidden x expert width a forward; forward, forward again under
rematerialisation, backward twice that) over peak FLOP/s x the kernels'
device time.  The picks are the traced steps' own ``moe_picks_held`` step
metric (``models/moe.py routing_stats``), not tokens x top_k, which would
count the rows behind the held groups, 8 x too much here: hence a reader
of its own and not ``kernel.expert_matmul_roofline.py``.

The kernels are the trace's ``gmm.<n>`` and ``tgmm.<n>`` (``megablox``
gives its ``pallas_call`` no name).  If their count is not sparse layers x
12 calls x steps the reader reports nothing."""

import re

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"

GMM = re.compile(r"^t?gmm(\.\d+)?$")


def read(run):
    from perfbench.kernels_hybrid import held_expert_matmul_flops
    from perfbench.kernels_moe import expert_matmul_step_calls
    from perfbench.peaks import peaks_for
    from perfbench.trace_reduce import op_seconds

    trace = run.get("trace")
    sh = run.get("shapes", {})
    picks = run.get("counters", {}).get("moe.picks_held_traced")
    if not trace or not picks or "sparse_layers" not in sh:
        return None
    steps = trace["host_spans"].get("bench.train_step", [0, 0])[1]
    seconds = op_seconds(trace, GMM)
    calls = sum(v[1] for k, v in trace["ops"].items() if GMM.match(k))
    per_step = expert_matmul_step_calls(sh["sparse_layers"], sh["remat"])
    if not steps or not seconds or calls != per_step * steps * run["chips"]:
        return None
    flops = held_expert_matmul_flops(picks, sh["hidden"], sh["expert_width"],
                                     sh["remat"])
    peak = peaks_for(run["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / peak / seconds
