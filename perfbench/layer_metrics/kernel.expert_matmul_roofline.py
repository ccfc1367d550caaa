"""The grouped expert matmuls' share of the chip's bf16 peak: the FLOPs
the traced steps' calls need (``perfbench/kernels_moe.py``: 6 x tokens x
top_k x hidden x expert width a layer and forward; forward, forward again
under rematerialisation, backward twice that) over peak FLOP/s x their
device time in the trace.  Compute bound: a group of 512 rows against a
[2048, 1024] expert does ~500 FLOPs per byte it reads.

The kernels are the trace's ``gmm.<n>`` (forward, and the rows' gradient)
and ``tgmm.<n>`` (the weights' gradient) operations: the ``megablox``
kernel's ``pallas_call`` has no name, so the instruction takes that of the
jitted function around it (read by hand from a v5e capture, PR 26).  If
their count is not layers x calls x steps the reader reports nothing
rather than a wrong share; a program without the kernel has no such
operation and reports nothing."""

import re

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"

GMM = re.compile(r"^t?gmm(\.\d+)?$")


def read(run):
    from perfbench.kernels_moe import (expert_matmul_step_calls,
                                       expert_matmul_step_flops)
    from perfbench.peaks import peaks_for
    from perfbench.trace_reduce import op_seconds

    trace = run.get("trace")
    sh = run.get("shapes", {})
    if not trace or "expert_width" not in sh:
        return None
    steps = trace["host_spans"].get("bench.train_step", [0, 0])[1]
    seconds = op_seconds(trace, GMM)
    calls = sum(v[1] for k, v in trace["ops"].items() if GMM.match(k))
    per_step = expert_matmul_step_calls(sh["layers"], sh["remat"])
    if not steps or not seconds or calls != per_step * steps * run["chips"]:
        return None
    flops = steps * expert_matmul_step_flops(
        sh["rows"] // run["chips"] * sh["seq"], sh["top_k"], sh["hidden"],
        sh["expert_width"], sh["layers"], sh["remat"])
    peak = peaks_for(run["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / peak / seconds
