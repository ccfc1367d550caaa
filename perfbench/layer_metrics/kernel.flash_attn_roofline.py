"""The flash-attention kernels' share of the chip's bf16 peak: the FLOPs
their calls of the traced steps need (``perfbench/kernels.py``: forward 2
matmuls over the causal score matrix, run again under rematerialisation,
backward 5) over peak FLOP/s x their device time in the trace.  Compute
bound: at seq 4096 and head 128 the kernel does 512 FLOPs per byte it reads.

The kernels are the trace's ``attn.<n>`` operations on one chip and its
``shard_map.<n>`` operations on a mesh: ``pallas_call`` is given no name,
so the instruction takes the flax module's, or that of the ``shard_map``
the kernel is wrapped in across chips (read by hand from v5e captures, PR
23: four per layer and step at 1.5-2.1 ms each in both; every
``custom-call.<n>`` lasts 0 ns).  If their count is not layers x calls x
steps x chips the reader reports nothing rather than a wrong share."""

import re

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"

FLASH = re.compile(r"^(attn|shard_map)(\.\d+)?$")


def read(run):
    from perfbench.kernels import flash_attention_step_flops
    from perfbench.peaks import peaks_for
    from perfbench.trace_reduce import op_seconds

    trace = run.get("trace")
    if not trace:
        return None
    sh = run["shapes"]
    steps = trace["host_spans"].get("bench.train_step", [0, 0])[1]
    seconds = op_seconds(trace, FLASH)
    calls = sum(v[1] for k, v in trace["ops"].items() if FLASH.match(k))
    per_step = sh["layers"] * (4 if sh["remat"] else 3)
    if not steps or not seconds or calls != per_step * steps * run["chips"]:
        return None
    flops = steps * flash_attention_step_flops(
        sh["seq"], sh["heads"], sh["head_dim"], sh["rows"] // run["chips"],
        sh["layers"], sh["remat"])
    peak = peaks_for(run["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / peak / seconds
