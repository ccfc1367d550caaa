"""The full-attention flash kernels' share of the chip's bf16 peak in a
model that also has window layers: the FLOPs the FULL layers' calls of the
traced steps need (``perfbench/kernels.py``'s causal score matrix at the
full layers' head count) over peak FLOP/s x their device time.

A call without a window keeps an unnamed ``pallas_call``, whose instruction
takes the name of the scope around it: ``attn_full.<n>`` in a model that
describes its layers one by one (``models/llama.py`` opens
``jax.named_scope("attn_full")`` around the call; read off a v5e capture,
PR 31).  If their count is not full layers x calls x steps the reader
reports nothing."""

import re

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"

FULL = re.compile(r"^attn_full(\.\d+)?$")


def read(run):
    from perfbench.kernels_hybrid import (attention_step_calls,
                                          full_attention_step_flops)
    from perfbench.peaks import peaks_for
    from perfbench.trace_reduce import op_seconds

    trace = run.get("trace")
    sh = run.get("shapes", {})
    if not trace or "full_layers" not in sh:
        return None
    steps = trace["host_spans"].get("bench.train_step", [0, 0])[1]
    seconds = op_seconds(trace, FULL)
    calls = sum(v[1] for k, v in trace["ops"].items() if FULL.match(k))
    per_step = attention_step_calls(sh["full_layers"], sh["remat"])
    if not steps or not seconds or calls != per_step * steps * run["chips"]:
        return None
    flops = steps * full_attention_step_flops(
        sh["seq"], sh["full_heads"], sh["head_dim"],
        sh["rows"] // run["chips"], sh["full_layers"], sh["remat"])
    peak = peaks_for(run["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / peak / seconds
