"""The window flash kernels' share of the chip's bf16 peak: the FLOPs the
window layers' calls of the traced steps need
(``perfbench/kernels_hybrid.py``: 2 x heads x head size x rows x sum_i
min(i + 1, window) a score-matrix matmul; forward 2, again under
rematerialisation, backward 5) over peak FLOP/s x their device time.

The kernels are the trace's ``flash_window_fwd.<n>``, ``flash_window_dq.<n>``
and ``flash_window_dkv.<n>``: only a call with a window is given a
``pallas_call`` name (``ops/pallas/flash_attention.py``).  If their count
is not window layers x calls x steps the reader reports nothing; a
program without the kernels has no such operation and reports nothing."""

import re

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"

WINDOW = re.compile(r"^flash_window_(fwd|dq|dkv)(\.\d+)?$")


def read(run):
    from perfbench.kernels_hybrid import (attention_step_calls,
                                          window_attention_step_flops)
    from perfbench.peaks import peaks_for
    from perfbench.trace_reduce import op_seconds

    trace = run.get("trace")
    sh = run.get("shapes", {})
    if not trace or "window_layers" not in sh:
        return None
    steps = trace["host_spans"].get("bench.train_step", [0, 0])[1]
    seconds = op_seconds(trace, WINDOW)
    calls = sum(v[1] for k, v in trace["ops"].items() if WINDOW.match(k))
    per_step = attention_step_calls(sh["window_layers"], sh["remat"])
    if not steps or not seconds or calls != per_step * steps * run["chips"]:
        return None
    flops = steps * window_attention_step_flops(
        sh["seq"], sh["window_heads"], sh["head_dim"],
        sh["rows"] // run["chips"], sh["window"], sh["window_layers"],
        sh["remat"])
    peak = peaks_for(run["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / peak / seconds
