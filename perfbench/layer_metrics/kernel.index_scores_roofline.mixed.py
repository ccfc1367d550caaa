"""The index-score kernel's share of the chip's HBM peak: the index-key
bytes of the live contexts that its calls in the traced window must read
(``perfbench/kernels_sparse.py``) over peak bytes/s x the kernel's device
time.  Memory bound: one index query a slot scores its whole context.

The kernel is the trace's ``paged_index_scores.<n>`` operations, one a
FULL layer and decode forward (``serve-mixed-window``: three; a window
layer has no indexer).  Live context comes from the benchmark's own
books, as ``kernel.paged_attn_roofline.closed`` takes it: before every
router step, prompt + delivered tokens of the requests already decoding;
within a chunk of c forwards each grows by one a forward.  Requests
admitted inside that step are not counted, so the share is, if anything,
too low."""

import re

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"

KERNEL = re.compile(r"^paged_index_scores(\.\d+)?$")


def read(run):
    from perfbench.kernels_sparse import index_scores_bytes
    from perfbench.peaks import peaks_for
    from perfbench.trace_reduce import op_seconds

    trace = run.get("trace")
    if not trace:
        return None
    sh = run["shapes"]
    t0, t1 = trace["host_clock"]
    inside = [(ctx, n) for t, ctx, n in run["samples"]["context"]
              if t0 <= t <= t1 and n]
    seconds = op_seconds(trace, KERNEL)
    calls = sum(v[1] for k, v in trace["ops"].items() if KERNEL.match(k))
    if not inside or not seconds or not calls:
        return None
    c = sh["chunk"]
    per_call = sum(index_scores_bytes(
        ctx + n * (c - 1) / 2.0, sh["index_dim"],
        sh["index_bytes_per_element"]) for ctx, n in inside) / len(inside)
    peak = peaks_for(run["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * per_call * calls / peak / seconds
