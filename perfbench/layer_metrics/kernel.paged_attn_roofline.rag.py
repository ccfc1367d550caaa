"""The paged decode-attention kernel's share of the chip's HBM peak: the K
and V bytes of the live contexts that its calls in the traced window must
read (``perfbench/kernels.py``) over peak bytes/s x the kernel's device time.
Memory bound: one query row per slot reads its whole context.

The kernel is the trace's ``paged_decode_attention.<n>`` operations (read by
hand from a v5e capture, PR 23: one per layer and decode forward; ONE
attention layer here, under ``serving/latent.py _gqa_layer``).  Live
context comes from the benchmark's own books: before every router step, the
prompt + delivered tokens of the requests already decoding; within a chunk
of c forwards each grows by one a forward.  Requests admitted inside that
step are not counted, so the share is, if anything, too low."""

import re

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"

PAGED = re.compile(r"^paged_decode_attention(\.\d+)?$")


def read(run):
    from perfbench.kernels import paged_decode_kv_bytes
    from perfbench.peaks import peaks_for
    from perfbench.trace_reduce import op_seconds

    trace = run.get("trace")
    if not trace:
        return None
    sh = run["shapes"]
    t0, t1 = trace["host_clock"]
    inside = [(ctx, n) for t, ctx, n in run["samples"]["context"]
              if t0 <= t <= t1 and n]
    seconds = op_seconds(trace, PAGED)
    calls = sum(v[1] for k, v in trace["ops"].items() if PAGED.match(k))
    if not inside or not seconds or not calls:
        return None
    c = sh["chunk"]
    # bytes of one layer's call, averaged over the forwards of the window
    per_call = sum(paged_decode_kv_bytes(
        ctx + n * (c - 1) / 2.0, sh["kv_heads"], sh["head_dim"],
        sh["kv_bytes_per_element"], 1) for ctx, n in inside) / len(inside)
    peak = peaks_for(run["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * per_call * calls / peak / seconds
