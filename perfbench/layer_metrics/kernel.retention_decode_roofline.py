"""The power-retention decode kernel's share of the chip's HBM peak: the
state bytes that its calls in the traced window must move (every decoding
slot's float32 state and sum of keys, each unordered pair ONCE, read once
and written once: ``perfbench/kernels_retention.py``) over peak bytes/s x
the kernel's device time.  Memory bound: under two FLOPs a byte.  The
kernel keeps the pairs in whole tiles (8 320 rows for 8 256), so it moves
0.8 % more than it is charged.

The kernel is the trace's ``retention_decode_step.<n>`` operations, one a
layer and decode forward.  The decoding slots come from the benchmark's own
books, as ``kernel.ssm_decode_roofline`` takes them: before every router
step, the requests that have delivered a token.  The same work whatever
implements it: a step that walked idle slots too is charged the decoding
ones alone.  Requests admitted inside that step are not counted, so the
share is, if anything, too low."""

import re

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"

KERNEL = re.compile(r"^retention_decode_step(\.\d+)?$")


def read(run):
    from perfbench.kernels_retention import retention_decode_bytes
    from perfbench.peaks import peaks_for
    from perfbench.trace_reduce import op_seconds

    trace = run.get("trace")
    if not trace:
        return None
    sh = run["shapes"]
    t0, t1 = trace["host_clock"]
    inside = [n for t, _, n in run["samples"]["context"]
              if t0 <= t <= t1 and n]
    seconds = op_seconds(trace, KERNEL)
    calls = sum(v[1] for k, v in trace["ops"].items() if KERNEL.match(k))
    if not inside or not seconds or not calls \
            or "retention_layers" not in sh:
        return None
    per_call = sum(retention_decode_bytes(n, sh["kv_heads"], sh["head_dim"])
                   for n in inside) / len(inside)
    peak = peaks_for(run["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * per_call * calls / peak / seconds
