"""The window layers' decode attention's share of the chip's HBM peak: the
bytes of the rows INSIDE the windows of the decoding slots that its calls
in the traced window must read (``perfbench/kernels_window.py``: rows x
2 304 B a window layer) over peak bytes/s x its device time.  Memory
bound: one absorbed query a slot reads its window's rows once for all
heads.

The kernel is the trace's ``mla_window_decode_attn.<n>`` operations, one a
window layer and decode forward (the decode kernel called again under a
name of its own).  The rows come from the benchmark's own books: before
every router step, ``min(prompt + delivered tokens, 513)`` of the requests
already decoding.  What the kernel streams beyond the window (whole
blocks: ``engine.window_stream_ratio.mixed``) is no part of the work, so
the share cannot pass 100 x in-window / streamed.  A trace with no such
operation (the parent of PR 47) reports nothing."""

import re

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"

KERNEL = re.compile(r"^mla_window_decode_attn(\.\d+)?$")


def read(run):
    from perfbench.kernels_window import window_decode_bytes
    from perfbench.peaks import peaks_for
    from perfbench.trace_reduce import op_seconds

    trace = run.get("trace")
    samples = run.get("samples", {}).get("window")
    if not trace or not samples:
        return None
    sh = run["shapes"]
    t0, t1 = trace["host_clock"]
    inside = [rows for t, rows, n in samples if t0 <= t <= t1 and n]
    seconds = op_seconds(trace, KERNEL)
    calls = sum(v[1] for k, v in trace["ops"].items() if KERNEL.match(k))
    if not inside or not seconds or not calls:
        return None
    per_call = sum(window_decode_bytes(rows, sh["window_row_bytes"])
                   for rows in inside) / len(inside)
    peak = peaks_for(run["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * per_call * calls / peak / seconds
