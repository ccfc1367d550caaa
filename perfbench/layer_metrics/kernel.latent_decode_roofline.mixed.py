"""The latent decode kernel's share of the chip's HBM peak in this cell
(``kernel.latent_decode_roofline.longctx``'s quantity and arithmetic): the
latent-row bytes of the live contexts that its calls in the traced window
must read (``perfbench/kernels_latent.py``) over peak bytes/s x the
kernel's device time.  The kernel is the trace's ``mla_decode_attn.<n>``
operations, one a FULL layer and decode forward (``serve-mixed-window``: the
three full layers' calls, under the selection's mask; a window layer's call
has a name of its own, ``mla_window_decode_attn``, and is read by
``kernel.window_decode_roofline.mixed``); live context comes from the
benchmark's own books.  Half the slots here are 16-31 k deep, half under
2.6 k."""

import re

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"

KERNEL = re.compile(r"^mla_decode_attn(\.\d+)?$")


def read(run):
    from perfbench.kernels_latent import latent_decode_bytes
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
    per_call = sum(latent_decode_bytes(
        ctx + n * (c - 1) / 2.0, sh["latent_row_bytes"])
        for ctx, n in inside) / len(inside)
    peak = peaks_for(run["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * per_call * calls / peak / seconds
