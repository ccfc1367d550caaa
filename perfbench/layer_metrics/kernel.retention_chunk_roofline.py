"""The power-retention chunk kernel's share of its roofline: the larger of
the FLOPs that the REAL prompt tokens its calls in the traced window took
need (every query head's read-out of the state, every key head's update:
``perfbench/kernels_retention.py``; the quadratic part inside a chunk is
the implementation's and is not counted) over the bf16 peak, and their
bytes (the state in and out a run, the tokens' rows) over the HBM peak,
over the kernel's device time.  Compute is the larger at 512 tokens a run.
The share is of the published bf16 peak, the only one there is: with
float32 operands (six passes) a sixth is the most a product can show.

The kernel is the trace's ``retention_chunk_fwd.<n>`` operations, one a
layer and prompt chunk.  The real tokens are the
``retention_chunk_rows_real`` the engine books on each
``dlrover.engine.prefill_chunk`` span of the window (one layer's, as every
layer takes the same), the runs those spans' rows (``n``): what pads a
prompt's last chunk is nobody's work."""

import re

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"

KERNEL = re.compile(r"^retention_chunk_fwd(\.\d+)?$")
CHUNK = "dlrover.engine.prefill_chunk"


def read(run):
    from perfbench import program_spans as ps
    from perfbench.kernels_retention import (retention_chunk_bytes,
                                             retention_chunk_flops)
    from perfbench.peaks import peaks_for
    from perfbench.trace_reduce import op_seconds

    trace = run.get("trace")
    parsed = ps.of_run(run)
    sh = run.get("shapes", {})
    if not trace or parsed is None or "retention_layers" not in sh:
        return None
    spans = [a for _, _, _, a in ps.named(parsed, CHUNK)
             if "retention_chunk_rows_real" in a]
    tokens = sum(int(a["retention_chunk_rows_real"]) for a in spans)
    runs = sum(int(a.get("n", 1)) for a in spans)
    seconds = op_seconds(trace, KERNEL)
    if not tokens or not seconds:
        return None
    peaks = peaks_for(run["device_kind"])
    dims = (sh["heads"], sh["kv_heads"], sh["head_dim"])
    least = max(
        retention_chunk_flops(tokens, *dims, layers=sh["retention_layers"])
        / peaks["bf16_flops_per_s"],
        retention_chunk_bytes(tokens, runs, *dims,
                              layers=sh["retention_layers"])
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
