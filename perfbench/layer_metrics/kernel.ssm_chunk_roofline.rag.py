"""The Mamba-2 chunk kernel's share of its roofline: the larger of the
FLOPs of the chunked scan (the SSD form) over the REAL prompt tokens its
calls in the traced window took over the bf16 peak, and their rows' bytes
over the HBM peak (``perfbench/kernels_ssm.py``), over the kernel's device
time.  Compute is the larger here (~60 FLOPs a byte of float32 rows); the
kernel's matrices are float32, which the MXU multiplies in several bf16
passes, so a sixth of the published peak is the most it can show.

The kernel is the trace's ``ssm_chunk_fwd.<n>`` operations, one a Mamba-2
layer and prompt chunk.  The real tokens are the ``ssm_chunk_rows_real``
the engine books on each ``dlrover.engine.prefill_chunk`` span of the
window (one layer's, as every layer takes the same): what pads a
prompt's last chunk is nobody's work."""

import re

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"

KERNEL = re.compile(r"^ssm_chunk_fwd(\.\d+)?$")
CHUNK = "dlrover.engine.prefill_chunk"


def read(run):
    from perfbench import program_spans as ps
    from perfbench.kernels_ssm import ssm_chunk_bytes, ssm_chunk_flops
    from perfbench.peaks import peaks_for
    from perfbench.trace_reduce import op_seconds

    trace = run.get("trace")
    parsed = ps.of_run(run)
    sh = run.get("shapes", {})
    if not trace or parsed is None or "ssm_heads" not in sh:
        return None
    tokens = sum(int(a["ssm_chunk_rows_real"])
                 for _, _, _, a in ps.named(parsed, CHUNK)
                 if "ssm_chunk_rows_real" in a)
    seconds = op_seconds(trace, KERNEL)
    if not tokens or not seconds:
        return None
    peaks = peaks_for(run["device_kind"])
    args = (tokens, sh["ssm_heads"], sh["ssm_head_dim"], sh["ssm_state"])
    least = max(
        ssm_chunk_flops(*args, layers=sh["ssm_layers"])
        / peaks["bf16_flops_per_s"],
        ssm_chunk_bytes(*args, layers=sh["ssm_layers"])
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
