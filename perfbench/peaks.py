"""Published peaks of the chips the benchmark may run on, keyed by the
exact ``device_kind`` JAX reports.  A device that is not here is an
error, never a default: a roofline share against a guessed peak is noise.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud docs, TPU v5e system architecture",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add a "
            "row to perfbench/peaks.py with its source") from None
