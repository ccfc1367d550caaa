"""Pallas TPU kernels."""


def interpret_off_chip() -> bool:
    """Whether callers that pick the mode themselves run a kernel in
    Pallas interpret mode.  Kernels compile for the TPU; only on the CPU
    (or GPU) backend — where the tests run — do they interpret, as a
    parity harness.  This is the one place that decides it: on a chip
    the answer is always False, so a kernel that does not compile there
    fails instead of being interpreted."""
    import jax

    return jax.default_backend() in ("cpu", "gpu")
