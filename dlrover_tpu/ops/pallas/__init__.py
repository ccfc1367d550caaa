"""Pallas TPU kernels.

- ``flash_attention``: training's causal / segmented / windowed attention,
  forward and backward.
- ``paged_attention``: a dense decoder's decode attention over paged K/V
  pools (bf16, int8), ending at each slot's length.
- ``paged_index``: a latent model's index scores over a slot's live pages
  of index keys (decode).
- ``mla_prefill``: a latent model's masked, absorbed attention of a run
  of queries (a prefill chunk) over its live pages of latent rows.
- ``mla_decode``: a latent model's absorbed attention of one query a slot
  over the slot's live pages of latent rows (decode, no selection).
- ``kda``: linear attention with a recurrent state (a gated delta rule
  with a decay a channel): one token for every active slot in place, and
  a run of one slot's tokens in chunks of 64.
- ``quant_matmul``: int8 x int8 matmul with per-row / per-column scales.
"""


def interpret_off_chip() -> bool:
    """Whether callers that pick the mode themselves run a kernel in
    Pallas interpret mode.  Kernels compile for the TPU; only on the CPU
    (or GPU) backend — where the tests run — do they interpret, as a
    parity harness.  This is the one place that decides it: on a chip
    the answer is always False, so a kernel that does not compile there
    fails instead of being interpreted."""
    import jax

    return jax.default_backend() in ("cpu", "gpu")
