"""What the decode kernel of a served latent-attention model that attends
to its whole context NEEDS, from its shapes (``perfbench/kernels.py``'s
build: kept with the benchmark, so that no later PR moves the yardstick
with the kernel).

``mla_decode_attn`` (``dlrover_tpu/ops/pallas/mla_decode.py``): one
absorbed query of ``heads`` heads a slot scores every live latent row of
its slot and takes the attended latent.  A row of ``row_width`` values is
read ONCE for all heads and meets ``heads`` x (``row_width`` +
``latent``) multiply-adds: 64 x 2 x (640 + 512) FLOPs for 1 280 bytes at
sarvam-105b's sizes, 115 a byte against the chip's 240.  Memory bound.
"""

from __future__ import annotations


def latent_decode_bytes(context_tokens: float, row_bytes: int,
                        layers: int = 1) -> float:
    """Bytes of latent rows one decode forward must read for slots whose
    live contexts sum to ``context_tokens``, over ``layers`` layers (the
    rows as the pool holds them: 576 values padded to 640)."""
    return context_tokens * row_bytes * layers


def latent_decode_flops(context_tokens: float, heads: int, row_width: int,
                        latent: int, layers: int = 1) -> float:
    """FLOPs of the same: ``q . row`` over the row's width and ``p x
    row[:latent]``, every head and live row."""
    return context_tokens * heads * 2.0 * (row_width + latent) * layers
