"""What a kernel's call NEEDS, from its shapes: the operations and bytes a
roofline share is taken against.  Kept with the benchmark, so that no later
PR can move the yardstick with the kernel.

A share is ``needed / peak / measured kernel seconds``; it cannot pass
100 %: above that the count here is too high or the time leaves work out.
"""

from __future__ import annotations


def flash_attention_matmul_flops(seq: int, heads: int, head_dim: int,
                                 rows: int, causal: bool = True) -> float:
    """FLOPs of ONE matmul over the score matrix (``Q K^T`` or ``P V``) for
    ``rows`` sequences: 2 * seq^2 * heads * head_dim, halved when causal
    (the masked half need not be computed)."""
    full = 2.0 * seq * seq * heads * head_dim * rows
    return full * (0.5 if causal else 1.0)


def flash_attention_step_flops(seq: int, heads: int, head_dim: int,
                               rows: int, layers: int, remat: bool) -> float:
    """FLOPs the flash kernels' calls of one training step need: forward
    2 matmuls (Q K^T, P V), run twice under full rematerialisation (each
    call needs its own); backward 5 (recomputed Q K^T, dV, dP, dQ, dK).
    The split dq / dkv kernels recompute Q K^T and dP once more than the
    algorithm needs; that is the kernel's overhead, not its need."""
    unit = flash_attention_matmul_flops(seq, heads, head_dim, rows)
    return layers * unit * ((4 if remat else 2) + 5)


def paged_decode_kv_bytes(context_tokens: float, kv_heads: int,
                          head_dim: int, bytes_per_element: int,
                          layers: int) -> float:
    """Bytes of K and V one decode forward must read for slots whose live
    contexts sum to ``context_tokens``, over all layers."""
    return (context_tokens * kv_heads * head_dim * 2.0
            * bytes_per_element * layers)
