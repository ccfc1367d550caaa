"""What the kernels of a model with window and full attention layers and
a SHARE of its experts need, from its shapes: the operations a roofline
share is taken against.  Kept with the benchmark, beside ``kernels.py`` and
``kernels_moe.py``, so that no later PR can move the yardstick with the
kernel.
"""

from __future__ import annotations

from perfbench.kernels import flash_attention_matmul_flops


def window_attention_matmul_flops(seq: int, heads: int, head_dim: int,
                                  rows: int, window: int) -> float:
    """FLOPs of ONE matmul over the score matrix (``Q K^T`` or ``P V``)
    of a window layer for ``rows`` sequences: query ``i`` sees
    ``min(i + 1, window)`` keys, so
    2 x heads x head_dim x rows x sum_i min(i + 1, window)."""
    full = min(window, seq)
    visible = full * (full + 1) // 2 + (seq - full) * full
    return 2.0 * heads * head_dim * rows * visible


def attention_step_flops(unit: float, layers: int, remat: bool) -> float:
    """FLOPs the flash kernels' calls of one training step need, from one
    score-matrix matmul's (``unit``): forward 2 matmuls, run twice under
    full rematerialisation; backward 5 (``kernels.py``)."""
    return layers * unit * ((4 if remat else 2) + 5)


def window_attention_step_flops(seq, heads, head_dim, rows, window, layers,
                                remat) -> float:
    return attention_step_flops(
        window_attention_matmul_flops(seq, heads, head_dim, rows, window),
        layers, remat)


def full_attention_step_flops(seq, heads, head_dim, rows, layers,
                              remat) -> float:
    return attention_step_flops(
        flash_attention_matmul_flops(seq, heads, head_dim, rows),
        layers, remat)


def attention_step_calls(layers: int, remat: bool) -> int:
    """Kernel calls a step: the forward (twice under rematerialisation),
    dq and dkv."""
    return layers * (4 if remat else 3)


def held_expert_matmul_flops(picks_held: float, hidden: int,
                             expert_width: int, remat: bool) -> float:
    """FLOPs the grouped matmuls need for ``picks_held`` rows on held
    experts (summed over layers and steps): 3 matmuls x 2 x rows x hidden
    x expert_width a forward; forward, forward again under
    rematerialisation, backward twice that.  From the picks the router
    MADE on held experts, not from tokens x top_k: the rows behind the
    held groups are multiplied by nothing."""
    return 6.0 * picks_held * hidden * expert_width * (
        (2 if remat else 1) + 2)
