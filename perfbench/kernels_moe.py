"""What the grouped expert matmuls of a sparse layer NEED, from its shapes:
the operations a roofline share is taken against.  Kept with the
benchmark, beside ``kernels.py``, so that no later PR can move the
yardstick with the kernel.
"""

from __future__ import annotations


def expert_matmul_forward_flops(tokens: int, top_k: int, hidden: int,
                                expert_width: int) -> float:
    """FLOPs of one layer's three expert matmuls (gate, up, down) in one
    forward pass: every token visits ``top_k`` experts, none is dropped,
    so 3 matmuls x 2 x (tokens x top_k) x hidden x expert_width, however
    the picks are spread over the experts."""
    return 6.0 * tokens * top_k * hidden * expert_width


def expert_matmul_step_flops(tokens: int, top_k: int, hidden: int,
                             expert_width: int, layers: int,
                             remat: bool) -> float:
    """FLOPs the grouped matmuls of one training step need: the forward,
    the forward again under full rematerialisation (each call needs its
    own), and the backward at twice the forward (the gradient of the rows
    and that of the weights)."""
    unit = expert_matmul_forward_flops(tokens, top_k, hidden, expert_width)
    return layers * unit * ((2 if remat else 1) + 2)


def expert_matmul_step_calls(layers: int, remat: bool) -> int:
    """Kernel calls a step: 3 matmuls a forward, and in the backward 3
    for the rows' gradient and 3 for the weights'."""
    return layers * 3 * ((2 if remat else 1) + 2)
