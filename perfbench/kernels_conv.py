"""What the gates and taps of a gated short convolution NEED, from its
shapes: the bytes a roofline share is taken against.  Kept with the
benchmark, beside ``kernels.py`` and ``kernels_hybrid.py``, so that no
later PR can move the yardstick with what implements it (``jnp`` fused by
XLA or not, or a kernel: the SAME bytes).

The mixer between its two projections: ``v = B * u``; ``c_t = sum_j w_j
v_{t-(K-1-j)}``; ``y = C * c``.  ``B``, ``C`` and ``u`` are written by one
matmul and ``y`` is read by another, so the least a pass between them moves
is what those matmuls hand over.
"""

from __future__ import annotations


def conv_mix_forward_bytes(tokens: int, channels: int,
                           act_bytes: int) -> float:
    """One layer, one forward pass: read ``B``, ``C``, ``u`` (3 x
    channels a token), write ``y`` (channels a token)."""
    return 4.0 * tokens * channels * act_bytes


def conv_mix_backward_bytes(tokens: int, channels: int, act_bytes: int,
                            taps: int) -> float:
    """One layer, one backward pass: read the incoming gradient of ``y``
    and what the forward read (``B``, ``C``, ``u``), write the gradients
    of ``B``, ``C`` and ``u``; the taps' gradient (``taps`` x channels)
    written once."""
    return (7.0 * tokens * channels + taps * channels) * act_bytes


def conv_mix_step_bytes(tokens: int, channels: int, act_bytes: int,
                        taps: int, layers: int, remat: bool) -> float:
    """Bytes the gates and taps of one training step need: the forward,
    the forward again under full rematerialisation (its twin reads and
    writes the same), and the backward."""
    forward = conv_mix_forward_bytes(tokens, channels, act_bytes)
    return layers * (forward * (2 if remat else 1)
                     + conv_mix_backward_bytes(tokens, channels, act_bytes,
                                               taps))
