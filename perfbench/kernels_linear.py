"""What the kernels of a served linear-attention (KDA) layer NEED, from
their shapes (``perfbench/kernels.py``'s build: kept with the benchmark,
so that no later PR moves the yardstick with the kernel).  The published
mathematics, not what an implementation happens to do.

``kda_decode_step`` (``dlrover_tpu/ops/pallas/kda.py``): one token a slot.
A head's float32 state of ``dim`` x ``dim`` is read once and written once:
2 x 64 KiB at Kimi-Linear's 128, against ~7 ``dim``^2 FLOPs (the decay, the
state's answer at the key, the rank-one update, the read-out): under one
FLOP a byte against the chip's 240.  Memory bound; the vectors (five rows
of ``dim`` a head) are a 64th of the state and are not counted.

``kda_chunk_fwd``: a run of tokens in chunks of ``chunk``, the state
carried between them.  A chunk and head: the two causal score matrices
(``K K^T`` and ``Q K^T`` with the decay folded in: ``chunk``^2 x ``dim``
multiply-adds each, half of a full product), the triangular solve for the
corrected values and their use (``chunk``^2 x ``dim`` each), and three
products with the state (``K S``, ``Q S``, ``K^T U``: 2 x ``chunk`` x
``dim``^2 each): ``4 chunk^2 dim + 6 chunk dim^2`` FLOPs, 131 k a token
and head at 64 and 128, beside 3 KiB of its rows (q, k, v, the decay, beta
in; o out: float32).  At 43 FLOPs a byte it is neither: the share is of
whichever bound is the larger, compute here.  Its matrices are float32,
which the MXU multiplies in several bf16 passes: the share is of the
published bf16 peak, the only one there is, so a sixth is the most a
float32 product can show.
"""

from __future__ import annotations


def kda_decode_bytes(active_slots: float, heads: int, dim: int,
                     layers: int = 1) -> float:
    """Bytes one decode forward must move for ``active_slots`` slots: each
    head's float32 state read and written, over ``layers`` layers."""
    return active_slots * heads * 2.0 * dim * dim * 4 * layers


def kda_decode_flops(active_slots: float, heads: int, dim: int,
                     layers: int = 1) -> float:
    return active_slots * heads * 7.0 * dim * dim * layers


def kda_chunk_flops(tokens: float, heads: int, dim: int, chunk: int = 64,
                    layers: int = 1) -> float:
    """FLOPs of the chunked delta rule over ``tokens`` real tokens."""
    return tokens * heads * (4.0 * chunk * dim + 6.0 * dim * dim) * layers


def kda_chunk_bytes(tokens: float, heads: int, dim: int,
                    layers: int = 1) -> float:
    """Bytes of the same: q, k, v and the decay in, o out (float32 rows
    of ``dim``), beta."""
    return tokens * heads * (5.0 * dim + 1) * 4 * layers
