"""What the kernels of a served Mamba-2 state-space layer NEED, from their
shapes (``perfbench/kernels.py``'s build: kept with the benchmark, so that
no later PR moves the yardstick with the kernel).  The published
mathematics, not what an implementation happens to do.

``ssm_decode_step`` (``dlrover_tpu/ops/pallas/ssm.py``): one token a slot.
A head's float32 state of ``head_dim`` x ``state`` is read once and
written once: 2 x 32 KiB at granite-4.0-h-small's 64 x 128, against ~5
FLOPs an element (the decay, the rank-one update, the read-out): under one
FLOP a byte against the chip's 240.  Memory bound; the vectors (a head's
``x`` of 64, the slot's ``B`` and ``C`` of 128) are under a 100th of the
state and are not counted.

``ssm_chunk_fwd``: a run of tokens in chunks of ``chunk`` (the SSD form),
the state carried between them.  A chunk and head: the masked scores times
the inputs (``(L o C B^T) (dt X)``: ``chunk``^2 x ``head_dim``
multiply-adds, half of a full product, so ``chunk^2 head_dim`` FLOPs), the
state's read-out ``C S^T`` and its update ``(dt X)^T B`` (2 x ``chunk`` x
``head_dim`` x ``state`` FLOPs each); and ``C B^T`` ONCE a slot and chunk,
shared by all heads (``chunk``^2 x ``state`` FLOPs, half of a full
product).  A token and head at 128, 64 and 128: 8 k + 32 k FLOPs, beside
0.5 KiB of its rows (x in, y out: float32) and the slot's B and C: ~60
FLOPs a byte, neither bound by far; the share is of whichever is the
larger, compute here.  The kernel's matrices are float32, which the MXU
multiplies in several bf16 passes: the share is of the published bf16
peak, the only one there is, so a sixth is the most a float32 product can
show.
"""

from __future__ import annotations


def ssm_decode_bytes(active_slots: float, heads: int, head_dim: int,
                     state: int, layers: int = 1) -> float:
    """Bytes one decode forward must move for ``active_slots`` slots: each
    head's float32 state read and written, over ``layers`` layers."""
    return active_slots * heads * 2.0 * head_dim * state * 4 * layers


def ssm_chunk_flops(tokens: float, heads: int, head_dim: int, state: int,
                    chunk: int = 128, layers: int = 1) -> float:
    """FLOPs of the chunked scan over ``tokens`` real tokens of one
    slot's runs: a head's three products, and ``C B^T`` once for all
    heads."""
    a_head = chunk * head_dim + 4.0 * head_dim * state
    return tokens * (heads * a_head + chunk * state) * layers


def ssm_chunk_bytes(tokens: float, heads: int, head_dim: int, state: int,
                    layers: int = 1) -> float:
    """Bytes of the same: x in and y out a head (float32 rows of
    ``head_dim``), the step and the decay a head, B and C a token."""
    return tokens * (heads * (2.0 * head_dim + 2) + 2.0 * state) * 4 * layers
