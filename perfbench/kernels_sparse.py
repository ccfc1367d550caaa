"""What the kernels of a served model with a learned selection of keys
NEED, from their shapes (``perfbench/kernels.py``'s build: kept with the
benchmark, so that no later PR moves the yardstick with the kernel).

``paged_index_scores`` (``dlrover_tpu/ops/pallas/paged_index.py``): one
index query of ``index_heads`` heads a slot scores every live index key of
its slot.  Memory bound: a key row of ``index_dim`` values is read once and
meets ``index_heads`` x ``index_dim`` multiply-adds, 32 FLOPs a byte at
GLM-5's sizes against the chip's 240.
"""

from __future__ import annotations


def index_scores_bytes(context_tokens: float, index_dim: int,
                       bytes_per_element: int, layers: int = 1) -> float:
    """Bytes of index keys one decode forward must read for slots whose
    live contexts sum to ``context_tokens``, over ``layers`` layers."""
    return context_tokens * index_dim * bytes_per_element * layers


def index_scores_flops(context_tokens: float, index_heads: int,
                       index_dim: int, layers: int = 1) -> float:
    """FLOPs of the same: ``q_i k_i^T`` over every head and live key (the
    ReLU, the weights and the sum over heads are 3 more a head and key)."""
    return context_tokens * index_heads * (2.0 * index_dim + 3.0) * layers
