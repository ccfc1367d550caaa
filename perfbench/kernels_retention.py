"""What the kernels of a served power-retention layer (degree 2) NEED, from
their shapes (``perfbench/kernels.py``'s build: kept with the benchmark, so
that no later PR moves the yardstick with the kernel).  The published
mathematics, not what an implementation happens to do.

A KEY head of ``head_dim`` d keeps, float32, a state of ``d (d + 1) / 2``
rows (the symmetric square's unordered pairs, each ONCE: 8 256 at 128) by
``d`` values and one more column, the sum of keys: ``pairs x (d + 1) x 4``
bytes (4.26 MB at 128).  A layout that pads the pairs to whole tiles moves
more than this and reads LOWER here, whatever implements it.

``retention_decode_step`` (``dlrover_tpu/ops/pallas/retention.py``): one
token a slot.  A key head's state and sum of keys are read once and
written once, against ~13 FLOPs an element (the decay, the rank-one
update, the read-out by ``group`` query heads): ~1.6 FLOPs a byte against
the chip's 240.  Memory bound; the vectors (``group`` queries, a key, a
value of ``d``) are a 1 000th of the state and are not counted.

``retention_chunk_fwd``: a run of tokens of one slot, the state carried
across.  A token and key head: the read-out ``phi(q) . [S | z]`` for each of
its ``group`` query heads (``2 x pairs x (d + 1)`` FLOPs a query head) and
the update ``phi(k) [v | 1]^T`` (``2 x pairs x (d + 1)``); the quadratic
part inside an implementation's chunk is its own choice and is NOT counted
(it is ~5 % of the above at a chunk of 128).  A token at 40 / 8 heads of
128: 102 M FLOPs.  Bytes: the state read once and written once a RUN and
layer (a prompt chunk's program: the ``runs`` argument), and a token's q,
k, v in and y out, float32.  At 512 tokens a run: ~770 FLOPs a byte,
compute bound; the share is of whichever bound is the larger.
"""

from __future__ import annotations


def pairs(head_dim: int) -> int:
    """Unordered pairs of a head's dimensions, a dimension with itself
    among them."""
    return head_dim * (head_dim + 1) // 2


def state_bytes(kv_heads: int, head_dim: int) -> float:
    """A slot's float32 state and sum of keys of ONE layer, each pair
    once."""
    return kv_heads * pairs(head_dim) * (head_dim + 1) * 4.0


def retention_decode_bytes(active_slots: float, kv_heads: int,
                           head_dim: int, layers: int = 1) -> float:
    """Bytes one decode forward must move for ``active_slots`` slots: each
    key head's state and sum of keys read and written, over ``layers``
    layers."""
    return active_slots * 2.0 * state_bytes(kv_heads, head_dim) * layers


def retention_chunk_flops(tokens: float, heads: int, kv_heads: int,
                          head_dim: int, layers: int = 1) -> float:
    """FLOPs of ``tokens`` real tokens of runs: every query head's
    read-out and every key head's update."""
    return tokens * (heads + kv_heads) * 2.0 * pairs(head_dim) \
        * (head_dim + 1) * layers


def retention_chunk_bytes(tokens: float, runs: float, heads: int,
                          kv_heads: int, head_dim: int,
                          layers: int = 1) -> float:
    """Bytes of the same: the state in and out a run, q and y a query head
    and k and v a key head a token, float32."""
    return (runs * 2.0 * state_bytes(kv_heads, head_dim)
            + tokens * (2.0 * heads + 2.0 * kv_heads) * head_dim * 4) * layers
