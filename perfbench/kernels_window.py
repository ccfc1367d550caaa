"""What the decode attention of a served WINDOW layer of latent attention
NEEDS, from the benchmark's own books of lengths (``perfbench/kernels.py``'s
build: kept with the benchmark, so that no later PR moves the yardstick
with the kernel; the same work whatever implements it).

A window layer's query at position ``t`` sees the ``window`` keys ``t -
window < s <= t``: ``min(t + 1, window)`` cached rows ``[c_kv | k_r]`` of
``row_width`` values, read ONCE for all heads, each meeting ``heads`` x
(``row_width`` + ``latent``) multiply-adds (dots3-note: 64 x 2 x (1 152 +
1 024) FLOPs for 2 304 bytes, 121 a byte against the chip's 240).  Memory
bound.  Rows outside the window are no part of the work, whatever a
kernel streams of them (``engine.window_stream_ratio`` says how many).
"""

from __future__ import annotations


def window_rows(lengths, window: int) -> float:
    """Rows inside the windows of queries that see ``lengths`` keys with
    no window."""
    return float(sum(min(int(n), window) for n in lengths))


def window_decode_bytes(rows_in_window: float, row_bytes: int,
                        layers: int = 1) -> float:
    """Bytes one decode forward must read for slots whose windows hold
    ``rows_in_window`` rows in all, over ``layers`` window layers."""
    return rows_in_window * row_bytes * layers


def window_decode_flops(rows_in_window: float, heads: int, row_width: int,
                        latent: int, layers: int = 1) -> float:
    """FLOPs of the same: ``q . row`` over the row's width and ``p x
    row[:latent]``, every head and row inside a window."""
    return rows_in_window * heads * 2.0 * (row_width + latent) * layers
