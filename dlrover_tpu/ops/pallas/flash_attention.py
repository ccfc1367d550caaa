"""Pallas TPU flash attention (forward + backward, causal, segment ids).

The TPU-native counterpart of the reference's FlashAttention-2 CUDA
integration (reference: atorch/atorch/modules/transformer/layers.py:1278
``FlashAttnModule`` and tfplus/tfplus/flash_attn/ops/flash_attention_ops.cc)
— re-implemented from the blockwise online-softmax algorithm as Pallas
kernels so the MXU sees [block_q, d] x [d, block_k] matmuls and HBM never
holds the [sq, skv] score matrix.

Layout: kernels run on [batch, heads, seq, dim] so the trailing (seq, dim)
block dims are MXU/VPU tile friendly.  GQA never materializes repeated
K/V: the K/V BlockSpec index maps divide the query-head grid index by the
group size (``ih // reps``), so each query-head block reads its kv head's
block directly from HBM.

Forward (per batch x head x q-block, kv-blocks innermost grid dim):
    m, l, acc scratch carried across kv blocks.  LSE is written for backward.
Backward: FlashAttention-2 style — a precomputed delta = rowsum(do * o),
    one kernel accumulating dq over kv blocks, one accumulating (dk, dv)
    over q blocks (on K Q^T, the scores transposed).

Which blocks are masked.  A grid step fetches one DMA block of scores
([block_q, block_k], 1024 x 1024 by default) and walks it in compute
tiles (``_for_live_tiles``).  Under a causal mask and no segment ids a
block is one of three kinds, told apart from the program ids: above the
diagonal it is skipped (and asks for no new K/V); wholly below it every
tile runs with NO mask (no iota, compare or select); crossed by it, only
the ``_SUB_TILE``-wide tiles ON the diagonal are masked, the tiles below
run unmasked and the tiles above do not run.  That needs the diagonal to
cross a block corner to corner (block_q == block_k, seq_offset a multiple
of it), which the training shapes give; any other crossed block is masked
whole.  With segment ids every tile of every block that runs keeps the
full mask and the guard for rows it masks fully.  ``score_tiles`` counts
all this from the shapes.

A window.  ``window=w`` lets query ``i`` see the keys ``i - w < j <= i``
(its own counted; causal only).  A block is then skipped above the
diagonal OR wholly below the window, runs unmasked wholly inside both,
and is masked where it crosses either boundary: with corner-to-corner
blocks the crossed ones are known when the kernel is traced (the offset
``q0 - k0`` takes a few multiples of the block), and each runs only its
live tiles, masked only where a boundary passes through them.  The grid
is as wide as a window reaches (two k blocks a q block at block 1024 and
``w`` 512), not as the sequence: a grid step walks back from the row's
last live block (``_walk_block``).  Only calls WITH a window are named
(``flash_window_fwd`` / ``_dq`` / ``_dkv``); ``w`` at or over the key
length is the kernel without one.

Numerics: MXU operands in the inputs' dtype (``q * scale``, ``p`` and
``ds`` rounded to it once, explicitly: with f32 operands Mosaic's default
precision rounds to bf16 inside the matmul anyway), f32 accumulators,
scores, softmax statistics, ``lse`` and ``delta``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The DMA block a grid step fetches, overridable for end-to-end sweeps (and
# per-deployment tuning) without code edits.  2048-wide blocks exceed the
# 16MB scoped-VMEM limit in the backward; _fwd/_bwd clamp blocks to the
# sequence length.  The compute tile inside the block is _SUB_TILE below.
# Measured on a TPU v5e (PR 29, device time of one call from a profiler
# trace, bf16, seq 4096, head_dim 128, causal; before -> after this tiling):
#   32 q / 8 kv heads (Mistral-7B): fwd 2.249 -> 1.223 ms, dq 1.623 -> 1.353,
#     dkv 2.028 -> 1.612; a layer and step under remat (2 fwd + dq + dkv)
#     8.149 -> 5.411 ms, 58 % of the bf16 peak on what causal attention needs
#   16 / 16 heads (OLMoE): 1.126 -> 0.609, 0.809 -> 0.676, 1.047 -> 0.840;
#     4.107 -> 2.735 ms
# One tile width everywhere, 128 / 512 / 1024: the Mistral layer 6.84 / 5.54 /
# 6.20 ms; 256 on the diagonal and 512 below it 5.41 (kept); 512 and 1024
# 5.62.  What did NOT
# help: splitting a tile's rows, emitting the next tile's Q K^T before this
# tile's softmax (the scheduler already orders them), 1-D m / l (their
# layout changes cost the forward 1.2 ms a call at 512-wide tiles).
import os as _os

def _block_from_env(var: str, default: int) -> int:
    """A bad override must never make the ops package unimportable
    (this runs at import time, and an elastic restart inherits the same
    env — raising here would crash-loop every worker): any malformed or
    out-of-range value warns and falls back to the measured default."""
    raw = _os.getenv(var)
    if raw is None or not raw.strip():
        return default
    import warnings

    try:
        val = int(raw)
    except ValueError:
        warnings.warn(
            f"{var}={raw!r} is not an integer; using default {default}"
        )
        return default
    if val <= 0 or val % 128 != 0 or val > 4096:
        warnings.warn(
            f"{var}={val} ignored: flash blocks must be positive "
            "multiples of 128 (TPU lane width) and <= 4096 (16MB "
            f"scoped-VMEM bound); using default {default}"
        )
        return default
    return val


DEFAULT_BLOCK_Q = _block_from_env("DLROVER_FLASH_BLOCK_Q", 1024)
DEFAULT_BLOCK_K = _block_from_env("DLROVER_FLASH_BLOCK_K", 1024)
_NEG_INF = -1e30
_LANES = 128
# Compute tiles inside the DMA block (see the module docstring): the
# diagonal is followed in steps of _SUB_TILE, and a block with no mask is
# walked in tiles twice as wide.
_SUB_TILE = 256

_NT = (((1,), (1,)), ((), ()))  # A @ B^T
_NN = (((1,), (0,)), ((), ()))  # A @ B


def _sub_tile(block: int) -> int:
    """The compute tile along an axis whose DMA block is ``block`` wide."""
    return _SUB_TILE if block % _SUB_TILE == 0 else block


def _wide_tile(block: int) -> int:
    """The tile of a walk that follows no diagonal."""
    tile = _sub_tile(block)
    return 2 * tile if block % (2 * tile) == 0 else tile


def _trimmed(block_q: int, block_k: int, causal: bool, seq_offset: int) -> bool:
    """Whether the only blocks the diagonal crosses are those with
    ``q0 == k0``: then a crossed block's live tiles are known when the
    kernel is traced, and it computes those alone.  Otherwise a crossed
    block is masked whole."""
    return causal and block_q == block_k and seq_offset % block_q == 0


def _static_tiles(d: int, block_q: int, block_k: int, walk: str,
                  window: int):
    """The live compute tiles of a block whose first query stands ``d``
    positions after its first key, under a window: ``[(rows, cols,
    masked)]`` as ``_for_live_tiles`` hands them to a kernel.  Along the
    ``walk`` axis one tile at a time; along the other, neighbouring tiles
    of one class are run as one."""
    width, other = (block_k, block_q) if walk == "k" else (block_q, block_k)
    tw, to = _sub_tile(width), _sub_tile(other)
    out = []
    for c in range(0, width, tw):
        runs = []  # [r0, r1, masked] along the other axis
        for r in range(0, other, to):
            # i - j over the tile: queries r.., keys c.. (walk "k") or the
            # other way round
            q, k, nq, nk = (r, c, to, tw) if walk == "k" else (c, r, tw, to)
            lo, hi = d + q - (k + nk - 1), d + q + nq - 1 - k
            if hi < 0 or lo >= window:
                continue
            masked = lo < 0 or hi >= window
            if runs and runs[-1][1] == r and runs[-1][2] == masked:
                runs[-1][1] = r + to
            else:
                runs.append([r, r + to, masked])
        out += [((r0, r1), (c, c + tw), masked) for r0, r1, masked in runs]
    return out


def _crossed_offsets(block: int, window: int):
    """The offsets ``q0 - k0`` (multiples of the block) at which a block
    is crossed by the diagonal or by the window's far edge."""
    return [d for d in range(0, window + block - 1, block)
            if not (d >= block - 1 and d + block - 1 < window)]


def score_tiles(
    sq: int, skv: int, block_q: int, block_k: int, causal: bool,
    seq_offset: int, window: Optional[int] = None,
) -> Tuple[float, float, float]:
    """``(computed, unmasked, needed)`` of one head's score matrix, in
    units of one compute tile (``_sub_tile(block_k)`` squared): what the
    kernels run through the MXU, how much of that pays for no mask, and
    what the attention needs.  The kernels walk a block exactly as this
    counts it (calls without segment ids; with them every tile of a
    block that runs is masked)."""
    block_q, block_k = min(block_q, sq), min(block_k, skv)
    t = _sub_tile(block_k)
    unit = float(t * t)
    if not causal:
        whole = sq * skv / unit
        return whole, whole, whole
    if window is not None and window < skv:
        return _window_score_tiles(sq, skv, block_q, block_k, seq_offset,
                                   window, unit)
    # query row i sees the keys up to its own position, i + seq_offset
    needed = sum(min(max(i + seq_offset + 1, 0), skv) for i in range(sq)) / unit
    trimmed = _trimmed(block_q, block_k, causal, seq_offset)
    n = block_k // t
    computed = unmasked = 0.0
    for iq in range(sq // block_q):
        q0 = iq * block_q + seq_offset
        for ik in range(skv // block_k):
            k0 = ik * block_k
            block = block_q * block_k / unit
            if q0 + block_q - 1 < k0:
                continue  # above the diagonal
            if q0 >= k0 + block_k - 1:
                computed, unmasked = computed + block, unmasked + block
            elif trimmed:  # n tiles on the diagonal, n (n - 1) / 2 below
                computed += n * (n + 1) / 2
                unmasked += n * (n - 1) / 2
            else:
                computed += block
    return computed, unmasked, needed


def _window_score_tiles(sq, skv, block_q, block_k, seq_offset, window, unit):
    """``score_tiles`` under a window, block by block as the kernels class
    them."""
    needed = sum(min(max(i + seq_offset + 1, 0), skv, window)
                 for i in range(sq)) / unit
    trimmed = _trimmed(block_q, block_k, True, seq_offset)
    computed = unmasked = 0.0
    for iq in range(sq // block_q):
        for ik in range(skv // block_k):
            d = iq * block_q + seq_offset - ik * block_k
            if d + block_q - 1 < 0 or d - (block_k - 1) >= window:
                continue  # above the diagonal, or wholly below the window
            block = block_q * block_k / unit
            if d >= block_k - 1 and d + block_q - 1 < window:
                computed, unmasked = computed + block, unmasked + block
            elif trimmed:
                for (r0, r1), (c0, c1), masked in _static_tiles(
                        d, block_q, block_k, "k", window):
                    area = (r1 - r0) * (c1 - c0) / unit
                    computed += area
                    unmasked += 0.0 if masked else area
            else:
                computed += block
    return computed, unmasked, needed


def _lanes(x: jax.Array, n: int) -> jax.Array:
    """A per-row statistic kept on all 128 lanes, widened to ``n`` columns
    (no lane broadcast: the same vregs again)."""
    reps, rem = divmod(n, _LANES)
    if rem:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return x if reps == 1 else jnp.tile(x, (1, reps))


def _fold(p: jax.Array) -> jax.Array:
    """The row sums of ``p`` left in 128 per-lane parts (a row's parts add
    up to its sum): whole-vreg adds, no reduction across lanes."""
    rows, n = p.shape
    if n % _LANES:
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
        return jnp.where(lane == 0, jnp.sum(p, axis=1, keepdims=True), 0.0)
    return functools.reduce(
        jnp.add, [p[:, i:i + _LANES] for i in range(0, n, _LANES)]
    )


def _tile_mask(
    shape, q_start, k_start, causal: bool,
    q_seg: Optional[jax.Array], k_seg: Optional[jax.Array], q_axis: int,
    window: Optional[int] = None,
) -> jax.Array:
    """Boolean mask (True = attend) of a score tile whose axis ``q_axis``
    runs over queries from ``q_start`` and the other over keys from
    ``k_start``."""
    mask = None
    if causal:
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
        mask = q_pos >= k_pos
        if window is not None:
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
    if q_seg is not None:
        if q_axis == 0:
            seg = q_seg[:, None] == k_seg[None, :]
        else:
            seg = k_seg[:, None] == q_seg[None, :]
        mask = seg if mask is None else jnp.logical_and(mask, seg)
    return mask


def _live_k_block(iq, ik, block_q, block_k, causal, seq_offset):
    """The k block the step (iq, ik) asks for: its own, or above the
    diagonal, where nothing runs, the row's last live one again, so that
    the pipeline fetches nothing for a step that computes nothing."""
    if not causal:
        return ik
    return jnp.minimum(ik, (iq * block_q + seq_offset + block_q - 1) // block_k)


def _window_steps(block_walker: int, block_walked: int, n: int,
                  seq_offset: int, window: int) -> int:
    """Grid steps along the walked axis under a window: the blocks of
    ``block_walked`` that the window of one ``block_walker`` block can
    reach, of the ``n`` there are."""
    if block_walker == block_walked and seq_offset % block_walker == 0:
        return min(n, (window + block_walker - 2) // block_walker + 1)
    return min(n, (block_walker + window - 2) // block_walked + 2)


def _walk_k_block(iq, step, *, block_q, block_k, nk, steps, seq_offset,
                  window):
    """Under a window, the k block of grid step ``step`` of q block
    ``iq`` (below zero where the row has fewer live blocks than steps:
    nothing runs there) and the block to fetch for it (the nearest live
    one, so that a dead step fetches nothing new).  The steps END at the
    row's last live block."""
    q0 = iq * block_q + seq_offset
    last = jnp.minimum((q0 + block_q - 1) // block_k, nk - 1)
    first = jnp.maximum((q0 - window + 1) // block_k, 0)
    true = last - (steps - 1) + step
    return true, jnp.clip(true, first, last)


def _walk_q_block(ik, step, *, block_q, block_k, nq, seq_offset, window):
    """Under a window, the q block of step ``step`` of k block ``ik``
    in the dkv kernel's walk (past the last where fewer are live) and the
    block to fetch.  The steps START at the first q block that sees the
    k block."""
    k0 = ik * block_k
    first = jnp.maximum((k0 - seq_offset) // block_q, 0)
    last = jnp.clip((k0 + block_k - 1 + window - 1 - seq_offset) // block_q,
                    0, nq - 1)
    true = first + step
    return true, jnp.clip(true, first, last)


def _for_live_tiles(
    tile_fn, q0, k0, *, block_q: int, block_k: int, walk: str,
    causal: bool, seq_offset: int, have_segs: bool,
    window: Optional[int] = None, in_range=True,
) -> None:
    """Run ``tile_fn((r0, r1), (c0, c1), masked)`` for every compute tile
    of the block at (``q0``, ``k0``) that holds a live score.  ``c0:c1``
    is the tile's extent along the ``walk`` axis ("k" or "q") and
    ``r0:r1`` along the other.  Three classes of block: above the
    diagonal, nothing runs; wholly below it (or not causal), every tile
    runs unmasked; crossed by it, the tiles on the diagonal run masked,
    those below it unmasked and those above it not at all (or, when the
    crossing is not known at trace time, every tile masked).  With
    segment ids every tile of a block that runs is masked."""
    width, other = (block_k, block_q) if walk == "k" else (block_q, block_k)
    tile, wide = _sub_tile(width), _wide_tile(width)

    def whole(masked):
        for c in range(0, width, wide):
            tile_fn((0, other), (c, c + wide), masked)

    if not causal:
        whole(have_segs)
        return
    if window is not None:
        d = q0 - k0
        run = jnp.logical_and(
            jnp.logical_and(d + block_q - 1 >= 0, d - (block_k - 1) < window),
            in_range)
        if have_segs:
            pl.when(run)(lambda: whole(True))
            return
        inside = jnp.logical_and(d >= block_k - 1, d + block_q - 1 < window)
        pl.when(jnp.logical_and(run, inside))(lambda: whole(False))
        crossed = jnp.logical_and(run, jnp.logical_not(inside))
        if not _trimmed(block_q, block_k, causal, seq_offset):
            pl.when(crossed)(lambda: whole(True))
            return
        for dd in _crossed_offsets(block_q, window):
            def _at(dd=dd):
                for rows, cols, masked in _static_tiles(
                        dd, block_q, block_k, walk, window):
                    tile_fn(rows, cols, masked)

            pl.when(jnp.logical_and(crossed, d == dd))(_at)
        return
    run = q0 + block_q - 1 >= k0
    if have_segs:
        pl.when(run)(lambda: whole(True))
        return
    below = q0 >= k0 + block_k - 1
    pl.when(below)(lambda: whole(False))

    @pl.when(jnp.logical_and(run, jnp.logical_not(below)))
    def _crossed():
        if not _trimmed(block_q, block_k, causal, seq_offset):
            whole(True)
            return
        for c in range(0, width, tile):  # here q0 == k0
            tile_fn((c, c + tile), (c, c + tile), True)
            if walk == "k" and c + tile < other:  # the query rows after it
                tile_fn((c + tile, other), (c, c + tile), False)
            if walk == "q" and c > 0:  # the key rows before it
                tile_fn((0, c), (c, c + tile), False)


def _named(window, name):
    """``pallas_call``'s name: only a call with a window has one, the
    others keep the instruction names the benchmark's readers know
    (``attn.<n>``, ``shard_map.<n>``)."""
    return None if window is None else name


def _k_walk(nk, block_q, block_k, causal, seq_offset, window):
    """``(grid steps along k, index map (iq, step) -> k block to fetch)``
    of the forward and dq kernels."""
    if window is None:
        return nk, lambda iq, ik: _live_k_block(
            iq, ik, block_q, block_k, causal, seq_offset)
    steps = _window_steps(block_q, block_k, nk, seq_offset, window)
    return steps, lambda iq, ik: _walk_k_block(
        iq, ik, block_q=block_q, block_k=block_k, nk=nk, steps=steps,
        seq_offset=seq_offset, window=window)[1]


def _k_start(iq, ik, block_q, block_k, nk_all, steps, seq_offset, window):
    """First key position of grid step (iq, ik) in the forward and dq
    kernels, and whether that step has a block at all."""
    if window is None:
        return ik * block_k, True
    true, _ = _walk_k_block(
        iq, ik, block_q=block_q, block_k=block_k, nk=nk_all, steps=steps,
        seq_offset=seq_offset, window=window)
    return true * block_k, true >= 0


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, qseg_ref, kseg_ref,
    o_ref, lse_ref,
    q_scr, m_scr, l_scr, acc_scr,
    *, causal: bool, scale: float, block_q: int, block_k: int,
    seq_offset: int, have_segs: bool, window: Optional[int] = None,
    nk_all: int = 0,
):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    d = acc_scr.shape[-1]

    @pl.when(ik == 0)
    def _init():
        # q * scale is rounded for the MXU once a q block
        q_scr[:] = (q_ref[0, 0].astype(jnp.float32) * scale).astype(q_scr.dtype)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # First global position of this block's rows/cols.  seq_offset shifts
    # query positions (queries are the tail of the kv sequence when sq < skv).
    q0 = iq * block_q + seq_offset
    k0, in_range = _k_start(iq, ik, block_q, block_k, nk_all, nk,
                            seq_offset, window)

    def _tile(rows, cols, masked):
        (r0, r1), (c0, c1) = rows, cols
        s = jax.lax.dot_general(
            q_scr[r0:r1], k_ref[0, 0, c0:c1], _NT,
            preferred_element_type=jnp.float32,
        )
        if masked:
            mask = _tile_mask(
                s.shape, q0 + r0, k0 + c0, causal,
                qseg_ref[0, 0, r0:r1] if have_segs else None,
                kseg_ref[0, 0, c0:c1] if have_segs else None, 0, window,
            )
            s = jnp.where(mask, s, _NEG_INF)
        # m and l live on all 128 lanes of their rows ([rows, 128]): a
        # 1-D statistic would change layout at every use
        m_prev = m_scr[r0:r1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, c1 - c0))
        if masked and have_segs:
            # Only segment ids can mask a row fully: m_new then stays at
            # -inf and exp(s - m_new) would be 1 at masked entries.
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[r0:r1] = l_scr[r0:r1] * corr + _fold(p)
        m_scr[r0:r1] = m_new
        v = v_ref[0, 0, c0:c1]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32
        )
        acc_scr[r0:r1] = acc_scr[r0:r1] * _lanes(corr, d) + pv

    _for_live_tiles(
        _tile, q0, k0, block_q=block_q, block_k=block_k, walk="k",
        causal=causal, seq_offset=seq_offset, have_segs=have_segs,
        window=window, in_range=in_range,
    )

    @pl.when(ik == nk - 1)
    def _final():
        l = jnp.sum(l_scr[:], axis=1, keepdims=True)  # the lanes' parts
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # every lane of m holds the row's value: the max lays it out 1-D
        lse_ref[0, 0, 0] = jnp.max(m_scr[:], axis=1) + jnp.log(l_safe[:, 0])


def _fwd(
    q, k, v, q_seg, k_seg, *, causal, scale, block_q, block_k, interpret,
    window=None,
) -> Tuple[jax.Array, jax.Array]:
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    reps = h // hkv  # GQA: kv heads are shared by `reps` query heads
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    nq, nk = sq // block_q, skv // block_k
    have_segs = q_seg is not None
    if not have_segs:
        # placeholder inputs keep one kernel signature
        q_seg = jnp.zeros((b, 1, sq), jnp.int32)
        k_seg = jnp.zeros((b, 1, skv), jnp.int32)
    seq_offset = skv - sq

    kernel = functools.partial(
        _fwd_kernel,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        seq_offset=seq_offset, have_segs=have_segs, window=window, nk_all=nk,
    )
    steps, kb = _k_walk(nk, block_q, block_k, causal, seq_offset, window)
    grid = (b, h, nq, steps)
    out_shape = [
        jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32),
    ]

    o, lse = pl.pallas_call(
        kernel,
        name=_named(window, "flash_window_fwd"),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda ib, ih, iq, ik: (ib, ih // reps, kb(iq, ik), 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda ib, ih, iq, ik: (ib, ih // reps, kb(iq, ik), 0),
            ),
            pl.BlockSpec((1, 1, block_q), lambda ib, ih, iq, ik: (ib, 0, iq)),
            pl.BlockSpec((1, 1, block_k), lambda ib, ih, iq, ik: (ib, 0, kb(iq, ik))),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, 1, block_q), lambda ib, ih, iq, ik: (ib, ih, 0, iq)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), q.dtype),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        out_shape=out_shape,
        interpret=interpret,
    )(q, k, v, q_seg, k_seg)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(
    q_ref, k_ref, v_ref, qseg_ref, kseg_ref, do_ref, lse_ref, delta_ref,
    dq_ref,
    q_scr, dq_scr,
    *, causal, scale, block_q, block_k, seq_offset, have_segs,
    window=None, nk_all=0,
):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        # q * scale is rounded for the MXU once a q block
        q_scr[:] = (q_ref[0, 0].astype(jnp.float32) * scale).astype(q_scr.dtype)
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q0 = iq * block_q + seq_offset
    k0, in_range = _k_start(iq, ik, block_q, block_k, nk_all, nk,
                            seq_offset, window)

    def _tile(rows, cols, masked):
        (r0, r1), (c0, c1) = rows, cols
        k = k_ref[0, 0, c0:c1]
        s = jax.lax.dot_general(
            q_scr[r0:r1], k, _NT, preferred_element_type=jnp.float32
        )
        if masked:
            mask = _tile_mask(
                s.shape, q0 + r0, k0 + c0, causal,
                qseg_ref[0, 0, r0:r1] if have_segs else None,
                kseg_ref[0, 0, c0:c1] if have_segs else None, 0, window,
            )
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0, 0, r0:r1][:, None])
        if masked and have_segs:
            p = jnp.where(mask, p, 0.0)  # fully-masked rows have lse=-inf
        dp = jax.lax.dot_general(
            do_ref[0, 0, r0:r1], v_ref[0, 0, c0:c1], _NT,
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, 0, 0, r0:r1][:, None])
        dq_scr[r0:r1] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32
        )

    _for_live_tiles(
        _tile, q0, k0, block_q=block_q, block_k=block_k, walk="k",
        causal=causal, seq_offset=seq_offset, have_segs=have_segs,
        window=window, in_range=in_range,
    )

    @pl.when(ik == nk - 1)
    def _final():
        dq_ref[0, 0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, qseg_ref, kseg_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, causal, scale, block_q, block_k, seq_offset, have_segs, reps,
    window=None, nq_all=0,
):
    # Grid is (batch, kv_head, kv_block, q_block * reps): the innermost dim
    # folds the q-blocks of every query head sharing this kv head, so dk/dv
    # accumulate in scratch across the whole GQA group (no HBM revisits).
    # The scores are computed TRANSPOSED (K Q^T: keys down the rows, queries
    # across the lanes), so that p^T and ds^T feed the MXU as they come and
    # lse / delta are rows, as they are stored.
    ik, j = pl.program_id(2), pl.program_id(3)
    nj = pl.num_programs(3)
    iq, in_range = j // reps, True
    if window is not None:  # the walk starts at the first q block that sees
        iq, _ = _walk_q_block(
            ik, iq, block_q=block_q, block_k=block_k, nq=nq_all,
            seq_offset=seq_offset, window=window)
        in_range = iq <= nq_all - 1

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q0 = iq * block_q + seq_offset
    k0 = ik * block_k

    def _tile(rows, cols, masked):
        (r0, r1), (c0, c1) = rows, cols  # keys, queries
        q = q_ref[0, 0, c0:c1]
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
        do = do_ref[0, 0, c0:c1]
        st = jax.lax.dot_general(
            k_ref[0, 0, r0:r1], q, _NT, preferred_element_type=jnp.float32
        )
        if masked:
            mask = _tile_mask(
                st.shape, q0 + c0, k0 + r0, causal,
                qseg_ref[0, 0, c0:c1] if have_segs else None,
                kseg_ref[0, 0, r0:r1] if have_segs else None, 1, window,
            )
            st = jnp.where(mask, st, _NEG_INF)
        pt = jnp.exp(st - lse_ref[0, 0, :, c0:c1])
        if masked and have_segs:
            pt = jnp.where(mask, pt, 0.0)  # fully-masked rows have lse=-inf
        dv_scr[r0:r1] += jax.lax.dot_general(
            pt.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32
        )
        dpt = jax.lax.dot_general(
            v_ref[0, 0, r0:r1], do, _NT, preferred_element_type=jnp.float32
        )
        dst = pt * (dpt - delta_ref[0, 0, :, c0:c1])
        # dk += ds^T @ q  (q already carries `scale`)
        dk_scr[r0:r1] += jax.lax.dot_general(
            dst.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32
        )

    _for_live_tiles(
        _tile, q0, k0, block_q=block_q, block_k=block_k, walk="q",
        causal=causal, seq_offset=seq_offset, have_segs=have_segs,
        window=window, in_range=in_range,
    )

    @pl.when(j == nj - 1)
    def _final():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(
    res, g, *, causal, scale, block_q, block_k, interpret, window=None
):
    q, k, v, q_seg, k_seg, o, lse = res
    do = g
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    reps = h // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    nq, nk = sq // block_q, skv // block_k
    have_segs = q_seg is not None
    if not have_segs:
        q_seg = jnp.zeros((b, 1, sq), jnp.int32)
        k_seg = jnp.zeros((b, 1, skv), jnp.int32)
    seq_offset = skv - sq

    # [b, h, 1, sq] — the singleton axis keeps Mosaic block tiling legal.
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )[:, :, None, :]

    common = dict(
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        seq_offset=seq_offset, have_segs=have_segs,
    )
    qkv_spec = lambda blk, which: pl.BlockSpec(  # noqa: E731
        (1, 1, blk, d),
        (lambda ib, ih, i, j: (ib, ih, i, 0)) if which == "outer"
        else (lambda ib, ih, i, j: (ib, ih, j, 0)),
    )

    steps, kb = _k_walk(nk, block_q, block_k, causal, seq_offset, window)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common, window=window, nk_all=nk),
        name=_named(window, "flash_window_dq"),
        grid=(b, h, nq, steps),
        in_specs=[
            qkv_spec(block_q, "outer"),       # q
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda ib, ih, i, j: (ib, ih // reps, kb(i, j), 0),
            ),                                 # k
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda ib, ih, i, j: (ib, ih // reps, kb(i, j), 0),
            ),                                 # v
            pl.BlockSpec((1, 1, block_q), lambda ib, ih, i, j: (ib, 0, i)),
            pl.BlockSpec((1, 1, block_k), lambda ib, ih, i, j: (ib, 0, kb(i, j))),
            qkv_spec(block_q, "outer"),       # do
            pl.BlockSpec((1, 1, 1, block_q), lambda ib, ih, i, j: (ib, ih, 0, i)),
            pl.BlockSpec((1, 1, 1, block_q), lambda ib, ih, i, j: (ib, ih, 0, i)),
        ],
        out_specs=qkv_spec(block_q, "outer"),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), q.dtype),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v, q_seg, k_seg, do, lse, delta)

    # No such clamp on the dkv grid: there the dead steps come FIRST in a
    # k block's walk, and asking them for the first live q block measured
    # no gain (PR 29: 1.650 ms a call with and without).
    if window is None:
        q_steps = nq

        def qb(i, j):
            return j // reps
    else:
        # under a window the walk over q blocks is as long as a window
        # reaches, from the first q block that sees the k block
        q_steps = _window_steps(block_k, block_q, nq, seq_offset, window)

        def qb(i, j):
            return _walk_q_block(
                i, j // reps, block_q=block_q, block_k=block_k, nq=nq,
                seq_offset=seq_offset, window=window)[1]

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common, reps=reps, window=window,
                          nq_all=nq),
        name=_named(window, "flash_window_dkv"),
        grid=(b, hkv, nk, q_steps * reps),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d),
                lambda ib, ih, i, j: (ib, ih * reps + j % reps, qb(i, j), 0),
            ),                                 # q
            qkv_spec(block_k, "outer"),       # k
            qkv_spec(block_k, "outer"),       # v
            pl.BlockSpec((1, 1, block_q), lambda ib, ih, i, j: (ib, 0, qb(i, j))),
            pl.BlockSpec((1, 1, block_k), lambda ib, ih, i, j: (ib, 0, i)),
            pl.BlockSpec(
                (1, 1, block_q, d),
                lambda ib, ih, i, j: (ib, ih * reps + j % reps, qb(i, j), 0),
            ),                                 # do
            pl.BlockSpec(
                (1, 1, 1, block_q),
                lambda ib, ih, i, j: (ib, ih * reps + j % reps, 0, qb(i, j)),
            ),
            pl.BlockSpec(
                (1, 1, 1, block_q),
                lambda ib, ih, i, j: (ib, ih * reps + j % reps, 0, qb(i, j)),
            ),
        ],
        out_specs=[
            qkv_spec(block_k, "outer"),
            qkv_spec(block_k, "outer"),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
    )(q, k, v, q_seg, k_seg, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10)
)
def _flash_bhsd(q, k, v, q_seg, k_seg, causal, scale, block_q, block_k,
                interpret, window=None):
    o, _ = _fwd(
        q, k, v, q_seg, k_seg,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret, window=window,
    )
    return o


def _flash_fwd_rule(q, k, v, q_seg, k_seg, causal, scale, block_q, block_k,
                    interpret, window):
    o, lse = _fwd(
        q, k, v, q_seg, k_seg,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret, window=window,
    )
    return o, (q, k, v, q_seg, k_seg, o, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, interpret, window, res, g):
    dq, dk, dv = _bwd(
        res, g, causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret, window=window,
    )
    return dq, dk, dv, None, None


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Flash attention on [batch, seq, heads, dim] inputs (GQA allowed).

    ``window=w`` (causal only): query ``i`` sees keys ``i - w < j <= i``;
    ``w`` at or over the key length is the call without a window.

    Raises ValueError for shapes the kernels cannot tile; the caller
    (ops.attention.dot_product_attention) has no fallback once it has
    dispatched here, so keep inputs block-aligned (seq divisible by 128).
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    if scale is None:
        scale = d ** -0.5
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    if window is not None:
        if not causal or window < 1:
            raise ValueError(
                f"a window ({window}) needs causal attention and a width of "
                "at least 1")
        window = None if window >= skv else int(window)
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    if sq % bq or skv % bk:
        raise ValueError(
            f"flash_attention needs seq divisible by block: sq={sq} bq={bq} "
            f"skv={skv} bk={bk}"
        )
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    q_seg = k_seg = None
    if segment_ids is not None:
        segs = segment_ids.astype(jnp.int32)
        k_seg = segs[:, None, :]
        q_seg = (segs if segs.shape[1] == sq else segs[:, -sq:])[:, None, :]
    out = _flash_bhsd(
        qt, kt, vt, q_seg, k_seg, causal, float(scale), bq, bk, interpret,
        window,
    )
    return out.transpose(0, 2, 1, 3)
