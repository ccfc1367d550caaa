"""Absorbed latent attention of ONE query a slot over the slot's live pages
of a paged latent pool (TPU Pallas): the decode step of every
latent-attention model, one that attends to its whole context and, under
a mask of the chosen rows, one with a learned selection of keys.

A latent-attention model (``serving/latent.py``) caches one row
``[c_kv | k_r]`` a token for all heads.  At decode a slot's absorbed query
``[H, W]`` scores every live row, softmax, and the attended latent is
``p x rows[:, :C]``: a row is read ONCE for all H heads, 2 x H x (W + C)
FLOPs for its W values (sarvam-105b: 64 x 2 x (640 + 512) for 1 280
bytes = 115 FLOPs a byte against this chip's 240), so the kernel is bound
by the bytes of the live rows.  Gathering them into a dense
``[slots, table rows, W]`` array first would write and read every row the
TABLE could hold: at 32 slots and tables of 33 k rows 1.35 GB a layer and
forward.  A learned selection (GLM-5: 2 048 rows of 16-33 k) does not
change that: gathering the CHOSEN rows by position runs at a tenth of
the stream's pace on this chip and needs the positions, a sort, first
(until PR 44: 14.6 of a decode forward's 26 ms), so a selection arrives
here as a mask and every live row is streamed under it.

The build is ``paged_index.py``'s and ``mla_prefill.py``'s: the grid is
``(B,)``, a program streams ITS slot's live pages in groups of ``pages``
by double-buffered manual DMA through the scalar-prefetched block table,
the next group's pages in flight under this group's two matmuls, and the
group loop ends at the slot's length: a slot of length 0 reads nothing
and gives zeros, a slot reads ``ceil(length / rows) x rows`` latent rows
and not its table's width, and pages two slots share (a cached document)
are read through each slot's own table.  Scores ``[H, rows]`` are born,
masked behind the length (and by the slot's row of ``bias``, where there
is one: a group nobody chose leaves the running softmax as it was),
exponentiated and consumed in VMEM; running max
and sum in float32 on all 128 lanes of a row (``flash_attention._lanes``
/ ``_fold``), ``p`` rounded to the query's dtype once before ``p x
rows[:, :C]``, float32 accumulator, ``acc / max(l, 1e-30)`` out.

Layout (serving/paged.py, serving/latent.py):
  qq       [B, H, W]      absorbed queries (zeros behind C + R)
  pool     [NB, bs, W]    latent rows, paged
  table    [B, MB] int32  block lists (0 = the trash block)
  lengths  [B] int32      keys a slot sees (0: nothing wanted)
  bias     [B, n] f32     optional: 0 where the slot attends row s, -inf
                          elsewhere (``n`` up to the table's rows in
                          whole groups; a row behind ``n`` is nobody's).
                          None: every row behind the length, and the
                          call has no such operand at all
Returns [B, H, C] float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.pallas.flash_attention import _NN, _NT, _fold, _lanes
from dlrover_tpu.ops.pallas.paged_attention import (
    _page_groups,
    streamed_rows as _streamed_rows,
)

#: pages a compute group holds: 8 x 128 rows = a [64, 1024] score tile and
#: 1.3 MB of rows a buffer
PAGES_PER_BLOCK = 8
_NEG_INF = -jnp.inf


def _decode_kernel(
    table_ref, lengths_ref,            # scalar-prefetched (SMEM)
    q_ref, pool_hbm, *rest,            # [bias_ref,] o_ref, the scratch
    pages: int, block_size: int, num_groups: int, c: int, scale: float,
    masked: bool,
):
    bias_ref = rest[0] if masked else None
    o_ref, kbuf, sem, m_scr, l_scr, acc_scr = rest[masked:]
    b = pl.program_id(0)
    rows = pages * block_size
    length = lengths_ref[b]

    def _copies(g, slot):
        return [pltpu.make_async_copy(
            pool_hbm.at[table_ref[b, g * pages + j]], kbuf.at[slot, j],
            sem.at[slot, j]) for j in range(pages)]

    n_live = jnp.minimum(pl.cdiv(length, rows), num_groups)
    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(n_live > 0)               # a DMA that starts is waited
    def _():
        for cp in _copies(0, 0):
            cp.start()

    q = q_ref[0]                       # [H, W]

    def body(g, _):
        slot = jax.lax.rem(g, 2)

        @pl.when(g + 1 < n_live)
        def _():                       # the next group's pages first
            for cp in _copies(g + 1, 1 - slot):
                cp.start()

        for cp in _copies(g, slot):
            cp.wait()
        keys = kbuf[slot].reshape(rows, kbuf.shape[-1]).astype(q.dtype)
        s = jax.lax.dot_general(
            q, keys, _NT,
            preferred_element_type=jnp.float32) * scale      # [H, rows]
        if masked:                     # the slot's row, over its heads
            s = s + bias_ref[0, :, pl.ds(pl.multiple_of(g * rows, rows),
                                         rows)]
        key_pos = g * rows + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # the last live group is read whole: its rows behind the length
        # hold whatever the pool held, and count for nothing
        s = jnp.where(key_pos < length, s, _NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        alpha = jnp.exp(jnp.where(m_prev == _NEG_INF, safe, m_prev) - safe)
        p = jnp.exp(s - _lanes(safe, rows))
        l_scr[...] = alpha * l_scr[...] + _fold(p)
        m_scr[...] = m_new
        pv = jax.lax.dot_general(
            p.astype(q.dtype), keys[:, :c], _NN,
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * _lanes(alpha, c) + pv
        return 0

    jax.lax.fori_loop(0, n_live, body, 0)
    l = jnp.sum(l_scr[...], axis=1, keepdims=True)     # the lanes' parts
    o_ref[0] = acc_scr[...] / jnp.maximum(l, 1e-30)


#: latent rows the kernel copies for slots of given lengths: whole groups
#: up to each length, none for length 0, never more than the table
#: (``paged_attention.streamed_rows``' host arithmetic at this kernel's
#: group size, for a caller that books what it streams)
streamed_rows = functools.partial(_streamed_rows,
                                  pages_per_block=PAGES_PER_BLOCK)


def _whole_groups(table: jax.Array, pages_per_block: int):
    """``(table padded with the trash block to whole groups, pages a
    group, groups)``."""
    p_n, num_groups = _page_groups(table.shape[1], pages_per_block)
    pad = num_groups * p_n - table.shape[1]
    if pad:                           # zeros: the trash block, masked
        table = jnp.concatenate(
            [table, jnp.zeros((table.shape[0], pad), table.dtype)], axis=1)
    return table, p_n, num_groups


def _whole_bias(bias: jax.Array, width: int) -> jax.Array:
    """``bias`` [B, n] as float32 [B, width]: the rows behind ``n`` (the
    padding of the table to whole groups) are nobody's."""
    n = bias.shape[1]
    assert n <= width, (bias.shape, width)
    return jnp.pad(bias.astype(jnp.float32), ((0, 0), (0, width - n)),
                   constant_values=_NEG_INF)


@functools.partial(
    jax.jit, static_argnames=("c", "scale", "pages_per_block", "interpret",
                              "name"))
def mla_decode_attention(
    qq: jax.Array,       # [B, H, W]
    pool: jax.Array,     # [NB, bs, W]
    table: jax.Array,    # [B, MB] int32
    lengths: jax.Array,  # [B] int32
    bias: Optional[jax.Array] = None,  # [B, n] f32
    *,
    c: int,
    scale: float,
    pages_per_block: int = PAGES_PER_BLOCK,
    interpret: bool = False,
    name: str = "mla_decode_attn",
) -> jax.Array:
    b, heads, w = qq.shape
    bs = pool.shape[1]
    assert pool.shape[2] == w, (qq.shape, pool.shape)
    table, p_n, num_groups = _whole_groups(table, pages_per_block)
    width = num_groups * p_n * bs
    # a slot's whole row of the bias rides the pipeline beside its query
    # (33 k rows: 133 KB a slot beside ~30 MB of latent rows)
    masks = [] if bias is None else [_whole_bias(bias, width)[:, None]]

    def per_slot(bi, table_ref, lengths_ref):
        return (bi, 0, 0)

    return pl.pallas_call(
        functools.partial(_decode_kernel, pages=p_n, block_size=bs,
                          num_groups=num_groups, c=c, scale=scale,
                          masked=bool(masks)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, heads, w), per_slot),
                      pl.BlockSpec(memory_space=pl.ANY)]
            + [pl.BlockSpec((1, 1, width), per_slot)] * len(masks),
            out_specs=pl.BlockSpec((1, heads, c), per_slot),
            scratch_shapes=[pltpu.VMEM((2, p_n, bs, w), pool.dtype),
                            pltpu.SemaphoreType.DMA((2, p_n)),
                            pltpu.VMEM((heads, 128), jnp.float32),
                            pltpu.VMEM((heads, 128), jnp.float32),
                            pltpu.VMEM((heads, c), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, heads, c), jnp.float32),
        interpret=interpret,
        # the kernel's instruction in a device trace: ``mla_decode_attn.<n>``
        # (a window layer's call has a name of its own, so that a reader
        # tells the two apart: ``mla_window_decode_attn``)
        name=name,
    )(table.astype(jnp.int32), lengths.astype(jnp.int32), qq, pool, *masks)


def gather_latent_decode(qq, pool, table, lengths, bias=None, *, c: int,
                         scale: float,
                         pages_per_block: int = PAGES_PER_BLOCK):
    """:func:`mla_decode_attention` in plain ``jnp``: the off-chip path and
    the parity oracle.  Group by group of the same pages, every slot's at
    once, as far as the LONGEST slot's length (the trip count is read from
    ``lengths``, not from the table's width), under the same ``bias``,
    a running softmax in the kernel's arithmetic; each group's ``[B, H,
    rows]`` scores pass through memory, which the kernel exists to
    avoid."""
    b, heads, w = qq.shape
    bs = pool.shape[1]
    table, p_n, num_groups = _whole_groups(table, pages_per_block)
    rows = p_n * bs
    lengths = lengths.astype(jnp.int32)
    if bias is not None:
        bias = _whole_bias(bias, num_groups * rows)
    n_live = jnp.minimum((jnp.max(lengths) + rows - 1) // rows, num_groups)

    def group(g, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(table, g * p_n, p_n, axis=1)
        keys = jnp.take(pool, ids, axis=0).reshape(b, rows, w)
        s = jnp.einsum("bhw,bsw->bhs", qq, keys.astype(qq.dtype),
                       preferred_element_type=jnp.float32) * scale
        if bias is not None:
            s = s + jax.lax.dynamic_slice_in_dim(
                bias, g * rows, rows, axis=1)[:, None, :]
        key_pos = g * rows + jnp.arange(rows)
        s = jnp.where((key_pos[None, :] < lengths[:, None])[:, None, :],
                      s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.exp(jnp.where(jnp.isfinite(m), m, safe) - safe)
        p = jnp.exp(s - safe[..., None])
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhs,bsc->bhc", p.astype(qq.dtype),
            keys[..., :c].astype(qq.dtype),
            preferred_element_type=jnp.float32)
        return m_new, alpha * l + p.sum(axis=-1), acc

    m, l, acc = jax.lax.fori_loop(
        0, n_live, group,
        (jnp.full((b, heads), _NEG_INF, jnp.float32),
         jnp.zeros((b, heads), jnp.float32),
         jnp.zeros((b, heads, c), jnp.float32)))
    return acc / jnp.maximum(l, 1e-30)[..., None]
