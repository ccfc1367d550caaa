"""Masked, absorbed latent attention of a run of queries over a paged
latent pool (TPU Pallas): a prefill chunk's score tiles never leave the
chip.

A latent-attention model (``serving/latent.py``) caches ONE row
``[c_kv | k_r]`` a token for all heads, and a run of K queries of one
sequence (a prefill chunk) scores every live row with every head:
``[K x H, W] x [keys, W]^T``, masked by the learned selection (a mask a
QUERY, the same for its H heads), a softmax, then ``p x keys[:, :C]``.
In plain ``jnp`` that is a ``[K, H, 1024]`` float32 tile written to HBM
and read back three times a key block (the loop of
``serving/latent.py _attend_run``, which stays as the off-chip path and
this kernel's oracle).  Here the tile is born, masked, exponentiated and
consumed in VMEM, flash-attention fashion:

- the grid is ``(K / tq,)``: a program holds ``tq`` queries x H heads as
  the ROWS of one 2-D matmul (``tq`` 32 and 64 heads: 2048 rows) and walks
  ITS live key blocks in a loop whose trip count is read from the
  positions (scalar prefetch): ``q_pos[last REAL query of the tile] //
  keys a block + 1``.  A block behind the run's depth, or wholly above
  the tile's queries (causal), costs no DMA and no compute;
- only the run's first ``n_real`` queries are anybody's (a prompt's last
  chunk is padded to the chunk program's shape): a tile whose first
  query stands at or behind ``n_real`` walks NO block, starts no DMA and
  writes zeros, and a tile that straddles it walks as deep as its last
  real query.  The one block that drops is one no real query can see,
  so the real queries' results are the bits they were;
- a key block is ``pages`` pages of the pool, copied by double-buffered
  manual DMA through the scalar-prefetched block table, the next block's
  pages in flight under this block's matmuls (``paged_index.py``'s
  build);
- the selection arrives as 32-bit words, an additive float32 bias
  ``[K, keys]`` (0: attend, -inf: not), and a query's row of it is
  broadcast over its heads' 64 sublanes by the add itself;
- running max / sum in float32 on all 128 lanes of a row
  (``flash_attention._lanes`` / ``_fold``), ``p`` rounded to the queries'
  dtype once before ``p x keys[:, :C]``, float32 accumulator,
  ``acc / max(l, 1e-30)`` out: ``_attend_run``'s arithmetic step for
  step, so a fully masked row gives zeros and no NaN.

Layout:
  qq     [K, H, W]        absorbed queries (zeros behind C + R)
  bias   [K, MB * bs] f32 0 where query k attends key s, -inf elsewhere
                          (a row at or behind ``n_real``: -inf throughout,
                          the caller's to see to, so that a padded query
                          of a straddling tile comes out zero too)
  q_pos  [K] int32        positions, ascending
  n_real int32 scalar     the run's real queries (default: all K)
  pool   [NB, bs, W]      latent rows, paged
  table  [MB] int32       the sequence's pages (whole key blocks; 0 =
                          the trash block)
Returns [K, H, C] float32.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.pallas.flash_attention import _NN, _NT, _fold, _lanes

# Measured on a TPU v5e (PR 37; one call = one layer of a 512-query chunk,
# 64 heads, rows of 640, bf16; wall clock over 10 calls, the bias's
# conversion from the bool selection included; % of the bf16 peak on
# 2 x rows x keys x (576 + 512), the FLOPs the jnp loop's 59 % is counted
# on, at depths of 16.9 k / 25.1 k / 32.8 k):
#   the jnp loop                          15.72 ms at 25.1 k (59.0 %)
#   tq  8, key blocks of 1024             11.20 ms (82.7 %)
#   tq 16 / 32, 1024                      10.94 / 10.80-10.94 (84.7-85.8 %)
#   tq 32, 512 (kept)                     7.19 / 10.40 / 13.46 (87.7-89.1 %)
#   tq 64, 512; tq 32 / 64, 256           within 1.8 % of the kept tile
#   one contiguous gather of the table's pages a call, then one DMA a
#   block, in place of 4-8 page DMAs through the table: the same to 1 %
#   (11.12 against 11.20 at tq 8), so the pages stay where they are
#   the selection as int8 [32, keys] tiles in place of the float32 bias:
#   1-2 % faster (10.19 against 10.40); not kept, a run shorter than 32
#   queries would have no int8 tile
#: queries a program holds: x 64 heads = the rows of its matmuls
QUERIES_PER_TILE = 32
#: pages of the pool a key block holds: 4 x 128 rows
PAGES_PER_BLOCK = 4
_NEG_INF = -jnp.inf


def _prefill_kernel(
    table_ref, qpos_ref, nreal_ref,    # scalar-prefetched (SMEM)
    q_ref, bias_ref, pool_hbm, o_ref,
    kbuf, sem, m_scr, l_scr, acc_scr,
    *, tq: int, heads: int, pages: int, block_size: int, num_blocks: int,
    c: int, scale: float,
):
    i = pl.program_id(0)
    kb = pages * block_size

    def _copies(g, slot):
        return [pltpu.make_async_copy(
            pool_hbm.at[table_ref[g * pages + j]], kbuf.at[slot, j],
            sem.at[slot, j]) for j in range(pages)]

    # the tile's last REAL query sees no key behind its own position; a
    # tile of padding walks nothing
    first, n_real = i * tq, nreal_ref[0]
    last = jnp.maximum(jnp.minimum(first + tq, n_real) - 1, 0)
    n_live = jnp.where(
        first < n_real,
        jnp.minimum(qpos_ref[last] // kb + 1, num_blocks), 0)
    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(n_live > 0)
    def _():             # a copy started is a copy the loop waits for
        for cp in _copies(0, 0):
            cp.start()

    def body(g, _):
        slot = jax.lax.rem(g, 2)

        @pl.when(g + 1 < n_live)
        def _():                       # the next block's pages first
            for cp in _copies(g + 1, 1 - slot):
                cp.start()

        for cp in _copies(g, slot):
            cp.wait()
        q = q_ref[...]
        keys = kbuf[slot].reshape(kb, kbuf.shape[-1]).astype(q.dtype)
        s = jax.lax.dot_general(
            q, keys, _NT,
            preferred_element_type=jnp.float32) * scale     # [rows, kb]
        bias = bias_ref[:, pl.ds(pl.multiple_of(g * kb, kb), kb)]
        s = jnp.concatenate(
            [s[t * heads:(t + 1) * heads] + bias[t:t + 1]
             for t in range(tq)], axis=0)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        alpha = jnp.exp(jnp.where(m_prev == _NEG_INF, safe, m_prev) - safe)
        p = jnp.exp(s - _lanes(safe, kb))
        l_scr[...] = alpha * l_scr[...] + _fold(p)
        m_scr[...] = m_new
        pv = jax.lax.dot_general(
            p.astype(q.dtype), keys[:, :c], _NN,
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * _lanes(alpha, c) + pv
        return 0

    jax.lax.fori_loop(0, n_live, body, 0)
    l = jnp.sum(l_scr[...], axis=1, keepdims=True)     # the lanes' parts
    o_ref[...] = acc_scr[...] / jnp.maximum(l, 1e-30)


def block_pages(table_width: int) -> int:
    """Pages a key block holds for a table of ``table_width`` pages:
    ``PAGES_PER_BLOCK`` where that divides the table (the engine's are
    padded to whole selection blocks, twice that), else what does."""
    return math.gcd(table_width, PAGES_PER_BLOCK)


def key_blocks(ends, block_size: int, table_width: int,
               pages: Optional[int] = None):
    """``(attended, held)``: the key blocks walked for runs whose last
    REAL query stands at ``ends - 1`` (whole blocks of ``pages`` pages,
    the kernel's own by default, up to it, never more than the table;
    what pads a run to its program's shape walks nothing) and the
    blocks their tables hold (host arithmetic, for a caller that books
    what it attends; a later roofline reader counts FLOPs from the
    first).  The kernel's tiles of queries before a run's last stop at
    THEIR last query: at most one block earlier."""
    pages = pages or block_pages(table_width)
    ends = np.asarray(ends)
    held = -(-table_width // pages)
    live = np.minimum(-(-ends // (pages * block_size)), held)
    return int(live.sum()), held * ends.size


def query_tiles(n_real, klen: int):
    """``(live, held)``: the tiles of queries that hold a real query, the
    only ones that walk a key block, and the tiles their programs hold,
    for runs of ``klen`` queries whose first ``n_real`` are real (host
    arithmetic, for a caller that books what its padding costs)."""
    tq = min(QUERIES_PER_TILE, klen)
    n_real = np.asarray(n_real)
    return int((-(-n_real // tq)).sum()), -(-klen // tq) * n_real.size


@functools.partial(
    jax.jit, static_argnames=("c", "scale", "pages", "queries_per_tile",
                              "interpret", "name"))
def mla_prefill_attention(
    qq: jax.Array,       # [K, H, W]
    bias: jax.Array,     # [K, MB * bs] f32
    q_pos: jax.Array,    # [K] int32
    pool: jax.Array,     # [NB, bs, W]
    table: jax.Array,    # [MB] int32
    n_real: Optional[jax.Array] = None,  # int32 scalar; default: K
    *,
    c: int,
    scale: float,
    pages: Optional[int] = None,         # default: ``block_pages``
    queries_per_tile: int = QUERIES_PER_TILE,
    interpret: bool = False,
    name: str = "mla_prefill_attn",
) -> jax.Array:
    klen, heads, w = qq.shape
    bs = pool.shape[1]
    mb = table.shape[0]
    pages = pages or block_pages(mb)
    assert pool.shape[2] == w and mb % pages == 0, (qq.shape, pool.shape, mb)
    assert bias.shape == (klen, mb * bs), (bias.shape, klen, mb * bs)
    tq = min(queries_per_tile, klen)
    pad = -klen % tq
    if pad:   # whole tiles: rows that attend to nothing, as deep as the last
        qq = jnp.pad(qq, ((0, pad), (0, 0), (0, 0)))
        bias = jnp.pad(bias, ((0, pad), (0, 0)), constant_values=_NEG_INF)
        q_pos = jnp.pad(q_pos, (0, pad), mode="edge")
    rows = tq * heads

    def per_tile(i, table_ref, qpos_ref, nreal_ref):
        return (i, 0)

    out = pl.pallas_call(
        functools.partial(
            _prefill_kernel, tq=tq, heads=heads, pages=pages, block_size=bs,
            num_blocks=mb // pages, c=c, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=((klen + pad) // tq,),
            in_specs=[pl.BlockSpec((rows, w), per_tile),
                      pl.BlockSpec((tq, mb * bs), per_tile),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((rows, c), per_tile),
            scratch_shapes=[pltpu.VMEM((2, pages, bs, w), pool.dtype),
                            pltpu.SemaphoreType.DMA((2, pages)),
                            pltpu.VMEM((rows, 128), jnp.float32),
                            pltpu.VMEM((rows, 128), jnp.float32),
                            pltpu.VMEM((rows, c), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(((klen + pad) * heads, c),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=96 * 1024 * 1024),
        interpret=interpret,
        # the kernel's instruction in a device trace:
        # ``mla_prefill_attn.<n>`` (a window layer's call:
        # ``mla_window_prefill_attn``)
        name=name,
    )(table.astype(jnp.int32), q_pos.astype(jnp.int32),
      jnp.full((1,), klen if n_real is None else n_real, jnp.int32),
      qq.reshape((klen + pad) * heads, w),
      bias.astype(jnp.float32), pool)
    return out.reshape(klen + pad, heads, c)[:klen]
