"""Index scores of a learned selection of keys, over a paged pool (TPU
Pallas): the one stream of a latent-attention decode step that still
grows with the context.

A model with an indexer (``LlamaConfig.index_topk``) keeps one index key
``k_i`` [Di] a token a layer beside its latent row.  A query scores every
key behind it,

    I[s] = sum_h w[h] * relu(q_i[h] . k_i[s])        (float32)

and attends to the ``index_topk`` largest.  Everything else in the
step reads a bounded number of rows; this reads them all.  The kernel
is of the build of ``paged_attention._decode_kernel`` (PR 25): the grid
is ``(B,)``, a program streams ITS slot's live pages of the pool in
groups of ``pages_per_block`` by double-buffered manual DMA, the page
list from the scalar-prefetched block table, and the group loop ends at
the slot's length, so a slot reads ``ceil(length / rows) * rows`` index
keys and not its table's width.  ``q_i k_i^T`` runs on the MXU with
float32 accumulation; the ReLU, the weights and the sum over heads on
the VPU in float32 (a float32 dot on the MXU would round ``relu(.)`` to
bfloat16 first).

Layout (serving/paged.py, serving/latent.py):
  q        [B, Hi, Di]     the slots' index queries, rotated
  w        [B, Hi] f32     the heads' weights, scaled
  pool     [NB, bs, Di]    index keys, normed and rotated
  table    [B, MB] int32   block lists (0 = the trash block)
  lengths  [B] int32       keys a slot sees (0: nothing wanted)
Returns [B, MBp * bs] float32, ``MBp`` the table's width padded to whole
groups: ``I[s]`` for ``s < length``, minus infinity behind it (dead
groups are never read; the last live group is read whole and masked).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.pallas.paged_attention import _page_groups

#: pages a compute group holds: 4 x 128 rows = a [32, 512] score tile
PAGES_PER_BLOCK = 4


def _index_kernel(
    table_ref, lengths_ref,          # scalar-prefetched (SMEM)
    q_ref, w_ref, pool_hbm, o_ref, kb, sem,
    *, block_size: int, pages: int, num_groups: int,
):
    b = pl.program_id(0)
    rows = pages * block_size

    def _copies(g, slot):
        return [pltpu.make_async_copy(
            pool_hbm.at[table_ref[b, g * pages + j]], kb.at[slot, j],
            sem.at[slot, j]) for j in range(pages)]

    n_live = jnp.minimum(pl.cdiv(lengths_ref[b], rows), num_groups)
    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)

    @pl.when(n_live > 0)              # a DMA that starts is waited
    def _():
        for c in _copies(0, 0):
            c.start()

    q = q_ref[0]                      # [Hi, Di]
    w = w_ref[0]                      # [Hi, 1] f32

    def body(g, _):
        slot = jax.lax.rem(g, 2)

        @pl.when(g + 1 < n_live)
        def _():                      # the next group's pages first
            for c in _copies(g + 1, jax.lax.rem(g + 1, 2)):
                c.start()

        for c in _copies(g, slot):
            c.wait()
        k = kb[slot].reshape(rows, kb.shape[-1])
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [Hi, rows]
        score = jnp.sum(jnp.maximum(s, 0.0) * w, axis=0, keepdims=True)
        key_pos = g * rows + jax.lax.broadcasted_iota(
            jnp.int32, score.shape, 1)
        o_ref[0, pl.ds(g, 1), :] = jnp.where(
            key_pos < lengths_ref[b], score, -jnp.inf)
        return 0

    jax.lax.fori_loop(0, n_live, body, 0)


def scanned_rows(lengths, block_size: int, table_width: int,
                 pages_per_block: int = PAGES_PER_BLOCK) -> int:
    """Index keys the kernel copies for slots of these ``lengths``: whole
    groups up to each length, none for length 0, never more than the
    table (host arithmetic, for a caller that books what it streams)."""
    p_n, num_groups = _page_groups(table_width, pages_per_block)
    rows = p_n * block_size
    groups = np.clip(-(-np.asarray(lengths) // rows), 0, num_groups)
    return int(groups.sum()) * rows


def padded_rows(block_size: int, table_width: int,
                pages_per_block: int = PAGES_PER_BLOCK) -> int:
    """Width of the score rows :func:`paged_index_scores` returns."""
    p_n, num_groups = _page_groups(table_width, pages_per_block)
    return p_n * num_groups * block_size


@functools.partial(
    jax.jit, static_argnames=("pages_per_block", "interpret"))
def paged_index_scores(
    q: jax.Array,        # [B, Hi, Di]
    w: jax.Array,        # [B, Hi]
    pool: jax.Array,     # [NB, bs, Di]
    table: jax.Array,    # [B, MB] int32
    lengths: jax.Array,  # [B] int32
    *,
    pages_per_block: int = PAGES_PER_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    b, hi, di = q.shape
    bs = pool.shape[1]
    assert pool.shape[2] == di, (q.shape, pool.shape)
    mb = table.shape[1]
    p_n, num_groups = _page_groups(mb, pages_per_block)
    pad = num_groups * p_n - mb
    if pad:                           # zeros: the trash block, masked
        table = jnp.concatenate(
            [table, jnp.zeros((b, pad), table.dtype)], axis=1)
    rows = p_n * bs

    def per_slot(bi, table_ref, lengths_ref):
        return (bi, 0, 0)

    out = pl.pallas_call(
        functools.partial(_index_kernel, block_size=bs, pages=p_n,
                          num_groups=num_groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, hi, di), per_slot),
                      pl.BlockSpec((1, hi, 1), per_slot),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, num_groups, rows), per_slot),
            scratch_shapes=[pltpu.VMEM((2, p_n, bs, di), pool.dtype),
                            pltpu.SemaphoreType.DMA((2, p_n))],
        ),
        out_shape=jax.ShapeDtypeStruct((b, num_groups, rows), jnp.float32),
        interpret=interpret,
        # the kernel's instruction in a device trace:
        # ``paged_index_scores.<n>``
        name="paged_index_scores",
    )(table.astype(jnp.int32), lengths.astype(jnp.int32),
      q.astype(pool.dtype), w.astype(jnp.float32)[..., None], pool)
    return out.reshape(b, num_groups * rows)


def index_scores(q: jax.Array, w: jax.Array, keys: jax.Array) -> jax.Array:
    """The same scores in plain ``jnp``: ``q`` [..., T, Hi, Di], ``w``
    [..., T, Hi], ``keys`` [..., S, Di] -> [..., T, S] float32, unmasked.
    What the prefill paths run on a block of keys, and the kernel's
    oracle."""
    s = jnp.einsum("...thd,...sd->...ths", q, keys.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    return jnp.sum(jnp.maximum(s, 0.0)
                   * w.astype(jnp.float32)[..., None], axis=-2)


def gather_index_scores(q, w, pool, table, lengths,
                        pages_per_block: int = PAGES_PER_BLOCK):
    """:func:`paged_index_scores` through a dense gather of every slot's
    whole table: the off-chip path and the parity oracle (its traffic is
    the table's width, which the kernel exists to avoid)."""
    b, mb = table.shape
    bs = pool.shape[1]
    width = padded_rows(bs, mb, pages_per_block)
    keys = jnp.take(pool, table, axis=0).reshape(b, mb * bs, -1)
    keys = jnp.pad(keys, ((0, 0), (0, width - mb * bs), (0, 0)))
    scores = index_scores(q[:, None], w[:, None], keys)[:, 0]
    return jnp.where(jnp.arange(width)[None, :] < lengths[:, None],
                     scores, -jnp.inf)
