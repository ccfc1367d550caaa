"""Fused paged-attention decode kernel (TPU Pallas).

The seam named in PERF.md: the XLA path materializes each slot's dense
cache view (``gather_blocks``) before attention — a second full pass
over the cache bytes, and for quantized pools a pass at FULL bf16
width (the gather dequantizes first, so XLA pays code-width bytes once
to read and bf16 width again to re-stream the materialized view).
This kernel reads K/V blocks IN PLACE from the pools and folds the
dequant INSIDE, so an int8 pool streams at 1 byte/element — the dense
bf16 view never exists.

CONTRACT (supersedes the old EXPERIMENTAL/STATUS header): the serving
engine selects this kernel through ``attention_impl`` —

- ``"pallas"`` forces it, ``"xla"`` forces the fused-gather path, and
  ``"auto"`` (the default) runs a one-shot measured comparison on the
  engine's real pool geometry at build time and picks the faster one,
  so auto can never select a slower impl (bench-gated as
  ``paged_kernel_ok``; on non-TPU backends auto resolves to ``"xla"``
  because the interpret-mode kernel is a correctness tool, not a perf
  candidate);
- numerically the kernel matches the gather path to float tolerance
  for bf16 and int8 pools (parity tests run in ``interpret=True`` mode
  on CPU in tier-1, so a numerics regression cannot hide behind missing
  hardware).

Design:

1. **One stream of page groups, continuous across slots.**  The pools
   stay in HBM (``memory_space=ANY``); the grid is ``(B,)``, one slot a
   program, run in order, and a program streams its slot's pages in
   GROUPS by per-page async copies into a 2-buffer VMEM scratch, the
   page list coming from the scalar-prefetched block table.  While a
   group is multiplied the next one is already in flight into the other
   buffer: the slot's own next group, or, behind the slot's LAST live
   group, the FIRST group of the next slot that holds a key.  The
   buffers, their semaphores and one SMEM word (which buffer a slot
   starts in) carry those copies from one program to the next, so the
   DMA queue never drains at a slot's edge: only the call's first group
   is waited for with nothing to compute.  A slot of length 0 (idle, or
   parked while its prompt prefills) starts nothing, waits for nothing
   and is stepped over; the call's last live group starts nothing
   behind itself.  A DMA that starts is waited, on every path.
2. **Groups are sized in ROWS.**  Where the caller names no
   ``pages_per_block`` a group is ``GROUP_ROWS`` (256) key rows
   whatever a page is: 2 pages of 128 rows, 16 of 16 (one rule, a
   function of the page size alone; a narrower table is one group).
   Contexts of 0.2-5 k are a few groups each, so the dead tail of a
   slot's last group is an eighth of what it reads and not a half.
3. **Tiles go to the MXU as stored.**  A group lands as ``[rows x KV,
   D]``: a row's KV heads side by side.  ONE dot of all H query heads
   against it gives ``[H, rows x KV]`` scores, of which a head owns the
   columns of its own KV head; the others are masked with the rows
   behind the length.  No tile is sliced, relaid or copied per head,
   and the weight tiles the MXU loads are the ones the per-head dots
   would load.  For a float pool the K and V tiles and the query are
   the dots' operands in the POOL's dtype with a float32 result; ``p``
   is rounded to V's dtype once before ``p x V``, as ``mla_decode.py``
   and the flash kernels do.  Scores, the running maximum and sum and
   the accumulator are float32 values carried through the group loop.
4. **Dequantization folded inside.**  Quantized codes are widened to
   float32 in VMEM right after the copy lands, and their dots are
   float32.  HBM traffic for the codes is code-width;
   the XLA gather path cannot avoid materializing the dequantized rows.
   The per-(token, head) SCALES do not stream in place: their pool's
   minor dimension is the KV-head count, which the TPU pads to 128
   lanes in HBM and refuses as a DMA slice, so the wrapper gathers this
   batch's scales (2/D of the code bytes) into a lane-dense view in the
   order of a group's score columns, and the kernel applies them to the
   score and probability tiles — a scale is constant along the head
   dim, so it factors out of both dots.

Scope: single-query decode (the serving engine's K=1 step — its hot
path; speculative verify and prefill keep the gather path).

Layout contract (matches serving/paged.py):
  q        [B, H, D]        current-token queries
  k_pool   [NB, bs, KV, D]  float (bf16) or int8 codes
  v_pool   [NB, bs, KV, D]
  k_scale  [NB, bs, KV]     per-(token, head) scales (quantized pools)
  v_scale  [NB, bs, KV]
  table    [B, MB] int32    per-slot block lists (0 = trash block)
  lengths  [B]    int32     visible keys per slot (= position + 1;
                            0 = the slot's output is not wanted)
  bias     [B, n] f32       optional: 0 where the slot attends key row s,
                            -inf elsewhere (a learned selection of keys,
                            ``serving/latent.py _gqa_layer``; rows behind
                            ``n`` are nobody's).  None: every row behind
                            the length, and the call has no such operand
                            at all: the kernel every model without a
                            selection compiles is the one it compiled
  runs     (order, plan)    optional, under a bias: ``shared_runs(table,
                            lengths, ...)``, which a forward derives once
                            for all its layers (None: derived here)
Returns [B, H, D] fp32.

Under a ``bias`` the call is MASKED-DENSE and its own kernel
(:func:`_selected_kernel`, named ``SELECTED_ATTENTION`` in a device trace so
that no reader of the unmasked call matches it): the rows nobody chose
leave the running softmax as it was (a selection of 2 048 of 24 k rows wants
8 % of the bytes a slot's pages hold; gathering the chosen rows alone is
ROADMAP Reach A12), and A RUN OF LEADING PAGES THAT SEVERAL SLOTS SHARE IS
STREAMED ONCE for all of them.  Two slots share a run where their table
rows hold the same block ids from entry 0 on (requests on one cached
document: ``serving/paged.py BlockManager.alloc_sequence`` hands them the
same blocks); :func:`shared_runs` derives the runs from the table and the
lengths on the device, once a decode forward, cut to whole groups that lie
wholly behind every member's length (a slot of length 0 is in no run; a
slot nobody shares with is a run of one).  The slots are taken in the
order of their runs.  A run's first slot streams the shared groups as it
streams its own, through the same two buffers and the same look-ahead, and
multiplies each group as it lands against the queries of EVERY member,
``RUN_TILES`` slots a pass (further passes are over the group in VMEM, not
further copies of it), each member under ITS OWN row of the bias with a
running maximum, sum and float32 accumulator of its own, which wait in
VMEM.  Every slot then streams the groups behind its run (its tail and its
output; for a run of one, everything) as the unmasked kernel does, and
merges the two carries by their maxima and sums: the softmax over the same
chosen rows (a slot nobody shares with: bit for bit the unshared
kernel's).  The
wrapper lays a slot's row of the bias out as the score tile's columns lie
(a key row's KV heads side by side), a group a sublane row; every slot's
block of it is copied into VMEM once a call (17 MB at 32 slots of 33 k
rows, under a raised ``vmem_limit_bytes``), from where a run's first slot
reads its members' rows.  ``serving/engine.py _book_kv_rows`` books what
this call copies (:func:`streamed_rows` with the table: a shared group
once), so ``kv_rows_streamed / kv_rows_live`` falls under 1 as far as
pages are shared.  The unmasked call (``bias=None``) shares nothing: its
roofline reader counts a slot's bytes each (ROADMAP S18).

Only a slot's LIVE pages stream: each program's group loop runs
``min(ceil(length / rows), groups)`` times (``rows`` = one group's key
rows), a trip count read from ``lengths`` at run time
(:func:`streamed_rows` is the same arithmetic on the host), so the
kernel's traffic follows the context a slot holds and not the width its
table was sized for.  Length 0 reads nothing and returns zeros — the
engine passes it for a slot that is idle or parked while its prompt
prefills, whose logits nobody reads.  The LAST live group streams
whole: its rows past the length are masked to -inf, and its pages past
the allocation are table zeros, the trash block, whose junk the mask
discards (the table is padded with them to whole groups).  A length
past the table's capacity reads the whole table and no further.  For
every length >= 1 the output is bit-identical to running every group: a
wholly dead group only ever multiplied the carry by ``alpha = 1`` and
added ``p = 0``.

On a v5e (PR 53, the kernel alone, bf16 pools): 128 slots of 0.5-5 k
rows in 128-row pages 1.00 ms a call where the float32 per-head dots of
8-page groups took 2.69 (83 % of the HBM peak for the live bytes); 8
slots of ~0.2 k rows in 16-row pages 16 us where they took 34.

On a v5e (PR 59, the call under a selection alone, device time by the
trace, bf16 pools): 32 slots of 32 query heads on pages of 128 rows x 4 KV
heads x 128, six documents of 16-30 k rows shared by 13 / 7 / 4 / 3 / 3 / 2
slots, tails of 0.3-2.3 k, 9 % of the rows chosen a slot: 1.31 ms a call
where every slot streaming its own document took 3.97 (850 k live rows,
190 k copied); passes of 4 members alone 2.49 ms by the host's clock where
16 / 8 / 4 read 2.16.  The same slots with NOTHING shared: 3.95 against
3.97.
"""

from __future__ import annotations

import functools
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128

#: key rows a compute group holds where the caller names no
#: ``pages_per_block``: 2 pages of 128 rows, 16 of 16 (measured on a
#: v5e at both page sizes, PR 53: 512 rows read 9 % slower at 128 slots
#: of 0.5-5 k and 1.7-1.8 x slower at 8 slots of ~0.2 k, a longer dead
#: tail and a short slot's whole stream in one group; 128 rows read
#: 1 % and 6-11 % faster: PERF.md section 7 (d))
GROUP_ROWS = 256

#: the kernel's instruction in a device trace (``<name>.<n>``): the call
#: under a selection's ``bias`` has a name no reader of the unmasked one
#: matches
DECODE_ATTENTION = "paged_decode_attention"
SELECTED_ATTENTION = "paged_selected_attention"

def _group_copies(table_ref, k_hbm, v_hbm, kb, vb, sem, pages: int):
    """``(start_group, wait_group)`` of a decode kernel's stream: a page
    group of a slot's table row into one of the two VMEM buffers, one
    copy a page, K and V (static unroll: all of them in flight)."""
    def start_group(slot, g, buf):
        for j in range(pages):
            page = table_ref[slot, g * pages + j]
            pltpu.make_async_copy(
                k_hbm.at[page], kb.at[buf, j], sem.at[buf, j, 0]).start()
            pltpu.make_async_copy(
                v_hbm.at[page], vb.at[buf, j], sem.at[buf, j, 1]).start()

    def wait_group(pool_hbm, bufs, buf, which):
        # a wait reads its semaphore and the destination's size: the
        # page a copy came from is not its business
        for j in range(pages):
            pltpu.make_async_copy(
                pool_hbm.at[0], bufs.at[buf, j], sem.at[buf, j, which]).wait()

    return start_group, wait_group


def _decode_kernel(
    table_ref, lengths_ref,          # scalar-prefetched (SMEM)
    *args,
    block_size: int, pages: int, num_groups: int, capacity: int,
    kv_heads: int, group: int, head_dim: int,
    quant: bool, scale: float,
):
    if quant:
        q_ref, k_hbm, v_hbm, ks_ref, vs_ref, *rest = args
    else:
        q_ref, k_hbm, v_hbm, *rest = args
        ks_ref = vs_ref = None
    o_ref, kb, vb, sem, buf_ref = rest

    b = pl.program_id(0)
    slots = pl.num_programs(0)
    rows = pages * block_size         # key rows per compute group
    heads = kv_heads * group
    cols = rows * kv_heads            # a group's (row, KV head) pairs

    start_group, wait_group = _group_copies(
        table_ref, k_hbm, v_hbm, kb, vb, sem, pages)

    def next_live(after):
        """The first slot behind ``after`` that holds a key, ``slots``
        where none does: a slot of length 0 is stepped over."""
        return jax.lax.while_loop(
            lambda i: jnp.logical_and(
                i < slots, lengths_ref[jnp.minimum(i, slots - 1)] <= 0),
            lambda i: i + 1, after + 1)

    # the call's first copies; every later slot finds its first group in
    # flight, started under the last group of the live slot before it
    @pl.when(b == 0)
    def _():
        buf_ref[0] = 0
        first = next_live(-1)

        @pl.when(first < slots)       # a DMA that starts is waited:
        def _():                      # a call of empty slots starts none
            start_group(first, 0, 0)

    # the slot's LIVE groups: the loop below ends at the slot's length,
    # not at the table's width, so a wholly dead group is never copied
    length = jnp.minimum(lengths_ref[b], capacity)
    n_live = jnp.minimum(pl.cdiv(length, rows), num_groups)
    behind = next_live(b)
    buf0 = buf_ref[0]                 # where this slot's first group is

    # dots take the tiles as the pool stores them (float32 accumulator);
    # quantized codes are widened in VMEM, after the copy, so HBM only
    # ever saw code-width bytes
    operand = jnp.float32 if quant else kb.dtype
    q = q_ref[0].reshape(heads, head_dim).astype(operand)

    def tile(bufs, buf):
        raw = bufs[buf]               # [P, bs, KV, D]
        return raw.reshape(cols, head_dim).astype(operand)

    def scaled(x, s_ref, g):
        """``x`` [heads, cols] times the group's per-(row, KV head)
        scales, which lie 128 columns a sublane row (the wrapper's
        layout): a scale is constant along the head dim, so it factors
        out of both dots onto the score / probability tile."""
        if not quant:
            return x
        s = s_ref[0, g]               # [ceil(cols / 128), 128]
        return jnp.concatenate(
            [x[:, t:t + _LANES] * s[t // _LANES:t // _LANES + 1,
                                    :min(_LANES, cols - t)]
             for t in range(0, cols, _LANES)], axis=1)

    # A group's tile is [rows x KV, D] as it lands: row r's KV heads lie
    # side by side.  One dot of all the heads against it gives
    # [heads, rows x KV] scores of which a head owns the columns of ITS
    # KV head; the others are masked like rows behind the length, so no
    # tile is sliced or relaid per head.  ``key_row``: the key's row in
    # the group where the column is the head's own, else behind any
    # length.
    col = jax.lax.broadcasted_iota(jnp.int32, (heads, cols), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (heads, cols), 0)
    key_row = jnp.where(col % kv_heads == head // group,
                        col // kv_heads, capacity)

    def body(g, carry):
        m, l, acc = carry
        buf = jax.lax.rem(buf0 + g, 2)
        last = g + 1 == n_live
        # the queue never drains: this slot's next group, or behind its
        # last group the next live slot's first, goes out before this
        # group is waited for (the call's last group starts nothing)
        nxt_slot = jnp.where(last, jnp.minimum(behind, slots - 1), b)

        @pl.when(jnp.logical_or(jnp.logical_not(last), behind < slots))
        def _():
            start_group(nxt_slot, jnp.where(last, 0, g + 1), 1 - buf)

        wait_group(k_hbm, kb, buf, 0)
        s = jax.lax.dot_general(q, tile(kb, buf), (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = scaled(s, ks_ref, g) * scale
        # a live group's first row is visible to every head, so the
        # running maximum is finite from the first group on and a masked
        # column's exp is exactly 0
        s = jnp.where(key_row < length - g * rows, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + p.sum(axis=-1, keepdims=True)
        wait_group(v_hbm, vb, buf, 1)
        # p is rounded to V's dtype once, as the flash kernels do
        pv = jax.lax.dot_general(
            scaled(p, vs_ref, g).astype(operand), tile(vb, buf),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l, acc * alpha + pv

    _, l, acc = jax.lax.fori_loop(0, n_live, body, (
        jnp.full((heads, 1), _NEG_INF, jnp.float32),
        jnp.zeros((heads, 1), jnp.float32),
        jnp.zeros((heads, head_dim), jnp.float32)))
    buf_ref[0] = jax.lax.rem(buf0 + n_live, 2)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).reshape(
        kv_heads, group, head_dim).astype(o_ref.dtype)


#: slots whose queries ONE PASS over a shared page group multiplies
#: against it, largest first: a pass pays the MXU's 16 weight tiles of the
#: group whatever it pushes through them, so a run takes the largest tiles
#: that its members fill by more than half, and the smallest for the rest
RUN_TILES = (16, 8, 4)

#: what the call under a selection may hold of a v5e core's 128 MiB of
#: VMEM: every slot's bias (4 B a score column: 26 MB at 32 slots of 33 k
#: rows), the runs' carries, the page buffers
SELECTED_VMEM_ROOM = 96 << 20

# rows of the selected call's plan (``shared_runs``), a sorted slot a column
_START, _LIVE, _SHARED, _MEMBERS, _NEXT, _LENGTH, _ORDER, _FIRST = range(8)


def _selected_kernel(
    table_ref, plan_ref,             # scalar-prefetched (SMEM)
    q_ref, k_hbm, v_hbm, bias_hbm, o_ref,
    kb, vb, sem, buf_ref, bias_vm, bias_sem, m_ref, l_ref, acc_ref,
    *, block_size: int, pages: int, capacity: int, kv_heads: int,
    group: int, head_dim: int, scale: float,
):
    """The decode kernel UNDER A SELECTION, slots in the order of their
    runs (:func:`shared_runs`): program ``b`` streams the groups ``start
    .. live`` of its own table row, as :func:`_decode_kernel` streams ``0
    .. live``.  A run's first slot starts at 0 and multiplies the run's
    ``shared`` leading groups against the queries of ALL its ``members``
    (``RUN_TILES`` slots a pass, each under its own row of the bias; their
    running maxima, sums and accumulators wait in VMEM); every slot then
    streams its own groups behind ``shared`` and merges the two carries."""
    b = pl.program_id(0)
    slots = pl.num_programs(0)
    rows = pages * block_size
    heads = kv_heads * group
    cols = rows * kv_heads

    start_group, wait_group = _group_copies(
        table_ref, k_hbm, v_hbm, kb, vb, sem, pages)

    def bias_copy(slot):
        # (the bias lies in the caller's order: a row gather in XLA runs
        # at a tenth of a copy's pace on this chip)
        return pltpu.make_async_copy(
            bias_hbm.at[plan_ref[_ORDER, slot]], bias_vm.at[slot],
            bias_sem.at[slot])

    @pl.when(b == 0)
    def _():
        buf_ref[0] = 0
        first = plan_ref[_FIRST, 0]

        @pl.when(first < slots)
        def _():
            start_group(first, plan_ref[_START, first], 0)

        # every slot's bias into VMEM, behind the stream's first group:
        # a run's first slot reads its members' rows of it
        def send(slot, _):
            bias_copy(slot).start()
            return 0

        jax.lax.fori_loop(0, slots, send, 0)

    start = plan_ref[_START, b]       # the first group this slot streams
    n_live = plan_ref[_LIVE, b]
    shared = plan_ref[_SHARED, b]     # groups its run streams once
    members = plan_ref[_MEMBERS, b]   # of the run it is the first slot of
    behind = plan_ref[_NEXT, b]       # the next slot that streams a group
    length = plan_ref[_LENGTH, b]
    buf0 = buf_ref[0]

    # a slot that leads awaits its members' rows of the bias, its own
    # among them (a slot nobody shares with leads itself)
    def arrived(i, _):
        bias_copy(b + i).wait()
        return 0

    jax.lax.fori_loop(0, members, arrived, 0)

    def tile(bufs, buf):
        return bufs[buf].reshape(cols, head_dim)

    def step(g):
        """The buffer group ``g`` lands in, awaited, with the stream's
        next group started behind it: this slot's, or behind its last
        the first of the next slot that streams one."""
        buf = jax.lax.rem(buf0 + g - start, 2)
        last = g + 1 == n_live
        nxt_slot = jnp.where(last, jnp.minimum(behind, slots - 1), b)

        @pl.when(jnp.logical_or(jnp.logical_not(last), behind < slots))
        def _():
            start_group(nxt_slot, jnp.where(
                last, plan_ref[_START, nxt_slot], g + 1), 1 - buf)

        wait_group(k_hbm, kb, buf, 0)
        return buf

    # a head owns the columns of its own KV head (``_decode_kernel``)
    col = jax.lax.broadcasted_iota(jnp.int32, (heads, cols), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (heads, cols), 0)
    own = col % kv_heads == head // group

    def softmax_step(s, m, l):
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        # p is rounded to V's dtype once, as the flash kernels do
        return (m_new, alpha * l + p.sum(axis=-1, keepdims=True), alpha,
                p.astype(kb.dtype))

    # ---- the run's shared groups: every row of them is live for every
    # member (the run never reaches behind a member's length), so the
    # member's own row of the bias is the whole mask
    def shared_group(g, _):
        buf = step(g)
        k_tile = tile(kb, buf)
        wait_group(v_hbm, vb, buf, 1)
        v_tile = tile(vb, buf)
        fresh = g == 0

        def members_pass(at, size):
            """``size`` slots from sorted slot ``at`` on against the group."""
            q = q_ref[pl.ds(at, size)].reshape(size * heads, head_dim)
            s = jax.lax.dot_general(
                q, k_tile, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            alphas, ps = [], []
            for j in range(size):
                s_j = s[j * heads:(j + 1) * heads] \
                    + bias_vm[at + j, pl.ds(g, 1), :]
                m, l, alpha, p = softmax_step(
                    jnp.where(own, s_j, _NEG_INF),
                    jnp.where(fresh, _NEG_INF, m_ref[at + j]),
                    jnp.where(fresh, 0.0, l_ref[at + j]))
                m_ref[at + j] = m
                l_ref[at + j] = l
                alphas.append(alpha)
                ps.append(p)
            pv = jax.lax.dot_general(
                jnp.concatenate(ps, axis=0), v_tile,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc = jnp.where(fresh, 0.0, acc_ref[pl.ds(at, size)].reshape(
                size * heads, head_dim))
            acc_ref[pl.ds(at, size)] = (
                acc * jnp.concatenate(alphas, axis=0) + pv).reshape(
                    size, heads, head_dim)

        # a last pass that reaches past the run writes the carries of
        # slots BEHIND it, which their own run's first group makes anew
        at, left = b, members
        for size in RUN_TILES:
            over = size // 2 if size != RUN_TILES[-1] else 0
            passes = left // size + (left % size > over).astype(jnp.int32)

            def tile_passes(t, _, at=at, size=size):
                members_pass(at + t * size, size)
                return 0

            jax.lax.fori_loop(0, passes, tile_passes, 0)
            at = at + passes * size
            left = jnp.maximum(left - passes * size, 0)
        return 0

    jax.lax.fori_loop(start, shared, shared_group, 0)

    # ---- the slot's own groups
    q = q_ref[b]
    key_row = jnp.where(own, col // kv_heads, capacity)

    def own_group(g, carry):
        m, l, acc = carry
        buf = step(g)
        s = jax.lax.dot_general(q, tile(kb, buf), (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # the group's row of the slot's bias, over every head: minus
        # infinity where nobody chose the key, whose exp is exactly 0
        # against the finite running maximum.  (A group may hold no
        # chosen row: the maximum then stays at its floor, the columns
        # behind the length count as 1 each, and the first chosen row's
        # ``alpha`` of exactly 0 takes them out again: every slot with a
        # key has a chosen row.)
        s = s + bias_vm[b, pl.ds(g, 1), :]
        s = jnp.where(key_row < length - g * rows, s, _NEG_INF)
        m, l, alpha, p = softmax_step(s, m, l)
        wait_group(v_hbm, vb, buf, 1)
        pv = jax.lax.dot_general(p, tile(vb, buf), (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return m, l, acc * alpha + pv

    # from constants, as the unmasked kernel's carry starts (a carry that
    # starts from a VMEM load keeps that load's layout through the loop:
    # 8.7 % more a group on the chip), and merged with the run's carry
    # behind the loop by the two maxima.  A part that chose no row stands
    # at the floor and its weight is exactly 0; a slot in no run, or with
    # no group of its own, takes the other part as it is
    m, l, acc = jax.lax.fori_loop(shared, n_live, own_group, (
        jnp.full((heads, 1), _NEG_INF, jnp.float32),
        jnp.zeros((heads, 1), jnp.float32),
        jnp.zeros((heads, head_dim), jnp.float32)))
    joined = shared > 0
    m_run = jnp.where(joined, m_ref[b], _NEG_INF)
    top = jnp.maximum(m, m_run)
    own_w, run_w = jnp.exp(m - top), jnp.exp(m_run - top)
    l = l * own_w + jnp.where(joined, l_ref[b], 0.0) * run_w
    acc = acc * own_w + jnp.where(joined, acc_ref[b], 0.0) * run_w
    buf_ref[0] = jax.lax.rem(buf0 + n_live - start, 2)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).reshape(
        kv_heads, group, head_dim).astype(o_ref.dtype)


def _page_groups(table_width: int, pages_per_block: int):
    """``(pages per group, groups)`` a table of ``table_width`` pages is
    cut into: the wrapper's padding and the kernel's trip count both
    come from here."""
    p_n = max(1, min(int(pages_per_block), table_width))
    return p_n, -(-table_width // p_n)


def _group_pages(block_size: int, pages_per_block: Optional[int]) -> int:
    """Pages a group of THIS kernel holds where the caller names none:
    ``GROUP_ROWS`` key rows, whatever a page is (at least one page)."""
    if pages_per_block is not None:
        return pages_per_block
    return max(1, GROUP_ROWS // block_size)


def _live_groups(xp, lengths, rows: int, num_groups: int):
    """Page groups up to each length: the kernels' trip count."""
    return xp.clip(-(-lengths // rows), 0, num_groups)


def streamed_rows(lengths, block_size: int, table_width: int,
                  pages_per_block: Optional[int] = None,
                  table=None) -> int:
    """Key rows the kernel copies (K and V each) for slots of these
    ``lengths``: whole groups up to each length, none for length 0,
    never more than the table — the kernel's trip count as host
    arithmetic, for a caller that books what it streams.  With the
    slots' ``table`` [B, table_width] (``lengths`` [B] or [B, forwards]):
    what the call under a SELECTION copies, a run's shared groups once a
    forward (:func:`shared_runs`)."""
    p_n, num_groups = _page_groups(
        table_width, _group_pages(block_size, pages_per_block))
    rows = p_n * block_size
    lengths = np.asarray(lengths)
    groups = int(_live_groups(np, lengths, rows, num_groups).sum())
    if table is not None:
        table = np.asarray(table)
        table = np.pad(table, ((0, 0), (0, num_groups * p_n - table_width)))
        common = _common_groups(np, table, p_n)
        for forward in lengths.reshape(lengths.shape[0], -1).T:
            leader, shared, _ = _runs(np, common, forward, rows, num_groups)
            groups -= int(shared[leader != np.arange(leader.size)].sum())
    return groups * rows


def _common_groups(xp, table, pages: int):
    """[B, B]: the leading page groups that rows b and s of ``table``
    (whole groups wide) hold block for block in common."""
    slots = table.shape[0]
    grouped = table.reshape(slots, -1, pages)
    same = (grouped[:, None] == grouped[None, :]).all(-1)
    # (the first group that differs: a reduction, not a scan)
    return xp.where(same.all(-1), same.shape[-1], xp.argmin(same, axis=-1))


def _runs(xp, common, lengths, rows: int, num_groups: int):
    """The RUNS of slots of these ``lengths`` [B] whose table rows begin
    alike (``common``: :func:`_common_groups`): ``(leader, shared, live)``
    [B] each.  Slots belong to one run where their first group is the
    same pages and wholly behind both lengths; ``leader`` is the run's
    lowest-numbered slot (a slot nobody shares with leads itself),
    ``shared`` the leading groups every member of the slot's run holds in
    common, wholly behind every member's length (0 in a run of one), and
    ``live`` the slot's groups up to its length.  ``numpy`` or
    ``jax.numpy`` as ``xp``: the plan a call is handed and the host's
    booking of it are one arithmetic."""
    n = lengths.shape[0]
    slot = xp.arange(n)
    whole = xp.clip(lengths // rows, 0, num_groups)
    both = xp.minimum(common, xp.minimum(whole[:, None], whole[None, :]))
    leader = xp.argmax((both > 0) | (slot[:, None] == slot[None, :]), axis=1)
    member = leader[:, None] == slot[None, :]            # [slot, run]
    to_leader = xp.take_along_axis(both, leader[:, None], axis=1)
    shared = xp.where(member, to_leader, num_groups).min(axis=0)
    shared = xp.where(member.sum(axis=0) > 1, shared, 0)[leader]
    return leader, shared, _live_groups(xp, lengths, rows, num_groups)


def shared_runs(table: jax.Array, lengths: jax.Array, block_size: int,
                pages_per_block: Optional[int] = None):
    """What :func:`paged_decode_attention` under a ``bias`` is handed as
    ``runs``: ``(order, plan)``, the slots in the order of their runs
    (:func:`_runs`; a run's slots side by side, its leader first) and
    each sorted slot's column of the kernel's plan, int32 [8, B]: the
    first group it streams (0 for a leader, else its run's ``shared``),
    its live groups, its run's shared groups, its run's members (a
    leader's; else 0), the next sorted slot that streams a group (B: none),
    its length, its place in the caller's order, and in column 0 of the
    last row the first slot that streams one.  A function of the table and the lengths alone: a decode
    forward derives it once for all its layers."""
    slots, width = table.shape
    p_n, num_groups = _page_groups(
        width, _group_pages(block_size, pages_per_block))
    table = jnp.pad(table, ((0, 0), (0, num_groups * p_n - width)))
    lengths = jnp.minimum(lengths.astype(jnp.int32), width * block_size)
    leader, shared, live = _runs(
        jnp, _common_groups(jnp, table, p_n), lengths, p_n * block_size,
        num_groups)
    slot = jnp.arange(slots)
    order = jnp.argsort(leader * slots + slot)
    leads = (leader == slot)[order]
    shared, live = shared[order], live[order]
    start = jnp.where(leads, 0, shared)
    members = jnp.where(leads, (leader[:, None] == slot[None, :]).sum(0)[
        order], 0)
    streams = jnp.where(live > start, slot, slots)
    nxt = jnp.where(slot[None, :] > slot[:, None], streams[None, :],
                    slots).min(axis=1)
    first = jnp.full((slots,), streams.min())
    return order.astype(jnp.int32), jnp.stack(
        [start, live, shared, members, nxt, lengths[order], order, first]
    ).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("pages_per_block", "interpret", "scale"))
def paged_decode_attention(
    q: jax.Array,        # [B, H, D]
    k_pool: jax.Array,   # [NB, bs, KV, D]
    v_pool: jax.Array,
    table: jax.Array,    # [B, MB] int32
    lengths: jax.Array,  # [B] int32
    *,
    k_scale: Optional[jax.Array] = None,   # [NB, bs, KV] (quant pools)
    v_scale: Optional[jax.Array] = None,
    pages_per_block: Optional[int] = None,  # None: GROUP_ROWS of rows
    interpret: bool = False,
    scale: Optional[float] = None,     # None: head_dim ** -0.5
    bias: Optional[jax.Array] = None,  # [B, n] f32: 0 chosen, -inf not
    runs=None,           # shared_runs(table, lengths, ...): under a bias
) -> jax.Array:
    b, h, d = q.shape
    nb, bs, kv, _ = k_pool.shape
    quant = k_scale is not None
    if bias is not None:
        assert not quant, "a selection's bias over a quantized pool"
        return _selected_attention(
            q, k_pool, v_pool, table, lengths, bias, runs,
            pages_per_block, interpret, scale)
    assert k_pool.shape[-1] == d, (q.shape, k_pool.shape)
    assert h % kv == 0, (h, kv)
    g = h // kv
    mb = table.shape[1]
    # pad the table to a multiple of the page-group size with zeros —
    # the trash block, which no length reaches
    p_n, num_groups = _page_groups(mb, _group_pages(bs, pages_per_block))
    pad = num_groups * p_n - mb
    if pad:
        table = jnp.concatenate(
            [table, jnp.zeros((b, pad), table.dtype)], axis=1)
    qg = q.reshape(b, kv, g, d)

    def q_map(bi, table_ref, lengths_ref):
        return (bi, 0, 0, 0)

    kernel = functools.partial(
        _decode_kernel, block_size=bs, pages=p_n, num_groups=num_groups,
        capacity=mb * bs, kv_heads=kv, group=g, head_dim=d,
        quant=quant,
        scale=float(d ** -0.5 if scale is None else scale),
    )
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, kv, g, d), q_map), any_spec, any_spec]
    operands = [qg, k_pool, v_pool]
    if quant:
        # The scale pools' minor dimension is the KV-head count, which
        # no TPU tiling accepts as a DMA slice (and which HBM pads to
        # 128 lanes), so the kernel never touches them in place.  The
        # wrapper gathers this batch's scales — 2/D of the code bytes —
        # into a lane-dense f32 view: a group's scales in the order of
        # its score tile's columns (row, then KV head), 128 a sublane
        # row; the codes, which are the traffic that matters, still
        # stream in place.
        cols = p_n * bs * kv
        tiles = -(-cols // _LANES)

        def on_lanes(scale_pool):
            s = jnp.take(scale_pool, table, axis=0)   # [B, MBp, bs, KV]
            s = s.reshape(b, num_groups, cols).astype(jnp.float32)
            s = jnp.pad(s, ((0, 0), (0, 0), (0, tiles * _LANES - cols)))
            return s.reshape(b, num_groups, tiles, _LANES)

        s_spec = pl.BlockSpec((1, num_groups, tiles, _LANES), q_map)
        in_specs += [s_spec, s_spec]
        operands += [on_lanes(k_scale), on_lanes(v_scale)]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, kv, g, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((2, p_n, bs, kv, d), k_pool.dtype),
                pltpu.VMEM((2, p_n, bs, kv, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, p_n, 2)),
                pltpu.SMEM((1,), jnp.int32),   # the buffer a slot starts in
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, d), jnp.float32),
        # one slot after another: a slot's first copies are started by
        # the slot before it, and the buffers carry them across
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # the name of the kernel's instruction in a device trace
        # (``paged_decode_attention.<n>``), whatever wraps the call
        name=DECODE_ATTENTION,
    )(table.astype(jnp.int32), lengths.astype(jnp.int32), *operands)
    return out.reshape(b, h, d)


def _selected_attention(q, k_pool, v_pool, table, lengths, bias, runs,
                        pages_per_block, interpret, scale):
    """:func:`paged_decode_attention` under a selection's ``bias``
    (float pools): the slots in the order of their runs, so that a run's
    queries and rows of the bias lie side by side, through
    :func:`_selected_kernel` and back into the caller's order."""
    b, h, d = q.shape
    _, bs, kv, _ = k_pool.shape
    assert h % kv == 0, (h, kv)
    mb = table.shape[1]
    p_n, num_groups = _page_groups(mb, _group_pages(bs, pages_per_block))
    rows = p_n * bs
    if runs is None:
        runs = shared_runs(table, lengths, bs, pages_per_block)
    order, plan = runs
    table = jnp.pad(table, ((0, 0), (0, num_groups * p_n - mb)))[order]
    # a run's last pass reads a whole tile of slots from its first on
    padded = b + RUN_TILES[0] - 1
    q = jnp.pad(q.astype(k_pool.dtype)[order],
                ((0, padded - b), (0, 0), (0, 0)))
    # a slot's row of the bias as a group's score columns lie (row, then
    # KV head), a group a sublane row; rows behind the bias's own width
    # (the table's padding to whole groups) are nobody's
    # (whole sublane tiles of groups: the copy into VMEM takes no less)
    held = -(-num_groups // 8) * 8
    width = held * rows
    bias = bias.astype(jnp.float32)[:, :num_groups * rows]
    bias = jnp.pad(bias, ((0, 0), (0, width - bias.shape[1])),
                   constant_values=-jnp.inf)
    bias = jnp.repeat(bias, kv, axis=1).reshape(b, held, rows * kv)
    kernel = functools.partial(
        _selected_kernel, block_size=bs, pages=p_n, capacity=mb * bs,
        kv_heads=kv, group=h // kv, head_dim=d,
        scale=float(d ** -0.5 if scale is None else scale))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    carry = (padded, h, 1)
    vmem = (4 * (padded * held * rows * kv
                 + 2 * padded * h * _LANES + padded * h * d)
            + 4 * p_n * bs * kv * d * k_pool.dtype.itemsize
            + 2 * padded * h * d * q.dtype.itemsize)
    if vmem > SELECTED_VMEM_ROOM:
        raise NotImplementedError(
            f"every slot's bias in VMEM is {vmem >> 20} MiB at {b} slots of "
            f"{mb * bs} rows: more than the {SELECTED_VMEM_ROOM >> 20} MiB "
            "the kernel under a selection may hold; fewer slots, a shorter "
            "max_len, or attention_impl='xla'")
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((padded, h, d), lambda bi, *_: (0, 0, 0)),
                any_spec, any_spec, any_spec],
            # (a sorted slot's output into its place in the caller's order)
            out_specs=pl.BlockSpec(
                (1, kv, h // kv, d),
                lambda bi, table_ref, plan_ref: (plan_ref[_ORDER, bi], 0, 0,
                                                 0)),
            scratch_shapes=[
                pltpu.VMEM((2, p_n, bs, kv, d), k_pool.dtype),
                pltpu.VMEM((2, p_n, bs, kv, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, p_n, 2)),
                pltpu.SMEM((1,), jnp.int32),   # the buffer a slot starts in
                pltpu.VMEM((padded, held, rows * kv), jnp.float32),
                pltpu.SemaphoreType.DMA((b,)),
                pltpu.VMEM(carry, jnp.float32),          # running maxima
                pltpu.VMEM(carry, jnp.float32),          # running sums
                pltpu.VMEM((padded, h, d), jnp.float32),  # accumulators
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, h // kv, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # every slot's bias waits in VMEM beside the carries
            vmem_limit_bytes=vmem + (16 << 20)),
        interpret=interpret,
        # a name of its own, so that a device trace tells the call from
        # the unmasked one (``paged_selected_attention.<n>``)
        name=SELECTED_ATTENTION,
    )(table.astype(jnp.int32), plan, q, k_pool, v_pool, bias)
    return out.reshape(b, h, d)


# ----------------------------------------------------- the XLA twin
@functools.partial(jax.jit, static_argnames=())
def gather_reference(
    q: jax.Array,        # [B, H, D]
    k_pool: jax.Array,   # [NB, bs, KV, D]
    v_pool: jax.Array,
    table: jax.Array,    # [B, MB]
    lengths: jax.Array,  # [B]
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,   # [B, n] f32 (0 chosen, -inf not)
) -> jax.Array:
    """The fused-gather path the engine's ``attention_impl="xla"``
    runs, as a standalone function: materialize the dense (dequantized)
    per-slot view, then masked GQA attention — both the parity oracle
    for the kernel and the ``"xla"`` side of the auto-pick
    measurement.  Mirrors ``serving/model.py`` exactly: ``gather_blocks
    [_q]`` then the unexpanded-cache einsum pair."""
    from dlrover_tpu.serving.paged import gather_blocks, gather_blocks_q

    b, h, d = q.shape
    kv = k_pool.shape[2]
    g = h // kv
    if k_scale is None:
        ck = gather_blocks(k_pool, table).astype(jnp.float32)
        cv = gather_blocks(v_pool, table).astype(jnp.float32)
    else:
        ck = gather_blocks_q(k_pool, k_scale, table, jnp.float32)
        cv = gather_blocks_q(v_pool, v_scale, table, jnp.float32)
    qg = q.astype(jnp.float32).reshape(b, kv, g, d)
    scores = jnp.einsum(
        "bkgd,blkd->bkgl", qg, ck,
        preferred_element_type=jnp.float32,
    ) / jnp.sqrt(float(d))
    key_pos = jnp.arange(ck.shape[1])
    mask = key_pos[None, :] < lengths[:, None]          # [B, L]
    if bias is not None:        # the rows a selection chose, of the live
        n = min(bias.shape[1], mask.shape[1])
        mask &= jnp.zeros_like(mask).at[:, :n].set(bias[:, :n] == 0)
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgl,blkd->bkgd", probs, cv,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, h, d)


def kernel_parity(
    *, slots: int, max_blocks: int, block_size: int, num_heads: int,
    num_kv_heads: int, head_dim: int, dtype, kv_dtype: Optional[str],
    interpret: bool, seed: int = 0,
) -> Dict[str, object]:
    """The fused kernel against :func:`gather_reference` at an engine's
    pool geometry (page size, heads, dtypes, its table shape; no more
    blocks than the table can name, so the check costs megabytes beside
    a pool that fills the device), filled from ``seed``.  Runs wherever
    the caller runs: on a chip it is the COMPILED kernel, which the
    interpret-mode tests cannot vouch for — a serving worker reports it
    before it announces.  The reference's dots run at full f32
    precision so the comparison measures the kernel, not the backend's
    default matmul truncation."""
    from dlrover_tpu.models.quantize import quantize_kv_int8

    b, mb = slots, max_blocks
    nb = b * mb + 1
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (nb, block_size, num_kv_heads, head_dim)
    q = (0.3 * jax.random.normal(kq, (b, num_heads, head_dim))).astype(dtype)
    k = (0.3 * jax.random.normal(kk, shape)).astype(dtype)
    v = (0.3 * jax.random.normal(kv_, shape)).astype(dtype)
    scales = {}
    if kv_dtype == "int8":
        k, scales["k_scale"] = quantize_kv_int8(k)
        v, scales["v_scale"] = quantize_kv_int8(v)
    table = jax.random.randint(
        jax.random.fold_in(kq, 1), (b, mb), 1, nb, jnp.int32)
    # one key, odd mid lengths, ..., every column live
    top = mb * block_size
    lengths = jnp.clip(
        jnp.linspace(1, top, b).astype(jnp.int32) | 1, 1, top)
    out = paged_decode_attention(
        q, k, v, table, lengths, interpret=interpret, **scales)
    with jax.default_matmul_precision("highest"):
        ref = gather_reference(
            q, k, v, table, lengths, scales.get("k_scale"),
            scales.get("v_scale"))
    return {
        "kv_dtype": kv_dtype or "bf16",
        "max_abs_err": float(jnp.max(jnp.abs(out - ref))),
        "finite": bool(jnp.isfinite(out).all()),
        "pool_shape": list(k.shape), "table_shape": [b, mb],
        "interpret": interpret,
    }


# ------------------------------------------------- measured auto-pick
def measure_paged_attention(
    q, k_pool, v_pool, table, lengths,
    k_scale=None, v_scale=None, trials: int = 3,
    interpret: bool = False,
) -> Dict[str, float]:
    """Best-of-``trials`` wall seconds for each impl on THESE operands
    — the one-shot measurement ``attention_impl="auto"`` runs at
    engine build (and the bench's crossover probe).  Both sides
    compile first; the measured runs sync via block_until_ready.
    The kernel's time follows ``lengths`` (it streams live pages
    only), the gather's the table's width: the engine hands in full
    lengths, the kernel's worst case."""
    impls = {
        "xla": lambda: gather_reference(
            q, k_pool, v_pool, table, lengths, k_scale, v_scale),
        "pallas": lambda: paged_decode_attention(
            q, k_pool, v_pool, table, lengths,
            k_scale=k_scale, v_scale=v_scale, interpret=interpret),
    }
    out: Dict[str, float] = {}
    for name, fn in impls.items():
        jax.block_until_ready(fn())          # compile outside the clock
        best = None
        for _ in range(max(1, trials)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        out[name] = best
    return out


def resolve_attention_impl(
    requested: str, timings: Optional[Dict[str, float]],
) -> str:
    """The auto-pick decision, factored pure so the ``never picks a
    slower impl`` contract is directly testable: an explicit request is
    honored; ``auto`` with measurements picks the faster impl; ``auto``
    without measurements (non-TPU backend, or measurement skipped)
    falls back to the always-available gather path."""
    if requested in ("xla", "pallas"):
        return requested
    if requested != "auto":
        raise ValueError(
            f"attention_impl={requested!r} not supported: use "
            "'auto', 'xla' or 'pallas'")
    if not timings:
        return "xla"
    return min(("xla", "pallas"), key=lambda k: timings[k])
