"""Fused paged-attention decode kernel (TPU Pallas).

The seam named in PERF.md: the XLA path materializes each slot's dense
cache view (``gather_blocks``) before attention — a second full pass
over the cache bytes, and for quantized pools a pass at FULL bf16
width (the gather dequantizes first, so XLA pays code-width bytes once
to read and bf16 width again to re-stream the materialized view).
This kernel reads K/V blocks IN PLACE from the pools and folds the
dequant INSIDE, so an int8 pool streams at 1 byte/element and a packed
int4 pool at 0.5 — the dense bf16 view never exists.

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
  for bf16, int8 and packed int4 pools (parity tests run in
  ``interpret=True`` mode on CPU in tier-1, so a numerics regression
  cannot hide behind missing hardware).

Design — the two fixes the old STATUS header prescribed, plus the new
leverage:

1. **Multi-page compute blocks with double-buffered manual DMA.**  The
   old kernel's grid was ``(B, MB)`` — one 16-row page per grid step,
   so per-grid-step latency dominated (472 us vs the gather's 86 us)
   and the per-kv-head dots under-filled the MXU.  Now the grid is
   ``(B,)`` and each program streams its slot's pages in GROUPS of
   ``pages_per_block`` (default 8 -> 128 key rows per compute block at
   the engine's 16-row pages): the pools stay in HBM
   (``memory_space=ANY``) and the kernel issues per-page async copies
   into a 2-slot VMEM scratch, starting group ``g+1``'s DMAs before
   computing group ``g`` — the double-buffer pattern, with the page
   list coming from the scalar-prefetched block table.
2. **Dequantization folded inside.**  Quantized codes are widened in
   VMEM right after the copy lands (int4 codes unpack split-half: byte
   ``j`` holds code ``j`` low-nibble and ``j + D/2`` high-nibble, so
   unpack is a concatenate, not an interleave).  HBM traffic for the
   codes is code-width; the XLA gather path cannot avoid materializing
   the dequantized rows.  The per-(token, head) SCALES do not stream in
   place: their pool's minor dimension is the KV-head count, which the
   TPU pads to 128 lanes in HBM and refuses as a DMA slice, so the
   wrapper gathers this batch's scales (2/D of the code bytes) into a
   lane-dense ``[B, groups, KV, rows]`` view, and the kernel applies
   them to the ``[G, rows]`` score and probability tiles — a scale is
   constant along the head dim, so it factors out of both dots.
   **Packed int4 pools do not compile on a TPU** (``INT4_REFUSAL``):
   that variant runs in interpret mode only, as the parity harness.
3. Online softmax (flash-style m/l/acc carry in VMEM scratch) over
   ``[KV*G, pages*bs]`` score tiles per group; GQA queries regroup to
   ``[KV, G, D]`` and each kv head's scores come from one dot against
   its slice of the group.

Scope: single-query decode (the serving engine's K=1 step — its hot
path; speculative verify and prefill keep the gather path).

Layout contract (matches serving/paged.py):
  q        [B, H, D]        current-token queries
  k_pool   [NB, bs, KV, Dc] Dc = D (bf16/int8) or D//2 (packed int4)
  v_pool   [NB, bs, KV, Dc]
  k_scale  [NB, bs, KV]     per-(token, head) scales (quantized pools)
  v_scale  [NB, bs, KV]
  table    [B, MB] int32    per-slot block lists (0 = trash block)
  lengths  [B]    int32     visible keys per slot (= position + 1;
                            0 = the slot's output is not wanted)
Returns [B, H, D] fp32.

Only a slot's LIVE pages stream: each program's group loop runs
``min(ceil(length / rows), groups)`` times (``rows`` = one group's
``pages_per_block * bs`` key rows), a trip count read from ``lengths``
at run time, so the kernel's traffic follows the context a slot holds
and not the width its table was sized for.  Length 0 reads nothing
and returns zeros — the engine passes it for a slot that is idle or
parked while its prompt prefills, whose logits nobody reads.  The LAST
live group streams whole: its rows past the length are masked to -inf,
and its pages past the allocation are table zeros, the trash block,
whose junk the mask discards (the table is padded with them to a
multiple of ``pages_per_block``).  A length past the table's capacity
reads the whole table and no further.  For every length >= 1 the
output is bit-identical to running every group: a wholly dead group
only ever multiplied the carry by ``alpha = 1`` and added ``p = 0``.
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

#: Why the packed-int4 variant is refused on a TPU (compiled for a
#: described v5e, PR 21).  The pool's minor dimension is D//2 = 64, and
#: Mosaic accepts a DMA slice only of a 128-aligned minor dimension.
#: Until the pool is re-laid lane-dense, int4 pools run the gather.
INT4_REFUSAL = (
    "the packed int4 pool does not compile on TPU v5e — MosaicError: "
    "'Slice shape along dimension 3 must be aligned to tiling (128), "
    "but is 64' (the packed minor dimension D//2); use "
    "attention_impl='xla' (the gather) or kv_dtype='int8'"
)


def _unpack4_f32(x: jax.Array) -> jax.Array:
    """Packed int4 ``[..., Dc] -> f32 codes [..., 2*Dc]`` (split-half
    layout; the int32 shifts sign-extend each nibble).  Kept local so
    the kernel has no cross-module imports to trace."""
    p = x.astype(jnp.int32)
    lo = (p << 28) >> 28
    hi = (p << 24) >> 28
    return jnp.concatenate([lo, hi], axis=-1).astype(jnp.float32)


def _decode_kernel(
    table_ref, lengths_ref,          # scalar-prefetched (SMEM)
    *args,
    block_size: int, pages: int, num_groups: int,
    kv_heads: int, group: int, head_dim: int,
    quant: bool, packed: bool, scale: Optional[float] = None,
):
    if quant:
        (q_ref, k_hbm, v_hbm, ks_ref, vs_ref, o_ref,
         kb, vb, m_scr, l_scr, acc_scr, sem) = args
    else:
        (q_ref, k_hbm, v_hbm, o_ref,
         kb, vb, m_scr, l_scr, acc_scr, sem) = args
        ks_ref = vs_ref = None

    b = pl.program_id(0)
    bs, p_n = block_size, pages
    rows = p_n * bs                   # key rows per compute group

    def _group_copies(g, slot):
        """The per-page DMA descriptors for group ``g`` into buffer
        ``slot`` — built identically at start() and wait() time (the
        canonical Pallas double-buffer idiom)."""
        copies = []
        for j in range(p_n):          # static unroll: p_n DMAs in flight
            page = table_ref[b, g * p_n + j]
            copies.append(pltpu.make_async_copy(
                k_hbm.at[page], kb.at[slot, j], sem.at[slot, j, 0]))
            copies.append(pltpu.make_async_copy(
                v_hbm.at[page], vb.at[slot, j], sem.at[slot, j, 1]))
        return copies

    def start_group(g, slot):
        for c in _group_copies(g, slot):
            c.start()

    def wait_group(g, slot):
        for c in _group_copies(g, slot):
            c.wait()

    # the slot's LIVE groups: the loop below ends at the slot's length,
    # not at the table's width, so a wholly dead group is never copied
    # (a skipped group contributed alpha = 1, p = 0: nothing changes)
    n_live = jnp.minimum(pl.cdiv(lengths_ref[b], rows), num_groups)

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(n_live > 0)              # a DMA that starts is waited:
    def _():                          # length 0 starts none
        start_group(0, 0)             # warm-up: first group in flight

    qf = q_ref[0].astype(jnp.float32)            # [KV, G, D]

    def _codes(raw):
        # raw [P, bs, KV, Dc] -> f32 [P, bs, KV, D]; the whole point:
        # this runs on VMEM-resident codes AFTER the copy, so HBM only
        # ever saw code-width bytes.  The per-(token, head) scales are
        # NOT applied here: a scale is constant along the head dim, so
        # it factors out of both dots and multiplies the [G, rows]
        # score / probability tiles instead (rows on lanes — see the
        # wrapper's scale layout)
        return _unpack4_f32(raw) if packed else raw.astype(jnp.float32)

    def body(g, _):
        slot = jax.lax.rem(g, 2)

        @pl.when(g + 1 < n_live)
        def _():                      # overlap: next group's DMA first
            start_group(g + 1, jax.lax.rem(g + 1, 2))

        wait_group(g, slot)
        kf = _codes(kb[slot]).reshape(rows, kv_heads, head_dim)
        vf = _codes(vb[slot]).reshape(rows, kv_heads, head_dim)
        if quant:
            ks = ks_ref[0, g]         # [KV, rows] f32, this group's
            vs = vs_ref[0, g]

        def k_scaled(x, kvi):         # [G, rows] * [1, rows]
            return x * ks[kvi:kvi + 1, :] if quant else x

        def v_scaled(x, kvi):
            return x * vs[kvi:kvi + 1, :] if quant else x

        # per-kv-head scores: [KV*G, rows] via KV dots (static loop) —
        # at rows = pages*bs the dot's N dim is 128+ and fills the MXU
        scores = jnp.concatenate(
            [
                k_scaled(jax.lax.dot_general(
                    qf[kvi], kf[:, kvi], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ), kvi)
                for kvi in range(kv_heads)
            ],
            axis=0,
        )
        # the softmax scale a model states for itself, else the head's
        scores = scores / (head_dim ** 0.5) if scale is None \
            else scores * scale
        key_pos = g * rows + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        visible = key_pos < lengths_ref[b]
        scores = jnp.where(visible, scores, _NEG_INF)

        m_prev = m_scr[...]                      # [KV*G]
        m_new = jnp.maximum(m_prev, scores.max(axis=-1))
        # guard the all-masked group: exp(-inf - -inf) must not NaN
        alpha = jnp.where(m_new == _NEG_INF, 0.0,
                          jnp.exp(m_prev - m_new))
        p = jnp.exp(scores - m_new[:, None])
        p = jnp.where(visible, p, 0.0)
        l_scr[...] = alpha * l_scr[...] + p.sum(axis=-1)
        pv = jnp.concatenate(
            [
                jax.lax.dot_general(
                    v_scaled(p[kvi * group:(kvi + 1) * group], kvi),
                    vf[:, kvi],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for kvi in range(kv_heads)
            ],
            axis=0,
        )
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
        m_scr[...] = m_new
        return 0

    jax.lax.fori_loop(0, n_live, body, 0)
    denom = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0] = (acc_scr[...] / denom[:, None]).reshape(
        kv_heads, group, head_dim).astype(o_ref.dtype)


def _page_groups(table_width: int, pages_per_block: int):
    """``(pages per group, groups)`` a table of ``table_width`` pages is
    cut into: the wrapper's padding and the kernel's trip count both
    come from here."""
    p_n = max(1, min(int(pages_per_block), table_width))
    return p_n, -(-table_width // p_n)


def streamed_rows(lengths, block_size: int, table_width: int,
                  pages_per_block: int = 8) -> int:
    """Key rows the kernel copies (K and V each) for slots of these
    ``lengths``: whole groups up to each length, none for length 0,
    never more than the table — the kernel's trip count as host
    arithmetic, for a caller that books what it streams."""
    p_n, num_groups = _page_groups(table_width, pages_per_block)
    rows = p_n * block_size
    groups = np.clip(-(-np.asarray(lengths) // rows), 0, num_groups)
    return int(groups.sum()) * rows


@functools.partial(
    jax.jit, static_argnames=("pages_per_block", "interpret", "scale"))
def paged_decode_attention(
    q: jax.Array,        # [B, H, D]
    k_pool: jax.Array,   # [NB, bs, KV, Dc]
    v_pool: jax.Array,
    table: jax.Array,    # [B, MB] int32
    lengths: jax.Array,  # [B] int32
    *,
    k_scale: Optional[jax.Array] = None,   # [NB, bs, KV] (quant pools)
    v_scale: Optional[jax.Array] = None,
    pages_per_block: int = 8,
    interpret: bool = False,
    scale: Optional[float] = None,     # None: head_dim ** -0.5
) -> jax.Array:
    b, h, d = q.shape
    nb, bs, kv, dc = k_pool.shape
    quant = k_scale is not None
    packed = quant and dc != d
    if packed:
        assert dc * 2 == d, (q.shape, k_pool.shape)
        if not interpret:
            raise NotImplementedError(INT4_REFUSAL)
    else:
        assert dc == d, (q.shape, k_pool.shape)
    assert h % kv == 0, (h, kv)
    g = h // kv
    mb = table.shape[1]
    # pad the table to a multiple of the page-group size with zeros —
    # the trash block, whose junk the length mask discards
    p_n, num_groups = _page_groups(mb, pages_per_block)
    pad = num_groups * p_n - mb
    if pad:
        table = jnp.concatenate(
            [table, jnp.zeros((b, pad), table.dtype)], axis=1)
    qg = q.reshape(b, kv, g, d)

    def q_map(bi, table_ref, lengths_ref):
        return (bi, 0, 0, 0)

    kernel = functools.partial(
        _decode_kernel, block_size=bs, pages=p_n,
        num_groups=num_groups, kv_heads=kv, group=g, head_dim=d,
        quant=quant, packed=packed, scale=scale,
    )
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, kv, g, d), q_map), any_spec, any_spec]
    operands = [qg, k_pool, v_pool]
    scratch = [
        pltpu.VMEM((2, p_n, bs, kv, dc), k_pool.dtype),
        pltpu.VMEM((2, p_n, bs, kv, dc), v_pool.dtype),
    ]
    if quant:
        # The scale pools' minor dimension is the KV-head count, which
        # no TPU tiling accepts as a DMA slice (and which HBM pads to
        # 128 lanes), so the kernel never touches them in place.  The
        # wrapper gathers this batch's scales — 2/D of the code bytes —
        # into a lane-dense [B, groups, KV, rows] f32 view whose rows
        # line up with the score tile's key axis; the codes, which are
        # the traffic that matters, still stream in place.
        def rows_on_lanes(scale_pool):
            s = jnp.take(scale_pool, table, axis=0)   # [B, MBp, bs, KV]
            s = s.reshape(b, num_groups, p_n * bs, kv)
            return s.transpose(0, 1, 3, 2).astype(jnp.float32)

        s_spec = pl.BlockSpec((1, num_groups, kv, p_n * bs), q_map)
        in_specs += [s_spec, s_spec]
        operands += [rows_on_lanes(k_scale), rows_on_lanes(v_scale)]
    scratch += [
        pltpu.VMEM((kv * g,), jnp.float32),
        pltpu.VMEM((kv * g,), jnp.float32),
        pltpu.VMEM((kv * g, d), jnp.float32),
        pltpu.SemaphoreType.DMA((2, p_n, 2)),
    ]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, kv, g, d), q_map),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, d), jnp.float32),
        interpret=interpret,
        # the name of the kernel's instruction in a device trace
        # (``paged_decode_attention.<n>``), whatever wraps the call
        name="paged_decode_attention",
    )(table.astype(jnp.int32), lengths.astype(jnp.int32), *operands)
    return out.reshape(b, h, d)


# ----------------------------------------------------- the XLA twin
@functools.partial(jax.jit, static_argnames=())
def gather_reference(
    q: jax.Array,        # [B, H, D]
    k_pool: jax.Array,   # [NB, bs, KV, Dc]
    v_pool: jax.Array,
    table: jax.Array,    # [B, MB]
    lengths: jax.Array,  # [B]
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """The fused-gather path the engine's ``attention_impl="xla"``
    runs, as a standalone function: materialize the dense (dequantized)
    per-slot view, then masked GQA attention — both the parity oracle
    for the kernel and the ``"xla"`` side of the auto-pick
    measurement.  Mirrors ``serving/model.py`` exactly: ``gather_blocks
    [_q|_q4]`` then the unexpanded-cache einsum pair."""
    from dlrover_tpu.serving.paged import (
        gather_blocks,
        gather_blocks_q,
        gather_blocks_q4,
    )

    b, h, d = q.shape
    kv = k_pool.shape[2]
    g = h // kv
    if k_scale is None:
        ck = gather_blocks(k_pool, table).astype(jnp.float32)
        cv = gather_blocks(v_pool, table).astype(jnp.float32)
    elif k_pool.shape[-1] != d:
        ck = gather_blocks_q4(k_pool, k_scale, table, jnp.float32)
        cv = gather_blocks_q4(v_pool, v_scale, table, jnp.float32)
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
    from dlrover_tpu.models.quantize import (
        quantize_kv_int4,
        quantize_kv_int8,
    )

    if kv_dtype == "int4" and not interpret:
        return {"kv_dtype": "int4", "refused": INT4_REFUSAL}
    b, mb = slots, max_blocks
    nb = b * mb + 1
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (nb, block_size, num_kv_heads, head_dim)
    q = (0.3 * jax.random.normal(kq, (b, num_heads, head_dim))).astype(dtype)
    k = (0.3 * jax.random.normal(kk, shape)).astype(dtype)
    v = (0.3 * jax.random.normal(kv_, shape)).astype(dtype)
    scales = {}
    if kv_dtype in ("int8", "int4"):
        quant = quantize_kv_int4 if kv_dtype == "int4" else quantize_kv_int8
        k, scales["k_scale"] = quant(k)
        v, scales["v_scale"] = quant(v)
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
