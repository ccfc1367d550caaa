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
    m, l, acc scratch carried across kv blocks; causal blocks fully above
    the diagonal are skipped with @pl.when.  LSE is written for backward.
Backward: FlashAttention-2 style — a precomputed delta = rowsum(do * o),
    one kernel accumulating dq over kv blocks, one accumulating (dk, dv)
    over q blocks.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Measured on v5e (470M-class Llama, bf16, head_dim 128): 1024x1024
# blocks are best in the FULL training step (0.70 MFU at seq 4096).
# Note: an isolated fwd+bwd kernel microbenchmark prefers 512-wide q
# tiles by ~16%, but the full model with remat regresses to 0.69 MFU
# with them — tune against the end-to-end step, not the kernel alone.
# 2048-wide blocks exceed the 16MB scoped-VMEM limit; _fwd/_bwd clamp
# blocks to the sequence length.
# 1024x1024: the r3 end-to-end sweep measured 2048x2048 ~0.8% faster on
# the fwd-dominant probe, but its BACKWARD kernel exceeds the 16M scoped
# VMEM limit in full bench compiles (22.5M stack) — 1024 is the largest
# robust block.
# Overridable for end-to-end sweeps (and per-deployment tuning) without
# code edits; the values above remain the measured defaults.
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


def _block_mask(
    q_pos: jax.Array,
    k_pos: jax.Array,
    causal: bool,
    q_seg: Optional[jax.Array],
    k_seg: Optional[jax.Array],
) -> Optional[jax.Array]:
    """[BQ, BK] boolean mask (True = attend) or None when unmasked."""
    mask = None
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]
    if q_seg is not None:
        seg = q_seg[:, None] == k_seg[None, :]
        mask = seg if mask is None else jnp.logical_and(mask, seg)
    return mask


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, qseg_ref, kseg_ref,
    o_ref, lse_ref,
    m_scr, l_scr, acc_scr,
    *, causal: bool, scale: float, block_q: int, block_k: int,
    seq_offset: int, have_segs: bool,
):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Global positions of this block's rows/cols.  seq_offset shifts query
    # positions (queries are the tail of the kv sequence when sq < skv).
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q,), 0) + seq_offset
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_k,), 0)

    # Causal: skip blocks entirely above the diagonal.
    run = True
    if causal:
        run = (iq * block_q + seq_offset) + block_q - 1 >= ik * block_k

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        q_seg = qseg_ref[0, 0] if have_segs else None
        k_seg = kseg_ref[0, 0] if have_segs else None
        mask = _block_mask(q_pos, k_pos, causal, q_seg, k_seg)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        if mask is not None:
            # For a fully-masked row m_new stays at -inf and exp(s - m_new)
            # would be 1 at masked entries; force them to 0.
            p = jnp.where(mask, p, 0.0)
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=1)
        m_scr[:] = m_new
        v = v_ref[0, 0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[:] = acc_scr[:] * corr[:, None] + pv

    @pl.when(ik == nk - 1)
    def _final():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, 0] = m_scr[:] + jnp.log(l_safe)


def _fwd(
    q, k, v, q_seg, k_seg, *, causal, scale, block_q, block_k, interpret
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
        seq_offset=seq_offset, have_segs=have_segs,
    )
    grid = (b, h, nq, nk)
    out_shape = [
        jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda ib, ih, iq, ik: (ib, ih // reps, ik, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d), lambda ib, ih, iq, ik: (ib, ih // reps, ik, 0)
            ),
            pl.BlockSpec((1, 1, block_q), lambda ib, ih, iq, ik: (ib, 0, iq)),
            pl.BlockSpec((1, 1, block_k), lambda ib, ih, iq, ik: (ib, 0, ik)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, 1, block_q), lambda ib, ih, iq, ik: (ib, ih, 0, iq)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
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
    dq_scr,
    *, causal, scale, block_q, block_k, seq_offset, have_segs,
):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q,), 0) + seq_offset
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_k,), 0)
    run = True
    if causal:
        run = (iq * block_q + seq_offset) + block_q - 1 >= ik * block_k

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0]
        delta = delta_ref[0, 0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        q_seg = qseg_ref[0, 0] if have_segs else None
        k_seg = kseg_ref[0, 0] if have_segs else None
        mask = _block_mask(q_pos, k_pos, causal, q_seg, k_seg)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)  # fully-masked rows have lse=-inf
        dov = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dov - delta[:, None])
        dq_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ik == nk - 1)
    def _final():
        dq_ref[0, 0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, qseg_ref, kseg_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, causal, scale, block_q, block_k, seq_offset, have_segs, reps,
):
    # Grid is (batch, kv_head, kv_block, q_block * reps): the innermost dim
    # folds the q-blocks of every query head sharing this kv head, so dk/dv
    # accumulate in scratch across the whole GQA group (no HBM revisits).
    ik, j = pl.program_id(2), pl.program_id(3)
    nj = pl.num_programs(3)
    iq = j // reps

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q,), 0) + seq_offset
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_k,), 0)
    run = True
    if causal:
        run = (iq * block_q + seq_offset) + block_q - 1 >= ik * block_k

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0]
        delta = delta_ref[0, 0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        q_seg = qseg_ref[0, 0] if have_segs else None
        k_seg = kseg_ref[0, 0] if have_segs else None
        mask = _block_mask(q_pos, k_pos, causal, q_seg, k_seg)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)  # fully-masked rows have lse=-inf
        # dv += p^T @ do
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dov = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dov - delta[:, None])
        # dk += ds^T @ q  (q already carries `scale`)
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == nj - 1)
    def _final():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(
    res, g, *, causal, scale, block_q, block_k, interpret
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

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(b, h, nq, nk),
        in_specs=[
            qkv_spec(block_q, "outer"),       # q
            pl.BlockSpec(
                (1, 1, block_k, d), lambda ib, ih, i, j: (ib, ih // reps, j, 0)
            ),                                 # k
            pl.BlockSpec(
                (1, 1, block_k, d), lambda ib, ih, i, j: (ib, ih // reps, j, 0)
            ),                                 # v
            pl.BlockSpec((1, 1, block_q), lambda ib, ih, i, j: (ib, 0, i)),
            pl.BlockSpec((1, 1, block_k), lambda ib, ih, i, j: (ib, 0, j)),
            qkv_spec(block_q, "outer"),       # do
            pl.BlockSpec((1, 1, 1, block_q), lambda ib, ih, i, j: (ib, ih, 0, i)),
            pl.BlockSpec((1, 1, 1, block_q), lambda ib, ih, i, j: (ib, ih, 0, i)),
        ],
        out_specs=qkv_spec(block_q, "outer"),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v, q_seg, k_seg, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common, reps=reps),
        grid=(b, hkv, nk, nq * reps),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d),
                lambda ib, ih, i, j: (ib, ih * reps + j % reps, j // reps, 0),
            ),                                 # q
            qkv_spec(block_k, "outer"),       # k
            qkv_spec(block_k, "outer"),       # v
            pl.BlockSpec((1, 1, block_q), lambda ib, ih, i, j: (ib, 0, j // reps)),
            pl.BlockSpec((1, 1, block_k), lambda ib, ih, i, j: (ib, 0, i)),
            pl.BlockSpec(
                (1, 1, block_q, d),
                lambda ib, ih, i, j: (ib, ih * reps + j % reps, j // reps, 0),
            ),                                 # do
            pl.BlockSpec(
                (1, 1, 1, block_q),
                lambda ib, ih, i, j: (ib, ih * reps + j % reps, 0, j // reps),
            ),
            pl.BlockSpec(
                (1, 1, 1, block_q),
                lambda ib, ih, i, j: (ib, ih * reps + j % reps, 0, j // reps),
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
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9)
)
def _flash_bhsd(q, k, v, q_seg, k_seg, causal, scale, block_q, block_k, interpret):
    o, _ = _fwd(
        q, k, v, q_seg, k_seg,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return o


def _flash_fwd_rule(q, k, v, q_seg, k_seg, causal, scale, block_q, block_k, interpret):
    o, lse = _fwd(
        q, k, v, q_seg, k_seg,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return o, (q, k, v, q_seg, k_seg, o, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, interpret, res, g):
    dq, dk, dv = _bwd(
        res, g, causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret,
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
) -> jax.Array:
    """Flash attention on [batch, seq, heads, dim] inputs (GQA allowed).

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
        qt, kt, vt, q_seg, k_seg, causal, float(scale), bq, bk, interpret
    )
    return out.transpose(0, 2, 1, 3)
