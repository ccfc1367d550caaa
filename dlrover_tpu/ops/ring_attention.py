"""Ring (context-parallel) flash attention over the ``cp`` mesh axis.

Long-context training beyond one chip's HBM: the sequence is sharded into
contiguous chunks over ``cp``; each ring step every peer runs blockwise
flash attention of its local queries against the K/V chunk it currently
holds, merges the result into an online-softmax accumulator ``(o, lse)``,
and rotates K/V to its ring neighbour with ``jax.lax.ppermute`` (one ICI
hop).  HBM never holds more than two K/V chunks and attention compute per
chip is O(s^2 / cp) FLOPs.  For causal attention the default is the
*zigzag* chunk placement (section at the bottom of this file), which
keeps the critical path at O(s^2 / cp) too — the plain contiguous ring
would synchronize every ppermute step to its busiest peer, costing ~2x.

The reference framework has **no** ring/context parallelism — its sequence
parallelism is Ulysses all-to-all only (reference:
atorch/atorch/auto/opt_lib/sequence_parallel_optimization.py:9-51 and
distributed/distributed.py:474-501, confirmed by SURVEY.md §2.3) — so this
is a beyond-parity capability.  Design follows the ring-attention recipe
(blockwise parallel transformers) re-expressed TPU-natively:

- per-step block attention reuses the Pallas flash kernels
  (:mod:`dlrover_tpu.ops.pallas.flash_attention`): the diagonal chunk runs
  the causal kernel, strictly-past chunks run the non-causal kernel, and
  strictly-future chunks are skipped entirely via ``jax.lax.switch`` — so
  causal masking never wastes MXU time on masked blocks;
- chunk merging uses the normalized-output + LSE identity
  ``o = sum_i o_i * exp(lse_i - logsumexp_i lse_i)``;
- the backward pass runs the ring again: ``dq`` accumulates locally while
  ``(dk, dv)`` ride around the ring *with* their K/V chunk and are home
  after ``cp`` rotations.

Composes with Ulysses ``sp`` inside one shard_map (2D sequence parallel):
the seq axis is sharded cp-major / sp-minor (mesh rule ``("cp", "sp")``),
so the sp all-to-all reassembles a contiguous cp chunk before the ring.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# per-chunk block attention returning (normalized output, LSE)
# ---------------------------------------------------------------------------


def _xla_chunk_fwd(q, k, v, q_seg, k_seg, *, causal: bool, scale: float):
    """Chunk attention in plain XLA; [b, h, s, d] layout, f32 compute.

    Matches the Pallas kernel contract: normalized output in ``q.dtype``
    plus ``lse = m + log(l)`` of shape [b, h, 1, sq]; fully-masked rows get
    ``o = 0`` and ``lse = -1e30``.
    """
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    reps = h // hkv
    qf = (q.astype(jnp.float32) * scale).reshape(b, hkv, reps, sq, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kf)
    mask = None
    if causal:
        mask = jnp.arange(sq)[:, None] >= jnp.arange(skv)[None, :]
        mask = mask[None, None, None]
    if q_seg is not None:
        seg = (q_seg[:, 0, :, None] == k_seg[:, 0, None, :])[:, None, None]
        mask = seg if mask is None else jnp.logical_and(mask, seg)
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, vf) / l_safe[..., None]
    lse = m + jnp.log(l_safe)
    return (
        o.reshape(b, h, sq, d).astype(q.dtype),
        lse.reshape(b, h, 1, sq),
    )


def _xla_chunk_bwd(
    q, k, v, q_seg, k_seg, lse, do, delta, *, causal: bool, scale: float
):
    """Chunk backward in plain XLA given the *global* lse/delta.

    Same math as the Pallas ``_dq_kernel``/``_dkv_kernel``
    (flash_attention.py): ``p = exp(s - lse)``, ``ds = p (do.v - delta)``,
    ``dq = scale * ds.k``, ``dk = scale * ds^T.q``, ``dv = p^T.do``.
    """
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    reps = h // hkv
    qf = (q.astype(jnp.float32) * scale).reshape(b, hkv, reps, sq, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    dof = do.astype(jnp.float32).reshape(b, hkv, reps, sq, d)
    lse_g = lse.reshape(b, hkv, reps, sq)
    delta_g = delta.reshape(b, hkv, reps, sq)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kf)
    mask = None
    if causal:
        mask = (jnp.arange(sq)[:, None] >= jnp.arange(skv)[None, :])[
            None, None, None
        ]
    if q_seg is not None:
        seg = (q_seg[:, 0, :, None] == k_seg[:, 0, None, :])[:, None, None]
        mask = seg if mask is None else jnp.logical_and(mask, seg)
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    p = jnp.exp(s - lse_g[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    dv = jnp.einsum("bhgqk,bhgqd->bhkd", p, dof)
    dov = jnp.einsum("bhgqd,bhkd->bhgqk", dof, vf)
    ds = p * (dov - delta_g[..., None])
    dq = jnp.einsum("bhgqk,bhkd->bhgqd", ds, kf) * scale
    # qf already carries `scale` (matches the Pallas kernels).
    dk = jnp.einsum("bhgqk,bhgqd->bhkd", ds, qf)
    return dq.reshape(b, h, sq, d), dk, dv


def _pallas_ok(sq: int, skv: int, d: int) -> bool:
    """Kernel tiling constraints for the per-chunk Pallas path."""
    if jax.default_backend() in ("cpu", "gpu"):
        return False
    return sq % 128 == 0 and skv % 128 == 0 and d % 128 == 0


# ---------------------------------------------------------------------------
# local ring (runs inside shard_map over the cp axis)
# ---------------------------------------------------------------------------


def _ring_perm(cp: int):
    # send to the previous peer => after t steps peer i holds chunk (i+t)%cp
    return [(j, (j - 1) % cp) for j in range(cp)]


def _rotate(xs, axis_name: str, cp: int):
    return jax.lax.ppermute(xs, axis_name, _ring_perm(cp))


def _merge_acc(acc, ob_lse):
    """Online-softmax accumulator merge shared by all ring variants:
    acc = (o f32, lse); new chunk result folds in via the normalized-
    output + LSE identity."""
    o_acc, lse_acc = acc
    o_b, lse_b = ob_lse
    lse_new = jnp.logaddexp(lse_acc, lse_b)
    # [b,h,1,sq] -> [b,h,sq,1] to broadcast over head_dim
    w_acc = jnp.exp(jnp.swapaxes(lse_acc - lse_new, 2, 3))
    w_b = jnp.exp(jnp.swapaxes(lse_b - lse_new, 2, 3))
    return o_acc * w_acc + o_b.astype(jnp.float32) * w_b, lse_new


def _block_size(seq: int) -> int:
    """Largest kernel block (<=1024, >=128) that divides the chunk."""
    for b in (1024, 512, 256, 128):
        if seq % b == 0:
            return b
    return seq


def _chunk_fwd(q, k, v, q_seg, k_seg, causal, scale, use_pallas, interpret):
    if use_pallas:
        from dlrover_tpu.ops.pallas.flash_attention import _fwd

        return _fwd(
            q, k, v, q_seg, k_seg,
            causal=causal, scale=scale,
            block_q=_block_size(q.shape[2]),
            block_k=_block_size(k.shape[2]),
            interpret=interpret,
        )
    return _xla_chunk_fwd(q, k, v, q_seg, k_seg, causal=causal, scale=scale)


def _chunk_bwd(
    q, k, v, q_seg, k_seg, o, lse, do, delta,
    causal, scale, use_pallas, interpret,
):
    if use_pallas:
        from dlrover_tpu.ops.pallas.flash_attention import _bwd

        return _bwd(
            (q, k, v, q_seg, k_seg, o, lse), do,
            causal=causal, scale=scale,
            block_q=_block_size(q.shape[2]),
            block_k=_block_size(k.shape[2]),
            interpret=interpret,
        )
    return _xla_chunk_bwd(
        q, k, v, q_seg, k_seg, lse, do, delta, causal=causal, scale=scale
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _ring_local(
    q, k, v, q_seg, k_seg, axis_name, cp, causal, scale, use_pallas, interpret
):
    o, _ = _ring_fwd(
        q, k, v, q_seg, k_seg, axis_name, cp, causal, scale, use_pallas,
        interpret,
    )
    return o


def _ring_fwd(
    q, k, v, q_seg, k_seg, axis_name, cp, causal, scale, use_pallas, interpret
):
    """Forward ring: returns (o [b,h,sq,d] in q.dtype, lse [b,h,1,sq] f32)."""
    b, h, sq, d = q.shape
    me = jax.lax.axis_index(axis_name)
    have_segs = q_seg is not None

    def block(kc, vc, ksegc, blk_causal):
        return _chunk_fwd(
            q, kc, vc, q_seg, ksegc, blk_causal, scale, use_pallas, interpret
        )

    def skip(kc, vc, ksegc):
        return (
            jnp.zeros((b, h, sq, d), q.dtype),
            jnp.full((b, h, 1, sq), _NEG_INF, jnp.float32),
        )

    def merge(t, o_acc, lse_acc, kc, vc, ksegc):
        ki = (me + t) % cp
        if causal:
            branch = jnp.where(ki == me, 1, jnp.where(ki < me, 2, 0))
            o_b, lse_b = jax.lax.switch(
                branch,
                [
                    skip,
                    lambda kc, vc, sc: block(kc, vc, sc, True),
                    lambda kc, vc, sc: block(kc, vc, sc, False),
                ],
                kc, vc, ksegc,
            )
        else:
            o_b, lse_b = block(kc, vc, ksegc, False)
        return _merge_acc((o_acc, lse_acc), (o_b, lse_b))

    def body(t, carry):
        o_acc, lse_acc, kc, vc, ksegc = carry
        o_acc, lse_acc = merge(t, o_acc, lse_acc, kc, vc, ksegc)
        rot = (kc, vc, ksegc) if have_segs else (kc, vc)
        rot = _rotate(rot, axis_name, cp)
        kc, vc = rot[0], rot[1]
        ksegc = rot[2] if have_segs else ksegc
        return o_acc, lse_acc, kc, vc, ksegc

    init = (
        jnp.zeros((b, h, sq, d), jnp.float32),
        jnp.full((b, h, 1, sq), _NEG_INF, jnp.float32),
        k,
        v,
        k_seg if have_segs else jnp.zeros((b, 1, k.shape[2]), jnp.int32),
    )
    # cp-1 compute+rotate steps, then the final chunk without the rotation
    # (its K/V would be discarded — one ICI hop saved per call).
    o_acc, lse, kc, vc, ksegc = jax.lax.fori_loop(0, cp - 1, body, init)
    o_acc, lse = merge(cp - 1, o_acc, lse, kc, vc, ksegc)
    return o_acc.astype(q.dtype), lse


def _ring_fwd_rule(
    q, k, v, q_seg, k_seg, axis_name, cp, causal, scale, use_pallas, interpret
):
    o, lse = _ring_fwd(
        q, k, v, q_seg, k_seg, axis_name, cp, causal, scale, use_pallas,
        interpret,
    )
    return o, (q, k, v, q_seg, k_seg, o, lse)


def _ring_bwd_rule(
    axis_name, cp, causal, scale, use_pallas, interpret, res, g
):
    q, k, v, q_seg, k_seg, o, lse = res
    do = g
    b, h, sq, d = q.shape
    me = jax.lax.axis_index(axis_name)
    have_segs = q_seg is not None
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )[:, :, None, :]

    def block(kc, vc, ksegc, blk_causal):
        dq_b, dk_b, dv_b = _chunk_bwd(
            q, kc, vc, q_seg, ksegc, o, lse, do, delta,
            blk_causal, scale, use_pallas, interpret,
        )
        return (
            dq_b.astype(jnp.float32),
            dk_b.astype(jnp.float32),
            dv_b.astype(jnp.float32),
        )

    def skip(kc, vc, ksegc):
        return (
            jnp.zeros((b, h, sq, d), jnp.float32),
            jnp.zeros(kc.shape, jnp.float32),
            jnp.zeros(vc.shape, jnp.float32),
        )

    def accum(t, dq_acc, kc, vc, ksegc, dk_acc, dv_acc):
        ki = (me + t) % cp
        if causal:
            branch = jnp.where(ki == me, 1, jnp.where(ki < me, 2, 0))
            dq_b, dk_b, dv_b = jax.lax.switch(
                branch,
                [
                    skip,
                    lambda kc, vc, sc: block(kc, vc, sc, True),
                    lambda kc, vc, sc: block(kc, vc, sc, False),
                ],
                kc, vc, ksegc,
            )
        else:
            dq_b, dk_b, dv_b = block(kc, vc, ksegc, False)
        return dq_acc + dq_b, dk_acc + dk_b, dv_acc + dv_b

    def body(t, carry):
        dq_acc, kc, vc, ksegc, dk_acc, dv_acc = carry
        dq_acc, dk_acc, dv_acc = accum(t, dq_acc, kc, vc, ksegc, dk_acc, dv_acc)
        # (dk, dv) travel WITH their chunk; after cp rotations they're home.
        rot = (kc, vc, dk_acc, dv_acc, ksegc) if have_segs else (
            kc, vc, dk_acc, dv_acc
        )
        rot = _rotate(rot, axis_name, cp)
        kc, vc, dk_acc, dv_acc = rot[0], rot[1], rot[2], rot[3]
        ksegc = rot[4] if have_segs else ksegc
        return dq_acc, kc, vc, ksegc, dk_acc, dv_acc

    init = (
        jnp.zeros((b, h, sq, d), jnp.float32),
        k,
        v,
        k_seg if have_segs else jnp.zeros((b, 1, k.shape[2]), jnp.int32),
        jnp.zeros(k.shape, jnp.float32),
        jnp.zeros(v.shape, jnp.float32),
    )
    # cp-1 full steps; the final step computes, then rotates ONLY dk/dv
    # (one more hop homes them; the K/V copies would be discarded).
    dq, kc, vc, ksegc, dk, dv = jax.lax.fori_loop(0, cp - 1, body, init)
    dq, dk, dv = accum(cp - 1, dq, kc, vc, ksegc, dk, dv)
    dk, dv = _rotate((dk, dv), axis_name, cp)
    return (
        dq.astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
        None,
        None,
    )


_ring_local.defvjp(_ring_fwd_rule, _ring_bwd_rule)


# ---------------------------------------------------------------------------
# public API: global arrays, shard_map over (cp [, sp]) from the mesh rules
# ---------------------------------------------------------------------------


def _cp_applicable(q, k, mesh, rules=None) -> bool:
    """Seq must be cp-sharded by the active rules; when sp > 1 the Ulysses
    head split must also hold (heads divide by sp after tp sharding)."""
    from dlrover_tpu.ops.attention import (
        _attention_specs,
        _heads_split_over_sp,
        _spec_uses,
    )

    cp = mesh.shape.get("cp", 1)
    sp = mesh.shape.get("sp", 1)
    q_spec, kv_spec, _ = _attention_specs(mesh, rules)
    if not (_spec_uses(q_spec[1], "cp") and _spec_uses(kv_spec[1], "cp")):
        return False
    if q.shape[1] % (cp * sp) or k.shape[1] % (cp * sp):
        return False
    if sp > 1:
        if not (_spec_uses(q_spec[1], "sp") and _spec_uses(kv_spec[1], "sp")):
            return False
        if not _heads_split_over_sp(q, k, mesh, q_spec, kv_spec):
            return False
    return True


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    use_pallas: Optional[bool] = None,
    rules=None,
    interpret: bool = False,
    zigzag: Optional[bool] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Context-parallel attention on *global* [b, s, h, d] arrays.

    shard_maps over the mesh: when ``sp > 1`` the Ulysses all-to-all first
    trades the sp-sub-chunks for a head slice (2D sequence parallelism),
    then the ring runs over ``cp``.  Output is partitioned like ``q``.

    ``zigzag`` (default: auto for causal) uses the balanced zigzag chunk
    placement — see the module section below.
    """
    from dlrover_tpu.ops.attention import (
        _attention_specs,
        heads_to_seq_all_to_all,
        seq_to_heads_all_to_all,
    )

    if window is not None:
        raise NotImplementedError(
            f"ring attention has no window (window={window}): a layer "
            "with one must not run under a cp axis")
    cp = mesh.shape.get("cp", 1)
    sp = mesh.shape.get("sp", 1)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    q_spec, kv_spec, seg_spec = _attention_specs(mesh, rules)
    chunk = q.shape[1] // cp  # local seq after the sp gather
    # zigzag balances the causal ring (every peer computes two half-chunk
    # pairs per step instead of 0..cp); needs even half-chunks
    # auto (None) and explicit True both require causal + even halves
    zigzag = (zigzag is not False) and causal and cp > 1 and chunk % 2 == 0
    if zigzag and use_pallas and (
        (chunk // 2) % 128 != 0 and not interpret
    ):
        # explicit Pallas request but the zigzag halves break the
        # kernel's 128-divisibility contract: keep the contiguous ring
        # (full chunks) that the caller's request was validated against
        zigzag = False
    if use_pallas is None:
        half = chunk // 2 if zigzag else chunk
        resolved_pallas = _pallas_ok(half, half, q.shape[-1])
    else:
        resolved_pallas = bool(use_pallas)

    have_segs = segment_ids is not None

    def inner(q, k, v, seg):
        if sp > 1:
            q = seq_to_heads_all_to_all(q)
            k = seq_to_heads_all_to_all(k)
            v = seq_to_heads_all_to_all(v)
            if seg is not None:
                seg = jax.lax.all_gather(seg, "sp", axis=1, tiled=True)
        # kernel layout [b, heads, seq, d]
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        sg = seg[:, None, :].astype(jnp.int32) if seg is not None else None
        if zigzag:
            q_lo, q_hi = _zigzag_shuffle(qt, "cp", cp, axis=2)
            k_lo, k_hi = _zigzag_shuffle(kt, "cp", cp, axis=2)
            v_lo, v_hi = _zigzag_shuffle(vt, "cp", cp, axis=2)
            if sg is not None:
                sg_lo, sg_hi = _zigzag_shuffle(sg, "cp", cp, axis=2)
            else:
                sg_lo = sg_hi = None
            o_lo, o_hi = _ring_local_zigzag(
                q_lo, q_hi, k_lo, k_hi, v_lo, v_hi,
                sg_lo, sg_hi, sg_lo, sg_hi,
                "cp", cp, float(scale), resolved_pallas, interpret,
            )
            o = _zigzag_unshuffle(o_lo, o_hi, "cp", cp, axis=2)
        else:
            o = _ring_local(
                qt, kt, vt, sg, sg,
                "cp", cp, causal, float(scale), resolved_pallas, interpret,
            )
        o = o.transpose(0, 2, 1, 3)
        if sp > 1:
            o = heads_to_seq_all_to_all(o)
        return o

    if not have_segs:
        sm = jax.shard_map(
            lambda q, k, v: inner(q, k, v, None),
            mesh=mesh,
            in_specs=(q_spec, kv_spec, kv_spec),
            out_specs=q_spec,
            check_vma=False,
        )
        return sm(q, k, v)
    sm = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, seg_spec),
        out_specs=q_spec,
        check_vma=False,
    )
    return sm(q, k, v, segment_ids)


# ---------------------------------------------------------------------------
# zigzag chunk placement: balanced causal ring
# ---------------------------------------------------------------------------
#
# Plain contiguous chunks make the causal ring unbalanced: peer 0 attends
# 1 chunk, peer cp-1 attends cp, and the per-step ppermute synchronizes
# everyone to the busiest peer (~2x the balanced critical path).  Zigzag
# placement pairs head and tail half-chunks — peer p holds global half-
# chunks {p, 2cp-1-p} — so EVERY peer computes exactly two half-chunk
# block pairs per ring step: (q_lo x k_lo or q_hi x k_hi, whichever is
# past/diagonal) plus the always-past (q_hi x k_lo).  Entry/exit is two
# ppermutes each way (half-chunk exchange), amortized over the whole
# attention computation.


def _zz(h: int, cp: int) -> int:
    """Zigzag owner of global half-chunk ``h``."""
    return h if h < cp else 2 * cp - 1 - h


def _zigzag_tables(cp: int):
    """Static permutations and selection tables for the boundary shuffles."""
    perm_a = [(c, _zz(2 * c, cp)) for c in range(cp)]       # lo half out
    perm_b = [(c, _zz(2 * c + 1, cp)) for c in range(cp)]   # hi half out
    dest_a = {c: _zz(2 * c, cp) for c in range(cp)}
    dest_b = {c: _zz(2 * c + 1, cp) for c in range(cp)}
    inv_a = {v: k for k, v in dest_a.items()}
    inv_b = {v: k for k, v in dest_b.items()}
    # after the forward shuffle: is peer p's A-received half its LOW id?
    a_is_lo = [2 * inv_a[p] == p for p in range(cp)]
    # inverse shuffle: does peer q send its z-LOW half on the invA hop?
    send_lo_inv_a = [2 * inv_a[q] == q for q in range(cp)]
    inv_perm_a = [(dest_a[c], c) for c in range(cp)]
    inv_perm_b = [(dest_b[c], c) for c in range(cp)]
    return perm_a, perm_b, inv_perm_a, inv_perm_b, a_is_lo, send_lo_inv_a


def _take_flag(table, axis_name):
    idx = jax.lax.axis_index(axis_name)
    return jnp.take(jnp.asarray(table, jnp.bool_), idx)


def _zigzag_shuffle(x, axis_name: str, cp: int, axis: int):
    """Contiguous local chunk -> (lo, hi) zigzag half-chunks."""
    perm_a, perm_b, _, _, a_is_lo, _ = _zigzag_tables(cp)
    lo, hi = jnp.split(x, 2, axis=axis)
    ra = jax.lax.ppermute(lo, axis_name, perm_a)
    rb = jax.lax.ppermute(hi, axis_name, perm_b)
    flag = _take_flag(a_is_lo, axis_name)
    return jnp.where(flag, ra, rb), jnp.where(flag, rb, ra)


def _zigzag_unshuffle(lo_z, hi_z, axis_name: str, cp: int, axis: int):
    """(lo, hi) zigzag half-chunks -> contiguous local chunk."""
    _, _, inv_perm_a, inv_perm_b, _, send_lo_inv_a = _zigzag_tables(cp)
    flag = _take_flag(send_lo_inv_a, axis_name)
    send_a = jnp.where(flag, lo_z, hi_z)
    send_b = jnp.where(flag, hi_z, lo_z)
    ra = jax.lax.ppermute(send_a, axis_name, inv_perm_a)  # the 2c half
    rb = jax.lax.ppermute(send_b, axis_name, inv_perm_b)  # the 2c+1 half
    return jnp.concatenate([ra, rb], axis=axis)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(10, 11, 12, 13, 14)
)
def _ring_local_zigzag(
    q_lo, q_hi, k_lo, k_hi, v_lo, v_hi,
    qseg_lo, qseg_hi, kseg_lo, kseg_hi,
    axis_name, cp, scale, use_pallas, interpret,
):
    (o_lo, o_hi), _ = _ring_zigzag_fwd(
        q_lo, q_hi, k_lo, k_hi, v_lo, v_hi,
        qseg_lo, qseg_hi, kseg_lo, kseg_hi,
        axis_name, cp, scale, use_pallas, interpret,
    )
    return o_lo, o_hi


def _zz_cases(me, src, which):
    """Branch index for a (q, k) half pair: 0 skip / 1 diag / 2 full."""
    if which == "ll":   # q id me vs k id src
        return jnp.where(src == me, 1, jnp.where(src < me, 2, 0))
    if which == "hh":   # q id 2cp-1-me vs k id 2cp-1-src
        return jnp.where(src == me, 1, jnp.where(src > me, 2, 0))
    raise AssertionError(which)


def _ring_zigzag_fwd(
    q_lo, q_hi, k_lo, k_hi, v_lo, v_hi,
    qseg_lo, qseg_hi, kseg_lo, kseg_hi,
    axis_name, cp, scale, use_pallas, interpret,
):
    b, h, s2, d = q_lo.shape
    me = jax.lax.axis_index(axis_name)
    have_segs = qseg_lo is not None

    def block(q, qseg, kc, vc, ksegc, blk_causal):
        return _chunk_fwd(
            q, kc, vc, qseg, ksegc, blk_causal, scale, use_pallas, interpret
        )

    def pair(q, qseg, case, kc, vc, ksegc):
        def skip(kc, vc, sc):
            return (
                jnp.zeros((b, h, s2, d), q.dtype),
                jnp.full((b, h, 1, s2), _NEG_INF, jnp.float32),
            )

        return jax.lax.switch(
            case,
            [
                skip,
                lambda kc, vc, sc: block(q, qseg, kc, vc, sc, True),
                lambda kc, vc, sc: block(q, qseg, kc, vc, sc, False),
            ],
            kc, vc, ksegc,
        )

    merge = _merge_acc

    def step(t, lo_acc, hi_acc, kl, kh, vl, vh, sl, sh):
        src = (me + t) % cp
        lo_acc = merge(
            lo_acc, pair(q_lo, qseg_lo, _zz_cases(me, src, "ll"), kl, vl, sl)
        )
        # q_hi x k_lo: the high half is always past every low half
        hi_acc = merge(
            hi_acc, block(q_hi, qseg_hi, kl, vl, sl, False)
        )
        hi_acc = merge(
            hi_acc, pair(q_hi, qseg_hi, _zz_cases(me, src, "hh"), kh, vh, sh)
        )
        return lo_acc, hi_acc

    def body(t, carry):
        lo_acc, hi_acc, kl, kh, vl, vh, sl, sh = carry
        lo_acc, hi_acc = step(t, lo_acc, hi_acc, kl, kh, vl, vh, sl, sh)
        rot = (kl, kh, vl, vh) + ((sl, sh) if have_segs else ())
        rot = _rotate(rot, axis_name, cp)
        kl, kh, vl, vh = rot[0], rot[1], rot[2], rot[3]
        if have_segs:
            sl, sh = rot[4], rot[5]
        return lo_acc, hi_acc, kl, kh, vl, vh, sl, sh

    def zero_acc():
        return (
            jnp.zeros((b, h, s2, d), jnp.float32),
            jnp.full((b, h, 1, s2), _NEG_INF, jnp.float32),
        )

    dummy = jnp.zeros((b, 1, s2), jnp.int32)
    init = (
        zero_acc(), zero_acc(), k_lo, k_hi, v_lo, v_hi,
        kseg_lo if have_segs else dummy,
        kseg_hi if have_segs else dummy,
    )
    lo_acc, hi_acc, kl, kh, vl, vh, sl, sh = jax.lax.fori_loop(
        0, cp - 1, body, init
    )
    lo_acc, hi_acc = step(cp - 1, lo_acc, hi_acc, kl, kh, vl, vh, sl, sh)
    (o_lo, lse_lo), (o_hi, lse_hi) = lo_acc, hi_acc
    outs = (o_lo.astype(q_lo.dtype), o_hi.astype(q_hi.dtype))
    return outs, (lse_lo, lse_hi)


def _ring_zigzag_fwd_rule(
    q_lo, q_hi, k_lo, k_hi, v_lo, v_hi,
    qseg_lo, qseg_hi, kseg_lo, kseg_hi,
    axis_name, cp, scale, use_pallas, interpret,
):
    (o_lo, o_hi), (lse_lo, lse_hi) = _ring_zigzag_fwd(
        q_lo, q_hi, k_lo, k_hi, v_lo, v_hi,
        qseg_lo, qseg_hi, kseg_lo, kseg_hi,
        axis_name, cp, scale, use_pallas, interpret,
    )
    res = (
        q_lo, q_hi, k_lo, k_hi, v_lo, v_hi,
        qseg_lo, qseg_hi, kseg_lo, kseg_hi,
        o_lo, o_hi, lse_lo, lse_hi,
    )
    return (o_lo, o_hi), res


def _ring_zigzag_bwd_rule(axis_name, cp, scale, use_pallas, interpret, res, g):
    (
        q_lo, q_hi, k_lo, k_hi, v_lo, v_hi,
        qseg_lo, qseg_hi, kseg_lo, kseg_hi,
        o_lo, o_hi, lse_lo, lse_hi,
    ) = res
    do_lo, do_hi = g
    b, h, s2, d = q_lo.shape
    me = jax.lax.axis_index(axis_name)
    have_segs = qseg_lo is not None
    delta_lo = jnp.sum(
        do_lo.astype(jnp.float32) * o_lo.astype(jnp.float32), axis=-1
    )[:, :, None, :]
    delta_hi = jnp.sum(
        do_hi.astype(jnp.float32) * o_hi.astype(jnp.float32), axis=-1
    )[:, :, None, :]

    def block(q, qseg, o, lse, do, delta, kc, vc, ksegc, blk_causal):
        dq_b, dk_b, dv_b = _chunk_bwd(
            q, kc, vc, qseg, ksegc, o, lse, do, delta,
            blk_causal, scale, use_pallas, interpret,
        )
        return (
            dq_b.astype(jnp.float32),
            dk_b.astype(jnp.float32),
            dv_b.astype(jnp.float32),
        )

    def pair(q, qseg, o, lse, do, delta, case, kc, vc, ksegc):
        def skip(kc, vc, sc):
            return (
                jnp.zeros((b, h, s2, d), jnp.float32),
                jnp.zeros(kc.shape, jnp.float32),
                jnp.zeros(vc.shape, jnp.float32),
            )

        return jax.lax.switch(
            case,
            [
                skip,
                lambda kc, vc, sc: block(q, qseg, o, lse, do, delta,
                                         kc, vc, sc, True),
                lambda kc, vc, sc: block(q, qseg, o, lse, do, delta,
                                         kc, vc, sc, False),
            ],
            kc, vc, ksegc,
        )

    def accum(t, dq_lo, dq_hi, kl, kh, vl, vh, sl, sh, dkl, dkh, dvl, dvh):
        src = (me + t) % cp
        a, bk, bv = pair(q_lo, qseg_lo, o_lo, lse_lo, do_lo, delta_lo,
                         _zz_cases(me, src, "ll"), kl, vl, sl)
        dq_lo = dq_lo + a
        dkl = dkl + bk
        dvl = dvl + bv
        a, bk, bv = block(q_hi, qseg_hi, o_hi, lse_hi, do_hi, delta_hi,
                          kl, vl, sl, False)
        dq_hi = dq_hi + a
        dkl = dkl + bk
        dvl = dvl + bv
        a, bk, bv = pair(q_hi, qseg_hi, o_hi, lse_hi, do_hi, delta_hi,
                         _zz_cases(me, src, "hh"), kh, vh, sh)
        dq_hi = dq_hi + a
        dkh = dkh + bk
        dvh = dvh + bv
        return dq_lo, dq_hi, dkl, dkh, dvl, dvh

    def body(t, carry):
        (dq_lo, dq_hi, kl, kh, vl, vh, sl, sh,
         dkl, dkh, dvl, dvh) = carry
        dq_lo, dq_hi, dkl, dkh, dvl, dvh = accum(
            t, dq_lo, dq_hi, kl, kh, vl, vh, sl, sh, dkl, dkh, dvl, dvh
        )
        rot = (kl, kh, vl, vh, dkl, dkh, dvl, dvh) + (
            (sl, sh) if have_segs else ()
        )
        rot = _rotate(rot, axis_name, cp)
        kl, kh, vl, vh, dkl, dkh, dvl, dvh = rot[:8]
        if have_segs:
            sl, sh = rot[8], rot[9]
        return (dq_lo, dq_hi, kl, kh, vl, vh, sl, sh, dkl, dkh, dvl, dvh)

    dummy = jnp.zeros((b, 1, s2), jnp.int32)
    zq = jnp.zeros((b, h, s2, d), jnp.float32)
    init = (
        zq, zq, k_lo, k_hi, v_lo, v_hi,
        kseg_lo if have_segs else dummy,
        kseg_hi if have_segs else dummy,
        jnp.zeros(k_lo.shape, jnp.float32),
        jnp.zeros(k_hi.shape, jnp.float32),
        jnp.zeros(v_lo.shape, jnp.float32),
        jnp.zeros(v_hi.shape, jnp.float32),
    )
    carry = jax.lax.fori_loop(0, cp - 1, body, init)
    (dq_lo, dq_hi, kl, kh, vl, vh, sl, sh, dkl, dkh, dvl, dvh) = carry
    dq_lo, dq_hi, dkl, dkh, dvl, dvh = accum(
        cp - 1, dq_lo, dq_hi, kl, kh, vl, vh, sl, sh, dkl, dkh, dvl, dvh
    )
    # final hop homes the travelling dk/dv halves
    dkl, dkh, dvl, dvh = _rotate((dkl, dkh, dvl, dvh), axis_name, cp)
    return (
        dq_lo.astype(q_lo.dtype),
        dq_hi.astype(q_hi.dtype),
        dkl.astype(k_lo.dtype),
        dkh.astype(k_hi.dtype),
        dvl.astype(v_lo.dtype),
        dvh.astype(v_hi.dtype),
        None, None, None, None,
    )


_ring_local_zigzag.defvjp(_ring_zigzag_fwd_rule, _ring_zigzag_bwd_rule)
