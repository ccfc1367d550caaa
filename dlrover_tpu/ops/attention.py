"""Attention ops with backend dispatch, plus Ulysses sequence-parallel
all-to-all.

Parity targets in the reference:
- FlashAttention-2 module integrations (reference:
  atorch/atorch/modules/transformer/layers.py:1278 ``FlashAttnModule``) —
  here the fast path is a Pallas TPU flash-attention kernel
  (:mod:`dlrover_tpu.ops.pallas.flash_attention`) and the portable path is a
  plain XLA softmax attention (which XLA fuses well on TPU anyway).
- Ulysses-style sequence parallelism (reference:
  atorch/atorch/distributed/distributed.py:474-501 ``_SeqAllToAll``) — here
  an ``all_to_all`` over the ``sp`` mesh axis re-partitioning seq<->heads.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.common.log import default_logger as logger

_warned_cp = False


def _xla_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    segment_ids: Optional[jax.Array],
    scale: Optional[float],
    window: Optional[int] = None,
) -> jax.Array:
    """Reference softmax attention in pure XLA ops.

    Shapes: q [b, sq, hq, d]; k/v [b, skv, hkv, d] with hq % hkv == 0 (GQA).
    Computed in float32 for numerical stability, cast back to q.dtype.
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    if scale is None:
        scale = d ** -0.5
    groups = hq // hkv
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # [b, hkv, groups, sq, d] x [b, hkv, skv, d] -> [b, hkv, groups, sq, skv]
    qf = qf.reshape(b, sq, hkv, groups, d).transpose(0, 2, 3, 1, 4)
    kf = kf.transpose(0, 2, 1, 3)
    vf = vf.transpose(0, 2, 1, 3)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kf)
    mask = None
    if causal:
        q_pos = jnp.arange(sq)[:, None] + (skv - sq)
        kv_pos = jnp.arange(skv)[None, :]
        mask = q_pos >= kv_pos
        if window is not None:  # the key itself counted: i - w < j <= i
            mask = jnp.logical_and(mask, q_pos - kv_pos < window)
    if segment_ids is not None:
        seg = segment_ids[:, :, None] == segment_ids[:, None, :]
        seg = seg[:, None, None, :, :]
        mask = seg if mask is None else jnp.logical_and(mask, seg)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, vf)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, d)
    return out.astype(q.dtype)


_warned_probe = False


def _under_named_axes() -> bool:
    """True when tracing inside shard_map/pmap (named mesh axes bound)."""
    global _warned_probe
    try:
        from jax._src import core

        return bool(core.get_axis_env().axis_sizes)
    except Exception as e:  # private API — may move across jax versions
        if not _warned_probe:
            _warned_probe = True
            logger.warning(
                "axis-env probe failed (%s: %s) — Ulysses sp dispatch "
                "degraded; jax internals may have moved",
                type(e).__name__, e,
            )
        return False


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    use_pallas: Optional[bool] = None,
    sp_ulysses: Optional[bool] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Multi-head attention with GQA; dispatches to the Pallas TPU kernel
    when running on TPU (and shapes are kernel-friendly), else pure XLA.

    When the ambient mesh has an ``sp`` axis of size > 1 (and we are not
    already inside a shard_map), the computation routes through
    :func:`ulysses_attention` — the explicit seq<->heads all-to-all
    re-partition of the reference's ``_SeqAllToAll`` (reference:
    atorch/atorch/distributed/distributed.py:474-501) — so each sp peer
    attends over the full sequence with a head slice.  ``sp_ulysses=False``
    forces plain GSPMD semantics.

    ``window=w`` (causal only) lets query ``i`` see the keys
    ``i - w < j <= i``; the ring and Ulysses paths refuse it.

    q: [batch, q_seq, q_heads, head_dim]
    k, v: [batch, kv_seq, kv_heads, head_dim]
    """
    if window is not None and not causal:
        raise ValueError("a window needs causal attention")
    if use_pallas is None:
        import os

        if os.getenv("DLROVER_DISABLE_PALLAS", "").lower() in ("1", "true", "yes"):
            use_pallas = False
    if sp_ulysses is not False and not _under_named_axes():
        from dlrover_tpu.accel.parallel.mesh import ambient_mesh

        mesh = ambient_mesh()
        if mesh is not None and mesh.shape.get("cp", 1) > 1:
            # Context parallelism: ring flash attention over cp (composing
            # Ulysses over sp when sp > 1 — 2D sequence parallel).
            from dlrover_tpu.ops.ring_attention import (
                _cp_applicable,
                ring_attention,
            )

            if _cp_applicable(q, k, mesh):
                return ring_attention(
                    q,
                    k,
                    v,
                    mesh=mesh,
                    causal=causal,
                    segment_ids=segment_ids,
                    scale=scale,
                    use_pallas=use_pallas,
                    window=window,
                )
            global _warned_cp
            if not _warned_cp:
                _warned_cp = True
                logger.warning(
                    "mesh has cp > 1 but ring attention is not applicable "
                    "(q %s, k %s, mesh %s) — falling back to GSPMD "
                    "semantics (correct but the seq-sharded softmax will "
                    "all-gather K/V)", q.shape, k.shape, dict(mesh.shape),
                )
        if mesh is not None and mesh.shape.get("sp", 1) > 1:
            ok = _ulysses_applicable(q, k, mesh)
            if ok:
                return ulysses_attention(
                    q,
                    k,
                    v,
                    mesh=mesh,
                    causal=causal,
                    segment_ids=segment_ids,
                    scale=scale,
                    use_pallas=use_pallas,
                    window=window,
                )
            if sp_ulysses:
                raise ValueError(
                    "sp_ulysses requested but not applicable: either head "
                    "counts are not divisible by sp after tp head sharding "
                    f"(q heads {q.shape[2]}, kv heads {k.shape[2]}, mesh "
                    f"{dict(mesh.shape)}), or the active logical rules do "
                    "not shard the seq axis over 'sp'"
                )
        elif sp_ulysses:
            raise ValueError(
                "sp_ulysses requested but no ambient mesh with an sp axis "
                "of size > 1 is active (wrap the call in `with mesh:`)"
            )
    elif sp_ulysses and _under_named_axes():
        raise ValueError(
            "sp_ulysses requested inside shard_map/pmap — the Ulysses "
            "dispatch only applies to global (unmapped) arrays"
        )
    if use_pallas is None:
        # XLA's fused attention is competitive up to ~2k tokens; the pallas
        # kernel wins (and avoids O(s^2) memory) beyond that.  The gate must
        # match the kernel's block-divisibility requirement — there is no
        # exception fallback once dispatched.
        from dlrover_tpu.ops.pallas.flash_attention import (
            DEFAULT_BLOCK_K,
            DEFAULT_BLOCK_Q,
        )

        use_pallas = (
            jax.default_backend() not in ("cpu", "gpu")
            and q.shape[1] >= 2048
            and q.shape[1] % DEFAULT_BLOCK_Q == 0
            and k.shape[1] % DEFAULT_BLOCK_K == 0
        )
    if use_pallas:
        return _sharded_flash_attention(
            q, k, v, causal=causal, segment_ids=segment_ids, scale=scale,
            window=window,
        )
    return _xla_attention(
        q, k, v, causal=causal, segment_ids=segment_ids, scale=scale,
        window=window,
    )


def _sharded_flash_attention(q, k, v, *, causal, segment_ids, scale,
                             window=None):
    """The Pallas flash kernel on global arrays of a plain (dp/fsdp/tp)
    mesh.  A Mosaic kernel cannot be partitioned by GSPMD, so under an
    ambient mesh the call is wrapped in ``shard_map`` with the specs the
    logical rules give the activations — batch over dp/fsdp, heads over
    tp, the sequence whole on every device — and each device runs the
    kernel on its own shard; nothing is gathered.  Without a mesh, inside
    an enclosing shard_map (the Ulysses and ring paths), or when every
    axis the operands use has size 1, the kernel is called directly."""
    from jax.sharding import PartitionSpec

    from dlrover_tpu.accel.parallel.mesh import ambient_mesh, axes_size
    from dlrover_tpu.ops.pallas.flash_attention import flash_attention

    def direct(q, k, v, seg):
        return flash_attention(
            q, k, v, causal=causal, segment_ids=seg, scale=scale,
            window=window,
        )

    mesh = None if _under_named_axes() else ambient_mesh()
    if mesh is None:
        return direct(q, k, v, segment_ids)
    q_spec, kv_spec, _ = _attention_specs(mesh)
    batch_axes, head_axes, kv_head_axes = q_spec[0], q_spec[2], kv_spec[2]
    n_batch = axes_size(mesh, batch_axes)
    n_heads = axes_size(mesh, head_axes)
    if n_batch * n_heads == 1:
        return direct(q, k, v, segment_ids)
    if head_axes != kv_head_axes:
        raise ValueError(
            "flash attention under a mesh needs q heads and kv heads "
            f"sharded alike (rules give {head_axes!r} and "
            f"{kv_head_axes!r}): a device must hold whole GQA groups"
        )
    if q.shape[0] % n_batch or q.shape[2] % n_heads or k.shape[2] % n_heads:
        raise ValueError(
            f"flash attention under mesh {dict(mesh.shape)} needs batch "
            f"{q.shape[0]} divisible by {n_batch} ({batch_axes!r}) and "
            f"head counts {q.shape[2]}/{k.shape[2]} divisible by "
            f"{n_heads} ({head_axes!r}): the Pallas kernel runs per "
            "device on whole shards"
        )
    qkv_spec = PartitionSpec(batch_axes, None, head_axes, None)
    args, specs = (q, k, v), (qkv_spec,) * 3
    if segment_ids is not None:
        args += (segment_ids,)
        specs += (PartitionSpec(batch_axes, None),)
    return jax.shard_map(
        lambda q, k, v, seg=None: direct(q, k, v, seg),
        mesh=mesh,
        in_specs=specs,
        out_specs=qkv_spec,
        check_vma=False,
    )(*args)


# ---------------------------------------------------------------------------
# Ulysses sequence parallelism
# ---------------------------------------------------------------------------


def seq_to_heads_all_to_all(x: jax.Array, axis_name: str = "sp") -> jax.Array:
    """Re-partition [b, seq/P, H, d] -> [b, seq, H/P, d] across the sp axis.

    The TPU-native ``_SeqAllToAll`` (reference:
    atorch/atorch/distributed/distributed.py:474-501): inside ``shard_map``
    over the ``sp`` mesh axis, swap which dimension is distributed so
    attention sees the full sequence with a head slice.
    """
    # Tiled all_to_all: split the head dim across sp peers, concatenate the
    # received sequence chunks (in peer order = global seq order).
    return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)


def heads_to_seq_all_to_all(x: jax.Array, axis_name: str = "sp") -> jax.Array:
    """Inverse of :func:`seq_to_heads_all_to_all`:
    [b, seq, H/P, d] -> [b, seq/P, H, d]."""
    return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)


def _attention_specs(mesh, rules=None):
    """(q_spec, kv_spec, seg_spec) rank-padded PartitionSpecs for the
    Ulysses shard_map, derived from the active logical rules so they agree
    with the model's activation constraints."""
    from jax.sharding import PartitionSpec

    from dlrover_tpu.accel.parallel.mesh import logical_to_spec

    def pad(spec, rank):
        entries = list(spec) + [None] * (rank - len(spec))
        return PartitionSpec(*entries)

    q_spec = pad(logical_to_spec(("batch", "seq", "heads", "head_dim"), rules), 4)
    kv_spec = pad(
        logical_to_spec(("batch", "seq", "kv_heads", "head_dim"), rules), 4
    )
    seg_spec = pad(logical_to_spec(("batch", "seq"), rules), 2)
    return q_spec, kv_spec, seg_spec


def _spec_uses(entry, axis: str) -> bool:
    if entry is None:
        return False
    if isinstance(entry, str):
        return entry == axis
    return axis in entry


def _heads_split_over_sp(q, k, mesh, q_spec, kv_spec) -> bool:
    """Head counts (after any tp head sharding) must divide by sp for the
    Ulysses seq<->heads all-to-all.  Shared by the Ulysses and ring
    applicability checks so the two dispatchers can never disagree."""
    sp = mesh.shape.get("sp", 1)
    from dlrover_tpu.accel.parallel.mesh import axes_size

    q_heads_local = q.shape[2] // max(1, axes_size(mesh, q_spec[2]))
    kv_heads_local = k.shape[2] // max(1, axes_size(mesh, kv_spec[2]))
    return q_heads_local % sp == 0 and kv_heads_local % sp == 0


def _ulysses_applicable(q: jax.Array, k: jax.Array, mesh, rules=None) -> bool:
    """The active rules must shard seq over sp, and head counts must split
    across sp after any tp head sharding.  If seq is NOT sp-sharded (custom
    rules), the all-to-all would concatenate replicated copies into a bogus
    doubled sequence — GSPMD semantics are the correct path there."""
    sp = mesh.shape.get("sp", 1)
    q_spec, kv_spec, _ = _attention_specs(mesh, rules)
    if not (_spec_uses(q_spec[1], "sp") and _spec_uses(kv_spec[1], "sp")):
        return False
    if mesh.shape.get("cp", 1) > 1 and _spec_uses(q_spec[1], "cp"):
        # cp-sharded seq belongs to the ring path; the sp-only all-to-all
        # would reassemble just one cp chunk and attend block-diagonally.
        return False
    seq_ok = q.shape[1] % sp == 0 and k.shape[1] % sp == 0
    return seq_ok and _heads_split_over_sp(q, k, mesh, q_spec, kv_spec)


def ulysses_attention(
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
    window: Optional[int] = None,
) -> jax.Array:
    """Sequence-parallel attention via explicit seq<->heads all-to-all.

    The TPU-native ``_SeqAllToAll`` (reference:
    atorch/atorch/distributed/distributed.py:474-501 and its opt-lib wiring
    auto/opt_lib/sequence_parallel_optimization.py:9-51): under shard_map
    over the mesh, each ``sp`` peer trades its head slice for the full
    sequence, runs ordinary (flash) attention over full-seq x heads/P, and
    trades back.  Collectives ride ICI as three all-to-alls instead of the
    all-gather + reduce-scatter GSPMD would insert for seq-sharded softmax.

    Arguments are *global* arrays; returns the global [b, sq, hq, d] output
    partitioned like the input.
    """
    if window is not None:
        raise NotImplementedError(
            f"Ulysses attention has no window (window={window}): a layer "
            "with one must not run under an sp axis")
    q_spec, kv_spec, seg_spec = _attention_specs(mesh, rules)

    def inner(q, k, v, seg):
        q = seq_to_heads_all_to_all(q)
        k = seq_to_heads_all_to_all(k)
        v = seq_to_heads_all_to_all(v)
        if seg is not None:
            seg = jax.lax.all_gather(seg, "sp", axis=1, tiled=True)
        out = dot_product_attention(
            q,
            k,
            v,
            causal=causal,
            segment_ids=seg,
            scale=scale,
            use_pallas=use_pallas,
            sp_ulysses=False,
        )
        return heads_to_seq_all_to_all(out)

    if segment_ids is None:
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
