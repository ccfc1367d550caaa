"""Functional Llama forward for serving: prefill + per-slot decode.

The inference-engine half of the reference's RL serving story
(atorch/atorch/rl/inference_backend/vllm_backend.py:11-24): a
purpose-built decode path instead of the training module, because
serving wants different things than training —

- **per-slot positions**: every batch row is an independent sequence at
  its own decode position (continuous batching), so the KV cache is
  written with a per-row scatter and masked with per-row lengths; the
  training module's cache clock is a single shared offset
  (models/llama.py:271).
- **prefill/decode split**: prefill is one causal pass over a
  right-padded prompt bucket ([1, Lp]); decode is a one-token step for
  all slots at once.  Right-padding needs NO validity bookkeeping: a
  pad entry at cache index i > pos is invisible to the ``key <= pos``
  mask until the sequence itself overwrites index i with a real token.
- **chunked decode**: ``decode_chunk`` runs N steps inside one
  ``lax.scan`` so the host syncs once per chunk, not per token (the
  multi-step scheduling trick of serving engines).
- **pre-quantized int8 weights**: every projection may be
  ``{"q", "scale"}``; only activations quantize per call and weights
  stream from HBM at int8 width through XLA's native int8 MXU dot —
  decode's actual bottleneck (see ``_mm``).

All functions are pure; the engine (serving/engine.py) owns jit and
cache state.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dlrover_tpu.models.llama import LlamaConfig, apply_rope, rope_frequencies
from dlrover_tpu.ops.attention import dot_product_attention

from dlrover_tpu.rl.generation import select_token
from dlrover_tpu.utils.profiler import device_scope, device_scoped


def _mm(x: jax.Array, w: Any, dtype) -> jax.Array:
    """x @ w for fp or pre-quantized ({"q","scale"}) weights.

    Every int8 matmul — decode AND prefill — runs XLA's NATIVE int8
    dot: per-row activation scales, int8xint8 -> int32 on the MXU,
    per-column weight scales applied on the OUTPUT (column scales
    commute with the contraction, so this matches dequantize-first
    numerics).  Measured on v5e by the int8 decode probes of commit
    3d8b828 (since deleted; this comment is the record): at decode
    shapes (M=8, h2048) the native dot streams weights at 331 GB/s vs
    the Pallas kernel's 259 and bf16's wins grow with N (square 1.25x,
    qkv-fused 1.51x, lm head 1.83x) — XLA's own pipeline beats the
    hand-tiled kernel at every serving shape, so the Pallas path is
    gone (it remains in ops/ for the training-side frozen-layer use).
    """
    if isinstance(w, dict):
        amax = jnp.maximum(
            jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True),
            1e-8,
        )
        xq = jnp.round(
            x.astype(jnp.float32) / amax * 127.0
        ).astype(jnp.int8)
        out = jax.lax.dot_general(
            xq, w["q"],
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return (
            out.astype(jnp.float32) * (amax / 127.0) * w["scale"]
        ).astype(dtype)
    return (x.astype(dtype) @ w.astype(dtype)).astype(dtype)


def _rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32))


def _split_heads(x: jax.Array, n_heads: int, d: int) -> jax.Array:
    b, t = x.shape[:2]
    return x.reshape(b, t, n_heads, d)


@device_scoped("kv_write")
def _write_cache(cache: jax.Array, kv: jax.Array,
                 positions: jax.Array) -> jax.Array:
    """Per-row BLOCK scatter: writes kv[b]'s full K-token run at
    cache[b, positions[b] : positions[b]+K] (dynamic_update_slice block
    semantics — K=1 is the plain decode write; the speculative verify
    and the engine's cache-slack sizing both rely on the K-row case)."""
    def one(c, x, p):
        return jax.lax.dynamic_update_slice(c, x, (p, 0, 0))
    return jax.vmap(one)(cache, kv, positions.astype(jnp.int32))


def _layer_weights(layers, i: int) -> Dict[str, Any]:
    """Layer ``i``'s weights: params store an unstacked per-layer LIST
    so each weight is its own buffer — read directly by the Pallas int8
    kernel / XLA with no per-step slice copies (serving/params.py)."""
    return layers[i]


def _qkv_split(cfg: LlamaConfig, qkv: jax.Array):
    d = cfg.head_dim_
    qd = cfg.num_heads * d
    kvd = cfg.num_kv_heads * d
    return (
        _split_heads(qkv[..., :qd], cfg.num_heads, d),
        _split_heads(qkv[..., qd:qd + kvd], cfg.num_kv_heads, d),
        _split_heads(qkv[..., qd + kvd:], cfg.num_kv_heads, d),
    )


def _qkv(lp, h, cfg: LlamaConfig, dtype):
    """q/k/v projections for either param layout: fused ``wqkv``
    (single-chip decode: fewer, larger launches) or unfused
    ``wq/wk/wv`` (tensor-parallel serving: per-matrix column sharding
    keeps head semantics — params.py shard_serving_state)."""
    d = cfg.head_dim_
    if "wqkv" in lp:
        qkv = _mm(h, lp["wqkv"], dtype)
        if "bqkv" in lp:  # Qwen2-family qkv biases
            qkv = qkv + lp["bqkv"].astype(dtype)
        return _qkv_split(cfg, qkv)

    def one(wn: str, bn: str, heads: int):
        y = _mm(h, lp[wn], dtype)
        if bn in lp:
            y = y + lp[bn].astype(dtype)
        return _split_heads(y, heads, d)

    return (
        one("wq", "bq", cfg.num_heads),
        one("wk", "bk", cfg.num_kv_heads),
        one("wv", "bv", cfg.num_kv_heads),
    )


@device_scoped("attn_proj")
def _attn_proj(lp, h, cfg: LlamaConfig, dtype, angles):
    """The block's q, k (rotated by ``angles``) and v."""
    q, k, v = _qkv(lp, h, cfg, dtype)
    return apply_rope(q, angles), apply_rope(k, angles), v


@device_scoped("attn_proj")
def _attn_out(lp, o, x, dtype):
    """The residual stream with the attended heads projected back in."""
    return x + _mm(o, lp["wo"], dtype)


@device_scoped("mlp")
def _mlp(lp, h, cfg: LlamaConfig, dtype):
    f = cfg.intermediate_size
    if "wgu" in lp:
        gu = _mm(h, lp["wgu"], dtype)
        act = jax.nn.silu(gu[..., :f]) * gu[..., f:]
    else:
        act = jax.nn.silu(_mm(h, lp["wgate"], dtype)) * _mm(
            h, lp["wup"], dtype)
    return _mm(act, lp["down"], dtype)


def decode_step(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    cache: Dict[str, Any],         # {"k","v"}: per-layer LISTS of
    tokens: jax.Array,             #   [B, L, KV, D] buffers
    positions: jax.Array,          # [B] write position per slot
    attention_impl: str = "xla",
    kernel_interpret: bool = False,
    active: Optional[jax.Array] = None,   # [B] bool
) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode step for all slots; returns (logits [B, V], cache).

    Implemented as :func:`verify_step` with K=1 so the decode and
    speculative-verify programs are identical by construction — a
    change to one cannot silently break the other's greedy-match
    invariant.  The layer loop stays python-unrolled and qkv / gate+up
    run as single fused matmuls — decode is launch/bandwidth-bound, so
    fewer, larger kernels over unsliced weights is the win (module
    docstring).  ``attention_impl="pallas"`` routes the paged-cache
    attention read through the fused kernel (the K=1 single-query
    path — exactly this function's case), which streams only the pages
    a slot's length makes live: ``positions + 1`` keys, the last group
    of pages whole.  ``active`` (the engine's mask: the slot holds a
    request and is not prefilling) hands the kernel length 0 for every
    other slot, so an idle slot or one parked past its allocation reads
    no page and attends to nothing (zeros) — its logits are junk either
    way and the caller must not read them.  With no mask every slot
    reads ``positions + 1`` keys.  The gather path ignores the mask.
    """
    logits, cache = verify_step(params, cfg, cache, tokens[:, None],
                                positions,
                                attention_impl=attention_impl,
                                kernel_interpret=kernel_interpret,
                                active=active)
    return logits[:, 0, :], cache


def verify_step(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    cache: Dict[str, Any],
    tokens: jax.Array,             # [B, K]: last committed token + K-1 drafts
    positions: jax.Array,          # [B] position of tokens[:, 0]
    slots: Optional[jax.Array] = None,
    logits_index: Optional[jax.Array] = None,
    attention_impl: str = "xla",
    kernel_interpret: bool = False,
    active: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """Speculative VERIFY: process K tokens per slot in one dispatch and
    return next-token logits at every position ([B, K, V], cache).

    ``tokens[:, 0]`` is each slot's last committed token (what
    ``decode_step`` would process) and ``tokens[:, 1:]`` are draft
    continuations; ``logits[:, i]`` predicts the token AFTER
    ``tokens[:, i]``, so the caller accepts the longest prefix where
    ``argmax(logits[:, i]) == tokens[:, i+1]`` and takes one bonus token
    from the first mismatch.  Decode is bandwidth-bound (weights stream
    once regardless of K<=8 riding the matmul M-dim), so a verify step
    costs ~one decode step while committing up to K tokens — the
    speculative-decoding trade (beyond-reference capability; the
    reference serves via vLLM, vllm_backend.py:11-24).

    Cache safety on rejection: K entries are written at
    ``positions..positions+K-1``; after accepting ``a`` drafts the
    caller advances the position pointer by ``a+1`` only — entries past
    it are invisible to the ``key <= pos`` mask and get overwritten
    when the sequence actually reaches them.  No rewind needed.

    ``slots`` generalizes the batch dim to a SUBSET of cache slots:
    ``tokens [G, K]`` / ``positions [G]`` operate on cache rows (or
    paged table rows) ``slots [G]`` while the rest of the cache rides
    along untouched — this is the chunked-prefill program (a prompt
    chunk is exactly a draft-free K-token run attending to what the
    previous chunks already cached), so decode, speculative verify and
    chunk prefill stay ONE transformer program by construction.
    ``logits_index [B or G]`` gathers a single time index per row
    before the lm head (returns ``[*, 1, V]``): chunk prefill only
    needs the prompt-final position's logits, and K-1 wasted
    vocab-width matmuls per chunk is exactly the kind of cost a
    bounded prefill chunk exists to avoid.

    ``attention_impl="pallas"`` (paged caches, K=1, full batch only —
    the decode hot path) replaces the gather-then-attend read with the
    fused paged kernel (ops/pallas/paged_attention): blocks stream IN
    PLACE from the pools with dequantization folded inside, so the
    dense (bf16-width) view is never materialized.  Every other shape
    (speculative verify, chunk prefill, slot subsets) keeps the gather
    path; ``kernel_interpret`` runs the kernel in Pallas interpret
    mode (the CPU parity harness).  ``active`` is :func:`decode_step`'s
    mask and reaches nothing but that kernel.

    A latent-attention model (``cfg.kv_lora_rank``) has its own blocks
    and cache rows, and so has any model whose layers are of more than
    one kind, sparse, or scaled (``cfg.layer_kinds``):
    ``serving/latent.py verify_step``, same arguments.
    """
    if cfg.layer_kinds:
        from dlrover_tpu.serving import latent

        return latent.verify_step(
            params, cfg, cache, tokens, positions, slots=slots,
            logits_index=logits_index, attention_impl=attention_impl,
            kernel_interpret=kernel_interpret, active=active)
    dtype = cfg.dtype
    d = cfg.head_dim_
    n_rep = cfg.num_heads // cfg.num_kv_heads
    b, klen = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)            # [B, K, E]
    pos_k = positions[:, None] + jnp.arange(klen)[None, :]   # [B, K]
    angles = rope_frequencies(d, cfg.max_seq_len, cfg.rope_theta)[
        pos_k]                                               # [B, K, d/2]

    # paged cache ({"k_pool","v_pool","table"}, quantized pools add
    # {"k_scale","v_scale"}) vs dense ({"k","v"}): same transformer
    # loop, different cache plumbing (serving/paged.py)
    paged = "table" in cache
    quant = "k_scale" in cache
    use_kernel = (
        paged and attention_impl == "pallas" and klen == 1
        and slots is None and logits_index is None
    )
    if paged:
        from dlrover_tpu.serving.paged import (
            gather_blocks,
            gather_blocks_q,
            scatter_tokens,
            scatter_tokens_q,
        )

        table = cache["table"]
        if slots is not None:
            table = jnp.take(table, slots, axis=0)           # [G, MB]
    if use_kernel:
        from dlrover_tpu.ops.pallas.paged_attention import (
            paged_decode_attention,
        )

        lengths = positions.astype(jnp.int32) + 1
        if active is not None:
            lengths = jnp.where(active, lengths, 0)

    new_k, new_v = [], []
    new_ks, new_vs = [], []
    for i in range(cfg.num_layers):
        lp = _layer_weights(params["layers"], i)
        h = _rmsnorm(x, lp["input_norm"], cfg.rms_norm_eps).astype(dtype)
        q, k, v = _attn_proj(lp, h, cfg, dtype, angles)
        ck = cv = None
        if paged and quant:
            kp, ksc = scatter_tokens_q(
                cache["k_pool"][i], cache["k_scale"][i], table,
                k, positions)
            vp, vsc = scatter_tokens_q(
                cache["v_pool"][i], cache["v_scale"][i], table,
                v, positions)
            if use_kernel:
                with device_scope("paged_attn"):
                    o = paged_decode_attention(
                        q[:, 0], kp, vp, table, lengths,
                        k_scale=ksc, v_scale=vsc,
                        interpret=kernel_interpret)[:, None]
            else:
                ck = gather_blocks_q(kp, ksc, table, dtype)
                cv = gather_blocks_q(vp, vsc, table, dtype)
            new_k.append(kp)
            new_v.append(vp)
            new_ks.append(ksc)
            new_vs.append(vsc)
        elif paged:
            kp = scatter_tokens(cache["k_pool"][i], table,
                                k.astype(cache["k_pool"][i].dtype),
                                positions)
            vp = scatter_tokens(cache["v_pool"][i], table,
                                v.astype(cache["v_pool"][i].dtype),
                                positions)
            if use_kernel:
                with device_scope("paged_attn"):
                    o = paged_decode_attention(
                        q[:, 0], kp, vp, table, lengths,
                        interpret=kernel_interpret)[:, None]
            else:
                ck = gather_blocks(kp, table)
                cv = gather_blocks(vp, table)
            new_k.append(kp)
            new_v.append(vp)
        elif slots is not None:
            # dense slot-subset write: [G, K] advanced-index scatter
            # (out-of-bounds positions drop, matching the paged trash
            # sink), then gather the G rows back for attention
            ck_full = cache["k"][i].at[slots[:, None], pos_k].set(
                k.astype(cache["k"][i].dtype))
            cv_full = cache["v"][i].at[slots[:, None], pos_k].set(
                v.astype(cache["v"][i].dtype))
            ck = jnp.take(ck_full, slots, axis=0)
            cv = jnp.take(cv_full, slots, axis=0)
            new_k.append(ck_full)
            new_v.append(cv_full)
        else:
            ck = _write_cache(cache["k"][i], k, positions)
            cv = _write_cache(cache["v"][i], v, positions)
            new_k.append(ck)
            new_v.append(cv)
        if not use_kernel:
            o = _attn_verify(q, ck, cv, positions, n_rep)
        o = o.astype(dtype).reshape(b, klen, cfg.num_heads * d)
        x = _attn_out(lp, o, x, dtype)
        h = _rmsnorm(x, lp["post_norm"], cfg.rms_norm_eps).astype(dtype)
        x = x + _mlp(lp, h, cfg, dtype)

    x = _rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    if logits_index is not None:
        x = jnp.take_along_axis(
            x, logits_index.astype(jnp.int32)[:, None, None], axis=1
        )                                                    # [*, 1, E]
    logits = _lm_head(params, x.astype(dtype), cfg)          # [B, K|1, V]
    if paged and quant:
        out_cache = dict(cache, k_pool=new_k, v_pool=new_v,
                         k_scale=new_ks, v_scale=new_vs)
    elif paged:
        out_cache = dict(cache, k_pool=new_k, v_pool=new_v)
    else:
        out_cache = {"k": new_k, "v": new_v}
    return logits, out_cache


@device_scoped("paged_attn")
def _attn_verify(
    q: jax.Array,            # [B, K, H, D]
    cache_k: jax.Array,      # [B, L, KV, D]
    cache_v: jax.Array,
    positions: jax.Array,    # [B] position of q[:, 0]
    n_rep: int,
) -> jax.Array:
    """GQA attention for a K-token run against the cache WITHOUT
    materializing the n_rep-expanded cache (a ``jnp.repeat`` would
    stream 4x the cache bytes per step on a 16:4 model — decode is
    bandwidth-bound, so that costs as much as the weight reads).
    Query i may see keys at ``key_pos <= positions + i`` (causal within
    the run, everything committed before it); K=1 is plain decode.
    q folds to [B, K, KV, G, D] and both einsums contract against the
    unexpanded cache; f32 accumulation on the MXU via
    preferred_element_type."""
    b, qlen, h, d = q.shape
    kv = cache_k.shape[2]
    g = h // kv
    qg = q.reshape(b, qlen, kv, g, d)
    scores = jnp.einsum(
        "bqkgd,blkd->bkgql", qg, cache_k,
        preferred_element_type=jnp.float32,
    ) / jnp.sqrt(float(d))
    key_pos = jnp.arange(cache_k.shape[1])
    q_pos = positions[:, None] + jnp.arange(qlen)[None, :]   # [B, K]
    mask = key_pos[None, None, :] <= q_pos[:, :, None]       # [B, K, L]
    scores = jnp.where(
        mask[:, None, None, :, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgql,blkd->bqkgd", probs.astype(cache_v.dtype), cache_v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, qlen, h, d)


@device_scoped("head")
def _lm_head(params, x, cfg: LlamaConfig) -> jax.Array:
    # compute dtype mirrors the training module (models/llama.py lm_head:
    # bf16 matmul; tied path attends in param_dtype) so greedy decode
    # agrees with the trainer's forward down to tie-breaks
    if params.get("lm_head") is None:  # tied embeddings
        logits = x.astype(cfg.param_dtype) @ params["embed"].astype(
            cfg.param_dtype).T
    else:
        logits = _mm(x, params["lm_head"], cfg.dtype)
    logits = logits.astype(jnp.float32)
    if cfg.logit_scale != 1.0:
        logits = logits * cfg.logit_scale
    return logits


def prefill(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    tokens: jax.Array,        # [G, Lp] right-padded prompt bucket(s)
    real_len: jax.Array,      # [G] (or scalar) actual prompt lengths
) -> Tuple[jax.Array, list, list]:
    """Causal pass over a GROUP of same-bucket prompts; returns
    (last_logits [G, V], per-layer k list of [G, Lp, KV, D], v list) —
    the engine scatters the K/V into decode-cache slots.  Rows are
    independent (causal attention never crosses the batch dim), so a
    group of G prompts costs one dispatch instead of G — the admission
    path batches same-bucket arrivals through here.  Pad garbage beyond
    ``real_len`` is harmless: decode overwrites/masks it (module
    docstring).  Of a latent-attention model (``cfg.kv_lora_rank``) the
    two lists are its cache rows and index keys (``serving/latent.py``)."""
    if cfg.layer_kinds:
        from dlrover_tpu.serving import latent

        return latent.prefill(params, cfg, tokens, real_len)
    dtype = cfg.dtype
    d = cfg.head_dim_
    lp_len = tokens.shape[1]
    x = jnp.take(params["embed"], tokens, axis=0)          # [1, Lp, E]
    angles = rope_frequencies(d, cfg.max_seq_len, cfg.rope_theta)[
        jnp.arange(lp_len)]

    ks, vs = [], []
    for i in range(cfg.num_layers):
        lp = _layer_weights(params["layers"], i)
        h = _rmsnorm(x, lp["input_norm"], cfg.rms_norm_eps).astype(dtype)
        q, k, v = _attn_proj(lp, h, cfg, dtype, angles)
        # no scope of its own around the call: the flash kernel has no
        # name and takes the innermost one's (utils/profiler.device_scope)
        o = dot_product_attention(q, k, v, causal=True,
                                  sp_ulysses=False).astype(dtype)
        o = o.reshape(o.shape[0], lp_len, cfg.num_heads * d)
        x = _attn_out(lp, o, x, dtype)
        h = _rmsnorm(x, lp["post_norm"], cfg.rms_norm_eps).astype(dtype)
        x = x + _mlp(lp, h, cfg, dtype)
        ks.append(k)
        vs.append(v)
    x = _rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    last_idx = (jnp.atleast_1d(real_len).astype(jnp.int32) - 1)
    last = jnp.take_along_axis(
        x, last_idx[:, None, None].astype(jnp.int32), axis=1
    )                                                     # [G, 1, E]
    logits = _lm_head(params, last.astype(dtype), cfg)[:, 0, :]
    return logits, ks, vs


