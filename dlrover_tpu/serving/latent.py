"""The served forward of a latent-attention model with sparse experts
(``LlamaConfig.kv_lora_rank``), with a learned selection of keys (the
preset ``LlamaConfig.glm5``) or WITHOUT one, every query attending to its
whole context (``LlamaConfig.sarvam_105b``): the blocks
``serving/model.py``'s ``verify_step`` and ``prefill`` run in place of the
grouped-query ones.

A model may mix such layers with layers that keep a recurrent STATE A
SLOT in place of rows in the pools (``LayerSpec.mixer`` "kda", linear
attention, "ssm", a Mamba-2 scan, or "retention", power retention:
``serving/linear.py``): the layer
loop of :func:`verify_step` dispatches on each layer's description, the
pools are indexed by the attention layers alone, and a ``RopeSpec`` whose
``rotary_fraction`` is 0 (``LlamaConfig.kimi_linear_48b``) rotates
nothing.  A model whose EVERY layer keeps a state
(``LlamaConfig.brumby_14b``) has no pool and no table in its cache at all,
and a dense MLP behind every layer.

This loop is THE loop of layer kinds (``LlamaConfig.layer_kinds``): the
attention block it calls is latent or GROUPED-QUERY by what the config
says.  A model with no ``kv_lora_rank`` whose layers are sparse or keep a
state (``LlamaConfig.granite_4_h_small``) runs :func:`_gqa_layer` where a
latent model runs its projections: ``serving/model.py``'s fused ``W_qkv``
and ``W_o``, K/V rows in ``k_pool`` / ``v_pool`` under the one block
table, ``paged_decode_attention`` at decode and a walk of the live key
blocks for a run, the softmax scale the config's (``attn_scale``).  The
embedding and every residual branch are scaled where the config says so
(``embedding_mult``, ``residual_mult``).

A grouped-query layer may carry the learned selection too
(``LlamaConfig.keye_vl2_30b_a3b``: ``index_topk`` with no
``kv_lora_rank``), and a QK-norm a head (``qk_norm_kind="head"``: each
head of q and of k RMS-normed before rotation, one scale for all query
heads and one for all key heads).  The INDEXER HAS TWO SOURCES OF QUERIES:
a latent layer projects them from the query's bottleneck, ``q_i = W_iq
c_q`` (:func:`_projections`), a grouped-query layer, which has no
bottleneck, from the layer's normed input, ``q_i = W_iq h``
(:func:`_gqa_index`); keys, head weights, scores and the choice are one
(:func:`_select_decode`, :func:`_select_run`, :func:`_chosen`).  Such a
layer keeps its index keys in an ``index_pool`` of its own beside
``k_pool`` / ``v_pool`` under the SAME block table (rows of
:func:`index_row_width` values: whole lanes), shared and copied with its
K/V blocks, and attends UNDER the selection, masked-dense: decode streams
the live K/V pages through ``paged_decode_attention`` with the chosen
rows as a float32 ``bias``, a run of leading pages that several slots'
table rows hold in common (a cached document) ONCE a layer and forward
for all of them (``paged_attention.shared_runs``; the kernel names that call
``paged_attention.SELECTED_ATTENTION`` in a device trace; a table
of no more rows than a query may choose runs the unmasked kernel, and a
model without an indexer traces no bias at all), a run walks its live key
blocks under the mask (:func:`_gqa_attend_run`).  Of each K/V row streamed
at 24 k under a selection of 2 048, 8 % is wanted: attending the chosen
rows alone is ROADMAP Reach A12.  The WITNESS of such a layer is a latent
layer's: the rows each query attended to, a layer (decode: positions; a
run: the packed mask), beside the first sparse MLP's input and output.

A model's latent layers may be of MORE THAN ONE KIND
(``LlamaConfig.dots3_note``): each reads its own geometry from its
``LayerSpec`` (heads, latent rank, nope size, rotary embedding:
``LlamaConfig.latent_dims``), a layer whose ``LayerSpec.window`` is w
attends the last w keys only and keeps its rows in a RING a slot
(``serving/paged.py``: no block of the pools, no table on the host;
:func:`_window_layer`), and the indexer runs in the layers that have one.
With ``mla_lora_rescale`` the normed bottleneck and the normed latent are
scaled behind their norms; with ``attn_head_gate`` each head's attended
value is gated ahead of ``W_o``.  All of it is chosen by what the model
has, at trace time: a model with one kind of layer traces what it did.

One layer, on its normed input ``h`` (positions ``t``, ``s``):

- *latent attention*: ``c_q = RMSNorm(W_qa h)``, ``q = W_qb c_q`` (or,
  with ``q_lora_rank`` 0, ``q = W_q h``: no bottleneck) in heads of
  ``[nope | rope]``, each head RMS-normed where ``qk_norm`` says so;
  ``[c | k_r] = W_kva h``, ``c_kv = RMSNorm(c)``,
  ``k_r`` rotated (one row for all heads, adjacent pairs, at the
  frequencies of ``LlamaConfig.rope``: plain or YaRN's), ``q_rope``
  rotated alike.  The cache keeps ``[c_kv | k_r]`` a token a layer and
  nothing a head.  Attention runs ABSORBED: ``q~_h = W_kvb,h[K]^T q_nope_h``
  scores the latent row itself, ``score_h[t, s] = (q~_h[t] . c_kv[s] +
  q_rope_h[t] . k_r[s]) / sqrt(nope + rope) x attn_scale_mult``, and the
  value projection ``W_kvb,h[V]`` is applied to the attended latent, once
  a query.
- *the indexer* (``index_topk`` > 0): ``q_i = W_iq c_q`` in
  ``index_n_heads`` heads (of a grouped-query layer ``W_iq h``), ``k_i =
  LayerNorm(W_ik h)`` one row a token
  (cached beside the latent row), the
  first ``rope`` dimensions of both rotated (of a grouped-query layer all
  of them, halves paired), ``w = W_iw h / sqrt(heads x
  size)`` in float32; ``I[t, s] = sum_h w[t, h] relu(q_i[t, h] . k_i[s])``
  for ``s <= t``, and query ``t`` attends to the ``index_topk`` largest
  only (to every key behind it while they are no more than that).
- *the MLP*: a dense SwiGLU in the leading layers, then the router of
  ``models/moe.py`` (:func:`~dlrover_tpu.models.moe.route`) over ALL
  experts, the grouped matmuls over the experts this device holds
  (``moe_experts_held``), a pick on an absent expert adding nothing, and
  the shared expert on every token.

Two attention paths, both reading the pools through the block table and
neither making a dense copy of a slot's table:

- decode (one query a slot, every slot): ``ops/pallas/mla_decode.py
  mla_decode_attention``, one kernel over each slot's live pages of the
  latent pool (``attention_impl="pallas"``), or its ``jnp`` oracle
  ``gather_latent_decode``, group by group of the same live pages.  With
  a selection the kernel streams the same pages under a MASK of the
  chosen rows: the index scores of a slot's LIVE pages from the
  ``paged_index_scores`` kernel, the ``index_topk``-th largest of them
  (:func:`_kth_largest`, as a run of queries chooses), ``scores >= that``
  as a float32 bias.  No sort, no positions, no gather of rows: this
  chip gathers rows at a tenth of the pace it streams them.
- a run of queries (a prefill chunk, a speculative verify, a bucketed
  prefill): row by row of the group (``lax.map``), over the row's live
  key blocks only (trip counts read from the positions): the index
  scores of every block, the ``index_topk``-th largest of each query's
  scores (:func:`_kth_largest`), then causal attention over the blocks
  again under the mask ``I >= that``, with a running softmax.  With
  ``attention_impl="pallas"`` (``verify_step``: the engine's prompt
  chunks and verifies on a chip) that attention is ONE kernel,
  ``ops/pallas/mla_prefill.py mla_prefill_attention``, whose score tiles
  live and die in VMEM; otherwise, and in :func:`prefill`, it is the
  ``jnp`` loop of :func:`_attend_run`, the kernel's oracle, whose tiles
  pass through HBM.  Either way a run computes scores against every live
  row, chosen or not: masked-dense at the MXU's pace.  Attending the
  chosen rows alone is ROADMAP Reach A12, a row gather away.  A prompt's
  last chunk is padded to its program's shape: the queries behind
  ``logits_index`` are nobody's, select and attend nothing, and the
  kernel walks no key block for a tile of them.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.models.llama import (LayerSpec, LlamaConfig, RopeSpec,
                                      apply_rope, rope_inverse_frequencies)
from dlrover_tpu.models.moe import buffer_rows, grouped_matmul, route
from dlrover_tpu.serving.model import _lm_head, _mm, _rmsnorm
from dlrover_tpu.serving.paged import (ring_table, scatter_ring,
                                       scatter_tokens)
from dlrover_tpu.utils.profiler import device_scope

#: pages of the pools one key block of the query-run path holds
KEY_BLOCK_PAGES = 8
_NEG_INF = -jnp.inf
_LANES = 128


def latent_row_width(cfg: LlamaConfig,
                     spec: Optional[LayerSpec] = None) -> int:
    """Values of one row of the latent pool (of the layer ``spec``, where
    a model's latent layers differ): ``[c_kv | k_r]`` and zeros up
    to whole 128-lane tiles (GLM-5: 576 -> 640).  With a minor dimension
    that is no multiple of 128 the TPU keeps a ``[blocks, 128, 576]``
    array with the BLOCK's rows minor, and every program that gathers
    rows copies the pool into row-major order and back (compiled for a
    described v5e, PR 34: two copies of every layer's pool a dispatch).
    The absorbed query carries zeros there too, so no score moves."""
    c = cfg.latent_dims(spec)[0] if spec is not None else cfg.kv_lora_rank
    return -(-(c + cfg.qk_rope_head_dim) // _LANES) * _LANES


def index_row_width(cfg: LlamaConfig) -> int:
    """Values of one row of the index-key pool.  Of a GROUPED-QUERY
    model: ``index_head_dim`` and zeros up to whole 128-lane tiles
    (Keye-VL-2.0: 64 -> 128).  A minor dimension of 64 is no DMA slice the
    chip's compiler takes (Mosaic: "Slice shape along dimension 3 must be
    aligned to tiling (128), but is 64"), and the TPU keeps a
    ``[blocks, 128, 64]`` bfloat16 array in tiles of 128 lanes anyway:
    the padded pool holds what the unpadded one would.  The index queries
    carry zeros there too, so no score moves.  Of a latent model: ``index_head_dim`` as it is (128 in
    every served one; :func:`_projections` writes unpadded rows)."""
    if cfg.kv_lora_rank:
        return cfg.index_head_dim
    return -(-cfg.index_head_dim // _LANES) * _LANES


def _layernorm(x, scale, bias, eps=1e-6):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    return ((xf - mean) * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32) + bias.astype(jnp.float32))


def rope_pairs(x: jax.Array, positions: jax.Array, spec: RopeSpec,
               rotary: int) -> jax.Array:
    """The first ``rotary`` dimensions of ``x`` rotated in ADJACENT pairs
    ``(x_2i, x_2i+1)`` by ``positions`` x the inverse frequencies of
    ``spec`` over those ``rotary`` (``models/llama.py
    rope_inverse_frequencies``, training's: plain ``theta^(-2i / rotary)``
    or YaRN's blend), cos and sin times its ``attention_factor``; the rest
    pass.  ``positions`` has ``x``'s leading dimensions (fewer broadcast
    over the rest, heads).  A ``rotary_fraction`` of 0 says NO positional
    encoding: ``x`` passes whole.  Float32 out."""
    if not spec.rotary_fraction:
        return x.astype(jnp.float32)
    inv = rope_inverse_frequencies(spec, rotary)
    ang = positions.astype(jnp.float32)[..., None] * inv
    ang = ang.reshape(positions.shape
                      + (1,) * (x.ndim - 1 - positions.ndim)
                      + (rotary // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if spec.attention_factor != 1.0:
        cos, sin = cos * spec.attention_factor, sin * spec.attention_factor
    xf = x.astype(jnp.float32)
    pairs = xf[..., :rotary].reshape(*x.shape[:-1], rotary // 2, 2)
    x0, x1 = pairs[..., 0], pairs[..., 1]
    rot = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                    axis=-1).reshape(*x.shape[:-1], rotary)
    return jnp.concatenate([rot, xf[..., rotary:]], axis=-1)


def _projections(lp, h, cfg: LlamaConfig, spec: LayerSpec, pos, dtype):
    """``h`` [B, K, E], ``pos`` [B, K] -> the absorbed queries ``qq``
    [B, K, H, W], the cache rows ``row`` [B, K, W] (``W`` =
    :func:`latent_row_width`: C + R and zeros), and the
    indexer's ``q_i`` [B, K, Hi, Di], ``k_i`` [B, K, Di], ``w`` [B, K, Hi]
    (float32), in the geometry of the layer ``spec``: its heads, its
    latent rank and nope size (``LlamaConfig.latent_dims``), its rotary
    embedding; the indexer's three where it runs in this layer."""
    b, k = h.shape[:2]
    r, heads, rope = cfg.qk_rope_head_dim, spec.num_heads, spec.rope
    c, nope, indexed = cfg.latent_dims(spec)
    # (``mla_lora_rescale``: behind their norms, ahead of every reader)
    q_up, kv_up = ((cfg.hidden_size / cfg.q_lora_rank) ** 0.5,
                   (cfg.hidden_size / c) ** 0.5) \
        if cfg.mla_lora_rescale else (None, None)
    with device_scope("swa_proj" if spec.window else "mla_proj"):
        if "wq_a" in lp:               # the query through its bottleneck
            c_q = _rmsnorm(_mm(h, lp["wq_a"], dtype), lp["q_a_norm"],
                           cfg.rms_norm_eps)
            if q_up:
                c_q = c_q * q_up
            c_q = c_q.astype(dtype)
            q = _mm(c_q, lp["wq_b"], dtype).reshape(b, k, heads, nope + r)
        else:
            # W_q^T, [H x (nope + rope), E]: the layout the chip's
            # compiler gives the weight itself where heads of 192 follow
            # (with [E, H x 192] it copied every layer's 100 MB ahead of
            # each decode chunk: compiled for a described v5e, PR 41)
            q = jnp.einsum("bke,ne->bkn", h.astype(dtype),
                           lp["wq_t"].astype(dtype)
                           ).astype(dtype).reshape(b, k, heads, nope + r)
        if "q_norm" in lp:             # a head at a time, before rotation
            q = _rmsnorm(q, lp["q_norm"], cfg.rms_norm_eps).astype(dtype)
        q_rope = rope_pairs(q[..., nope:], pos, rope, r)
        ckv = _mm(h, lp["wkv_a"], dtype)
        c_kv = _rmsnorm(ckv[..., :c], lp["kv_a_norm"], cfg.rms_norm_eps)
        if kv_up:
            c_kv = c_kv * kv_up
        k_r = rope_pairs(ckv[..., c:], pos, rope, r)
        pad = latent_row_width(cfg, spec) - c - r
        row = jnp.concatenate(
            [c_kv, k_r, jnp.zeros((b, k, pad), jnp.float32)],
            axis=-1).astype(dtype)
        q_abs = jnp.einsum("bkhn,hnc->bkhc", q[..., :nope],
                           lp["wkv_b_k"].astype(dtype),
                           preferred_element_type=jnp.float32)
        qq = jnp.concatenate(
            [q_abs, q_rope, jnp.zeros((b, k, heads, pad), jnp.float32)],
            axis=-1).astype(dtype)
    q_i = k_i = w = None
    if indexed:
        hi, di = cfg.index_n_heads, cfg.index_head_dim
        with device_scope("dsa_index"):
            q_i = rope_pairs(
                _mm(c_q, lp["iwq"], dtype).reshape(b, k, hi, di),
                pos, rope, r).astype(dtype)
            k_i = rope_pairs(
                _layernorm(_mm(h, lp["iwk"], dtype), lp["ik_norm_scale"],
                           lp["ik_norm_bias"]),
                pos, rope, r).astype(dtype)
            w = jnp.dot(h.astype(dtype), lp["iw"].astype(dtype),
                        preferred_element_type=jnp.float32
                        ) * float((hi * di) ** -0.5)
    return qq, row, q_i, k_i, w


def _attn_spec(cfg: LlamaConfig, spec: Optional[LayerSpec]) -> LayerSpec:
    """``spec``, or of a model whose attention layers are all alike the
    one kind."""
    return spec or next(s for s in cfg.layer_specs if s.mixer == "attn")


def _softmax_scale(cfg: LlamaConfig,
                   spec: Optional[LayerSpec] = None) -> float:
    nope = cfg.latent_dims(_attn_spec(cfg, spec))[1]
    return float((nope + cfg.qk_rope_head_dim) ** -0.5
                 * cfg.attn_scale_mult)


def _orderable(x: jax.Array) -> jax.Array:
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    keys = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jax.lax.bitcast_convert_type(keys, jnp.uint32) \
        ^ jnp.uint32(0x80000000)


def _kth_largest(keys: jax.Array, k: int) -> jax.Array:
    """Of each row of ``keys`` [R, W] (:func:`_orderable`), the largest
    value ``t`` with at least ``k`` entries ``>= t``: the ``k``-th largest
    entry, exactly, by 32 counting passes, bit by bit from the top (a
    row of fewer than ``k`` entries gives 0: everything passes).  No sort:
    a prefill chunk asks this of 512 rows of a whole context at once."""
    def bit(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(keys >= cand[:, None], axis=-1) >= k
        return jnp.where(enough, cand, t)

    return jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(keys.shape[:1], jnp.uint32))


def _chosen(keys: jax.Array, k: int, dead: jax.Array) -> jax.Array:
    """What a selection IS, for a run of queries and for decode: of each
    row of ``keys`` [R, W] (:func:`_orderable` scores; ``dead``: no key
    there) the ``k`` largest, and with the ``k``-th every key that ties
    it, as the reference chooses (``scores >= kth``); every live key of a
    row that has no more than ``k``."""
    return (keys >= _kth_largest(keys, k)[:, None]) & (keys > dead)


def _live_blocks(q_pos, n_real, table_pages: int, bs: int, pages: int):
    """Key blocks (``pages`` pages of ``bs`` rows) a run walks: up to its
    last REAL query's position, never more than the table holds."""
    kb = pages * bs
    last = q_pos[-1] if n_real is None else q_pos[n_real - 1]
    return jnp.minimum((last + kb) // kb, table_pages // pages)


def _select_run(q_i, w, q_pos, index_pool, table_row, bs: int,
                cfg: LlamaConfig, pages: int, n_real, n_live):
    """The keys each query of a run of ONE sequence attends to, [K, MB x
    bs] bool (``table_row`` [MB], a multiple of ``pages``; ``bs`` rows a
    page): with an indexer (``q_i`` [K, Hi, Di], ``w`` [K, Hi]) and a
    table of more rows than a query may choose, the index scores of every
    live key block (``index_pool`` [NB, bs, Di]) and of each query its
    ``index_topk`` largest (:func:`_chosen`); otherwise every key behind
    the query.  A query at or behind ``n_real`` (None: all are real)
    chooses nothing, and only the first ``n_live`` key blocks, those up to
    the last real query (:func:`_live_blocks`), are scored.  The one
    selection of a latent layer's run and a grouped-query layer's."""
    klen = q_pos.shape[0]
    kb = pages * bs                               # keys a block
    width = table_row.shape[0] // pages * kb

    def real_only(chosen):
        if n_real is None:
            return chosen
        return chosen & (jnp.arange(klen) < n_real)[:, None]

    def causal(j):
        key_pos = j * kb + jnp.arange(kb)
        return key_pos[None, :] <= q_pos[:, None]            # [K, kb]

    if q_i is None or width <= cfg.index_topk:
        # no more keys than a query may choose: plain causal attention
        return real_only(jnp.arange(width)[None, :] <= q_pos[:, None])

    from dlrover_tpu.ops.pallas.paged_index import index_scores

    with device_scope("dsa_index"):
        def score_block(j, keys):
            ids = jax.lax.dynamic_slice_in_dim(table_row, j * pages, pages)
            block = jnp.take(index_pool, ids, axis=0).reshape(
                kb, index_pool.shape[-1])
            s = index_scores(q_i, w, block)
            s = _orderable(jnp.where(causal(j), s, _NEG_INF))
            return jax.lax.dynamic_update_slice_in_dim(
                keys, s, j * kb, axis=1)

        dead = _orderable(jnp.full((), _NEG_INF, jnp.float32))
        keys = jax.lax.fori_loop(
            0, n_live, score_block,
            jnp.full((klen, width), dead, jnp.uint32))
    with device_scope("dsa_select"):
        return real_only(_chosen(keys, cfg.index_topk, dead))


def _attend_run(qq, q_i, w, q_pos, latent_pool, index_pool, table_row,
                cfg: LlamaConfig, pages: int, impl: str = "xla",
                interpret: bool = False, n_real=None,
                spec: Optional[LayerSpec] = None):
    """A run of queries of ONE sequence against its cached rows, this
    run's own among them: ``qq`` [K, H, C + R], ``q_i`` [K, Hi, Di], ``w``
    [K, Hi], ``q_pos`` [K] ascending, ``table_row`` [MB] (a multiple of
    ``pages``).  Returns the attended latent [K, H, C] float32 and the
    selection [K, MB x bs] bool.  Work follows the last REAL query's
    position, not MB: ``n_real`` (an int32 scalar; None: all K) says how
    many of the run's queries are anybody's, the rest being what pads a
    prompt's last chunk to its program's shape.  A padded query selects
    and attends nothing (zeros out), no key block behind the last real
    query is scored or walked, and a real query's selection and result
    are what they are with ``n_real`` None, bit for bit: the one block
    that drops is one no real query can see.

    The selection is computed the same way whatever ``impl``; the masked
    attention under it is the ``mla_prefill_attention`` kernel with
    ``impl == "pallas"`` (score tiles stay in VMEM; key blocks of its
    own size, which divides the table's) and the ``jnp`` loop
    below otherwise (the off-chip path and the kernel's oracle: every
    block's ``[K, H, keys]`` float32 scores pass through HBM)."""
    klen, heads, _ = qq.shape
    c = cfg.latent_dims(_attn_spec(cfg, spec))[0]
    bs = latent_pool.shape[1]
    kb = pages * bs                               # keys a block
    n_live = _live_blocks(q_pos, n_real, table_row.shape[0], bs, pages)
    scale = _softmax_scale(cfg, spec)

    chosen = _select_run(q_i, w, q_pos, index_pool, table_row, bs, cfg,
                         pages, n_real, n_live)

    with device_scope("mla_attn"):
        if impl == "pallas":
            from dlrover_tpu.ops.pallas.mla_prefill import (
                mla_prefill_attention,
            )

            o_lat = mla_prefill_attention(
                qq, jnp.where(chosen, 0.0, _NEG_INF), q_pos, latent_pool,
                table_row, n_real, c=c, scale=scale, interpret=interpret,
                queries_per_tile=_queries_per_tile(heads))
            return o_lat, chosen

        def attend_block(j, carry):
            m, l, acc = carry
            ids = jax.lax.dynamic_slice_in_dim(table_row, j * pages, pages)
            lat = jnp.take(latent_pool, ids, axis=0).reshape(
                kb, latent_pool.shape[-1])                    # [kb, C+R]
            s = jnp.einsum("khc,sc->khs", qq, lat.astype(qq.dtype),
                           preferred_element_type=jnp.float32) * scale
            keep = jax.lax.dynamic_slice_in_dim(chosen, j * kb, kb, axis=1)
            s = jnp.where(keep[:, None, :], s, _NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            alpha = jnp.exp(jnp.where(jnp.isfinite(m), m, safe) - safe)
            p = jnp.exp(s - safe[..., None])
            acc = acc * alpha[..., None] + jnp.einsum(
                "khs,sc->khc", p.astype(qq.dtype),
                lat[:, :c].astype(qq.dtype),
                preferred_element_type=jnp.float32)
            return m_new, alpha * l + p.sum(axis=-1), acc

        m, l, acc = jax.lax.fori_loop(
            0, n_live, attend_block,
            (jnp.full((klen, heads), _NEG_INF, jnp.float32),
             jnp.zeros((klen, heads), jnp.float32),
             jnp.zeros((klen, heads, c), jnp.float32)))
        return acc / jnp.maximum(l, 1e-30)[..., None], chosen


def _queries_per_tile(heads: int) -> int:
    """Queries a program of the run kernel holds: the kernel's own for up
    to 64 heads, fewer for more, so that ``queries x heads``, the rows of
    its matmuls and of its float32 accumulator, stay 2048."""
    from dlrover_tpu.ops.pallas.mla_prefill import QUERIES_PER_TILE

    return max(8, min(QUERIES_PER_TILE, QUERIES_PER_TILE * 64 // heads))


def _select_decode(q_i, w, index_pool, table, lengths, cfg: LlamaConfig,
                   impl: str, interpret: bool):
    """The keys one query a slot attends to, [B, rows] bool (``q_i`` [B,
    Hi, Di], ``w`` [B, Hi], ``lengths`` [B] the keys each slot sees): the
    index scores of every slot's LIVE pages (the ``paged_index_scores``
    kernel with ``impl == "pallas"``, its gather otherwise) and of each
    slot its ``index_topk`` largest (:func:`_chosen`).  None where a query
    attends to every row behind it: no indexer, or a table of no more rows
    than a query may choose.  The one selection of a latent layer's decode
    forward and a grouped-query layer's."""
    from dlrover_tpu.ops.pallas import paged_index

    if q_i is None or table.shape[1] * index_pool.shape[1] <= cfg.index_topk:
        return None
    with device_scope("dsa_index"):
        if impl == "pallas":
            scores = paged_index.paged_index_scores(
                q_i, w, index_pool, table, lengths, interpret=interpret)
        else:
            scores = paged_index.gather_index_scores(
                q_i, w, index_pool, table, lengths)
    with device_scope("dsa_select"):
        return _chosen(
            _orderable(scores), cfg.index_topk,
            _orderable(jnp.full((), _NEG_INF, jnp.float32)))


def _attend_decode(qq, q_i, w, latent_pool, index_pool, table, lengths,
                   cfg: LlamaConfig, impl: str, interpret: bool,
                   spec: Optional[LayerSpec] = None):
    """One query a slot: ``qq`` [B, H, C + R], ``q_i`` [B, Hi, Di], ``w``
    [B, Hi], ``lengths`` [B] the keys each slot sees (0: its output is
    not wanted).  Returns the attended latent [B, H, C] float32 and the
    selection, [B, rows] bool (None where a query attends to every row
    behind it: a model with no selection, or a table of no more rows
    than a query may choose)."""
    from dlrover_tpu.ops.pallas import mla_decode

    c, scale = cfg.latent_dims(_attn_spec(cfg, spec))[0], \
        _softmax_scale(cfg, spec)
    chosen = _select_decode(q_i, w, index_pool, table, lengths, cfg, impl,
                            interpret)
    bias = None if chosen is None else jnp.where(chosen, 0.0, _NEG_INF)
    with device_scope("mla_attn"):
        if impl == "pallas":
            o = mla_decode.mla_decode_attention(
                qq, latent_pool, table, lengths, bias,
                c=c, scale=scale, interpret=interpret)
        else:
            o = mla_decode.gather_latent_decode(
                qq, latent_pool, table, lengths, bias, c=c, scale=scale)
    return o, chosen


def _rows_of(chosen, length, rows: int, size: int) -> jax.Array:
    """ONE slot's selection as positions, for the witness: ``chosen``
    [W] bool (None: every row behind ``length``, of ``rows``) -> [size]
    int32 ascending, -1 behind the last (more than ``size`` chosen rows
    show their first ``size``: the caller leaves room for a tie).  Without a
    scatter, a gather or a sort: ``jnp.nonzero(size=)`` scatters (299 us
    a row of 33 k on the chip, 1.4 ms a forward) and a search over the
    prefix sums takes 47-51, this 5.7 (PR 44).  The ``j``-th chosen row
    is found in two steps of counting, first its group of 128 lanes (by
    the chosen rows ahead of each group), then its lane (by the prefix
    counts inside that group: two small matmuls on 0 / 1)."""
    if chosen is None:
        chosen = jnp.arange(rows) < length
    lanes = jnp.pad(chosen, (0, -chosen.shape[0] % _LANES)
                    ).reshape(-1, _LANES)                     # [G, 128]
    count = jnp.sum(lanes, axis=-1, dtype=jnp.int32)
    ahead = jnp.cumsum(count) - count          # chosen before a group
    j = jnp.arange(size, dtype=jnp.int32)
    group = jnp.sum(ahead[None, :] <= j[:, None], axis=-1) - 1
    hot = group[:, None] == jnp.arange(lanes.shape[0])[None, :]
    rank = j - jnp.sum(jnp.where(hot, ahead[None, :], 0), axis=-1)
    exact = dict(preferred_element_type=jnp.float32)  # sums of 0 / 1
    bits = jnp.dot(hot.astype(jnp.bfloat16), lanes.astype(jnp.bfloat16),
                   **exact)                    # [size, 128]: its group's
    upto = jnp.dot(bits.astype(jnp.bfloat16), jnp.triu(
        jnp.ones((_LANES, _LANES), jnp.bfloat16)), **exact)
    lane = jnp.sum(upto <= rank[:, None].astype(jnp.float32), axis=-1)
    return jnp.where(j < jnp.sum(count), group * _LANES + lane,
                     -1).astype(jnp.int32)


def _attn_out(lp, o_lat, cfg: LlamaConfig, dtype, h=None,
              scope: str = "mla_attn"):
    """Attended latents [B, K, H, C] -> the block's output [B, K, E]; with
    a head gate (``attn_head_gate``) each head's value times the sigmoid
    of its gate, from the layer's normed input ``h``, ahead of ``W_o``."""
    with device_scope(scope):
        o = jnp.einsum("bkhc,hcv->bkhv", o_lat.astype(dtype),
                       lp["wkv_b_v"].astype(dtype),
                       preferred_element_type=jnp.float32)
        if "head_gate" in lp:
            gate = jnp.dot(h.astype(dtype), lp["head_gate"].astype(dtype),
                           preferred_element_type=jnp.float32)
            o = o * jax.nn.sigmoid(gate)[..., None]
        o = o.astype(dtype)
        return _mm(o.reshape(*o.shape[:2], -1), lp["wo"], dtype)


def _window_mask(q_pos, key_pos, window: int):
    """[..., K, keys] bool: key ``s`` is one of the ``window`` a query at
    ``t`` sees, itself counted: ``t - window < s <= t``."""
    return (key_pos[..., None, :] <= q_pos[..., :, None]) & (
        key_pos[..., None, :] > q_pos[..., :, None] - window)


def _attend_window_decode(qq, pool, positions, active, cfg: LlamaConfig,
                          spec: LayerSpec, ring: int, impl: str,
                          interpret: bool):
    """One query a slot of a WINDOW layer: ``qq`` [B, H, W] at
    ``positions`` [B] against the slots' rings in ``pool``
    [slots x ``ring``, block, W] (``serving/paged.py``), through a
    position-ordered table of the blocks a window touches and under a
    mask of the positions inside it.  The decode kernel again, by a name of its own;
    [B, H, C] float32."""
    from dlrover_tpu.ops.pallas import mla_decode

    b, bs = qq.shape[0], pool.shape[1]
    reach = -(-(spec.window - 1) // bs) + 1
    table, base = ring_table(jnp.arange(b), positions, reach, spec.window,
                             ring, bs)
    lengths = positions.astype(jnp.int32) + 1 - base
    if active is not None:
        lengths = jnp.where(active, lengths, 0)
    key_pos = base[:, None] + jnp.arange(reach * bs)
    bias = jnp.where(key_pos > (positions[:, None] - spec.window), 0.0,
                     _NEG_INF)
    kw = dict(c=cfg.latent_dims(spec)[0], scale=_softmax_scale(cfg, spec))
    with device_scope("swa_attn"):
        if impl == "pallas":
            return mla_decode.mla_decode_attention(
                qq, pool, table, lengths, bias, interpret=interpret,
                name="mla_window_decode_attn", **kw)
        return mla_decode.gather_latent_decode(
            qq, pool, table, lengths, bias, **kw)


def _attend_window_run(qq, q_pos, pool, slot, n_real, cfg: LlamaConfig,
                       spec: LayerSpec, ring: int, impl: str,
                       interpret: bool):
    """A run of queries of ONE sequence in a WINDOW layer: ``qq`` [K, H,
    W] at ``q_pos`` [K] (ascending, the run's own rows already in the
    ring of ``slot``) against the whole ring in position order, each query
    under the mask of its window, a padded query (at or behind ``n_real``)
    attending nothing.  ``impl == "pallas"``: the run kernel over the
    ring's ``ring`` pages, by a name of its own; otherwise one dense
    masked softmax over the gathered ring.  [K, H, C] float32."""
    klen, bs = qq.shape[0], pool.shape[1]
    c, scale = cfg.latent_dims(spec)[0], _softmax_scale(cfg, spec)
    table, base = ring_table(slot[None], q_pos[:1], ring, spec.window,
                             ring, bs)
    key_pos = base[0] + jnp.arange(ring * bs)
    keep = _window_mask(q_pos, key_pos, spec.window)
    if n_real is not None:
        keep = keep & (jnp.arange(klen) < n_real)[:, None]
    with device_scope("swa_attn"):
        if impl == "pallas":
            from dlrover_tpu.ops.pallas.mla_prefill import (
                mla_prefill_attention,
            )

            return mla_prefill_attention(
                qq, jnp.where(keep, 0.0, _NEG_INF), q_pos - base[0], pool,
                table[0], n_real, c=c, scale=scale, interpret=interpret,
                queries_per_tile=_queries_per_tile(qq.shape[1]),
                name="mla_window_prefill_attn")
        rows = jnp.take(pool, table[0], axis=0).reshape(
            ring * bs, pool.shape[-1]).astype(qq.dtype)
        s = jnp.einsum("khc,sc->khs", qq, rows,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(keep[:, None, :], s, _NEG_INF)
        m = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
        o = jnp.einsum("khs,sc->khc", p.astype(qq.dtype), rows[:, :c],
                       preferred_element_type=jnp.float32)
        return o / jnp.maximum(p.sum(axis=-1), 1e-30)[..., None]


def _swiglu(h, wgu, down, dtype):
    gu = _mm(h, wgu, dtype)
    f = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], down, dtype)


# The picks from which :func:`sparse_mlp`'s buffer is cut at all (its rows
# are models/moe.py ``buffer_rows``, the rule the trained layer shares, with
# the readings that chose 3 / 2): a forward of fewer (every cell's decode
# forward: 1 280 picks of granite's 128 slots) keeps a
# buffer of every pick, in line, with no ``cond`` behind it.  With granite's
# decode buffer cut to 512 rows ``serve-rag-ssm`` read 2 137-2 168 tokens/s
# in seven runs and 1 734 and 1 965 in two more, their decode forwards at
# 44 and 38 ms for 34.5 (slots that decode alike pick alike, and the walks
# behind the ``cond`` are slow when taken); uncut, 2 140-2 150 in three and
# nothing to overflow (my chip runs, PR 51; the parent 1 974-2 000).
WALKED_FROM = 2048


def _buffer_rows(picks: int, held: int, num_experts: int) -> int:
    """Rows of :func:`sparse_mlp`'s sorted buffer for ``picks`` (tokens x
    ``top_k``): models/moe.py ``buffer_rows``, and every pick in a forward
    of fewer than ``WALKED_FROM``."""
    if picks < WALKED_FROM:
        return picks
    return buffer_rows(picks, held, num_experts)


def sparse_mlp(lp, h, cfg: LlamaConfig, dtype, counted):
    """The sparse MLP of one layer on ``h`` [B, K, E]: ``(y, [picks,
    picks on held experts, walks, 1])``, the two counts over the rows
    ``counted`` [B, K] marks (a parked slot's junk row routes too).

    The picks on the experts held here, in expert order, are ``n_held``
    rows; the sorted buffer that feeds the grouped matmuls has
    :func:`_buffer_rows` rows, ``C``, and is WALKED over them ``C`` at a
    time, ``walks = ceil(n_held / C)`` times, a count read from the routing
    on the device: one walk while the routing is near even, more where a
    hot expert overloads this share.  Every pass around the matmuls is
    over ``C`` rows: a pick on an absent expert is never placed,
    multiplied, zeroed or brought back, and no pick is dropped under any
    routing.  The first walk is in line and the others behind a ``cond``
    that near-even routing never takes: in ``serve-rag-ssm`` a ``while``
    from the first walk read 2 067 tokens/s, one from the second 2 097,
    the ``cond`` 2 144 where the parent read 1 977 (my chip runs, PR 51: a
    loop in a layer, taken or not, costs the programs around it more than
    the rows it spares).

    A pick's row needs no sort: its expert's first row plus the tokens
    ahead of its own that picked the same expert (a token picks an expert
    once).  Rows are placed by a 0 / 1 matrix ``[T, C]`` against ``x`` (an
    exact copy) and come back as ``[T, C]`` carrying ``top_p`` in float32
    times the result at ``HIGHEST``.  Timed alone at granite's chunk /
    its decode forward (128 tokens, 512 rows) / glm5's decode forward (32
    tokens x top 8, hidden 6144, 256 rows), us (my chip runs, PR 51; the
    decode forwards' buffers have since been left uncut, ``WALKED_FROM``).
    The rows: this 30 / 37 / 33, a prefix sum over tokens 46 / 40 / 34,
    one over picks 74 / 67 / 39, the parent's ``argsort`` 48 / 42 / 40.
    Placing: this 124 / 53 / 43, a gather of ``C`` rows 155 / 62 / 55 (128
    / 60 / 52 with its index given), the parent's sort and gather of
    ``T x top_k`` 252 / 97 / 58.  Bringing back: this 224 / 55 / 47, the
    weights split by hand into three bfloat16 parts 222 / 69 / 64 (one
    bfloat16 pass, not exact, is 125 / 52 / 43), a scatter-add of ``C``
    rows 379 / 118 / 83, a gather of ``top_k`` rows a token 522 / 119 /
    69, the parent's un-sort of ``T x top_k`` 566 / 141 / -.  The three
    grouped matmuls are 771 / 734 / 1 147 of the layer."""
    b, klen, e = h.shape
    t, k = b * klen, cfg.moe_top_k
    first, held = cfg.moe_experts_held or (0, cfg.num_experts)
    x = h.reshape(t, e)
    with device_scope("moe_route"):
        logits = jnp.dot(x.astype(jnp.float32), lp["router"],
                         precision=jax.lax.Precision.HIGHEST)
        top_p, top_e, _ = route(
            logits, k, cfg.moe_score_fn, cfg.moe_norm_topk_prob,
            cfg.moe_routed_scale, lp.get("select_bias"))
        # [T, k, held]: a pick on an absent expert has no column
        hot = (top_e - first)[:, :, None] == jnp.arange(held)
        is_held = hot.any(axis=-1)
        sizes = jnp.sum(hot, axis=(0, 1), dtype=jnp.int32)
        rows = counted.reshape(t, 1)
        picks = jnp.stack([k * jnp.sum(rows), jnp.sum(rows & is_held)]
                          ).astype(jnp.uint32)
    with device_scope("moe_experts"):
        c = _buffer_rows(t * k, held, cfg.num_experts)
        exact = jax.lax.Precision.HIGHEST
        xs, weights = x.astype(dtype), [
            lp[name].astype(dtype) for name in ("w_gate", "w_up", "w_down")]
        ends = jnp.cumsum(sizes)
        n_held = ends[-1]
        walks = (n_held + c - 1) // c
        ahead = jnp.dot(jnp.tri(t, k=-1, dtype=jnp.bfloat16),
                        hot.any(axis=1).astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)   # [T, held]
        row = (ends - sizes) + ahead.astype(jnp.int32)
        row = jnp.sum(jnp.where(hot, row[:, None, :], 0), axis=-1)
        row = jnp.where(is_held, row, -1).T                    # [k, T]
        weight = top_p.T

        def walk(w, y):
            lo = w * c
            here = jnp.clip(ends, lo, lo + c) - jnp.clip(ends - sizes, lo,
                                                         lo + c)
            at = (row - lo)[:, :, None] == jnp.arange(c)       # [k, T, C]
            # one bfloat16 pass copies bfloat16 rows exactly
            buf = jnp.einsum(
                "tc,te->ce", at.any(axis=0).astype(dtype), xs,
                preferred_element_type=dtype,
                precision=None if dtype == jnp.bfloat16 else exact)
            gate = grouped_matmul(buf, weights[0], here)
            up = grouped_matmul(buf, weights[1], here)
            out = grouped_matmul(jax.nn.silu(gate) * up, weights[2], here)
            # rows behind the window's groups are no expert's: never
            # written
            live = (jnp.arange(c) < n_held - lo)[:, None]
            out = jnp.where(live, out, jnp.zeros((), out.dtype))
            back = jnp.sum(jnp.where(at, weight[:, :, None], 0.0), axis=0)
            return y + jnp.dot(back, out.astype(jnp.float32),
                               precision=exact)

        y = walk(0, jnp.zeros((t, e), jnp.float32))
        if c < t * k:               # a buffer of every pick has no others
            y = jax.lax.cond(
                walks > 1,
                lambda y: jax.lax.fori_loop(1, walks, walk, y),
                lambda y: y, y)
        picks = jnp.concatenate([picks, jnp.stack(
            [walks, jnp.ones_like(walks)]).astype(jnp.uint32)])
    if "shared_wgu" in lp:
        with device_scope("moe_shared"):
            y = y + _swiglu(x, lp["shared_wgu"], lp["shared_down"],
                            dtype).astype(jnp.float32)
    return y.astype(dtype).reshape(b, klen, e), picks


def _mlp(lp, h, cfg: LlamaConfig, dtype, counted):
    if "router" in lp:
        return sparse_mlp(lp, h, cfg, dtype, counted)
    with device_scope("mlp"):
        return _swiglu(h, lp["wgu"], lp["down"], dtype), None


def _state_mixer(kind: str, lp, h, state, second, second_axis: int,
                 cfg: LlamaConfig, dtype, positions, slots, n_real, active,
                 impl: str, interpret: bool):
    """A layer that keeps a state a slot (``kind`` "kda" | "ssm" |
    "retention": ``serving/linear.py BLOCKS``) on ``h`` [B, K, E]: ``(y,
    state, second, decay)``, the layer's two per-slot arrays advanced (its
    float32 state and what ``serving/linear.py state_shapes`` names beside
    it: a convolution's last inputs, or power retention's sum of keys,
    whose slots lie along ``second_axis``) and, of a KDA or retention
    layer, what its decay was computed from and to ([B, K, 2, ...]:
    ``kda_decode``, ``retention_decode``; None of a state-space layer).
    One query a slot over every slot is a decode forward (``active`` [B] or
    None: all); a run of queries of the slots ``slots`` is a prompt chunk,
    a row at a time, from zeros where the run starts at position 0, to its
    ``n_real``-th token (None: all of it).  ``positions`` [B], each row's
    first, reach both branches: power retention rotates its queries and
    keys."""
    from dlrover_tpu.serving.linear import BLOCKS

    decode_fn, run_fn = BLOCKS[kind]
    b, klen, _ = h.shape
    if slots is None:
        if klen != 1:
            raise ValueError(
                "a run of queries over every slot is a speculative verify, "
                "and a rejected draft has already advanced a linear-"
                "attention layer's state.  Missing: roll-back of recurrent "
                "state under drafts (ROADMAP Reach A6)")
        if active is None:
            active = jnp.ones((b,), bool)
        y, state, second, decay = decode_fn(
            lp, h[:, 0], state, second, active, cfg, dtype, impl, interpret,
            positions)
        return y[:, None], state, second, \
            None if decay is None else decay[:, None]
    ys, decays = [], []
    for r in range(b):
        slot = slots[r]
        y, s_new, c_new, decay = run_fn(
            lp, h[r], jnp.take(state, slot, axis=0),
            jnp.take(second, slot, axis=second_axis),
            (start := positions[r]) == 0,
            jnp.asarray(klen, jnp.int32) if n_real is None else n_real[r],
            cfg, dtype, impl, interpret, start)
        state = state.at[slot].set(s_new)
        second = second.at[(slice(None),) * second_axis + (slot,)].set(c_new)
        ys.append(y)
        decays.append(decay)
    return jnp.stack(ys), state, second, \
        None if decays[0] is None else jnp.stack(decays)


def _gqa_attend_run(q, q_pos, k_pool, v_pool, table_row, n_real,
                    scale: float, pages: int, chosen=None):
    """A run of queries of ONE sequence against its cached K/V rows, this
    run's own among them: ``q`` [K, H, D], ``q_pos`` [K] ascending,
    ``table_row`` [MB] (a multiple of ``pages``).  Causal softmax attention
    with a running maximum over the key blocks up to the last REAL query's
    position (``n_real``: :func:`_attend_run`), never the whole table; a
    query group a KV head, the cache not expanded.  Under a selection
    (``chosen`` [K, MB x bs] bool, :func:`_select_run`: causal already) a
    query attends to its chosen keys only, MASKED-DENSE: every live block
    is scored and the rows nobody chose leave the softmax as it was.
    [K, H, D] float32."""
    klen, heads, d = q.shape
    bs, kv = k_pool.shape[1:3]
    kb = pages * bs
    n_live = _live_blocks(q_pos, n_real, table_row.shape[0], bs, pages)
    qg = q.reshape(klen, kv, heads // kv, d)

    def block(pool, j):
        ids = jax.lax.dynamic_slice_in_dim(table_row, j * pages, pages)
        return jnp.take(pool, ids, axis=0).reshape(kb, kv, d)

    def attend_block(j, carry):
        m, l, acc = carry
        s = jnp.einsum("qkgd,skd->qkgs", qg, block(k_pool, j).astype(q.dtype),
                       preferred_element_type=jnp.float32) * scale
        if chosen is None:
            keep = (j * kb + jnp.arange(kb))[None, :] <= q_pos[:, None]
        else:
            keep = jax.lax.dynamic_slice_in_dim(chosen, j * kb, kb, axis=1)
        s = jnp.where(keep[:, None, None, :], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.exp(jnp.where(jnp.isfinite(m), m, safe) - safe)
        p = jnp.exp(s - safe[..., None])
        acc = acc * alpha[..., None] + jnp.einsum(
            "qkgs,skd->qkgd", p.astype(q.dtype),
            block(v_pool, j).astype(q.dtype),
            preferred_element_type=jnp.float32)
        return m_new, alpha * l + p.sum(axis=-1), acc

    shape = qg.shape[:3]
    m, l, acc = jax.lax.fori_loop(
        0, n_live, attend_block,
        (jnp.full(shape, _NEG_INF, jnp.float32),
         jnp.zeros(shape, jnp.float32), jnp.zeros(qg.shape, jnp.float32)))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).reshape(klen, heads, d)


def _gqa_index(lp, h, cfg: LlamaConfig, spec: LayerSpec, pos, dtype):
    """The indexer of a GROUPED-QUERY layer on its normed input ``h`` [B,
    K, E] at ``pos`` [B, K]: ``q_i = W_iq h`` [B, K, Hi, W], ``k_i =
    LayerNorm(W_ik h)`` [B, K, W] (``W`` = :func:`index_row_width`: Di and
    zeros), both rotated over ALL ``Di`` dimensions, halves paired, at
    the layer's theta (``h`` where a latent layer's indexer has the
    query bottleneck ``c_q``), and ``w = W_iw h x (Hi x Di)^-0.5`` [B, K,
    Hi] float32."""
    b, k = h.shape[:2]
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    q_i = _mm(h, lp["iwq"], dtype).reshape(b, k, hi, di)
    k_i = _layernorm(_mm(h, lp["iwk"], dtype), lp["ik_norm_scale"],
                     lp["ik_norm_bias"])
    if spec.rope.rotary_fraction:
        angles = pos.astype(jnp.float32)[..., None] \
            * rope_inverse_frequencies(spec.rope, di)
        q_i = apply_rope(q_i, angles)
        k_i = apply_rope(k_i[:, :, None], angles)[:, :, 0]
    pad = index_row_width(cfg) - di
    q_i = jnp.pad(q_i.astype(dtype), ((0, 0), (0, 0), (0, 0), (0, pad)))
    k_i = jnp.pad(k_i.astype(dtype), ((0, 0), (0, 0), (0, pad)))
    w = jnp.dot(h.astype(dtype), lp["iw"].astype(dtype),
                preferred_element_type=jnp.float32) * float((hi * di) ** -0.5)
    return q_i, k_i, w


def _gqa_layer(lp, h, k_pool, v_pool, idx_pool, table, run_table,
               cfg: LlamaConfig, spec: LayerSpec, dtype, positions, pos_k,
               lengths, n_real, decode: bool, impl: str, interpret: bool,
               runs=None):
    """A GROUPED-QUERY attention layer on ``h`` [B, K, E]: ``(y, k_pool,
    v_pool, idx_pool, chosen)``, the block's output, the layer's pools with
    this forward's rows written and the keys each query attended to (None:
    every key behind it).  One query a slot over every slot with ``impl ==
    "pallas"`` is ``paged_decode_attention`` over each slot's live pages;
    every other shape walks a row's live key blocks
    (:func:`_gqa_attend_run`).  Each head of q and k RMS-normed under one
    scale where the layer has them (``q_norm``, ``k_norm``), rotated where
    the layer's ``RopeSpec`` says so, scaled by the config's ``attn_scale``
    where it states one.

    With an INDEXER (``idx_pool`` [NB, bs, :func:`index_row_width`], the
    layer's index keys under the same block table; None: the layer has
    none) a query attends to its ``index_topk`` chosen keys only, the
    selection a latent layer makes (:func:`_select_decode`,
    :func:`_select_run`) from index queries projected from ``h``.  Decode
    on the chip streams the live K/V pages under the mask of the chosen
    rows (``paged_decode_attention`` with a ``bias``), a run of leading
    pages that several slots' table rows hold in common once for all of
    them (``runs``: ``paged_attention.shared_runs``, which the forward
    derives once for its layers); a run of queries walks its live key
    blocks under the mask."""
    from dlrover_tpu.serving.model import _qkv

    b, klen, _ = h.shape
    d = cfg.head_dim_
    with device_scope("attn_proj"):
        q, k, v = _qkv(lp, h, cfg, dtype)
        if "q_norm" in lp:             # a head at a time, before rotation
            q = _rmsnorm(q, lp["q_norm"], cfg.rms_norm_eps)
            k = _rmsnorm(k, lp["k_norm"], cfg.rms_norm_eps)
        if spec.rope.rotary_fraction:
            angles = pos_k.astype(jnp.float32)[..., None] \
                * rope_inverse_frequencies(spec.rope, d)
            q, k = apply_rope(q, angles), apply_rope(k, angles)
        q = q.astype(dtype)
    k_pool = scatter_tokens(k_pool, table, k.astype(k_pool.dtype), positions)
    v_pool = scatter_tokens(v_pool, table, v.astype(v_pool.dtype), positions)
    scale = float(d ** -0.5 if cfg.attn_scale is None else cfg.attn_scale)
    chosen = None
    if idx_pool is not None:
        with device_scope("dsa_index"):
            q_i, k_i, w = _gqa_index(lp, h, cfg, spec, pos_k, dtype)
        idx_pool = scatter_tokens(idx_pool, table,
                                  k_i.astype(idx_pool.dtype), positions)
        o, chosen = _gqa_attend_selected(
            q, q_i, w, k_pool, v_pool, idx_pool, table, run_table, cfg,
            pos_k, lengths, n_real, scale, decode, impl, interpret, runs)
    else:
        with device_scope("paged_attn"):
            if decode and impl == "pallas":
                from dlrover_tpu.ops.pallas.paged_attention import (
                    paged_decode_attention,
                )

                o = paged_decode_attention(
                    q[:, 0], k_pool, v_pool, table, lengths, scale=scale,
                    interpret=interpret)[:, None]
            else:
                if decode:
                    run_table = _pad_table(table, KEY_BLOCK_PAGES)
                o = jax.lax.map(
                    lambda a: _gqa_attend_run(a[0], a[1], k_pool, v_pool,
                                              a[2], a[3], scale,
                                              KEY_BLOCK_PAGES),
                    (q, pos_k, run_table, n_real))
    o = o.astype(dtype).reshape(b, klen, -1)
    with device_scope("attn_proj"):
        return _mm(o, lp["wo"], dtype), k_pool, v_pool, idx_pool, chosen


def _gqa_attend_selected(q, q_i, w, k_pool, v_pool, idx_pool, table,
                         run_table, cfg: LlamaConfig, pos_k, lengths, n_real,
                         scale: float, decode: bool, impl: str,
                         interpret: bool, runs=None):
    """The attention of a grouped-query layer UNDER ITS SELECTION: ``(o
    [B, K, H, D] float32, chosen)``.  Decode with ``impl == "pallas"``:
    ``chosen`` [B, rows] from :func:`_select_decode` (None while the table
    holds no more rows than a query may choose), then the decode kernel
    over the slots' live pages under the mask, the pages of a shared run
    once (``runs``: the forward's ``shared_runs``).  Every other shape, a row
    at a time: ``chosen`` [B, K, rows] from :func:`_select_run`, then the
    walk of the row's live key blocks under it."""
    bs = k_pool.shape[1]
    if decode and impl == "pallas":
        from dlrover_tpu.ops.pallas.paged_attention import (
            paged_decode_attention,
        )

        chosen = _select_decode(q_i[:, 0], w[:, 0], idx_pool, table, lengths,
                                cfg, impl, interpret)
        with device_scope("paged_attn"):
            bias = None if chosen is None else jnp.where(chosen, 0.0,
                                                         _NEG_INF)
            o = paged_decode_attention(
                q[:, 0], k_pool, v_pool, table, lengths, scale=scale,
                interpret=interpret, bias=bias, runs=runs)[:, None]
        return o, chosen
    if decode:
        run_table = _pad_table(table, KEY_BLOCK_PAGES)

    def row(a):
        q_r, qi_r, w_r, pos_r, table_r, n_r = a
        chosen = _select_run(qi_r, w_r, pos_r, idx_pool, table_r, bs, cfg,
                             KEY_BLOCK_PAGES, n_r, _live_blocks(
                                 pos_r, n_r, table_r.shape[0], bs,
                                 KEY_BLOCK_PAGES))
        with device_scope("paged_attn"):
            return _gqa_attend_run(q_r, pos_r, k_pool, v_pool, table_r, n_r,
                                   scale, KEY_BLOCK_PAGES, chosen), chosen

    rows = (q, q_i, w, pos_k, run_table, n_real)
    if q.shape[0] == 1:
        # the engine's prompt chunks are one row a dispatch: no loop, and
        # no copy of the row's [K, table rows] selection into a stack
        o, chosen = jax.tree_util.tree_map(
            lambda x: x[None],
            row(jax.tree_util.tree_map(lambda x: x[0], rows)))
    else:
        with device_scope("paged_attn"):      # the loop's own copies
            o, chosen = jax.lax.map(row, rows)
    return o, (chosen[:, 0] if decode else chosen)


def _residual(x, y, cfg: LlamaConfig):
    """The residual stream with a branch's output added, times the
    config's ``residual_mult`` where it has one."""
    if cfg.residual_mult == 1.0:
        return x + y
    return (x.astype(jnp.float32) + cfg.residual_mult
            * y.astype(jnp.float32)).astype(x.dtype)


def _window_layer(lp, h, held, cfg: LlamaConfig, spec: LayerSpec, dtype,
                  positions, pos_k, slots, n_real, active, decode: bool,
                  impl: str, interpret: bool):
    """A WINDOW layer of latent attention on ``h`` [B, K, E]: ``(y,
    held)``, the block's output and the layer's rings ``held`` [slots,
    ring, block, W] with this forward's rows written (``serving/paged.py``:
    no table, the row of position ``p`` in its slot's ring block ``(p //
    block) % ring``).  One query a slot over every slot is a decode
    forward (``active`` [B] or None: all; any other slot's row is written
    nowhere); a run of queries of the slots ``slots`` is a prompt chunk, a
    row at a time, its first ``n_real`` queries real."""
    b, klen, _ = h.shape
    if slots is None and not decode:
        raise ValueError(
            "a run of queries over every slot is a speculative verify, and "
            "a window layer's ring keeps no row a rejected draft could "
            "hand back.  Missing: a window under drafts (ROADMAP Reach A4)")
    n_slots, ring, bs, width = held.shape
    pool = held.reshape(n_slots * ring, bs, width)
    qq, row, _, _, _ = _projections(lp, h, cfg, spec, pos_k, dtype)
    if decode:
        real = jnp.ones((b, 1), bool) if active is None else active[:, None]
        pool = scatter_ring(pool, jnp.arange(b), row.astype(pool.dtype),
                            positions, real, ring)
        o_lat = _attend_window_decode(
            qq[:, 0], pool, positions, active, cfg, spec, ring, impl,
            interpret)[:, None]
    else:
        real = jnp.ones((b, klen), bool) if n_real is None else (
            jnp.arange(klen)[None, :] < n_real[:, None])
        pool = scatter_ring(pool, slots, row.astype(pool.dtype), positions,
                            real, ring)
        o_lat = jax.lax.map(
            lambda a: _attend_window_run(
                a[0], a[1], pool, a[2], a[3], cfg, spec, ring, impl,
                interpret),
            (qq, pos_k, slots, n_real))
    y = _attn_out(lp, o_lat, cfg, dtype, h, "swa_attn")
    return y, pool.reshape(held.shape)


def _seen_selection(chosen, watch, decode: bool, rows: int, lengths,
                    cfg: LlamaConfig, packed: bool = False):
    """One layer's selection for the WITNESS: the watched row of
    ``chosen`` (decode: [B, rows] or None, every row behind the length; a
    run: [B, K, rows]).  Decode hands it back as positions
    (:func:`_rows_of`), the watched slot's row alone, with room for the
    rows that tie the ``index_topk``-th, which are chosen and attended
    with it (a tie at the threshold is one query in a few thousand at 33 k
    float32 scores: every window has some).  ``packed``: a run's mask as
    ``jnp.packbits`` of it, a layer at a time (a grouped-query model's
    layers: stacking eight layers' [512, 33 792] masks unpacked was 1.2 ms
    of a 29 ms chunk under no scope; my chip run, PR 58)."""
    seen_row = None if chosen is None else jnp.take(chosen, watch, axis=0)
    if decode:
        with device_scope("dsa_select"):
            seen_row = _rows_of(
                seen_row, jnp.take(lengths, watch), rows,
                min(cfg.index_topk + _LANES, rows))
    elif packed:
        with device_scope("dsa_select"):
            seen_row = jnp.packbits(seen_row, axis=-1)
    return seen_row


def _pad_table(table: jax.Array, pages: int) -> jax.Array:
    """The table padded with the trash block to whole key blocks."""
    pad = -table.shape[1] % pages
    if not pad:
        return table
    return jnp.concatenate(
        [table, jnp.zeros((table.shape[0], pad), table.dtype)], axis=1)


def verify_step(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    cache: Dict[str, Any],   # {"latent_pool", "index_pool" (or "k_pool",
    tokens: jax.Array,       #   "v_pool"): lists, an ATTENTION layer
                             #   each; "<kind>_state", "<kind>_conv" (or
                             #   "_keysum"): a layer of that kind each, by
                             #   slot; "table" (with a pool); "moe_picks"}
    positions: jax.Array,
    slots: Optional[jax.Array] = None,
    logits_index: Optional[jax.Array] = None,
    attention_impl: str = "xla",
    kernel_interpret: bool = False,
    active: Optional[jax.Array] = None,
):
    """``serving/model.py verify_step`` for a latent model, argument for
    argument; one query a slot over every slot (decode) takes the decode
    path, every other shape the query-run path (module docstring).

    A cache that carries ``watch_slot`` (an int32 scalar: the engine's
    ``watch``) comes back with ``witness``, what THIS forward did for that
    slot's row: the keys each query attended to, a layer (decode: ``rows``
    [layers, S] int32 positions, ascending, -1 none: that slot's row of
    the mask alone, :func:`_rows_of`, ``S`` being ``index_topk`` and 128
    more for ties at the threshold; a run: ``chosen_bits`` [layers,
    K, table rows / 8] uint8, ``jnp.packbits`` of the mask), and the first
    sparse MLP's normed input and output (``sparse_in``, ``sparse_out``
    [K, E]); of a model with window layers also ``full_out`` and
    ``window_out`` [K, E], the first full and the first window layer's
    attention output for the slot's row, ``window_in`` [K, E], that window
    layer's normed input, and the slot's ``logits`` beside the rows.  A slot that is not among the rows
    leaves junk there.  A model
    with NO selection has no rows to tell of (every query attends to every
    row behind it) and hands back, in their place, what the selection
    otherwise makes the only judge of: ``logits`` [V] float32, the slot's
    own (decode: this forward's; a run: at its ``logits_index``); and of a
    model with linear-attention layers also ``kda_state`` [2, H, d, d],
    the slot's recurrent state behind this forward of the first and the
    last such layer, and ``kda_decay`` [2, H, d], the first such layer's
    ``(f, g)`` at the slot's query (a run: its last real one): the
    log-decay ``g`` beside the float32 sums ``f`` it is a function of; of
    a model of power-retention layers ``retention_state`` [2, Hk, tiles, d,
    d], ``retention_keysum`` [2, Hk, tiles, d] and ``retention_decay`` [2,
    Hk] (the gate's sums and its logarithm) alike; of
    a model with state-space layers ``ssm_state`` [2, H, P, N] and
    ``ssm_conv`` [2, taps - 1, H P + 2 N], the slot's state and convolution
    rows behind this forward of the first and the last such layer."""
    dtype = cfg.dtype
    b, klen = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)            # [B, K, E]
    if cfg.embedding_mult != 1.0:
        x = (x.astype(jnp.float32) * cfg.embedding_mult).astype(x.dtype)
    pos_k = positions[:, None] + jnp.arange(klen)[None, :]   # [B, K]
    # (None: a model whose every layer keeps a state a slot has no rows,
    # no pools and no table)
    table = cache.get("table")
    if slots is not None and table is not None:
        table = jnp.take(table, slots, axis=0)
    decode = klen == 1 and slots is None and logits_index is None
    run_table = n_real = None
    if decode:
        lengths = positions.astype(jnp.int32) + 1
        counted = jnp.ones((b, 1), bool)
        if active is not None:
            lengths = jnp.where(active, lengths, 0)
            counted = active[:, None]
    else:
        lengths = None
        if table is not None:
            run_table = _pad_table(table, KEY_BLOCK_PAGES)
        counted = jnp.ones((b, klen), bool) if logits_index is None else (
            jnp.arange(klen)[None, :] <= logits_index[:, None])
        # a row's real queries: behind ``logits_index`` a run is padding
        n_real = None if logits_index is None \
            else logits_index.astype(jnp.int32) + 1
    runs = None
    if decode and attention_impl == "pallas" and "index_pool" in cache \
            and not cfg.kv_lora_rank:
        # the slots whose table rows begin alike, once for every
        # grouped-query layer's kernel under its selection
        from dlrover_tpu.ops.pallas.paged_attention import shared_runs

        with device_scope("paged_attn"):
            runs = shared_runs(table, lengths, cache["k_pool"][0].shape[1])
    picks = cache.get("moe_picks")
    watch = cache.get("watch_slot")
    if watch is not None:
        watch = jnp.clip(watch, 0, b - 1) if slots is None \
            else jnp.argmax(slots == watch)
    latent_pools, index_pools, selections, seen = [], [], [], {}
    k_pools, v_pools = [], []
    states, convs, rings = [], [], []
    kind = next((s.mixer for s in cfg.layer_specs if s.mixer != "attn"),
                None)                  # the one kind of state a slot
    if kind is not None:
        from dlrover_tpu.serving.linear import state_shapes

        # the array a layer of this kind keeps beside its state, and the
        # axis its slots lie along
        name, held = list(state_shapes(cfg, b, kind).items())[1]
        second, second_axis = kind + "_" + name, held.slot_axis
    for lp, spec in zip(params["layers"], cfg.layer_specs):
        h = _rmsnorm(x, lp["input_norm"], cfg.rms_norm_eps).astype(dtype)
        if spec.mixer != "attn":
            y, state, conv, decay = _state_mixer(
                spec.mixer, lp, h, cache[kind + "_state"][len(states)],
                cache[second][len(convs)], second_axis, cfg, dtype,
                positions, slots, None if decode else n_real,
                active if decode else None, attention_impl,
                kernel_interpret)
            if watch is not None and not states and decay is not None:
                # the first such layer's decay at the watched row's query
                # (a run: its last real one), beside what it came from
                at = jnp.zeros((), jnp.int32) if logits_index is None \
                    else jnp.take(logits_index, watch).astype(jnp.int32)
                seen[kind + "_decay"] = jnp.take(
                    jnp.take(decay, watch, axis=0), at, axis=0)
            states.append(state)
            convs.append(conv)
            x = _residual(x, y, cfg)
        elif not cfg.kv_lora_rank:
            indexed = cfg.latent_dims(spec)[2]
            y, k_new, v_new, idx, chosen = _gqa_layer(
                lp, h, cache["k_pool"][len(k_pools)],
                cache["v_pool"][len(v_pools)],
                cache["index_pool"][len(index_pools)] if indexed else None,
                table, run_table, cfg, spec, dtype, positions, pos_k,
                lengths, n_real, decode, attention_impl, kernel_interpret,
                runs)
            if watch is not None and indexed:
                selections.append(_seen_selection(
                    chosen, watch, decode, table.shape[1] * k_new.shape[1],
                    lengths, cfg, packed=True))
            k_pools.append(k_new)
            v_pools.append(v_new)
            if idx is not None:
                index_pools.append(idx)
            x = _residual(x, y, cfg)
        elif spec.window:
            y, held = _window_layer(
                lp, h, cache["window_ring"][len(rings)], cfg, spec, dtype,
                positions, pos_k, slots, None if decode else n_real,
                active if decode else None, decode, attention_impl,
                kernel_interpret)
            if watch is not None and not rings:
                # the first window layer's normed input and output for
                # the watched row
                seen.update(window_in=jnp.take(h, watch, axis=0),
                            window_out=jnp.take(y, watch, axis=0))
            rings.append(held)
            x = _residual(x, y, cfg)
        else:
            i = len(latent_pools)
            qq, row, q_i, k_i, w = _projections(lp, h, cfg, spec, pos_k,
                                                dtype)
            lat = cache["latent_pool"][i]
            lat = scatter_tokens(lat, table, row.astype(lat.dtype),
                                 positions)
            idx = None
            if k_i is not None:
                idx = cache["index_pool"][len(index_pools)]
                idx = scatter_tokens(idx, table, k_i.astype(idx.dtype),
                                     positions)
            if decode:
                o_lat, chosen = _attend_decode(
                    qq[:, 0], None if q_i is None else q_i[:, 0],
                    None if w is None else w[:, 0], lat, idx, table,
                    lengths, cfg, attention_impl, kernel_interpret, spec)
                o_lat = o_lat[:, None]
            else:
                o_lat, chosen = jax.lax.map(
                    lambda a: _attend_run(a[0], a[1], a[2], a[3], lat, idx,
                                          a[4], cfg, KEY_BLOCK_PAGES,
                                          attention_impl, kernel_interpret,
                                          a[5], spec),
                    (qq, q_i, w, pos_k, run_table, n_real))
            if watch is not None and k_i is not None:
                selections.append(_seen_selection(
                    chosen, watch, decode, table.shape[1] * lat.shape[1],
                    lengths, cfg))
            y = _attn_out(lp, o_lat, cfg, dtype, h)
            if watch is not None and "window_ring" in cache \
                    and not latent_pools:
                # the first full layer's output for the watched row
                seen["full_out"] = jnp.take(y, watch, axis=0)
            x = _residual(x, y, cfg)
            latent_pools.append(lat)
            if idx is not None:
                index_pools.append(idx)
        h = _rmsnorm(x, lp["post_norm"], cfg.rms_norm_eps).astype(dtype)
        y, n = _mlp(lp, h, cfg, dtype, counted)
        if n is not None and picks is not None:
            picks = picks + n
        if n is not None and watch is not None and "sparse_in" not in seen:
            seen.update(sparse_in=jnp.take(h, watch, axis=0),
                        sparse_out=jnp.take(y, watch, axis=0))
        x = _residual(x, y, cfg)

    x = _rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    if logits_index is not None:
        x = jnp.take_along_axis(
            x, logits_index.astype(jnp.int32)[:, None, None], axis=1)
    logits = _lm_head(params, x.astype(dtype), cfg)
    if cfg.kv_lora_rank:
        out_cache = dict(cache, latent_pool=latent_pools)
    elif k_pools:
        out_cache = dict(cache, k_pool=k_pools, v_pool=v_pools)
    else:                              # no layer that caches rows
        out_cache = dict(cache)
    if states:
        out_cache.update({kind + "_state": states, second: convs})
        if watch is not None:
            # the watched slot's state behind this forward, of the first
            # and the last layer that keeps one
            at = watch if slots is None else jnp.take(slots, watch)
            seen[kind + "_state"] = jnp.stack(
                [jnp.take(states[0], at, axis=0),
                 jnp.take(states[-1], at, axis=0)])
            if kind == "ssm":
                # (a head's [P, N], not the kernels' kept layout)
                from dlrover_tpu.ops.pallas.ssm import unpack_state

                seen["ssm_state"] = unpack_state(seen["ssm_state"],
                                                 cfg.ssm_head_dim)
            if kind != "kda":
                # ... and what the layer keeps beside it, its convolution
                # rows or its sum of keys (KDA's witness has the decay's
                # two sides in their place)
                seen[second] = jnp.stack(
                    [jnp.take(convs[0], at, axis=second_axis),
                     jnp.take(convs[-1], at, axis=second_axis)])
    if cfg.index_topk:
        out_cache["index_pool"] = index_pools
    if rings:
        out_cache["window_ring"] = rings
    if picks is not None:
        out_cache["moe_picks"] = picks
    if watch is not None and cfg.index_topk:
        chosen = jnp.stack(selections)
        if rings:
            # a model of window layers beside layers with a selection:
            # the slot's logits too, as a model with no selection gives
            seen["logits"] = jnp.take(logits[:, 0], watch, axis=0)
        out_cache["witness"] = dict(seen, **(
            {"rows": chosen} if decode
            # (a grouped-query model's layers packed theirs one by one)
            else {"chosen_bits": jnp.packbits(chosen, axis=-1)
                  if cfg.kv_lora_rank else chosen}))
    elif watch is not None:
        out_cache["witness"] = dict(
            seen, logits=jnp.take(logits[:, 0], watch, axis=0))
    return logits, out_cache


def prefill(params: Dict[str, Any], cfg: LlamaConfig, tokens: jax.Array,
            real_len: jax.Array):
    """``serving/model.py prefill`` for a latent model: a causal pass over
    a group of right-padded prompts with no cache behind them; returns
    (last logits [G, V], per-layer cache rows [G, Lp, C + R], per-layer
    index keys [G, Lp, Di]: none of a model with no indexer) for the
    engine to scatter.  A prompt is one key block here, so scores are
    [Lp, heads, Lp]: buckets the size of a
    prefill chunk, which is all an engine with ``prefill_chunk`` sends.
    Its attention stays the ``jnp`` loop of :func:`_attend_run` whatever
    the engine's ``attention_impl`` (one block of the prompt's own rows,
    no pool behind it: nothing for the kernel to save).
    The experts' picks of this path are not counted."""
    dtype = cfg.dtype
    if any(s.mixer != "attn" or s.window for s in cfg.layer_specs) \
            or not cfg.kv_lora_rank:
        raise ValueError(
            "a bucketed prefill hands back cache rows to scatter into "
            "blocks, and a linear-attention or state-space layer keeps a "
            "state a slot, a window layer a ring a slot; the grouped-query "
            "block of this loop has no bucketed path either: their prompts "
            "go through the chunked path "
            "(InferenceEngine(prefill_chunk=...))")
    g, lp_len = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    pos = jnp.broadcast_to(jnp.arange(lp_len), (g, lp_len))
    one_page = jnp.zeros((g, 1), jnp.int32)
    counted = jnp.ones((g, lp_len), bool)
    rows, keys = [], []
    for lp, spec in zip(params["layers"], cfg.layer_specs):
        h = _rmsnorm(x, lp["input_norm"], cfg.rms_norm_eps).astype(dtype)
        qq, row, q_i, k_i, w = _projections(lp, h, cfg, spec, pos, dtype)
        # each prompt's own rows as a pool of one page
        o_lat, _ = jax.lax.map(
            lambda a: _attend_run(
                a[0], a[1], a[2], a[3], a[4][None],
                None if a[5] is None else a[5][None], a[6], cfg, 1,
                spec=spec),
            (qq, q_i, w, pos, row, k_i, one_page))
        x = x + _attn_out(lp, o_lat, cfg, dtype, h)
        h = _rmsnorm(x, lp["post_norm"], cfg.rms_norm_eps).astype(dtype)
        x = x + _mlp(lp, h, cfg, dtype, counted)[0]
        rows.append(row)
        if k_i is not None:
            keys.append(k_i)
    x = _rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    last = jnp.take_along_axis(
        x, (jnp.atleast_1d(real_len).astype(jnp.int32) - 1)[:, None, None],
        axis=1)
    return _lm_head(params, last.astype(dtype), cfg)[:, 0, :], rows, keys
