"""The plain reference: GLM-5's decoder layer (``glm_moe_dsa``) as its
config.json describes it, one chip's share of the experts, nothing else.

Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no cache, no kernel, no code
of ``dlrover_tpu/serving`` or ``dlrover_tpu/ops``.  ``x`` is a layer's
input after ``input_layernorm`` (RMSNorm), positions ``t``, ``s``:

1. latent attention.  ``c_q = RMSNorm(W_qa x)``; ``q = W_qb c_q`` in heads
   of ``[q_nope | q_rope]``; ``[c | k_r] = W_kva x``, ``c_kv = RMSNorm(c)``,
   ``k_r = RoPE(k_r)`` (one row for all heads), ``q_rope = RoPE(q_rope)``.
   RoPE at theta, plain, ADJACENT pairs ``(x_2i, x_2i+1)`` rotated.  A
   head: ``[k_nope_h | v_h] = W_kvb,h c_kv``; ``score_h[t, s] = (q_nope_h[t]
   . k_nope_h[s] + q_rope_h[t] . k_r[s]) / sqrt(nope + rope)``; softmax
   over ``s in S_t`` only; ``o_h = sum p v_h``; output ``W_o concat(o_h)``.
   (The UNABSORBED form: keys and values of every head are made.)
2. the indexer.  ``q_i = W_iq c_q`` in heads; ``k_i = LayerNorm(W_ik x)``
   (scale and bias, eps 1e-6), one row a token; the first ``rope``
   dimensions of each rotated as above; ``w = (W_iw x) / sqrt(heads) /
   sqrt(size)``.  ``I[t, s] = sum_h w[t, h] relu(q_i[t, h] . k_i[s])`` for
   ``s <= t``; ``S_t`` = the ``min(topk, t + 1)`` largest ``I[t, .]``
   (computed as ``I >= the topk-th largest``: equal scores at the
   threshold all stay).
3. the MLP.  Leading layers: SwiGLU.  Sparse layers: ``sc = sigmoid(W_r
   x)``; the ``top_k`` largest of ``sc + b`` chosen; weights ``sc[chosen]
   / sum sc[chosen] x routed_scale``; ``y = sum over chosen AND HELD e of
   weight_e SwiGLU_e(x)`` + the shared expert on every token.  What the
   absent experts would add is left out (``held`` = the share's experts,
   ``first .. first + count - 1``).
4. pre-norm residual blocks, ``post_attention_layernorm`` before the MLP,
   a final RMSNorm, an untied head.

Departures from the published model, each also under ``assumed`` in
``perfbench/configs/glm5-serve.json``: no multi-token-prediction module
(not part of the next-token forward pass); the indexer in float32 with no
Hadamard rotation (orthogonal: every ``q . k`` is what it was) and no fp8
codes; ``k_norm`` a LayerNorm with scale and bias.

Sizes come as a plain dict ``dims`` (:func:`dims_of`).  A sequence of 33 k
positions fits because queries go in blocks against the keys behind them,
heads in groups, experts one at a time; weights arrive a layer at a time
in the run's dtype and are up-cast here.

Several sequences that share a head go through as ONE packed batch, the
head once: ``positions`` and ``segments`` a token (segment 0 the head,
1.. the tails, each tail's positions going on from the head's last);
token ``t`` sees token ``s`` when ``s`` comes no later in the batch and is
of the head or of ``t``'s own tail.  A plain sequence is one segment.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISION = "highest"   # every matmul here; read when a program is traced
Q_BLOCK = 256      # queries a block
HEAD_GROUP = 8     # heads whose keys and values exist at once
KEY_BUCKET = 4096  # a query block sees keys up to a multiple of this


def dims_of(config: dict) -> dict:
    """The sizes the reference reads, from a configuration file's keys."""
    held = config.get("experts_held") or [0, config["n_routed_experts"]]
    return {
        "heads": config["num_attention_heads"],
        "nope": config["qk_nope_head_dim"], "rope": config["qk_rope_head_dim"],
        "latent": config["kv_lora_rank"], "v": config["v_head_dim"],
        "index_heads": config["index_n_heads"],
        "index_dim": config["index_head_dim"],
        "topk": config["index_topk"],
        "theta": float(config["rope_parameters"]["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "experts": config["n_routed_experts_published"],
        "top_k": config["num_experts_per_tok"],
        "scale": float(config["routed_scaling_factor"]),
        "first": int(held[0]), "held": int(held[1]),
    }


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def layernorm(x, w, b, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def rope(x, positions, theta, rotary):
    """x [T, ..., d]; the first ``rotary`` dimensions rotate in adjacent
    pairs (x_2i, x_2i+1) by positions * theta^(-2i / rotary)."""
    inv = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32)
                           / rotary))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (rotary // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0:rotary:2], x[..., 1:rotary:2]
    rot = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                    axis=-1).reshape(*x.shape[:-1], rotary)
    return jnp.concatenate([rot, x[..., rotary:]], axis=-1)


def _static(fn):
    return functools.partial(jax.jit, static_argnames=("d",))(fn)


class _Dims(dict):
    """``dims`` as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


@_static
def _project(x, lp, pos, d):
    """x [T, E] normed, at positions ``pos`` [T] -> q_nope [T, H, nope],
    q_rope [T, H, rope], c_kv [T, C], k_r [T, rope], q_i [T, Hi, Di], k_i
    [T, Di], w [T, Hi]."""
    with jax.default_matmul_precision(PRECISION):
        a, ix = _f32(lp["attn"]), _f32(lp["indexer"])
        c_q = rmsnorm(x @ a["q_a_proj"]["kernel"], a["q_a_norm"]["scale"],
                      d["eps"])
        q = jnp.einsum("tq,qhd->thd", c_q, a["q_b_proj"]["kernel"])
        q_nope = q[..., :d["nope"]]
        q_rope = rope(q[..., d["nope"]:], pos, d["theta"], d["rope"])
        ckv = x @ a["kv_a_proj"]["kernel"]
        c_kv = rmsnorm(ckv[:, :d["latent"]], a["kv_a_norm"]["scale"],
                       d["eps"])
        k_r = rope(ckv[:, d["latent"]:], pos, d["theta"], d["rope"])
        q_i = rope(jnp.einsum("tq,qhd->thd", c_q, ix["wq_b"]["kernel"]),
                   pos, d["theta"], d["rope"])
        k_i = rope(layernorm(x @ ix["wk"]["kernel"], ix["k_norm"]["scale"],
                             ix["k_norm"]["bias"]),
                   pos, d["theta"], d["rope"])
        w = (x @ ix["weights_proj"]["kernel"]) / jnp.sqrt(
            float(d["index_heads"])) / jnp.sqrt(float(d["index_dim"]))
        return q_nope, q_rope, c_kv, k_r, q_i, k_i, w


@_static
def _index_block(q_i, w, k_i, start, seg_q, seg_k, d):
    """Index scores of one query block (the batch's tokens ``start ..``)
    against the keys given: ``I`` [Q, S] (minus infinity where the query
    does not see the key) and each query's threshold, the ``topk``-th
    largest (minus infinity while a query sees fewer)."""
    with jax.default_matmul_precision(PRECISION):
        s = jnp.einsum("qhd,sd->qhs", q_i, k_i)
        scores = jnp.sum(jnp.maximum(s, 0.0) * w[:, :, None], axis=1)
        t = start + jnp.arange(q_i.shape[0])
        sees = (jnp.arange(k_i.shape[0])[None, :] <= t[:, None]) & (
            (seg_k[None, :] == 0) | (seg_k[None, :] == seg_q[:, None]))
        scores = jnp.where(sees, scores, -jnp.inf)
        k = min(d["topk"], k_i.shape[0])
        kth = jax.lax.top_k(scores, k)[0][:, -1]
        return scores, kth


@_static
def _heads_kv(c_kv, kv_b, d):
    """[k_nope_h | v_h] = W_kvb,h c_kv for a group of heads."""
    with jax.default_matmul_precision(PRECISION):
        kv = jnp.einsum("sc,chd->shd", c_kv, kv_b.astype(jnp.float32))
        return kv[..., :d["nope"]], kv[..., d["nope"]:]


@_static
def _attend_block(q_nope, q_rope, k_nope, k_r, v, chosen, d):
    """Softmax attention of one query block and one head group over the
    chosen keys: [Q, G, v]."""
    with jax.default_matmul_precision(PRECISION):
        s = (jnp.einsum("qhd,shd->hqs", q_nope, k_nope)
             + jnp.einsum("qhd,sd->hqs", q_rope, k_r)
             ) / jnp.sqrt(float(d["nope"] + d["rope"]))
        s = jnp.where(chosen[None], s, -jnp.inf)
        return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(s, axis=-1), v)


def attention(x, lp, d, selection_of=None, positions=None, segments=None):
    """The attention block's output [T, E] for normed input ``x``.
    ``selection_of=(first, count)`` also returns, for those tokens of the
    batch, ``(I [count, T], chosen [count, T])``."""
    d = _Dims(d)
    t_len = x.shape[0]
    pos = jnp.arange(t_len) if positions is None else jnp.asarray(positions)
    seg = jnp.zeros(t_len, jnp.int32) if segments is None \
        else jnp.asarray(segments, jnp.int32)
    q_nope, q_rope, c_kv, k_r, q_i, k_i, w = _project(x, lp, pos, d)
    blocks = []          # (start, keys seen, chosen [Q, keys])
    kept_i, kept_s = [], []
    for s0 in range(0, t_len, Q_BLOCK):
        q_n = min(Q_BLOCK, t_len - s0)
        seen = min(t_len, -(-(s0 + q_n) // KEY_BUCKET) * KEY_BUCKET)
        scores, kth = _index_block(
            q_i[s0:s0 + q_n], w[s0:s0 + q_n], k_i[:seen], s0,
            seg[s0:s0 + q_n], seg[:seen], d)
        chosen = (scores >= kth[:, None]) & (scores > -jnp.inf)
        blocks.append((s0, q_n, seen, chosen))
        if selection_of and s0 < sum(selection_of) \
                and s0 + q_n > selection_of[0]:
            pad = ((0, 0), (0, t_len - seen))
            kept_i.append(jnp.pad(scores, pad, constant_values=-jnp.inf))
            kept_s.append(jnp.pad(chosen, pad))
    del q_i, k_i, w                   # the selection is made
    kv_b = lp["attn"]["kv_b_proj"]["kernel"]
    w_o = lp["attn"]["o_proj"]["kernel"]
    out = 0.0        # W_o concat(o_h), summed a group of heads at a time
    for h0 in range(0, d["heads"], HEAD_GROUP):
        hs = slice(h0, h0 + HEAD_GROUP)
        k_nope, v = _heads_kv(c_kv, kv_b[:, hs], d)
        o = jnp.concatenate([
            _attend_block(q_nope[s0:s0 + q_n, hs], q_rope[s0:s0 + q_n, hs],
                          k_nope[:seen], k_r[:seen], v[:seen], chosen, d)
            for s0, q_n, seen, chosen in blocks], axis=0)   # [T, G, v]
        out = out + _o_proj(o, w_o[hs])
    if not selection_of:
        return out
    first, count = selection_of
    lo = (first // Q_BLOCK) * Q_BLOCK
    rows = slice(first - lo, first - lo + count)
    return out, (jnp.concatenate(kept_i)[rows], jnp.concatenate(kept_s)[rows])


@jax.jit
def _o_proj(o, w):
    with jax.default_matmul_precision(PRECISION):
        return jnp.einsum("thv,hve->te", o, w.astype(jnp.float32))


@jax.jit
def _swiglu(x, gate, up, down):
    with jax.default_matmul_precision(PRECISION):
        gate, up, down = _f32((gate, up, down))
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down


@_static
def _route(x, router, bias, d):
    """Weights [T, experts] float32: 0 but on a token's chosen experts."""
    with jax.default_matmul_precision(PRECISION):
        sc = jax.nn.sigmoid(x @ router.astype(jnp.float32))
        _, chosen = jax.lax.top_k(sc + bias.astype(jnp.float32), d["top_k"])
        picked = jnp.take_along_axis(sc, chosen, axis=-1)
        weights = picked / jnp.sum(picked, axis=-1, keepdims=True) \
            * d["scale"]
        rows = jnp.arange(x.shape[0])[:, None]
        return jnp.zeros_like(sc).at[rows, chosen].set(weights)


def mlp(x, m, d, held=None):
    """The MLP's output for normed input ``x``; of a sparse layer the
    part the experts ``held = (first, count)`` give (default: ``d``'s),
    with the shared expert."""
    if "router" not in m:
        return _swiglu(x, m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                       m["down_proj"]["kernel"])
    d = _Dims(d)
    first, count = held or (d["first"], d["held"])
    weights = _route(x, m["router"]["kernel"], m["select_bias"], d)
    y = _swiglu(x, m["shared_gate"]["kernel"], m["shared_up"]["kernel"],
                m["shared_down"]["kernel"])
    for e in range(count):     # expert ``first + e`` is row e of the stack
        y = y + weights[:, first + e, None] * _swiglu(
            x, m["w_gate"][e], m["w_up"][e], m["w_down"][e])
    return y


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, eps):
    return rmsnorm(x, w.astype(jnp.float32), eps)


def layer_forward(x, lp, d, selection_of=None, positions=None,
                  segments=None):
    """One decoder layer on one sequence (or one packed batch): x [T, E]
    float32 -> [T, E] (and the selection asked for, as :func:`attention`
    gives it)."""
    h = _norm(x, lp["input_norm"]["scale"], d["eps"])
    a = attention(h, lp, d, selection_of, positions, segments)
    picked = None
    if selection_of:
        a, picked = a
    x = x + a
    h = _norm(x, lp["post_norm"]["scale"], d["eps"])
    x = x + mlp(h, lp["mlp"], d)
    return (x, picked) if selection_of else x


@jax.jit
def embed(tokens, top):
    return top["embed_tokens"]["embedding"].astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logits(x, top, eps):
    with jax.default_matmul_precision(PRECISION):
        h = rmsnorm(x, top["final_norm"]["scale"].astype(jnp.float32), eps)
        return h @ top["lm_head"]["kernel"].astype(jnp.float32)


def hidden_states(seq, get_layer, top, num_layers, d, selection_of=None,
                  positions=None, segments=None):
    """Final hidden states (before the last norm) of one token sequence
    (or one packed batch); with ``selection_of`` also a list, a layer, of
    ``(I, chosen)`` for those tokens."""
    x = embed(jnp.asarray(seq), top)
    picked = []
    for i in range(num_layers):
        out = layer_forward(x, get_layer(i), d, selection_of, positions,
                            segments)
        if selection_of:
            x, p = out
            picked.append(p)
        else:
            x = out
    return (x, picked) if selection_of else x
