"""The plain reference: the decoder layer of Keye-VL-2.0-30B-A3B's language
model (``KeyeVL2``) as its config.json describes it, one chip's share of
the experts, nothing else.

Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no cache, no kernel, no code
of ``dlrover_tpu/serving`` or ``dlrover_tpu/ops``.  ``x`` is a layer's
input after ``input_layernorm`` (RMSNorm), positions ``t``, ``s``:

1. grouped-query attention.  ``q = W_q x`` in ``heads`` heads of ``head``,
   ``k = W_k x``, ``v = W_v x`` in ``kv_heads``; each head of q and of k
   RMS-normed over its ``head`` values (one learned scale for all query
   heads, one for all key heads), then rotated: M-RoPE, a head's halves
   paired ``(x_j, x_{j + head/2})``, pair ``j`` turning by ``theta^(-2j /
   head)`` times the position of ITS stream (``mrope_section`` [16, 24,
   24]: stream 0 for ``j < 16``, 1 for ``16 <= j < 40``, 2 above).  A text
   token's three positions are equal, and that is plain RoPE; the
   reference takes ``positions`` [3, T] all the same (or [T], which it
   repeats).  Query head ``h`` reads KV head ``h // (heads / kv_heads)``;
   ``score_h[t, s] = q_h[t] . k[s] / sqrt(head)``; softmax over ``s in
   S_t`` only; ``o_h = sum p v``; output ``W_o concat(o_h)``.
2. the indexer (``sa_config``).  ``q_i = W_iq x`` in ``index_heads`` heads
   of ``index_dim``; ``k_i = LayerNorm(W_ik x)`` (scale and bias, eps
   1e-6), ONE row a token; both rotated over all ``index_dim``
   dimensions, halves paired, by the first position stream; ``w = (W_iw x)
   x (index_heads x index_dim)^-0.5``.  ``I[t, s] = sum_j w[t, j]
   relu(q_i[t, j] . k_i[s])`` for ``s <= t``; ``S_t`` = the ``min(topk, t
   + 1)`` largest ``I[t, .]`` (computed as ``I >= the topk-th largest``:
   equal scores at the threshold all stay).  A token's selection, never a
   block's.
3. the MLP, every layer: ``p = softmax(W_r x)`` over ALL experts; the
   ``top_k`` largest chosen; weights ``p[chosen] / sum p[chosen]``; ``y =
   sum over chosen AND HELD e of weight_e SwiGLU_e(x)``.  No shared
   expert.  What the absent experts would add is left out (``held`` = the
   share's experts, ``first .. first + count - 1``).
4. pre-norm residual blocks, ``post_attention_layernorm`` before the MLP,
   a final RMSNorm, an untied head.

Departures from the published model, each also under ``assumed`` in
``perfbench/configs/keye-vl2-30b-a3b-serve.json``: no vision tower and no
image tokens (so every token's three positions are equal); the QK-norm;
the indexer's inputs, key norm, rotation and head-weight scale; float32
index scores with no fp8 codes.

Sizes come as a plain dict ``dims`` (:func:`dims_of`).  A sequence of 36 k
positions fits because queries go in blocks against the keys behind them,
KV heads one at a time, experts one at a time; weights arrive a layer at a
time in the run's dtype and are up-cast here.

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
KEY_BUCKET = 4096  # a query block sees keys up to a multiple of this


def dims_of(config: dict) -> dict:
    """The sizes the reference reads, from a configuration file's keys."""
    held = config.get("experts_held") or [0, config["num_experts"]]
    sa = config["sa_config"]
    return {
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head": config["head_dim"],
        "sections": tuple(config["rope_scaling"]["mrope_section"]),
        "index_heads": sa["indexer_num_heads"],
        "index_dim": sa["indexer_head_dim"],
        "topk": sa["topk"],
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "experts": config["num_local_experts"],
        "top_k": config["num_experts_per_tok"],
        "norm_topk": bool(config["norm_topk_prob"]),
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


def three_streams(positions):
    """``positions`` [T] (a text token: its three streams equal) or [3, T]
    as [3, T]."""
    positions = jnp.asarray(positions)
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions, (3,) + positions.shape)
    return positions


def mrope(x, positions, theta, sections):
    """x [T, ..., d] rotated with its halves paired ``(x_j, x_{j + d/2})``:
    pair ``j`` by ``theta^(-2j / d)`` times the position of its stream,
    ``positions`` [3, T], ``sections`` the pairs each stream owns (they
    sum to d / 2)."""
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    inv = 1.0 / (theta ** (jnp.arange(0, 2 * half, 2, dtype=jnp.float32)
                           / (2 * half)))
    stream = jnp.repeat(jnp.arange(len(sections)), jnp.asarray(sections),
                        total_repeat_length=half)               # [d/2]
    pos = positions.astype(jnp.float32)[stream, :].T            # [T, d/2]
    ang = (pos * inv[None, :]).reshape(
        (x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def rope(x, positions, theta):
    """Plain RoPE over the whole of x [T, ..., d], halves paired, at
    ``positions`` [T]: :func:`mrope` with one stream."""
    return mrope(x, positions[None], theta, (x.shape[-1] // 2,))


def _static(fn):
    return functools.partial(jax.jit, static_argnames=("d",))(fn)


class _Dims(dict):
    """``dims`` as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


@_static
def _project(x, lp, pos, d):
    """x [T, E] normed, at positions ``pos`` [3, T] -> q [T, H, D], k [T,
    KV, D] (normed, rotated), v [T, KV, D], q_i [T, Hi, Di], k_i [T, Di],
    w [T, Hi]."""
    with jax.default_matmul_precision(PRECISION):
        a, ix = _f32(lp["attn"]), _f32(lp["indexer"])
        q = jnp.einsum("te,ehd->thd", x, a["q_proj"]["kernel"])
        k = jnp.einsum("te,ehd->thd", x, a["k_proj"]["kernel"])
        v = jnp.einsum("te,ehd->thd", x, a["v_proj"]["kernel"])
        q, k = qk_norm(q, k, a, d)
        q = mrope(q, pos, d["theta"], d["sections"])
        k = mrope(k, pos, d["theta"], d["sections"])
        q_i = rope(jnp.einsum("te,ehd->thd", x, ix["wq"]["kernel"]),
                   pos[0], d["theta"])
        k_i = index_key(x, ix, pos, d)
        w = (x @ ix["weights_proj"]["kernel"]) * float(
            (d["index_heads"] * d["index_dim"]) ** -0.5)
        return q, k, v, q_i, k_i, w


def qk_norm(q, k, a, d):
    """Each head of q and of k RMS-normed, one scale for all query heads
    and one for all key heads."""
    return (rmsnorm(q, a["q_norm"]["scale"], d["eps"]),
            rmsnorm(k, a["k_norm"]["scale"], d["eps"]))


def index_key(x, ix, pos, d):
    """``k_i = RoPE(LayerNorm(W_ik x))``, one row a token."""
    return rope(layernorm(x @ ix["wk"]["kernel"], ix["k_norm"]["scale"],
                          ix["k_norm"]["bias"]), pos[0], d["theta"])


def index_scores(q_i, w, k_i):
    """``I[t, s] = sum_j w[t, j] relu(q_i[t, j] . k_i[s])`` [Q, S]
    float32, before the mask."""
    s = jnp.einsum("qhd,sd->qhs", q_i, k_i)
    return jnp.sum(jnp.maximum(s, 0.0) * w[:, :, None], axis=1)


@_static
def _index_block(q_i, w, k_i, start, seg_q, seg_k, d):
    """Index scores of one query block (the batch's tokens ``start ..``)
    against the keys given: ``I`` [Q, S] (minus infinity where the query
    does not see the key) and each query's threshold, the ``topk``-th
    largest (minus infinity while a query sees fewer)."""
    with jax.default_matmul_precision(PRECISION):
        scores = index_scores(q_i, w, k_i)
        t = start + jnp.arange(q_i.shape[0])
        sees = (jnp.arange(k_i.shape[0])[None, :] <= t[:, None]) & (
            (seg_k[None, :] == 0) | (seg_k[None, :] == seg_q[:, None]))
        scores = jnp.where(sees, scores, -jnp.inf)
        k = min(d["topk"], k_i.shape[0])
        kth = jax.lax.top_k(scores, k)[0][:, -1]
        return scores, kth


def chosen_of(scores, kth):
    """``S_t``: the keys at or above the threshold, of those seen."""
    return (scores >= kth[:, None]) & (scores > -jnp.inf)


def kv_head_of(head: int, d) -> int:
    """The KV head a query head reads."""
    return head // (d["heads"] // d["kv_heads"])


@_static
def _attend_block(q, k, v, chosen, d):
    """Softmax attention of one query block's heads ``q`` [Q, G, D] that
    share ONE KV head (``k``, ``v`` [S, D]) over the chosen keys: [Q, G,
    D]."""
    with jax.default_matmul_precision(PRECISION):
        s = jnp.einsum("qgd,sd->gqs", q, k) / jnp.sqrt(float(d["head"]))
        s = jnp.where(chosen[None], s, -jnp.inf)
        return jnp.einsum("gqs,sd->qgd", jax.nn.softmax(s, axis=-1), v)


def attention(x, lp, d, selection_of=None, positions=None, segments=None):
    """The attention block's output [T, E] for normed input ``x``.
    ``selection_of=(first, count)`` also returns, for those tokens of the
    batch, ``(I [count, T], chosen [count, T])``."""
    d = _Dims(d)
    t_len = x.shape[0]
    pos = three_streams(jnp.arange(t_len) if positions is None
                        else positions)
    seg = jnp.zeros(t_len, jnp.int32) if segments is None \
        else jnp.asarray(segments, jnp.int32)
    q, k, v, q_i, k_i, w = _project(x, lp, pos, d)
    blocks = []          # (start, queries, keys seen, chosen [Q, keys])
    kept_i, kept_s = [], []
    for s0 in range(0, t_len, Q_BLOCK):
        q_n = min(Q_BLOCK, t_len - s0)
        seen = min(t_len, -(-(s0 + q_n) // KEY_BUCKET) * KEY_BUCKET)
        scores, kth = _index_block(
            q_i[s0:s0 + q_n], w[s0:s0 + q_n], k_i[:seen], s0,
            seg[s0:s0 + q_n], seg[:seen], d)
        chosen = chosen_of(scores, kth)
        blocks.append((s0, q_n, seen, chosen))
        if selection_of and s0 < sum(selection_of) \
                and s0 + q_n > selection_of[0]:
            pad = ((0, 0), (0, t_len - seen))
            kept_i.append(jnp.pad(scores, pad, constant_values=-jnp.inf))
            kept_s.append(jnp.pad(chosen, pad))
    del q_i, k_i, w                   # the selection is made
    w_o = lp["attn"]["o_proj"]["kernel"]
    out = 0.0        # W_o concat(o_h), summed a KV head's heads at a time
    group = d["heads"] // d["kv_heads"]
    for g0 in range(0, d["heads"], group):
        hs = slice(g0, g0 + group)
        kv = kv_head_of(g0, d)
        o = jnp.concatenate([
            _attend_block(q[s0:s0 + q_n, hs], k[:seen, kv], v[:seen, kv],
                          chosen, d)
            for s0, q_n, seen, chosen in blocks], axis=0)   # [T, G, D]
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


def router_scores(x, router):
    """``softmax(W_r x)`` over all experts, float32."""
    return jax.nn.softmax(x @ router.astype(jnp.float32), axis=-1)


@_static
def _route(x, router, d):
    """Weights [T, experts] float32: 0 but on a token's chosen experts."""
    with jax.default_matmul_precision(PRECISION):
        p = router_scores(x, router)
        picked, chosen = jax.lax.top_k(p, d["top_k"])
        if d["norm_topk"]:
            picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
        rows = jnp.arange(x.shape[0])[:, None]
        return jnp.zeros_like(p).at[rows, chosen].set(picked)


def mlp(x, m, d, held=None):
    """The sparse MLP's output for normed input ``x``: the part the
    experts ``held = (first, count)`` give (default: ``d``'s)."""
    d = _Dims(d)
    first, count = held or (d["first"], d["held"])
    weights = _route(x, m["router"]["kernel"], d)
    y = jnp.zeros_like(x)
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
