"""The plain reference: dots3-note-prev's decoder layers (``dots3_note``) as
its config.json describes them, one chip's share of the experts, nothing
else.

Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no cache, no kernel, no
batching, no code of ``dlrover_tpu/serving`` or ``dlrover_tpu/ops``.  ONE
sequence, the whole forward.  ``x`` is a layer's input after
``input_layernorm`` (RMSNorm), positions ``t``, ``s``; a layer is FULL or
SLIDING by the file's ``layer_types``:

1. latent attention, both kinds, each with its own sizes (``swa_*`` keys
   for a sliding layer).  ``c_q = RMSNorm(W_qa x) x sqrt(E / q_lora_rank)``;
   ``q = W_qb c_q`` in heads of ``[q_nope | q_rope]``; ``[c | k_r] = W_kva
   x``, ``c_kv = RMSNorm(c) x sqrt(E / kv_lora_rank)``, ``k_r = RoPE(k_r)``
   (one row for all heads; NOT scaled), ``q_rope = RoPE(q_rope)``.  RoPE at
   the kind's theta, plain, ADJACENT pairs.  A head: ``[k_nope_h | v_h] =
   W_kvb,h c_kv``; ``score_h[t, s] = (q_nope_h[t] . k_nope_h[s] + q_rope_h[t]
   . k_r[s]) / sqrt(nope + rope)``; softmax over ``s in S_t``; ``o_h = (sum
   p v_h) x sigmoid(W_g x)_h`` (the head gate); output ``W_o concat(o_h)``.
   (The UNABSORBED form: keys and values of every head are made.)
2. ``S_t`` of a SLIDING layer: ``t - window < s <= t`` (the window counts
   the query).  Of a FULL layer: the indexer's choice, GLM-5's form: ``q_i =
   W_iq c_q`` (the SCALED bottleneck) in heads, ``k_i = LayerNorm(W_ik x)``
   one row a token, the first ``rope`` dimensions of each rotated at the
   layer's theta, ``w = (W_iw x) / sqrt(heads) / sqrt(size)``; ``I[t, s] =
   sum_h w[t, h] relu(q_i[t, h] . k_i[s])`` for ``s <= t``; ``S_t`` = the
   ``min(topk, t + 1)`` largest (``I >= the topk-th largest``: ties stay).
3. the MLP.  Layer 0: SwiGLU.  Then ``sc = sigmoid(W_r x)``; the ``top_k``
   largest of ``sc + b`` chosen; weights ``sc[chosen] / sum sc[chosen] x
   routed_scale``; ``y = sum over chosen AND HELD e of weight_e SwiGLU_e(x)``
   + the shared expert on every token.
4. pre-norm residual blocks, a final RMSNorm, an untied head.

What is assumed where the config names a mechanism and not its equation is
under ``assumed`` in ``perfbench/configs/dots3-note-serve.json``.

``dims["fault"]`` plants ONE fault (``perfbench/controls_dots3.py``): the
same forward with one mechanism wrong, which the benchmark's comparison has
to tell from the right one.

A sequence of 31 k positions fits because queries go in blocks against the
keys they can see, heads in groups, experts one at a time; weights arrive
a layer at a time in the run's dtype and are up-cast here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISION = "highest"   # every matmul here; read when a program is traced
Q_BLOCK = 256      # queries a block
HEAD_GROUP = 8     # heads whose keys and values exist at once
KEY_BUCKET = 4096  # a full layer's query block sees keys up to a multiple

#: the faults :func:`hidden_states` can plant (``dims["fault"]``)
FAULTS = ("fp8_latent_rows", "no_gate", "no_rescale", "thetas_swapped",
          "window_1026", "stale_ring_block", "warm_start_without_window_rows",
          "no_selection", "no_shared_expert")


def dims_of(config: dict) -> dict:
    """The sizes the reference reads, from a configuration file's keys."""
    held = config.get("experts_held") or [0, config["n_routed_experts"]]
    rope = config["qk_rope_head_dim"]
    if (config["swa_qk_rope_head_dim"], config["swa_q_lora_rank"]) != (
            rope, config["q_lora_rank"]):
        raise ValueError("the reference computes ONE rotary size and one "
                         "query bottleneck for both kinds of layer")
    kinds = {
        "full_attention": {
            "heads": config["num_attention_heads"],
            "nope": config["qk_nope_head_dim"], "rope": rope,
            "latent": config["kv_lora_rank"], "v": config["v_head_dim"],
            "theta": float(config["rope_theta"]), "window": 0},
        "sliding_attention": {
            "heads": config["swa_num_attention_heads"],
            "nope": config["swa_qk_nope_head_dim"], "rope": rope,
            "latent": config["swa_kv_lora_rank"],
            "v": config["swa_v_head_dim"],
            "theta": float(config["swa_rope_theta"]),
            "window": int(config["sliding_window_size"])},
    }
    return {
        "kinds": kinds,
        # (the file keeps the published list whole; the first
        # ``num_hidden_layers`` entries are run)
        "layer_types": tuple(
            config["layer_types"][:config["num_hidden_layers"]]),
        "hidden": config["hidden_size"], "q_rank": config["q_lora_rank"],
        "rescale": bool(config["apply_mla_qkv_lora_rescale"]),
        "index_heads": config["index_n_heads"],
        "index_dim": config["index_head_dim"],
        "topk": config["index_topk"],
        "eps": float(config["rms_norm_eps"]),
        "experts": config["n_routed_experts_published"],
        "top_k": config["num_experts_per_tok"],
        "scale": float(config["routed_scaling_factor"]),
        "first": int(held[0]), "held": int(held[1]),
        # the ring a stale row would come from (the engine's geometry, for
        # the ``stale_ring_block`` fault alone) and the rows a warm start
        # would miss (``warm_start_without_window_rows``: (lo, hi))
        "ring_rows": 1024, "ring_block": 128, "missing": None,
        "fault": None,
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


class _Dims(dict):
    """A dict of sizes as a hashable static argument."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def _static(*names):
    return functools.partial(jax.jit, static_argnames=names)


def _kind(d: dict, layer: int) -> dict:
    """The layer's own sizes, with what a planted fault changes of them."""
    name = d["layer_types"][layer]
    k = dict(d["kinds"][name])
    if d["fault"] == "thetas_swapped":
        other = next(n for n in d["kinds"] if n != name)
        k["theta"] = d["kinds"][other]["theta"]
    if d["fault"] == "window_1026" and k["window"]:
        k["window"] = 1026
    k["indexed"] = not k["window"] and d["fault"] != "no_selection"
    return _Dims(k)


@_static("d", "k")
def _project(x, lp, pos, d, k):
    """x [T, E] normed, at positions ``pos`` [T] -> q_nope [T, H, nope],
    q_rope [T, H, rope], c_kv [T, C], k_r [T, rope], gate [T, H] and, of
    a full layer, q_i [T, Hi, Di], k_i [T, Di], w [T, Hi]."""
    with jax.default_matmul_precision(PRECISION):
        a = _f32(lp["attn"])
        up_q = up_kv = 1.0
        if d["rescale"] and d["fault"] != "no_rescale":
            up_q = (d["hidden"] / d["q_rank"]) ** 0.5
            up_kv = (d["hidden"] / k["latent"]) ** 0.5
        c_q = rmsnorm(x @ a["q_a_proj"]["kernel"], a["q_a_norm"]["scale"],
                      d["eps"]) * up_q
        q = jnp.einsum("tq,qhd->thd", c_q, a["q_b_proj"]["kernel"])
        q_nope = q[..., :k["nope"]]
        q_rope = rope(q[..., k["nope"]:], pos, k["theta"], k["rope"])
        ckv = x @ a["kv_a_proj"]["kernel"]
        c_kv = rmsnorm(ckv[:, :k["latent"]], a["kv_a_norm"]["scale"],
                       d["eps"]) * up_kv
        k_r = rope(ckv[:, k["latent"]:], pos, k["theta"], k["rope"])
        gate = jax.nn.sigmoid(x @ a["g_proj"]["kernel"])
        if d["fault"] == "no_gate":
            gate = jnp.ones_like(gate)
        if d["fault"] == "fp8_latent_rows":
            # the cached row in the nearest precision below the stated one
            # (e4m3's 4 bits of exponent and 3 of mantissa, kept float32:
            # NOT ``astype`` there and back, a pair of converts the chip's
            # compiler may take away)
            c_kv, k_r = (jax.lax.reduce_precision(
                v, exponent_bits=4, mantissa_bits=3) for v in (c_kv, k_r))
        if d["fault"] == "bf16_latent_rows":
            # NOT a fault: the cached row in the precision the
            # configuration STATES for it (a witness of what bf16 alone
            # moves: PERF.md section 6, PR 47)
            c_kv, k_r = (jax.lax.reduce_precision(
                v, exponent_bits=8, mantissa_bits=7) for v in (c_kv, k_r))
        if not k["indexed"]:
            return q_nope, q_rope, c_kv, k_r, gate, None, None, None
        ix = _f32(lp["indexer"])
        q_i = rope(jnp.einsum("tq,qhd->thd", c_q, ix["wq_b"]["kernel"]),
                   pos, k["theta"], k["rope"])
        k_i = rope(layernorm(x @ ix["wk"]["kernel"], ix["k_norm"]["scale"],
                             ix["k_norm"]["bias"]),
                   pos, k["theta"], k["rope"])
        w = (x @ ix["weights_proj"]["kernel"]) / jnp.sqrt(
            float(d["index_heads"])) / jnp.sqrt(float(d["index_dim"]))
        return q_nope, q_rope, c_kv, k_r, gate, q_i, k_i, w


@_static("d")
def _index_block(q_i, w, k_i, start, d):
    """Index scores of one query block (positions ``start ..``) against
    the keys given: ``I`` [Q, S] (minus infinity ahead of the query) and
    each query's threshold, the ``topk``-th largest."""
    with jax.default_matmul_precision(PRECISION):
        s = jnp.einsum("qhd,sd->qhs", q_i, k_i)
        scores = jnp.sum(jnp.maximum(s, 0.0) * w[:, :, None], axis=1)
        t = start + jnp.arange(q_i.shape[0])
        sees = jnp.arange(k_i.shape[0])[None, :] <= t[:, None]
        scores = jnp.where(sees, scores, -jnp.inf)
        kth = jax.lax.top_k(scores, min(d["topk"], k_i.shape[0]))[0][:, -1]
        return scores, kth


@_static("k")
def _heads_kv(c_kv, kv_b, k):
    """[k_nope_h | v_h] = W_kvb,h c_kv for a group of heads."""
    with jax.default_matmul_precision(PRECISION):
        kv = jnp.einsum("sc,chd->shd", c_kv, kv_b.astype(jnp.float32))
        return kv[..., :k["nope"]], kv[..., k["nope"]:]


@_static("k")
def _attend_block(q_nope, q_rope, k_nope, k_r, v, chosen, k):
    """Softmax attention of one query block and one head group over the
    keys ``chosen`` [Q, S] marks: [Q, G, v]."""
    with jax.default_matmul_precision(PRECISION):
        s = (jnp.einsum("qhd,shd->hqs", q_nope, k_nope)
             + jnp.einsum("qhd,sd->hqs", q_rope, k_r)
             ) / jnp.sqrt(float(k["nope"] + k["rope"]))
        s = jnp.where(chosen[None], s, -jnp.inf)
        return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(s, axis=-1), v)


@jax.jit
def _o_proj(o, gate, w):
    with jax.default_matmul_precision(PRECISION):
        return jnp.einsum("thv,hve->te", o * gate[..., None],
                          w.astype(jnp.float32))


def _faulty_rows(rows, d, k):
    """A window layer's cached rows as a planted fault leaves them."""
    t_len = rows.shape[0]
    if d["fault"] == "stale_ring_block" and k["window"]:
        # the ring's last block is never written again behind the first
        # wrap: a query finds there the rows of a ring's length earlier
        s = jnp.arange(t_len)
        ring, bs = d["ring_rows"], d["ring_block"]
        stale = (s >= ring) & ((s % ring) >= ring - bs)
        return jnp.where(stale[:, None], rows[jnp.maximum(s - ring, 0)],
                         rows)
    if d["fault"] == "warm_start_without_window_rows" and k["window"] \
            and d["missing"]:
        lo, hi = d["missing"]
        s = jnp.arange(t_len)
        return jnp.where(((s >= lo) & (s < hi))[:, None], 0.0, rows)
    return rows


def attention(x, lp, d, layer: int, selection_of=None):
    """The attention block's output [T, E] for normed input ``x`` of
    layer ``layer``.  ``selection_of=(first, count)`` also returns, of a
    full layer, those queries' ``(I [count, T], chosen [count, T])``."""
    d, k = _Dims(d), _kind(d, layer)
    t_len = x.shape[0]
    pos = jnp.arange(t_len)
    q_nope, q_rope, c_kv, k_r, gate, q_i, k_i, w = _project(
        x, lp, pos, d, k)
    c_kv, k_r = _faulty_rows(c_kv, d, k), _faulty_rows(k_r, d, k)
    blocks = []          # (start, queries, first key, keys, chosen)
    kept_i, kept_s = [], []
    for s0 in range(0, t_len, Q_BLOCK):
        q_n = min(Q_BLOCK, t_len - s0)
        t = s0 + jnp.arange(q_n)
        if k["window"]:
            lo, hi = max(0, s0 - (k["window"] - 1)), s0 + q_n
            s = lo + jnp.arange(hi - lo)
            chosen = (s[None, :] <= t[:, None]) & (
                s[None, :] > t[:, None] - k["window"])
        else:
            lo = 0
            hi = min(t_len, -(-(s0 + q_n) // KEY_BUCKET) * KEY_BUCKET)
            if k["indexed"] and hi > d["topk"]:
                scores, kth = _index_block(
                    q_i[s0:s0 + q_n], w[s0:s0 + q_n], k_i[:hi], s0, d)
                chosen = (scores >= kth[:, None]) & (scores > -jnp.inf)
            else:
                scores = None
                chosen = jnp.arange(hi)[None, :] <= t[:, None]
            if selection_of and s0 < sum(selection_of) \
                    and s0 + q_n > selection_of[0]:
                pad = ((0, 0), (0, t_len - hi))
                kept_i.append(None if scores is None else jnp.pad(
                    scores, pad, constant_values=-jnp.inf))
                kept_s.append(jnp.pad(chosen, pad))
        blocks.append((s0, q_n, lo, hi, chosen))
    del q_i, k_i, w                   # the selection is made
    kv_b = lp["attn"]["kv_b_proj"]["kernel"]
    w_o = lp["attn"]["o_proj"]["kernel"]
    out = 0.0        # W_o concat(o_h), summed a group of heads at a time
    for h0 in range(0, k["heads"], HEAD_GROUP):
        hs = slice(h0, h0 + HEAD_GROUP)
        k_nope, v = _heads_kv(c_kv, kv_b[:, hs], k)
        o = jnp.concatenate([
            _attend_block(q_nope[s0:s0 + q_n, hs], q_rope[s0:s0 + q_n, hs],
                          k_nope[lo:hi], k_r[lo:hi], v[lo:hi], chosen, k)
            for s0, q_n, lo, hi, chosen in blocks], axis=0)   # [T, G, v]
        out = out + _o_proj(o, gate[:, hs], w_o[hs])
    if not (selection_of and kept_s):
        return (out, None) if selection_of else out
    first, count = selection_of
    lo = (first // Q_BLOCK) * Q_BLOCK
    rows = slice(first - lo, first - lo + count)
    scores = None if any(i is None for i in kept_i) \
        else jnp.concatenate(kept_i)[rows]
    return out, (scores, jnp.concatenate(kept_s)[rows])


@jax.jit
def _swiglu(x, gate, up, down):
    with jax.default_matmul_precision(PRECISION):
        gate, up, down = _f32((gate, up, down))
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down


@functools.partial(jax.jit, static_argnames=("d",))
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
    y = 0.0 if d["fault"] == "no_shared_expert" else _swiglu(
        x, m["shared_gate"]["kernel"], m["shared_up"]["kernel"],
        m["shared_down"]["kernel"])
    for e in range(count):     # expert ``first + e`` is row e of the stack
        y = y + weights[:, first + e, None] * _swiglu(
            x, m["w_gate"][e], m["w_up"][e], m["w_down"][e])
    return y


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, eps):
    return rmsnorm(x, w.astype(jnp.float32), eps)


@jax.jit
def embed(tokens, top):
    return top["embed_tokens"]["embedding"].astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logits(x, top, eps):
    with jax.default_matmul_precision(PRECISION):
        h = rmsnorm(x, top["final_norm"]["scale"].astype(jnp.float32), eps)
        return h @ top["lm_head"]["kernel"].astype(jnp.float32)


def hidden_states(seq, get_layer, top, num_layers, d, keep=None,
                  selection_of=None):
    """Final hidden states (before the last norm) of one token sequence.
    ``keep`` (a dict) is given ``full_out`` and ``window_out``, the first
    full and the first sliding layer's attention output [T', E] (T' = T
    up to a whole block), and with ``selection_of=(first, count)``
    ``selection``, the first full layer's ``(I, chosen)`` for those
    queries."""
    # Whole blocks of queries only: the sequence is padded behind its end
    # (no position sees a later one) and the result cut back.  On the chip
    # a LAST block of 148 queries once read every key as chosen for the
    # rows with bit 2 of their index set (seed 2147483921, a sequence of
    # 20 884; the program had chosen its 2 048; cause not found; the same
    # pass is right on the CPU and in every whole block: my chip runs,
    # PR 47); whole blocks are also fewer shapes to compile.
    seq = jnp.asarray(seq)
    t_len = seq.shape[0]
    x = embed(jnp.pad(seq, (0, -t_len % Q_BLOCK)), top)
    for i in range(num_layers):
        lp = get_layer(i)
        h = _norm(x, lp["input_norm"]["scale"], d["eps"])
        first_full = selection_of and keep is not None \
            and "selection" not in keep \
            and not d["kinds"][d["layer_types"][i]]["window"]
        a = attention(h, lp, d, i, selection_of if first_full else None)
        if first_full:
            a, keep["selection"] = a
        if keep is not None:
            # the first layer's output of each kind
            keep.setdefault(
                "window_out" if d["kinds"][d["layer_types"][i]]["window"]
                else "full_out", a)
        x = x + a
        h = _norm(x, lp["post_norm"]["scale"], d["eps"])
        x = x + mlp(h, lp["mlp"], d)
    return x[:t_len]
