"""The plain reference: sarvam-105b's decoder layer (``sarvam_mla``) as its
config.json describes it, one chip's share of the experts, nothing else.

Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no cache, no kernel, no code
of ``dlrover_tpu``.  ``x`` is a layer's input after ``input_layernorm``
(RMSNorm), positions ``t``, ``s``:

1. latent attention, UN-absorbed (keys and values of every head are made
   from the latent).  ``q = W_q x`` in 64 heads of ``[q_nope | q_rope]``
   (128 + 64), straight from the hidden state: no bottleneck; each head's
   192 values RMS-normed with one learned scale (``use_qk_norm``), before
   rotation.  ``[c | k_r] = W_kva x`` (512 + 64), ``c_kv = RMSNorm(c)``;
   ``k_r = rot(k_r)`` (one row for all heads), ``q_rope = rot(q_rope)``.
   ``rot``: ADJACENT pairs ``(x_2i, x_2i+1)`` at YaRN's frequencies
   (``deepseek_yarn``: factor 40 over 4 096 original positions, beta 32 /
   1, theta 10 000: a pair that turns more than 32 times over the original
   length keeps ``theta^(-2i/64)``, one that turns less than once has it
   divided by 40, a linear ramp between), cos and sin times
   ``yarn_mscale(40, mscale) / yarn_mscale(40, mscale_all_dim)`` = 1.  A
   head: ``[k_nope_h | v_h] = W_kvb,h c_kv`` (128 + 128); ``score_h[t, s] =
   (q_nope_h[t] . k_nope_h[s] + q_rope_h[t] . k_r[s]) x 192^-0.5 x m^2``,
   ``m = yarn_mscale(40, mscale_all_dim) = 0.1 ln 40 + 1``; causal softmax
   in float32; ``o_h = sum p v_h``; output ``W_o concat(o_h)``.
2. the MLP.  Layer 0: SwiGLU of 16 384.  Layers 1..: ``sc = sigmoid(W_r
   x)`` over 128 experts; the 8 largest of ``sc + b`` chosen
   (``moe_router_enable_expert_bias``); weights ``sc[chosen] / sum
   sc[chosen] x 2.5``; ``y = sum over chosen AND HELD e of weight_e
   SwiGLU_e(x)`` + one shared SwiGLU expert of 2 048 on every token.  What
   the absent experts would add is left out (``held`` = the share's
   experts, ``first .. first + count - 1``).
3. pre-norm residual blocks, ``post_attention_layernorm`` before the MLP,
   a final RMSNorm (eps 1e-6 throughout), an untied head over the share's
   slice of the vocabulary.

Departures from the published model, each also under ``assumed`` in
``perfbench/configs/sarvam-105b-serve.json``: ``use_qk_norm`` read as the
norm over a query head above, with the latent's RMSNorm as the key's;
adjacent-pair rotation.

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
import math

import jax
import jax.numpy as jnp

PRECISION = "highest"   # every matmul here; read when a program is traced
Q_BLOCK = 512      # queries a block
HEAD_GROUP = 8     # heads whose keys and values exist at once
KEY_BUCKET = 8192  # a query block sees keys up to a multiple of this


def dims_of(config: dict) -> dict:
    """The sizes the reference reads, from a configuration file's keys."""
    held = config.get("experts_held") or [0, config["num_experts"]]
    y = config["rope_scaling"]
    if y["type"] != "deepseek_yarn" or config["num_shared_experts"] != 1 \
            or not config["moe_router_enable_expert_bias"] \
            or not config["use_qk_norm"] \
            or config["q_head_dim"] != (config["qk_nope_head_dim"]
                                        + config["qk_rope_head_dim"]):
        raise ValueError("not what perfbench/reference_sarvam.py computes")
    return {
        "heads": config["num_attention_heads"],
        "nope": config["qk_nope_head_dim"], "rope": config["qk_rope_head_dim"],
        "latent": config["kv_lora_rank"], "v": config["v_head_dim"],
        "theta": float(config["rope_theta"]),
        "yarn_factor": float(y["factor"]),
        "yarn_original": int(y["original_max_position_embeddings"]),
        "yarn_beta_fast": float(y["beta_fast"]),
        "yarn_beta_slow": float(y["beta_slow"]),
        "yarn_mscale": float(y["mscale"]),
        "yarn_mscale_all_dim": float(y["mscale_all_dim"]),
        "eps": float(config["rms_norm_eps"]),
        "experts": config["num_experts_published"],
        "top_k": config["num_experts_per_tok"],
        "scale": float(config["routed_scaling_factor"]),
        "first": int(held[0]), "held": int(held[1]),
    }


class _Dims(dict):
    """``dims`` as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _static(fn):
    return functools.partial(jax.jit, static_argnames=("d",))(fn)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def inverse_frequencies(d):
    """[rope / 2]: YaRN's blend of ``theta^(-2i/rope)`` and that over the
    factor (``deepseek_yarn``'s ``yarn_find_correction_range`` and linear
    ramp)."""
    rotary, base = d["rope"], d["theta"]
    plain = 1.0 / (base ** (jnp.arange(0, rotary, 2, dtype=jnp.float32)
                            / rotary))

    def correction_dim(turns):
        return (rotary * math.log(d["yarn_original"] / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(d["yarn_beta_fast"])), 0)
    high = min(math.ceil(correction_dim(d["yarn_beta_slow"])), rotary - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(rotary // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return plain / d["yarn_factor"] * ramp + plain * (1.0 - ramp)


def softmax_scale(d) -> float:
    m = yarn_mscale(d["yarn_factor"], d["yarn_mscale_all_dim"])
    return (d["nope"] + d["rope"]) ** -0.5 * m * m


def softmax(s):
    """Over the keys, in float32."""
    return jax.nn.softmax(s, axis=-1)


def rope(x, positions, d):
    """x [T, ..., rope]: adjacent pairs (x_2i, x_2i+1) rotated by
    ``positions`` x :func:`inverse_frequencies`."""
    rotary = d["rope"]
    ang = positions.astype(jnp.float32)[:, None] \
        * inverse_frequencies(d)[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (rotary // 2,))
    factor = yarn_mscale(d["yarn_factor"], d["yarn_mscale"]) / yarn_mscale(
        d["yarn_factor"], d["yarn_mscale_all_dim"])
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


@_static
def _project(x, lp, pos, d):
    """x [T, E] normed, at positions ``pos`` [T] -> q_nope [T, H, nope],
    q_rope [T, H, rope], c_kv [T, C], k_r [T, rope]."""
    with jax.default_matmul_precision(PRECISION):
        a = _f32(lp["attn"])
        q = jnp.einsum("te,ehd->thd", x, a["q_proj"]["kernel"])
        q = rmsnorm(q, a["q_norm"]["scale"], d["eps"])
        q_nope = q[..., :d["nope"]]
        q_rope = rope(q[..., d["nope"]:], pos, d)
        ckv = x @ a["kv_a_proj"]["kernel"]
        c_kv = rmsnorm(ckv[:, :d["latent"]], a["kv_a_norm"]["scale"],
                       d["eps"])
        k_r = rope(ckv[:, d["latent"]:], pos, d)
        return q_nope, q_rope, c_kv, k_r


@_static
def _heads_kv(c_kv, kv_b, d):
    """[k_nope_h | v_h] = W_kvb,h c_kv for a group of heads."""
    with jax.default_matmul_precision(PRECISION):
        kv = jnp.einsum("sc,chd->shd", c_kv, kv_b.astype(jnp.float32))
        return kv[..., :d["nope"]], kv[..., d["nope"]:]


@functools.partial(jax.jit, static_argnames=("d", "q_n", "seen"))
def _attend_block(q_nope, q_rope, k_nope, k_r, v, seg, start, d, q_n, seen):
    """Causal softmax attention of one query block (the batch's tokens
    ``start .. start + q_n``) and one head group over the first ``seen``
    keys: [q_n, G, v].  The whole sequence's arrays come in and are cut
    here, inside the program: cut by the caller, every block copied its
    keys and values first."""
    with jax.default_matmul_precision(PRECISION):
        q_nope, q_rope, seg_q = (
            jax.lax.dynamic_slice_in_dim(a, start, q_n)
            for a in (q_nope, q_rope, seg))
        k_nope, k_r, v, seg_k = k_nope[:seen], k_r[:seen], v[:seen], \
            seg[:seen]
        s = (jnp.einsum("qhd,shd->hqs", q_nope, k_nope)
             + jnp.einsum("qhd,sd->hqs", q_rope, k_r)) * softmax_scale(d)
        t = start + jnp.arange(q_n)
        sees = (jnp.arange(seen)[None, :] <= t[:, None]) & (
            (seg_k[None, :] == 0) | (seg_k[None, :] == seg_q[:, None]))
        s = jnp.where(sees[None], s, -jnp.inf)
        return jnp.einsum("hqs,shd->qhd", softmax(s), v)


@jax.jit
def _o_proj(o, w):
    with jax.default_matmul_precision(PRECISION):
        return jnp.einsum("thv,hve->te", o, w.astype(jnp.float32))


def attention(x, lp, d, positions=None, segments=None):
    """The attention block's output [T, E] for normed input ``x``."""
    d = _Dims(d)
    t_len = x.shape[0]
    pos = jnp.arange(t_len) if positions is None else jnp.asarray(positions)
    seg = jnp.zeros(t_len, jnp.int32) if segments is None \
        else jnp.asarray(segments, jnp.int32)
    q_nope, q_rope, c_kv, k_r = _project(x, lp, pos, d)
    kv_b = lp["attn"]["kv_b_proj"]["kernel"]
    w_o = lp["attn"]["o_proj"]["kernel"]
    out = 0.0        # W_o concat(o_h), summed a group of heads at a time
    for h0 in range(0, d["heads"], HEAD_GROUP):
        hs = slice(h0, h0 + HEAD_GROUP)
        k_nope, v = _heads_kv(c_kv, kv_b[:, hs], d)
        q_n_g, q_r_g = q_nope[:, hs], q_rope[:, hs]
        blocks = []
        for s0 in range(0, t_len, Q_BLOCK):
            q_n = min(Q_BLOCK, t_len - s0)
            seen = min(t_len, -(-(s0 + q_n) // KEY_BUCKET) * KEY_BUCKET)
            blocks.append(_attend_block(
                q_n_g, q_r_g, k_nope, k_r, v, seg, s0, d, q_n, seen))
        out = out + _o_proj(jnp.concatenate(blocks, axis=0), w_o[hs])
    return out


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


def shared_expert(x, m):
    return _swiglu(x, m["shared_gate"]["kernel"], m["shared_up"]["kernel"],
                   m["shared_down"]["kernel"])


@jax.jit
def _add_expert(y, x, weights, column, gate, up, down, e):
    """``y`` + ``weights[:, column]`` x the SwiGLU expert that is row ``e``
    of the stacks (one program for every expert of a layer: the row and
    the column are arguments)."""
    with jax.default_matmul_precision(PRECISION):
        gate, up, down = (
            jax.lax.dynamic_index_in_dim(w, e, keepdims=False).astype(
                jnp.float32) for w in (gate, up, down))
        weight = jax.lax.dynamic_index_in_dim(weights, column, axis=1)
        return y + weight * ((jax.nn.silu(x @ gate) * (x @ up)) @ down)


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
    y = shared_expert(x, m)
    for e in range(count):     # expert ``first + e`` is row e of the stack
        y = _add_expert(y, x, weights, first + e, m["w_gate"], m["w_up"],
                        m["w_down"], e)
    return y


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, eps):
    return rmsnorm(x, w.astype(jnp.float32), eps)


def layer_forward(x, lp, d, positions=None, segments=None, keep=None):
    """One decoder layer on one sequence (or one packed batch): x [T, E]
    float32 -> [T, E].  ``keep`` (a dict) is given the MLP's normed input
    and its output (``mlp_in``, ``mlp_out``)."""
    h = _norm(x, lp["input_norm"]["scale"], d["eps"])
    x = x + attention(h, lp, d, positions, segments)
    h = _norm(x, lp["post_norm"]["scale"], d["eps"])
    y = mlp(h, lp["mlp"], d)
    if keep is not None:
        keep.update(mlp_in=h, mlp_out=y)
    return x + y


@jax.jit
def embed(tokens, top):
    return top["embed_tokens"]["embedding"].astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logits(x, top, eps):
    with jax.default_matmul_precision(PRECISION):
        h = rmsnorm(x, top["final_norm"]["scale"].astype(jnp.float32), eps)
        return h @ top["lm_head"]["kernel"].astype(jnp.float32)


def hidden_states(seq, get_layer, top, num_layers, d, positions=None,
                  segments=None):
    """Final hidden states (before the last norm) of one token sequence
    (or one packed batch)."""
    x = embed(jnp.asarray(seq), top)
    for i in range(num_layers):
        x = layer_forward(x, get_layer(i), d, positions, segments)
    return x
