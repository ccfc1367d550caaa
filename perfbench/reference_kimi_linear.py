"""The plain reference: Kimi-Linear-48B-A3B-Instruct's decoder layers
(``kimi_linear``) as its config.json describes them, one chip's share of
the experts, nothing else.

Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no cache, no kernel, no
chunks, no batching, no code of ``dlrover_tpu``.  ``x`` is a layer's input
after ``input_layernorm`` (RMSNorm, eps 1e-5), one sequence, tokens ``t``.

1. A KDA layer (``linear_attn_config.kda_layers``; H = 32 heads, d = 128
   for keys and values), as a RECURRENCE over the tokens.
   ``q' = SiLU(conv(W_q x))``, ``k' = SiLU(conv(W_k x))``, ``v =
   SiLU(conv(W_v x))``: ``conv`` a causal depthwise convolution over the
   last 4 positions, a channel (``y_t = sum_j w_j x_(t-3+j)``, zeros ahead
   of the sequence, no bias).  A head: ``q = q' / sqrt(|q'|^2 + 1e-6) x
   d^-0.5``, ``k = k' / sqrt(|k'|^2 + 1e-6)``.  The log-decay a channel
   ``g_t = -exp(A_h) softplus(W_f2 W_f1 x + b)`` (``W_f1``: E -> 128,
   ``W_f2``: 128 -> H d), ``alpha_t = exp(g_t)``; ``beta_t = sigmoid(W_b
   x)`` a head.  A head's state ``S`` in R^(d x d) starts at 0:
   ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_(t-1) + beta_t k_t
   v_t^T``, ``o_t = S_t^T q_t``.  Output ``W_o [RMSNorm_head(o_t) x
   sigmoid(W_g2 W_g1 x)]`` (one learned scale of d for every head).
2. An MLA layer (``full_attn_layers``), UN-absorbed, with NO positional
   encoding (``mla_use_nope``) and no query bottleneck (``q_lora_rank``
   null): ``q = W_q x`` in 32 heads of ``[128 | 64]``; ``[c | k_r] = W_kva
   x`` (512 + 64), ``c_kv = RMSNorm(c)``; a head's key ``[W_kb,h c_kv |
   k_r]``, its value ``W_vb,h c_kv`` (128); scores x ``192^-0.5``, causal
   softmax in float32, ``W_o`` over 32 x 128.
3. The MLP.  Layer 1: SwiGLU of 9 216.  Layers 2..: ``sc = sigmoid(W_r
   x)`` over 256 experts; the 8 largest of ``sc + b`` chosen (one group
   of one: ``use_grouped_topk`` is a no-op); weights ``sc[chosen] / sum
   sc[chosen] x 2.446``; ``y = sum over chosen AND HELD e of weight_e
   SwiGLU_e(x)`` + one shared SwiGLU expert of 1 024 on every token.  What
   the absent experts would add is left out (``held`` = the share's
   experts, ``first .. first + count - 1``).
4. Pre-norm residual blocks, ``post_attention_layernorm`` before the MLP,
   a final RMSNorm, an untied head over the share's slice of the
   vocabulary.

What the config leaves to inference is under ``assumed`` in
``perfbench/configs/kimi-linear-48b-serve.json``: the bottlenecks' width,
the decay's parametrisation, the order of SiLU and the norm, the
selection bias.

Sizes come as a plain dict ``dims`` (:func:`dims_of`).  Weights arrive a
layer at a time in the run's dtype and are up-cast here; attention goes a
group of heads at a time, experts one at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISION = "highest"   # every matmul here; read when a program is traced
HEAD_GROUP = 8     # MLA heads whose keys and values exist at once
L2_EPS = 1e-6


def dims_of(config: dict) -> dict:
    """The sizes the reference reads, from a configuration file's keys."""
    held = config.get("experts_held") or [0, config["num_experts"]]
    lin = config["linear_attn_config"]
    if not config["mla_use_nope"] or config["q_lora_rank"] is not None \
            or config["num_shared_experts"] != 1 \
            or config["moe_router_activation_func"] != "sigmoid" \
            or not config["moe_renormalize"] \
            or config["num_expert_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("not what perfbench/reference_kimi_linear.py "
                         "computes")
    n = config["num_hidden_layers"]
    return {
        "heads": config["num_attention_heads"],
        "nope": config["qk_nope_head_dim"], "rope": config["qk_rope_head_dim"],
        "latent": config["kv_lora_rank"], "v": config["v_head_dim"],
        "kda_heads": lin["num_heads"], "kda_dim": lin["head_dim"],
        "taps": lin["short_conv_kernel_size"],
        # 1-based layer numbers, as published; those beyond the cut drop
        "kda_layers": tuple(i for i in lin["kda_layers"] if i <= n),
        "eps": float(config["rms_norm_eps"]),
        "experts": config["num_experts_published"],
        "top_k": config["num_experts_per_token"],
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


def is_kda(layer: int, d) -> bool:
    """Whether 0-based layer ``layer`` is a KDA layer."""
    return layer + 1 in d["kda_layers"]


# ------------------------------------------------------------------- KDA
def causal_conv(x, w):
    """``y_t = sum_j w[j] x[t - (taps - 1) + j]``, zeros ahead of the
    sequence: x [T, D], w [taps, D]."""
    taps = w.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, x.shape[1]), x.dtype), x], axis=0)
    return sum(padded[j:j + x.shape[0]] * w[j] for j in range(taps))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def decay_of(f, a_log):
    """The log-decay a channel from the bottleneck's output ``f`` [T, H,
    d] (bias added) and a head's ``A_log`` [H]: float32."""
    return -jnp.exp(a_log)[:, None] * jax.nn.softplus(f)


def state_dtype(s):
    """What the recurrence keeps its state in: float32, as it comes (a
    control plants a rounding here)."""
    return s


def delta_rule(q, k, v, g, beta):
    """The recurrence: ``q k v g`` [T, H, d], ``beta`` [T, H] -> ``(o [T,
    H, d], S [H, d, d])``, the state starting at 0."""
    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = state_dtype(s * jnp.exp(gt)[:, :, None])
        u = bt[:, None] * (vt - jnp.einsum("hk,hkv->hv", kt, s))
        s = state_dtype(s + kt[:, :, None] * u[:, None, :])
        return s, jnp.einsum("hk,hkv->hv", qt, s)

    heads, dim = q.shape[1:]
    s, o = jax.lax.scan(step, jnp.zeros((heads, dim, dim), jnp.float32),
                        (q, k, v, g, beta))
    return o, s


@_static
def kda(x, lp, d):
    """A KDA layer's output [T, E] for normed input ``x``, and its final
    state [H, d, d]."""
    with jax.default_matmul_precision(PRECISION):
        a = _f32(lp["kda"])
        heads, dim = d["kda_heads"], d["kda_dim"]
        t = x.shape[0]

        def mixed(name):
            y = jnp.einsum("te,ehd->thd", x, a[name + "_proj"]["kernel"])
            y = causal_conv(y.reshape(t, heads * dim),
                            a[name + "_conv"]["kernel"])
            return jax.nn.silu(y).reshape(t, heads, dim)

        q = l2norm(mixed("q")) * dim ** -0.5
        k = l2norm(mixed("k"))
        v = mixed("v")
        f = (x @ a["f_a_proj"]["kernel"]) @ a["f_b_proj"]["kernel"] \
            + a["dt_bias"]
        g = decay_of(f.reshape(t, heads, dim), a["A_log"])
        beta = jax.nn.sigmoid(x @ a["b_proj"]["kernel"])
        o, s = delta_rule(q, k, v, g, beta)
        gate = jax.nn.sigmoid(
            (x @ a["g_a_proj"]["kernel"]) @ a["g_b_proj"]["kernel"]
        ).reshape(t, heads, dim)
        y = rmsnorm(o, a["o_norm"]["scale"], d["eps"]) * gate
        return jnp.einsum("thd,hde->te", y, a["o_proj"]["kernel"]), s


# ------------------------------------------------------------------- MLA
@_static
def _project(x, lp, d):
    """x [T, E] normed -> q [T, H, nope + rope], c_kv [T, C], k_r [T,
    rope]: nothing rotates."""
    with jax.default_matmul_precision(PRECISION):
        a = _f32(lp["attn"])
        q = jnp.einsum("te,ehd->thd", x, a["q_proj"]["kernel"])
        ckv = x @ a["kv_a_proj"]["kernel"]
        c_kv = rmsnorm(ckv[:, :d["latent"]], a["kv_a_norm"]["scale"],
                       d["eps"])
        return q, c_kv, ckv[:, d["latent"]:]


@_static
def _attend_heads(q, c_kv, k_r, kv_b, w_o, d):
    """Causal softmax attention of a group of heads and its part of the
    output projection: [T, E]."""
    with jax.default_matmul_precision(PRECISION):
        kv = jnp.einsum("sc,chd->shd", c_kv, kv_b.astype(jnp.float32))
        k_nope, v = kv[..., :d["nope"]], kv[..., d["nope"]:]
        s = (jnp.einsum("qhd,shd->hqs", q[..., :d["nope"]], k_nope)
             + jnp.einsum("qhd,sd->hqs", q[..., d["nope"]:], k_r)
             ) * (d["nope"] + d["rope"]) ** -0.5
        t = q.shape[0]
        sees = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        p = jax.nn.softmax(jnp.where(sees[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqs,shd->qhd", p, v)
        return jnp.einsum("thv,hve->te", o, w_o.astype(jnp.float32))


def attention(x, lp, d):
    """An MLA layer's output [T, E] for normed input ``x``."""
    d = _Dims(d)
    q, c_kv, k_r = _project(x, lp, d)
    kv_b = lp["attn"]["kv_b_proj"]["kernel"]
    w_o = lp["attn"]["o_proj"]["kernel"]
    out = 0.0
    for h0 in range(0, d["heads"], HEAD_GROUP):
        hs = slice(h0, h0 + HEAD_GROUP)
        out = out + _attend_heads(q[:, hs], c_kv, k_r, kv_b[:, hs],
                                  w_o[hs], d)
    return out


# ------------------------------------------------------------------- MLP
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
    of the stacks."""
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


# ----------------------------------------------------------------- model
@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, eps):
    return rmsnorm(x, w.astype(jnp.float32), eps)


def layer_forward(x, lp, d, layer: int, keep=None):
    """0-based decoder layer ``layer`` on one sequence: x [T, E] float32
    -> [T, E].  ``keep`` (a dict) is given a KDA layer's final state
    (``kda_state`` [H, d, d]) and the MLP's normed input and output
    (``mlp_in``, ``mlp_out``)."""
    h = _norm(x, lp["input_norm"]["scale"], d["eps"])
    if is_kda(layer, d):
        y, state = kda(h, lp, _Dims(d))
        if keep is not None:
            keep["kda_state"] = state
    else:
        y = attention(h, lp, d)
    x = x + y
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


def hidden_states(seq, get_layer, top, num_layers, d, keep=None):
    """Final hidden states (before the last norm) of one token sequence.
    ``keep`` (a dict) is given every KDA layer's final state by its
    0-based layer number (``kda_states``)."""
    x = embed(jnp.asarray(seq), top)
    for i in range(num_layers):
        mine = {} if keep is not None else None
        x = layer_forward(x, get_layer(i), d, i, mine)
        if mine and "kda_state" in mine:
            keep.setdefault("kda_states", {})[i] = mine["kda_state"]
    return x
