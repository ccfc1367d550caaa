"""The plain reference: granite-4.0-h-small's decoder layers
(``granitemoehybrid``) as its config.json describes them, one chip's share
of the experts, nothing else.

Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no cache, no kernel, no
chunks, no batching, no code of ``dlrover_tpu``.  ``u`` is a layer's input
after ``input_layernorm`` (RMSNorm, eps 1e-5), one sequence, tokens ``t``.

1. A Mamba-2 layer (``layer_types`` "mamba"; H = 128 heads of P = 64
   channels, a state of N = 128, one group), as a RECURRENCE over the
   tokens.  ``[z | xBC | dt] = W_in u`` (8 192 | 8 448 | 128, no bias);
   ``xBC <- SiLU(conv(xBC) + b)``: ``conv`` a causal depthwise convolution
   over the last 4 positions, a channel (``y_t = sum_j w_j x_(t-3+j)``,
   zeros ahead of the sequence); ``[x | B | C] = xBC`` (8 192 | 128 | 128:
   B and C shared by all heads).  A head: ``Delta_t = softplus(dt_t +
   dt_bias)``, ``a_t = exp(-exp(A_log) Delta_t)`` (one scalar a head and
   token); its state ``S`` in R^(P x N) starts at 0: ``S_t = a_t S_(t-1) +
   Delta_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``.  Output ``W_out
   RMSNorm_8192(y x SiLU(z))``: the gate INSIDE the norm, one learned
   scale over all 8 192 channels, eps 1e-5.
2. An attention layer ("attention"): 32 query / 8 KV heads of 128, no
   bias, NO positional encoding (``position_embedding_type`` "nope"),
   scores x ``attention_multiplier`` (1/128, NOT 128^-0.5), causal softmax
   in float32, ``W_o`` over 32 x 128.
3. The MLP of every layer: ``p = softmax(W_r h)`` over 72 experts; the 10
   largest chosen; weights ``p[chosen] / sum p[chosen]`` (= softmax over
   the 10 chosen logits); ``y = sum over chosen AND HELD e of weight_e
   SwiGLU_e(h)`` (width 768) + one shared SwiGLU expert of 1 536 on every
   token, unweighted.  What the absent experts would add is left out
   (``held`` = the share's experts, ``first .. first + count - 1``).
4. ``x_0 = embedding_multiplier x embed[id]`` (12); every branch is added
   times ``residual_multiplier`` (0.22): ``x <- x + 0.22 mixer(RMSNorm(x))``,
   ``x <- x + 0.22 mlp(RMSNorm(x))``; a final RMSNorm; logits = ``h E^T /
   logits_scaling`` (16) over the share's slice of the TIED embedding.

Departures from the published description, each noted where it is made:
the experts and the vocabulary are a share (3, 4); everything the config
leaves to inference is under ``assumed`` in
``perfbench/configs/granite-4.0-h-small-serve.json``.

Sizes come as a plain dict ``dims`` (:func:`dims_of`).  Weights arrive a
layer at a time in the run's dtype and are up-cast here; experts go one at
a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISION = "highest"   # every matmul here; read when a program is traced


def dims_of(config: dict) -> dict:
    """The sizes the reference reads, from a configuration file's keys."""
    held = config.get("experts_held") or [0, config["num_local_experts"]]
    if config["position_embedding_type"] != "nope" \
            or config["mamba_n_groups"] != 1 or config["mamba_proj_bias"] \
            or not config["mamba_conv_bias"] or config["attention_bias"] \
            or not config["tie_word_embeddings"] \
            or config["normalization_function"] != "rmsnorm" \
            or config["hidden_act"] != "silu":
        raise ValueError("not what perfbench/reference_granite.py computes")
    n = config["num_hidden_layers"]
    heads = config["num_attention_heads"]
    return {
        "heads": heads, "kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // heads,
        "attn_scale": float(config["attention_multiplier"]),
        "ssm_heads": config["mamba_n_heads"], "ssm_dim": config["mamba_d_head"],
        "ssm_state": config["mamba_d_state"], "taps": config["mamba_d_conv"],
        # 0-based, as published; those beyond the cut drop
        "ssm_layers": tuple(i for i, kind in enumerate(
            config["layer_types"][:n]) if kind == "mamba"),
        "eps": float(config["rms_norm_eps"]),
        "embedding_mult": float(config["embedding_multiplier"]),
        "residual_mult": float(config["residual_multiplier"]),
        "logits_scaling": float(config["logits_scaling"]),
        "experts": config["num_experts_published"],
        "top_k": config["num_experts_per_tok"],
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


def is_ssm(layer: int, d) -> bool:
    """Whether 0-based layer ``layer`` is a Mamba-2 layer."""
    return layer in d["ssm_layers"]


# --------------------------------------------------------------- Mamba-2
def causal_conv(x, w, bias):
    """``y_t = sum_j w[j] x[t - (taps - 1) + j] + bias``, zeros ahead of
    the sequence: x [T, D], w [taps, D], bias [D]."""
    taps = w.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, x.shape[1]), x.dtype), x], axis=0)
    return sum(padded[j:j + x.shape[0]] * w[j] for j in range(taps)) + bias


def state_dtype(s):
    """What the recurrence keeps its state in: float32, as it comes (a
    control plants a rounding here)."""
    return s


def skip_of(d_skip, x):
    """The skip ``D x`` a head: d_skip [H], x [T, H, P]."""
    return d_skip[:, None] * x


def gated_norm(y, z, scale, eps):
    """The gate INSIDE the norm: ``RMSNorm(y x SiLU(z))`` over all
    channels: y z [T, H P]."""
    return rmsnorm(y * jax.nn.silu(z), scale, eps)


def scan(x, dt, a, b, c):
    """The recurrence: ``x`` [T, H, P], ``dt a`` [T, H] (the step and the
    decay in (0, 1]), ``b c`` [T, N] -> ``(y [T, H, P] = S_t C_t, S [H, P,
    N])``, the state starting at 0."""
    def step(s, t):
        xt, dtt, at, bt, ct = t
        s = state_dtype(s * at[:, None, None])
        s = state_dtype(s + (dtt[:, None] * xt)[:, :, None]
                        * bt[None, None, :])
        return s, jnp.einsum("hpn,n->hp", s, ct)

    heads, p = x.shape[1:]
    s, y = jax.lax.scan(
        step, jnp.zeros((heads, p, b.shape[1]), jnp.float32),
        (x, dt, a, b, c))
    return y, s


@_static
def ssm(u, lp, d):
    """A Mamba-2 layer's output [T, E] for normed input ``u``, its final
    state [H, P, N] and the convolution's last ``taps - 1`` inputs
    [taps - 1, H P + 2 N] (``xBC`` ahead of the convolution)."""
    with jax.default_matmul_precision(PRECISION):
        a = _f32(lp["ssm"])
        h, p, n = d["ssm_heads"], d["ssm_dim"], d["ssm_state"]
        t, w = u.shape[0], h * p
        full = u @ a["in_proj"]["kernel"]
        z, xbc, dt = full[:, :w], full[:, w:w + w + 2 * n], full[:, -h:]
        act = jax.nn.silu(causal_conv(xbc, a["conv"]["kernel"],
                                      a["conv"]["bias"]))
        x = act[:, :w].reshape(t, h, p)
        b, c = act[:, w:w + n], act[:, w + n:]
        step = jax.nn.softplus(dt + a["dt_bias"])
        decay = jnp.exp(-jnp.exp(a["A_log"]) * step)
        y, s = scan(x, step, decay, b, c)
        y = (y + skip_of(a["D"], x)).reshape(t, w)
        o = gated_norm(y, z, a["norm"]["scale"], d["eps"])
        taps = d["taps"]
        rows = jnp.concatenate(
            [jnp.zeros((taps - 1, xbc.shape[1]), xbc.dtype), xbc],
            axis=0)[-(taps - 1):]
        return o @ a["out_proj"]["kernel"], s, rows


# ------------------------------------------------------------- attention
def softmax_scale(d) -> float:
    return d["attn_scale"]


@_static
def attention(u, lp, d):
    """An attention layer's output [T, E] for normed input ``u``: full
    causal softmax, grouped queries, nothing rotated."""
    with jax.default_matmul_precision(PRECISION):
        a = _f32(lp["attn"])
        q = jnp.einsum("te,ehd->thd", u, a["q_proj"]["kernel"])
        k = jnp.einsum("te,ehd->thd", u, a["k_proj"]["kernel"])
        v = jnp.einsum("te,ehd->thd", u, a["v_proj"]["kernel"])
        t, kv = u.shape[0], d["kv_heads"]
        qg = q.reshape(t, kv, d["heads"] // kv, d["head_dim"])
        s = jnp.einsum("qkgd,skd->kgqs", qg, k) * softmax_scale(d)
        sees = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        pr = jax.nn.softmax(jnp.where(sees[None, None], s, -jnp.inf),
                            axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", pr, v).reshape(
            t, d["heads"], d["head_dim"])
        return jnp.einsum("thd,hde->te", o, a["o_proj"]["kernel"])


# ------------------------------------------------------------------- MLP
@jax.jit
def _swiglu(x, gate, up, down):
    with jax.default_matmul_precision(PRECISION):
        gate, up, down = _f32((gate, up, down))
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down


@_static
def _route(x, router, d):
    """Weights [T, experts] float32: 0 but on a token's chosen experts."""
    with jax.default_matmul_precision(PRECISION):
        logits = x @ router.astype(jnp.float32)
        picked, chosen = jax.lax.top_k(logits, d["top_k"])
        weights = jax.nn.softmax(picked, axis=-1)
        rows = jnp.arange(x.shape[0])[:, None]
        return jnp.zeros_like(logits).at[rows, chosen].set(weights)


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


def routed(x, m, d, held=None):
    """What the experts ``held = (first, count)`` (default: ``d``'s) give
    of a layer's routed part, for normed input ``x``."""
    d = _Dims(d)
    first, count = held or (d["first"], d["held"])
    weights = _route(x, m["router"]["kernel"], d)
    y = jnp.zeros_like(x)
    for e in range(count):     # expert ``first + e`` is row e of the stack
        y = _add_expert(y, x, weights, first + e, m["w_gate"], m["w_up"],
                        m["w_down"], e)
    return y


def mlp(x, m, d, held=None):
    """The MLP's output for normed input ``x``: the held experts' part and
    the shared expert, added unweighted."""
    return routed(x, m, d, held) + shared_expert(x, m)


# ----------------------------------------------------------------- model
@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, eps):
    return rmsnorm(x, w.astype(jnp.float32), eps)


def residual(x, y, d):
    """``x + residual_multiplier x y``."""
    return x + d["residual_mult"] * y


def layer_forward(x, lp, d, layer: int, keep=None):
    """0-based decoder layer ``layer`` on one sequence: x [T, E] float32
    -> [T, E].  ``keep`` (a dict) is given a Mamba-2 layer's final state
    and convolution rows (``ssm_state`` [H, P, N], ``ssm_conv``) and the
    MLP's normed input and output (``mlp_in``, ``mlp_out``)."""
    h = _norm(x, lp["input_norm"]["scale"], d["eps"])
    if is_ssm(layer, d):
        y, state, rows = ssm(h, lp, _Dims(d))
        if keep is not None:
            keep.update(ssm_state=state, ssm_conv=rows)
    else:
        y = attention(h, lp, _Dims(d))
    x = residual(x, y, d)
    h = _norm(x, lp["post_norm"]["scale"], d["eps"])
    y = mlp(h, lp["mlp"], d)
    if keep is not None:
        keep.update(mlp_in=h, mlp_out=y)
    return residual(x, y, d)


def embed(tokens, top, d):
    return top["embed_tokens"]["embedding"].astype(jnp.float32)[
        jnp.asarray(tokens)] * d["embedding_mult"]


@functools.partial(jax.jit, static_argnames=("eps", "scaling"))
def head_logits(x, top, eps, scaling):
    """Logits over the TIED embedding's slice, / ``logits_scaling``."""
    with jax.default_matmul_precision(PRECISION):
        h = rmsnorm(x, top["final_norm"]["scale"].astype(jnp.float32), eps)
        return h @ top["embed_tokens"]["embedding"].astype(
            jnp.float32).T / scaling


def hidden_states(seq, get_layer, top, num_layers, d, keep=None):
    """Final hidden states (before the last norm) of one token sequence.
    ``keep`` (a dict) is given every Mamba-2 layer's final state and
    convolution rows by its 0-based layer number (``ssm_states``,
    ``ssm_convs``)."""
    x = embed(seq, top, d)
    for i in range(num_layers):
        mine = {} if keep is not None else None
        x = layer_forward(x, get_layer(i), d, i, mine)
        if mine and "ssm_state" in mine:
            keep.setdefault("ssm_states", {})[i] = mine["ssm_state"]
            keep.setdefault("ssm_convs", {})[i] = mine["ssm_conv"]
    return x
