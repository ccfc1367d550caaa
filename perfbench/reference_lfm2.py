"""The plain reference of LFM2-8B-A1B's decoder and training loss, over one
chip's share of it.

Straight ``jax.numpy`` in float32 with
``jax.default_matmul_precision("highest")``; no kernels, no scan, no sort,
no grouped matmul, no code of the program.  It reads the PUBLISHED keys of
the configuration file (``layer_types``, ``num_dense_layers``,
``conv_L_cache``, ``num_attention_heads``, ``num_key_value_heads``,
``rope_theta``, ``norm_eps``, ``num_experts_per_tok``, ``norm_topk_prob``,
``use_expert_bias``, ``routed_scaling_factor``), not the program's
``LlamaConfig``; the run of published layers that is computed starts at
``deployment.first_layer``.  Equations (LiquidAI/LFM2-8B-A1B ``config.json``
and the catalog's ``described_as``; what the configuration file lists
under ``assumed`` is marked *):

- RMSNorm:  ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w``
- every layer, ``x`` the residual stream: ``x <- x + mixer(RMSNorm(x))``,
  then ``x <- x + ffn(RMSNorm(x))``.
- conv layer (``layer_types[l] == "conv"``), ``n = RMSNorm(x)``:
  ``[B | C | u] = n W_in`` (* this order), three widths of the hidden
  size, no bias; ``v = B * u``; ``c_t = sum_j w_j v_{t - (K - 1 - j)}``,
  ``K = conv_L_cache`` taps a channel, the OLDEST position's first (*),
  zeros ahead of the sequence's first token, no bias, no activation: an
  explicit sum over ``K`` shifted copies; ``y = C * c``; ``x + y W_out``.
- attention layer: 32 query / 8 key-value heads of 64;
  ``q_h <- RMSNorm_64(q_h)``, ``k_h <- RMSNorm_64(k_h)`` with ONE learned
  scale for all query heads and one for all key heads (*), before the
  rotation; plain RoPE at ``rope_theta`` over all 64 dimensions in the
  "rotate_half" layout (*); ``softmax(q k^T / sqrt(64)) v``, key ``j``
  visible to query ``i`` when ``j <= i``, query head ``h`` reading
  key/value head ``h // 4``; ``x + W_o o``.
- dense MLP (published layers below ``num_dense_layers``),
  ``m = RMSNorm(h)``: ``h + W_down (silu(W_gate m) * W_up m)``.
- sparse MLP: ``s = sigmoid(m W_r)`` over all 32 experts in float32;
  ``i_1..i_4`` the 4 largest of ``s + b``, ``b`` the expert bias, which
  enters the CHOICE only; ``w_j = routed_scaling_factor x s[i_j] /
  sum_j s[i_j]`` (the sum over all four, held here or not; * without the
  1e-6 the public code adds under it);
  ``y = h + sum_{j: i_j held} w_j E_{i_j}(m)``, ``E`` a SwiGLU as above;
  no shared expert.  ONE SHARE: only the experts ``first .. first + count
  - 1`` that this chip holds add to the sum; what the absent ones would
  add is left out, as in the program, and that partial result goes on.
- final RMSNorm; logits ``= RMSNorm(x) E^T``, the head the embedding
  itself (* tied) over the chip's slice of the vocabulary; loss = mean
  next-token cross entropy over positions 0..T-2 (no auxiliary term: the
  config gives no coefficient).

- the optimizer's FIRST step (``adamw_first_step``), from the gradient ``g``
  already clipped to a global norm of 1: ``m = (1 - b1) g``,
  ``v = (1 - b2) g^2``, both divided by their bias corrections,
  ``p <- p - lr (m / (sqrt(v) + eps) + wd p)``, the decay on EVERY leaf
  (* the trainer's chain has no mask).

``gate_in``, ``gate_out``, ``shifted``, ``head_norm``, ``choice``,
``pick_weights``, ``head_matrix`` and ``loss_rows`` are the mechanisms
``perfbench/controls_lfm2.py`` replaces one at a time.

``chosen`` (``lm_loss``, ``lm_loss_and_grads``): the experts each token
VISITS may be given, a sparse layer at a time ([N, experts] bool), in place
of the reference's own choice.  The driver gives the system's: which of two
experts whose ``score + bias`` lie closer than bfloat16 rounds a token
visits is then compared on its own (the reference's own choice is still
made and counted, and the picks that differ are counted), and every other
number is compared on the SAME visits, where one token on another expert
is otherwise a whole row of another expert's gradient.

Weights arrive one layer at a time in the run's own dtype and are up-cast
here.  Attention (``reference_laguna.attention``, no window) runs one
key/value head and a block of query rows at a time, the held experts one
at a time over every token (``reference_laguna.routed_sum``).  The
gradient (``lm_loss_and_grads``) is ``jax.vjp`` of these same functions, a
layer at a time from the last to the first; the tied embedding's is the
sum of the head's and the look-up's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.reference import _f32, embed, rmsnorm, rope
from perfbench.reference_laguna import attention, routed_sum, swiglu


# ---- the mechanisms a control replaces (perfbench/controls_lfm2.py)
def gate_in(b, u):
    return b * u


def gate_out(c, conv):
    return c * conv


def shifted(v, back: int):
    """``v`` [T, E] moved ``back`` positions towards the future: row ``t``
    holds ``v[t - back]``, zeros ahead of the first token."""
    return jnp.concatenate(
        [jnp.zeros((back, v.shape[1]), v.dtype), v[:v.shape[0] - back]])


def head_norm(x, scale, eps):
    """x [T, heads, 64]: each head normed alone, one scale for all."""
    return rmsnorm(x, scale, eps)


def choice(scores, bias):
    """What the top-k is taken of."""
    return scores + bias


def pick_weights(weights, scale):
    """weights [N, experts], 0 off the picks."""
    return scale * weights / weights.sum(axis=-1, keepdims=True)


def head_matrix(top):
    """[vocab, E]: the head IS the embedding."""
    return top["embed_tokens"]["embedding"].astype(jnp.float32)


def loss_rows(batch):
    """The rows of the batch whose tokens the loss is the mean over."""
    return range(len(batch))


# ---- layers
def layer_kind(config: dict, i: int) -> dict:
    """What the published keys say of layer ``i`` of the run."""
    j = int(config["deployment"].get("first_layer", 0)) + i
    return {"conv": config["layer_types"][j] == "conv",
            "sparse": j >= int(config["num_dense_layers"])}


def short_conv(v, taps):
    """v [T, E], taps [K, E] oldest first: the sum over K shifted copies."""
    k = taps.shape[0]
    return sum(taps[j] * shifted(v, k - 1 - j) for j in range(k))


@functools.partial(jax.jit, static_argnames=("eps",))
def conv_block(x, lp, eps):
    """x [T, E] float32 -> h [T, E]."""
    with jax.default_matmul_precision("highest"):
        c = _f32(lp["conv"])
        n = rmsnorm(x, lp["input_norm"]["scale"].astype(jnp.float32), eps)
        bcu = jnp.einsum("te,ejc->jtc", n, c["in_proj"]["kernel"])
        y = gate_out(bcu[1], short_conv(gate_in(bcu[0], bcu[2]), c["taps"]))
        return x + y @ c["out_proj"]["kernel"]


@functools.partial(jax.jit, static_argnames=("eps", "theta"))
def _qkv(x, lp, eps, theta):
    with jax.default_matmul_precision("highest"):
        a = _f32(lp["attn"])
        pos = jnp.arange(x.shape[0])
        n = rmsnorm(x, lp["input_norm"]["scale"].astype(jnp.float32), eps)
        q = jnp.einsum("te,ehd->thd", n, a["q_proj"]["kernel"])
        k = jnp.einsum("te,ehd->thd", n, a["k_proj"]["kernel"])
        v = jnp.einsum("te,ehd->thd", n, a["v_proj"]["kernel"])
        q = head_norm(q, a["q_norm"]["scale"], eps)
        k = head_norm(k, a["k_norm"]["scale"], eps)
        return rope(q, pos, theta), rope(k, pos, theta), v


@jax.jit
def _out(x, o, w_o):
    with jax.default_matmul_precision("highest"):
        return x + jnp.einsum("thd,hde->te", o, w_o.astype(jnp.float32))


def attention_block(x, lp, config, eps):
    q, k, v = _qkv(x, {"attn": lp["attn"], "input_norm": lp["input_norm"]},
                   eps, float(config["rope_theta"]))
    return _out(x, attention(q, k, v, None), lp["attn"]["o_proj"]["kernel"])


@functools.partial(jax.jit, static_argnames=("eps", "biased"))
def router(h, lp, eps, biased):
    """m = RMSNorm(h), the sigmoid scores of all experts and what the
    choice is made of, [N, .] each."""
    with jax.default_matmul_precision("highest"):
        m = rmsnorm(h, lp["post_norm"]["scale"].astype(jnp.float32), eps)
        s = jax.nn.sigmoid(
            m @ lp["mlp"]["router"]["kernel"].astype(jnp.float32))
        bias = (lp["mlp"]["select_bias"].astype(jnp.float32) if biased
                else jnp.zeros(s.shape[-1:]))
        return m, s, choice(s, bias)


def sparse_parts(tokens_h, lp, eps, config, held, chosen=None):
    """The sparse MLP over ALL the batch's tokens ``tokens_h`` [N, E]:
    (what the held routed experts add, picks per expert over all of them
    by the reference's OWN choice, how many of those are not the picks of
    a choice by score alone, how many are not the picks ``chosen``).
    ``held = (first, count)``: ``lp``'s expert weights are those ``count``
    experts'.  ``chosen`` [N, experts] bool: the experts each token
    visits, where they are given."""
    m, s, chosen_of = router(tokens_h, lp, eps,
                             bool(config["use_expert_bias"]))

    def largest(of):
        top = jnp.argsort(-of, axis=-1)[:, :config["num_experts_per_tok"]]
        return jnp.zeros(s.shape, bool).at[
            jnp.arange(s.shape[0])[:, None], top].set(True)

    own = largest(chosen_of)
    chosen = own if chosen is None else jnp.asarray(chosen)
    weights = jnp.where(chosen, s, 0.0)
    scale = float(config["routed_scaling_factor"])
    weights = (pick_weights(weights, scale) if config["norm_topk_prob"]
               else scale * weights)
    mlp = lp["mlp"]
    first, count = held
    routed = routed_sum(weights[:, first:first + count], m,
                        mlp["w_gate"], mlp["w_up"], mlp["w_down"])
    return (routed, own.sum(axis=0), (own & ~largest(s)).sum(),
            (own & ~chosen).sum())


@functools.partial(jax.jit, static_argnames=("eps",))
def dense_mlp(h, lp, eps):
    with jax.default_matmul_precision("highest"):
        m = rmsnorm(h, lp["post_norm"]["scale"].astype(jnp.float32), eps)
    mlp = lp["mlp"]
    return h + swiglu(m, mlp["gate_proj"]["kernel"],
                      mlp["up_proj"]["kernel"], mlp["down_proj"]["kernel"])


def layer_step(xs, lp, kind, config, held, chosen=None):
    """One decoder layer over the batch's sequences ``xs`` ([T, E] each):
    the sequences behind it, and of a sparse layer (else None) the picks
    per expert, how many of them the bias moved and how many are not the
    given ``chosen``."""
    eps = float(config["norm_eps"])
    if kind["conv"]:
        hs = [conv_block(x, {"conv": lp["conv"],
                             "input_norm": lp["input_norm"]}, eps)
              for x in xs]
    else:
        hs = [attention_block(x, lp, config, eps) for x in xs]
    if not kind["sparse"]:
        return [dense_mlp(h, lp, eps) for h in hs], None
    h = jnp.concatenate(hs)
    routed, *counted = sparse_parts(h, lp, eps, config, held, chosen)
    return (list((h + routed).reshape(len(xs), *xs[0].shape)),
            tuple(counted))


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logits(x, top, eps):
    with jax.default_matmul_precision("highest"):
        h = rmsnorm(x, top["final_norm"]["scale"].astype(jnp.float32), eps)
        return h @ head_matrix(top).T


def cross_entropy(xs, top, batch, eps):
    """Mean next-token cross entropy over every row's positions 0..T-2,
    from the hidden states ``xs`` before the last norm."""
    total, n = 0.0, 0
    for j in loss_rows(batch):
        row, x = batch[j], xs[j]
        logp = jax.nn.log_softmax(head_logits(x, top, eps)[:-1], axis=-1)
        labels = jnp.asarray(row)[1:]
        total = total - jnp.take_along_axis(
            logp, labels[:, None], axis=-1).sum()
        n += int(labels.shape[0])
    return total / n


def _answer(ce, xs, routing, top, eps):
    scale = top["final_norm"]["scale"].astype(jnp.float32)
    return {"total": ce, "ce": ce,
            "counts": jnp.stack([r[0] for r in routing]),
            "moved_by_bias": jnp.stack([r[1] for r in routing]),
            "not_as_chosen": jnp.stack([r[2] for r in routing]),
            "hidden": jnp.stack([rmsnorm(x, scale, eps) for x in xs])}


def _chosen_of_layers(config, chosen):
    """``i -> the given picks of layer i`` (None: its own choice), of
    ``chosen``, one entry a SPARSE layer in layer order."""
    if chosen is None:
        return lambda i: None
    sparse = [i for i in range(config["num_hidden_layers"])
              if layer_kind(config, i)["sparse"]]
    return dict(zip(sparse, chosen, strict=True)).get


def lm_loss(batch, get_layer, top, config, held, chosen=None):
    """The training objective on ``batch`` [B, T]: ``{"total", "ce",
    "counts" [sparse layers, experts], "moved_by_bias" and
    "not_as_chosen" [sparse layers], "hidden" [B, T, E] (after the last
    norm)}``; ``top`` holds ``embed_tokens`` and ``final_norm``.
    Traceable: ``jax.grad`` of ``["total"]`` is the reference's gradient."""
    eps = float(config["norm_eps"])
    xs = [embed(jnp.asarray(row), top) for row in batch]
    counts, given = [], _chosen_of_layers(config, chosen)
    for i in range(config["num_hidden_layers"]):
        xs, c = layer_step(xs, get_layer(i), layer_kind(config, i), config,
                           held, given(i))
        if c is not None:
            counts.append(c)
    return _answer(cross_entropy(xs, top, batch, eps), xs, counts, top, eps)


def lm_loss_and_grads(batch, get_layer, top, config, held, visit,
                      chosen=None):
    """``lm_loss``'s answer, and the gradient of its ``"total"``: reverse
    mode by hand over the same functions, one layer's backward alive at a
    time.  ``visit(i, grads)`` is given layer ``i``'s gradient in
    ``get_layer(i)``'s tree, from the last layer to the first, then
    ``visit("top", grads)`` in ``top``'s (the embedding's the sum of the
    head's and the look-up's), each in its leaf's dtype."""
    eps = float(config["norm_eps"])
    rows = [jnp.asarray(row) for row in batch]
    xs = [embed(row, top) for row in rows]
    inputs, counts, given = [], [], _chosen_of_layers(config, chosen)
    for i in range(config["num_hidden_layers"]):
        inputs.append(xs)
        xs, c = layer_step(xs, get_layer(i), layer_kind(config, i), config,
                           held, given(i))
        if c is not None:
            counts.append(c)
    ce, pull = jax.vjp(
        lambda xs, top: cross_entropy(xs, top, batch, eps), xs, top)
    answer = _answer(ce, xs, counts, top, eps)
    dxs, dtop = pull(jnp.ones_like(ce))
    del xs, pull
    for i in reversed(range(config["num_hidden_layers"])):
        kind = layer_kind(config, i)
        _, pull = jax.vjp(
            lambda xs, lp: layer_step(xs, lp, kind, config, held,
                                      given(i))[0],
            inputs.pop(), get_layer(i))
        dxs, dlp = pull(dxs)
        visit(i, dlp)      # reads the leaves: the host waits for the layer
        del pull, dlp
    _, pull = jax.vjp(lambda top: [embed(row, top) for row in rows], top)
    visit("top", jax.tree_util.tree_map(jnp.add, dtop, pull(dxs)[0]))
    return answer


def adamw_first_step(p, g, lr, b1, b2, eps, weight_decay):
    """A leaf ``p`` behind AdamW's FIRST step on the gradient ``g`` (as
    clipped), in float32: the moments start at zero, so their bias
    corrections give ``g`` and ``g^2`` back; the decay is decoupled and on
    every leaf."""
    p, g = p.astype(jnp.float32), g.astype(jnp.float32)
    m = (1 - b1) * g / (1 - b1)
    v = (1 - b2) * jnp.square(g) / (1 - b2)
    return p - lr * (m / (jnp.sqrt(v) + eps) + weight_decay * p)
