"""The plain reference of OLMoE-1B-7B's decoder and training loss.

Straight ``jax.numpy`` in float32 with
``jax.default_matmul_precision("highest")``; no kernels, no sort, no
grouped matmul, no code of the program: the experts are a Python loop
over all of them with a mask.  Equations (allenai/OLMoE-1B-7B-0125's
``config.json`` and transformers' ``modeling_olmoe.py``; what the
configuration file lists under ``assumed`` is marked *):

- RMSNorm:  ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w``
- attention block, ``n = RMSNorm(x)``:
  ``h = x + W_o . Attn(RoPE(RMSNorm_q(W_q n)), RoPE(RMSNorm_k(W_k n)), W_v n)``
  ``RMSNorm_q`` and ``RMSNorm_k`` have a learned scale as wide as the
  whole projection (heads x head size) and normalise over that whole
  vector, BEFORE the split into heads and before RoPE.  RoPE at theta in
  the "rotate_half" layout: the head is split in two halves (x1, x2) and,
  with ``a[p, i] = p / theta^(2i/d)``, becomes
  ``(x1 cos a - x2 sin a, x2 cos a + x1 sin a)``.  ``Attn`` is causal
  ``softmax(q k^T / sqrt(d)) v``, one key/value head per query head
  (grouped where there are fewer).
- expert block, ``m = RMSNorm(h)``:
  ``p = softmax(m W_r)`` in float32 over all experts; ``i_1..i_k`` the k
  largest of ``p``; ``y = h + sum_j p[i_j] . E_{i_j}(m)``, the weights NOT
  renormalised (``norm_topk_prob: false``; divided by their sum where it
  is true); ``E_e(m) = W_down,e (silu(W_gate,e m) * W_up,e m)``.  Every
  token goes to all k of its experts: nothing is dropped.
- final RMSNorm, untied output head.
- loss = mean next-token cross entropy over positions 0..T-2
  + ``aux_coef`` x mean over layers of ``E . sum_e f_e P_e``
  + ``z_coef`` x mean over layers of ``mean_t logsumexp(m_t W_r)^2``
  with ``f_e`` the share of the tokens' k picks (all k, all tokens of the
  batch) that chose expert e and ``P_e`` the mean of ``p[e]`` over those
  tokens.  (* the coefficients 0.01 and 0.001 and the mean over layers.)

Weights arrive one layer at a time in the run's own dtype and are
up-cast here, as in ``perfbench/reference.py``, whose norm, RoPE,
attention, embedding and head this file uses as they are.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.reference import (_f32, attention, embed, head_logits,
                                 rmsnorm, rope)


@functools.partial(jax.jit, static_argnames=("theta", "eps"))
def attention_block(x, lp, theta, eps):
    """x [T, E] float32 -> h [T, E]."""
    with jax.default_matmul_precision("highest"):
        a = _f32(lp["attn"])
        t = x.shape[0]
        pos = jnp.arange(t)
        n = rmsnorm(x, lp["input_norm"]["scale"].astype(jnp.float32), eps)
        q = jnp.einsum("te,ehd->thd", n, a["q_proj"]["kernel"])
        k = jnp.einsum("te,ehd->thd", n, a["k_proj"]["kernel"])
        v = jnp.einsum("te,ehd->thd", n, a["v_proj"]["kernel"])
        q = rmsnorm(q.reshape(t, -1), a["q_norm"]["scale"], eps).reshape(
            q.shape)
        k = rmsnorm(k.reshape(t, -1), a["k_norm"]["scale"], eps).reshape(
            k.shape)
        o = attention(rope(q, pos, theta), rope(k, pos, theta), v)
        return x + jnp.einsum("thd,hde->te", o, a["o_proj"]["kernel"])


@functools.partial(jax.jit, static_argnames=("eps",))
def router(h, lp, eps):
    """m = RMSNorm(h), the router's logits and its softmax, all [T, .]."""
    with jax.default_matmul_precision("highest"):
        m = rmsnorm(h, lp["post_norm"]["scale"].astype(jnp.float32), eps)
        logits = m @ lp["mlp"]["router"]["kernel"].astype(jnp.float32)
        return m, logits, jax.nn.softmax(logits, axis=-1)


@jax.jit
def one_expert(m, weight, w_gate, w_up, w_down):
    """``weight[t] * E(m[t])`` with this expert's three matrices; rows
    whose weight is zero are computed and contribute zero (the mask)."""
    with jax.default_matmul_precision("highest"):
        w_gate, w_up, w_down = _f32((w_gate, w_up, w_down))
        out = (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down
        return out * weight[:, None]


def expert_block(tokens_h, lp, eps, top_k, norm_topk_prob):
    """The expert block over ALL the batch's tokens ``tokens_h`` [N, E].
    Returns (y, balance term, z term, picks per expert)."""
    m, logits, p = router(tokens_h, lp, eps)
    num_experts = p.shape[-1]
    top = jnp.argsort(-p, axis=-1)[:, :top_k]                # [N, k]
    chosen = jnp.zeros(p.shape, bool).at[
        jnp.arange(p.shape[0])[:, None], top].set(True)
    weights = jnp.where(chosen, p, 0.0)
    if norm_topk_prob:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    mlp = lp["mlp"]
    y = tokens_h
    for e in range(num_experts):
        y = y + one_expert(m, weights[:, e], mlp["w_gate"][e],
                           mlp["w_up"][e], mlp["w_down"][e])
    counts = chosen.sum(axis=0)
    share = counts.astype(jnp.float32) / (p.shape[0] * top_k)
    balance = num_experts * jnp.sum(share * p.mean(axis=0))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return y, balance, z, counts


def forward(batch, get_layer, top, num_layers, theta, eps, top_k,
            norm_topk_prob):
    """Final hidden states [B, T, E] (before the last norm) and, a layer,
    the balance term, the z term and the picks per expert."""
    b, t = batch.shape
    xs = [embed(jnp.asarray(row), top) for row in batch]
    per_layer = []
    for i in range(num_layers):
        lp = get_layer(i)
        hs = jnp.concatenate([attention_block(x, lp, theta, eps)
                              for x in xs])
        y, balance, z, counts = expert_block(hs, lp, eps, top_k,
                                             norm_topk_prob)
        xs = list(y.reshape(b, t, -1))
        per_layer.append((balance, z, counts))
        del lp
    return xs, per_layer


def lm_loss(batch, get_layer, top, num_layers, theta, eps, top_k,
            norm_topk_prob, aux_coef, z_coef):
    """The training objective on ``batch`` [B, T], in parts:
    ``{"total", "ce", "balance", "z", "counts"}`` (balance and z are means
    over layers without their coefficients, counts is [layers, experts]).
    Traceable: ``jax.grad`` of ``["total"]`` is the reference's gradient."""
    xs, per_layer = forward(batch, get_layer, top, num_layers, theta, eps,
                            top_k, norm_topk_prob)
    total, count = 0.0, 0
    for row, x in zip(batch, xs):
        logp = jax.nn.log_softmax(head_logits(x, top, eps)[:-1], axis=-1)
        labels = jnp.asarray(row)[1:]
        total = total - jnp.take_along_axis(
            logp, labels[:, None], axis=-1).sum()
        count += int(labels.shape[0])
    ce = total / count
    balance = jnp.mean(jnp.stack([p[0] for p in per_layer]))
    z = jnp.mean(jnp.stack([p[1] for p in per_layer]))
    return {"total": ce + aux_coef * balance + z_coef * z, "ce": ce,
            "balance": balance, "z": z,
            "counts": jnp.stack([p[2] for p in per_layer])}
