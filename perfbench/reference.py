"""The plain reference: Mistral-7B's decoder as published, nothing else.

Straight ``jax.numpy`` in float32 with
``jax.default_matmul_precision("highest")``; no kernels, no cache, no
batching tricks, no code of the program.  Equations (Mistral-7B-v0.3 /
the Llama family it follows):

- RMSNorm:  y = x / sqrt(mean(x^2) + eps) * w
- RoPE at theta: the head dimension is split in two halves (x1, x2);
  with angle a[p, i] = p / theta^(2i/d):
  (x1 cos a - x2 sin a, x2 cos a + x1 sin a)   (the "rotate_half" layout)
- attention: causal softmax(q k^T / sqrt(d)) v, grouped queries — query
  head j reads key/value head j // (H / KV)
- SwiGLU:  down( silu(gate(x)) * up(x) )
- pre-norm residual blocks, final RMSNorm, untied output head
- loss: mean next-token cross entropy over positions 0..T-2

Weights arrive one layer at a time in the run's own dtype and are
up-cast here: a float32 copy of a whole cell's model would not fit
beside the system under test.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 1024   # queries per attention block: bounds the score matrix


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rope(x, positions, theta):
    """x [T, heads, d]; positions [T]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal grouped-query attention.  q [T, H, d]; k, v [T, KV, d]."""
    t, h, d = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    outs = []
    for s in range(0, t, Q_BLOCK):
        qb = q[s:s + Q_BLOCK]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(float(d))
        qpos = jnp.arange(s, s + qb.shape[0])[:, None]
        mask = jnp.arange(t)[None, :] <= qpos
        scores = jnp.where(mask[None], scores, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd",
                               jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("theta", "eps"))
def layer_forward(x, lp, theta, eps):
    """One decoder layer on one sequence.  x [T, E] float32; ``lp`` the
    layer's weights in the model's tree, any float dtype."""
    with jax.default_matmul_precision("highest"):
        lp = _f32(lp)
        t = x.shape[0]
        pos = jnp.arange(t)
        a = lp["attn"]
        h = rmsnorm(x, lp["input_norm"]["scale"], eps)
        q = jnp.einsum("te,ehd->thd", h, a["q_proj"]["kernel"])
        k = jnp.einsum("te,ehd->thd", h, a["k_proj"]["kernel"])
        v = jnp.einsum("te,ehd->thd", h, a["v_proj"]["kernel"])
        o = attention(rope(q, pos, theta), rope(k, pos, theta), v)
        x = x + jnp.einsum("thd,hde->te", o, a["o_proj"]["kernel"])
        h = rmsnorm(x, lp["post_norm"]["scale"], eps)
        m = lp["mlp"]
        gate = h @ m["gate_proj"]["kernel"]
        up = h @ m["up_proj"]["kernel"]
        return x + (jax.nn.silu(gate) * up) @ m["down_proj"]["kernel"]


@jax.jit
def embed(tokens, top):
    return top["embed_tokens"]["embedding"].astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logits(x, top, eps):
    with jax.default_matmul_precision("highest"):
        h = rmsnorm(x, top["final_norm"]["scale"].astype(jnp.float32), eps)
        return h @ top["lm_head"]["kernel"].astype(jnp.float32)


def hidden_states(seqs, get_layer, top, num_layers, theta, eps):
    """Final hidden states (before the last norm) of each 1-D token
    sequence.  Layers are the outer loop, so each is made once."""
    xs = [embed(jnp.asarray(s), top) for s in seqs]
    for i in range(num_layers):
        lp = get_layer(i)
        xs = [layer_forward(x, lp, theta, eps) for x in xs]
        del lp
    return xs


def lm_loss(batch, get_layer, top, num_layers, theta, eps):
    """Mean next-token cross entropy of ``batch`` [B, T] (the training
    objective the system optimises: every row's positions 0..T-2)."""
    xs = hidden_states(list(batch), get_layer, top, num_layers, theta, eps)
    total, count = 0.0, 0
    for row, x in zip(batch, xs):
        logits = head_logits(x, top, eps)[:-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        labels = jnp.asarray(row)[1:]
        total += float(-jnp.take_along_axis(
            logp, labels[:, None], axis=-1).sum())
        count += int(labels.shape[0])
    return total / count
