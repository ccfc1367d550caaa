"""Seeded weights of the Brumby-14B-Base cut, made on the device in the
dtype they are used in, a layer at a time (``perfbench/weights_glm5.py``'s
build).

The tree is the one ``dlrover_tpu.serving.params`` converts and
``perfbench/reference_brumby.py`` reads: ``layer_{i}`` with ``retention``
(``q_proj`` [E, Hq, d], ``k_proj`` / ``v_proj`` [E, Hk, d], ``q_norm`` /
``k_norm`` scale [d], ``gate_proj`` kernel [E, Hk] and bias [Hk], ``o_proj``
[Hq, d, E]) and ``mlp`` (``gate_proj`` / ``up_proj`` [E, F], ``down_proj``
[F, E]); ``embed_tokens``, ``final_norm`` and an untied ``lm_head`` [E, V].

Every matrix is LeCun-normal by ITS OWN fan-in; the embedding N(0, 0.02);
the block norms' scales 1; the two head norms' scales 1 + N(0, 0.1), so
that they are not invisible to a comparison.

The gate, so that it spans heads that forget within a chunk and heads that
hardly forget within a request: a key head's memory ``m = 1 / (1 - g)`` is
drawn log-uniform inside its own of ``Hk`` equal parts of [10, 10 000]
tokens (one head a part, the parts' order permuted by the seed), and the
bias is ``logit(g) = ln(m - 1)``; ``W_gate`` at HALF its LeCun scale, so a
token moves a head's gate around its bias (a standard deviation of 0.5 in
the logit) and does not swamp it.
"""

from __future__ import annotations

import functools
from collections.abc import Mapping

import jax
import jax.numpy as jnp

from perfbench.weights import fold_seed
from perfbench.weights_glm5 import _normal

MEMORY_TOKENS = (10.0, 10000.0)


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def make_retention(key, dims, dtype):
    e, hq, hk, d = dims
    ks = jax.random.split(key, 9)
    f32 = jnp.float32
    lo, hi = jnp.log(MEMORY_TOKENS[0]), jnp.log(MEMORY_TOKENS[1])
    part = (jax.random.permutation(ks[7], hk)
            + jax.random.uniform(ks[8], (hk,), f32)) / hk
    memory = jnp.exp(lo + (hi - lo) * part)

    def scale(k):
        return (1.0 + 0.1 * jax.random.normal(k, (d,))).astype(dtype)

    return {
        "q_proj": {"kernel": _normal(ks[0], (e, hq, d), e, dtype)},
        "k_proj": {"kernel": _normal(ks[1], (e, hk, d), e, dtype)},
        "v_proj": {"kernel": _normal(ks[2], (e, hk, d), e, dtype)},
        "q_norm": {"scale": scale(ks[3])},
        "k_norm": {"scale": scale(ks[4])},
        "gate_proj": {"kernel": _normal(ks[5], (e, hk), e, dtype, 0.5),
                      # sigmoid(bias) = 1 - 1 / memory
                      "bias": jnp.log(memory - 1.0)},
        "o_proj": {"kernel": _normal(ks[6], (hq, d, e), hq * d, dtype)},
    }


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def make_mlp(key, dims, dtype):
    e, f = dims
    ks = jax.random.split(key, 3)
    return {
        "gate_proj": {"kernel": _normal(ks[0], (e, f), e, dtype)},
        "up_proj": {"kernel": _normal(ks[1], (e, f), e, dtype)},
        "down_proj": {"kernel": _normal(ks[2], (f, e), f, dtype)},
    }


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def make_top(key, dims, dtype):
    e, v = dims
    ks = jax.random.split(key, 2)
    return {
        "embed_tokens": {"embedding": (
            jax.random.normal(ks[0], (v, e), jnp.float32) * 0.02
        ).astype(dtype)},
        "final_norm": {"scale": jnp.ones((e,), dtype)},
        "lm_head": {"kernel": _normal(ks[1], (e, v), e, dtype)},
    }


class SeededBrumbyParams(Mapping):
    """``params`` whose layers are made when asked for and not kept (the
    engine converts layer by layer; the reference asks again after the
    window): a layer is a function of ``(seed, layer index)`` alone."""

    def __init__(self, cfg, seed: int):
        self.cfg = cfg
        self.key = jax.random.PRNGKey(fold_seed(seed))
        e = cfg.hidden_size
        self._mixer = (e, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_)
        self._mlp = (e, cfg.intermediate_size)
        self._dtype = jnp.dtype(cfg.param_dtype).name
        self._top = None

    def layer(self, i: int):
        key = jax.random.fold_in(self.key, i + 1)
        ones = jnp.ones((self.cfg.hidden_size,), self._dtype)
        return {
            "input_norm": {"scale": ones}, "post_norm": {"scale": ones},
            "retention": make_retention(jax.random.fold_in(key, 5),
                                        self._mixer, self._dtype),
            "mlp": make_mlp(jax.random.fold_in(key, 3), self._mlp,
                            self._dtype)}

    def top(self):
        if self._top is None:
            self._top = make_top(
                jax.random.fold_in(self.key, 0),
                (self.cfg.hidden_size, self.cfg.vocab_size), self._dtype)
        return self._top

    def __getitem__(self, name):
        if name.startswith("layer_"):
            return self.layer(int(name[len("layer_"):]))
        if name in ("embed_tokens", "final_norm", "lm_head"):
            return self.top()[name]
        raise KeyError(name)

    def __iter__(self):
        return iter([f"layer_{i}" for i in range(self.cfg.num_layers)]
                    + ["embed_tokens", "final_norm", "lm_head"])

    def __len__(self):
        return self.cfg.num_layers + 3
