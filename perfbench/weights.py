"""Seeded weights, made on the device in the dtype they are used in.

One jitted function makes one decoder layer from a key, another the
embedding, final norm and output head.  A layer is a function of
``(seed, layer index)`` alone, so the reference can make the same layer
again after the window instead of keeping a second copy of the model.

The tree is the one ``dlrover_tpu.models.llama.LlamaModel`` holds without
``nn.scan`` (``layer_{i}`` subtrees), which is what
``InferenceEngine(cfg, variables)`` takes.  Scales follow the model's own
initialisers (LeCun normal kernels, N(0, 0.02) embedding, unit norms).
"""

from __future__ import annotations

import functools
from collections.abc import Mapping

import jax
import jax.numpy as jnp
import numpy as np


def fold_seed(seed: int) -> int:
    """``--seed`` may exceed 32 signed bits; a PRNG key takes 31."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0] >> 1)


def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * (1.0 / np.sqrt(fan_in))).astype(dtype)


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def make_layer(key, dims, dtype):
    e, f, h, kv, d = dims
    ks = jax.random.split(key, 7)
    ones = jnp.ones((e,), dtype)
    return {
        "input_norm": {"scale": ones},
        "post_norm": {"scale": ones},
        "attn": {
            "q_proj": {"kernel": _normal(ks[0], (e, h, d), e, dtype)},
            "k_proj": {"kernel": _normal(ks[1], (e, kv, d), e, dtype)},
            "v_proj": {"kernel": _normal(ks[2], (e, kv, d), e, dtype)},
            "o_proj": {"kernel": _normal(ks[3], (h, d, e), h * d, dtype)},
        },
        "mlp": {
            "gate_proj": {"kernel": _normal(ks[4], (e, f), e, dtype)},
            "up_proj": {"kernel": _normal(ks[5], (e, f), e, dtype)},
            "down_proj": {"kernel": _normal(ks[6], (f, e), f, dtype)},
        },
    }


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def make_top(key, dims, dtype):
    e, v = dims
    k0, k1 = jax.random.split(key)
    return {
        "embed_tokens": {"embedding": (
            jax.random.normal(k0, (v, e), jnp.float32) * 0.02).astype(dtype)},
        "final_norm": {"scale": jnp.ones((e,), dtype)},
        "lm_head": {"kernel": _normal(k1, (e, v), e, dtype)},
    }


class SeededParams(Mapping):
    """``params`` of a ``LlamaModel`` (no scan) whose layers are made when
    they are asked for and not kept: the engine converts layer by layer
    into its own layout, so the device never holds the model twice."""

    def __init__(self, cfg, seed: int):
        self.cfg = cfg
        self.key = jax.random.PRNGKey(fold_seed(seed))
        self._layer_dims = (cfg.hidden_size, cfg.intermediate_size,
                            cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_)
        self._dtype = jnp.dtype(cfg.param_dtype).name
        self._top = None

    def layer(self, i: int):
        return make_layer(jax.random.fold_in(self.key, i + 1),
                          self._layer_dims, self._dtype)

    def top(self):
        if self._top is None:
            self._top = make_top(
                jax.random.fold_in(self.key, 0),
                (self.cfg.hidden_size, self.cfg.vocab_size), self._dtype)
        return self._top

    def _keys(self):
        return [f"layer_{i}" for i in range(self.cfg.num_layers)] + [
            "embed_tokens", "final_norm", "lm_head"]

    def __getitem__(self, name):
        if name.startswith("layer_"):
            return self.layer(int(name[len("layer_"):]))
        if name in ("embed_tokens", "final_norm", "lm_head"):
            return self.top()[name]
        raise KeyError(name)

    def __iter__(self):
        return iter(self._keys())

    def __len__(self):
        return self.cfg.num_layers + 3
