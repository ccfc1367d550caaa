"""Seeded weights of the granite-4.0-h-small share, made on the device in
the dtype they are used in, a layer at a time (``perfbench/weights_glm5.py``'s
build).

The tree is the one ``dlrover_tpu.serving.params`` converts and
``perfbench/reference_granite.py`` reads: ``layer_{i}`` with ``ssm``
(``in_proj`` [E, 2 H P + 2 N + H] to ``[z | xBC | dt]``; ``conv`` kernel
[taps, H P + 2 N] and bias; ``dt_bias``, ``A_log``, ``D`` [H]; ``norm`` [H
P]; ``out_proj`` [H P, E]) or ``attn`` (``q_proj`` [E, H, d], ``k_proj`` /
``v_proj`` [E, KV, d], ``o_proj`` [H, d, E]; no bias, no norm), and ``mlp``
(``router`` [E, experts] float32, ``w_gate`` / ``w_up`` [held, E, F],
``w_down`` [held, F, E], ``shared_gate`` / ``shared_up`` [E, Fs],
``shared_down``).  The embedding is TIED: there is no ``lm_head``.

Every matrix is LeCun-normal by ITS OWN fan-in, an expert's too; the
convolution's taps N(0, 1 / taps) with 1 added to the last (the current
position passes), its bias N(0, 0.1); the embedding N(0, 0.02); the block
norms' scales 1; the gated norm's scale 1 + N(0, 0.1), so that it is not
invisible to a comparison; ``D`` = 1.

The decay, so that it spans heads that forget within a chunk and heads
that hardly forget: ``A_log`` = ln U(1, 16) a head; ``dt_bias`` the inverse
softplus of exp U(ln 1e-3, ln 1e-1) a head (the family's ``time_step_min``
/ ``time_step_max``).
"""

from __future__ import annotations

import functools
from collections.abc import Mapping

import jax
import jax.numpy as jnp

from perfbench.weights import fold_seed
from perfbench.weights_glm5 import _normal


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def make_ssm(key, dims, dtype):
    e, h, p, n, taps = dims
    w, xbc = h * p, h * p + 2 * n
    ks = jax.random.split(key, 8)
    f32 = jnp.float32
    dt = jnp.exp(jax.random.uniform(
        ks[3], (h,), f32, jnp.log(1e-3), jnp.log(1e-1)))
    taps_w = jax.random.normal(ks[1], (taps, xbc), f32) / taps
    return {
        "in_proj": {"kernel": _normal(ks[0], (e, w + xbc + h), e, dtype)},
        "conv": {"kernel": taps_w.at[taps - 1].add(1.0),
                 "bias": 0.1 * jax.random.normal(ks[2], (xbc,), f32)},
        # softplus(dt_bias) = dt
        "dt_bias": jnp.log(jnp.expm1(dt)),
        "A_log": jnp.log(jax.random.uniform(ks[4], (h,), f32, 1.0, 16.0)),
        "D": jnp.ones((h,), f32),
        "norm": {"scale": (
            1.0 + 0.1 * jax.random.normal(ks[5], (w,))).astype(dtype)},
        "out_proj": {"kernel": _normal(ks[6], (w, e), w, dtype)},
    }


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def make_attn(key, dims, dtype):
    e, h, kv, d = dims
    ks = jax.random.split(key, 4)
    return {
        "q_proj": {"kernel": _normal(ks[0], (e, h, d), e, dtype)},
        "k_proj": {"kernel": _normal(ks[1], (e, kv, d), e, dtype)},
        "v_proj": {"kernel": _normal(ks[2], (e, kv, d), e, dtype)},
        "o_proj": {"kernel": _normal(ks[3], (h, d, e), h * d, dtype)},
    }


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def make_mlp(key, dims, dtype):
    e, f, fs, experts, held = dims
    ks = jax.random.split(key, 8)
    return {
        "router": {"kernel": _normal(ks[0], (e, experts), e, jnp.float32)},
        "w_gate": _normal(ks[1], (held, e, f), e, dtype),
        "w_up": _normal(ks[2], (held, e, f), e, dtype),
        "w_down": _normal(ks[3], (held, f, e), f, dtype),
        "shared_gate": {"kernel": _normal(ks[4], (e, fs), e, dtype)},
        "shared_up": {"kernel": _normal(ks[5], (e, fs), e, dtype)},
        "shared_down": {"kernel": _normal(ks[6], (fs, e), fs, dtype)},
    }


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def make_top(key, dims, dtype):
    e, v = dims
    return {
        "embed_tokens": {"embedding": (
            jax.random.normal(key, (v, e), jnp.float32) * 0.02
        ).astype(dtype)},
        "final_norm": {"scale": jnp.ones((e,), dtype)},
    }


class SeededGraniteParams(Mapping):
    """``params`` whose layers are made when asked for and not kept (the
    engine converts layer by layer; the reference asks again after the
    window): a layer is a function of ``(seed, layer index)`` alone."""

    def __init__(self, cfg, seed: int):
        self.cfg = cfg
        self.key = jax.random.PRNGKey(fold_seed(seed))
        held = (cfg.moe_experts_held or (0, cfg.num_experts))[1]
        e = cfg.hidden_size
        self._ssm = (e, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                     cfg.ssm_conv)
        self._attn = (e, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_)
        self._mlp = (e, cfg.expert_width, cfg.moe_shared_width,
                     cfg.num_experts, held)
        self._dtype = jnp.dtype(cfg.param_dtype).name
        self._top = None

    def layer(self, i: int):
        key = jax.random.fold_in(self.key, i + 1)
        ones = jnp.ones((self.cfg.hidden_size,), self._dtype)
        layer = {"input_norm": {"scale": ones}, "post_norm": {"scale": ones},
                 "mlp": make_mlp(jax.random.fold_in(key, 3), self._mlp,
                                 self._dtype)}
        if self.cfg.layer_specs[i].mixer == "ssm":
            layer["ssm"] = make_ssm(jax.random.fold_in(key, 7), self._ssm,
                                    self._dtype)
        else:
            layer["attn"] = make_attn(jax.random.fold_in(key, 5),
                                      self._attn, self._dtype)
        return layer

    def top(self):
        if self._top is None:
            self._top = make_top(
                jax.random.fold_in(self.key, 0),
                (self.cfg.hidden_size, self.cfg.vocab_size), self._dtype)
        return self._top

    def __getitem__(self, name):
        if name.startswith("layer_"):
            return self.layer(int(name[len("layer_"):]))
        if name in ("embed_tokens", "final_norm"):
            return self.top()[name]
        raise KeyError(name)

    def __iter__(self):
        return iter([f"layer_{i}" for i in range(self.cfg.num_layers)]
                    + ["embed_tokens", "final_norm"])

    def __len__(self):
        return self.cfg.num_layers + 2
