"""Seeded weights of the Kimi-Linear share, made on the device in the dtype
they are used in, a layer at a time (``perfbench/weights_glm5.py``'s
build).

The tree is the one ``dlrover_tpu.serving.params`` converts and
``perfbench/reference_kimi_linear.py`` reads: ``layer_{i}`` with ``kda``
(``q_proj`` / ``k_proj`` / ``v_proj`` [E, H, d]; ``q_conv`` / ``k_conv`` /
``v_conv`` [taps, H d]; ``f_a_proj`` [E, r], ``f_b_proj`` [r, H d],
``dt_bias`` [H d], ``A_log`` [H]; ``b_proj`` [E, H]; ``g_a_proj``,
``g_b_proj``; ``o_norm`` [d]; ``o_proj`` [H, d, E]) or ``attn``
(``q_proj`` [E, H, nope + rope]: no bottleneck, no norm; ``kv_a_proj``,
``kv_a_norm``, ``kv_b_proj``, ``o_proj``), and ``mlp`` as
``weights_sarvam.py`` makes it.  Every matrix is LeCun-normal by ITS OWN
fan-in, an expert's too; a convolution's taps N(0, 1 / taps) with 1 added
to the last (the current position passes); the embedding N(0, 0.02); the
block norms' scales 1; the head norm's scale 1 + N(0, 0.1) and the
router's selection bias N(0, 0.01), so that neither is invisible to a
comparison.

The decay, so that it spans channels that forget within a chunk and
channels that hardly forget: ``A_log`` = ln U(1, 16) a head; ``dt_bias``
the inverse softplus of exp U(ln 1e-4, ln 1) a channel; the bottleneck's
second matrix at half its LeCun scale, so that the bias is not drowned.
A token's log-decay then lies between about -1e-4 and -16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.weights_glm5 import SeededGlm5Params, _normal
from perfbench.weights_sarvam import make_layer as _sarvam_layer


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def make_kda(key, dims, dtype):
    e, h, d, taps, r = dims
    ks = jax.random.split(key, 16)
    f32 = jnp.float32

    def conv(k):
        w = jax.random.normal(k, (taps, h * d), f32) / taps
        return {"kernel": w.at[taps - 1].add(1.0)}

    dt = jnp.exp(jax.random.uniform(
        ks[8], (h * d,), f32, jnp.log(1e-4), jnp.log(1.0)))
    return {
        "q_proj": {"kernel": _normal(ks[0], (e, h, d), e, dtype)},
        "k_proj": {"kernel": _normal(ks[1], (e, h, d), e, dtype)},
        "v_proj": {"kernel": _normal(ks[2], (e, h, d), e, dtype)},
        "q_conv": conv(ks[3]), "k_conv": conv(ks[4]), "v_conv": conv(ks[5]),
        "f_a_proj": {"kernel": _normal(ks[6], (e, r), e, dtype)},
        "f_b_proj": {"kernel": _normal(ks[7], (r, h * d), r, dtype, 0.5)},
        # softplus(dt_bias) = dt
        "dt_bias": jnp.log(jnp.expm1(dt)),
        "A_log": jnp.log(jax.random.uniform(ks[9], (h,), f32, 1.0, 16.0)),
        "b_proj": {"kernel": _normal(ks[10], (e, h), e, dtype)},
        "g_a_proj": {"kernel": _normal(ks[11], (e, r), e, dtype)},
        "g_b_proj": {"kernel": _normal(ks[12], (r, h * d), r, dtype)},
        "o_norm": {"scale": (
            1.0 + 0.1 * jax.random.normal(ks[13], (d,))).astype(dtype)},
        "o_proj": {"kernel": _normal(ks[14], (h, d, e), h * d, dtype)},
    }


class SeededKimiLinearParams(SeededGlm5Params):
    """``SeededGlm5Params`` (layers made when asked for and not kept: a
    layer is a function of ``(seed, layer index)`` alone) with this
    model's two kinds of layer."""

    def __init__(self, cfg, seed: int):
        super().__init__(cfg, seed)
        held = (cfg.moe_experts_held or (0, cfg.num_experts))[1]
        self._dims = (
            cfg.hidden_size, cfg.num_heads, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim,
            cfg.intermediate_size, cfg.expert_width, cfg.num_experts, held)
        self._kda_dims = (cfg.hidden_size, cfg.kda_heads, cfg.kda_head_dim,
                          cfg.kda_conv, cfg.kda_rank)

    def layer(self, i: int):
        spec = self.cfg.layer_specs[i]
        key = jax.random.fold_in(self.key, i + 1)
        layer = _sarvam_layer(key, self._dims, spec.mlp == "sparse",
                              self._dtype)
        attn = layer.pop("attn")
        if spec.mixer == "kda":
            layer["kda"] = make_kda(jax.random.fold_in(key, 7),
                                    self._kda_dims, self._dtype)
        else:
            attn.pop("q_norm")           # this model's query has no norm
            layer["attn"] = attn
        return layer
