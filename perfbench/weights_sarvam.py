"""Seeded weights of the sarvam-105b share, made on the device in the dtype
they are used in, a layer at a time (``perfbench/weights_glm5.py``'s
build).

The tree is the one ``dlrover_tpu.serving.params`` converts and
``perfbench/reference_sarvam.py`` reads: ``layer_{i}`` with ``attn``
(``q_proj`` [E, H, nope + rope]: no bottleneck; ``q_norm``; ``kv_a_proj``,
``kv_a_norm``, ``kv_b_proj``, ``o_proj``) and ``mlp`` (dense:
``gate_proj`` / ``up_proj`` / ``down_proj``; sparse: ``router``,
``select_bias``, the held experts' stacks ``w_gate`` / ``w_up`` /
``w_down`` and the shared expert).  Every matrix is LeCun-normal by ITS
OWN fan-in, an expert's too; the embedding N(0, 0.02); the block norms'
scales 1; the query norm's scale 1 + N(0, 0.1) and the router's selection
bias N(0, 0.01), so that neither is invisible to a comparison.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.weights_glm5 import SeededGlm5Params, _normal


@functools.partial(jax.jit, static_argnames=("dims", "sparse", "dtype"))
def make_layer(key, dims, sparse, dtype):
    (e, h, nope, r, c, v, f, fe, experts, held) = dims
    ks = jax.random.split(key, 16)
    ones = functools.partial(jnp.ones, dtype=dtype)
    layer = {
        "input_norm": {"scale": ones((e,))},
        "post_norm": {"scale": ones((e,))},
        "attn": {
            "q_proj": {"kernel": _normal(ks[0], (e, h, nope + r), e, dtype)},
            "q_norm": {"scale": (
                1.0 + 0.1 * jax.random.normal(ks[1], (nope + r,))
            ).astype(dtype)},
            "kv_a_proj": {"kernel": _normal(ks[2], (e, c + r), e, dtype)},
            "kv_a_norm": {"scale": ones((c,))},
            "kv_b_proj": {"kernel": _normal(ks[3], (c, h, nope + v), c,
                                            dtype)},
            "o_proj": {"kernel": _normal(ks[4], (h, v, e), h * v, dtype)},
        },
    }
    if not sparse:
        layer["mlp"] = {
            "gate_proj": {"kernel": _normal(ks[5], (e, f), e, dtype)},
            "up_proj": {"kernel": _normal(ks[6], (e, f), e, dtype)},
            "down_proj": {"kernel": _normal(ks[7], (f, e), f, dtype)},
        }
        return layer
    layer["mlp"] = {
        "router": {"kernel": _normal(ks[5], (e, experts), e, jnp.float32)},
        "select_bias": 0.01 * jax.random.normal(ks[6], (experts,),
                                                jnp.float32),
        "w_gate": _normal(ks[7], (held, e, fe), e, dtype),
        "w_up": _normal(ks[8], (held, e, fe), e, dtype),
        "w_down": _normal(ks[9], (held, fe, e), fe, dtype),
        "shared_gate": {"kernel": _normal(ks[10], (e, fe), e, dtype)},
        "shared_up": {"kernel": _normal(ks[11], (e, fe), e, dtype)},
        "shared_down": {"kernel": _normal(ks[12], (fe, e), fe, dtype)},
    }
    return layer


class SeededSarvamParams(SeededGlm5Params):
    """``SeededGlm5Params`` (layers made when asked for and not kept: a
    layer is a function of ``(seed, layer index)`` alone) with this
    model's layer."""

    def __init__(self, cfg, seed: int):
        super().__init__(cfg, seed)
        held = (cfg.moe_experts_held or (0, cfg.num_experts))[1]
        self._dims = (
            cfg.hidden_size, cfg.num_heads, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim,
            cfg.intermediate_size, cfg.expert_width, cfg.num_experts, held)

    def layer(self, i: int):
        return make_layer(jax.random.fold_in(self.key, i + 1), self._dims,
                          self.cfg.layer_specs[i].mlp == "sparse",
                          self._dtype)
