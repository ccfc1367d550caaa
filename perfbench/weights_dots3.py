"""Seeded weights of the dots3-note-prev share, made on the device in the
dtype they are used in, a layer at a time (``perfbench/weights.py``'s
build).

The tree is the one ``dlrover_tpu.serving.params`` converts and
``perfbench/reference_dots3.py`` reads: ``layer_{i}`` with ``attn`` (in the
sizes of the layer's KIND: heads, nope size and latent rank are the
layer's own; ``g_proj`` the head gate), of a full layer ``indexer``, and
``mlp`` (dense: ``gate_proj`` / ``up_proj`` / ``down_proj``; sparse:
``router``, ``select_bias``, the held experts' stacks ``w_gate`` / ``w_up``
/ ``w_down`` and the shared expert).  Every matrix is LeCun-normal by ITS
OWN fan-in, an expert's too, but the three that read a RESCALED bottleneck
(``apply_mla_qkv_lora_rescale``: ``q_b_proj``, ``kv_b_proj`` and the
indexer's ``wq_b``), which are N(0, 1 / hidden): the rescale gives a
bottleneck the norm of a hidden-size vector so that its up-projection is
initialised like any matrix that reads the hidden state.  By the
bottleneck's own fan-in a head's scores would have 7 x the spread
(sqrt(5120 / 1024) x sqrt(5120 / 512)), a standard deviation of 6: attention
would be an argmax, and the rows a selection swaps at its threshold under
bf16 would move the logits by 0.85 RMS at 6 k positions (my chip run, PR
47: nothing could be told from a fault).  The embedding N(0, 0.02); RMSNorm
scales 1;
the indexer's LayerNorm scale 1 + N(0, 0.1) and bias N(0, 0.1), the
router's selection bias N(0, 0.01), so that neither is invisible to a
comparison.
"""

from __future__ import annotations

import functools
from collections.abc import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.weights import fold_seed, make_top


def _normal(key, shape, fan_in, dtype, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32)
            * (scale / np.sqrt(fan_in))).astype(dtype)


@functools.partial(jax.jit,
                   static_argnames=("dims", "sparse", "indexed", "rescaled",
                                    "dtype"))
def make_layer(key, dims, sparse, indexed, rescaled, dtype):
    (e, q, h, nope, r, c, v, hi, di, f, fe, experts, held) = dims
    # what a matrix that reads a bottleneck counts as its fan-in
    q_in, c_in = (e, e) if rescaled else (q, c)
    ks = jax.random.split(key, 20)
    ones = functools.partial(jnp.ones, dtype=dtype)
    layer = {
        "input_norm": {"scale": ones((e,))},
        "post_norm": {"scale": ones((e,))},
        "attn": {
            "q_a_proj": {"kernel": _normal(ks[0], (e, q), e, dtype)},
            "q_a_norm": {"scale": ones((q,))},
            "q_b_proj": {"kernel": _normal(ks[1], (q, h, nope + r), q_in,
                                           dtype)},
            "kv_a_proj": {"kernel": _normal(ks[2], (e, c + r), e, dtype)},
            "kv_a_norm": {"scale": ones((c,))},
            "kv_b_proj": {"kernel": _normal(ks[3], (c, h, nope + v), c_in,
                                            dtype)},
            "o_proj": {"kernel": _normal(ks[4], (h, v, e), h * v, dtype)},
            "g_proj": {"kernel": _normal(ks[18], (e, h), e, dtype)},
        },
    }
    if indexed:
        layer["indexer"] = {
            "wq_b": {"kernel": _normal(ks[5], (q, hi, di), q_in, dtype)},
            "wk": {"kernel": _normal(ks[6], (e, di), e, dtype)},
            "k_norm": {
                "scale": (1.0 + 0.1 * jax.random.normal(ks[7], (di,))
                          ).astype(dtype),
                "bias": (0.1 * jax.random.normal(ks[8], (di,))
                         ).astype(dtype)},
            "weights_proj": {"kernel": _normal(ks[9], (e, hi), e, dtype)},
        }
    if not sparse:
        layer["mlp"] = {
            "gate_proj": {"kernel": _normal(ks[10], (e, f), e, dtype)},
            "up_proj": {"kernel": _normal(ks[11], (e, f), e, dtype)},
            "down_proj": {"kernel": _normal(ks[12], (f, e), f, dtype)},
        }
        return layer
    layer["mlp"] = {
        "router": {"kernel": _normal(ks[10], (e, experts), e, jnp.float32)},
        "select_bias": 0.01 * jax.random.normal(ks[11], (experts,),
                                                jnp.float32),
        "w_gate": _normal(ks[12], (held, e, fe), e, dtype),
        "w_up": _normal(ks[13], (held, e, fe), e, dtype),
        "w_down": _normal(ks[14], (held, fe, e), fe, dtype),
        "shared_gate": {"kernel": _normal(ks[15], (e, fe), e, dtype)},
        "shared_up": {"kernel": _normal(ks[16], (e, fe), e, dtype)},
        "shared_down": {"kernel": _normal(ks[17], (fe, e), fe, dtype)},
    }
    return layer


class SeededDots3Params(Mapping):
    """``params`` whose layers are made when asked for and not kept (the
    engine converts layer by layer; the reference asks again after the
    window): a layer is a function of ``(seed, layer index)`` alone."""

    def __init__(self, cfg, seed: int):
        self.cfg = cfg
        self.key = jax.random.PRNGKey(fold_seed(seed))
        self._dtype = jnp.dtype(cfg.param_dtype).name
        self._top = None

    def _dims(self, spec):
        cfg = self.cfg
        c, nope, _ = cfg.latent_dims(spec)
        held = (cfg.moe_experts_held or (0, cfg.num_experts))[1]
        return (cfg.hidden_size, cfg.q_lora_rank, spec.num_heads, nope,
                cfg.qk_rope_head_dim, c, cfg.v_head_dim, cfg.index_n_heads,
                cfg.index_head_dim, cfg.intermediate_size, cfg.expert_width,
                cfg.num_experts, held)

    def layer(self, i: int):
        spec = self.cfg.layer_specs[i]
        return make_layer(jax.random.fold_in(self.key, i + 1),
                          self._dims(spec), spec.mlp == "sparse",
                          self.cfg.latent_dims(spec)[2],
                          bool(self.cfg.mla_lora_rescale), self._dtype)

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
