"""Seeded weights of the Keye-VL-2.0-30B-A3B share (its language model),
made on the device in the dtype they are used in, a layer at a time
(``perfbench/weights_glm5.py``'s build).

The tree is the one ``dlrover_tpu.serving.params`` converts and
``perfbench/reference_keye.py`` reads: ``layer_{i}`` with ``attn``
(``q_proj`` [E, H, D], ``k_proj`` / ``v_proj`` [E, KV, D], ``o_proj`` [H,
D, E], the QK-norm's two scales ``q_norm`` / ``k_norm`` [D]), ``indexer``
(``wq`` [E, Hi, Di], from the layer's input; ``wk`` [E, Di]; ``k_norm``
scale and bias; ``weights_proj`` [E, Hi]) and ``mlp`` (``router`` [E,
experts] float32 and the held experts' stacks ``w_gate`` / ``w_up`` /
``w_down``; no shared expert).  Every matrix is LeCun-normal by ITS OWN
fan-in, an expert's too; the embedding N(0, 0.02); a block's RMSNorm
scales 1; the indexer's LayerNorm scale 1 + N(0, 0.1) and bias N(0, 0.1),
and the QK-norm's two scales 1 + N(0, 0.25), so that none of them is
invisible to a comparison: with LeCun-normal projections a head of q or k
has an RMS of about 1 already, and behind UNIT scales a dropped QK-norm
moved the logits by 0.13 at the 90th percentile where the program stands
at 0.02 (my chip run, PR 58).
"""

from __future__ import annotations

import functools
from collections.abc import Mapping

import jax
import jax.numpy as jnp

from perfbench.weights import fold_seed, make_top
from perfbench.weights_glm5 import _normal


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def make_layer(key, dims, dtype):
    e, h, kv, d, hi, di, fe, experts, held = dims
    ks = jax.random.split(key, 15)
    ones = functools.partial(jnp.ones, dtype=dtype)
    return {
        "input_norm": {"scale": ones((e,))},
        "post_norm": {"scale": ones((e,))},
        "attn": {
            "q_proj": {"kernel": _normal(ks[0], (e, h, d), e, dtype)},
            "k_proj": {"kernel": _normal(ks[1], (e, kv, d), e, dtype)},
            "v_proj": {"kernel": _normal(ks[2], (e, kv, d), e, dtype)},
            "o_proj": {"kernel": _normal(ks[3], (h, d, e), h * d, dtype)},
            "q_norm": {"scale": (1.0 + 0.25 * jax.random.normal(
                ks[13], (d,))).astype(dtype)},
            "k_norm": {"scale": (1.0 + 0.25 * jax.random.normal(
                ks[14], (d,))).astype(dtype)},
        },
        "indexer": {
            "wq": {"kernel": _normal(ks[4], (e, hi, di), e, dtype)},
            "wk": {"kernel": _normal(ks[5], (e, di), e, dtype)},
            "k_norm": {
                "scale": (1.0 + 0.1 * jax.random.normal(ks[6], (di,))
                          ).astype(dtype),
                "bias": (0.1 * jax.random.normal(ks[7], (di,))
                         ).astype(dtype)},
            "weights_proj": {"kernel": _normal(ks[8], (e, hi), e, dtype)},
        },
        "mlp": {
            "router": {"kernel": _normal(ks[9], (e, experts), e,
                                         jnp.float32)},
            "w_gate": _normal(ks[10], (held, e, fe), e, dtype),
            "w_up": _normal(ks[11], (held, e, fe), e, dtype),
            "w_down": _normal(ks[12], (held, fe, e), fe, dtype),
        },
    }


class SeededKeyeParams(Mapping):
    """``params`` whose layers are made when asked for and not kept (the
    engine converts layer by layer; the reference asks again after the
    window): a layer is a function of ``(seed, layer index)`` alone."""

    def __init__(self, cfg, seed: int):
        self.cfg = cfg
        self.key = jax.random.PRNGKey(fold_seed(seed))
        held = (cfg.moe_experts_held or (0, cfg.num_experts))[1]
        self._dims = (
            cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_,
            cfg.index_n_heads, cfg.index_head_dim, cfg.expert_width,
            cfg.num_experts, held)
        self._dtype = jnp.dtype(cfg.param_dtype).name
        self._top = None

    def layer(self, i: int):
        return make_layer(jax.random.fold_in(self.key, i + 1), self._dims,
                          self._dtype)

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
