"""Weight quantization for serving/eval.

Two int8 paths, matching the reference's csrc int8 GEMM serving role:

- **int8 storage quantization** (this module): kernels are STORED int8
  with per-output-channel scales — 4x smaller serving/export footprint
  (1.89 GB -> 474 MB measured on the 496M bench model) and 0.9+ greedy
  token agreement after requantization.  Measured honestly: on the
  current v5e rig the in-step dequant does NOT stay fused (XLA
  rematerializes the bf16 weights per decode step), so this is a
  memory/interchange tool, not a latency win — see the numbers in
  tests/test_quantize_weights.py and COVERAGE.md.
- **w8a8 compute quantization** (`LlamaConfig(w8a8=True)` ->
  ops/pallas/quant_matmul.int8_dot_general): both operands int8 on the
  MXU.  The RAW kernel beats bf16 by 1.39x at large M; end-to-end
  forwards pay a per-call dynamic weight-quantization pass that
  currently outweighs it (0.6x at seq-4096 eval, measured) — the
  honest conclusion is that an MXU int8 win needs weights PRE-quantized
  in the layout the kernel reads, a planned follow-up.

Usage::

    qvars = quantize_weights_int8(variables)      # once, host or device
    logits = model.apply(dequantize_weights(qvars), ids)   # inside jit
    # or for generation:
    toks, _ = generate_int8(model, qvars, prompts, ...)
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np



def _is_quantizable(path_leaf, leaf) -> bool:
    name = path_leaf[-1] if path_leaf else ""
    return (
        getattr(leaf, "ndim", 0) >= 2
        and str(name) in ("kernel", "embedding")
        and leaf.shape[-1] >= 128
    )


def quantize_weights_int8(variables: Any) -> Any:
    """Replace kernel/embedding leaves with ``{"__w8__", "q", "scale"}``
    dicts (int8 codes + per-last-dim-channel f32 scales).  Everything
    else passes through unchanged."""

    def walk(tree, path):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        leaf = tree
        if not _is_quantizable(path, leaf):
            return leaf
        x = jnp.asarray(leaf, jnp.float32)
        amax = jnp.max(jnp.abs(x), axis=tuple(range(x.ndim - 1)),
                       keepdims=True)
        scale = jnp.maximum(amax, 1e-8) / 127.0
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        # marker-free: a quantized node is recognized structurally (a
        # bool leaf would become a tracer under jit and break tree walks)
        return {"q": q, "scale": scale.astype(jnp.float32)}

    return walk(variables, ())


def dequantize_weights(qvariables: Any, dtype=jnp.bfloat16) -> Any:
    """Inverse of :func:`quantize_weights_int8`; call INSIDE jit so the
    int8->fp convert fuses into the consuming matmuls (weights are read
    from HBM at int8 width)."""

    def walk(tree):
        if isinstance(tree, dict):
            if set(tree) == {"q", "scale"}:
                return (tree["q"].astype(jnp.float32)
                        * tree["scale"]).astype(dtype)
            return {k: walk(v) for k, v in tree.items()}
        return tree

    return walk(qvariables)


#: Storage dtype for KV quantization scales.  bf16 keeps the paged
#: int8 KV pool's byte overhead at 2/D per element (>=1.9x budget win
#: at D=64; the acceptance bar) — a scale is already a lossy rounding
#: step, so bf16's ~0.4% relative error folds into the quantization
#: noise the drift tests bound, instead of deserving f32's 4 bytes.
KV_SCALE_DTYPE = jnp.bfloat16


def quantize_kv_int8(kv: jax.Array):
    """Symmetric per-vector int8 quantization over the LAST axis (the
    head dim): ``kv [..., D] -> (codes int8 [..., D], scale [...])``.

    The same symmetric amax/127 scheme as :func:`quantize_weights_int8`
    but at per-token-per-head granularity, which is what a paged KV
    pool needs: a block is written token-by-token (prefill chunks,
    decode steps, speculative runs), so the scale must be local to the
    written vector — one scale per whole block would force a
    read-modify-write requantization of the block on every append."""
    x = kv.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(
        jnp.round(x / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale.astype(KV_SCALE_DTYPE)


def dequantize_kv_int8(q: jax.Array, scale: jax.Array,
                       dtype=jnp.bfloat16) -> jax.Array:
    """Inverse of :func:`quantize_kv_int8` (``scale`` broadcasts over
    the last axis); call INSIDE jit so the int8->fp convert fuses into
    the consuming attention einsum and the pool streams from HBM at
    int8 width."""
    return (
        q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
    ).astype(dtype)


def quantized_nbytes(qvariables: Any) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(qvariables):
        total += leaf.size * leaf.dtype.itemsize
    return total


def generate_int8(model, qvariables, prompt_ids, max_new_tokens, rng,
                  **kwargs):
    """KV-cache generation over int8-stored weights: the dequant runs
    inside the jitted prefill/decode programs."""
    from dlrover_tpu.models.generation import generate

    class _Deq:
        """Model proxy whose apply dequantizes first (inside jit)."""

        def __init__(self, inner):
            self._inner = inner
            self.config = inner.config

        def apply(self, variables, *args, **kw):
            return self._inner.apply(
                dequantize_weights(variables), *args, **kw
            )

        def __hash__(self):  # jit static identity for the lru cache
            return hash((id(self._inner), "int8"))

        def __eq__(self, other):
            return (
                isinstance(other, _Deq) and self._inner is other._inner
            )

    return generate(
        _Deq(model), qvariables, prompt_ids, max_new_tokens, rng, **kwargs
    )
