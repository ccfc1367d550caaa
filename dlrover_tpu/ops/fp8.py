"""FP8 training: quantized matmuls with current scaling.

Parity target: the reference's fp8 option in the AMP optimization
(reference: atorch/atorch/auto/opt_lib/amp_optimization.py:377, fp8 via
TransformerEngine).  TPU-native shape: an fp8 ``dot_general`` injected
into flax ``DenseGeneral`` layers (``LlamaConfig(fp8=True)``), built from
a fake-quantize with straight-through gradients:

- forward operands are quantized to ``float8_e4m3fn`` with per-tensor
  *current scaling* (scale = e4m3_max / amax, recomputed every step — the
  stateless variant of TransformerEngine's delayed scaling, so no amax
  history threads through the train state);
- the incoming gradient is quantized to ``float8_e5m2`` (wider range,
  lower precision — the standard fp8 training recipe) by an
  identity-forward ``grad_quant_fp8`` wrapped around the dot output, so
  the quantization happens BEFORE autodiff's transposed dot_generals —
  dgrad and wgrad matmuls consume the e5m2 gradient, matching what
  fp8-capable hardware executes;
- the matmul itself runs on dequantized bf16 values: v5e has no fp8 MXU
  mode, so fp8 here buys *numerics parity and a validated migration
  path* (and, via ``jnp.float8_*`` storage dtypes, memory), while on
  fp8-capable hardware XLA can fuse quantize->dot natively.

Accuracy guard: fully-masked/zero tensors quantize to zero scale safely,
and quantization error is bounded by the fp8 eps times amax.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

# Finite maxima of the fp8 formats (jnp.finfo(jnp.float8_e4m3fn).max etc.;
# hardcoded so the module imports even on jax builds without fp8 dtypes).
E4M3_MAX = 448.0
E5M2_MAX = 57344.0


def quantize_dequantize(x: jax.Array, fp8_dtype: Any, max_val: float) -> jax.Array:
    """Round-trip x through fp8 with per-tensor current scaling."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf))
    # inf/nan amax (overflow spikes — the canonical fp8 hazard) must not
    # zero the scale and NaN-poison the whole tensor: fall back to
    # scale=1, letting clip saturate only the overflowed entries.
    ok = jnp.isfinite(amax) & (amax > 0)
    scale = jnp.where(ok, max_val / jnp.where(ok, amax, 1.0), 1.0)
    q = jnp.clip(xf * scale, -max_val, max_val).astype(fp8_dtype)
    return (q.astype(jnp.float32) / scale).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def fake_quant_fp8(x: jax.Array) -> jax.Array:
    """Quantize to e4m3 in forward; straight-through gradient."""
    return quantize_dequantize(x, jnp.float8_e4m3fn, E4M3_MAX)


def _fq_fwd(x):
    return quantize_dequantize(x, jnp.float8_e4m3fn, E4M3_MAX), None


def _fq_bwd(_, g):
    return (g,)


fake_quant_fp8.defvjp(_fq_fwd, _fq_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def grad_quant_fp8(x: jax.Array) -> jax.Array:
    """Identity forward; quantizes the incoming cotangent to e5m2 —
    place around a dot output so the transposed dots see fp8 grads."""
    return x


def _gq_fwd(x):
    return x, None


def _gq_bwd(_, g):
    return (quantize_dequantize(g, jnp.float8_e5m2, E5M2_MAX),)


grad_quant_fp8.defvjp(_gq_fwd, _gq_bwd)


def fp8_dot_general(
    lhs: jax.Array,
    rhs: jax.Array,
    dimension_numbers,
    precision=None,
    preferred_element_type: Optional[Any] = None,
):
    """Drop-in ``lax.dot_general`` with fp8-quantized operands and
    fp8-quantized gradients.  Inject into flax layers:
    ``nn.DenseGeneral(..., dot_general=fp8_dot_general)``.
    """
    return grad_quant_fp8(jax.lax.dot_general(
        fake_quant_fp8(lhs),
        fake_quant_fp8(rhs),
        dimension_numbers,
        precision=precision,
        preferred_element_type=preferred_element_type,
    ))
