"""Rotary position embedding over WHOLE heads, as one elementwise TPU
Pallas kernel beside the ``jnp`` form that is its oracle and the off-chip
path.

A head of ``d`` lanes rotates its first ``2 * half`` in halves (lane ``i``
with lane ``i + half``); the rest pass through.  With two float32 rows a
position over the whole head,

    cos = [ c, c, 1 ...]        sin = [-s, s, 0 ...]

and ``pair(x)[i] = x[i + half]`` below ``half``, ``x[i - half]`` above
(two rolls of the head and a select by lane; ONE roll where the whole
head rotates, the two being the same then),

    y = x * cos + pair(x) * sin

is ``[x1 c - x2 s, x2 c + x1 s, x3]`` lane for lane, in float32 with one
rounding to ``x``'s dtype.  No lane of a head is sliced off or put back:
XLA made of the split-and-concatenate form one array a HALF, 64 or 32
lanes in tiles of 128, a float32 copy of ``x`` ahead of them and a third
pass to join them (PERF.md section 6, PR 45).  The transpose of the
rotation is the rotation by the negative angle: the same pass with
``sin`` negated (``conj``).

:func:`rope_rotate` (``rope_rotate`` in a device trace) reads ``x`` once
and writes it once in the layout the flash kernels take, ``[b, h, s, d]``:
the caller's ``[b, s, h, d]`` is transposed into and out of it, which XLA
turns into the layout of the projection ahead and of the attention call
behind, not into passes.  A grid step takes a block of heads x rows; heads
are the grid's innermost axis, so a block of the tables stays where it is
while the heads go by.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# rows the kernel's inner loop takes at a time (a whole number of bf16
# tiles): the tables' rows are loaded once for all heads of the block
ROWS = 32
BLOCK_BYTES = 1 << 20


def rotate_reference(x, cos, sin, half: int, conj: bool = False):
    """The rotation in ``jnp``: ``x`` [b, s, h, d], ``cos`` / ``sin``
    [s, d] or [b, s, d] float32."""
    cos, sin = ((t if t.ndim == 3 else t[None])[:, :, None]
                for t in (cos, sin))
    xf = x.astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, xf.shape, xf.ndim - 1)
    pair = jnp.where(lane < half, jnp.roll(xf, -half, -1),
                     jnp.roll(xf, half, -1))
    return (xf * cos + pair * (-sin if conj else sin)).astype(x.dtype)


def kernel_takes(x, cos) -> bool:
    """Whether :func:`rope_rotate` takes these shapes: heads of one
    vector's lanes, rows in whole inner steps."""
    return (x.ndim == 4 and x.shape[-1] == LANES
            and x.shape[1] % ROWS == 0 and cos.shape[-1] == LANES)


def _kernel(x_ref, cos_ref, sin_ref, o_ref, *, half, conj):
    _, heads, rows, d = x_ref.shape
    low = jax.lax.broadcasted_iota(jnp.int32, (ROWS, d), 1) < half

    def step(c, carry):
        at = pl.ds(pl.multiple_of(c * ROWS, ROWS), ROWS)
        cos, sin = (t[(0,) * (t.ndim - 2) + (at, slice(None))]
                    for t in (cos_ref, sin_ref))
        if conj:
            sin = -sin
        for h in range(heads):
            x = x_ref[0, h, at, :].astype(jnp.float32)
            pair = pltpu.roll(x, d - half, 1)
            if 2 * half != d:
                pair = jnp.where(low, pair, pltpu.roll(x, half, 1))
            o_ref[0, h, at, :] = (x * cos + pair * sin).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows // ROWS, step, None)


def rope_rotate(x, cos, sin, half: int, conj: bool = False, *,
                interpret: bool = False):
    """``x`` [b, s, h, 128] rotated by the tables ``cos`` / ``sin``
    ([s, 128] or [b, s, 128] float32, see the module's docstring) with
    ``s`` a multiple of 32; ``conj`` rotates back."""
    b, s, h, d = x.shape
    if not kernel_takes(x, cos):
        raise ValueError(
            f"rope_rotate takes heads of {LANES} and rows in multiples of "
            f"{ROWS}, not x {x.shape} with tables {cos.shape}")
    heads = max(n for n in range(1, 9) if h % n == 0)
    rows = max(
        (n for n in (512, 256, 128, 64) if s % n == 0
         and heads * n * d * x.dtype.itemsize <= BLOCK_BYTES),
        default=ROWS)
    block = pl.BlockSpec((1, heads, rows, d), lambda i, j, k: (i, k, j, 0))
    if cos.ndim == 2:
        table = pl.BlockSpec((rows, d), lambda i, j, k: (j, 0))
    else:
        table = pl.BlockSpec((1, rows, d), lambda i, j, k: (i, j, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, half=half, conj=conj),
        grid=(b, s // rows, h // heads),
        in_specs=[block, table, table],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * x.size, transcendentals=0,
            bytes_accessed=2 * x.size * x.dtype.itemsize
            + 2 * cos.size * 4),
        interpret=interpret,
        name="rope_rotate",
    )(x.transpose(0, 2, 1, 3), cos, sin)
    return out.transpose(0, 2, 1, 3)
