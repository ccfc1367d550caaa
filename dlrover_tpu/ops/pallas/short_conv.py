"""The gates and taps of a gated short convolution (LFM2's mixer between
its two projections) as ONE pass over the projection's output each way: a
pair of TPU Pallas kernels beside the ``jnp`` form that is their oracle
and the off-chip path.

``in_proj`` writes ``B``, ``C`` and ``u`` a token; with ``v = B * u``,

    c[t] = sum_j taps[j] * v[t - (K - 1 - j)]        y = C * c

(the OLDEST position's tap first, zeros ahead of a sequence), float32
sums whatever comes in and ONE rounding of ``y``.  XLA made of the ``jnp``
form four to five passes over float32 ``[tokens, h]`` intermediates
(PERF.md section 6, PR 55 and PR 61); the least a pass can move is what
the two matmuls hand over.

The operand is ``bcu`` [b, 3, s, h]: three PLANES a sequence, which is
how the chip's compiler lays ``in_proj``'s ``[b, s, 3, h]`` out of its own
accord (``{3,1,2,0}``), so the caller's transpose is a change of name and
no pass; ``[b, s, 3 h]`` rows made it write the projection with the
sequence in lanes and copy it (PERF.md section 6, PR 61).

:func:`gated_conv_fwd` reads a tile of rows x channels of the three planes
once and writes ``y``; :func:`gated_conv_bwd` reads the gradient of ``y``
and the same tile and writes the gradients of ``B``, ``C``, ``u`` as ONE
array of three planes and the taps' gradient, recomputing ``v`` and ``c``
in registers:

    dv[t] = sum_j taps[j] * (dy * C)[t + (K - 1 - j)]
    dB = dv * u        dC = dy * c        du = dv * B
    dtaps[j] = sum_t (dy * C)[t] * v[t - (K - 1 - j)]

A grid step takes (sequence, block of channels, tile of rows); the tiles
of a sequence go by in order (that axis is ``arbitrary``, the others
``parallel``), so the ``K - 1`` rows of ``v`` ahead of a tile are what the
tile before left in a scratch of ``HALO`` float32 rows (zeros ahead of row
0) and are never read twice; the rows of ``dy * C`` BEHIND a tile, which
the backward's transposed taps reach, come as one 16-row block of the next
tile (zeros behind the last).  The taps' gradient adds up in float32
across a sequence's tiles and is written once a sequence.  Nothing is
padded, copied or held in float32 outside the registers.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# rows the kernels' inner loop takes at a time (whole bf16 tiles)
ROWS = 32
# float32 rows of a neighbouring tile a tile sees (one vector register's
# sublanes): the reach of the taps, ``K - 1``, is at most this
HALO = 8
# rows of the block of the NEXT tile the backward reads (one bf16 tile)
AHEAD = 16
# what a grid step's blocks may hold, both buffers of each
BLOCK_BYTES = 8 << 20


def causal_depthwise_conv(v: jax.Array, taps: jax.Array,
                          segment_ids: Optional[jax.Array] = None
                          ) -> jax.Array:
    """``out[t] = sum_j taps[j] * v[t - (K - 1 - j)]``: v [b, s, c], taps
    [K, c] with the OLDEST position's tap first and the current one's
    last, one weight a channel and tap; float32 sums whatever comes in.
    Zeros stand ahead of a sequence's first token and, where
    ``segment_ids`` [b, s] are given, ahead of a SEGMENT's: a packed row
    does not leak across documents.  Shifted multiply-adds, so the
    backward is a convolution again (the shifts the other way)."""
    k, s = taps.shape[0], v.shape[1]
    v, taps = v.astype(jnp.float32), taps.astype(jnp.float32)
    out = v * taps[k - 1]
    for back in range(1, min(k, s)):
        shifted = jnp.pad(v, ((0, 0), (back, 0), (0, 0)))[:, :s]
        if segment_ids is not None:
            before = jnp.pad(segment_ids, ((0, 0), (back, 0)),
                             constant_values=-1)[:, :s]
            shifted = jnp.where((before == segment_ids)[..., None],
                                shifted, 0.0)
        out = out + shifted * taps[k - 1 - back]
    return out


def gated_conv_reference(bcu, taps, segment_ids=None):
    """``C * conv(B * u)`` in ``jnp``: ``bcu`` [b, 3, s, h], ``taps`` [K,
    h]; ``y`` [b, s, h] in ``bcu``'s dtype.  The only form that takes
    ``segment_ids``."""
    b, c, u = (bcu[:, i].astype(jnp.float32) for i in range(3))
    return (c * causal_depthwise_conv(b * u, taps, segment_ids)
            ).astype(bcu.dtype)


def _blocks(s: int, h: int, units: int, itemsize: int):
    """(rows, channels) of a grid step: the widest block of channels up
    to four vectors of lanes, and the most rows that divide the sequence
    with ``units`` such blocks, twice (two buffers), inside
    ``BLOCK_BYTES``."""
    cb = max(n for n in (4 * LANES, 2 * LANES, LANES) if h % n == 0)
    return max(n for n in (1024, 512, 256, 128, 64, ROWS) if s % n == 0
               and (n == ROWS or 2 * units * n * cb * itemsize
                    <= BLOCK_BYTES)), cb


def kernel_takes(bcu, taps) -> bool:
    """Whether the kernel pair takes these shapes: three planes, channels
    in whole vectors of lanes, the sequence in whole inner steps, the
    taps' reach inside one halo."""
    k, h = taps.shape
    return (bcu.ndim == 4 and bcu.shape[1] == 3 and bcu.shape[3] == h
            and h % LANES == 0 and bcu.shape[2] % ROWS == 0
            and 1 <= k <= HALO + 1)


def _check(bcu, taps):
    if not kernel_takes(bcu, taps):
        raise ValueError(
            f"the gated convolution kernels take bcu [b, 3, s, h] with h a "
            f"multiple of {LANES}, s of {ROWS} and at most {HALO + 1} taps "
            f"[K, h], not bcu {bcu.shape} with taps {taps.shape}")


def _taps_rows(w_ref, lanes):
    return [w_ref[j:j + 1, lanes] for j in range(w_ref.shape[0])]


def _f32(ref, *at):
    return ref[at].astype(jnp.float32)


def _fwd_kernel(x_ref, w_ref, o_ref, tail_ref):
    _, rows, cb = o_ref.shape
    k = w_ref.shape[0]
    first = pl.program_id(2) == 0

    def lanes(ln):
        w = _taps_rows(w_ref, ln)

        def step(i, tail):
            at = pl.ds(pl.multiple_of(i * ROWS, ROWS), ROWS)
            v = _f32(x_ref, 0, 0, at, ln) * _f32(x_ref, 0, 2, at, ln)
            # the rows ahead of these stand over them: a roll DOWN by
            # ``back`` brings row t - back under row t
            seen = jnp.concatenate([tail, v], 0)
            c = v * w[k - 1]
            for back in range(1, k):
                c = c + pltpu.roll(seen, back, 0)[HALO:] * w[k - 1 - back]
            o_ref[0, at, ln] = (_f32(x_ref, 0, 1, at, ln) * c
                                ).astype(o_ref.dtype)
            return v[ROWS - HALO:]

        tail_ref[:, ln] = jax.lax.fori_loop(
            0, rows // ROWS, step, jnp.where(first, 0.0, tail_ref[:, ln]))

    # a LOOP over the block's vectors of lanes, not four copies of the
    # body: the step holds twelve of these kernels, and copies cost its
    # warm set-up 2 s of lowering (PERF.md section 6, PR 61)
    def block(n, carry):
        lanes(pl.ds(pl.multiple_of(n * LANES, LANES), LANES))
        return carry

    jax.lax.fori_loop(0, cb // LANES, block, 0)


def gated_conv_fwd(bcu, taps, *, interpret: bool = False):
    """``y`` [b, s, h] of ``bcu`` [b, 3, s, h] and ``taps`` [K, h]."""
    _check(bcu, taps)
    b, _, s, h = bcu.shape
    k = taps.shape[0]
    rows, cb = _blocks(s, h, 4, bcu.dtype.itemsize)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(b, h // cb, s // rows),
        in_specs=[
            pl.BlockSpec((1, 3, rows, cb), lambda i, j, t: (i, 0, t, j)),
            pl.BlockSpec((k, cb), lambda i, j, t: (0, j))],
        out_specs=pl.BlockSpec((1, rows, cb), lambda i, j, t: (i, t, j)),
        out_shape=jax.ShapeDtypeStruct((b, s, h), bcu.dtype),
        scratch_shapes=[pltpu.VMEM((HALO, cb), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=(2 * k + 1) * b * s * h, transcendentals=0,
            bytes_accessed=4 * b * s * h * bcu.dtype.itemsize),
        interpret=interpret,
        name="gated_conv_fwd",
    )(bcu, taps.astype(jnp.float32))


def _fold(x):
    """[ROWS, lanes] summed to one register's rows: the rest of the sum
    over rows waits for a sequence's end."""
    return functools.reduce(
        jnp.add, (x[r:r + HALO] for r in range(0, x.shape[0], HALO)))


def _bwd_kernel(dy_ref, dy_ahead_ref, x_ref, c_ahead_ref, w_ref,
                dx_ref, dw_ref, tail_ref, sums_ref):
    _, rows, cb = dy_ref.shape
    k = w_ref.shape[0]
    steps = rows // ROWS
    first = pl.program_id(2) == 0
    last = pl.program_id(2) == pl.num_programs(2) - 1

    def lanes(ln):
        w = _taps_rows(w_ref, ln)
        # the first rows of ``dy * C`` behind this tile: the next tile's,
        # zeros behind the sequence's end
        behind = jnp.where(
            last, 0.0,
            _f32(dy_ahead_ref, 0, slice(None), ln)
            * _f32(c_ahead_ref, 0, 0, slice(None), ln))[:HALO]

        def step(i, carry):
            tail, sums = carry
            at = pl.ds(pl.multiple_of(i * ROWS, ROWS), ROWS)
            dy = _f32(dy_ref, 0, at, ln)
            b, c, u = (_f32(x_ref, 0, n, at, ln) for n in range(3))
            v, g = b * u, dy * c
            nxt = pl.ds(pl.multiple_of(
                jnp.minimum(i + 1, steps - 1) * ROWS, ROWS), AHEAD)
            head = jnp.where(
                i == steps - 1, behind,
                (_f32(dy_ref, 0, nxt, ln) * _f32(x_ref, 0, 1, nxt, ln)
                 )[:HALO])
            seen = jnp.concatenate([tail, v], 0)
            reach = jnp.concatenate([g, head], 0)
            conv, dv = v * w[k - 1], g * w[k - 1]
            sums = list(sums)
            sums[k - 1] = sums[k - 1] + _fold(g * v)
            for back in range(1, k):
                j = k - 1 - back
                past = pltpu.roll(seen, back, 0)[HALO:]
                conv = conv + past * w[j]
                sums[j] = sums[j] + _fold(g * past)
                # a roll UP by ``back`` brings row t + back over row t
                dv = dv + pltpu.roll(
                    reach, ROWS + HALO - back, 0)[:ROWS] * w[j]
            for n, d in enumerate((dv * u, dy * conv, dv * b)):
                dx_ref[0, n, at, ln] = d.astype(dx_ref.dtype)
            return v[ROWS - HALO:], tuple(sums)

        tail, sums = jax.lax.fori_loop(0, steps, step, (
            jnp.where(first, 0.0, tail_ref[:, ln]),
            tuple(jnp.where(first, 0.0, sums_ref[j, :, ln])
                  for j in range(k))))
        tail_ref[:, ln] = tail
        for j in range(k):
            sums_ref[j, :, ln] = sums[j]

        @pl.when(last)
        def _():
            for j in range(k):
                dw_ref[0, j:j + 1, ln] = jnp.sum(sums[j], 0, keepdims=True)

    def block(n, carry):
        lanes(pl.ds(pl.multiple_of(n * LANES, LANES), LANES))
        return carry

    jax.lax.fori_loop(0, cb // LANES, block, 0)


def gated_conv_bwd(dy, bcu, taps, *, interpret: bool = False):
    """The gradients of :func:`gated_conv_fwd`'s ``y`` to ``bcu`` ([b, 3,
    s, h], its dtype) and to the taps ([K, h] float32), of ``dy`` [b, s,
    h]."""
    _check(bcu, taps)
    b, _, s, h = bcu.shape
    k = taps.shape[0]
    rows, cb = _blocks(s, h, 7, bcu.dtype.itemsize)
    per, end = rows // AHEAD, s // AHEAD - 1

    def ahead(t):
        return jnp.minimum((t + 1) * per, end)

    dx, dw = pl.pallas_call(
        _bwd_kernel,
        grid=(b, h // cb, s // rows),
        in_specs=[
            pl.BlockSpec((1, rows, cb), lambda i, j, t: (i, t, j)),
            pl.BlockSpec((1, AHEAD, cb), lambda i, j, t: (i, ahead(t), j)),
            pl.BlockSpec((1, 3, rows, cb), lambda i, j, t: (i, 0, t, j)),
            pl.BlockSpec((1, 1, AHEAD, cb),
                         lambda i, j, t: (i, 1, ahead(t), j)),
            pl.BlockSpec((k, cb), lambda i, j, t: (0, j))],
        out_specs=[
            pl.BlockSpec((1, 3, rows, cb), lambda i, j, t: (i, 0, t, j)),
            pl.BlockSpec((1, k, cb), lambda i, j, t: (i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(bcu.shape, bcu.dtype),
                   jax.ShapeDtypeStruct((b, k, h), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((HALO, cb), jnp.float32),
                        pltpu.VMEM((k, HALO, cb), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=(6 * k + 5) * b * s * h, transcendentals=0,
            bytes_accessed=7 * b * s * h * bcu.dtype.itemsize),
        interpret=interpret,
        name="gated_conv_bwd",
    )(dy, dy, bcu, bcu, taps.astype(jnp.float32))
    return dx, dw.sum(0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def gated_short_conv(bcu, taps, interpret: bool = False):
    """``C * conv(B * u)`` [b, s, h] of ``bcu`` [b, 3, s, h] and ``taps``
    [K, h] through the kernel pair (see :func:`kernel_takes`).  The
    residuals are the inputs: under full rematerialisation the forward
    runs twice and the backward once, each one pass."""
    return gated_conv_fwd(bcu, taps, interpret=interpret)


def _vjp_fwd(bcu, taps, interpret):
    return gated_conv_fwd(bcu, taps, interpret=interpret), (bcu, taps)


def _vjp_bwd(interpret, res, dy):
    bcu, taps = res
    dx, dw = gated_conv_bwd(dy, bcu, taps, interpret=interpret)
    return dx, dw.astype(taps.dtype)


gated_short_conv.defvjp(_vjp_fwd, _vjp_bwd)
