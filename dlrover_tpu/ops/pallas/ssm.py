"""A Mamba-2 state-space layer's scan (a scalar-decay recurrence with no
delta correction), as two TPU Pallas kernels beside the ``jnp`` recurrence
that is their oracle and the off-chip path of both.

A head keeps a state ``S`` in R^(P x N), float32, its channels by the
state's size (kept transposed, two heads side by side on the lanes:
:func:`packed_shape`).  A token with input ``x`` [P], step
``dt > 0`` and log-decay ``la = -exp(A_log) dt <= 0`` (ONE scalar a head
and token), and the rows ``B``, ``C`` [N] that ALL heads of a sequence
share:

    S = exp(la) S + (dt x) B^T
    y = S C

(the skip ``D x`` and everything else elementwise is the caller's:
``serving/linear.py``).

:func:`ssm_decode_step` (``ssm_decode_step`` in a device trace) is that,
once, for every ACTIVE slot and head: ``S`` read once, decayed, updated,
written back in place, ``y`` out: 2 x 32 KiB moved
a head for ~25 k FLOPs, pure bytes.  The grid walks the active slots only
(compacted through a scalar-prefetched list, ``ops/pallas/kda.py``'s
structure): an inactive slot's state is neither read nor written.  In the
kept layout ``dt x``, the decay and ``y`` are ROWS as the projection lays
them, the sum over N runs down the sublanes, and ``B`` and ``C`` become
columns by one transpose a program.  (With the state kept ``[H, P, N]``
the read-out is a sum along the lanes of every vreg and ``dt x`` a column
a head: 57-59 % of the HBM peak where this layout reads 77 %, 128 slots
alone on the chip; my chip runs, PR 50.)

:func:`ssm_chunk_fwd` (``ssm_chunk_fwd``) takes a run of K tokens of ONE
slot from a given state to the state after its last REAL token, ``CHUNK``
tokens a step on the MXU (the SSD form).  With ``G_t`` the running sum of
``la`` inside a chunk (inclusive) and ``S_0`` the state ahead of it:

    L[t, s] = exp(G_t - G_s)  for s <= t, else 0
    Y = (L o (C B^T)) (dt X) + exp(G) o (C S_0^T)
    S_Q = exp(G_Q) S_0 + ((dt X) o exp(G_Q - G))^T B

``exp(G_t - G_s)`` is a DIFFERENCE of the cumulative log-decay, never
``exp(G_t) x exp(-G_s)``: the second overflows where a head decays fast.
``C B^T`` is one product a chunk for all heads (the caller's, under the
causal mask); the cumulative sums are the caller's too, float32.  Rows
behind the last real token arrive with ``dt`` = 0 and ``la`` = 0 (the
wrapper's), so they change nothing; a chunk wholly behind it is not
computed.  Operands and sums are float32 (``Precision.HIGHEST``).

Layout:
  state  [slots, H / pack, N, pack x P] float32 (decode), [H / pack, N,
         pack x P] (a run); the oracle's [..., H, P, N]
         (:func:`pack_state`, :func:`unpack_state`)
  x      [B, H, P] (decode), [K, H, P] (a run)
  dt la  [B, H] / [K, H] float32
  b c    [B, N] / [K, N]
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.pallas.flash_attention import _NN, _NT

#: tiles of the kept state (:func:`packed_shape`) a decode program holds
#: (8, 16 and 32 read 77.3-77.5 % of the HBM peak with every slot active;
#: with half of them 70.0, 73.1 and 74.0: my chip runs, PR 50)
TILES_PER_STEP = 32
#: tokens a step of the chunk kernel, and the tiles a program of it holds
CHUNK = 128
CHUNK_TILES = 8
_LANES = 128
_TN = (((0,), (0,)), ((), ()))  # A^T @ B
_HI = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------- oracle
def ssm_step(s, x, dt, la, b, c):
    """One token of the recurrence, any leading dimensions: ``s`` [..., H,
    P, N], ``x`` [..., H, P], ``dt la`` [..., H], ``b c`` [..., N].
    Returns ``(y [..., H, P], s)``."""
    s = s * jnp.exp(la)[..., None, None] \
        + (dt[..., None] * x)[..., None] * b[..., None, None, :]
    return jnp.sum(s * c[..., None, None, :], axis=-1), s


def ssm_recurrence(s0, x, dt, la, b, c, n_real=None):
    """A run of K tokens, token by token (the oracle and the off-chip path
    of :func:`ssm_chunk_fwd`): ``s0`` [H, P, N], ``x`` [K, H, P], ``dt
    la`` [K, H], ``b c`` [K, N]; tokens at or behind ``n_real`` change
    nothing.  Returns ``(y [K, H, P], state)``."""
    klen = x.shape[0]
    live = jnp.ones(klen, bool) if n_real is None \
        else jnp.arange(klen) < n_real

    def step(s, t):
        xt, dtt, lat, bt, ct, on = t
        y, new = ssm_step(s, xt, jnp.where(on, dtt, 0.0),
                          jnp.where(on, lat, 0.0), bt, ct)
        return new, y

    f32 = jnp.float32
    s, y = jax.lax.scan(step, s0.astype(f32), (
        x.astype(f32), dt.astype(f32), la.astype(f32), b.astype(f32),
        c.astype(f32), live))
    return y, s


# ---------------------------------------------------------------- layout
def lanes_pack(heads: int, p: int) -> int:
    """Heads whose channels lie side by side on a tile's 128 lanes: the
    largest divisor of ``heads`` that fits (2 at the published 64
    channels a head)."""
    fit = max(1, _LANES // p)
    return max(d for d in range(1, fit + 1) if heads % d == 0)


def packed_shape(heads: int, p: int, n: int):
    """The shape a sequence's state is KEPT in, ``[H / pack, N, pack x
    P]``: the state's N rows down the sublanes, ``pack`` heads' channels
    side by side on the lanes (:func:`lanes_pack`), so that what
    multiplies a head's channels (``dt x``, the decay) arrives as a ROW in
    the layout the projection gives it, ``y`` leaves as one, and the sum
    over N runs down the sublanes; B and C, which multiply the rows, are
    the same for every head and are turned into columns once a program."""
    pack = lanes_pack(heads, p)
    return heads // pack, n, pack * p


def pack_state(s):
    """``[..., H, P, N]`` -> the kept layout ``[..., H / pack, N, pack x
    P]``."""
    *lead, h, p, n = s.shape
    pack = lanes_pack(h, p)
    s = s.reshape(*lead, h // pack, pack, p, n)
    return jnp.moveaxis(s, -1, -3).reshape(*lead, h // pack, n, pack * p)


def unpack_state(s, p: int):
    """The kept layout ``[..., H / pack, N, pack x P]`` -> ``[..., H, P,
    N]``, ``p`` a head's channels."""
    *lead, hp, n, lanes = s.shape
    pack = lanes // p
    s = s.reshape(*lead, hp, n, pack, p)
    return jnp.moveaxis(s, -3, -1).reshape(*lead, hp * pack, p, n)


# ---------------------------------------------------------------- decode
def _decode_kernel(idx_ref, n_ref, rows_ref, bc_ref, s_ref, y_ref,
                   s_out_ref, *, hg: int):
    i = pl.program_id(0)
    n = n_ref[0]

    @pl.when(i < n)
    def _():
        bc = bc_ref[0]                                # [2, N]
        n_state, lanes = s_ref.shape[2:]
        # B and C as columns, by ONE transpose of a [128, N] tile, then
        # over the lanes, once for all the heads of this program
        cols = jnp.concatenate(
            [bc, jnp.zeros((-2 % _LANES, n_state), jnp.float32)], axis=0).T
        b_col = jnp.broadcast_to(cols[:, 0:1], (n_state, lanes))
        c_col = jnp.broadcast_to(cols[:, 1:2], (n_state, lanes))
        outs = []
        for k in range(hg):
            r = rows_ref[0, k]                        # [2, lanes]
            s = s_ref[0, k] * r[1:2] + b_col * r[0:1]
            s_out_ref[0, k] = s
            outs.append(jnp.sum(s * c_col, axis=0, keepdims=True))
        y_ref[0] = jnp.concatenate(outs, axis=0)

    @pl.when(n == 0)
    def _():
        # nobody decodes: every step maps to one block, which has to go
        # back as it came
        s_out_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def _per_step(total: int, want: int) -> int:
    """``want`` of ``total`` a program, or all of them where ``want`` does
    not divide ``total`` into whole sublane tiles."""
    if total % want or want % 8:
        return total
    return want


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def ssm_decode_step(state, x, dt, la, b, c, active, *,
                    interpret: bool = False):
    """One token for every slot ``active`` marks: ``state`` [B, H / pack,
    N, pack x P] float32 (:func:`packed_shape`; updated in place: donated
    and aliased), ``x`` [B, H, P], ``dt la`` [B, H], ``b c`` [B, N],
    ``active`` [B] bool.  Returns ``(y [B, H, P] float32, state)``; an
    inactive slot's ``y`` is zeros and its state is not touched."""
    bsz, tiles, n_state, lanes = state.shape
    _, heads, p = x.shape
    assert heads * p == tiles * lanes, (state.shape, x.shape)
    hg = _per_step(tiles, TILES_PER_STEP)
    groups = tiles // hg
    f32 = jnp.float32
    # a tile's ``dt x`` as a row and its heads' decay, P times each, under
    # it: [B, tiles, 2, lanes], nothing transposed
    dx = (dt.astype(f32)[..., None] * x.astype(f32)).reshape(
        bsz, tiles, 1, lanes)
    decay = jnp.broadcast_to(jnp.exp(la.astype(f32))[..., None],
                             x.shape).reshape(bsz, tiles, 1, lanes)
    rows = jnp.concatenate([dx, decay], axis=2)
    bc = jnp.stack([b.astype(f32), c.astype(f32)], axis=1)   # [B, 2, N]
    # the active slots first, in order; behind them the grid stays on the
    # last active slot's last block, which is neither fetched nor written
    # again
    idx = jnp.argsort(~active, stable=True).astype(jnp.int32)
    n = jnp.sum(active).astype(jnp.int32).reshape(1)

    def slot_of(i, idx_ref, n_ref):
        return idx_ref[jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0))]

    def group_of(i, j, n_ref):
        return jnp.where(i < n_ref[0], j, groups - 1)

    def where4(i, j, idx_ref, n_ref):
        return (slot_of(i, idx_ref, n_ref), group_of(i, j, n_ref), 0, 0)

    def where3(i, j, idx_ref, n_ref):
        return (slot_of(i, idx_ref, n_ref), group_of(i, j, n_ref), 0)

    def slot(i, j, idx_ref, n_ref):
        return (slot_of(i, idx_ref, n_ref), 0, 0)

    y, state = pl.pallas_call(
        functools.partial(_decode_kernel, hg=hg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz, groups),
            in_specs=[pl.BlockSpec((1, hg, 2, lanes), where4),
                      pl.BlockSpec((1, 2, n_state), slot),
                      pl.BlockSpec((1, hg, n_state, lanes), where4)],
            out_specs=[pl.BlockSpec((1, hg, lanes), where3),
                       pl.BlockSpec((1, hg, n_state, lanes), where4)],
        ),
        out_shape=[jax.ShapeDtypeStruct((bsz, tiles, lanes), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operands: idx, n, rows, bc, state -> outputs: y, state
        input_output_aliases={4: 1},
        interpret=interpret,
        name="ssm_decode_step",
    )(idx, n, rows, bc, state)
    return jnp.where(active[:, None, None], y.reshape(bsz, heads, p),
                     0.0), state


# ----------------------------------------------------------------- chunk
def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, precision=_HI,
                               preferred_element_type=jnp.float32)


def _chunk_kernel(n_ref, s0_ref, dx_ref, cum_ref, cum_t_ref, end_ref, b_ref,
                  c_ref, g_ref, y_ref, s_out_ref, s_scr, *, chunk: int,
                  tiles: int, pack: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _():
        s_scr[...] = s0_ref[...]

    @pl.when(ci * chunk < n_ref[0])
    def _():
        b, c, g = b_ref[...], c_ref[...], g_ref[...]  # [Q, N] x 2, [Q, Q]
        cum = cum_ref[0]                              # [Q, tiles x pack]
        cum_t = cum_t_ref[...]                        # [tiles x pack, Q]
        lanes = s_scr.shape[2]
        p = lanes // pack
        lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, lanes), 1)

        def spread(cols):
            """A column a head [Q, 1] over that head's lanes: [Q, lanes]."""
            out = jnp.broadcast_to(cols[-1], (chunk, lanes))
            for m in reversed(range(pack - 1)):
                out = jnp.where(lane < (m + 1) * p, cols[m], out)
            return out

        outs = []
        for k in range(tiles):
            downs = [cum[:, k * pack + m:k * pack + m + 1]
                     for m in range(pack)]            # [Q, 1] a head
            dx = dx_ref[:, k * lanes:(k + 1) * lanes]  # [Q, lanes]
            s0 = s_scr[k]                             # [N, lanes]
            inside = []
            for m in range(pack):
                across = cum_t[k * pack + m:k * pack + m + 1, :]   # [1, Q]
                # ``g`` is zero above the diagonal, where the difference
                # is positive: held at 0 there, so nothing overflows
                # under it
                mask = g * jnp.exp(jnp.minimum(downs[m] - across, 0.0))
                inside.append(_dot(mask, dx[:, m * p:(m + 1) * p]))
            inside = inside[0] if pack == 1 \
                else jnp.concatenate(inside, axis=1)
            outs.append(inside + spread([jnp.exp(d) for d in downs])
                        * _dot(c, s0))
            left = spread([jnp.exp(d[chunk - 1:chunk] - d) for d in downs])
            s_scr[k] = end_ref[0, k:k + 1] * s0 + _dot(b, dx * left, _TN)
        y_ref[...] = outs[0] if tiles == 1 else jnp.concatenate(outs, axis=1)

    @pl.when(ci * chunk >= n_ref[0])
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(ci == pl.num_programs(1) - 1)
    def _():
        s_out_ref[...] = s_scr[...]


def chunk_operands(x, dt, la, b, c, n_real=None, *, chunk: int = CHUNK):
    """What :func:`ssm_chunk_call` takes in place of ``x``, ``dt`` and
    ``la``: ``dt x`` [K, H x P]; the running sum of ``la`` inside each
    chunk of ``chunk`` tokens, inclusive, as columns [groups, K, heads a
    program] and as rows [H, K], and a chunk's whole decay a head over its
    lanes [K / chunk, H / pack, pack x P]; ``b`` and ``c`` [K, N]; and ``C
    B^T`` a chunk under its causal mask [K, chunk]; all float32, with
    ``dt`` and ``la`` zero at and behind ``n_real``, so that those rows
    change nothing.  Elementwise work, two small sums and one small
    product of the caller's, apart from the kernel."""
    f32 = jnp.float32
    klen, h, p = x.shape
    pack = lanes_pack(h, p)
    hb = _per_step(h // pack, CHUNK_TILES) * pack
    dt, la = dt.astype(f32), la.astype(f32)
    if n_real is not None:
        live = (jnp.arange(klen) < n_real)[:, None]
        dt, la = jnp.where(live, dt, 0.0), jnp.where(live, la, 0.0)
    dx = (dt[..., None] * x.astype(f32)).reshape(klen, h * p)
    cum = jnp.cumsum(la.reshape(klen // chunk, chunk, h), axis=1)
    end = jnp.broadcast_to(
        jnp.exp(cum[:, -1])[..., None], (klen // chunk, h, p)
    ).reshape(klen // chunk, h // pack, pack * p)
    cum = cum.reshape(klen, h)
    b, c = b.astype(f32), c.astype(f32)
    g = jnp.einsum("qtn,qsn->qts", c.reshape(klen // chunk, chunk, -1),
                   b.reshape(klen // chunk, chunk, -1), precision=_HI)
    g = jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool)), g, 0.0)
    return (dx, cum.reshape(klen, h // hb, hb).transpose(1, 0, 2), cum.T,
            end, b, c, g.reshape(klen, chunk))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_chunk_call(state, dx, cum, cum_t, end, b, c, g, n_real, *,
                   chunk: int = CHUNK, interpret: bool = False):
    """The kernel over :func:`chunk_operands`' rows: ``state`` [H / pack,
    N, pack x P] (:func:`packed_shape`), ``n_real`` an int32 scalar
    (chunks wholly behind it are skipped).  Returns ``(y [K, H, P]
    float32: C S a token, state)``."""
    all_tiles, n_state, lanes = state.shape
    groups, klen, hb = cum.shape
    tiles = all_tiles // groups
    pack = hb // tiles
    assert klen % chunk == 0 and tiles * pack == hb, (cum.shape, state.shape)
    f32 = jnp.float32

    def run(h, ci, n_ref):
        return (ci, h)

    def shared(h, ci, n_ref):
        return (ci, 0)

    def whole(h, ci, n_ref):
        return (h, 0, 0)

    y, state = pl.pallas_call(
        functools.partial(_chunk_kernel, chunk=chunk, tiles=tiles,
                          pack=pack),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(groups, klen // chunk),
            in_specs=[
                pl.BlockSpec((tiles, n_state, lanes), whole),
                pl.BlockSpec((chunk, tiles * lanes), run),
                pl.BlockSpec((1, chunk, hb), lambda h, ci, n: (h, ci, 0)),
                pl.BlockSpec((hb, chunk), lambda h, ci, n: (h, ci)),
                pl.BlockSpec((1, tiles, lanes), lambda h, ci, n: (ci, h, 0)),
                pl.BlockSpec((chunk, n_state), shared),
                pl.BlockSpec((chunk, n_state), shared),
                pl.BlockSpec((chunk, chunk), shared)],
            out_specs=[
                pl.BlockSpec((chunk, tiles * lanes), run),
                pl.BlockSpec((tiles, n_state, lanes), whole)],
            scratch_shapes=[pltpu.VMEM((tiles, n_state, lanes), f32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((klen, all_tiles * lanes), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_chunk_fwd",
    )(jnp.asarray(n_real, jnp.int32).reshape(1), state.astype(f32),
      dx, cum, cum_t, end, b, c, g)
    return y.reshape(klen, all_tiles * pack, lanes // pack), state


def ssm_chunk_fwd(state, x, dt, la, b, c, n_real=None, *,
                  chunk: int = CHUNK, interpret: bool = False):
    """A run of K tokens of one slot (K a multiple of ``chunk``):
    ``state`` [H / pack, N, pack x P] float32 (:func:`packed_shape`),
    ``x`` [K, H, P], ``dt la`` [K, H], ``b c`` [K, N], ``n_real`` an int32
    scalar (None: all K).  Returns ``(y [K, H, P] float32, state after
    token n_real - 1)``; ``y`` behind ``n_real`` is nobody's."""
    ops = chunk_operands(x, dt, la, b, c, n_real, chunk=chunk)
    return ssm_chunk_call(
        state, *ops, x.shape[0] if n_real is None else n_real,
        chunk=chunk, interpret=interpret)
