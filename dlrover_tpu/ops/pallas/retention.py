"""Power retention of degree 2 (attention whose weight is the SQUARE of
``q . k`` under a scalar gate, kept as a recurrence over the symmetric
square of the key), as two TPU Pallas kernels beside the ``jnp``
recurrence that is their oracle and the off-chip path of both.

As attention (the definition): for ``s <= t``, ``a[t, s] = (q_t . k_s)^2
exp(sum_{r = s+1..t} log g_r)`` and ``y_t = sum_s a[t, s] v_s / (sum_s a[t,
s] + eps)``.  As a recurrence (what is served): with ``phi(u)`` the
symmetric square of ``u`` in R^d, one entry an UNORDERED pair (``u_i^2``,
and ``sqrt 2 u_i u_j`` for ``i != j``), so that ``phi(q) . phi(k) = (q .
k)^2`` exactly, a KEY head keeps, float32,

    S = g S + phi(k) v^T          z = g z + phi(k)
    y = S^T phi(q) / (z . phi(q) + eps)

and the ``group`` query heads that share the key head read the one state.

The kept layout (:func:`phi`, :func:`kept_tiles`): ``d / 2 + 1`` tiles of
``d`` lanes; lane ``c`` of tile ``s`` is the pair ``{c, c - s mod d}``, ``u_c
u_(c-s)``: a lane ROTATION of ``u`` times ``u``, no gather.  Tile 0 holds
the squares, tiles ``1 .. d/2 - 1`` each ``d`` distinct pairs, and tile ``d
/ 2`` its pairs twice over, so its upper half is zeros: every unordered
pair once, ``d / 2`` rows of padding (8 320 rows for the 8 256 pairs of a
head of 128).  The state is kept TRANSPOSED, ``[tiles, d (v), d (pair)]``
a key head, so that ``phi`` of the queries and of the key arrive as ROWS
(the layout the projections give them) and broadcast down the sublanes,
and ``z`` ``[tiles, d]`` beside it.

:func:`retention_decode_step` (``retention_decode_step`` in a device
trace) is the recurrence, once, for every ACTIVE slot and key head: ``S``
and ``z`` read once, decayed, updated, written back in place, the
``group`` heads' numerators and denominators out.  ``phi`` is formed in
VMEM (one vreg a tile for the key head's q's, k, gate and v together) and
never leaves it.  The grid walks the active slots only (compacted through
a scalar-prefetched list, ``ops/pallas/kda.py``'s structure), a key head
and ``V_ROWS`` of the value's channels a program.  Pure bytes: 2 x 4.3 MB
moved a key head for ~28 M FLOPs on the VPU.

:func:`retention_chunk_fwd` (``retention_chunk_fwd``) takes a run of K
tokens of ONE slot from a given state to the state after its last REAL
token, ``CHUNK`` tokens a step on the MXU.  With ``G_t`` the running sum
of ``log g`` inside a chunk (inclusive), ``S_0`` / ``z_0`` ahead of it:

    L[t, s] = exp(G_t - G_s)  for s <= t, else 0
    A = (Q K^T)^2 o L
    num = A V + exp(G) o (phi(Q) S_0)      den = A 1 + exp(G) o (phi(Q) z_0)
    S_C = exp(G_C) S_0 + (V o exp(G_C - G))^T phi(K)     z_C alike

``phi(Q)`` and ``phi(K)`` are formed a tile at a time in VMEM (a rotation
and a product) and meet the state's tile there.  The two LARGE products
(``phi(Q) S_0``: 2 x group x d x rows FLOPs a token; the state's update:
2 x d x rows) run with bfloat16 OPERANDS and float32 sums by default, the
state itself, the decay, ``z`` and the division float32 throughout
(``operands="float32"``: ``Precision.HIGHEST``, six passes).  Rows behind
the last real token arrive with ``k`` = 0 and ``log g`` = 0 (the
wrapper's), so they change nothing; a chunk wholly behind it is not
computed.

Layout:
  state  [slots, Hk, tiles, d, d] float32 (decode), [Hk, tiles, d, d] (a run)
  z      [slots, Hk, tiles, d] / [Hk, tiles, d] float32
  q      [B, Hq, d] (decode), [K, Hq, d] (a run); head i reads key head
         i // group
  k v    [B, Hk, d] / [K, Hk, d]
  lg     [B, Hk] / [K, Hk] float32, the gate's logarithm (<= 0)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.pallas.flash_attention import _NN, _NT

#: added to the denominator
EPS = 1e-6
#: channels of the value a decode program holds of a key head's state (16 /
#: 32 / 64 / 128 read 70.9 / 77.2 / 77.4 / 77.2 % of the HBM peak with 24
#: slots active, 62.7 / 71.1 / 73.4 / 74.3 % with 12: my chip runs, PR 62)
V_ROWS = 64
#: tokens a step of the chunk kernel
CHUNK = 128
_TN = (((0,), (0,)), ((), ()))  # A^T @ B
_HI = jax.lax.Precision.HIGHEST
_ROOT2 = float(np.sqrt(2.0))
#: a pair's ``sqrt 2`` in the kernels, half from either factor
_FOURTH_ROOT2 = float(2.0 ** 0.25)


# ---------------------------------------------------------------- layout
def kept_tiles(d: int) -> int:
    """Tiles of ``d`` lanes the symmetric square of a head of ``d`` is
    kept in: ``d / 2 + 1`` (65 at 128)."""
    assert d % 2 == 0, d
    return d // 2 + 1


def kept_rows(d: int) -> int:
    """Rows of the kept layout, ``(d / 2 + 1) d``: the ``d (d + 1) / 2``
    unordered pairs and ``d / 2`` zeros."""
    return kept_tiles(d) * d


def pair_weights(d: int) -> np.ndarray:
    """``[tiles, d]``: what multiplies ``u_c u_(c - s)`` at lane ``c`` of
    tile ``s``: 1 on the squares, ``sqrt 2`` on a pair, 0 where tile ``d /
    2`` would hold a pair a second time."""
    w = np.full((kept_tiles(d), d), _ROOT2, np.float32)
    w[0] = 1.0
    w[-1, d // 2:] = 0.0
    return w


def phi(u: jax.Array) -> jax.Array:
    """The symmetric square of ``u`` [..., d] in the kept layout [...,
    tiles, d], float32: ``phi(a) . phi(b) = (a . b)^2``."""
    d = u.shape[-1]
    u = u.astype(jnp.float32)
    other = (np.arange(d)[None, :] - np.arange(kept_tiles(d))[:, None]) % d
    return u[..., None, :] * jnp.take(u, other, axis=-1) * pair_weights(d)


# ---------------------------------------------------------------- oracle
def retention_step(s, z, q, k, v, lg):
    """One token of the recurrence, any leading dimensions: ``s`` [..., Hk,
    tiles, d, d], ``z`` [..., Hk, tiles, d], ``q`` [..., Hq, d], ``k v``
    [..., Hk, d], ``lg`` [..., Hk].  Returns ``(y [..., Hq, d], s, z)``."""
    hk, d = k.shape[-2:]
    group = q.shape[-2] // hk
    g = jnp.exp(lg.astype(jnp.float32))
    pk = phi(k)                                      # [..., Hk, tiles, d]
    s = s * g[..., None, None, None] \
        + v.astype(jnp.float32)[..., None, :, None] * pk[..., :, None, :]
    z = z * g[..., None, None] + pk
    pq = phi(q.reshape(*q.shape[:-2], hk, group, d))  # [..., Hk, G, tiles, d]
    num = jnp.einsum("...hsvc,...hgsc->...hgv", s, pq, precision=_HI)
    den = jnp.einsum("...hsc,...hgsc->...hg", z, pq, precision=_HI) + EPS
    return (num / den[..., None]).reshape(q.shape), s, z


def retention_recurrence(s0, z0, q, k, v, lg, n_real=None):
    """A run of K tokens, token by token (the oracle and the off-chip path
    of :func:`retention_chunk_fwd`): ``s0`` [Hk, tiles, d, d], ``z0`` [Hk,
    tiles, d], ``q`` [K, Hq, d], ``k v`` [K, Hk, d], ``lg`` [K, Hk]; tokens
    at or behind ``n_real`` change nothing.  Returns ``(y [K, Hq, d],
    state, z)``."""
    klen = q.shape[0]
    live = jnp.ones(klen, bool) if n_real is None \
        else jnp.arange(klen) < n_real

    def step(carry, t):
        qt, kt, vt, lgt, on = t
        y, s, z = retention_step(*carry, qt, jnp.where(on, kt, 0.0), vt,
                                 jnp.where(on, lgt, 0.0))
        return (s, z), y

    f32 = jnp.float32
    (s, z), y = jax.lax.scan(
        step, (s0.astype(f32), z0.astype(f32)),
        (q.astype(f32), k.astype(f32), v.astype(f32), lg.astype(f32), live))
    return y, s, z


# ---------------------------------------------------------------- decode
def _phi_tile(u, root, s: int, d: int):
    """Tile ``s`` of the symmetric square of the rows of ``u`` [R, d];
    ``root`` is ``u`` times the fourth root of 2 (a pair's ``sqrt 2``,
    half from either side)."""
    if s == 0:
        return u * u
    t = root * pltpu.roll(root, s, 1)
    if s == d // 2:
        lane = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
        t = jnp.where(lane < d // 2, t, 0.0)
    return t


def _decode_kernel(idx_ref, n_ref, u_ref, s_ref, z_ref, num_ref, den_ref,
                   s_out_ref, z_out_ref, phi_scr, vcol_scr, *, d: int,
                   tiles: int, vb: int, group: int):
    i, j = pl.program_id(0), pl.program_id(2)
    n = n_ref[0]
    rows = u_ref.shape[2]

    @pl.when(i < n)
    def _():
        # rows of ``u``: the key head's ``group`` queries, its key, the
        # gate (over the lanes) and the value
        u = u_ref[0, 0]
        gate = u[group + 1:group + 2]                 # [1, d]

        @pl.when(j == 0)
        def _():
            root = u * _FOURTH_ROOT2
            for s in range(tiles):
                phi_scr[s] = _phi_tile(u, root, s, d)
            # the value as a column over the lanes, by ONE transpose
            turned = jnp.concatenate(
                [u, jnp.zeros((d - rows, d), jnp.float32)], axis=0).T
            vcol_scr[...] = jnp.broadcast_to(
                turned[:, group + 2:group + 3], (d, d))

            def z_tile(s, acc):
                p = phi_scr[s]
                zt = z_ref[0, 0, pl.ds(s, 1), :] * gate \
                    + p[group:group + 1]
                z_out_ref[0, 0, pl.ds(s, 1), :] = zt
                return acc + p * zt

            acc = jax.lax.fori_loop(
                0, tiles, z_tile, jnp.zeros((rows, d), jnp.float32))
            den_ref[0, 0] = jnp.broadcast_to(
                jnp.sum(acc, axis=1, keepdims=True), (rows, d))

        vcol = vcol_scr[pl.ds(pl.multiple_of(j * vb, vb), vb), :]

        def s_tile(s, accs):
            p = phi_scr[s]
            new = s_ref[0, 0, s] * gate + vcol * p[group:group + 1]
            s_out_ref[0, 0, s] = new
            return tuple(a + new * p[m:m + 1] for m, a in enumerate(accs))

        accs = jax.lax.fori_loop(
            0, tiles, s_tile,
            tuple(jnp.zeros((vb, d), jnp.float32) for _ in range(group)))
        # head ``m``'s sums over the pairs on lane ``m``
        lane = jax.lax.broadcasted_iota(jnp.int32, (vb, d), 1)
        out = jnp.zeros((vb, d), jnp.float32)
        for m, a in enumerate(accs):
            out = jnp.where(lane == m, jnp.sum(a, axis=1, keepdims=True),
                            out)
        num_ref[0, 0] = out

    @pl.when(n == 0)
    def _():
        # nobody decodes: every step maps to one block, which has to go
        # back as it came
        s_out_ref[...] = s_ref[...]
        z_out_ref[...] = z_ref[...]
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)


def _rows_of(group: int) -> int:
    """Rows of a key head's operand tile: its queries, key, gate and value
    in whole sublane tiles (8 for the published 5 queries a key head)."""
    return -(-(group + 3) // 8) * 8


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0, 1))
def retention_decode_step(state, z, q, k, v, lg, active, *,
                          interpret: bool = False):
    """One token for every slot ``active`` marks: ``state`` [B, Hk, tiles,
    d, d] and ``z`` [B, Hk, tiles, d] float32 (updated in place: donated
    and aliased), ``q`` [B, Hq, d], ``k v`` [B, Hk, d], ``lg`` [B, Hk],
    ``active`` [B] bool.  Returns ``(y [B, Hq, d] float32, state, z)``; an
    inactive slot's ``y`` is zeros and its state is not touched."""
    b, hk, tiles, d, _ = state.shape
    heads = q.shape[1]
    group = heads // hk
    rows = _rows_of(group)
    assert tiles == kept_tiles(d) and heads == hk * group and rows <= d, (
        state.shape, q.shape)
    vb = V_ROWS if d % V_ROWS == 0 else d
    nvb = d // vb
    f32 = jnp.float32
    u = jnp.concatenate(
        [q.astype(f32).reshape(b, hk, group, d), k.astype(f32)[:, :, None],
         jnp.broadcast_to(jnp.exp(lg.astype(f32))[..., None, None],
                          (b, hk, 1, d)),
         v.astype(f32)[:, :, None],
         jnp.zeros((b, hk, rows - group - 3, d), f32)], axis=2)
    # the active slots first, in order; behind them the grid stays on the
    # last active slot's last block, which is neither fetched nor written
    # again
    idx = jnp.argsort(~active, stable=True).astype(jnp.int32)
    n = jnp.sum(active).astype(jnp.int32).reshape(1)

    def at(i, h, j, idx_ref, n_ref):
        live = i < n_ref[0]
        return (idx_ref[jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0))],
                jnp.where(live, h, hk - 1), jnp.where(live, j, nvb - 1))

    def head(i, h, j, idx_ref, n_ref):
        slot, h, _ = at(i, h, j, idx_ref, n_ref)
        return slot, h, 0, 0

    def strip(i, h, j, idx_ref, n_ref):
        slot, h, j = at(i, h, j, idx_ref, n_ref)
        return slot, h, 0, j, 0

    def part(i, h, j, idx_ref, n_ref):
        slot, h, j = at(i, h, j, idx_ref, n_ref)
        return slot, h, j, 0

    num, den, state, z = pl.pallas_call(
        functools.partial(_decode_kernel, d=d, tiles=tiles, vb=vb,
                          group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hk, nvb),
            in_specs=[pl.BlockSpec((1, 1, rows, d), head),
                      pl.BlockSpec((1, 1, tiles, vb, d), strip),
                      pl.BlockSpec((1, 1, tiles, d), head)],
            out_specs=[pl.BlockSpec((1, 1, vb, d), part),
                       pl.BlockSpec((1, 1, rows, d), head),
                       pl.BlockSpec((1, 1, tiles, vb, d), strip),
                       pl.BlockSpec((1, 1, tiles, d), head)],
            scratch_shapes=[pltpu.VMEM((tiles, rows, d), f32),
                            pltpu.VMEM((d, d), f32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, hk, d, d), f32),
                   jax.ShapeDtypeStruct((b, hk, rows, d), f32),
                   jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct(z.shape, f32)],
        # operands: idx, n, u, state, z -> outputs: num, den, state, z
        input_output_aliases={3: 2, 4: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            # a strip of the state in and out, each double-buffered
            vmem_limit_bytes=4 * tiles * vb * d * 4 + (8 << 20)),
        interpret=interpret,
        name="retention_decode_step",
    )(idx, n, u, state, z)
    y = jnp.swapaxes(num[..., :group], -1, -2) \
        / (den[:, :, :group, :1] + EPS)
    return jnp.where(active[:, None, None], y.reshape(b, heads, d),
                     0.0), state, z


# ----------------------------------------------------------------- chunk
def _dot(a, b, dims, operands: str):
    """``a . b`` with float32 sums: the operands rounded to bfloat16 (one
    pass of the MXU) or kept (``Precision.HIGHEST``: six)."""
    if operands == "bfloat16":
        return jax.lax.dot_general(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dims,
            preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a, b, dims, precision=_HI,
                               preferred_element_type=jnp.float32)


def _chunk_kernel(n_ref, s0_ref, z0_ref, q_ref, k_ref, v_ref, cum_ref,
                  mask_ref, y_ref, s_out_ref, z_out_ref, s_scr, z_scr, *,
                  chunk: int, d: int, tiles: int, group: int,
                  operands: str):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _():
        s_scr[...] = s0_ref[0]
        z_scr[...] = z0_ref[0]

    @pl.when(ci * chunk < n_ref[0])
    def _():
        k, v, cum = k_ref[...], v_ref[...], cum_ref[...]      # [Q, d]
        # the key head's queries one under another: [group x Q, d]
        stack = (lambda x: x) if group == 1 else (
            lambda x: jnp.concatenate([x] * group, axis=0))
        qs = q_ref[...] if group == 1 else jnp.concatenate(
            [q_ref[:, m * d:(m + 1) * d] for m in range(group)], axis=0)
        # inside the chunk: the quadratic form under the decay mask (small
        # products, float32 whatever ``operands``)
        a = _dot(qs, k, _NT, "float32")
        a = a * a * stack(mask_ref[0])
        num = _dot(a, v, _NN, "float32")
        den = jnp.sum(a, axis=1, keepdims=True)
        # across chunks: the state ahead of this one, a tile at a time
        last = cum[chunk - 1:chunk]                            # [1, d]
        left = jnp.exp(last - cum)          # a token's decay to the end
        vw, end = v * left, jnp.exp(last)
        q_root, k_root = qs * _FOURTH_ROOT2, k * _FOURTH_ROOT2
        inter = jnp.zeros(qs.shape, jnp.float32)
        below = jnp.zeros(qs.shape, jnp.float32)
        for s in range(tiles):
            pq = _phi_tile(qs, q_root, s, d)
            pk = _phi_tile(k, k_root, s, d)
            st, zt = s_scr[s], z_scr[s:s + 1]
            inter = inter + _dot(pq, st, _NT, operands)
            below = below + pq * zt
            s_scr[s] = st * end + _dot(vw, pk, _TN, operands)
            z_scr[s:s + 1] = zt * end + jnp.sum(pk * left, axis=0,
                                                keepdims=True)
        decay = stack(jnp.exp(cum))
        num = num + decay * inter
        den = den + jnp.sum(decay * below, axis=1, keepdims=True) + EPS
        y = num / den
        for m in range(group):
            y_ref[:, m * d:(m + 1) * d] = y[m * chunk:(m + 1) * chunk]

    @pl.when(ci * chunk >= n_ref[0])
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(ci == pl.num_programs(1) - 1)
    def _():
        s_out_ref[0] = s_scr[...]
        z_out_ref[0] = z_scr[...]


def chunk_of(klen: int) -> int:
    """Tokens a step the chunk kernel takes of a run of ``klen``: ``CHUNK``,
    a shorter run whole; 0 where the run is not whole steps of whole
    sublane tiles (the recurrence takes it)."""
    chunk = min(CHUNK, klen)
    return chunk if klen % chunk == 0 and chunk % 8 == 0 else 0


def chunk_operands(q, k, v, lg, n_real=None, *, chunk: int = CHUNK):
    """What :func:`retention_chunk_call` takes in place of ``lg``: ``q``
    [K, Hq x d], ``k`` and ``v`` [K, Hk x d]; the running sum of ``lg``
    inside each chunk of ``chunk`` tokens, inclusive, a key head's over its
    ``d`` lanes [K, Hk x d]; and the decay mask ``exp(G_t - G_s)`` (0 above
    the diagonal) a key head and chunk [Hk, K, chunk]; all float32, with
    ``k`` and ``lg`` zero at and behind ``n_real``, so that those rows
    change nothing.  Elementwise work and two small sums of the caller's,
    apart from the kernel."""
    f32 = jnp.float32
    klen, hk, d = k.shape
    k, lg = k.astype(f32), lg.astype(f32)
    if n_real is not None:
        live = (jnp.arange(klen) < n_real)[:, None]
        k, lg = jnp.where(live[..., None], k, 0.0), jnp.where(live, lg, 0.0)
    cum = jnp.cumsum(lg.reshape(klen // chunk, chunk, hk), axis=1)
    # the difference, never ``exp(G_t) x exp(-G_s)``: held at 0 above the
    # diagonal, where it is positive
    mask = jnp.where(
        jnp.tril(jnp.ones((chunk, chunk), bool))[None, :, :, None],
        jnp.exp(jnp.minimum(cum[:, :, None] - cum[:, None, :], 0.0)), 0.0)
    mask = mask.transpose(3, 0, 1, 2).reshape(hk, klen, chunk)
    cum = jnp.broadcast_to(cum.reshape(klen, hk, 1), (klen, hk, d))
    return (q.astype(f32).reshape(klen, -1), k.reshape(klen, hk * d),
            v.astype(f32).reshape(klen, hk * d), cum.reshape(klen, hk * d),
            mask)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "operands", "interpret"))
def retention_chunk_call(state, z, q, k, v, cum, mask, n_real, *,
                         chunk: int = CHUNK, operands: str = "bfloat16",
                         interpret: bool = False):
    """The kernel over :func:`chunk_operands`' rows: ``state`` [Hk, tiles,
    d, d], ``z`` [Hk, tiles, d], ``n_real`` an int32 scalar (chunks wholly
    behind it are skipped).  Returns ``(y [K, Hq, d] float32, state,
    z)``."""
    hk, tiles, d, _ = state.shape
    klen = q.shape[0]
    group = q.shape[1] // (hk * d)
    assert klen % chunk == 0 and tiles == kept_tiles(d), (
        q.shape, state.shape)
    f32 = jnp.float32

    def run(h, ci, n_ref):
        return (ci, h)

    tokens = pl.BlockSpec((chunk, d), run)
    whole = pl.BlockSpec((1, tiles, d, d), lambda h, ci, n: (h, 0, 0, 0))
    keys = pl.BlockSpec((1, tiles, d), lambda h, ci, n: (h, 0, 0))
    held = state.nbytes // hk + z.nbytes // hk
    y, state, z = pl.pallas_call(
        functools.partial(_chunk_kernel, chunk=chunk, d=d, tiles=tiles,
                          group=group, operands=operands),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(hk, klen // chunk),
            in_specs=[whole, keys, pl.BlockSpec((chunk, group * d), run),
                      tokens, tokens, tokens,
                      pl.BlockSpec((1, chunk, chunk),
                                   lambda h, ci, n: (h, ci, 0))],
            out_specs=[pl.BlockSpec((chunk, group * d), run), whole, keys],
            scratch_shapes=[pltpu.VMEM((tiles, d, d), f32),
                            pltpu.VMEM((tiles, d), f32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((klen, hk * group * d), f32),
                   jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct(z.shape, f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # a key head's state ahead, behind and in the scratch, the
            # first two double-buffered
            vmem_limit_bytes=5 * held + (24 << 20)),
        interpret=interpret,
        name="retention_chunk_fwd",
    )(jnp.asarray(n_real, jnp.int32).reshape(1), state.astype(f32),
      z.astype(f32), q, k, v, cum, mask)
    return y.reshape(klen, hk * group, d), state, z


def retention_chunk_fwd(state, z, q, k, v, lg, n_real=None, *,
                        chunk: int = CHUNK, operands: str = "bfloat16",
                        interpret: bool = False):
    """A run of K tokens of one slot (K a multiple of ``chunk``):
    ``state`` [Hk, tiles, d, d] and ``z`` [Hk, tiles, d] float32, ``q`` [K,
    Hq, d], ``k v`` [K, Hk, d], ``lg`` [K, Hk], ``n_real`` an int32 scalar
    (None: all K).  Returns ``(y [K, Hq, d] float32, state and z after
    token n_real - 1)``; ``y`` behind ``n_real`` is nobody's."""
    ops = chunk_operands(q, k, v, lg, n_real, chunk=chunk)
    return retention_chunk_call(
        state, z, *ops, q.shape[0] if n_real is None else n_real,
        chunk=chunk, operands=operands, interpret=interpret)
