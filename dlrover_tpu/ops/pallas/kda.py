"""Kimi Delta Attention (KDA): a gated delta rule with a decay a CHANNEL,
as two TPU Pallas kernels beside the ``jnp`` recurrence that is their
oracle and the off-chip path of both.

A head keeps a state ``S`` in R^(d x d), float32, rows the key's channels
and columns the value's.  A token with query ``q`` (normed, scaled), key
``k`` (normed), value ``v``, log-decay ``g <= 0`` a channel and ``beta``
in (0, 1):

    S' = Diag(exp g) S                    every row decays by its channel
    u  = beta (v - S'^T k)                what the state lacks of v at k
    S  = S' + k u^T                       = (I - beta k k^T) S' + beta k v^T
    o  = S^T q

:func:`kda_decode_step` (``kda_decode_step`` in a device trace) is that,
once, for every ACTIVE slot and head: ``S`` read once from ``[slots, H, d,
d]``, decayed, corrected, written back in place, ``o`` out: 2 x 64 KiB
moved a head for ~130 k FLOPs, pure bytes.  The grid walks the active
slots only (compacted through a scalar-prefetched list): an inactive
slot's state is neither read nor written.  The three vectors that
multiply ROWS of ``S`` (``exp g``, ``k``, ``q``) arrive as rows, eight
heads a step, and are turned into columns by ONE transpose of a
``[128, 128]`` tile; everything else is elementwise work and sums down
the rows.

:func:`kda_chunk_fwd` (``kda_chunk_fwd``) takes a run of K tokens of ONE
slot from a given state to the state after its last REAL token, 64 tokens
a step on the MXU (the WY / UT transform).  With ``G_t`` the running sum
of ``g`` inside a chunk (inclusive) and ``S_0`` the state ahead of it:

    A[t, s] = sum_c beta_t k_t[c] k_s[c] exp(G_t[c] - G_s[c])     s <  t
    B[t, s] = sum_c        q_t[c] k_s[c] exp(G_t[c] - G_s[c])     s <= t
    U = (I + A)^-1 (beta V - (beta K . exp G) S_0)
    O = (Q . exp G) S_0 + B U
    S_C = Diag(exp G_C) S_0 + (K . exp(G_C - G))^T U

``exp(G_t - G_s)`` is never split into ``exp(G_t) x exp(-G_s)``: the
second overflows where a channel decays fast.  Between sub-blocks of 16
tokens both factors are taken relative to the cumulative decay just AHEAD
of the later sub-block (both exponents <= 0, and where either underflows
the product is that small too); inside a sub-block the exponent is the
difference itself, a key at a time.  ``(I + A)^-1`` is built by doubling:
the inverse of a unit lower-triangular matrix in blocks ``[[P, 0], [R,
Q]]`` is ``[[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]``, six levels from 1 to 64,
each two matmuls.  Rows behind the last real token arrive with ``g`` = 0
and ``beta`` = 0 (the wrapper's), so they change nothing; a chunk wholly
behind it is not computed.

Layout:
  state  [slots, H, d, d] float32 (decode), [H, d, d] (a run)
  q k v g  [B, H, d] (decode), [K, H, d] (a run), float32
  beta   [B, H] / [K, H] float32
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.pallas.flash_attention import _NN, _NT

#: heads a decode program holds (their vectors share one transpose)
HEADS_PER_STEP = 8
#: tokens a step of the chunk kernel, and the sub-blocks inside it
CHUNK = 64
SUB = 16
_HI = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------- oracle
def kda_step(s, q, k, v, g, beta):
    """One token of the recurrence, any leading dimensions: ``s`` [..., d,
    d], ``q k v g`` [..., d], ``beta`` [...].  Returns ``(o, s)``."""
    s = s * jnp.exp(g)[..., :, None]
    u = beta[..., None] * (v - jnp.sum(k[..., :, None] * s, axis=-2))
    s = s + k[..., :, None] * u[..., None, :]
    return jnp.sum(q[..., :, None] * s, axis=-2), s


def kda_recurrence(s0, q, k, v, g, beta, n_real=None):
    """A run of K tokens, token by token (the oracle and the off-chip path
    of :func:`kda_chunk_fwd`): ``s0`` [H, d, d], ``q k v g`` [K, H, d],
    ``beta`` [K, H]; tokens at or behind ``n_real`` change nothing.
    Returns ``(o [K, H, d], state)``."""
    klen = q.shape[0]
    live = jnp.ones(klen, bool) if n_real is None \
        else jnp.arange(klen) < n_real

    def step(s, x):
        qt, kt, vt, gt, bt, on = x
        o, new = kda_step(s, qt, kt, vt, jnp.where(on, gt, 0.0),
                          jnp.where(on, bt, 0.0))
        return new, o

    s, o = jax.lax.scan(step, s0.astype(jnp.float32),
                        (q, k, v, g, beta, live))
    return o, s


# ---------------------------------------------------------------- decode
def _decode_kernel(idx_ref, n_ref, rows_ref, s_ref, o_ref, s_out_ref, *,
                   hg: int, d: int):
    i = pl.program_id(0)
    n = n_ref[0]

    @pl.when(i < n)
    def _():
        rows = rows_ref[0, 0]                        # [5 hg, d]
        pad = -(3 * hg) % 128
        cols = jnp.concatenate(
            [rows[:3 * hg], jnp.zeros((pad, d), jnp.float32)], axis=0).T
        outs = []
        for h in range(hg):
            decay = jnp.exp(cols[:, h:h + 1])        # [d, 1]
            kc = cols[:, hg + h:hg + h + 1]
            qc = cols[:, 2 * hg + h:2 * hg + h + 1]
            v = rows[3 * hg + h:3 * hg + h + 1]      # [1, d]
            beta = rows[4 * hg + h:4 * hg + h + 1]   # [1, d], one value
            s = s_ref[0, h] * decay
            u = beta * (v - jnp.sum(kc * s, axis=0, keepdims=True))
            s = s + kc * u
            s_out_ref[0, h] = s
            outs.append(jnp.sum(qc * s, axis=0, keepdims=True))
        o_ref[0, 0] = jnp.concatenate(outs, axis=0)

    @pl.when(n == 0)
    def _():
        # nobody decodes: every step maps to one block, which has to go
        # back as it came
        s_out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def _heads_per_step(heads: int) -> int:
    hg = min(HEADS_PER_STEP, heads)
    assert heads % hg == 0, heads
    return hg


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def kda_decode_step(state, q, k, v, g, beta, active, *,
                    interpret: bool = False):
    """One token for every slot ``active`` marks: ``state`` [B, H, d, d]
    float32 (updated in place: donated and aliased), ``q k v g`` [B, H,
    d], ``beta`` [B, H], ``active`` [B] bool.  Returns ``(o [B, H, d]
    float32, state)``; an inactive slot's ``o`` is zeros and its state is
    not touched."""
    b, heads, d, dv = state.shape
    assert d == dv and q.shape == (b, heads, d), (state.shape, q.shape)
    hg = _heads_per_step(heads)
    groups = heads // hg
    f32 = jnp.float32
    rows = jnp.concatenate(
        [x.astype(f32).reshape(b, groups, hg, d) for x in (g, k, q, v)]
        + [jnp.broadcast_to(beta.astype(f32).reshape(b, groups, hg, 1),
                            (b, groups, hg, d))], axis=2)   # [B, G, 5 hg, d]
    # the active slots first, in order; behind them the grid stays on the
    # last active slot's last block, which is neither fetched nor written
    # again
    idx = jnp.argsort(~active, stable=True).astype(jnp.int32)
    n = jnp.sum(active).astype(jnp.int32).reshape(1)

    def where(i, j, idx_ref, n_ref):
        last = jnp.maximum(n_ref[0] - 1, 0)
        return (idx_ref[jnp.minimum(i, last)],
                jnp.where(i < n_ref[0], j, groups - 1), 0, 0)

    o, state = pl.pallas_call(
        functools.partial(_decode_kernel, hg=hg, d=d),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, groups),
            in_specs=[pl.BlockSpec((1, 1, 5 * hg, d), where),
                      pl.BlockSpec((1, hg, d, d), where)],
            out_specs=[pl.BlockSpec((1, 1, hg, d), where),
                       pl.BlockSpec((1, hg, d, d), where)],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, groups, hg, d), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operands: idx, n, rows, state -> outputs: o, state
        input_output_aliases={3: 1},
        interpret=interpret,
        name="kda_decode_step",
    )(idx, n, rows, state)
    o = jnp.where(active[:, None, None], o.reshape(b, heads, d), 0.0)
    return o, state


def decode_states_walked(active) -> int:
    """Slots whose state :func:`kda_decode_step`'s grid reads and writes
    for a decode forward of the slots ``active`` marks (host arithmetic
    for a caller that books what it streams)."""
    return int(np.count_nonzero(active))


# ----------------------------------------------------------------- chunk
def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, precision=_HI,
                               preferred_element_type=jnp.float32)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` of a strictly lower-triangular ``a`` [C, C], C a
    power of two, by doubling (module docstring)."""
    c = a.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    t = (row == col).astype(jnp.float32)
    size = 1
    while size < c:
        below = (row // (2 * size) == col // (2 * size)) \
            & ((row // size) % 2 == 1) & ((col // size) % 2 == 0)
        t = t - _dot(t, _dot(jnp.where(below, a, 0.0), t))
        size *= 2
    return t


def _chunk_math(s0, q, k, kb, vb, g, *, sub: int):
    """One chunk of one head: ``s0`` [d, d], ``q k kb vb g`` [C, d] (``kb``
    = beta k, ``vb`` = beta v).  Returns ``(o [C, d], state [d, d])``."""
    c, d = q.shape
    f32 = jnp.float32
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    cum = _dot((row >= col).astype(f32), g)          # G, inclusive
    lane = jax.lax.broadcasted_iota(jnp.int32, (sub, c), 1)
    at = jax.lax.broadcasted_iota(jnp.int32, (sub, c), 0)
    a_rows, b_rows = [], []
    for i in range(c // sub):
        r0 = i * sub
        mine = slice(r0, r0 + sub)
        cum_i, kb_i, q_i = cum[mine], kb[mine], q[mine]
        pan_a = jnp.zeros((sub, c), f32)
        pan_b = jnp.zeros((sub, c), f32)
        if i:
            # earlier sub-blocks' keys, both sides relative to the decay
            # just ahead of this sub-block: exponents <= 0
            ref = cum[r0 - 1:r0]
            mine_dec = jnp.exp(cum_i - ref)
            keys = k * jnp.exp(jnp.minimum(ref - cum, 0.0))
            both = _dot(jnp.concatenate([kb_i * mine_dec, q_i * mine_dec],
                                        axis=0), keys, _NT)   # [2 sub, C]
            pan_a = jnp.where(lane < r0, both[:sub], 0.0)
            pan_b = jnp.where(lane < r0, both[sub:], 0.0)
        for r in range(sub):
            key = r0 + r
            w = jnp.exp(jnp.minimum(cum_i - cum[key:key + 1], 0.0)) \
                * k[key:key + 1]
            pan_a = jnp.where(
                (lane == key) & (at > r),
                jnp.sum(kb_i * w, axis=1, keepdims=True), pan_a)
            pan_b = jnp.where(
                (lane == key) & (at >= r),
                jnp.sum(q_i * w, axis=1, keepdims=True), pan_b)
        a_rows.append(pan_a)
        b_rows.append(pan_b)
    a = jnp.concatenate(a_rows, axis=0)               # [C, C]
    b = jnp.concatenate(b_rows, axis=0)
    decay = jnp.exp(cum)                              # <= 1
    u = _dot(_unit_lower_inverse(a), vb - _dot(kb * decay, s0))
    o = _dot(q * decay, s0) + _dot(b, u)
    # K . exp(G_C - G) and G itself through ONE transpose: the key-side
    # factor of the state's update and the last row of G as a column
    last = cum[c - 1:c]
    turned = jnp.concatenate([k * jnp.exp(last - cum), cum], axis=0).T
    s = jnp.exp(turned[:, 2 * c - 1:2 * c]) * s0 + _dot(turned[:, :c], u)
    return o, s


def _chunk_kernel(n_ref, s0_ref, q_ref, k_ref, kb_ref, vb_ref, g_ref,
                  o_ref, s_out_ref, s_scr, *, chunk: int, sub: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        s_scr[...] = s0_ref[0]

    @pl.when(c * chunk < n_ref[0])
    def _():
        o, s = _chunk_math(s_scr[...], q_ref[...], k_ref[...], kb_ref[...],
                           vb_ref[...], g_ref[...], sub=sub)
        o_ref[...] = o
        s_scr[...] = s

    @pl.when(c * chunk >= n_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        s_out_ref[0] = s_scr[...]


def chunk_operands(k, v, g, beta, n_real=None):
    """What :func:`kda_chunk_call` takes in place of ``v``, ``g`` and
    ``beta``: ``(beta k, beta v, g)`` [K, H, d] float32 with ``beta`` and
    ``g`` zero at and behind ``n_real``, so that those rows change nothing.
    Elementwise work of the caller's, apart from the kernel."""
    f32 = jnp.float32
    klen = k.shape[0]
    if n_real is not None:
        live = (jnp.arange(klen) < n_real)[:, None]
        beta = jnp.where(live, beta, 0.0)
        g = jnp.where(live[..., None], g, 0.0)
    beta = beta.astype(f32)[..., None]
    return k.astype(f32) * beta, v.astype(f32) * beta, g.astype(f32)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "sub", "interpret"))
def kda_chunk_call(state, q, k, kb, vb, g, n_real, *, chunk: int = CHUNK,
                   sub: int = SUB, interpret: bool = False):
    """The kernel over :func:`chunk_operands`' rows: ``state`` [H, d, d],
    ``q k kb vb g`` [K, H, d] float32, ``n_real`` an int32 scalar (chunks
    wholly behind it are skipped)."""
    klen, heads, d = q.shape
    assert klen % chunk == 0 and chunk % sub == 0, (klen, chunk, sub)
    f32 = jnp.float32

    def flat(x):
        # [K, H, d] as [K, H x d]: a head's chunk is one [chunk, d] block
        # of it, and nothing is transposed on the way in or out
        return x.astype(f32).reshape(klen, heads * d)

    def run(h, c, n_ref):
        return (c, h)

    def whole(h, c, n_ref):
        return (h, 0, 0)

    tokens = pl.BlockSpec((chunk, d), run)
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, chunk=chunk, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(heads, klen // chunk),
            in_specs=[pl.BlockSpec((1, d, d), whole)] + [tokens] * 5,
            out_specs=[tokens, pl.BlockSpec((1, d, d), whole)],
            scratch_shapes=[pltpu.VMEM((d, d), f32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((klen, heads * d), f32),
                   jax.ShapeDtypeStruct((heads, d, d), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kda_chunk_fwd",
    )(jnp.asarray(n_real, jnp.int32).reshape(1), state.astype(f32),
      flat(q), flat(k), flat(kb), flat(vb), flat(g))
    return o.reshape(klen, heads, d), state


def kda_chunk_fwd(state, q, k, v, g, beta, n_real=None, *,
                  chunk: int = CHUNK, sub: int = SUB,
                  interpret: bool = False):
    """A run of K tokens of one slot (K a multiple of ``chunk``):
    ``state`` [H, d, d] float32, ``q k v g`` [K, H, d], ``beta`` [K, H],
    ``n_real`` an int32 scalar (None: all K).  Returns ``(o [K, H, d]
    float32, state after token n_real - 1)``; ``o`` behind ``n_real`` is
    nobody's."""
    kb, vb, g = chunk_operands(k, v, g, beta, n_real)
    return kda_chunk_call(
        state, q, k, kb, vb, g, q.shape[0] if n_real is None else n_real,
        chunk=chunk, sub=sub, interpret=interpret)


def chunk_rows(n_real, klen: int, chunk: int = CHUNK):
    """``(rows of real tokens, rows of the chunks the kernel computes)``
    for runs of ``n_real`` real tokens in programs of ``klen`` (host
    arithmetic): a chunk wholly behind the last real token is skipped,
    the one that holds it is computed whole."""
    n = np.minimum(np.asarray(n_real, np.int64), klen)
    return int(n.sum()), int((-(-n // chunk) * chunk).sum())
