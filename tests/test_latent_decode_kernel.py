"""``ops/pallas/mla_decode.py``: the paged latent decode kernel in interpret
mode against its ``jnp`` oracle (``gather_latent_decode``, the CPU path)
and both against a dense softmax over each slot's live rows, or, under a
mask (a learned selection's), over the rows the slot chose."""

import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.pallas import mla_decode

BS, W, C, HEADS = 8, 128, 16, 4
SCALE = 0.3


def _case(lengths, mb, shared=(), seed=0, nb=40, poison=None, chosen=None):
    """Slots of these ``lengths`` over a pool of ``nb`` pages of ``BS``
    rows, a table ``mb`` pages wide; ``shared``: pairs of slots whose
    tables name the same leading pages; ``chosen`` [slots, rows] bool:
    the rows a slot may attend, of those it sees (None: all of them)."""
    rng = np.random.RandomState(seed)
    b = len(lengths)
    pool = rng.randn(nb, BS, W).astype(np.float32)
    pool[..., C + 8:] = 0.0                   # the row's zero tail
    table = np.zeros((b, mb), np.int32)
    free = list(range(1, nb))
    for s, n in enumerate(lengths):
        for j in range(-(-n // BS)):
            table[s, j] = free.pop(0)
    for a, other, pages in shared:
        table[other, :pages] = table[a, :pages]
    if poison is not None:
        # every row no slot may see, loud: behind a length in its last
        # page, and every page no table names
        live = np.zeros((nb, BS), bool)
        for s, n in enumerate(lengths):
            for p in range(n):          # a row nobody CHOSE is dead too
                if chosen is None or chosen[s, p]:
                    live[table[s, p // BS], p % BS] = True
        pool[~live] = poison
    qq = rng.randn(b, HEADS, W).astype(np.float32)
    qq[..., C + 8:] = 0.0
    return (jnp.asarray(qq), jnp.asarray(pool), jnp.asarray(table),
            jnp.asarray(lengths, jnp.int32))


def _dense(qq, pool, table, lengths, chosen=None):
    out = np.zeros((qq.shape[0], HEADS, C), np.float32)
    rows = np.asarray(pool)[np.asarray(table)].reshape(
        qq.shape[0], -1, W)
    for s, n in enumerate(np.asarray(lengths)):
        keys = rows[s, :n]
        if chosen is not None:
            keys = keys[chosen[s, :n]]
        if not len(keys):
            continue
        sc = np.asarray(qq[s]) @ keys.T * SCALE
        p = np.exp(sc - sc.max(-1, keepdims=True))
        out[s] = (p / p.sum(-1, keepdims=True)) @ keys[:, :C]
    return out


CASES = {
    # lengths, table width, shared pages
    "ragged": ([37, 5, 64, 23], 10, ()),
    "a_slot_of_length_0": ([19, 0, 41], 8, ()),
    "off_a_blocks_edge": ([1, 7, 8, 9, 17], 4, ()),
    "pages_shared_by_two_slots": ([45, 52, 30], 8, ((0, 1, 4), (0, 2, 3))),
    "a_table_wider_than_any_live_length": ([12, 20], 33, ()),
    "every_slot_empty": ([0, 0], 4, ()),
}


def _some(lengths, mb, share=0.4, seed=3):
    """A mask a slot, ``share`` of the rows it sees and none behind."""
    mask = np.random.RandomState(seed).rand(len(lengths), mb * BS) < share
    return mask & (np.arange(mb * BS)[None, :] < np.asarray(lengths)[:, None])


def _span(lengths, mb, spans):
    """A mask a slot from ``spans``: a slot's (from, to) pairs of rows."""
    mask = np.zeros((len(lengths), mb * BS), bool)
    for s, pairs in enumerate(spans):
        for lo, hi in pairs:
            mask[s, lo:min(hi, lengths[s])] = True
    return mask


#: under a mask: lengths, table width, shared pages, the mask.  Groups are
#: 16 rows (``pages`` 2) or 64 (8)
MASKED = {
    "ragged": ([37, 5, 64, 23], 10, (), _some([37, 5, 64, 23], 10)),
    # the first group, a middle one, the last: each chosen by nobody in
    # some slot, at both group sizes
    "a_group_nobody_chose": (
        [150, 130, 140], 20, (),
        _span([150, 130, 140], 20, [[(64, 150)], [(0, 64), (128, 130)],
                                    [(3, 40), (70, 128)]])),
    "chosen_rows_in_the_last_partial_group_only": (
        [150, 139], 20, (), _span([150, 139], 20, [[(129, 150)], [(138, 139)]])),
    "a_slot_of_length_0": ([19, 0, 41], 8, (), _some([19, 0, 41], 8)),
    "a_slot_that_chose_nothing": (
        [19, 30, 41], 8, (),
        _some([19, 30, 41], 8) & (np.arange(3) != 1)[:, None]),
    # one document under two questions: the same pages, a mask each
    "shared_pages_under_different_masks": (
        [45, 52, 30], 8, ((0, 1, 4), (0, 2, 3)), _some([45, 52, 30], 8)),
    "a_mask_narrower_than_the_padded_table": (
        [12, 20], 33, (), _some([12, 20], 33)[:, :24]),
}


@pytest.mark.parametrize("pages", [2, 8])
@pytest.mark.parametrize(
    "case", sorted(CASES) + ["masked." + name for name in sorted(MASKED)])
def test_kernel_is_its_oracle_is_the_dense_softmax(case, pages):
    """Every row no slot may attend is poisoned: those behind a length,
    those in pages no table names and, under a mask, those nobody chose."""
    lengths, mb, shared, chosen = (
        CASES[case] + (None,) if case in CASES
        else MASKED[case[len("masked."):]])
    seen = None if chosen is None else np.pad(
        chosen, ((0, 0), (0, mb * BS - chosen.shape[1])))
    args = _case(lengths, mb, shared, nb=80, poison=1e4, chosen=seen)
    if chosen is not None:
        args += (jnp.where(jnp.asarray(chosen), 0.0, -jnp.inf),)
    kw = dict(c=C, scale=SCALE, pages_per_block=pages)
    got = mla_decode.mla_decode_attention(*args, interpret=True, **kw)
    oracle = mla_decode.gather_latent_decode(*args, **kw)
    want = _dense(*args[:4], seen)
    assert got.shape == (len(lengths), HEADS, C) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, oracle, atol=1e-5)
    np.testing.assert_allclose(got, want, atol=1e-5)
    for s, n in enumerate(lengths):
        if not n or (seen is not None and not seen[s].any()):
            assert not np.asarray(got[s]).any()     # zeros, and no NaN


def test_bf16_rows_and_queries():
    qq, pool, table, lengths = _case([37, 5, 64], 10)
    kw = dict(c=C, scale=SCALE, pages_per_block=4)
    got = mla_decode.mla_decode_attention(
        qq.astype(jnp.bfloat16), pool.astype(jnp.bfloat16), table, lengths,
        interpret=True, **kw)
    oracle = mla_decode.gather_latent_decode(
        qq.astype(jnp.bfloat16), pool.astype(jnp.bfloat16), table, lengths,
        **kw)
    np.testing.assert_allclose(got, oracle, atol=2e-2)
    want = _dense(qq.astype(jnp.bfloat16).astype(jnp.float32),
                  pool.astype(jnp.bfloat16).astype(jnp.float32), table,
                  lengths)
    np.testing.assert_allclose(got, want, atol=3e-2)


@pytest.mark.parametrize("lengths,mb,pages,want", [
    ([37, 5, 0], 10, 2, (48 + 16 + 0)),     # groups of 16 rows
    ([37, 5, 0], 10, 8, (64 + 64 + 0)),     # groups of 64 rows
    ([100], 4, 8, 32),                      # never more than the table
])
def test_streamed_rows_is_the_kernels_trip_count(lengths, mb, pages, want):
    assert mla_decode.streamed_rows(
        lengths, BS, mb, pages_per_block=pages) == want


def test_the_unmasked_call_is_the_parents_and_an_all_chosen_masks_bits():
    """With no mask the call has no such operand and no branch: its trace
    is, to the letter, the one the tree before the mask traced (what
    ``sarvam-105b-serve`` and ``kimi-linear-48b-serve`` compile is what
    they compiled), so its results are that tree's bit for bit; and a
    mask that chooses every row adds 0.0 to every score."""
    import hashlib
    import re

    import jax

    S = jax.ShapeDtypeStruct
    kw = dict(c=C, scale=SCALE, pages_per_block=2, interpret=True)
    shapes = (S((3, HEADS, W), jnp.float32), S((40, BS, W), jnp.float32),
              S((3, 10), jnp.int32), S((3,), jnp.int32))
    plain = jax.make_jaxpr(
        lambda *a: mla_decode.mla_decode_attention(*a, **kw))(*shapes)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(plain))
    assert (len(text.splitlines()),
            hashlib.sha256(text.encode()).hexdigest()) == (
        277, "29161fdee7e67fdfe103d90e6aaf15c9"
             "edc53132b2c349a5d0901f32721e381e"), f"jax {jax.__version__}"
    masked = jax.make_jaxpr(
        lambda *a: mla_decode.mla_decode_attention(*a, **kw))(
        *shapes, S((3, 80), jnp.float32))

    def operands(jaxpr):
        call, = [e for e in jaxpr.eqns[-1].params["jaxpr"].eqns
                 if e.primitive.name == "pallas_call"]
        return len(call.invars)

    assert operands(masked) == operands(plain) + 1
    args = _case([37, 5, 64], 10, poison=1e4)
    everything = jnp.zeros((3, 80), jnp.float32)
    for pages in (2, 8):
        kw["pages_per_block"] = pages
        got = mla_decode.mla_decode_attention(*args, **kw)
        assert (np.asarray(got) == np.asarray(
            mla_decode.mla_decode_attention(*args, everything, **kw))).all()


# ------------------------------------------------- a window layer's rings
def _rings(positions, window, ring, seed=0, slots=None):
    """Rings ``[slots x ring, BS, W]`` in which the row of position ``p``
    of slot ``s`` (every ``p`` up to ``positions[s]``) sits where
    ``serving/paged.py`` puts it, a later position over an earlier one;
    every other row LOUD.  Returns the pool and, a slot, its rows by
    position."""
    rng = np.random.RandomState(seed)
    slots = slots or len(positions)
    pool = np.full((slots * ring, BS, W), 64.0, np.float32)
    rows = []
    for s, last in enumerate(positions):
        mine = rng.randn(last + 1, W).astype(np.float32)
        mine[:, C + 8:] = 0.0
        for p in range(last + 1):
            pool[s * ring + (p // BS) % ring, p % BS] = mine[p]
        rows.append(mine)
    return pool, rows


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("positions, window, ring", [
    ((0, 5, 8, 16), 9, 2),          # inside the window, at it, past it
    ((40, 3, 95, 64), 17, 4),       # rings that have wrapped many times
    ((30, 31, 32, 33), 10, 4),      # a window that is no whole blocks
])
def test_a_window_decode_reads_its_window_of_the_ring(positions, window,
                                                      ring, impl):
    """``serving/latent.py _attend_window_decode``: the decode kernel
    again (by its window's name) and its oracle, over a position-ordered
    table of the ring's blocks under the window's mask, are the dense
    softmax over the last ``window`` rows; an idle slot reads nothing."""
    from dlrover_tpu.models.llama import LayerSpec, LlamaConfig
    from dlrover_tpu.serving import latent

    cfg = LlamaConfig(kv_lora_rank=C, qk_nope_head_dim=int(SCALE ** -2) - 8,
                      qk_rope_head_dim=8, v_head_dim=8, num_heads=HEADS)
    spec = LayerSpec(num_heads=HEADS, window=window)
    scale = latent._softmax_scale(cfg, spec)
    pool, rows = _rings(positions, window, ring)
    rng = np.random.RandomState(7)
    qq = rng.randn(len(positions), HEADS, W).astype(np.float32)
    qq[..., C + 8:] = 0.0
    active = np.array([True] * (len(positions) - 1) + [False])
    got = np.asarray(latent._attend_window_decode(
        jnp.asarray(qq), jnp.asarray(pool), jnp.asarray(positions),
        jnp.asarray(active), cfg, spec, ring, impl, True))
    for s, last in enumerate(positions):
        if not active[s]:
            np.testing.assert_array_equal(got[s], 0.0)
            continue
        keys = rows[s][max(0, last - window + 1):last + 1]
        sc = qq[s] @ keys.T * scale
        p = np.exp(sc - sc.max(-1, keepdims=True))
        np.testing.assert_allclose(
            got[s], (p / p.sum(-1, keepdims=True)) @ keys[:, :C],
            atol=2e-5)
