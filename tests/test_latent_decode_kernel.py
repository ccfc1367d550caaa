"""``ops/pallas/mla_decode.py``: the paged latent decode kernel in interpret
mode against its ``jnp`` oracle (``gather_latent_decode``, the CPU path)
and both against a dense softmax over each slot's live rows."""

import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.pallas import mla_decode

BS, W, C, HEADS = 8, 128, 16, 4
SCALE = 0.3


def _case(lengths, mb, shared=(), seed=0, nb=40, poison=None):
    """Slots of these ``lengths`` over a pool of ``nb`` pages of ``BS``
    rows, a table ``mb`` pages wide; ``shared``: pairs of slots whose
    tables name the same leading pages."""
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
            for p in range(n):
                live[table[s, p // BS], p % BS] = True
        pool[~live] = poison
    qq = rng.randn(b, HEADS, W).astype(np.float32)
    qq[..., C + 8:] = 0.0
    return (jnp.asarray(qq), jnp.asarray(pool), jnp.asarray(table),
            jnp.asarray(lengths, jnp.int32))


def _dense(qq, pool, table, lengths):
    out = np.zeros((qq.shape[0], HEADS, C), np.float32)
    rows = np.asarray(pool)[np.asarray(table)].reshape(
        qq.shape[0], -1, W)
    for s, n in enumerate(np.asarray(lengths)):
        if not n:
            continue
        keys = rows[s, :n]
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


@pytest.mark.parametrize("pages", [2, 8])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_is_its_oracle_is_the_dense_softmax(case, pages):
    lengths, mb, shared = CASES[case]
    args = _case(lengths, mb, shared, poison=1e4)
    kw = dict(c=C, scale=SCALE, pages_per_block=pages)
    got = mla_decode.mla_decode_attention(*args, interpret=True, **kw)
    oracle = mla_decode.gather_latent_decode(*args, **kw)
    want = _dense(*args)
    assert got.shape == (len(lengths), HEADS, C) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, oracle, atol=1e-5)
    np.testing.assert_allclose(got, want, atol=1e-5)
    for s, n in enumerate(lengths):
        if not n:                   # reads nothing, gives zeros
            assert not np.asarray(got[s]).any()


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
