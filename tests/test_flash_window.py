"""The flash kernels with a window (``i - w < j <= i``) against plain masked
attention, forward and all three gradients, in Pallas's interpreter at
tiny shapes; ``score_tiles`` with a window against a count by hand; the
paths that have no window refuse one.

Tolerance 2e-5 on float32 inputs of unit scale: both sides are float32 and
differ in the order of their sums (the kernel's online softmax, the
reference's one softmax a row).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.attention import (_xla_attention, dot_product_attention,
                                       ulysses_attention)
from dlrover_tpu.ops.pallas.flash_attention import (_crossed_offsets,
                                                    _static_tiles,
                                                    _window_steps,
                                                    flash_attention,
                                                    score_tiles)
from dlrover_tpu.ops.ring_attention import ring_attention

TOL = 2e-5


def _qkv(sq, skv, h, hkv, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (1, sq, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, skv, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, skv, hkv, d), jnp.float32)
    do = jax.random.normal(ks[3], (1, sq, h, d), jnp.float32)
    return q, k, v, do


def _value_and_grads(fn, q, k, v, do):
    return jax.value_and_grad(
        lambda q, k, v: (fn(q, k, v) * do).sum(), argnums=(0, 1, 2))(q, k, v)


def _plain(window, seg, sq, skv):
    """Plain masked attention; with ``sq < skv`` the queries are the tail
    of the key sequence."""
    def fn(q, k, v):
        pad = jnp.zeros((1, skv - sq) + q.shape[2:], q.dtype)
        out = _xla_attention(
            jnp.concatenate([pad, q], 1), k, v, causal=True,
            segment_ids=seg, scale=None, window=window)
        return out[:, skv - sq:]

    return fn


CASES = {
    # name: (sq, skv, q heads, kv heads, window, block_q, block_k, segments)
    "w_below_block": (512, 512, 2, 1, 64, 256, 256, False),
    "w_equal_block": (512, 512, 2, 1, 256, 256, 256, False),
    "w_above_block": (512, 512, 2, 1, 300, 256, 256, False),
    "tiles_inside_blocks": (1024, 1024, 1, 1, 128, 512, 512, False),
    "heads_48_over_8": (256, 256, 6, 1, 96, 128, 128, False),
    "heads_64_over_8": (256, 256, 8, 1, 96, 128, 128, False),
    "blocks_differ": (512, 512, 2, 1, 100, 128, 256, False),
    "offset": (256, 512, 2, 1, 100, 128, 128, False),
    "offset_on_the_block": (256, 512, 2, 1, 200, 256, 256, False),
    "segment_ids": (512, 512, 2, 1, 100, 256, 256, True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_window_kernels_match_plain_attention(case):
    sq, skv, h, hkv, w, bq, bk, segs = CASES[case]
    q, k, v, do = _qkv(sq, skv, h, hkv)
    seg = ((jnp.arange(skv)[None, :] >= skv // 3).astype(jnp.int32)
           if segs else None)
    got = _value_and_grads(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, segment_ids=seg, window=w, block_q=bq,
            block_k=bk, interpret=True), q, k, v, do)
    want = _value_and_grads(_plain(w, seg, sq, skv), q, k, v, do)
    assert abs(float(got[0] - want[0])) < 1e-3 * max(
        1.0, abs(float(want[0])))
    for g, wnt, name in zip(got[1], want[1], ("dq", "dk", "dv")):
        np.testing.assert_allclose(g, wnt, atol=TOL, rtol=TOL, err_msg=name)


def test_a_window_as_long_as_the_sequence_is_the_full_kernel_bit_for_bit():
    q, k, v, do = _qkv(256, 256, 2, 1)
    run = lambda w: _value_and_grads(  # noqa: E731
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=w, block_q=128, block_k=128,
            interpret=True), q, k, v, do)
    full, wide, wider = run(None), run(256), run(10 ** 6)
    for a, b in ((full, wide), (full, wider)):
        assert np.array_equal(a[0], b[0])
        for x, y in zip(a[1], b[1]):
            assert np.array_equal(x, y)
    # and a jaxpr without a window names no kernel
    text = str(jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, window=256, block_q=128, block_k=128, interpret=True))(
            q, k, v))
    assert "flash_window" not in text
    text = str(jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, window=64, block_q=128, block_k=128, interpret=True))(
            q, k, v))
    assert "flash_window_fwd" in text


def _by_hand(sq, skv, bq, bk, off, w, t):
    """Tiles of t x t that hold a visible score, and those wholly
    visible, counted score by score."""
    i = np.arange(sq)[:, None] + off
    j = np.arange(skv)[None, :]
    vis = (j <= i) & (j > i - w)
    tiles = vis.reshape(sq // t, t, skv // t, t)
    live = tiles.any(axis=(1, 3))
    return float(live.sum()), float(tiles.all(axis=(1, 3)).sum()), \
        vis.sum() / float(t * t)


@pytest.mark.parametrize("seq,block,w", [
    (8192, 1024, 512),      # the cell's window layers
    (4096, 1024, 1024), (4096, 1024, 1536), (2048, 512, 300)])
def test_score_tiles_with_a_window_against_a_count_by_hand(seq, block, w):
    computed, unmasked, needed = score_tiles(seq, seq, block, block, True,
                                             0, w)
    live, whole, need = _by_hand(seq, seq, block, block, 0, w, 256)
    assert needed == pytest.approx(need)
    # the kernels run every tile that holds a visible score and no other,
    # and mask a tile only where a boundary passes through it
    assert (computed, unmasked) == (live, whole)
    assert computed >= needed >= unmasked


def test_the_cells_window_walk():
    """Block 1024, w 512 at 8192: two k blocks a q block, 16 grid steps a
    head and not 64; a crossed block runs 6 and 3 tiles of 16."""
    assert _window_steps(1024, 1024, 8, 0, 512) == 2
    assert _crossed_offsets(1024, 512) == [0, 1024]
    on_diagonal = _static_tiles(0, 1024, 1024, "k", 512)
    before = _static_tiles(1024, 1024, 1024, "k", 512)
    area = lambda tiles: sum(  # noqa: E731
        (r1 - r0) * (c1 - c0) for (r0, r1), (c0, c1), _ in tiles) // 256 ** 2
    assert (area(on_diagonal), area(before)) == (9, 3)
    assert score_tiles(8192, 8192, 1024, 1024, True, 0, 512)[:2] == (93.0,
                                                                    31.0)
    # a window wider than the sequence is the count without one
    assert score_tiles(4096, 4096, 1024, 1024, True, 0, 4096) == \
        score_tiles(4096, 4096, 1024, 1024, True, 0)


def test_xla_fallback_and_dispatch_take_the_window():
    q, k, v, _ = _qkv(64, 64, 2, 1)
    got = dot_product_attention(q, k, v, causal=True, window=8,
                                use_pallas=False)
    i, j = np.arange(64)[:, None], np.arange(64)[None, :]
    mask = (j <= i) & (j > i - 8)
    scores = np.einsum("bqhd,bkd->bhqk", np.asarray(q),
                       np.asarray(k)[:, :, 0]) / 4.0
    scores = np.where(mask, scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkd->bqhd", p, np.asarray(v)[:, :, 0])
    np.testing.assert_allclose(got, want, atol=1e-5)
    with pytest.raises(ValueError, match="causal"):
        dot_product_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=8, interpret=True)


@pytest.mark.parametrize("path", [ring_attention, ulysses_attention])
def test_ring_and_ulysses_refuse_a_window(path):
    q, k, v, _ = _qkv(64, 64, 2, 2)
    with pytest.raises(NotImplementedError, match="no window"):
        path(q, k, v, mesh=None, causal=True, window=8)
