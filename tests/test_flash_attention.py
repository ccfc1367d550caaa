"""Pallas flash-attention kernels vs the XLA reference (interpret mode on
CPU; the same kernels compile for TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.attention import _xla_attention
from dlrover_tpu.ops.pallas.flash_attention import flash_attention


def _rand_qkv(key, b, sq, skv, hq, hkv, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, sq, hq, d), dtype)
    k = jax.random.normal(kk, (b, skv, hkv, d), dtype)
    v = jax.random.normal(kv, (b, skv, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), 2, 256, 256, 2, 2, 128)
    ref = _xla_attention(q, k, v, causal=causal, segment_ids=None, scale=None)
    out = flash_attention(
        q, k, v, causal=causal, block_q=128, block_k=128, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_forward_gqa():
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), 1, 256, 256, 4, 2, 128)
    ref = _xla_attention(q, k, v, causal=True, segment_ids=None, scale=None)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_forward_segment_ids():
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), 2, 256, 256, 2, 2, 128)
    segs = jnp.concatenate(
        [jnp.zeros((2, 128), jnp.int32), jnp.ones((2, 128), jnp.int32)], axis=1
    )
    ref = _xla_attention(q, k, v, causal=True, segment_ids=segs, scale=None)
    out = flash_attention(
        q, k, v, causal=True, segment_ids=segs, block_q=128, block_k=128,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_forward_segment_ids_noncausal_fully_masked_rows():
    """Non-causal + segments: rows can be fully masked within a block."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), 1, 256, 256, 2, 2, 128)
    segs = jnp.concatenate(
        [jnp.zeros((1, 128), jnp.int32), jnp.ones((1, 128), jnp.int32)], axis=1
    )
    ref = _xla_attention(q, k, v, causal=False, segment_ids=segs, scale=None)
    out = flash_attention(
        q, k, v, causal=False, segment_ids=segs, block_q=128, block_k=128,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_reference(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), 1, 256, 256, 2, 2, 128)

    def ref_loss(q, k, v):
        o = _xla_attention(q, k, v, causal=causal, segment_ids=None, scale=None)
        return jnp.sum(o * o)

    def flash_loss(q, k, v):
        o = flash_attention(
            q, k, v, causal=causal, block_q=128, block_k=128, interpret=True
        )
        return jnp.sum(o * o)

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_out, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, err_msg=f"d{name}"
        )


def test_gradients_with_segments():
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), 1, 256, 256, 2, 2, 128)
    segs = jnp.concatenate(
        [jnp.zeros((1, 128), jnp.int32), jnp.ones((1, 128), jnp.int32)], axis=1
    )

    def ref_loss(q, k, v):
        o = _xla_attention(q, k, v, causal=True, segment_ids=segs, scale=None)
        return jnp.sum(jnp.square(o))

    def flash_loss(q, k, v):
        o = flash_attention(
            q, k, v, causal=True, segment_ids=segs, block_q=128, block_k=128,
            interpret=True,
        )
        return jnp.sum(jnp.square(o))

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_out, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, err_msg=f"d{name}"
        )


def test_gradients_gqa():
    """dk/dv accumulate over all query heads sharing a kv head."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), 1, 256, 256, 4, 2, 128)

    def ref_loss(q, k, v):
        o = _xla_attention(q, k, v, causal=True, segment_ids=None, scale=None)
        return jnp.sum(o * o)

    def flash_loss(q, k, v):
        o = flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128, interpret=True
        )
        return jnp.sum(o * o)

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_out, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, err_msg=f"d{name}"
        )


def test_bf16_forward_close():
    q, k, v = _rand_qkv(jax.random.PRNGKey(6), 1, 256, 256, 2, 2, 128, jnp.bfloat16)
    ref = _xla_attention(q, k, v, causal=True, segment_ids=None, scale=None)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2
    )


@pytest.mark.parametrize("causal", [True, False])
def test_forward_query_shorter_than_kv(causal):
    """sq < skv exercises the seq_offset path: query position i attends to
    kv positions up to i + (skv - sq) (decode-style suffix queries)."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), 2, 128, 256, 2, 2, 128)
    ref = _xla_attention(q, k, v, causal=causal, segment_ids=None, scale=None)
    out = flash_attention(
        q, k, v, causal=causal, block_q=128, block_k=128, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_gradients_query_shorter_than_kv():
    q, k, v = _rand_qkv(jax.random.PRNGKey(8), 1, 128, 256, 2, 2, 128)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=True, block_q=128, block_k=128, interpret=True
            )
            ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(
            _xla_attention(q, k, v, causal=True, segment_ids=None, scale=None)
            ** 2
        )

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_block_env_override_validation():
    """Malformed env overrides must not make the package unimportable;
    out-of-range values fail with a readable message (ADVICE r4)."""
    import warnings

    from dlrover_tpu.ops.pallas.flash_attention import _block_from_env

    assert _block_from_env("DLROVER_TEST_NOVAR", 1024) == 1024
    import os

    os.environ["DLROVER_TEST_BLK"] = "512"
    try:
        assert _block_from_env("DLROVER_TEST_BLK", 1024) == 512
        os.environ["DLROVER_TEST_BLK"] = "not-an-int"
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            assert _block_from_env("DLROVER_TEST_BLK", 1024) == 1024
        assert rec and "not an integer" in str(rec[0].message)
        os.environ["DLROVER_TEST_BLK"] = ""
        assert _block_from_env("DLROVER_TEST_BLK", 1024) == 1024
        for bad in ("-128", "100", "8192"):
            os.environ["DLROVER_TEST_BLK"] = bad
            with warnings.catch_warnings(record=True) as rec:
                warnings.simplefilter("always")
                assert _block_from_env("DLROVER_TEST_BLK", 1024) == 1024
            assert rec and "multiples of 128" in str(rec[0].message)
    finally:
        os.environ.pop("DLROVER_TEST_BLK", None)


# ---------------------------------------------------------------------------
# block classes and compute tiles (ISSUE 29)
# ---------------------------------------------------------------------------


def _reference_lse(q, k, causal, segs):
    """log-sum-exp of the masked, scaled scores, [b, h, sq]."""
    sq, skv, reps = q.shape[1], k.shape[1], q.shape[2] // k.shape[2]
    k = jnp.repeat(k, reps, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest")
    s = s * q.shape[-1] ** -0.5
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask = jnp.tril(mask, skv - sq)
    mask = mask[None, None]
    if segs is not None:
        mask = mask & (segs[:, -sq:, None] == segs[:, None, :])[:, None]
    return jax.nn.logsumexp(jnp.where(mask, s, -jnp.inf), axis=-1)


def _uneven_segments(b, seq):
    """Three segments whose ends fall inside tiles: a tile then holds rows
    with no live key in it, and the first key tile has rows it masks fully
    before they have seen any key (the guard's case)."""
    pos = jnp.arange(seq)
    segs = (pos >= 200).astype(jnp.int32) + (pos >= 500).astype(jnp.int32)
    return jnp.broadcast_to(segs, (b, seq))


# sq, skv, q heads, kv heads, causal, segments, block_q, block_k; a block of
# 256 holds 2 x 2 compute tiles of 128 (the fixture below), so a 768-long
# causal call has blocks above, on and below the diagonal
_TILED_CASES = {
    "causal_mha": (768, 768, 2, 2, True, False, 256, 256),
    "causal_gqa": (768, 768, 4, 2, True, False, 256, 256),
    "causal_segments": (768, 768, 2, 2, True, True, 256, 256),
    "q_shorter_than_kv": (256, 768, 2, 2, True, False, 256, 256),
    "non_causal": (512, 512, 2, 2, False, False, 256, 256),
    "non_causal_segments": (768, 768, 2, 2, False, True, 256, 256),
    # block_q != block_k: where the diagonal crosses a block is not known
    # when the kernel is traced, so a crossed block is masked whole
    "whole_block_fallback": (256, 768, 2, 2, True, False, 128, 256),
}


@pytest.fixture
def tiles_of_128(monkeypatch):
    from dlrover_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "_SUB_TILE", 128)
    return fa


@pytest.mark.parametrize("case", sorted(_TILED_CASES))
def test_tiled_kernels_match_reference(tiles_of_128, case):
    fa = tiles_of_128
    sq, skv, hq, hkv, causal, with_segs, bq, bk = _TILED_CASES[case]
    q, k, v = _rand_qkv(jax.random.PRNGKey(29), 1, sq, skv, hq, hkv, 128)
    segs = _uneven_segments(1, skv) if with_segs else None
    trimmed = fa._trimmed(bq, bk, causal, skv - sq)
    assert trimmed == (causal and case != "whole_block_fallback")
    computed, unmasked, needed = fa.score_tiles(sq, skv, bq, bk, causal, skv - sq)
    assert computed >= needed
    if trimmed and sq == skv:
        assert 0 < unmasked < computed < (sq // 128) * (skv // 128)

    def ref_loss(q, k, v):
        o = _xla_attention(q, k, v, causal=causal, segment_ids=segs, scale=None)
        return jnp.sum(o * o), o

    def flash_loss(q, k, v):
        o = flash_attention(
            q, k, v, causal=causal, segment_ids=segs, block_q=bq, block_k=bk,
            interpret=True,
        )
        return jnp.sum(o * o), o

    (_, o_ref), g_ref = jax.value_and_grad(
        ref_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, o_out), g_out = jax.value_and_grad(
        flash_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(o_out), np.asarray(o_ref), atol=2e-5)
    for a, b, name in zip(g_out, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, err_msg=f"d{name}"
        )

    seg3 = None if segs is None else segs[:, None, :]
    _, lse = fa._fwd(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        None if segs is None else seg3[:, :, -sq:], seg3,
        causal=causal, scale=128 ** -0.5, block_q=bq, block_k=bk,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(lse[:, :, 0]), np.asarray(_reference_lse(q, k, causal, segs)),
        atol=2e-5,
    )


def test_score_tiles_arithmetic():
    """What the kernels compute against what attention needs, in compute
    tiles: the figures PERF.md quotes for the cells' seq 4096."""
    from dlrover_tpu.ops.pallas.flash_attention import (
        DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, _sub_tile, score_tiles,
    )

    if (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K) == (1024, 1024):
        computed, unmasked, needed = score_tiles(4096, 4096, 1024, 1024, True, 0)
        per_side = 4096 // _sub_tile(1024)
        assert needed == pytest.approx(per_side * (per_side + 1 / _sub_tile(1024)) / 2)
        assert computed / needed <= 1.125  # 1.25 with whole masked blocks
        assert unmasked / computed >= 0.6  # 0 before
    for sq, skv, bq, bk in [(4096, 4096, 1024, 1024), (2048, 4096, 1024, 1024),
                            (1024, 4096, 512, 1024), (256, 256, 128, 128)]:
        computed, unmasked, needed = score_tiles(sq, skv, bq, bk, True, skv - sq)
        assert computed >= needed and unmasked <= computed
        whole = score_tiles(sq, skv, bq, bk, False, skv - sq)
        assert whole[0] == whole[1] == whole[2] > computed - 1e-9
    # queries are the tail of the keys: one block wholly below the diagonal
    # (n x n tiles), one on it (n on the diagonal, n (n - 1) / 2 below)
    n = 1024 // _sub_tile(1024)
    assert score_tiles(1024, 2048, 1024, 1024, True, 1024)[:2] == (
        n * n + n * (n + 1) / 2, n * n + n * (n - 1) / 2)


@pytest.mark.parametrize("seq,head_dim", [(64, 64), (192, 32)])
def test_shapes_off_the_lanes(seq, head_dim):
    """Blocks and heads narrower than the 128 lanes the softmax statistics
    are kept on: the statistics then take their one-column forms."""
    kq, kk = jax.random.split(jax.random.PRNGKey(31))
    q = jax.random.normal(kq, (1, seq, 2, head_dim))
    k = jax.random.normal(kk, (1, seq, 2, head_dim))
    v = 0.5 * k

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) ** 2)

    g_out = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=64, block_k=64, interpret=True,
    )), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(lambda q, k, v: _xla_attention(
        q, k, v, causal=True, segment_ids=None, scale=None,
    )), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_out, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, err_msg=f"d{name}"
        )
