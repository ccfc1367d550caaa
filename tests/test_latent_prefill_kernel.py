"""``mla_prefill_attention`` (a query run's masked, absorbed latent
attention as one Pallas kernel) in interpret mode on the CPU, tiny:
against the ``jnp`` loop of ``serving/latent.py _attend_run`` it stands
in for, case by case, and through ``verify_step`` on a chunk behind a
cache.  What the chip's compiler makes of it is
``tests/test_tpu_compile.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.pallas import mla_prefill
from dlrover_tpu.serving import latent
from dlrover_tpu.serving.params import serving_params_from_llama
from perfbench.weights_glm5 import SeededGlm5Params
from tests.test_glm5_reference import fresh_cache, tiny

BS = 8                 # rows a page

# name -> the run: its first position, its queries, the table's pages,
# the SELECTION's and the loop's pages a key block (the kernel's own
# are ``PAGES_PER_BLOCK``, 4: blocks of 32 keys), the selection's size,
# and positions whose index keys outscore all others (the selection is
# then those, wherever they lie)
_CASES = {
    # 37 rows: neither the kernel's 32 nor the loop's 64 divides them
    "ragged_depth": dict(start=21, klen=16, table=16, pages=8, topk=8),
    # the table holds 4 kernel blocks, the run sees 2: the pages behind
    # them are NaN in the pool the kernel reads
    "dead_block_nan": dict(start=40, klen=16, table=16, pages=8, topk=8,
                           poison=True),
    # no more keys than a query may choose: plain causal attention
    "no_selection": dict(start=16, klen=16, table=8, pages=4, topk=4096),
    # every query chooses positions 32 .. 39: the kernel's blocks 0, 2
    # and 3 hold nothing for either tile of queries (48 = 32 + 16)
    "block_masked_out": dict(start=64, klen=48, table=16, pages=8, topk=8,
                             boosted=range(32, 40)),
    # the run begins and ends inside a page
    "mid_page_start": dict(start=13, klen=10, table=8, pages=2, topk=8),
}


def _inputs(cfg, dtype, start, klen, table, seed=0, boosted=()):
    rng = np.random.RandomState(seed)
    width = latent.latent_row_width(cfg)
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    n_pages = table + 3
    lat = rng.randn(n_pages, BS, width).astype(np.float32)
    lat[..., cfg.kv_lora_rank + cfg.qk_rope_head_dim:] = 0.0
    keys = 0.1 * rng.randn(n_pages, BS, di).astype(np.float32)
    ids = rng.permutation(n_pages - 1)[:table].astype(np.int32) + 1
    for p in boosted:
        keys[ids[p // BS], p % BS] = 1.0
    qq = rng.randn(klen, cfg.num_heads, width).astype(np.float32)
    qq[..., cfg.kv_lora_rank + cfg.qk_rope_head_dim:] = 0.0
    q_i = np.abs(rng.randn(klen, hi, di)).astype(np.float32)
    w = np.abs(rng.randn(klen, hi)).astype(np.float32)
    return dict(
        qq=jnp.asarray(qq, dtype), q_i=jnp.asarray(q_i, dtype),
        w=jnp.asarray(w), q_pos=jnp.arange(start, start + klen),
        latent_pool=jnp.asarray(lat, dtype),
        index_pool=jnp.asarray(keys, dtype), table_row=jnp.asarray(ids))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_kernel_is_the_loop(case, dtype):
    spec = dict(_CASES[case])
    pages, poison = spec.pop("pages"), spec.pop("poison", False)
    cfg = tiny(index_topk=spec.pop("topk"), dtype=dtype)
    args = _inputs(cfg, dtype, **spec)
    want, chosen = latent._attend_run(**args, cfg=cfg, pages=pages)
    mine = mla_prefill.block_pages(spec["table"])
    if poison:
        live = (spec["start"] + spec["klen"] - 1) // (mine * BS) + 1
        assert live * mine < spec["table"]
        dead = args["table_row"][live * mine:]
        args["latent_pool"] = args["latent_pool"].at[dead].set(jnp.nan)
    got, chosen_k = latent._attend_run(
        **args, cfg=cfg, pages=pages, impl="pallas", interpret=True)
    np.testing.assert_array_equal(chosen_k, chosen)
    if "boosted" in spec:
        np.testing.assert_array_equal(
            np.flatnonzero(np.asarray(chosen).any(axis=0)),
            list(spec["boosted"]))
    else:
        assert np.asarray(chosen).any(axis=1).all()
    assert np.isfinite(np.asarray(got)).all()
    # float32: the two differ in the order of the sums alone.  bfloat16:
    # ``p`` is rounded against the running max of ITS block, and blocks
    # of 32 and of 64 keys round it at different points
    same_blocks = mine == pages
    tol = 5e-6 if same_blocks or dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


TQ = mla_prefill.QUERIES_PER_TILE
RUN = 3 * TQ           # a chunk program of three tiles of queries
_ATTEND = jax.jit(latent._attend_run, static_argnames=(
    "cfg", "pages", "impl", "interpret"))


@pytest.mark.parametrize("selection", [True, False],
                         ids=["selected", "causal_only"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("n_real", [1, TQ - 1, TQ, TQ + 1, RUN - 1, RUN])
def test_a_run_stops_at_its_last_real_query(n_real, dtype, selection):
    """A chunk program of ``RUN`` queries of which the first ``n_real``
    are a prompt's (the rest pad its last chunk): the real queries'
    selection and attended latents are, BIT FOR BIT, the whole run's; a
    padded query selects nothing and comes out exactly zero; the kernel
    is the ``jnp`` loop; and neither reads a row behind the last real
    query's key block (nor the trash block, which the table names behind
    it): those hold NaN in both pools."""
    start, table, pages = 40, 24, mla_prefill.block_pages(24)
    cfg = tiny(index_topk=8 if selection else 4096, dtype=dtype)
    args = _inputs(cfg, dtype, start, RUN, table)
    run = dict(cfg=cfg, pages=pages, impl="pallas", interpret=True)
    whole, chosen_whole = _ATTEND(**args, **run)
    again, _ = _ATTEND(**args, **run, n_real=jnp.int32(RUN))
    np.testing.assert_array_equal(again, whole)

    live = (start + n_real - 1) // (pages * BS) + 1    # key blocks walked
    assert live * pages < table
    ids = np.array(args["table_row"])
    dead = np.concatenate([[0], ids[live * pages:]])
    ids[-pages:] = 0            # the engine pads a table with the trash
    poisoned = dict(
        args, table_row=jnp.asarray(ids),
        latent_pool=args["latent_pool"].at[dead].set(jnp.nan),
        index_pool=args["index_pool"].at[dead].set(jnp.nan))
    got, chosen = _ATTEND(**poisoned, **run, n_real=jnp.int32(n_real))
    want, chosen_loop = _ATTEND(
        **poisoned, **dict(run, impl="xla"), n_real=jnp.int32(n_real))

    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_array_equal(got[:n_real], np.asarray(whole)[:n_real])
    np.testing.assert_array_equal(chosen[:n_real], chosen_whole[:n_real])
    assert np.asarray(chosen)[:n_real].any(axis=1).all()
    for out, picked in ((got, chosen), (want, chosen_loop)):
        assert not np.asarray(picked)[n_real:].any()
        assert (out[n_real:] == 0).all() and np.isfinite(out).all()
    np.testing.assert_array_equal(chosen_loop, chosen)
    # the same key blocks in both: they differ in the order of sums
    # alone, of bfloat16 products where the operands are that
    tol = 5e-6 if dtype == jnp.float32 else 2e-4
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


def test_key_blocks_are_whole_blocks_to_the_last_query():
    walked, held = mla_prefill.key_blocks(
        [1, 512, 513, 40_000], block_size=128, table_width=264)
    assert (walked, held) == (1 + 1 + 2 + 66, 4 * 66)


def test_query_tiles_are_the_tiles_with_a_real_query():
    # runs of 512 queries: 16 tiles of 32 each; 1, 32 and 33 real queries
    # fill 1, 1 and 2 of them, a whole chunk all 16
    assert mla_prefill.query_tiles([1, 32, 33, 512], 512) == (20, 64)
    # a run shorter than a tile is one tile
    assert mla_prefill.query_tiles([3, 16], 16) == (2, 2)


def test_a_chunk_through_the_kernel_is_the_chunk_through_the_loop():
    """``verify_step`` on a chunk at an offset, with a watched slot: the
    kernel's path gives the loop's logits (to rounding), the loop's
    selection bit for bit in every layer, and pools that hold the same
    rows."""
    cfg = tiny()
    sp = serving_params_from_llama(
        {"params": SeededGlm5Params(cfg, 7)}, cfg)
    seq = np.random.RandomState(3).randint(0, 128, 48).astype(np.int32)
    slot = jnp.zeros(1, jnp.int32)

    def step(impl):
        return jax.jit(lambda p, c, t, at: latent.verify_step(
            p, cfg, c, t, at, slots=slot, attention_impl=impl,
            kernel_interpret=True))

    _, behind = step("xla")(sp, fresh_cache(cfg), jnp.asarray(seq[None, :32]),
                            jnp.asarray([0], jnp.int32))
    behind = dict(behind, watch_slot=jnp.int32(0))
    out = {impl: step(impl)(sp, behind, jnp.asarray(seq[None, 32:]),
                            jnp.asarray([32], jnp.int32))
           for impl in ("xla", "pallas")}
    (want, cache_x), (got, cache_p) = out["xla"], out["pallas"]
    np.testing.assert_allclose(got, want, atol=2e-5)
    bits = np.asarray(cache_x["witness"]["chosen_bits"])
    assert bits.shape[:2] == (cfg.num_layers, 16) and bits.any()
    np.testing.assert_array_equal(cache_p["witness"]["chosen_bits"], bits)
    for name in ("latent_pool", "index_pool"):
        for a, b in zip(cache_p[name], cache_x[name]):
            np.testing.assert_allclose(a, b, atol=2e-5)
    np.testing.assert_array_equal(cache_p["moe_picks"], cache_x["moe_picks"])


# ------------------------------------------------- a window layer's rings
@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("start, klen, n_real, window, ring", [
    (0, 8, None, 9, 2),       # a prompt's first chunk
    (24, 8, None, 9, 2),      # a later one: the ring has wrapped
    (32, 16, 11, 17, 4),      # a last chunk, padded behind 11 queries
    (64, 16, None, 10, 4),    # a window that is no whole blocks
])
def test_a_window_run_reads_each_querys_window_of_the_ring(
        start, klen, n_real, window, ring, impl):
    """``serving/latent.py _attend_window_run``: the run kernel (by its
    window's name) over the ring's pages in position order, and the dense
    path, are each query's softmax over the last ``window`` rows; a padded
    query attends nothing; what the ring holds of positions a wrap has
    passed, or ahead of the run, is LOUD and never read."""
    from dlrover_tpu.models.llama import LayerSpec, LlamaConfig
    from dlrover_tpu.serving import latent
    from tests.test_latent_decode_kernel import BS, C, HEADS, W, _rings

    cfg = LlamaConfig(kv_lora_rank=C, qk_nope_head_dim=8,
                      qk_rope_head_dim=8, v_head_dim=8, num_heads=HEADS)
    spec = LayerSpec(num_heads=HEADS, window=window)
    scale = latent._softmax_scale(cfg, spec)
    real = klen if n_real is None else n_real
    slot = 2
    pool, rows = _rings((0, 0, start + real - 1), window, ring, slots=3)
    rng = np.random.RandomState(5)
    qq = rng.randn(klen, HEADS, W).astype(np.float32)
    qq[..., C + 8:] = 0.0
    got = np.asarray(latent._attend_window_run(
        jnp.asarray(qq), start + jnp.arange(klen), jnp.asarray(pool),
        jnp.asarray(slot), None if n_real is None else jnp.asarray(n_real),
        cfg, spec, ring, impl, True))
    for k in range(klen):
        if k >= real:
            np.testing.assert_array_equal(got[k], 0.0)
            continue
        t = start + k
        keys = rows[slot][max(0, t - window + 1):t + 1]
        sc = qq[k] @ keys.T * scale
        p = np.exp(sc - sc.max(-1, keepdims=True))
        np.testing.assert_allclose(
            got[k], (p / p.sum(-1, keepdims=True)) @ keys[:, :C],
            atol=2e-5)
