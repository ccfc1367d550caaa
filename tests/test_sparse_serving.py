"""A latent-attention model with a learned selection of keys and sparse
experts through the paged latent cache and the engine, tiny, float32, on
the CPU: against the plain reference, against itself in other chunkings,
with a shared prefix; the index-score kernel in interpret mode; what the
engine books and exports; and that the dense model traces what it did.

The rule of this file and of every serving test file behind it: the
config, the seeded params and the engines come from module-scoped
fixtures (``cfg``, ``params``, ``engines``).  An ``InferenceEngine`` jits
its programs as closures of the instance, so every new engine traces,
lowers and compiles them again; a test that reads OUTPUTS or DIFFERENCES
of counters takes the module's one engine of its keyword arguments from
``engines``, and builds its own only where it reads the books from their
start (or patches a function the programs trace through) and says so."""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.llama import LlamaConfig
from dlrover_tpu.ops.pallas import paged_index
from dlrover_tpu.serving import latent
from dlrover_tpu.serving import engine as engine_module
from dlrover_tpu.serving import model as serving_model
from dlrover_tpu.serving.engine import InferenceEngine
from dlrover_tpu.serving.params import serving_params_from_llama
from perfbench import controls_glm5
from perfbench.drivers import serve_sparse
from perfbench.weights import SeededParams
from perfbench.weights_glm5 import SeededGlm5Params
from tests.test_glm5_reference import (config_of, fresh_cache,
                                       reference_logits, tiny)

SLOT = jnp.zeros(1, jnp.int32)


def _served(cfg, seed=7):
    params = SeededGlm5Params(cfg, seed)
    return params, serving_params_from_llama({"params": params}, cfg)


@pytest.fixture(scope="module")
def cfg():
    return tiny()


@pytest.fixture(scope="module")
def params(cfg):
    return SeededGlm5Params(cfg, 9)


@pytest.fixture(scope="module")
def sp(cfg):
    """``cfg``'s served parameters (seed 7), for the tests of the blocks."""
    return _served(cfg)[1]


def _run(sp, cfg, cache, seq, start, **kw):
    # jitted (one program a shape and set of options): eagerly the layer
    # loop dispatches op by op
    step = jax.jit(lambda p, c, t, at: latent.verify_step(
        p, cfg, c, t, at, **kw))
    key = (cfg, len(seq), tuple(sorted(kw)))
    step = _PROGRAMS.setdefault(key, step)
    return step(sp, cache, jnp.asarray(seq[None]),
                jnp.asarray([start], jnp.int32))


_PROGRAMS = {}


@pytest.mark.parametrize("topk", [8, 4096], ids=["selected", "dense"])
def test_prefill_and_decode_through_the_cache_are_the_reference(topk):
    """Chunks of 16, then token by token: every position's logits are the
    reference's full forward, with the selection smaller than the context
    and with a context under it (plain latent attention)."""
    cfg = tiny(index_topk=topk)
    params, sp = _served(cfg)
    seq = np.random.RandomState(0).randint(0, 128, 45).astype(np.int32)
    want, picked = reference_logits(cfg, params, seq, selection_of=(0, 45))
    # ``watch_slot``: every forward hands back what it did for that slot
    cache = dict(fresh_cache(cfg), watch_slot=jnp.asarray(0, jnp.int32))
    got = []
    for s in range(0, 32, 16):
        logits, cache = _run(sp, cfg, cache, seq[s:s + 16], s, slots=SLOT)
        seen = cache.pop("witness")
        chosen = np.unpackbits(np.asarray(seen["chosen_bits"]),
                               axis=-1).astype(bool)
        assert seen["sparse_out"].shape == (16, cfg.hidden_size)
        got.append(logits[0])
        for layer in range(cfg.num_layers):
            assert (chosen[layer, :, :45]
                    == np.asarray(picked[layer][1][s:s + 16])).all()
    for p in range(32, 45):
        logits, cache = _run(sp, cfg, cache, seq[p:p + 1], p)
        seen = cache.pop("witness")
        assert seen["sparse_in"].shape == (1, cfg.hidden_size)
        for layer in range(cfg.num_layers):
            rows = np.asarray(seen["rows"][layer])
            assert sorted(rows[rows >= 0]) == np.flatnonzero(
                np.asarray(picked[layer][1][p])).tolist()
        got.append(logits[0])
    np.testing.assert_allclose(jnp.concatenate(got), want, atol=1e-4)


def test_a_chunk_at_an_offset_is_one_prefill(cfg, sp):
    seq = np.random.RandomState(3).randint(0, 128, 48).astype(np.int32)
    whole, _ = _run(sp, cfg, fresh_cache(cfg), seq, 0, slots=SLOT)
    cache, parts = fresh_cache(cfg), []
    for s in (0, 16, 32):
        logits, cache = _run(sp, cfg, cache, seq[s:s + 16], s, slots=SLOT)
        parts.append(logits[0])
    np.testing.assert_allclose(jnp.concatenate(parts), whole[0], atol=2e-5)
    # the bucketed prefill (no cache behind it) gives the same last logits
    last, rows, keys = serving_model.prefill(
        sp, cfg, jnp.asarray(seq[None]), jnp.asarray([48]))
    np.testing.assert_allclose(last[0], whole[0, -1], atol=2e-5)
    assert rows[0].shape == (1, 48, latent.latent_row_width(cfg))
    assert keys[0].shape == (1, 48, cfg.index_head_dim)


def _engine(cfg, params, **kw):
    args = dict(max_slots=2, chunk=4, temperature=0.0, max_len=96,
                prefill_buckets=(32, 48, 64, 96), paged=True, block_size=8,
                prefill_chunk=16, attention_impl="pallas")
    args.update(kw)
    return InferenceEngine(cfg, {"params": params}, **args)


@pytest.fixture(scope="module")
def engines(cfg, params):
    """``engines(**kw)``: the module's ONE engine of these keyword
    arguments over ``cfg`` and ``params``, with no work left.  Other
    tests have used it: read its outputs, and its counters as
    differences (``_since``)."""
    built = {}

    def engine(**kw):
        key = tuple(sorted(kw.items()))
        if key not in built:
            built[key] = _engine(cfg, params, **kw)
        assert not built[key].has_work
        return built[key]

    return engine


def _since(eng):
    """``done()``: the ``EngineStats`` of what ``eng`` has done since this
    call, every counter a difference, so that its ratios are those of
    the caller's work alone."""
    before = dataclasses.asdict(eng.stats)
    return lambda: type(eng.stats)(**{
        name: getattr(eng.stats, name) - was
        for name, was in before.items()})


def test_a_request_on_a_cached_document_answers_as_a_cold_one(cfg, engines):
    """The document's blocks are mapped, the tail's chunks start behind
    them, and tokens and books are those of an engine that never saw it."""
    rng = np.random.RandomState(5)
    doc = rng.randint(0, 128, 48).astype(np.int32)
    tails = [rng.randint(0, 128, n).astype(np.int32) for n in (9, 21)]
    warm = engines()
    shared_before = warm.prefix_stats()["prefix_shared_tokens"]
    done = _since(warm)
    warm.add_request(doc, 1)
    warm.run()
    chunks_before = done().prefill_chunks
    rids = [warm.add_request(np.concatenate([doc, t]), 6) for t in tails]
    hot = warm.run()
    # 48 tokens shared: one chunk for the 9-token tail, two for the 21
    assert done().prefill_chunks - chunks_before <= 3
    assert warm.prefix_stats()["prefix_shared_tokens"] - shared_before \
        == 2 * 48
    # one engine that shares nothing answers both, one behind the other
    cold = engines(prefix_sharing=False)
    for tail, rid in zip(tails, rids):
        crid = cold.add_request(np.concatenate([doc, tail]), 6)
        assert cold.run()[crid].tolist() == hot[rid].tolist()
    st = done()
    assert 0 < st.dsa_selected_ratio < 1 and st.attn_rows_selected > 0
    assert st.index_rows_scanned >= st.dsa_rows_live > st.attn_rows_selected
    assert 0 < st.moe_picks_held < st.moe_picks
    assert st.moe_picks % (cfg.moe_top_k * 2) == 0   # 2 sparse layers
    assert warm._blockmgr.check_books()


def test_the_decode_span_says_what_is_read_and_what_is_attended(
        cfg, engines, monkeypatch):
    """A selection model's decode chunk streams every live row under the
    mask: its span carries ``kv_rows_streamed`` (the kernel's whole groups
    of pages up to each length, as a model without a selection books
    them) beside the selection's own three, and the oracle path, which
    streams nothing, books none."""
    from dlrover_tpu.ops.pallas import mla_decode
    from dlrover_tpu.utils import profiler

    spans = []
    inner = profiler.span

    def span(name, **attrs):
        if name == "dlrover.engine.decode_chunk" and attrs:
            spans.append(attrs)
        return inner(name, **attrs)

    # (the host's side of the engine only: the programs trace no span)
    monkeypatch.setattr("dlrover_tpu.serving.engine.span", span)
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, 128, n).astype(np.int32) for n in (19, 37)]
    eng = engines()
    done = _since(eng)
    for prompt in prompts:
        eng.add_request(prompt, 5)
    eng.run()
    st = done()
    # one token from the prefill, then 4 forwards at lengths n + 1 .. n + 4
    lengths = np.array([[n + j for j in range(1, 5)] for n in (19, 37)])
    rows = mla_decode.PAGES_PER_BLOCK * 8
    assert st.kv_rows_live == int(lengths.sum())
    assert st.kv_rows_streamed == int((-(-lengths // rows) * rows).sum())
    assert sum(a["kv_rows_streamed"] for a in spans) == st.kv_rows_streamed
    assert sum(a["kv_rows_live"] for a in spans) == st.kv_rows_live
    assert sum(a["attn_rows_selected"] for a in spans) \
        == cfg.index_topk * lengths.size < st.kv_rows_live
    assert all(a["index_rows_scanned"] >= a["kv_rows_live"] for a in spans)
    oracle = engines(attention_impl="xla")
    done = _since(oracle)
    for prompt in prompts:
        oracle.add_request(prompt, 5)
    oracle.run()
    assert (oracle.stats.kv_rows_live, oracle.stats.kv_rows_streamed) \
        == (0, 0)
    assert done().attn_rows_selected == st.attn_rows_selected


def test_the_engine_refuses_a_latent_model_without_pools(cfg):
    with pytest.raises(ValueError, match="paged=True"):
        InferenceEngine(cfg, {"params": SeededGlm5Params(cfg, 1)},
                        max_len=96)


def test_the_books_are_inert_for_a_dense_model():
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    eng = InferenceEngine(
        cfg, {"params": SeededParams(cfg, 2)}, max_slots=2, chunk=4,
        temperature=0.0, max_len=64, paged=True, block_size=8,
        attention_impl="xla")
    assert eng._book_selection(np.zeros(2), np.ones(2)) == {}
    eng.add_request(np.arange(10, dtype=np.int32), 5)
    eng.run()
    st = eng.stats
    assert (st.dsa_rows_live, st.index_rows_scanned, st.attn_rows_selected,
            st.moe_picks, st.moe_picks_held) == (0, 0, 0, 0, 0)
    assert st.dsa_selected_ratio == 0.0 and st.moe_held_share == 0.0
    assert eng._book_key_blocks(np.zeros(2), np.ones(2)) == {}
    assert (st.prefill_key_blocks, st.prefill_key_block_share) == (0, 0.0)
    assert (st.prefill_query_tiles, st.prefill_live_tile_share) == (0, 0.0)
    assert "moe_picks" not in eng._cache and eng._prefill_group == 2
    assert "watch_slot" not in eng._cache and eng.witness_log == []
    with pytest.raises(ValueError, match="witness"):
        eng.watch(lambda req: True)


_GAUGES = {
    "serving_dsa_selected_ratio": 0.08,
    "serving_moe_held_share": 0.0625,
    "serving_moe_walks_per_layer": 1.2,
    "serving_prefill_live_tile_share": 0.7,
}


@pytest.mark.parametrize("gauge", sorted(_GAUGES))
def test_the_gauges_reach_the_scrape(gauge):
    """A fleet's ratio weighs its replicas by their work: the sums of
    what each reports, then the quotient."""
    from dlrover_tpu.serving.router.metrics import RouterMetrics
    from dlrover_tpu.utils.metric_registry import METRIC_HELP

    m = RouterMetrics()
    m.observe_engine_metrics([
        {"dsa_rows_live": 100.0, "attn_rows_selected": 8.0,
         "moe_picks": 64.0, "moe_picks_held": 4.0,
         "moe_buffer_walks": 10.0, "moe_layer_forwards": 10.0,
         "prefill_query_tiles": 16.0, "prefill_query_tiles_live": 16.0},
        {"dsa_rows_live": 300.0, "attn_rows_selected": 24.0,
         "moe_picks": 64.0, "moe_picks_held": 4.0,
         "moe_buffer_walks": 14.0, "moe_layer_forwards": 10.0,
         "prefill_query_tiles": 64.0, "prefill_query_tiles_live": 40.0},
        {}])
    assert m.metrics()[gauge] == pytest.approx(_GAUGES[gauge])
    assert RouterMetrics().metrics()[gauge] == 0.0
    assert gauge in METRIC_HELP


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_index_kernel_matches_jnp_on_ragged_lengths(dtype):
    """Interpret mode against the gather, lengths 0, inside a page, on a
    group's edge and at the table's end; pages no slot lists and the rows
    behind every length are NaN, so a read of a dead row would show."""
    rng = np.random.RandomState(0)
    b, hi, di, nb, bs, mb = 5, 4, 16, 40, 8, 7
    lengths = np.asarray([0, 3, 32, 41, 56], np.int32)
    table = np.zeros((b, mb), np.int32)
    pool = np.full((nb, bs, di), np.nan, np.float32)
    free = list(range(1, nb))
    for s, n in enumerate(lengths):
        for j in range(-(-int(n) // bs)):
            page = free.pop()
            table[s, j] = page
            live = min(bs, int(n) - j * bs)
            pool[page, :live] = rng.randn(live, di)
    q = jnp.asarray(rng.randn(b, hi, di), dtype)
    w = jnp.asarray(rng.randn(b, hi), jnp.float32)
    pool, table, lengths = (jnp.asarray(pool, dtype), jnp.asarray(table),
                            jnp.asarray(lengths))
    got = paged_index.paged_index_scores(q, w, pool, table, lengths,
                                         interpret=True)
    want = paged_index.gather_index_scores(q, w, pool, table, lengths)
    assert got.shape == want.shape == (b, paged_index.padded_rows(bs, mb))
    live = np.arange(got.shape[1])[None, :] < np.asarray(lengths)[:, None]
    assert np.isneginf(np.asarray(got)[~live]).all()
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=1e-5, atol=1e-5)
    assert paged_index.scanned_rows(np.asarray(lengths), bs, mb) \
        == (0 + 32 + 32 + 64 + 64)


def test_the_kth_largest_is_the_sorts():
    rng = np.random.RandomState(1)
    x = rng.randn(6, 50).astype(np.float32)
    x[0, :45] = -np.inf                   # fewer live entries than k
    x[1, 10:20] = x[1, 3]                 # ties
    keys = latent._orderable(jnp.asarray(x))
    kth = latent._kth_largest(keys, 8)
    chosen = np.asarray(keys >= kth[:, None])
    want = np.sort(x, axis=-1)[:, -8]
    assert (chosen == (x >= want[:, None])).all()
    assert chosen[0].all() and chosen[2:].sum(-1).tolist() == [8] * 4


def _parents_decode(qq, q_i, w, latent_pool, index_pool, table, lengths,
                    cfg, impl, interpret):
    """``latent._attend_decode`` as the tree before ISSUE 44 had it, kept
    here as an oracle: ``top_k`` over the index scores, a gather of the
    chosen latent rows through the table, two einsums over the copy.
    Returns the attended latent and the positions [B, S] (-1: none)."""
    b, mb = table.shape
    bs, c = latent_pool.shape[1], cfg.kv_lora_rank
    if impl == "pallas":
        scores = paged_index.paged_index_scores(
            q_i, w, index_pool, table, lengths, interpret=interpret)
    else:
        scores = paged_index.gather_index_scores(
            q_i, w, index_pool, table, lengths)
    top, pos = jax.lax.top_k(scores, cfg.index_topk)
    valid = top > -jnp.inf
    page = jnp.take_along_axis(
        table, jnp.minimum(pos // bs, mb - 1), axis=1)
    flat = jnp.where(valid, page * bs + pos % bs, 0)
    rows = jnp.take(latent_pool.reshape(-1, latent_pool.shape[-1]),
                    flat, axis=0)
    s = jnp.einsum("bhc,bsc->bhs", qq, rows.astype(qq.dtype),
                   preferred_element_type=jnp.float32
                   ) * latent._softmax_scale(cfg)
    s = jnp.where(valid[:, None, :], s, -jnp.inf)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("bhs,bsc->bhc", p.astype(qq.dtype),
                   rows[..., :c].astype(qq.dtype),
                   preferred_element_type=jnp.float32)
    return o, jnp.where(valid, pos, -1)


def _decode_operands(cfg, lengths, mb=15, bs=8, nb=60, seed=2,
                     whole_numbers=False):
    """One decode forward's attention operands for slots of these
    ``lengths``, each on pages of its own; dead rows of both pools are
    loud.  ``whole_numbers``: index queries, keys and weights whose
    scores are exact in float32 and few, so that many tie."""
    rng = np.random.RandomState(seed)
    b = len(lengths)
    width = latent.latent_row_width(cfg)
    live = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    table = np.zeros((b, mb), np.int32)
    free = list(range(1, nb))
    for s, n in enumerate(lengths):
        for j in range(-(-n // bs)):
            table[s, j] = free.pop(0)
    lat = np.full((nb, bs, width), 1e4, np.float32)
    idx = np.full((nb, bs, cfg.index_head_dim), 1e4, np.float32)
    for s, n in enumerate(lengths):
        at = (table[s, np.arange(n) // bs], np.arange(n) % bs)
        lat[at] = rng.randn(n, width)
        idx[at] = rng.randint(-2, 3, (n, cfg.index_head_dim)) \
            if whole_numbers else rng.randn(n, cfg.index_head_dim)
    lat[..., live:] = 0.0
    qq = rng.randn(b, cfg.num_heads, width).astype(np.float32)
    qq[..., live:] = 0.0
    shape = (b, cfg.index_n_heads, cfg.index_head_dim)
    q_i = rng.randint(-2, 3, shape) if whole_numbers else rng.randn(*shape)
    w = 2.0 ** -rng.randint(0, 3, shape[:2]) if whole_numbers \
        else rng.rand(*shape[:2])
    return tuple(jnp.asarray(a) for a in (
        qq, q_i.astype(np.float32), w.astype(np.float32), lat, idx, table,
        np.asarray(lengths, np.int32)))


def _as_mask(pos, width):
    mask = np.zeros((pos.shape[0], width + 1), bool)
    np.put_along_axis(mask, np.where(pos < 0, width, pos), True, axis=-1)
    return mask[:, :width]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_decode_under_the_threshold_is_the_parents_top_k_and_gather(
        impl, cfg):
    """No score ties the ``index_topk``-th: the threshold chooses the rows
    ``top_k`` chose, and streaming every live row under their mask
    attends them as the gathered copy was attended.  A slot with fewer
    rows than it may choose attends them all, a slot of length 0 none."""
    args = _decode_operands(cfg, [45, 0, 97, 5, 120])
    got, chosen = latent._attend_decode(*args, cfg, impl, True)
    want, pos = _parents_decode(*args, cfg, impl, True)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert (np.asarray(chosen)
            == _as_mask(np.asarray(pos), chosen.shape[1])).all()
    assert np.asarray(chosen).sum(-1).tolist() == [8, 0, 8, 5, 8]
    assert not np.asarray(got[1]).any()


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_tie_at_the_threshold_is_attended_whole_as_a_run_does(impl, cfg):
    """Scores that tie the ``index_topk``-th are ALL chosen, as the
    reference says (``scores >= kth``) and as the same query chooses
    when it comes as a run of one (``_attend_run``, the one helper);
    ``top_k`` kept the lowest positions among them."""
    lengths = [61, 110, 87]
    args = _decode_operands(cfg, lengths, whole_numbers=True)
    qq, q_i, w, lat, idx, table, _ = args
    # three rows that scored lower get the index key of the row that
    # scored ``index_topk``-th: whole numbers, so the four scores are one
    scores = np.asarray(paged_index.gather_index_scores(
        q_i, w, idx, table, args[6]))
    idx, pages = np.array(idx), np.asarray(table)
    for s, n in enumerate(lengths):
        order = np.argsort(-scores[s, :n], kind="stable")
        kth = order[cfg.index_topk - 1]
        for low in order[cfg.index_topk + 4:cfg.index_topk + 7]:
            idx[pages[s, low // 8], low % 8] = idx[pages[s, kth // 8],
                                                   kth % 8]
    idx = jnp.asarray(idx)
    args = args[:4] + (idx,) + args[5:]
    got, chosen = latent._attend_decode(*args, cfg, impl, True)
    _, pos = _parents_decode(*args, cfg, impl, True)
    chosen, kept = np.asarray(chosen), _as_mask(np.asarray(pos),
                                                chosen.shape[1])
    scores = np.asarray(paged_index.gather_index_scores(*args[1:3], idx,
                                                        table, args[6]))
    kth = np.sort(scores, axis=-1)[:, -cfg.index_topk]
    assert (chosen == (scores >= kth[:, None])).all()
    assert (chosen.sum(-1) > cfg.index_topk).all()      # ties, planted
    assert (chosen | kept == chosen).all() \
        and (kept.sum(-1) == cfg.index_topk).all()
    run_table = latent._pad_table(table, latent.KEY_BLOCK_PAGES)
    for s, n in enumerate(lengths):
        o_run, chosen_run = latent._attend_run(
            qq[s][None], q_i[s][None], w[s][None],
            jnp.asarray([n - 1], jnp.int32), lat, idx, run_table[s], cfg,
            latent.KEY_BLOCK_PAGES, impl, True)
        width = min(chosen.shape[1], chosen_run.shape[1])
        assert (np.asarray(chosen_run)[0, :width] == chosen[s, :width]).all()
        assert not chosen[s, width:].any()
        np.testing.assert_allclose(got[s], o_run[0], atol=1e-5)


@pytest.mark.parametrize("width,chosen,size", [
    (33 * 128, 300, 512),      # fewer than asked for: -1 behind the last
    (33 * 128, 512, 512),
    (33 * 128, 530, 512),      # a tie past the threshold: the first 512
    (200, 40, 64),             # no whole number of 128 lanes
    (24, 8, 8),
    (256, 0, 16),              # nothing chosen
    (256, 256, 256),           # everything
], ids=lambda v: str(v))
def test_a_mask_rows_positions_are_numpys(width, chosen, size):
    """``_rows_of`` counts where ``jnp.nonzero`` scatters: the same
    positions, ascending, -1 behind the last; and, with no mask, every
    row behind the length."""
    rng = np.random.RandomState(width + chosen)
    row = np.zeros(width, bool)
    row[rng.permutation(width)[:chosen]] = True
    row[:2] = row[-2:] = chosen > 0           # both edges
    got = np.asarray(jax.jit(
        lambda r: latent._rows_of(r, None, width, size))(jnp.asarray(row)))
    want = np.full(size, -1, np.int32)
    at = np.flatnonzero(row)[:size]
    want[:at.size] = at
    assert got.dtype == np.int32 and got.tolist() == want.tolist()
    short = np.asarray(latent._rows_of(None, jnp.asarray(5), width, size))
    assert short.tolist() == (list(range(5)) + [-1] * size)[:size]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_witness_rows_are_the_watched_slots_mask(impl, cfg, sp,
                                                     monkeypatch):
    """A decode forward over two slots hands back, a layer, the WATCHED
    slot's chosen positions and nothing of the other's: exactly its row
    of that layer's mask, ascending, -1 behind the last."""
    rng = np.random.RandomState(6)
    width = latent.latent_row_width(cfg)
    cache = {
        "latent_pool": [jnp.zeros((33, 8, width))] * cfg.num_layers,
        "index_pool": [jnp.zeros((33, 8, cfg.index_head_dim))]
        * cfg.num_layers,
        "table": jnp.asarray(np.arange(1, 33).reshape(2, 16), jnp.int32),
        "moe_picks": jnp.zeros(4, jnp.uint32)}
    kw = dict(attention_impl=impl, kernel_interpret=True)
    chunk = jax.jit(lambda p, c, t, at, slot: latent.verify_step(
        p, cfg, c, t, at, slots=slot, **kw))
    for slot, n in ((0, 48), (1, 32)):
        seq = rng.randint(0, 128, n).astype(np.int32)
        for s in range(0, n, 16):
            _, cache = chunk(sp, cache, jnp.asarray(seq[None, s:s + 16]),
                             jnp.asarray([s], jnp.int32),
                             jnp.asarray([slot], jnp.int32))
    masks = []
    real = latent._attend_decode

    def spy(*a):
        o, chosen = real(*a)
        masks.append(chosen)
        return o, chosen

    monkeypatch.setattr(latent, "_attend_decode", spy)

    @jax.jit
    def forward(p, c, t, at):
        del masks[:]
        _, out = latent.verify_step(p, cfg, c, t, at, **kw)
        return out["witness"]["rows"], jnp.stack(masks)

    for watch, length in ((1, 33), (0, 49)):
        rows, chosen = forward(
            sp, dict(cache, watch_slot=jnp.asarray(watch, jnp.int32)),
            jnp.asarray([[5], [9]], jnp.int32),
            jnp.asarray([48, 32], jnp.int32))
        rows, chosen = np.asarray(rows), np.asarray(chosen)
        # as many as a query may choose, and room for a tie at the
        # threshold (here: as many as the table holds)
        assert rows.shape == (cfg.num_layers, chosen.shape[-1]) \
            and rows.dtype == np.int32
        for layer in range(cfg.num_layers):
            at = np.flatnonzero(chosen[layer, watch])
            assert at.size == cfg.index_topk and at.max() < length
            assert rows[layer, :at.size].tolist() == at.tolist()
            assert (rows[layer, at.size:] == -1).all()
    # a tie at the threshold is attended whole and WITNESSED whole: slot
    # 1's 32 cached index keys of layer 0 made one key, the forward's own
    # the 33rd row
    keys = cache["index_pool"][0]
    tied = dict(cache, watch_slot=jnp.asarray(1, jnp.int32), index_pool=[
        keys.at[17:21].set(keys[17, 0])] + cache["index_pool"][1:])
    rows, chosen = forward(sp, tied, jnp.asarray([[5], [9]], jnp.int32),
                           jnp.asarray([48, 32], jnp.int32))
    at = np.flatnonzero(np.asarray(chosen)[0, 1])
    assert at.size >= 32 > cfg.index_topk
    assert np.asarray(rows)[0, :at.size].tolist() == at.tolist()


def _watched(cfg, params, **engine):
    """Questions on one cached document through an engine of its own
    that is watched, as ``perfbench/drivers/serve_sparse.py`` watches its
    window: what the engine's own programs handed back, from its first,
    packed for the reference."""
    rng = np.random.RandomState(11)
    doc = rng.randint(0, 128, 48).astype(np.int32)
    eng = _engine(cfg, params, **engine)
    eng.add_request(doc, 1)
    eng.run()
    eng.watch(lambda req: req.prompt.size > doc.size)
    for n in (13, 16, 9):
        eng.add_request(np.concatenate(
            [doc, rng.randint(0, 128, n).astype(np.int32)]), 14)
    eng.run()
    return serve_sparse.Witnessed(doc, eng.witness_log, cfg.num_layers, 1)


@pytest.fixture(scope="module")
def watched(cfg, params):
    return _watched(cfg, params)


def test_a_watched_request_is_witnessed_by_programs_that_have_been_read(
        cfg, params):
    """``step`` dispatches a latent model's prompt chunks (one a slot)
    and its decode chunk before it reads any of them, and leaves the
    decode chunk in flight: a witness entry joins the log when its
    program is READ, so bringing the log to the host after every step,
    as the benchmark's drivers do, waits for nothing (every leaf is
    ready, none is the in-flight chunk's); the log is complete after the
    drain, and the experts' picks, taken from what each program read
    handed back, lag by the chunk in flight and end at the parent's
    numbers.  An engine of its own: the witness log and the books are
    read from their start."""
    rng = np.random.RandomState(4)
    eng = _engine(cfg, params)
    eng.watch(lambda req: req.prompt.size == 40)
    for n in (40, 37):
        eng.add_request(rng.randint(0, 128, n).astype(np.int32), 6)
    picks, lagged = [], 0
    while eng.has_work:
        eng.step()
        assert [u.name for u in eng._unread] in ([], ["decode_chunk"])
        in_flight = [id(leaf) for u in eng._unread for w in u.witness
                     for leaf in jax.tree_util.tree_leaves(w["seen"])]
        lagged += bool(in_flight)
        for w in eng.witness_log:
            leaves = jax.tree_util.tree_leaves(w["seen"])
            assert all(leaf.is_ready() and id(leaf) not in in_flight
                       for leaf in leaves)
            with jax.transfer_guard_device_to_host("allow"):
                jax.tree_util.tree_map(np.asarray, w["seen"])
        picks.append(eng.stats.moe_picks)
    assert lagged == 2 and not eng._unread     # both decode chunks
    watched = eng.witness_log
    assert {w["request"].rid for w in watched} == {0}
    assert [(w["kind"], w["start"]) for w in watched] == [
        ("run", 0), ("run", 16), ("run", 32), ("decode", 40),
        ("decode", 44)]
    assert all(isinstance(leaf, jax.Array) for w in watched
               for leaf in jax.tree_util.tree_leaves(w["seen"]))
    st = eng.stats
    # three steps of two chunk programs, the second behind the first; a
    # decode chunk behind the third's, and one behind that, unread
    assert (st.dispatches, st.chained_dispatches) == (2 * 3 + 2, 3 + 2)
    assert (st.lookahead_steps, st.wasted_lane_chunks) == (2, 0)
    assert picks == sorted(picks) and picks[0] > 0
    # (the parent's numbers for these two requests)
    assert (st.moe_picks, st.moe_picks_held) == (picks[-1], 155) == (372, 155)
    # a prompt's three chunks end at 16, 32 and its last token, 40 or 37
    # (behind it the third program is padding, which walks nothing):
    # 1 + 1 + 2 of the kernel's blocks of 4 pages x 8 rows, where a
    # table of 12 pages (16 in whole selection blocks) holds 4.  A chunk
    # of 16 queries is one tile of the kernel's, and never without a
    # real query
    assert (st.prefill_key_blocks, st.prefill_key_blocks_table) == (8, 24)
    assert st.prefill_key_block_share == pytest.approx(1 / 3)
    assert (st.prefill_query_tiles, st.prefill_query_tiles_live) == (6, 6)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_padded_last_chunk_answers_as_whole_prompts_do(impl, monkeypatch):
    """Prompts of 70 and 100 tokens in chunk programs of 64 queries (two
    tiles of the attention kernel's): 58 and 28 rows of the last chunks
    are padding, which attends nothing.  Greedy, the tokens are those of
    an engine that prefills each prompt whole (the bucketed program,
    which has no padding to skip) and the reference's argmax; the books,
    the span and the gauge say what the padding was.  Engines of its
    own (a longer context than ``cfg``'s, and the books and the gauge are
    read whole)."""
    from dlrover_tpu.serving.router.metrics import RouterMetrics
    from dlrover_tpu.serving.router.replica import InferenceEngineAdapter

    spans = []
    inner = engine_module.span

    def span(name, **attrs):
        if name == "dlrover.engine.prefill_chunk" and attrs:
            spans.append(attrs)
        return inner(name, **attrs)

    monkeypatch.setattr(engine_module, "span", span)
    cfg = tiny(max_seq_len=192)
    params = SeededGlm5Params(cfg, 9)
    rng = np.random.RandomState(12)
    prompts = [rng.randint(0, 128, n).astype(np.int32) for n in (70, 100)]
    sizes = dict(max_len=192, prefill_buckets=(96, 128),
                 attention_impl=impl)
    eng = _engine(cfg, params, prefill_chunk=64, **sizes)
    rids = [eng.add_request(p, 6) for p in prompts]
    got = eng.run()
    whole = _engine(cfg, params, prefill_chunk=0, **sizes)
    wids = [whole.add_request(p, 6) for p in prompts]
    want = whole.run()
    assert whole.stats.prefill_chunks == 0 and eng.stats.prefill_chunks == 2
    for prompt, rid, wid in zip(prompts, rids, wids):
        assert got[rid].tolist() == want[wid].tolist()
        out = np.asarray(got[rid])
        assert out.size == 6
        logits, _ = reference_logits(
            cfg, params, np.concatenate([prompt, out]).astype(np.int32))
        at = prompt.size - 1 + np.arange(6)
        logits = np.asarray(logits)[at]
        assert (logits.max(-1) - logits[np.arange(6), out]).max() < 1e-3

    st = eng.stats
    # four chunk programs of two tiles; the one with 6 real queries
    # fills one of them
    assert (st.prefill_query_tiles, st.prefill_query_tiles_live) == (8, 7)
    assert st.prefill_live_tile_share == 7 / 8
    # real ends 64, 70, 64 and 100 in key blocks of 32 rows (the kernel's
    # 4 pages; the loop's 8 pages hold 64): by the programs' ends, 128
    # twice, it was 2 + 4 + 2 + 4
    blocks = (2 + 3 + 2 + 4) if impl == "pallas" else (1 + 2 + 1 + 2)
    pages = -(-eng._max_blocks // 8) * 8       # whole selection blocks
    held = 4 * pages // (4 if impl == "pallas" else 8)
    assert (st.prefill_key_blocks, st.prefill_key_blocks_table) \
        == (blocks, held)
    for key, total in (("query_tiles", 8), ("query_tiles_live", 7),
                       ("key_blocks", blocks)):
        assert sum(a[key] for a in spans) == total
    m = RouterMetrics()
    m.observe_engine_metrics([InferenceEngineAdapter(eng).engine_metrics()])
    assert m.metrics()["serving_prefill_live_tile_share"] == 7 / 8


def _verdicts(checked):
    return [checked[k] for k in controls_glm5.VERDICTS]


@pytest.mark.parametrize("fault", [None] + sorted(controls_glm5.FAULTS))
def test_the_timed_programs_witness_holds_and_a_planted_fault_shows(
        fault, cfg, params, watched):
    """The comparison the benchmark's cell makes of the engine's OWN
    prefill-chunk and decode-chunk programs (selected rows, first sparse
    MLP, emitted tokens) holds against the reference, and fails, by the
    driver's limits, against a reference with any one fault planted."""
    seen = watched
    assert len(seen.requests) == 2                   # one at a time
    assert seen.queries["run"].size == 13 + 9
    # (a request's last token is fed to no forward that counts)
    assert seen.queries["decode"].size == 2 * 13
    with (controls_glm5.FAULTS[fault]() if fault
          else controls_glm5._patched()):
        got = serve_sparse.reference_check(cfg, params, config_of(cfg), seen)
    if fault is None:
        assert _verdicts(got) == [True] * 3
        assert got["checked_positions"] == 2 * 14
        assert got["worst_logit_deficit"] < 1e-4
        for kind in ("run", "decode"):
            assert all(s["overlap"] == 1.0 and s["unseen"] == 0
                       for s in got[f"selection_{kind}"])
            assert got[f"sparse_{kind}"]["mlp_rel"] < 1e-5
    else:
        assert not all(_verdicts(got)), got


@pytest.mark.parametrize("program", ["decode", "run"])
def test_a_fault_in_one_timed_program_shows_there(program, cfg, params,
                                                  monkeypatch):
    """The fault planted in the PROGRAM: the indexer's head weights lose
    their sign in the decode program's scan alone, or in the prefill
    chunk's alone; the witness of that program fails the selection, and
    the prefill chunk's, which ran before any decode, is untouched by the
    decode program's.  An engine of its own: the programs it traces are
    patched."""
    if program == "decode":
        inner = paged_index.gather_index_scores
        monkeypatch.setattr(
            paged_index, "gather_index_scores",
            lambda q, w, *a: inner(q, jnp.abs(w), *a))
    else:
        inner = paged_index.index_scores
        monkeypatch.setattr(
            paged_index, "index_scores",
            lambda q, w, keys: inner(q, jnp.abs(w), keys))
    seen = _watched(cfg, params, attention_impl="xla")
    got = serve_sparse.reference_check(cfg, params, config_of(cfg), seen)
    assert not got["selection_matches_reference"]
    assert not serve_sparse.selection_holds(got[f"selection_{program}"])
    if program == "decode":
        assert all(s["overlap"] == 1.0 for s in got["selection_run"])


# the dense decoder's serving programs, as the parent of PR 34 traced them
# (bf16 tiny preset, paged pools): (lines, sha256 of the jaxpr's text);
# ``decode_step`` holds the paged GQA decode kernel's body and was pinned
# again when PR 53 changed that kernel (1669 lines before it)
_DENSE = {
    "decode_step": (1747, "7c60d0fb4c3fb61acdc0de9de26b136c73d5acc94678f345"
                          "92b10925d84cb4af"),
    "prefill": (619, "cd182c54b1a09da445322202802dcb7738bcba847ee412cb0066"
                     "3b52aa569325"),
    "prefill_chunk": (935, "0e0d6374ccc52a0fc590c0cf18c138096343708d5a04d1"
                           "5ab6963e31d32eb594"),
}


@pytest.mark.parametrize("program", sorted(_DENSE) + ["prefill_chunk.pallas"])
def test_dense_model_traces_what_it_did(program, tmp_path):
    """``decode_step``, ``prefill`` and the chunked prefill of a dense
    model are, to the letter, the programs the tree before the latent
    blocks traced (``serve-batch-closed`` compiles the same); the chunked
    prefill also where the engine hands it the kernels' options, as it
    has since a latent model's run of queries takes a kernel (PR 37): a
    dense run of queries takes none.  A change that means to move them,
    or a JAX that prints them otherwise, re-pins: the text is left in a
    file to diff."""
    program, _, impl = program.partition(".")
    kernels = dict(attention_impl=impl, kernel_interpret=True) if impl else {}
    cfg = LlamaConfig.tiny(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    sp = jax.eval_shape(lambda: serving_params_from_llama(
        {"params": SeededParams(cfg, 3)}, cfg))
    b, nb, bs, mb = 2, 9, 8, 4
    S = jax.ShapeDtypeStruct
    pool = [S((nb, bs, cfg.num_kv_heads, cfg.head_dim_), jnp.bfloat16)
            ] * cfg.num_layers
    cache = {"k_pool": pool, "v_pool": pool, "table": S((b, mb), jnp.int32)}
    ints = lambda *shape: S(shape, jnp.int32)  # noqa: E731
    if program == "decode_step":
        jaxpr = jax.make_jaxpr(
            lambda p, c, t, pos, act: serving_model.decode_step(
                p, cfg, c, t, pos, attention_impl="pallas",
                kernel_interpret=True, active=act))(
            sp, cache, ints(b), ints(b), S((b,), jnp.bool_))
    elif program == "prefill":
        jaxpr = jax.make_jaxpr(
            lambda p, t, n: serving_model.prefill(p, cfg, t, n))(
            sp, ints(b, 16), ints(b))
    else:
        jaxpr = jax.make_jaxpr(
            lambda p, c, t, pos, sl, li: serving_model.verify_step(
                p, cfg, c, t, pos, slots=sl, logits_index=li, **kernels))(
            sp, cache, ints(1, 8), ints(1), ints(1), ints(1))
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    (tmp_path / f"{program}.txt").write_text(text)
    got = (len(text.splitlines()), hashlib.sha256(text.encode()).hexdigest())
    assert got == _DENSE[program], \
        f"jax {jax.__version__}; the trace: {tmp_path / program}.txt"
