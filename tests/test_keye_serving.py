"""Keye-VL-2.0-30B-A3B's language model (``KeyeVL2``) at a tiny size on the
CPU, float32, seeded: a GROUPED-QUERY layer with an indexer inside it, a
QK-norm a head and softmax-routed experts with no shared one, through the
paged K/V + index-key cache and the engine, against the plain reference
(``perfbench/reference_keye.py``); M-RoPE on text; the shares of a layer
against the uncut layer; the masked decode kernel and the index kernel at
half a row of lanes in interpret mode against their ``jnp`` oracles; the
configuration file's arithmetic; what is still refused, by its message;
and every planted control.

The config, the seeded params and the engines come from module-scoped
fixtures (tests/test_sparse_serving.py's rule)."""

import ast
import contextlib
import dataclasses
import difflib
import hashlib
import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.llama import (PRESETS, LlamaConfig, LlamaModel,
                                      apply_rope, rope_inverse_frequencies)
from dlrover_tpu.ops.pallas import paged_index
from dlrover_tpu.ops.pallas.paged_attention import (SELECTED_ATTENTION,
                                                    gather_reference,
                                                    paged_decode_attention,
                                                    shared_runs,
                                                    streamed_rows)
from dlrover_tpu.serving import latent
from dlrover_tpu.serving.engine import InferenceEngine
from dlrover_tpu.serving.params import serving_params_from_llama
from perfbench import controls_keye
from perfbench import reference_keye as ref
from perfbench.drivers import serve_sparse, serve_sparse_gqa
from perfbench.weights_keye import SeededKeyeParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILE = os.path.join(ROOT, "perfbench", "configs",
                    "keye-vl2-30b-a3b-serve.json")
SLOT = jnp.zeros(1, jnp.int32)


def tiny(**kw):
    base = dict(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_layers=2,
        num_heads=8, num_kv_heads=2, head_dim=16, max_seq_len=96,
        index_n_heads=4, index_head_dim=8, index_topk=8, num_experts=8,
        moe_top_k=2, moe_intermediate_size=32, moe_experts_held=(2, 3),
        dtype=jnp.float32, param_dtype=jnp.float32, rope_theta=1e4)
    base.update(kw)
    return LlamaConfig.keye_vl2_30b_a3b(**base)


def config_of(cfg):
    """``cfg`` under the keys of a configuration file that
    ``reference_keye.dims_of`` reads."""
    first, held = cfg.moe_experts_held or (0, cfg.num_experts)
    half = cfg.head_dim_ // 2
    return {
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim_,
        "rope_scaling": {"mrope_section": [
            half // 4, half // 2, half - half // 4 - half // 2]},
        "sa_config": {"indexer_num_heads": cfg.index_n_heads,
                      "indexer_head_dim": cfg.index_head_dim,
                      "topk": cfg.index_topk},
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
        "num_local_experts": cfg.num_experts, "num_experts": held,
        "experts_held": [first, held],
        "num_experts_per_tok": cfg.moe_top_k,
        "norm_topk_prob": cfg.moe_norm_topk_prob}


def dims(cfg):
    return ref.dims_of(config_of(cfg))


def fresh_cache(cfg, blocks=16, bs=8, slots=1):
    table = np.zeros((slots, blocks - 1), np.int32)
    table[0] = np.arange(1, blocks)
    kvd = (blocks, bs, cfg.num_kv_heads, cfg.head_dim_)
    layers = range(cfg.num_layers)
    return {
        "k_pool": [jnp.zeros(kvd) for _ in layers],
        "v_pool": [jnp.zeros(kvd) for _ in layers],
        "index_pool": [jnp.zeros((blocks, bs, latent.index_row_width(cfg)))
                       for _ in layers],
        "table": jnp.asarray(table),
        "moe_picks": jnp.zeros(4, jnp.uint32)}


def reference_logits(cfg, params, seq, **kw):
    x = ref.hidden_states(seq, params.layer, params.top(), cfg.num_layers,
                          dims(cfg), **kw)
    picked = None
    if kw:
        x, picked = x
    return ref.head_logits(x, params.top(), cfg.rms_norm_eps), picked


_PROGRAMS = {}


def _run(sp, cfg, cache, seq, start, **kw):
    key = (cfg, len(seq), tuple(sorted(
        (k, v) for k, v in kw.items() if k != "slots")), "slots" in kw)
    step = _PROGRAMS.setdefault(key, jax.jit(
        lambda p, c, t, at: latent.verify_step(p, cfg, c, t, at, **kw)))
    return step(sp, cache, jnp.asarray(seq[None]),
                jnp.asarray([start], jnp.int32))


@pytest.fixture(scope="module")
def cfg():
    return tiny()


@pytest.fixture(scope="module")
def params(cfg):
    return SeededKeyeParams(cfg, 9)


# ----------------------------------------------- the blocks, the reference
@pytest.mark.parametrize("topk", [8, 4096], ids=["selected", "dense"])
def test_prefill_and_decode_through_the_cache_are_the_reference(topk):
    """Chunks of 16, then token by token (the decode kernels in interpret
    mode and their ``jnp`` twins in turn): every position's logits are the
    reference's full forward and every query's chosen rows the reference's
    ``S_t``, with the selection smaller than the context and with a
    context under it (plain grouped-query attention)."""
    cfg = tiny(index_topk=topk)
    params = SeededKeyeParams(cfg, 7)
    sp = serving_params_from_llama({"params": params}, cfg)
    assert {"q_norm", "k_norm", "iwq", "iwk", "iw", "ik_norm_scale",
            "ik_norm_bias", "wqkv", "wo", "router"} <= set(sp["layers"][0])
    seq = np.random.RandomState(0).randint(0, 128, 38).astype(np.int32)
    want, picked = reference_logits(cfg, params, seq, selection_of=(0, 38))
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
            assert (chosen[layer, :, :38]
                    == np.asarray(picked[layer][1][s:s + 16])).all()
    for p in range(32, 38):
        logits, cache = _run(
            sp, cfg, cache, seq[p:p + 1], p, kernel_interpret=True,
            attention_impl="pallas" if p % 2 else "xla")
        seen = cache.pop("witness")
        assert seen["sparse_in"].shape == (1, cfg.hidden_size)
        for layer in range(cfg.num_layers):
            rows = np.asarray(seen["rows"][layer])
            assert sorted(rows[rows >= 0]) == np.flatnonzero(
                np.asarray(picked[layer][1][p])).tolist()
        got.append(logits[0])
    np.testing.assert_allclose(jnp.concatenate(got), want, atol=1e-4)
    # the index keys are cached beside the K/V rows, under the one table
    assert len(cache["index_pool"]) == len(cache["k_pool"]) == 2
    assert float(jnp.abs(cache["index_pool"][0][1, 0, :8]).sum()) > 0
    assert float(jnp.abs(cache["index_pool"][0][..., 8:]).sum()) == 0


def test_mrope_at_three_equal_streams_is_plain_rope():
    """A text token's three position streams are equal: the reference's
    M-RoPE (``mrope_section`` [16, 24, 24] over a head of 128) is then the
    plain RoPE the program computes, halves paired; a token whose streams
    differ is rotated otherwise."""
    x = jnp.asarray(np.random.RandomState(2).randn(6, 3, 128), jnp.float32)
    pos = jnp.asarray([0, 1, 5, 17, 300, 33000])
    sections = (16, 24, 24)
    three = ref.mrope(x, ref.three_streams(pos), 1e7, sections)
    np.testing.assert_allclose(three, ref.rope(x, pos, 1e7), atol=1e-6)
    spec = tiny(rope_theta=1e7).layer_specs[0].rope
    angles = pos.astype(jnp.float32)[:, None] \
        * rope_inverse_frequencies(spec, 128)
    np.testing.assert_allclose(
        three, apply_rope(x[None], angles)[0], atol=1e-4)
    np.testing.assert_allclose(three[0], x[0], atol=1e-6)   # position 0
    image = jnp.stack([pos, pos + 3, pos + 7])
    moved = np.asarray(ref.mrope(x, image, 1e7, sections))
    # stream 0 owns pairs 0-15: they turn as on text, the others do not
    np.testing.assert_allclose(moved[..., :16], three[..., :16], atol=1e-6)
    np.testing.assert_allclose(moved[..., 64:80], three[..., 64:80],
                               atol=1e-6)
    assert np.abs(moved[..., 16:64] - np.asarray(three)[..., 16:64]).max() \
        > 0.1


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips share a layer of 8 experts, 2 each: their expert parts
    summed, with attention (computed alike on every chip) counted once,
    are the uncut reference's layer; and the program's sparse MLP of each
    share is the reference's of that share."""
    whole = tiny(num_layers=1, moe_experts_held=None)
    params = SeededKeyeParams(whole, 4)
    lp, d = params.layer(0), dims(whole)
    x = jnp.asarray(np.random.RandomState(1).randn(24, 64), jnp.float32)
    uncut = ref.layer_forward(x, lp, d)
    h = ref._norm(x, lp["input_norm"]["scale"], d["eps"])
    mid = x + ref.attention(h, lp, d)
    h2 = ref._norm(mid, lp["post_norm"]["scale"], d["eps"])
    parts = []
    for first in range(0, 8, 2):
        share = dataclasses.replace(whole, moe_experts_held=(first, 2))
        m = dict(lp["mlp"], **{k: lp["mlp"][k][first:first + 2]
                               for k in ("w_gate", "w_up", "w_down")})
        parts.append(ref.mlp(h2, m, dims(share)))
        if first != 4:
            continue
        sp = serving_params_from_llama(
            {"params": {"layer_0": dict(lp, mlp=m), **params.top()}}, share)
        got, picks = latent.sparse_mlp(
            sp["layers"][0], h2[None], share, jnp.float32,
            jnp.ones((1, 24), bool))
        np.testing.assert_allclose(got[0], parts[-1], atol=1e-5)
        assert int(picks[0]) == 24 * 2 and 0 < int(picks[1]) < int(picks[0])
    np.testing.assert_allclose(mid + sum(parts), uncut, atol=1e-5)
    assert float(jnp.abs(sum(parts)).max()) > 0.01


# ------------------------------------------------ the kernels, interpreted
@pytest.mark.parametrize("pages", [None, 2, 3])
def test_the_masked_decode_kernel_is_its_oracle(pages):
    """``paged_decode_attention`` under a selection's bias, with groups
    nobody chose a row of, against the gather; and without a bias it is
    the kernel it was."""
    b, h, kv, d, bs, mb = 3, 4, 2, 64, 16, 7
    nb = b * mb + 1
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (nb, bs, kv, d), jnp.float32)
    v = jax.random.normal(ks[2], (nb, bs, kv, d), jnp.float32)
    table = jnp.arange(1, nb).reshape(b, mb).astype(jnp.int32)
    lengths = jnp.asarray([5, 60, 112], jnp.int32)
    chosen = (jax.random.uniform(ks[3], (b, mb * bs)) < 0.05
              ).at[:, 0].set(True).at[2, 16:64].set(False)
    bias = jnp.where(chosen, 0.0, -jnp.inf)
    got = paged_decode_attention(
        q, k, v, table, lengths, bias=bias, interpret=True,
        pages_per_block=pages)
    np.testing.assert_allclose(
        got, gather_reference(q, k, v, table, lengths, bias=bias),
        atol=2e-6)
    # a bias narrower than the table: the rows behind it are nobody's
    np.testing.assert_allclose(
        paged_decode_attention(q, k, v, table, lengths, bias=bias[:, :40],
                               interpret=True, pages_per_block=pages),
        gather_reference(q, k, v, table, lengths, bias=bias[:, :40]),
        atol=2e-6)
    plain = paged_decode_attention(q, k, v, table, lengths, interpret=True,
                                   pages_per_block=pages)
    np.testing.assert_allclose(
        plain, gather_reference(q, k, v, table, lengths), atol=2e-6)
    assert float(jnp.abs(plain - got).max()) > 0.01
    # the kernel names its own call: apart under a bias, as it was without
    for given in (bias, None):
        text = str(jax.make_jaxpr(lambda b_: paged_decode_attention(
            q, k, v, table, lengths, bias=b_, interpret=True,
            pages_per_block=pages))(given))
        assert (SELECTED_ATTENTION in text) == (given is not None)
    # ... and without one the traced program is commit 8914f83's, the
    # parent of the shared-run stream (PR 59), to the letter: the sha256
    # of that tree's text at these shapes
    text = str(jax.make_jaxpr(lambda *a: paged_decode_attention(
        *a, interpret=True, pages_per_block=pages))(q, k, v, table, lengths))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == {
        None: "d4483c0a4fe05f5c", 2: "cda2193b21eb4974",
        3: "988b47066ffc7140"}[pages]


# slots of (document or None, leading pages of it in the table row, length)
# over pages of 16 rows in a table of 48: what the shared-run stream of the
# kernel under a selection must get right
_SHARED_RUNS = {
    # three runs of 3 / 2 / 1 slots, not side by side, of 34 and 20 pages
    "three_runs": [("A", 34, 700), ("B", 20, 400), ("A", 34, 768),
                   (None, 0, 300), ("B", 20, 321), ("A", 34, 545)],
    # 5 common pages: no whole number of groups of 2 or 3 pages
    "ragged_run": [("A", 5, 200), ("A", 5, 90), (None, 0, 17)],
    # a member of length 0, and two whose length ends on the run's last row
    "edges": [("A", 6, 0), ("A", 6, 96), ("A", 6, 130), ("A", 6, 96)],
    # every slot in one run, more of them than the largest pass holds
    # (RUN_TILES: one pass of 16 and one of 4)
    "all_slots": [("A", 36, 600 + 7 * i) for i in range(18)],
    # nothing shared: the parent's stream, and the parent's booking
    "none_shared": [(None, 0, 5), (None, 0, 300), (None, 0, 768)],
    # rows alike at entry 0 and apart at entry 1
    "one_page": [("A", 1, 100), ("A", 1, 200)],
    # a member that chose no row of the run's groups behind the first
    "chose_none": [("A", 34, 700), ("A", 34, 600), ("A", 34, 580)],
}


@pytest.mark.parametrize("pages", [None, 2, 3])
@pytest.mark.parametrize("case", sorted(_SHARED_RUNS))
def test_a_shared_run_is_streamed_once_and_attended_a_slot_at_a_time(
        case, pages):
    """``paged_decode_attention`` under a bias over slots whose table rows
    begin alike (interpret mode): the gather's result a slot, each under
    its own selection, and the rows the host books as streamed are the
    kernel's plan: a run's shared groups once."""
    bs, mb, h, kv, d = 16, 48, 4, 2, 64
    slots = _SHARED_RUNS[case]
    b, table, free, docs = len(slots), [], 1, {}
    for doc, n, _ in slots:
        if doc is not None and doc not in docs:
            docs[doc], free = np.arange(free, free + mb), free + mb
        table.append(np.concatenate(
            [docs[doc][:n] if n else [], np.arange(free, free + mb - n)]))
        free += mb - n
    table = jnp.asarray(np.stack(table), jnp.int32)
    lengths = jnp.asarray([s[2] for s in slots], jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (free, bs, kv, d), jnp.float32)
    v = jax.random.normal(ks[2], (free, bs, kv, d), jnp.float32)
    chosen = (jax.random.uniform(ks[3], (b, mb * bs)) < 0.1
              ).at[:, 0].set(True)
    if case == "chose_none":
        chosen = chosen.at[1, 32:544].set(False)
    bias = jnp.where(chosen, 0.0, -jnp.inf)
    got = paged_decode_attention(q, k, v, table, lengths, bias=bias,
                                 interpret=True, pages_per_block=pages)
    want = gather_reference(q, k, v, table, lengths, bias=bias)
    live = np.asarray(lengths) > 0      # (a slot of no key: zeros, unread)
    np.testing.assert_allclose(got[live], want[live], atol=3e-6)
    assert not np.asarray(got)[~live].any()
    # the plan the kernel walks and the host's booking are one arithmetic
    order, plan = shared_runs(table, lengths, bs, pages)
    start, n_live, shared, members = np.asarray(plan)[:4]
    rows = bs * (pages or 16)
    booked = streamed_rows(lengths, bs, mb, pages, table=table)
    assert booked == int((n_live - start).sum()) * rows
    each = streamed_rows(lengths, bs, mb, pages)
    runs = {"three_runs": [3, 2, 1], "all_slots": [18],
            "chose_none": [3]}.get(case)
    if case in ("none_shared", "one_page") or (
            pages is None and case in ("ragged_run", "edges")):
        assert booked == each and not shared.any()
        np.testing.assert_array_equal(order, np.arange(b))
    elif runs:
        assert sorted(members[members > 0], reverse=True) == runs
        assert booked == each - sum(
            (n - 1) * g * rows for n, g in zip(
                members[members > 0], shared[members > 0]))
        assert booked < each
    elif case == "ragged_run":           # 5 pages: 2 groups of 2, 1 of 3
        assert shared.tolist() == [5 // pages, 5 // pages, 0]
    else:                                # "edges": length 0 is in no run
        assert members.tolist() == [1, 3, 0, 0]
        assert shared.tolist() == [0] + [96 // rows] * 3


def test_the_index_kernel_scores_keys_of_half_a_row_of_lanes():
    """16 index heads of 64 over a pool whose rows are 64 values and 64
    zeros: the kernel (interpret mode) is its gather, and both are the
    scores of the 64 values alone."""
    b, hi, di, bs, mb = 2, 16, 64, 16, 9
    nb = b * mb + 1
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    pad = ((0, 0), (0, 0), (0, 64))
    q = jax.random.normal(ks[0], (b, hi, di), jnp.float32)
    keys = jax.random.normal(ks[1], (nb, bs, di), jnp.float32)
    w = jax.random.normal(ks[2], (b, hi), jnp.float32)
    table = jnp.arange(1, nb).reshape(b, mb).astype(jnp.int32)
    lengths = jnp.asarray([37, 144], jnp.int32)
    wide = paged_index.paged_index_scores(
        jnp.pad(q, pad), w, jnp.pad(keys, pad), table, lengths,
        interpret=True)
    np.testing.assert_allclose(wide, paged_index.gather_index_scores(
        jnp.pad(q, pad), w, jnp.pad(keys, pad), table, lengths), atol=1e-4)
    narrow = paged_index.gather_index_scores(q, w, keys, table, lengths)
    np.testing.assert_allclose(wide, narrow, atol=1e-4)
    assert np.isneginf(np.asarray(wide)[0, 37:]).all()


# ------------------------------------------------------------- the engine
def _engine(cfg, params, **kw):
    args = dict(max_slots=2, chunk=4, temperature=0.0, max_len=96,
                prefill_buckets=(32, 48, 64, 96), paged=True, block_size=8,
                prefill_chunk=16, attention_impl="pallas")
    args.update(kw)
    return InferenceEngine(cfg, {"params": params}, **args)


@pytest.fixture(scope="module")
def engines(cfg, params):
    built = {}

    def engine(**kw):
        key = tuple(sorted(kw.items()))
        if key not in built:
            built[key] = _engine(cfg, params, **kw)
        assert not built[key].has_work
        return built[key]

    return engine


def test_a_request_on_a_cached_document_answers_as_the_reference(
        cfg, params, engines):
    """A document that does NOT end on a block, so that the tail's first
    write copies the shared block (K, V and index keys alike); contexts of
    44-70 rows under a selection of 8: the tokens are a cold engine's, the
    reference's logits at every emitted token put it first, and the
    witness of a grouped-query layer holds the rows each query chose."""
    rng = np.random.RandomState(5)
    doc = rng.randint(0, 128, 44).astype(np.int32)
    tails = [rng.randint(0, 128, n).astype(np.int32) for n in (9, 21)]
    warm = engines()
    assert warm._pool_names == ("k_pool", "v_pool", "index_pool")
    assert warm._cache["index_pool"][0].shape[-1] == 128
    shared = warm.prefix_stats()["prefix_shared_tokens"]
    warm.add_request(doc, 1)
    warm.run()
    warm.watch(lambda req: req.prompt.size == 44 + 9)
    prompts = [np.concatenate([doc, t]) for t in tails]
    rids = [warm.add_request(p, 6) for p in prompts]
    hot = warm.run()
    warm.watch(None)
    # whole blocks of the 44-token document: 40 rows, or 32 where the
    # tail's first chunk starts on a chunk's edge
    assert warm.prefix_stats()["prefix_shared_tokens"] - shared >= 2 * 32
    cold = engines(prefix_sharing=False)
    for prompt, rid in zip(prompts, rids):
        crid = cold.add_request(prompt, 6)
        assert cold.run()[crid].tolist() == hot[rid].tolist()
        seq = np.concatenate([prompt, hot[rid]]).astype(np.int32)
        want, _ = reference_logits(cfg, params, seq)
        at = prompt.size - 1 + np.arange(6)
        deficit = want[at].max(-1) - want[at, np.asarray(hot[rid])]
        assert float(deficit.max()) < 1e-3
    st = warm.stats
    assert 0 < st.dsa_selected_ratio < 1 and st.attn_rows_selected > 0
    assert st.index_rows_scanned >= st.dsa_rows_live > st.attn_rows_selected
    # a table of 13 pages of 8 rows is ONE group of the kernel: every
    # forward copies it whole for each slot, shared document or not (the
    # pages two slots share are a whole group or more in
    # test_two_questions_on_one_document_stream_its_pages_once)
    assert st.kv_rows_streamed % (warm._max_blocks * 8) == 0
    assert st.kv_rows_streamed >= st.kv_rows_live > 0
    assert 0 < st.moe_picks_held < st.moe_picks
    assert warm._blockmgr.check_books()
    kinds = {e["kind"] for e in warm.witness_log}
    assert kinds == {"run", "decode"}
    for e in warm.witness_log:
        seen = e["seen"]
        if e["kind"] == "decode":      # [forwards, layers, topk + ties]
            rows = np.asarray(seen["rows"])
            assert rows.shape[1] == cfg.num_layers
            assert ((rows >= 0).sum(-1) == cfg.index_topk).all()
        else:
            assert np.asarray(seen["chosen_bits"]).shape[:2] == (
                cfg.num_layers, 16)
    warm.witness_log.clear()


def test_two_questions_on_one_document_stream_its_pages_once(
        cfg, params, monkeypatch):
    """A cached document of 65 pages of 8 rows, two groups of the decode
    kernel and a page: two questions that decode side by side give the
    tokens each gives alone, and the engine books the document's two
    groups ONCE a forward for both (``kv_rows_streamed`` under
    ``kv_rows_live``), by the arithmetic the kernel's plan is made of,
    in the stats and in the ``decode_chunk`` spans alike."""
    from dlrover_tpu.utils import profiler

    spans, books = [], []
    inner = profiler.span

    def span(name, **attrs):
        if name == "dlrover.engine.decode_chunk" and attrs:
            spans.append(attrs)
        return inner(name, **attrs)

    monkeypatch.setattr("dlrover_tpu.serving.engine.span", span)
    eng = _engine(dataclasses.replace(cfg, max_seq_len=600), params,
                  max_len=600, prefill_buckets=(600,), prefill_chunk=64)
    booking = eng._book_kv_rows

    def book(active, chunks=1):
        books.append((eng._positions[active].copy(),
                      eng._table_np[active].copy()))
        return booking(active, chunks)

    monkeypatch.setattr(eng, "_book_kv_rows", book)
    rng = np.random.RandomState(7)
    doc = rng.randint(0, 128, 523).astype(np.int32)
    prompts = [np.concatenate([doc, rng.randint(0, 128, n).astype(np.int32)])
               for n in (9, 21)]
    eng.add_request(doc, 1)
    eng.run()
    alone = []
    for prompt in prompts:
        rid = eng.add_request(prompt, 9)
        alone.append(eng.run()[rid].tolist())
    before = dataclasses.asdict(eng.stats)
    del spans[:], books[:]
    rids = [eng.add_request(prompt, 9) for prompt in prompts]
    both = eng.run()
    assert [both[rid].tolist() for rid in rids] == alone
    live = eng.stats.kv_rows_live - before["kv_rows_live"]
    streamed = eng.stats.kv_rows_streamed - before["kv_rows_streamed"]
    # the host's arithmetic, a dispatch and a forward at a time: groups of
    # 32 pages (256 rows) up to each length, less the whole groups of the
    # rows' common pages for the second slot of two
    want_live = want_streamed = shared_forwards = 0
    for positions, table in books:
        common = 0
        if len(table) == 2:
            differ = np.flatnonzero(table[0] != table[1])
            common = (differ[0] if differ.size else table.shape[1]) // 32
        for forward in range(1, eng.chunk + 1):
            lengths = positions + forward
            want_live += int(lengths.sum())
            want_streamed += 256 * (int((-(-lengths // 256)).sum()) - common)
            shared_forwards += common > 0
    assert shared_forwards >= eng.chunk and common == 2
    assert (live, streamed) == (want_live, want_streamed)
    assert streamed < live
    assert sum(a["kv_rows_streamed"] for a in spans) == streamed
    assert eng.stats.kv_stream_ratio == pytest.approx(
        eng.stats.kv_rows_streamed / eng.stats.kv_rows_live)
    assert eng._blockmgr.check_books()


def test_a_model_without_a_selection_traces_no_selected_attention():
    """``serving/model.py``'s decode forward under the kernel: the
    unmasked call and no other (``serve-batch-closed``'s program)."""
    from dlrover_tpu.ops.pallas.paged_attention import DECODE_ATTENTION
    from dlrover_tpu.serving import model as dense

    plain = LlamaConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    toks = jnp.zeros((2, 8), jnp.int32)
    variables = LlamaModel(plain).init(jax.random.PRNGKey(0), toks)
    sp = serving_params_from_llama(variables, plain)
    b, nb, bs, mb = 2, 12, 8, 5
    cache = {n: [jnp.zeros((nb, bs, plain.num_kv_heads, plain.head_dim_))
                 for _ in range(plain.num_layers)]
             for n in ("k_pool", "v_pool")}
    cache["table"] = jnp.asarray(
        np.arange(1, 1 + b * mb).reshape(b, mb), jnp.int32)
    text = str(jax.make_jaxpr(lambda c: dense.verify_step(
        sp, plain, c, toks[:, :1], jnp.asarray([19, 12], jnp.int32),
        attention_impl="pallas", kernel_interpret=True))(cache))
    assert DECODE_ATTENTION in text and SELECTED_ATTENTION not in text


# -------------------------------------------------- the file, the refusals
def test_the_file_keeps_the_published_widths_and_counts_its_parameters():
    with open(FILE) as f:
        conf = json.load(f)
    cfg = serve_sparse_gqa.model_config(conf, max_seq_len=33024)
    assert conf["parameters"]["total_as_run"] == cfg.num_params \
        == 852988928
    assert conf["parameters"]["layer_as_run"] == cfg.layer_params(
        cfg.layer_specs[0])
    published = LlamaConfig.keye_vl2_30b_a3b()
    assert conf["parameters"]["total_published"] == published.num_params \
        == 30640656384
    assert "keye_vl2_30b_a3b" in PRESETS
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_,
            cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk,
            cfg.expert_width, cfg.moe_top_k, cfg.num_experts) == (
        2048, 32, 4, 128, 16, 64, 2048, 768, 8, 128)
    assert set(conf["reduced"]) == {"num_hidden_layers", "num_experts",
                                    "vocab_size"}
    assert cfg.moe_experts_held == (0, 16) and cfg.vocab_size * 8 == 151936
    assert cfg.qk_norm and cfg.qk_norm_kind == "head" and cfg.layer_kinds
    # the indexer of a grouped-query layer is counted, from the hidden size
    bare = dataclasses.replace(published, index_topk=0)
    assert published.num_params - bare.num_params == 48 * 2261120


def test_what_is_still_missing_is_refused_by_name(cfg, params):
    variables = {"params": params}
    with pytest.raises(ValueError, match="no QK-norm of this kind.*"
                                         "'projection'"):
        serving_params_from_llama(
            variables, dataclasses.replace(cfg, qk_norm_kind="projection"))
    dense = LlamaConfig.tiny(index_n_heads=2, index_head_dim=8, index_topk=4)
    with pytest.raises(ValueError, match="served by the loop of layer kinds"):
        serving_params_from_llama(variables, dense)
    with pytest.raises(NotImplementedError, match="selection's own training"):
        LlamaModel(dense).init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))
    some = dataclasses.replace(cfg, layers=tuple(
        dataclasses.replace(s, indexer=i != 1)
        for i, s in enumerate(cfg.layer_specs)))
    with pytest.raises(ValueError, match="ONE kind of layer.*an indexer in "
                                         "some layers only"):
        serving_params_from_llama(variables, some)
    with pytest.raises(ValueError, match="latent layer's indexer"):
        serving_params_from_llama(variables, LlamaConfig.glm5(
            num_layers=1, q_lora_rank=0))


# ---------------------------------------------------------- the controls
@pytest.fixture(scope="module")
def unplanted(cfg, params):
    seq = np.random.RandomState(3).randint(0, 128, 24).astype(np.int32)
    logits, picked = reference_logits(cfg, params, seq,
                                      selection_of=(0, 24))
    return seq, np.asarray(logits), [np.asarray(p[1]) for p in picked]


@pytest.mark.parametrize("fault", sorted(controls_keye.FAULTS))
def test_every_planted_fault_moves_the_reference(fault, cfg, params,
                                                 unplanted, monkeypatch):
    """Each control changes what the comparison reads (the logits, or the
    rows chosen): planted in the reference at a tiny size, it is no longer
    the reference.  (``dims_of`` is what the driver calls; here the test
    reads the planted sizes the same way.)"""
    seq, logits, chosen = unplanted
    with controls_keye.FAULTS[fault]():
        d = ref.dims_of(config_of(cfg))
        x, picked = ref.hidden_states(
            seq, params.layer, params.top(), cfg.num_layers, d,
            selection_of=(0, 24))
        got = np.asarray(ref.head_logits(x, params.top(), cfg.rms_norm_eps))
    moved = float(np.abs(got - logits).max())
    rows = sum(int((np.asarray(p[1]) != c).sum())
               for p, c in zip(picked, chosen))
    if fault == "bf16_accumulation":
        # too fine for the picks of 24 tokens: the scores themselves
        q_i, w, k_i = (jnp.asarray(np.random.RandomState(i).randn(*shape),
                                   jnp.float32)
                       for i, shape in enumerate([(5, 4, 8), (5, 4), (9, 8)]))
        with controls_keye.FAULTS[fault]():
            planted = ref.index_scores(q_i, w, k_i)
        moved = float(np.abs(planted - ref.index_scores(q_i, w, k_i)).max())
    assert moved > 1e-4 or rows > 0, (fault, moved, rows)
    if fault in ("selection_off", "topk_2047", "index_key_not_rotated"):
        assert rows > 0
    # and the reference is itself again
    again, _ = reference_logits(cfg, params, seq)
    np.testing.assert_allclose(again, logits, atol=1e-6)


@pytest.mark.parametrize("fault", [None, "eighth_pick_dropped",
                                   "experts_swapped",
                                   "router_not_renormalised"])
def test_the_sparse_layer_check_sees_other_picks(fault, cfg, params):
    """(d) of the driver's comparison on the program's own sparse MLP:
    against the reference it holds with no token misrouted; against a
    reference that picks otherwise (one pick fewer, two held experts
    answering for each other) tokens are misrouted far from any tie, and
    against one that weighs otherwise the median token is off."""
    lp = params.layer(0)
    sp = serving_params_from_llama(
        {"params": {"layer_0": lp, "layer_1": params.layer(1),
                    **params.top()}}, cfg)
    h = jnp.asarray(np.random.RandomState(2).randn(1, 200, 64), jnp.float32)
    got, _ = latent.sparse_mlp(sp["layers"][0], h, cfg, jnp.float32,
                               jnp.ones((1, 200), bool))
    with controls_keye.FAULTS[fault]() if fault else contextlib.nullcontext():
        err = serve_sparse_gqa.sparse_layer_error(
            cfg, h[0], got[0], params.layer, ref.dims_of(config_of(cfg)))
    held = (err["misroute_gap_max"] <= serve_sparse_gqa.SPARSE_MISROUTE_GAP
            and err["routed_rel"] <= serve_sparse_gqa.SPARSE_ROUTED_REL)
    assert held == (fault is None), err
    if fault is None:
        assert err["misrouted"] == 0 and err["routed_tokens"] > 50
        assert err["routed_rel_max"] < 1e-4
    elif fault != "router_not_renormalised":
        assert err["misrouted"] > 5, err


def _loop_of(module):
    """``run``'s statements, without comments and docstrings."""
    tree = ast.parse(inspect.getsource(module.run))
    fn = tree.body[0]
    if isinstance(fn.body[0], ast.Expr) and isinstance(
            fn.body[0].value, ast.Constant):
        fn.body = fn.body[1:]
    return ast.unparse(fn).splitlines()


def test_the_drivers_loop_is_serve_sparses_loop():
    """``serve_sparse_gqa.run`` is ``serve_sparse.run`` COPIED (no file of
    the benchmark may be edited to take the model's hooks as arguments:
    PERF.md section 7): until one loop serves both, a repair of the
    window, of a rate or of the books made in one has to be made in the
    other, and this is where it shows.  What may differ is the model's own:
    its name in a message, the documents' time limit, the controls'
    module, and the builder's table by scope."""
    theirs, mine = _loop_of(serve_sparse), _loop_of(serve_sparse_gqa)
    differs = [line[0] + line[1:].strip() for line in difflib.unified_diff(
        theirs, mine, lineterm="", n=0)
        if line[:1] in "+-" and line[:3] not in ("+++", "---")]
    assert differs == [
        "-raise ValueError('the serve_sparse driver runs closed loops only')",
        "+raise ValueError('the serve_sparse_gqa driver runs closed loops "
        "only')",
        "-drain([router.submit(doc, 1) for doc in docs], 600.0)",
        "+drain([router.submit(doc, 1) for doc in docs], 900.0)",
        "-drain([router.submit(np.concatenate([doc, warm_rng.randint(0, "
        "cfg.vocab_size, chunk // 2 + 3 * i).astype(np.int32)]), "
        "int(eng['chunk']) + 2) for i, doc in enumerate(docs)], 600.0)",
        "+drain([router.submit(np.concatenate([doc, warm_rng.randint(0, "
        "cfg.vocab_size, chunk // 2 + 3 * i).astype(np.int32)]), "
        "int(eng['chunk']) + 2) for i, doc in enumerate(docs)], 900.0)",
        "-from perfbench import controls_glm5",
        "-checks['controls'] = controls_glm5.readings(ctx, kept, lambda "
        "keep=None: reference_check(cfg, params, ctx.config, seen, keep))",
        "+from perfbench import controls_keye",
        "+checks['controls'] = controls_keye.readings(ctx, kept, lambda "
        "keep=None: reference_check(cfg, params, ctx.config, seen, keep))",
        "+if trace and os.environ.get('PERFBENCH_SCOPES'):",
        "+from perfbench import device_scopes",
        "+reduced = device_scopes.of_run({'trace': trace})",
        "+if reduced is not None:",
        "+print(device_scopes.report(reduced), file=sys.stderr)",
    ], "\n".join(differs)
