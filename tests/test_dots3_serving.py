"""dots3-note-prev's layers (``dots3_note``) at a tiny size on the CPU,
float32, seeded, with EVERY mechanism of the published model: full latent
layers with an indexer beside window latent layers of their OWN geometry
(heads, nope size, latent rank, theta) in the published pattern (full,
full, then sliding x 3, full), the rescale behind the norms, the head
gate, a leading dense MLP, a shared expert, the selection bias, held
experts.  The plain reference (``perfbench/reference_dots3.py``, which
shares no code with ``serving/latent.py``) against the engine, LOGITS
compared; the window layers' rings when a sequence runs far past the
window, when a slot is reused, behind a shared prefix (warm, and cold
where the prefix's rows are not kept); the shares of a sparse layer
against the uncut layer; every refusal by its message; what the engine
books.

The rule of the serving test files (``tests/test_sparse_serving.py`` has
it whole): the config and the seeded params are module-scoped fixtures,
what several cases compute alike is computed once, and ONE engine is
built a module where the tests ask the same of it (``engine``); the
others differ in their slots, their attention path or their sharing, or
are compared FRESH against a used one."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.llama import (LayerSpec, LlamaConfig, LlamaModel,
                                      PRESETS, RopeSpec, layer_pattern)
from dlrover_tpu.serving import latent, paged
from dlrover_tpu.serving.engine import InferenceEngine
from dlrover_tpu.serving.params import serving_params_from_llama
from perfbench import reference_dots3 as ref
from perfbench.weights_dots3 import SeededDots3Params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 96
WINDOW = 9          # a query sees itself and 8 keys behind it: one block
TYPES = ("full_attention", "full_attention", "sliding_attention",
         "sliding_attention", "sliding_attention", "full_attention")


def tiny(**kw):
    full, sliding = RopeSpec(theta=8e7), RopeSpec(theta=5e4)
    layers = tuple(
        LayerSpec(num_heads=2, window=WINDOW, rope=sliding, kv_lora_rank=24,
                  qk_nope_head_dim=12, indexer=False,
                  mlp="sparse" if i else "dense")
        if t == "sliding_attention" else
        LayerSpec(num_heads=4, rope=full, mlp="sparse" if i else "dense")
        for i, t in enumerate(TYPES))
    base = dict(
        vocab_size=VOCAB, hidden_size=32, intermediate_size=48,
        num_layers=len(TYPES), num_heads=4, num_kv_heads=4, max_seq_len=256,
        q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, index_n_heads=2,
        index_head_dim=16, index_topk=12, num_experts=8, moe_top_k=2,
        moe_intermediate_size=16, moe_shared_width=16,
        moe_experts_held=(2, 4), dtype=jnp.float32,
        param_dtype=jnp.float32, layers=layers)
    base.update(kw)
    return LlamaConfig.dots3_note(**base)


def config_of(cfg):
    """``cfg`` under the keys of a configuration file that
    ``reference_dots3.dims_of`` reads."""
    first, held = cfg.moe_experts_held or (0, cfg.num_experts)
    full = next(s for s in cfg.layer_specs if not s.window)
    swa = next(s for s in cfg.layer_specs if s.window)
    return {
        "num_hidden_layers": cfg.num_layers, "layer_types": list(TYPES),
        "hidden_size": cfg.hidden_size, "q_lora_rank": cfg.q_lora_rank,
        "swa_q_lora_rank": cfg.q_lora_rank,
        "num_attention_heads": full.num_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "swa_qk_rope_head_dim": cfg.qk_rope_head_dim,
        "kv_lora_rank": cfg.kv_lora_rank, "v_head_dim": cfg.v_head_dim,
        "rope_theta": full.rope.theta,
        "swa_num_attention_heads": swa.num_heads,
        "swa_qk_nope_head_dim": swa.qk_nope_head_dim,
        "swa_kv_lora_rank": swa.kv_lora_rank,
        "swa_v_head_dim": cfg.v_head_dim, "swa_rope_theta": swa.rope.theta,
        "sliding_window_size": swa.window,
        "apply_mla_qkv_lora_rescale": cfg.mla_lora_rescale,
        "index_n_heads": cfg.index_n_heads,
        "index_head_dim": cfg.index_head_dim, "index_topk": cfg.index_topk,
        "rms_norm_eps": cfg.rms_norm_eps,
        "n_routed_experts_published": cfg.num_experts,
        "n_routed_experts": held, "experts_held": [first, held],
        "num_experts_per_tok": cfg.moe_top_k,
        "routed_scaling_factor": cfg.moe_routed_scale}


def dims(cfg, fault=None, **kw):
    return dict(ref.dims_of(config_of(cfg)), ring_rows=16, ring_block=8,
                fault=fault, **kw)


def reference_logits(cfg, params, seq, fault=None, keep=None, **kw):
    x = ref.hidden_states(seq, params.layer, params.top(), cfg.num_layers,
                          dims(cfg, fault, **kw), keep)
    return np.asarray(ref.head_logits(x, params.top(), cfg.rms_norm_eps))


@pytest.fixture(scope="module")
def cfg():
    return tiny()


@pytest.fixture(scope="module")
def params_of(cfg):
    """``params_of(seed)``: ``cfg``'s seeded params, made once a seed."""
    return functools.cache(lambda seed: SeededDots3Params(cfg, seed))


def _engine(cfg, params, impl="xla", **kw):
    base = dict(max_slots=3, chunk=4, temperature=0.0, eos_token=None,
                max_len=128, paged=True, block_size=8, cache_blocks=120,
                prefill_chunk=8, prefill_buckets=(128,),
                attention_impl=impl, seed=0, prefix_sharing=True)
    base.update(kw)
    return InferenceEngine(cfg, {"params": params}, **base)


@pytest.fixture(scope="module")
def engine(cfg, params_of):
    """The ONE engine of the tests that only serve through it."""
    return _engine(cfg, params_of(1))


def _drain(engine):
    done = {}
    while engine.has_work:
        for r in engine.step():
            done[r.rid] = r
    return done


def _witnessed_logits(engine, req):
    """{position: logits} the engine's own programs handed back for
    ``req``: the prompt's last chunk and every decode forward that fed a
    delivered token."""
    out = {}
    c = engine.prefill_chunk
    for e in engine.witness_log:
        if e["request"] is not req:
            continue
        got = np.asarray(e["seen"]["logits"])
        if e["kind"] == "run":
            if e["start"] + c >= req.prompt.size:
                out[req.prompt.size - 1] = got
        else:
            for j in range(got.shape[0]):
                if e["start"] + j <= req.prompt.size + len(req.output) - 2:
                    out[e["start"] + j] = got[j]
    return out


def _serve_one(engine, prompt, new):
    """``prompt`` through ``engine`` alone: (request, {position: logits})."""
    engine.witness_log.clear()
    engine.watch(lambda r: True)
    rid = engine.add_request(prompt, new)
    req = _drain(engine)[rid]
    engine.watch(None)
    return req, _witnessed_logits(engine, req)


def _against_reference(cfg, params, req, logits, atol=5e-5):
    seq = np.concatenate([req.prompt, np.asarray(req.output, np.int32)])
    want = reference_logits(cfg, params, seq)
    p = req.prompt.size
    assert sorted(logits) == list(range(p - 1, seq.size - 1))
    for pos, got in logits.items():
        np.testing.assert_allclose(got, want[pos], atol=atol)
    assert req.output == want[p - 1:-1].argmax(-1).tolist()


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(0, VOCAB, n).astype(np.int32)


# ------------------------------------------------------------ the model
def test_prefill_then_decode_through_the_engine_is_the_reference(
        cfg, params_of, engine):
    """Chunked prefill (8 a chunk: 5 chunks, the last of 5 tokens) then 30
    decode forwards, 67 positions against a window of 9 and rings of 16
    rows: every ring wraps four times.  Float32 on both sides, so the
    tolerance is that of two orders of summation (5e-5 on logits of ~1);
    an off-by-one window, a missing gate or a missing rescale moves them
    by 1e-2 or more (``test_every_planted_fault_moves_the_reference``)."""
    req, logits = _serve_one(engine, _prompt(0, 37), 30)
    _against_reference(cfg, params_of(1), req, logits)


def test_the_kernels_serve_what_the_gathers_serve(cfg, params_of):
    """``attention_impl="pallas"`` (interpret mode off the chip): the
    window layers' decode goes through ``mla_decode_attention`` under its
    window's name and mask, a chunk's through ``mla_prefill_attention``
    over the ring; the reference again, and the rows the decode streams
    are whole blocks (1.0 at a window of one block + 1 in blocks of 8
    would be 9 rows: it reads 16)."""
    engine = _engine(cfg, params_of(1), impl="pallas")
    req, logits = _serve_one(engine, _prompt(4, 21), 14)
    _against_reference(cfg, params_of(1), req, logits)
    s = engine.stats
    assert s.window_rows_streamed == 3 * 16 * s.decode_forwards
    assert 1.0 < s.window_stream_ratio <= 16 / 9 + 1e-9


def test_the_preset_is_the_published_model():
    """The preset's own count is ISSUE 47's arithmetic, the configuration
    file is a slice of it, and the file's ``parameters`` is held to
    ``num_params`` to the parameter."""
    full = LlamaConfig.dots3_note()
    assert "dots3_note" in PRESETS
    assert round(full.num_params / 1e9, 2) == 279.55
    assert layer_pattern(full.layer_specs) == (2, 4)
    kinds = [bool(s.window) for s in full.layer_specs]
    assert kinds.count(True) == 33 and kinds[:6] == [
        False, False, True, True, True, False]
    assert [full.layer_params(s) for s in full.layer_specs[:3]] == [
        356396800, 6208761856, 6155546880]
    with open(os.path.join(
            ROOT, "perfbench/configs/dots3-note-serve.json")) as f:
        config = json.load(f)
    from perfbench.drivers import serve_window

    cut = serve_window.model_config(config, max_seq_len=1024)
    assert cut.layer_specs == full.layer_specs[:6]
    assert config["parameters"]["total_as_run"] == cut.num_params \
        == 5011092992
    assert config["parameters"]["total_published"] == full.num_params
    assert config["layer_types"] == [
        "sliding_attention" if w else "full_attention" for w in kinds]
    assert config["layer_types_as_run"] == config["layer_types"][:6]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "dots3-note-serve")
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    window = next(s for s in cut.layer_specs if s.window)
    assert (latent.latent_row_width(cut), latent.latent_row_width(
        cut, window)) == (640, 1152)


def test_training_refuses_the_model_by_what_it_lacks(cfg):
    with pytest.raises(NotImplementedError, match="two geometries"):
        LlamaModel(cfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))


# ----------------------------------------------------- the window's cache
def test_the_rings_are_bounded_by_the_window_not_the_pool(cfg, params_of,
                                                          engine):
    """The window layers' bytes are ``slots x ring`` (+ the kept
    prefixes) whatever ``cache_blocks`` is, and a sequence that decodes
    far past the window never holds more than ``ring x block`` rows."""
    g = engine._blockmgr.windows.geometry
    assert g == paged.ring_geometry(WINDOW, 8, 8) and (
        g.ring, g.reach, g.keep) == (2, 2, 1)
    # never more than ``ceil((w - 1 + chunk) / block) + 1`` blocks
    for w, c, b in ((513, 512, 128), (513, 512, 96), (10, 6, 4), (9, 8, 8)):
        r = paged.ring_geometry(w, c, b)
        assert r.reach <= r.ring <= -(-(w - 1 + c) // b) + 1
    assert paged.ring_geometry(513, 512, 128).ring == 8
    other = _engine(cfg, params_of(1), cache_blocks=40)
    assert other.cache_nbytes_by_kind["window"] \
        == engine.cache_nbytes_by_kind["window"] \
        == 3 * (3 * 2 + 4 * 1) * 8 * 128 * 4    # rings + 4 kept, 3 layers
    assert other.cache_nbytes_by_kind["paged"] * 3 \
        == engine.cache_nbytes_by_kind["paged"]
    assert engine.cache_nbytes == sum(engine.cache_nbytes_by_kind.values())
    engine.stats.window_rows_resident_max = 0
    req, _ = _serve_one(engine, _prompt(5, 11), 60)
    assert len(req.output) == 60
    s = engine.stats
    assert 0 < s.window_rows_resident_max <= g.rows == 16
    assert s.window_rows_in_window <= s.window_rows_streamed


def test_a_reused_slot_gives_what_a_fresh_engine_gives(cfg, params_of,
                                                       engine):
    """A freed slot's ring is nobody's: behind other occupants (the
    module's engine has served several) a request reads what a fresh
    engine gives it, bit for bit."""
    prompt = _prompt(6, 19)
    used, used_logits = _serve_one(engine, prompt, 12)
    fresh, fresh_logits = _serve_one(_engine(cfg, params_of(1)), prompt, 12)
    assert used.output == fresh.output
    for pos, got in fresh_logits.items():
        np.testing.assert_array_equal(used_logits[pos], got)


def test_a_warm_start_behind_a_shared_prefix_equals_a_cold_run(
        cfg, params_of):
    """A request behind a prompt that was prefilled before starts WARM:
    the full layers share the prefix's blocks, the window layers get its
    last ``w - 1`` rows from the store.  Its logits are a cold run's (an
    engine with no sharing), and the reference's."""
    params = params_of(1)
    doc = _prompt(7, 48)                     # six blocks, six chunks
    tail = np.concatenate([doc, _prompt(8, 13)])
    warm = _engine(cfg, params)
    _serve_one(warm, doc, 1)
    req_w, logits_w = _serve_one(warm, tail, 10)
    assert (warm.stats.window_warm_starts,
            warm.stats.window_cold_fallbacks) == (1, 0)
    assert warm.prefix_stats()["prefix_shared_tokens"] == 48
    # (the warm run prefilled 2 chunks, not 8)
    assert warm.stats.prefill_chunks == 6 + 2
    cold = _engine(cfg, params, prefix_sharing=False)
    req_c, logits_c = _serve_one(cold, tail, 10)
    assert req_w.output == req_c.output
    for pos, got in logits_c.items():
        np.testing.assert_allclose(logits_w[pos], got, atol=2e-6)
    _against_reference(cfg, params, req_w, logits_w)


def test_a_shared_prefix_whose_window_rows_are_not_held_starts_cold(
        cfg, params_of):
    """The store holds four prefix ends at three slots and has no policy
    of its own: a full store keeps nothing new.  A request behind a
    prompt whose rows were not kept shares NO block (nothing is copied
    only to be overwritten), starts COLD at position 0 and is right."""
    params = params_of(1)
    engine = _engine(cfg, params)
    store = engine._blockmgr.windows
    assert store.snapshots == 4
    doc_a, doc_b = _prompt(9, 40), _prompt(10, 40)
    _serve_one(engine, doc_a, 1)
    for seed in (12, 13, 14):       # the store is full behind these
        _serve_one(engine, _prompt(seed, 24), 1)
    _serve_one(engine, doc_b, 1)    # ... and keeps nothing of this one
    s = engine.stats
    req, logits = _serve_one(
        engine, np.concatenate([doc_a, _prompt(15, 9)]), 6)
    assert (s.window_warm_starts, s.window_cold_fallbacks) == (1, 0)
    assert engine.prefix_stats()["prefix_shared_tokens"] == 40
    _against_reference(cfg, params, req, logits)
    chunks = s.prefill_chunks
    req, logits = _serve_one(
        engine, np.concatenate([doc_b, _prompt(17, 9)]), 6)
    assert (s.window_warm_starts, s.window_cold_fallbacks) == (1, 1)
    stats = engine.prefix_stats()
    assert (stats["prefix_shared_tokens"], stats["prefix_cow"]) \
        == (40, 0)
    assert s.prefill_chunks - chunks == 7       # all 49 tokens, from 0
    _against_reference(cfg, params, req, logits)
    assert engine._blockmgr.check_books()


def test_a_kept_prefix_end_goes_with_its_block():
    """A snapshot belongs to the committed block that ends at its
    boundary: held while the block is (a sequence stands behind the
    prefix), gone when the index evicts the block or a writer diverges
    it, and its entry is free for the next."""
    store = paged.WindowStore(paged.ring_geometry(9, 8, 8), 2, True)
    mgr = paged.BlockManager(6, 8, windows=store)   # 5 usable blocks
    doc = _prompt(20, 16)
    blocks, shared = mgr.alloc_sequence(doc, 16)
    assert shared == 0 and mgr.window_entry(blocks[1]) is not None
    assert mgr.window_entry(blocks[1]) is None       # it has its snapshot
    mgr.free_sequence(blocks)
    # behind the kept boundary a sequence shares the blocks
    behind, shared = mgr.alloc_sequence(
        np.concatenate([doc, _prompt(21, 5)]), 24)
    assert shared == 16 and behind[:2] == blocks
    assert store.lookup(behind[1]) is not None and store.cold_starts == 0
    mgr.free_sequence(behind)
    # behind the FIRST block alone nothing is kept: no block is shared
    short, shared = mgr.alloc_sequence(
        np.concatenate([doc[:8], _prompt(22, 12)]), 24)
    assert shared == 0 and store.cold_starts == 1
    mgr.free_sequence(short)
    # another prompt takes every block: the index lets the document go
    other, _ = mgr.alloc_sequence(_prompt(23, 40), 40)
    assert store.lookup(blocks[1]) is None and len(store._free) == 4
    mgr.free_sequence(other)
    assert mgr.check_books()


def test_requests_admitted_at_different_steps_equal_their_solo_runs(
        cfg, params_of, engine):
    """Three requests that share the engine, admitted steps apart (one
    prefilling while the others decode, a slot idle in between), each give
    the tokens of a run alone: a parked or idle slot's row is written
    nowhere, and no ring sees another's."""
    prompts = [_prompt(20, 29), _prompt(21, 9), _prompt(22, 17)]
    news = [15, 22, 9]
    solo = [_serve_one(engine, p, n)[0].output
            for p, n in zip(prompts, news)]
    rids = [engine.add_request(prompts[0], news[0])]
    done = {}
    for step in range(400):
        if step == 2:
            rids.append(engine.add_request(prompts[1], news[1]))
        if step == 5:
            rids.append(engine.add_request(prompts[2], news[2]))
        for r in engine.step():
            done[r.rid] = r
        if len(done) == 3:
            break
    assert [done[r].output for r in rids] == solo


# ------------------------------------------------------------- refusals
@pytest.mark.parametrize("kw, match", [
    (dict(speculative_k=4), "a window under drafts"),
    (dict(mesh=object()), "a mesh with window layers"),
    (dict(prefill_chunk=0), "prompts in chunks"),
])
def test_the_engine_refuses_what_cannot_be_right_yet(kw, match, cfg,
                                                     params_of):
    with pytest.raises(ValueError, match=match):
        _engine(cfg, params_of(1), **kw)


def test_the_blocks_refuse_a_bucketed_prefill_and_a_verify(cfg, params_of,
                                                           engine):
    sp = serving_params_from_llama({"params": params_of(1)}, cfg)
    toks = jnp.zeros((2, 4), jnp.int32)
    with pytest.raises(ValueError, match="chunked path"):
        latent.prefill(sp, cfg, toks, jnp.asarray([4, 4]))
    with pytest.raises(ValueError, match="a window under drafts"):
        latent.verify_step(sp, cfg, engine._cache, jnp.zeros((3, 4),
                           jnp.int32), jnp.zeros(3, jnp.int32))
    two = dataclasses.replace(cfg, layers=tuple(
        dataclasses.replace(s, window=5 if i == 3 else s.window)
        for i, s in enumerate(cfg.layer_specs)))
    with pytest.raises(ValueError, match="one geometry"):
        _engine(two, params_of(1))
    gqa = dataclasses.replace(cfg, kv_lora_rank=0, num_experts=0)
    with pytest.raises(ValueError, match="ONE kind of layer"):
        serving_params_from_llama({"params": params_of(1)}, gqa)


# --------------------------------------------------------------- shares
def test_the_shares_add_up_to_the_uncut_layer():
    """The eight shares' routed parts plus the shared expert counted once
    are the uncut reference's layer; the served share is its own."""
    cfg = tiny(moe_experts_held=None)
    params = SeededDots3Params(cfg, 9)
    m = params.layer(1)["mlp"]
    d = dims(cfg)
    x = jnp.asarray(np.random.RandomState(3).randn(24, cfg.hidden_size),
                    jnp.float32)
    whole = ref.mlp(x, m, d)
    shared = ref._swiglu(x, m["shared_gate"]["kernel"],
                         m["shared_up"]["kernel"],
                         m["shared_down"]["kernel"])
    parts = 0.0
    for first in range(8):
        stack = {k: (v[first:first + 1]
                     if k in ("w_gate", "w_up", "w_down") else v)
                 for k, v in m.items()}
        share = ref.mlp(x, stack, dict(d, first=first, held=1))
        parts = parts + (share - shared)
        cut = tiny(moe_experts_held=(first, 1))
        layers = {k: params[k] for k in params}
        layers["layer_1"] = dict(params.layer(1), mlp=stack)
        lp = serving_params_from_llama({"params": layers}, cut)["layers"][1]
        got, picks = latent.sparse_mlp(lp, x[None], cut, jnp.float32,
                                       jnp.ones((1, 24), bool))
        np.testing.assert_allclose(got[0], share, atol=1e-5)
        assert int(picks[0]) == 24 * 2
    np.testing.assert_allclose(parts + shared, whole, atol=1e-5)


# --------------------------------------------------------------- faults
@pytest.fixture(scope="module")
def unplanted(cfg, params_of):
    seq = _prompt(30, 70)
    return seq, reference_logits(cfg, params_of(1), seq)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_every_planted_fault_moves_the_reference(fault, cfg, params_of,
                                                 unplanted):
    """Each fault the benchmark's controls plant moves the reference's
    logits far beyond the engine's distance from it (5e-5): the comparison
    that passes the engine would fail a program with that fault.  (A cache
    row in float8 moves them least; the rest by tenths.)"""
    seq, want = unplanted
    got = reference_logits(cfg, params_of(1), seq, fault, missing=(40, 48))
    moved = float(np.abs(got - want).max())
    assert moved > (2e-3 if fault == "fp8_latent_rows" else 2e-2), moved
    if fault == "warm_start_without_window_rows":
        # ... and only behind the rows that are missing
        assert np.abs(got[:40] - want[:40]).max() == 0.0


def test_the_window_gauges_reach_the_scrape(engine):
    from dlrover_tpu.serving.router.metrics import RouterMetrics
    from dlrover_tpu.serving.router.replica import InferenceEngineAdapter
    from dlrover_tpu.utils.metric_registry import METRIC_HELP

    mine = InferenceEngineAdapter(engine).engine_metrics()
    kinds = engine.cache_nbytes_by_kind
    assert mine["window_cache_bytes"] == kinds["window"] > 0
    m = RouterMetrics()
    m.observe_engine_metrics([mine, {}])
    got = m.metrics()
    assert got["serving_window_cache_share"] == pytest.approx(
        kinds["window"] / engine.cache_nbytes)
    assert got["serving_window_stream_ratio"] == pytest.approx(
        engine.stats.window_stream_ratio) and got[
            "serving_window_stream_ratio"] >= 1.0
    assert RouterMetrics().metrics()["serving_window_stream_ratio"] == 0.0
    assert {"serving_window_stream_ratio",
            "serving_window_cache_share"} <= set(METRIC_HELP)


# ---------------------------------------------------------- the benchmark
def _rehearsal():
    from perfbench.harness import load_json, merged

    config = load_json(os.path.join(
        ROOT, "perfbench/configs/dots3-note-serve.json"))
    traffic = load_json(os.path.join(
        ROOT, "perfbench/traffic/mixed-closed-48.json"))
    return (merged(config, config["rehearse"]),
            merged(traffic, traffic["rehearse"]))


def test_the_schedule_is_the_files_and_replays():
    """One cycle: 64 draws, LONG and SHORT interleaved, 32 each, lengths
    inside the file's bounds; a seed permutes within groups of 8 and
    never redraws a length; the same seed replays byte for byte."""
    import itertools

    from perfbench.drivers import serve_window
    from perfbench.harness import load_json

    t = load_json(os.path.join(ROOT,
                               "perfbench/traffic/mixed-closed-48.json"))
    draws = serve_window.cycle_draws(t)
    assert len(draws) == 64
    assert [d >= 0 for d, _, _ in draws] == [True, False] * 32
    for d, p, o in draws:
        assert 128 <= o <= 512
        assert (64 <= p <= 512) if d >= 0 else (256 <= p <= 2048)
    assert len({d for d, _, _ in draws if d >= 0}) >= 4
    for seed in (0, 3000000019):
        got = list(itertools.islice(serve_window.schedule(t, seed), 128))
        for g in range(0, 128, 8):
            assert sorted((x.document, x.tail_len, x.output_len)
                          for x in got[g:g + 8]) == sorted(
                draws[g % 64:g % 64 + 8])
        assert serve_window.schedule_bytes(t, seed, 16) \
            == serve_window.schedule_bytes(t, seed, 16)
    assert serve_window.schedule_bytes(t, 1, 16) \
        != serve_window.schedule_bytes(t, 2, 16)


def test_the_drivers_check_passes_on_the_engine():
    """``drivers/serve_window.py``'s own comparison at the files'
    ``rehearse`` sizes: a LONG request warm behind a document and a SHORT
    one, watched through the engine's own programs, against the
    reference; then the same with each of two faults planted, which it
    has to refuse."""
    from perfbench.drivers import serve_window
    from perfbench.weights import fold_seed

    config, traffic = _rehearsal()
    eng = config["deployment"]["engine"]
    cfg = serve_window.model_config(config, max_seq_len=eng["max_len"])
    params = SeededDots3Params(cfg, 5)
    engine = InferenceEngine(
        cfg, {"params": params}, max_slots=eng["max_slots"],
        chunk=eng["chunk"], temperature=0.0, eos_token=None,
        max_len=eng["max_len"], prefill_buckets=(eng["max_len"],),
        paged=True, block_size=eng["block_size"],
        cache_blocks=eng["cache_blocks"],
        prefill_chunk=eng["prefill_chunk"], attention_impl="xla",
        seed=fold_seed(5))
    serve_window._poison(engine)
    doc = _prompt(40, 96)
    engine.add_request(doc, 1)
    _drain(engine)
    engine.watch(lambda r: True)
    # (one request is watched at a time: the SHORT one behind the LONG)
    for prompt, new in ((np.concatenate([doc, _prompt(41, 11)]), 9),
                        (_prompt(42, 30), 12)):
        engine.add_request(prompt, new)
        while engine.has_work:
            engine.step()
            serve_window._to_host(engine.witness_log, eng["prefill_chunk"])
    assert engine.stats.window_warm_starts == 1
    seen = serve_window.Witnessed(engine.witness_log, eng["prefill_chunk"])
    assert seen.watched == 2
    limits = serve_window.limits_of(traffic)
    got = serve_window.reference_check(cfg, params, config, seen, limits,
                                       [96])
    assert got["checked_lengths"] == [96 + 11 + 9, 30 + 12]
    assert got["checked_selections"] == 8, got
    for verdict in ("logits_match_reference", "tokens_match_reference",
                    "selection_matches_reference",
                    "full_output_matches_reference",
                    "window_output_matches_reference",
                    "warm_window_matches_reference"):
        assert got[verdict], (verdict, {
            k: v for k, v in got.items() if isinstance(v, float)})
    for fault, verdict in (
            ("warm_start_without_window_rows",
             "warm_window_matches_reference"),
            ("fp8_latent_rows", "full_output_matches_reference"),
            ("fp8_latent_rows", "window_output_matches_reference"),
            ("window_1026", "window_output_matches_reference"),
            ("no_gate", "logits_match_reference")):
        bad = serve_window.reference_check(cfg, params, config, seen, limits,
                                           [96], fault)
        assert not bad[verdict], (fault, bad)
