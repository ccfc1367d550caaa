"""sarvam-105b's layer (``sarvam_mla``) at a tiny size on the CPU, float32,
seeded, with EVERY mechanism of the published model: a query with no
bottleneck, the query-head norm, YaRN at positions past the original
length, a leading dense layer, a shared expert, the selection bias, held
experts.  The plain reference (``perfbench/reference_sarvam.py``) against
the served blocks (``serving/latent.py``) and the engine; the shares of a
sparse layer against the uncut layer; what the engine books.

The rule of the serving test files (``tests/test_sparse_serving.py`` has
it whole): the config and the seeded params are module-scoped fixtures
(``cfg``, ``params``, ``sp``), and an engine is built once where two
tests ask the same of it.  Here no two do: every engine below differs
from the others in its slots, its attention path or its context, and
each test reads its engine's books from their start, so each builds its
own."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.llama import LlamaConfig, LlamaModel, PRESETS
from dlrover_tpu.ops.pallas import mla_decode
from dlrover_tpu.serving import latent
from dlrover_tpu.serving.engine import InferenceEngine
from dlrover_tpu.serving.params import serving_params_from_llama
from perfbench import controls_sarvam
from perfbench import reference_sarvam as ref
from perfbench.drivers import serve_latent
from perfbench.weights_sarvam import SeededSarvamParams

# YaRN over 16 original positions: every test's context passes it
ORIGINAL = 16


def tiny(**kw):
    base = dict(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_layers=3,
        num_heads=4, num_kv_heads=4, max_seq_len=96, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=12,
        num_experts=8, moe_top_k=2, moe_intermediate_size=32,
        moe_shared_width=32, moe_experts_held=(2, 3),
        dtype=jnp.float32, param_dtype=jnp.float32)
    base.update(kw)
    cfg = LlamaConfig.sarvam_105b(**base)
    return dataclasses.replace(cfg, rope_scaling=dataclasses.replace(
        cfg.rope_scaling, yarn_original_max_len=ORIGINAL))


def config_of(cfg):
    """``cfg`` under the keys of a configuration file that
    ``reference_sarvam.dims_of`` reads."""
    first, held = cfg.moe_experts_held or (0, cfg.num_experts)
    y = cfg.rope_scaling
    return {
        "num_attention_heads": cfg.num_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "q_head_dim": cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
        "kv_lora_rank": cfg.kv_lora_rank, "v_head_dim": cfg.v_head_dim,
        "rope_theta": cfg.rope_theta, "use_qk_norm": True,
        "rope_scaling": {
            "type": "deepseek_yarn", "factor": y.yarn_factor,
            "original_max_position_embeddings": y.yarn_original_max_len,
            "beta_fast": y.yarn_beta_fast, "beta_slow": y.yarn_beta_slow,
            "mscale": 1, "mscale_all_dim": 1},
        "rms_norm_eps": cfg.rms_norm_eps,
        "num_experts_published": cfg.num_experts, "num_experts": held,
        "experts_held": [first, held], "num_shared_experts": 1,
        "moe_router_enable_expert_bias": True,
        "num_experts_per_tok": cfg.moe_top_k,
        "routed_scaling_factor": cfg.moe_routed_scale}


def dims(cfg):
    return ref.dims_of(config_of(cfg))


def fresh_cache(cfg, blocks=16, bs=8, slots=1):
    width = latent.latent_row_width(cfg)
    table = np.zeros((slots, blocks - 1), np.int32)
    table[0] = np.arange(1, blocks)
    return {
        "latent_pool": [jnp.zeros((blocks, bs, width))
                        for _ in range(cfg.num_layers)],
        "table": jnp.asarray(table),
        "moe_picks": jnp.zeros(4, jnp.uint32)}


def reference_logits(cfg, params, seq):
    x = ref.hidden_states(seq, params.layer, params.top(), cfg.num_layers,
                          dims(cfg))
    return ref.head_logits(x, params.top(), cfg.rms_norm_eps)


def _served(cfg, seed):
    params = SeededSarvamParams(cfg, seed)
    return params, serving_params_from_llama({"params": params}, cfg)


@pytest.fixture(scope="module")
def cfg():
    return tiny()


@pytest.fixture(scope="module")
def params(cfg):
    return SeededSarvamParams(cfg, 7)


@pytest.fixture(scope="module")
def sp(cfg, params):
    return serving_params_from_llama({"params": params}, cfg)


_PROGRAMS = {}


def _run(sp, cfg, cache, seq, start, **kw):
    step = jax.jit(lambda p, c, t, at: latent.verify_step(
        p, cfg, c, t, at, **kw))
    key = (cfg, len(seq), tuple(sorted(kw)), kw.get("attention_impl"))
    step = _PROGRAMS.setdefault(key, step)
    return step(sp, cache, jnp.asarray(seq[None]),
                jnp.asarray([start], jnp.int32))


def test_the_preset_is_the_published_model():
    cfg = LlamaConfig.sarvam_105b()
    assert "sarvam_105b" in PRESETS
    # attention 94.6 M a layer, the dense layer 296.0 M, a sparse layer
    # 3 342 M, 106.0 B in all (ISSUE 41's count from the config's keys)
    dense, sparse = (cfg.layer_params(cfg.layer_specs[i]) for i in (0, 1))
    assert round(dense / 1e6, 1) == 296.0 and round(sparse / 1e6) == 3342
    assert round(cfg.num_params / 1e9, 1) == 106.0
    assert (cfg.head_dim_, cfg.q_lora_rank, cfg.index_topk) == (192, 0, 0)
    assert cfg.attn_scale_mult == pytest.approx(
        (0.1 * np.log(40.0) + 1.0) ** 2)
    cut = LlamaConfig.sarvam_105b(num_layers=6, moe_experts_held=(0, 32),
                                  vocab_size=65536)
    assert round(cut.num_params / 1e6) == 5461        # 10.92 GB in bf16
    with pytest.raises(NotImplementedError, match="served only"):
        LlamaModel(tiny()).init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="latent-attention"):
        LlamaConfig.tiny(attn_scale_mult=2.0)


def test_yarn_frequencies_are_trainings_and_the_references():
    """One function gives training's and serving's frequencies
    (``models/llama.py rope_inverse_frequencies``); the reference has its
    own, from the config's keys."""
    from dlrover_tpu.models.llama import rope_inverse_frequencies

    for cfg in (tiny(), LlamaConfig.sarvam_105b()):
        got = rope_inverse_frequencies(cfg.rope, cfg.qk_rope_head_dim)
        want = ref.inverse_frequencies(dims(cfg))
        np.testing.assert_allclose(got, want, rtol=1e-6)
        plain = 1.0 / cfg.rope_theta ** (
            np.arange(0, cfg.qk_rope_head_dim, 2) / cfg.qk_rope_head_dim)
        # the fastest pair keeps its frequency, the slowest is divided
        assert got[0] == pytest.approx(plain[0])
        assert got[-1] == pytest.approx(plain[-1] / 40.0)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_and_decode_through_the_cache_are_the_reference(
        impl, cfg, params, sp):
    """Chunks of 16, then token by token (the decode path: the oracle,
    and the kernel interpreted): every position's logits are the
    reference's full forward, at positions past YaRN's original length."""
    seq = np.random.RandomState(0).randint(0, 128, 45).astype(np.int32)
    want = reference_logits(cfg, params, seq)
    cache = dict(fresh_cache(cfg), watch_slot=jnp.asarray(0, jnp.int32))
    kw = dict(attention_impl=impl, kernel_interpret=True)
    got = []
    for start in (0, 16):
        logits, cache = _run(sp, cfg, cache, seq[start:start + 16], start,
                             slots=jnp.zeros(1, jnp.int32), **kw)
        seen = cache.pop("witness")
        # a model with no selection hands back no rows: the slot's logits
        assert set(seen) == {"sparse_in", "sparse_out", "logits"}
        got.append(logits[0])
    for t in range(32, 45):
        logits, cache = _run(sp, cfg, cache, seq[t:t + 1], t, **kw)
        seen = cache.pop("witness")
        np.testing.assert_array_equal(seen["logits"], logits[0, 0])
        assert seen["sparse_in"].shape == (1, cfg.hidden_size)
        got.append(logits[0])
    got = np.concatenate(got)
    np.testing.assert_allclose(got, want, atol=2e-4)
    # every forward counted its picks: 45 tokens x 2 sparse layers x 2
    assert int(cache["moe_picks"][0]) == 45 * 2 * 2


def test_bucketed_prefill_is_the_reference(cfg):
    params, sp = _served(cfg, 3)
    seq = np.random.RandomState(1).randint(0, 128, 40).astype(np.int32)
    want = reference_logits(cfg, params, seq)
    padded = np.zeros(48, np.int32)
    padded[:40] = seq
    logits, rows, keys = jax.jit(
        lambda p, t, n: latent.prefill(p, cfg, t, n))(
        sp, jnp.asarray(padded[None]), jnp.asarray([40], jnp.int32))
    np.testing.assert_allclose(logits[0], want[39], atol=2e-4)
    assert len(rows) == cfg.num_layers and keys == []


@pytest.fixture(scope="module")
def unplanted(cfg, params):
    """The reference with no fault planted, once for all the faults:
    (sequence, its logits)."""
    seq = np.random.RandomState(0).randint(0, 128, 45).astype(np.int32)
    return seq, np.asarray(reference_logits(cfg, params, seq))


@pytest.mark.parametrize("fault", sorted(controls_sarvam.FAULTS))
def test_every_planted_fault_moves_the_reference(fault, cfg, params,
                                                 unplanted):
    """The controls' faults change what the reference computes (on the
    chip each has to read as not correct by the driver's limits:
    PERF.md section 6)."""
    seq, want = unplanted
    with controls_sarvam.FAULTS[fault]():
        got = np.asarray(reference_logits(cfg, params, seq))
    again = np.asarray(reference_logits(cfg, params, seq))
    np.testing.assert_array_equal(again, want)      # and it is unplanted
    assert np.abs(got[20:] - want[20:]).max() > 1e-3


def test_the_shares_add_up_to_the_uncut_layer():
    """The four shares' routed parts plus the shared expert counted once
    are the uncut reference's layer; the served share is its own."""
    cfg = tiny(num_layers=2, moe_experts_held=None)
    params = SeededSarvamParams(cfg, 9)
    m = params.layer(1)["mlp"]
    d = dims(cfg)
    x = jnp.asarray(np.random.RandomState(3).randn(24, cfg.hidden_size),
                    jnp.float32)
    whole = ref.mlp(x, m, d)
    shared = ref.shared_expert(x, m)
    parts = 0.0
    for first in range(0, 8, 2):
        stack = {k: (v[first:first + 2]
                     if k in ("w_gate", "w_up", "w_down") else v)
                 for k, v in m.items()}
        share = ref.mlp(x, stack, dict(d, first=first, held=2))
        parts = parts + (share - shared)
        cut = tiny(num_layers=2, moe_experts_held=(first, 2))
        lp = serving_params_from_llama(
            {"params": _Layers(cut, params, {1: dict(params.layer(1),
                                                      mlp=stack)})},
            cut)["layers"][1]
        got, picks = latent.sparse_mlp(lp, x[None], cut, jnp.float32,
                                       jnp.ones((1, 24), bool))
        np.testing.assert_allclose(got[0], share, atol=1e-5)
        assert int(picks[0]) == 24 * 2
    np.testing.assert_allclose(parts + shared, whole, atol=1e-5)


class _Layers(dict):
    """``params`` with some layers replaced."""

    def __init__(self, cfg, params, replaced):
        super().__init__({k: params[k] for k in params})
        for i, layer in replaced.items():
            self[f"layer_{i}"] = layer


def _engine(cfg, params, **kw):
    base = dict(max_slots=3, chunk=4, temperature=0.0, eos_token=None,
                max_len=96, paged=True, block_size=8, cache_blocks=40,
                prefill_chunk=16, prefill_buckets=(32, 48, 64, 96),
                attention_impl="xla", seed=0, prefix_sharing=True)
    base.update(kw)
    return InferenceEngine(cfg, {"params": params}, **base)


def _drain(engine, reqs):
    done = []
    while len(done) < reqs:
        done += engine.step()
    return sorted(done, key=lambda r: r.rid)


def test_engine_serves_it_behind_a_shared_prefix(cfg, params):
    """Through ``InferenceEngine``: a document prefilled once, then two
    questions behind it (the chunked warm start), greedy: every emitted
    token is the reference's argmax, teacher-forced, and the cache holds
    the document once."""
    engine = _engine(cfg, params)
    assert "index_pool" not in engine._cache
    rng = np.random.RandomState(5)
    doc = rng.randint(0, 128, 32).astype(np.int32)
    engine.add_request(doc, 1)
    _drain(engine, 1)
    tails = [rng.randint(0, 128, n).astype(np.int32) for n in (9, 21)]
    for tail in tails:
        engine.add_request(np.concatenate([doc, tail]), 6)
    done = _drain(engine, 2)
    assert engine.prefix_stats()["prefix_shared_tokens"] == 2 * 32
    for req in done:
        seq = np.concatenate([req.prompt, np.asarray(req.output, np.int32)])
        want = np.asarray(reference_logits(cfg, params, seq))
        at = req.prompt.size - 1 + np.arange(len(req.output))
        deficit = want[at].max(-1) - want[at, np.asarray(req.output)]
        assert deficit.max() < 1e-3, deficit
    st = engine.stats
    assert st.moe_picks and 0.2 < st.moe_held_share < 0.6
    assert (st.dsa_rows_live, st.index_rows_scanned) == (0, 0)
    # the oracle path books no streamed rows
    assert (st.kv_rows_live, st.kv_rows_streamed) == (0, 0)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_padded_last_chunk_attends_nothing_and_answers_the_same(impl):
    """A question of 6 and one of 36 tokens behind a document of 64, in
    chunk programs of 64 queries (two tiles of the prefill kernel's): the
    padding behind each last token attends nothing.  Greedy, the tokens
    are those of an engine that prefills each prompt whole (the bucketed
    program, which has no padding) and the reference's argmax; the books
    count the tiles with a real query."""
    cfg = tiny(max_seq_len=192)
    params = SeededSarvamParams(cfg, 7)
    rng = np.random.RandomState(9)
    doc = rng.randint(0, 128, 64).astype(np.int32)
    prompts = [np.concatenate([doc, rng.randint(0, 128, n).astype(np.int32)])
               for n in (6, 36)]
    sizes = dict(max_len=192, prefill_buckets=(96, 128), max_slots=2,
                 attention_impl=impl)
    engine = _engine(cfg, params, prefill_chunk=64, **sizes)
    engine.add_request(doc, 1)
    _drain(engine, 1)
    assert engine.stats.prefill_live_tile_share == 1.0   # a whole chunk
    for prompt in prompts:
        engine.add_request(prompt, 6)
    got = _drain(engine, 2)
    whole = _engine(cfg, params, prefill_chunk=0, prefix_sharing=False,
                    **sizes)
    for prompt in prompts:
        whole.add_request(prompt, 6)
    want = _drain(whole, 2)
    assert whole.stats.prefill_chunks == 0
    for req, cold in zip(got, want):
        assert req.output == cold.output and len(req.output) == 6
        seq = np.concatenate([req.prompt, np.asarray(req.output, np.int32)])
        logits = np.asarray(reference_logits(cfg, params, seq))
        at = req.prompt.size - 1 + np.arange(6)
        assert (logits[at].max(-1)
                - logits[at, np.asarray(req.output)]).max() < 1e-3
    st = engine.stats
    assert engine.prefix_stats()["prefix_shared_tokens"] == 2 * 64
    # the document's chunk and the two tails': 2 + 1 + 2 of 3 x 2 tiles
    assert (st.prefill_query_tiles, st.prefill_query_tiles_live) == (6, 5)
    assert st.prefill_live_tile_share == pytest.approx(5 / 6)
    # real ends 64, 70 and 100 in key blocks of 32 rows (the kernel's
    # 4 pages) or 64 (the loop's 8)
    assert st.prefill_key_blocks == (
        2 + 3 + 4 if impl == "pallas" else 1 + 2 + 2)


def test_engine_books_the_rows_the_kernel_streams(cfg, params, monkeypatch):
    """``kv_rows_streamed`` is the kernel's live page groups x their rows,
    and the decode chunk's span carries both counters."""
    from dlrover_tpu.utils import profiler

    spans = []
    inner = profiler.span

    def span(name, **attrs):
        if name == "dlrover.engine.decode_chunk" and attrs:
            spans.append(attrs)
        return inner(name, **attrs)

    monkeypatch.setattr("dlrover_tpu.serving.engine.span", span)
    engine = _engine(cfg, params, attention_impl="pallas", max_slots=2)
    rng = np.random.RandomState(6)
    for n in (19, 37):
        engine.add_request(rng.randint(0, 128, n).astype(np.int32), 5)
    _drain(engine, 2)
    st = engine.stats
    assert st.kv_rows_live and st.kv_rows_streamed >= st.kv_rows_live
    assert sum(a["kv_rows_live"] for a in spans) == st.kv_rows_live
    assert sum(a["kv_rows_streamed"] for a in spans) == st.kv_rows_streamed
    # whole groups of PAGES_PER_BLOCK pages of 8 rows up to each length
    rows = mla_decode.PAGES_PER_BLOCK * 8
    assert st.kv_rows_streamed % rows == 0
    # prompts of 19 and 37, one token from the prefill, then 4 forwards
    # at lengths n + 1 .. n + 4
    lengths = np.array([[n + j for j in range(1, 5)] for n in (19, 37)])
    assert st.kv_rows_live == int(lengths.sum())
    assert st.kv_rows_streamed == int((-(-lengths // rows) * rows).sum())
    assert st.kv_stream_ratio == pytest.approx(
        st.kv_rows_streamed / st.kv_rows_live)


def test_the_drivers_check_passes_on_the_engine_and_fails_on_a_fault(
        cfg, params):
    """``drivers/serve_latent.py``'s comparison, on the CPU: the engine's
    watched requests against the reference, and against the reference
    with each fault planted."""
    seen = serve_latent_watched(cfg, params)
    got = serve_latent.reference_check(cfg, params, config_of(cfg), seen)
    assert got["watched_requests"] == 2
    assert got["logit_rms_p90"] < 1e-4 and got["sparse_decode"][
        "mlp_rel"] < 1e-4, got
    with controls_sarvam.FAULTS["no_shared_expert"]():
        bad = serve_latent.reference_check(cfg, params, config_of(cfg),
                                           seen)
    assert bad["logit_rms_p90"] > 100 * got["logit_rms_p90"]
    assert bad["sparse_decode"]["mlp_rel"] > 0.05


def serve_latent_watched(cfg, params):
    # one slot: the two requests run one behind the other, both watched
    engine = _engine(cfg, params, max_slots=1)
    rng = np.random.RandomState(8)
    doc = rng.randint(0, 128, 32).astype(np.int32)
    engine.add_request(doc, 1)
    _drain(engine, 1)
    engine.watch(lambda req: req.prompt.size > doc.size)
    for n in (9, 14):
        engine.add_request(
            np.concatenate([doc, rng.randint(0, 128, n).astype(np.int32)]),
            7)
    _drain(engine, 2)
    serve_latent._to_host(engine.witness_log, 16)
    return serve_latent.Witnessed(doc, engine.witness_log, 1, 16)


# GLM-5's served programs, as the parent of PR 41 traced them (the tiny
# preset of tests/test_glm5_reference.py in bf16, paged pools, the
# kernels' options as the engine hands them): (lines, sha256 of the
# jaxpr's text).  ``prefill_chunk`` is PR 42's: its attention is told
# how many of the chunk's queries are the prompt's (``logits_index +
# 1``: the kernel's third scalar, the selection's mask of padded rows,
# trip counts from the last real query's position); ``decode`` is PR
# 44's: its selection is a threshold and a mask over the decode kernel
# (``_kth_largest``, a float32 bias as ``mla_decode_attention``'s fifth
# operand, the watched slot's mask row as positions) where it was
# ``top_k``, a gather and two einsums.  ALL THREE are PR 51's: the
# experts' sorted buffer is ``sparse_mlp``'s compact one, placed by
# counting and walked (no ``argsort``, no gather of ``T x top_k`` rows),
# and ``moe_picks`` carries four counts; nothing else in them moved.
_GLM5 = {
    "decode": (6346, "e67517283fd887fe4c76a2e213eb0dfc5f2c1e99a0ebb57e1565"
                     "dad87d3ae69d"),
    "prefill_chunk": (6582, "f2e975da3158689a8342e5e2355a75d8bde4bcee1a07"
                            "4b0a4b241cb6745ee158"),
    "prefill": (5056, "233fa462d5089b3d8b93c23260104424cd65a4470a8b539002"
                      "8bbaeaaf948227"),
}


@pytest.mark.parametrize("program", sorted(_GLM5))
def test_glm5_traces_what_it_did(program, tmp_path):
    """The decode forward, the prompt chunk and the bucketed prefill of a
    model WITH a selection are, to the letter, the programs the tree
    before this one traced (``serve-docqa-sparse`` compiles what it
    compiled): the query without a bottleneck, the query norm, the
    ``RopeSpec`` and the decode kernel are all chosen by what the model
    has, at trace time.  A change that means to move them, or a JAX that
    prints them otherwise, re-pins: the text is left in a file to diff."""
    import hashlib
    import re

    from perfbench.weights_glm5 import SeededGlm5Params
    from tests.test_glm5_reference import tiny as glm5_tiny

    cfg = glm5_tiny(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    sp = jax.eval_shape(lambda: serving_params_from_llama(
        {"params": SeededGlm5Params(cfg, 3)}, cfg))
    S = jax.ShapeDtypeStruct
    b, nb, bs, mb = 2, 9, 8, 4
    cache = {
        "latent_pool": [S((nb, bs, latent.latent_row_width(cfg)),
                          jnp.bfloat16)] * cfg.num_layers,
        "index_pool": [S((nb, bs, cfg.index_head_dim),
                         jnp.bfloat16)] * cfg.num_layers,
        "table": S((b, mb), jnp.int32), "moe_picks": S((4,), jnp.uint32),
        "watch_slot": S((), jnp.int32)}
    ints = lambda *shape: S(shape, jnp.int32)  # noqa: E731
    kernels = dict(attention_impl="pallas", kernel_interpret=True)
    if program == "decode":
        jaxpr = jax.make_jaxpr(
            lambda p, c, t, pos, act: latent.verify_step(
                p, cfg, c, t, pos, active=act, **kernels))(
            sp, cache, ints(b, 1), ints(b), S((b,), jnp.bool_))
    elif program == "prefill_chunk":
        jaxpr = jax.make_jaxpr(
            lambda p, c, t, pos, sl, li: latent.verify_step(
                p, cfg, c, t, pos, slots=sl, logits_index=li, **kernels))(
            sp, cache, ints(1, 16), ints(1), ints(1), ints(1))
    else:
        jaxpr = jax.make_jaxpr(
            lambda p, t, n: latent.prefill(p, cfg, t, n))(
            sp, ints(b, 16), ints(b))
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    (tmp_path / f"{program}.txt").write_text(text)
    got = (len(text.splitlines()), hashlib.sha256(text.encode()).hexdigest())
    assert got == _GLM5[program], \
        f"jax {jax.__version__}; the trace: {tmp_path / program}.txt"
