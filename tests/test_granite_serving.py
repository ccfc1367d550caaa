"""granite-4.0-h-small's layers (``granitemoehybrid``) at a tiny size on the
CPU, float32, seeded, with EVERY mechanism of the published model: Mamba-2
layers (convolution with bias, a scalar decay a head, the skip, the gate
inside the norm) on both sides of an un-rotated grouped-query attention
layer (layers 0-6 of the published pattern), softmax-routed experts and a
wider shared expert in every layer, held experts, the three multipliers
and the stated softmax scale, a tied embedding.  The plain reference (``perfbench/reference_granite.py``)
against the engine, LOGITS compared; a slot's state when the slot is
reused, idle or prefilling; the shares of a sparse layer against the uncut
layer; every refusal by its message; what the engine books; the
grouped-query block of the loop of layer kinds against ``serving/model.py``'s
own.  (The five older served models' programs stay pinned where they were:
``test_kimi_linear_serving.py`` (Kimi-Linear, sarvam), ``test_sarvam_serving.py``
(GLM-5), ``test_dots3_serving.py`` and ``test_sparse_serving.py`` (the
dense model).)

The rule of the serving test files (``tests/test_sparse_serving.py`` has
it whole): the config and the seeded params are module-scoped fixtures
(``cfg``, ``params_of(seed)``), what several cases compute alike is
computed once (``unplanted``), and an engine is built once where a test
asks the same of it again."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.llama import (LayerSpec, LlamaConfig, LlamaModel,
                                      PRESETS, layer_pattern)
from dlrover_tpu.serving import latent, model as dense
from dlrover_tpu.serving.engine import InferenceEngine
from dlrover_tpu.serving.params import serving_params_from_llama
from perfbench import controls_granite
from perfbench import reference_granite as ref
from perfbench.drivers import serve_ssm
from perfbench.weights import SeededParams
from perfbench.weights_granite import SeededGraniteParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 96


def tiny(**kw):
    base = dict(
        vocab_size=VOCAB, hidden_size=32, intermediate_size=16, num_layers=7,
        num_heads=4, num_kv_heads=2, head_dim=8, max_seq_len=512,
        ssm_heads=4, ssm_head_dim=8, ssm_state=16, num_experts=8,
        moe_top_k=3, moe_shared_width=24, moe_experts_held=(2, 4),
        attn_scale=1.0 / 16, dtype=jnp.float32, param_dtype=jnp.float32)
    base.update(kw)
    return LlamaConfig.granite_4_h_small(**base)


def config_of(cfg):
    """``cfg`` under the keys of a configuration file that
    ``reference_granite.dims_of`` reads."""
    first, held = cfg.moe_experts_held or (0, cfg.num_experts)
    return {
        "position_embedding_type": "nope", "mamba_n_groups": 1,
        "mamba_proj_bias": False, "mamba_conv_bias": True,
        "attention_bias": False, "tie_word_embeddings": True,
        "normalization_function": "rmsnorm", "hidden_act": "silu",
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "hidden_size": cfg.hidden_size,
        "attention_multiplier": cfg.attn_scale,
        "mamba_n_heads": cfg.ssm_heads, "mamba_d_head": cfg.ssm_head_dim,
        "mamba_d_state": cfg.ssm_state, "mamba_d_conv": cfg.ssm_conv,
        "layer_types": ["attention" if i % 10 == 5 else "mamba"
                        for i in range(40)],
        "rms_norm_eps": cfg.rms_norm_eps,
        "embedding_multiplier": cfg.embedding_mult,
        "residual_multiplier": cfg.residual_mult,
        "logits_scaling": 1.0 / cfg.logit_scale,
        "num_experts_published": cfg.num_experts, "num_local_experts": held,
        "experts_held": [first, held],
        "num_experts_per_tok": cfg.moe_top_k}


def dims(cfg):
    return ref.dims_of(config_of(cfg))


def reference_logits(cfg, params, seq, keep=None):
    d = dims(cfg)
    x = ref.hidden_states(seq, params.layer, params.top(), cfg.num_layers,
                          d, keep)
    return np.asarray(ref.head_logits(x, params.top(), d["eps"],
                                      d["logits_scaling"]))


@pytest.fixture(scope="module")
def cfg():
    return tiny()


@pytest.fixture(scope="module")
def params_of(cfg):
    """``params_of(seed)``: ``cfg``'s seeded params, made once a seed."""
    return functools.cache(lambda seed: SeededGraniteParams(cfg, seed))


def _engine(cfg, params, impl="xla", **kw):
    base = dict(max_slots=3, chunk=4, temperature=0.0, eos_token=None,
                max_len=400, paged=True, block_size=8, cache_blocks=200,
                prefill_chunk=128 if impl == "pallas" else 8,
                prefill_buckets=(400,), attention_impl=impl, seed=0,
                prefix_sharing=False)
    base.update(kw)
    return InferenceEngine(cfg, {"params": params}, **base)


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
    return req, _witnessed_logits(engine, req)


def _against_reference(cfg, params, req, logits, atol=2e-6):
    seq = np.concatenate([req.prompt, np.asarray(req.output, np.int32)])
    want = reference_logits(cfg, params, seq)
    p = req.prompt.size
    assert sorted(logits) == list(range(p - 1, seq.size - 1))
    for pos, got in logits.items():
        np.testing.assert_allclose(got, want[pos], atol=atol)
    assert req.output == want[p - 1:-1].argmax(-1).tolist()


# ------------------------------------------------------------ the model
def test_the_preset_is_the_published_model():
    """The preset's own count is ISSUE 50's arithmetic, the configuration
    file's ``parameters`` and the cut's 5.91 GB."""
    cfg = LlamaConfig.granite_4_h_small()
    assert "granite_4_h_small" in PRESETS
    assert [i for i, s in enumerate(cfg.layer_specs) if s.mixer == "attn"] \
        == [5, 15, 25, 35]
    assert layer_pattern(cfg.layer_specs) == (0, 10)
    assert all(s.mlp == "sparse" and s.rope.rotary_fraction == 0
               for s in cfg.layer_specs)
    mamba, attn = cfg.layer_specs[0], cfg.layer_specs[5]
    norms = 2 * cfg.hidden_size
    sparse = 72 * 9_437_184 + 4096 * 72 + 18_874_368
    assert cfg.layer_params(mamba) - norms - sparse == 102_286_976
    assert cfg.layer_params(attn) - norms - sparse == 41_943_040
    assert cfg.layer_params(mamba) == 800_941_696
    assert round(cfg.num_params / 1e9, 2) == 32.21
    assert cfg.layer_kinds and not LlamaConfig.tiny().layer_kinds
    with open(os.path.join(
            ROOT, "perfbench/configs/granite-4.0-h-small-serve.json")) as f:
        config = json.load(f)
    cut = serve_ssm.model_config(config, 5248)
    assert cut.num_params == config["parameters"]["total_as_run"] \
        == 2_955_758_208
    assert config["parameters"]["total_published"] == cfg.num_params
    assert cut.layer_params(cut.layer_specs[0]) == 291_333_760 \
        == config["parameters"]["mamba_layer_as_run"]
    assert cut.layer_params(cut.layer_specs[5]) == 230_989_824
    assert [s.mixer for s in cut.layer_specs] == ["ssm"] * 5 + ["attn"] \
        + ["ssm"] * 4
    assert (cut.attn_scale, cut.embedding_mult, cut.residual_mult,
            cut.logit_scale) == (1 / 128, 12.0, 0.22, 1 / 16)
    assert cut.moe_experts_held == (0, 18) and cut.tie_embeddings
    assert config["reduced"].keys() == {
        "num_hidden_layers", "num_local_experts", "vocab_size"}


def test_training_refuses_the_model_by_what_it_lacks(cfg):
    with pytest.raises(NotImplementedError, match="chunk kernel's backward"):
        LlamaModel(cfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(NotImplementedError, match="residual multiplier"):
        LlamaModel(LlamaConfig.tiny(residual_mult=0.5)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


# --------------------------------------------- engine against reference
@pytest.mark.parametrize("impl, lengths", [
    ("xla", (1, 7, 8, 9, 29)),
    ("pallas", (1, 127, 128, 129, 300)),
])
def test_prefill_then_decode_through_the_engine_is_the_reference(
        impl, lengths, cfg, params_of):
    """Prompts of 1, chunk - 1, chunk, chunk + 1 and several chunks, each
    decoded for two chunks and a bit: the logits of the prompt's last
    chunk and of every decode forward are the reference's full forward
    (the ``jnp`` recurrence at a chunk of 8; both kernels, interpreted, at
    a chunk of 128)."""
    params = params_of(3)
    engine = _engine(cfg, params, impl)
    rng = np.random.RandomState(0)
    for n in lengths:
        prompt = rng.randint(0, VOCAB, n).astype(np.int32)
        req, logits = _serve_one(engine, prompt, 10)
        _against_reference(cfg, params, req, logits)
    # every admission started from zeros, and only the decoding slot's
    # state was walked by the kernel
    s = engine.stats
    assert s.state_resets_total == len(lengths)
    assert s.state_stream_ratio == (1.0 if impl == "pallas" else 3.0)
    assert s.ssm_chunk_rows_real == sum(lengths) and not s.kda_chunk_rows_real
    if impl == "pallas":       # whole 128-token chunks up to the last token
        assert s.ssm_chunk_rows_padded == sum(
            -(-n // 128) * 128 for n in lengths)
    assert s.moe_picks and 0.3 < s.moe_held_share < 0.7


def test_a_reused_slot_gives_what_a_fresh_engine_gives(cfg, params_of):
    """The second request lands in the slot the first one left (one slot),
    whose state and convolution rows are the first one's last: the
    prompt's first chunk starts from zeros inside its own program."""
    params = params_of(5)
    rng = np.random.RandomState(1)
    first = rng.randint(0, VOCAB, 21).astype(np.int32)
    second = rng.randint(0, VOCAB, 13).astype(np.int32)
    used = _engine(cfg, params, max_slots=1)
    _serve_one(used, first, 9)
    assert float(jnp.abs(used._cache["ssm_state"][0]).max()) > 0
    got_req, got = _serve_one(used, second, 9)
    fresh_req, want = _serve_one(_engine(cfg, params, max_slots=1), second,
                                 9)
    assert got_req.output == fresh_req.output
    for pos in want:
        np.testing.assert_array_equal(got[pos], want[pos])


def test_a_poisoned_state_is_zeroed_by_the_first_chunk(cfg, params_of):
    """What the benchmark does in set-up: every slot's state and
    convolution rows and every K/V row LOUD before any request; the
    answers are the reference's."""
    params = params_of(5)
    engine = _engine(cfg, params)
    engine.warmup()
    serve_ssm._poison(engine)
    prompt = np.random.RandomState(2).randint(0, VOCAB, 19).astype(np.int32)
    req, logits = _serve_one(engine, prompt, 6)
    _against_reference(cfg, params, req, logits)


def test_requests_admitted_at_different_steps_equal_their_solo_runs(
        cfg, params_of):
    """Three requests admitted at different engine steps, so that each
    slot sits idle, prefills and decodes while the others do something
    else: every request's tokens are its solo run's.  An idle slot and a
    slot mid-prefill hold their state still through the others' decode
    forwards."""
    params = params_of(6)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, VOCAB, n).astype(np.int32)
               for n in (27, 9, 18)]
    alone = _engine(cfg, params)
    solo = [_serve_one(alone, p, 11)[0].output for p in prompts]
    engine = _engine(cfg, params)
    rids, done, step = [], {}, 0
    while len(done) < 3:
        if step in (0, 2, 5):
            rids.append(engine.add_request(prompts[len(rids)], 11))
        for r in engine.step():
            done[r.rid] = r
        step += 1
    assert [done[r].output for r in rids] == solo


def test_the_engine_counts_its_state_among_its_cache_bytes(cfg, params_of):
    engine = _engine(cfg, params_of(1))
    state = 3 * 4 * 8 * 16 * 4 * 6            # slots x H x P x N, 6 layers
    conv = 3 * 3 * (4 * 8 + 2 * 16) * 4 * 6
    pools = 2 * 200 * 8 * 2 * 8 * 4           # K and V, ONE attention layer
    assert len(engine._cache["k_pool"]) == len(engine._cache["v_pool"]) == 1
    assert engine.cache_nbytes_by_kind == {
        "paged": pools, "window": 0, "state": state + conv}


# ------------------------------------------------------------- refusals
@pytest.mark.parametrize("kw, match", [
    (dict(prefix_sharing=True),
     "prefix_sharing=True with state-space layers.*snapshot of recurrent"),
    (dict(speculative_k=4),
     "with state-space layers.*roll-back of recurrent state"),
    (dict(mesh=object()), "a mesh with state-space layers"),
    (dict(prefill_chunk=0), "state-space layers take their prompts in "
                            "chunks"),
    (dict(paged=False), "paged=True"),
])
def test_the_engine_refuses_what_cannot_be_right_yet(kw, match, cfg,
                                                     params_of):
    with pytest.raises(ValueError, match=match):
        _engine(cfg, params_of(1), **kw)


def test_the_blocks_refuse_what_is_still_missing(cfg, params_of):
    params = params_of(1)
    sp = serving_params_from_llama({"params": params}, cfg)
    toks = jnp.zeros((2, 4), jnp.int32)
    with pytest.raises(ValueError, match="chunked path"):
        latent.prefill(sp, cfg, toks, jnp.asarray([4, 4]))
    engine = _engine(cfg, params)
    with pytest.raises(ValueError, match="already advanced"):
        latent.verify_step(sp, cfg, engine._cache, jnp.zeros((3, 4),
                           jnp.int32), jnp.zeros(3, jnp.int32))
    with pytest.raises(ValueError, match="no QK-norm"):
        serving_params_from_llama({"params": params},
                                  dataclasses.replace(cfg, qk_norm=True))

    def layers(**kw):
        return dataclasses.replace(cfg, layers=tuple(
            dataclasses.replace(s, **kw) if i == 5 else s
            for i, s in enumerate(cfg.layer_specs)))

    for bad in (layers(window=8),
                layers(rope=dataclasses.replace(cfg.rope,
                                                rotary_fraction=0.5)),
                dataclasses.replace(cfg, attn_head_gate=True)):
        with pytest.raises(ValueError, match="ONE kind of layer.*Missing "
                                             "behind the grouped-query"):
            serving_params_from_llama({"params": params}, bad)
    with pytest.raises(ValueError, match="no served mixer"):
        serving_params_from_llama(
            {"params": params}, dataclasses.replace(cfg, layers=tuple(
                LayerSpec(num_heads=4, rope=cfg.layer_specs[0].rope,
                          mixer="rwkv", mlp="sparse")
                for _ in cfg.layer_specs)))
    with pytest.raises(ValueError, match="no int8 weights"):
        serving_params_from_llama({"params": params}, cfg, int8=True)
    with pytest.raises(ValueError, match="of one kind"):
        _engine(dataclasses.replace(cfg, layers=tuple(
            dataclasses.replace(s, mixer="kda") if i == 0 else s
            for i, s in enumerate(cfg.layer_specs))), params)


# ------------------------------------------- the grouped-query block
def test_the_loops_grouped_query_block_is_the_dense_models():
    """A dense grouped-query model (rotated, scale ``head_dim ** -0.5``)
    through the loop of layer kinds gives what ``serving/model.py``'s own
    loop gives, a prompt chunk and then decode forwards, ``jnp`` and the
    interpreted decode kernel: the block the state-space model's attention
    layer runs is the dense model's, rotation included."""
    cfg = LlamaConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    assert not cfg.layer_kinds
    variables = {"params": SeededParams(cfg, 4)}
    sp = serving_params_from_llama(variables, cfg)
    from dlrover_tpu.serving.params import _latent_params

    kinds = _latent_params(variables, cfg, jnp.float32)
    b, nb, bs, mb = 2, 12, 8, 5
    pools = {n: [jnp.zeros((nb, bs, cfg.num_kv_heads, cfg.head_dim_))
                 for _ in range(cfg.num_layers)]
             for n in ("k_pool", "v_pool")}
    table = jnp.asarray(np.arange(1, 1 + b * mb).reshape(b, mb), jnp.int32)
    one, two = dict(pools, table=table), dict(pools, table=table)
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 256, (b, 19)))
    kw = dict(slots=jnp.arange(b), logits_index=jnp.asarray([18, 11]))
    want, one = dense.verify_step(sp, cfg, one, toks, jnp.zeros(b, jnp.int32),
                                  **kw)
    got, two = latent.verify_step(kinds, cfg, two, toks,
                                  jnp.zeros(b, jnp.int32), **kw)
    np.testing.assert_allclose(got, want, atol=2e-5)
    pos = jnp.asarray([19, 12], jnp.int32)
    for impl in ("xla", "pallas"):
        nxt = jnp.asarray([[3], [5]], jnp.int32)
        want, _ = dense.verify_step(sp, cfg, one, nxt, pos,
                                    attention_impl=impl,
                                    kernel_interpret=True)
        got, _ = latent.verify_step(kinds, cfg, two, nxt, pos,
                                    attention_impl=impl,
                                    kernel_interpret=True)
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_decode_forward_traces_the_unmasked_kernel_alone(cfg, params_of):
    """The one grouped-query layer of ``serve-rag-ssm``'s decode program
    calls ``paged_decode_attention`` with no bias: the kernel under a
    selection (``paged_selected_attention``, which streams a shared run of
    pages once) is no part of it."""
    from dlrover_tpu.ops.pallas.paged_attention import (DECODE_ATTENTION,
                                                        SELECTED_ATTENTION)

    engine = _engine(cfg, params_of(3), "pallas")
    text = str(jax.make_jaxpr(lambda c: latent.verify_step(
        engine.params, cfg, c, jnp.zeros((3, 1), jnp.int32),
        jnp.asarray([19, 12, 5], jnp.int32), attention_impl="pallas",
        kernel_interpret=True))(engine._cache))
    assert DECODE_ATTENTION in text and SELECTED_ATTENTION not in text


def test_a_trained_sparse_model_is_served_behind_the_grouped_query_block():
    """What ROADMAP A1 asked for: a model ``LlamaModel`` TRAINS (rotated
    grouped-query attention, sparse experts, all of them held) goes
    through the loop of layer kinds from the trainer's own tree, and a run
    of queries over paged K/V rows gives the training forward's logits at
    every position; the experts' picks are counted."""
    cfg = LlamaConfig.tiny(num_experts=4, moe_top_k=2, dtype=jnp.float32,
                           param_dtype=jnp.float32)
    assert cfg.layer_kinds
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 19)))
    trainer = LlamaModel(cfg)
    variables = trainer.init(jax.random.PRNGKey(0), toks)
    want = trainer.apply(variables, toks)
    sp = serving_params_from_llama(variables, cfg)
    b, nb, bs, mb = 2, 12, 8, 5
    cache = {n: [jnp.zeros((nb, bs, cfg.num_kv_heads, cfg.head_dim_))
                 for _ in range(cfg.num_layers)]
             for n in ("k_pool", "v_pool")}
    cache.update(table=jnp.asarray(
        np.arange(1, 1 + b * mb).reshape(b, mb), jnp.int32),
        moe_picks=jnp.zeros(4, jnp.uint32))
    got, cache = dense.verify_step(sp, cfg, cache, toks,
                                   jnp.zeros(b, jnp.int32),
                                   slots=jnp.arange(b))
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert cache["moe_picks"].tolist() == [2 * 19 * 2 * cfg.num_layers] * 2 + [
        cfg.num_layers] * 2      # every expert held: one walk a layer
    with pytest.raises(ValueError, match="prompts in chunks"):
        InferenceEngine(cfg, variables, paged=True, max_len=64)


# --------------------------------------------------------------- shares
def test_the_shares_add_up_to_the_uncut_layer():
    """The four shares' routed parts plus the shared expert counted once
    are the uncut reference's layer; the served share is its own."""
    cfg = tiny(num_layers=1, moe_experts_held=None)
    params = SeededGraniteParams(cfg, 9)
    m = params.layer(0)["mlp"]
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
        cut = tiny(num_layers=1, moe_experts_held=(first, 2))
        layers = {k: params[k] for k in params}
        layers["layer_0"] = dict(params.layer(0), mlp=stack)
        lp = serving_params_from_llama({"params": layers}, cut)["layers"][0]
        got, picks = latent.sparse_mlp(lp, x[None], cut, jnp.float32,
                                       jnp.ones((1, 24), bool))
        np.testing.assert_allclose(got[0], share, atol=1e-5)
        assert int(picks[0]) == 24 * 3
    np.testing.assert_allclose(parts + shared, whole, atol=1e-5)


# ------------------------------------------------- the driver's own check
@pytest.fixture(scope="module")
def seen(cfg, params_of):
    """What the engine's programs hand back for two watched requests, one
    behind the other in one slot, as the driver keeps it."""
    engine = _engine(cfg, params_of(7), max_slots=1)
    rng = np.random.RandomState(8)
    engine.watch(lambda req: True)
    for n in (19, 30):
        engine.add_request(rng.randint(0, VOCAB, n).astype(np.int32), 10)
        while engine.has_work:
            engine.step()
            serve_ssm._to_host(engine.witness_log, 8)
    return serve_ssm.Witnessed(engine.witness_log, 8)


_TIGHT = {name: 1e-4 for name in serve_ssm.LIMITS}


def test_the_drivers_check_passes_on_the_engine(cfg, params_of, seen):
    """``drivers/serve_ssm.py``'s comparison, on the CPU: the watched
    requests' logits AND the watched slot's recurrent state and convolution
    rows of the first and last Mamba-2 layer behind its last forward,
    against the reference."""
    with open(os.path.join(
            ROOT, "perfbench/traffic/rag-closed-192.json")) as f:
        traffic = json.load(f)
    assert serve_ssm.limits_of(traffic).keys() == set(serve_ssm.LIMITS)
    got = serve_ssm.reference_check(cfg, params_of(7), config_of(cfg), seen,
                                    _TIGHT)
    assert got["watched_requests"] == 2
    assert got["checked_positions"] == 2 * 10
    assert got["logit_rms_p90"] < 1e-6 and got["state_rel_first"] < 1e-5 \
        and got["state_rel_last"] < 1e-4 and got["conv_rel_last"] < 1e-4, got
    assert all(got[v] for v in controls_granite.VERDICTS)


@pytest.mark.parametrize("fault", sorted(controls_granite.FAULTS))
def test_every_planted_fault_fails_the_drivers_check(fault, cfg, params_of,
                                                     seen):
    """Each planted fault reads as NOT correct by the driver's own
    verdicts (at limits a float32 engine passes by four orders), and the
    reference is itself again behind it."""
    params = params_of(7)
    with controls_granite.FAULTS[fault]():
        bad = controls_granite.summary(serve_ssm.reference_check(
            cfg, params, config_of(cfg), seen, _TIGHT))
    assert not bad["correct"], bad
    if fault == "state_bf16":
        assert not bad["state_matches_reference"]
    again = controls_granite.summary(serve_ssm.reference_check(
        cfg, params, config_of(cfg), seen, _TIGHT))
    assert again["correct"], again
