"""Kimi-Linear-48B-A3B-Instruct's layers (``kimi_linear``) at a tiny size on
the CPU, float32, seeded, with EVERY mechanism of the published model:
KDA layers (convolution, channel-wise decay, delta rule, gated head norm)
beside un-rotated latent attention in the published 3 : 1, a leading
dense MLP, a shared expert, the selection bias, held experts.  The plain
reference (``perfbench/reference_kimi_linear.py``) against the engine,
LOGITS compared; a slot's state when the slot is reused, idle or
prefilling; the shares of a sparse layer against the uncut layer; every
refusal by its message; what the engine books; and sarvam-105b's served
programs, which the layer loop's dispatch must leave to the letter.

The rule of the serving test files (``tests/test_sparse_serving.py`` has
it whole): the config and the seeded params are module-scoped fixtures
(``cfg``, ``params_of(seed)``), what several cases compute alike is
computed once (``unplanted``), and an engine is built once where a test
asks the same of it again (the solo runs of
``test_requests_admitted_at_different_steps_equal_their_solo_runs``).
The other engines differ in their seed, their slots or their attention
path, or are compared FRESH against a used one, so each test builds its
own."""

import dataclasses
import functools
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.llama import (LayerSpec, LlamaConfig, LlamaModel,
                                      PRESETS, RopeSpec)
from dlrover_tpu.serving import latent
from dlrover_tpu.serving.engine import InferenceEngine
from dlrover_tpu.serving.params import serving_params_from_llama
from perfbench import controls_kimi_linear
from perfbench import reference_kimi_linear as ref
from perfbench.drivers import serve_linear
from perfbench.weights_kimi_linear import SeededKimiLinearParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 96


def tiny(**kw):
    base = dict(
        vocab_size=VOCAB, hidden_size=32, intermediate_size=48, num_layers=4,
        num_heads=2, num_kv_heads=2, max_seq_len=256, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, kda_heads=2,
        kda_head_dim=16, kda_rank=8, num_experts=8, moe_top_k=2,
        moe_intermediate_size=16, moe_shared_width=16,
        moe_experts_held=(2, 4), dtype=jnp.float32, param_dtype=jnp.float32)
    base.update(kw)
    return LlamaConfig.kimi_linear_48b(**base)


def config_of(cfg):
    """``cfg`` under the keys of a configuration file that
    ``reference_kimi_linear.dims_of`` reads."""
    first, held = cfg.moe_experts_held or (0, cfg.num_experts)
    return {
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "kv_lora_rank": cfg.kv_lora_rank, "v_head_dim": cfg.v_head_dim,
        "mla_use_nope": True, "q_lora_rank": None,
        "linear_attn_config": {
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11],
            "full_attn_layers": [4, 8, 12], "num_heads": cfg.kda_heads,
            "head_dim": cfg.kda_head_dim,
            "short_conv_kernel_size": cfg.kda_conv},
        "rms_norm_eps": cfg.rms_norm_eps,
        "num_experts_published": cfg.num_experts, "num_experts": held,
        "experts_held": [first, held], "num_shared_experts": 1,
        "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
        "num_expert_group": 1, "topk_group": 1,
        "num_experts_per_token": cfg.moe_top_k,
        "routed_scaling_factor": cfg.moe_routed_scale}


def dims(cfg):
    return ref.dims_of(config_of(cfg))


def reference_logits(cfg, params, seq, keep=None):
    x = ref.hidden_states(seq, params.layer, params.top(), cfg.num_layers,
                          dims(cfg), keep)
    return np.asarray(ref.head_logits(x, params.top(), cfg.rms_norm_eps))


@pytest.fixture(scope="module")
def cfg():
    return tiny()


@pytest.fixture(scope="module")
def params_of(cfg):
    """``params_of(seed)``: ``cfg``'s seeded params, made once a seed."""
    return functools.cache(lambda seed: SeededKimiLinearParams(cfg, seed))


def _engine(cfg, params, impl="xla", **kw):
    base = dict(max_slots=3, chunk=4, temperature=0.0, eos_token=None,
                max_len=256, paged=True, block_size=8, cache_blocks=120,
                prefill_chunk=64 if impl == "pallas" else 8,
                prefill_buckets=(256,), attention_impl=impl, seed=0,
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


def _against_reference(cfg, params, req, logits, atol=5e-5):
    seq = np.concatenate([req.prompt, np.asarray(req.output, np.int32)])
    want = reference_logits(cfg, params, seq)
    p = req.prompt.size
    assert sorted(logits) == list(range(p - 1, seq.size - 1))
    for pos, got in logits.items():
        np.testing.assert_allclose(got, want[pos], atol=atol)
    assert req.output == want[p - 1:-1].argmax(-1).tolist()


# ------------------------------------------------------------ the model
def test_the_preset_is_the_published_model():
    """The preset's own count is ISSUE 43's arithmetic, the configuration
    file's ``parameters`` and the cut's 6.35 GB."""
    cfg = LlamaConfig.kimi_linear_48b()
    assert "kimi_linear_48b" in PRESETS
    kinds = [s.mixer for s in cfg.layer_specs]
    assert [i + 1 for i, k in enumerate(kinds) if k == "attn"] == [
        4, 8, 12, 16, 20, 24, 27]
    kda, mla = cfg.layer_specs[1], cfg.layer_specs[3]
    norms = 2 * cfg.hidden_size
    sparse = 3 * 2304 * 1024 * 257 + 2304 * 256 + 256
    assert cfg.layer_params(kda) - norms - sparse == 39_514_272
    assert cfg.layer_params(mla) - norms - sparse == 29_114_880
    assert cfg.layer_params(cfg.layer_specs[0]) - norms - 39_514_272 \
        == 3 * 2304 * 9216
    assert round(cfg.num_params / 1e9, 1) == 49.1
    with open(os.path.join(
            ROOT, "perfbench/configs/kimi-linear-48b-serve.json")) as f:
        config = json.load(f)
    cut = serve_linear.model_config(config, 4224)
    assert cut.num_params == config["parameters"]["total_as_run"] \
        == 3_176_867_744
    assert config["parameters"]["total_published"] == cfg.num_params
    assert [s.mixer for s in cut.layer_specs] == ["kda"] * 3 + ["attn"] \
        + ["kda"] * 3 + ["attn"] + ["kda"] * 3 + ["attn"]
    assert [s.mlp for s in cut.layer_specs] == ["dense"] + ["sparse"] * 11
    assert cut.rope.rotary_fraction == 0


def test_no_rotation_passes_the_row_whole():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 8))
    pos = jnp.arange(10).reshape(2, 5)
    got = latent.rope_pairs(x, pos, RopeSpec(rotary_fraction=0.0), 8)
    assert jnp.array_equal(got, x)
    turned = latent.rope_pairs(x, pos, RopeSpec(), 8)
    assert not jnp.allclose(turned[:, 1:], x[:, 1:])


def test_training_refuses_the_model_by_what_it_lacks(cfg):
    with pytest.raises(NotImplementedError, match="chunk kernel's backward"):
        LlamaModel(cfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))


# --------------------------------------------- engine against reference
@pytest.mark.parametrize("impl, lengths", [
    ("xla", (1, 7, 8, 9, 29)),
    ("pallas", (1, 63, 64, 65, 131)),
])
def test_prefill_then_decode_through_the_engine_is_the_reference(
        impl, lengths, cfg, params_of):
    """Prompts of 1, chunk - 1, chunk, chunk + 1 and several chunks, each
    decoded for two chunks and a bit: the logits of the prompt's last
    chunk and of every decode forward are the reference's full forward
    (the ``jnp`` recurrence at a chunk of 8; both kernels, interpreted, at
    a chunk of 64)."""
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
    assert float(jnp.abs(used._cache["kda_state"][0]).max()) > 0
    got_req, got = _serve_one(used, second, 9)
    fresh_req, want = _serve_one(_engine(cfg, params, max_slots=1), second,
                                 9)
    assert got_req.output == fresh_req.output
    for pos in want:
        np.testing.assert_array_equal(got[pos], want[pos])


def test_a_poisoned_state_is_zeroed_by_the_first_chunk(cfg, params_of):
    """What the benchmark does in set-up: every slot's state and
    convolution rows LOUD before any request; the answers are the
    reference's."""
    params = params_of(5)
    engine = _engine(cfg, params)
    engine.warmup()
    serve_linear._poison(engine)
    prompt = np.random.RandomState(2).randint(0, VOCAB, 19).astype(np.int32)
    req, logits = _serve_one(engine, prompt, 6)
    _against_reference(cfg, params, req, logits)


def test_requests_admitted_at_different_steps_equal_their_solo_runs(
        cfg, params_of):
    """Three requests admitted at different engine steps, so that each
    slot sits idle, prefills and decodes while the others do something
    else: every request's tokens are its solo run's.  An idle slot and a
    slot mid-prefill hold their state still through the others' decode
    forwards.  The solo runs go one behind the other through ONE other
    engine (a reused slot starts from zeros: the test above)."""
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
    state = 3 * 2 * 16 * 16 * 4 * 3          # slots x heads x d x d, 3 layers
    conv = 3 * 3 * (3 * 2 * 16) * 4 * 3
    pool = 120 * 8 * latent.latent_row_width(cfg) * 4 * 1   # ONE MLA layer
    assert len(engine._cache["latent_pool"]) == 1
    assert engine.cache_nbytes == state + conv + pool


# ------------------------------------------------------------- refusals
@pytest.mark.parametrize("kw, match", [
    (dict(prefix_sharing=True), "snapshot of recurrent state"),
    (dict(speculative_k=4), "roll-back of recurrent state"),
    (dict(mesh=object()), "a mesh with linear-attention layers"),
    (dict(prefill_chunk=0), "prompts in chunks"),
])
def test_the_engine_refuses_what_cannot_be_right_yet(kw, match, cfg,
                                                     params_of):
    with pytest.raises(ValueError, match=match):
        _engine(cfg, params_of(1), **kw)


def test_the_blocks_refuse_a_bucketed_prefill_and_a_verify(cfg, params_of):
    params = params_of(1)
    sp = serving_params_from_llama({"params": params}, cfg)
    toks = jnp.zeros((2, 4), jnp.int32)
    with pytest.raises(ValueError, match="chunked path"):
        latent.prefill(sp, cfg, toks, jnp.asarray([4, 4]))
    engine = _engine(cfg, params)
    with pytest.raises(ValueError, match="already advanced"):
        latent.verify_step(sp, cfg, engine._cache, jnp.zeros((3, 4),
                           jnp.int32), jnp.zeros(3, jnp.int32))
    mixed = dataclasses.replace(cfg, layers=tuple(
        dataclasses.replace(s, window=8 if i == 3 else 0)
        for i, s in enumerate(cfg.layer_specs)))
    # (a window in a LATENT layer is served since PR 47: a ring a slot,
    # tests/test_dots3_serving.py; behind the grouped-query block it is
    # still refused, tests/test_olmoe_reference.py)
    assert len(serving_params_from_llama(
        {"params": params}, mixed)["layers"]) == cfg.num_layers
    with pytest.raises(ValueError, match="no served mixer"):
        serving_params_from_llama(
            {"params": params}, dataclasses.replace(cfg, layers=tuple(
                LayerSpec(num_heads=2, rope=cfg.rope, mixer="rwkv",
                          mlp=s.mlp) for s in cfg.layer_specs)))


# --------------------------------------------------------------- shares
def test_the_shares_add_up_to_the_uncut_layer():
    """The four shares' routed parts plus the shared expert counted once
    are the uncut reference's layer; the served share is its own."""
    cfg = tiny(num_layers=2, moe_experts_held=None)
    params = SeededKimiLinearParams(cfg, 9)
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
        layers = {k: params[k] for k in params}
        layers["layer_1"] = dict(params.layer(1), mlp=stack)
        lp = serving_params_from_llama({"params": layers}, cut)["layers"][1]
        got, picks = latent.sparse_mlp(lp, x[None], cut, jnp.float32,
                                       jnp.ones((1, 24), bool))
        np.testing.assert_allclose(got[0], share, atol=1e-5)
        assert int(picks[0]) == 24 * 2
    np.testing.assert_allclose(parts + shared, whole, atol=1e-5)


# ------------------------------------------------- the driver's own check
def _watched(cfg, params):
    # one slot: the two requests run one behind the other, both watched
    engine = _engine(cfg, params, max_slots=1)
    rng = np.random.RandomState(8)
    engine.watch(lambda req: True)
    for n in (19, 30):
        engine.add_request(rng.randint(0, VOCAB, n).astype(np.int32), 10)
        while engine.has_work:
            engine.step()
            serve_linear._to_host(engine.witness_log, 8)
    return serve_linear.Witnessed(engine.witness_log, 8)


def test_the_drivers_check_passes_on_the_engine(cfg, params_of):
    """``drivers/serve_linear.py``'s comparison, on the CPU: the watched
    requests' logits AND the watched slot's recurrent state of the first
    and last KDA layer behind its last forward, against the reference."""
    params = params_of(7)
    seen = _watched(cfg, params)
    with open(os.path.join(
            ROOT, "perfbench/traffic/reason-closed-192.json")) as f:
        limits = serve_linear.limits_of(json.load(f))
    got = serve_linear.reference_check(cfg, params, config_of(cfg), seen,
                                       limits)
    assert got["watched_requests"] == 2
    assert got["checked_positions"] == 2 * 10
    assert got["logit_rms_p90"] < 1e-4 and got["state_rel_first"] < 1e-5 \
        and got["state_rel_last"] < 1e-4, got
    assert got["logits_match_reference"] and got["state_matches_reference"]
    assert got["decay_rel"] < 1e-6 and got["decay_matches_reference"]
    # a decay computed in bfloat16 is caught on its own input, where the
    # state and the logits do not see it at the published widths
    with controls_kimi_linear.FAULTS["decay_bf16"]():
        bad = serve_linear.reference_check(cfg, params, config_of(cfg),
                                           seen, limits)
    assert bad["decay_rel"] > 10 * limits["DECAY_REL"]
    assert not bad["decay_matches_reference"]


@pytest.fixture(scope="module")
def unplanted(cfg, params_of):
    """The reference with no fault planted, once for all the faults:
    (sequence, its logits, what it kept)."""
    seq = np.random.RandomState(0).randint(0, VOCAB, 45).astype(np.int32)
    base = {}
    return seq, reference_logits(cfg, params_of(7), seq, base), base


@pytest.mark.parametrize("fault", sorted(controls_kimi_linear.FAULTS))
def test_every_planted_fault_moves_the_reference(fault, cfg, params_of,
                                                 unplanted):
    """The controls' faults change what the reference computes, and the
    reference is itself again behind them (on the chip each has to read
    as not correct by the driver's limits: PERF.md section 6)."""
    params = params_of(7)
    seq, want, base = unplanted
    keep = {}
    with controls_kimi_linear.FAULTS[fault]():
        got = reference_logits(cfg, params, seq, keep)
    again = reference_logits(cfg, params, seq)
    np.testing.assert_array_equal(again, want)
    assert np.abs(got[20:] - want[20:]).max() > 1e-4
    if fault in ("state_bf16", "decay_bf16", "no_delta_correction",
                 "no_conv"):
        s0, s1 = np.asarray(base["kda_states"][0]), np.asarray(
            keep["kda_states"][0])
        assert np.linalg.norm(s1 - s0) > 1e-3 * np.linalg.norm(s0)


# ------------------------------------- the other latent models' programs
# sarvam-105b's served programs as the PARENT of PR 43 traced them (the
# tiny preset of tests/test_sarvam_serving.py in bf16, paged pools, the
# kernels' options as the engine hands them): (lines, sha256 of the
# jaxpr's text).  GLM-5's are pinned by
# tests/test_sarvam_serving.py::test_glm5_traces_what_it_did.  Re-pinned in
# PR 51, as ``_KIMI`` below was: the experts' sorted buffer is
# ``sparse_mlp``'s compact one and ``moe_picks`` carries four counts.
_SARVAM = {
    "decode": (4696, "03bdd4066bc9a46840f3f4ecc5486befdba66f9e000a118381"
                     "48b3f32c9b934e"),
    "prefill_chunk": (5457, "25819b5b4d713342210f6e1ec3678a64ec4aee610439"
                            "d218ee3db547d01b8dff"),
    "prefill": (4099, "40fa95bff008e47b9fb40c4dfe3fd40f9b60d1fcbfeca68cec"
                      "2fa2a911e4c46b"),
}


def sarvam_program_text(program):
    from perfbench.weights_sarvam import SeededSarvamParams
    from tests.test_sarvam_serving import tiny as sarvam_tiny

    cfg = sarvam_tiny(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    sp = jax.eval_shape(lambda: serving_params_from_llama(
        {"params": SeededSarvamParams(cfg, 3)}, cfg))
    S = jax.ShapeDtypeStruct
    b, nb, bs, mb = 2, 9, 8, 4
    cache = {
        "latent_pool": [S((nb, bs, latent.latent_row_width(cfg)),
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
    return re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))


# Kimi-Linear's own served programs as the PARENT of PR 47 traced them (the
# tiny preset above in bf16): a layer's geometry, the rescale, the gate and
# the window are all chosen by what the model has, at trace time.
_KIMI = {
    "decode": (4830, "54930e0dcb2f24089a6c71b4f46fd3753e39dc13d2d945ad68bf"
                     "c15432f644c4"),
    "prefill_chunk": (20334, "82484019bd0b4bf2fcc45a5d0bfd2623d6fc38cd6003"
                             "ccf1f0b381fcf2497ff7"),
}


def kimi_program_text(program):
    from dlrover_tpu.serving.linear import state_shapes

    cfg = tiny(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    sp = jax.eval_shape(lambda: serving_params_from_llama(
        {"params": SeededKimiLinearParams(cfg, 3)}, cfg))
    S = jax.ShapeDtypeStruct
    b, nb, bs, mb = 2, 9, 8, 4
    kda = sum(s.mixer == "kda" for s in cfg.layer_specs)
    state, conv = (a.shape for a in state_shapes(cfg, b).values())
    cache = {
        "latent_pool": [S((nb, bs, latent.latent_row_width(cfg)),
                          jnp.bfloat16)] * (cfg.num_layers - kda),
        "kda_state": [S(state, jnp.float32)] * kda,
        "kda_conv": [S(conv, jnp.bfloat16)] * kda,
        "table": S((b, mb), jnp.int32), "moe_picks": S((4,), jnp.uint32),
        "watch_slot": S((), jnp.int32)}
    ints = lambda *shape: S(shape, jnp.int32)  # noqa: E731
    kernels = dict(attention_impl="pallas", kernel_interpret=True)
    if program == "decode":
        jaxpr = jax.make_jaxpr(
            lambda p, c, t, pos, act: latent.verify_step(
                p, cfg, c, t, pos, active=act, **kernels))(
            sp, cache, ints(b, 1), ints(b), S((b,), jnp.bool_))
    else:
        jaxpr = jax.make_jaxpr(
            lambda p, c, t, pos, sl, li: latent.verify_step(
                p, cfg, c, t, pos, slots=sl, logits_index=li, **kernels))(
            sp, cache, ints(1, 64), ints(1), ints(1), ints(1))
    return re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))


@pytest.mark.parametrize("model, program", [
    ("kimi", p) for p in sorted(_KIMI)] + [
    ("sarvam", p) for p in sorted(_SARVAM)])
def test_the_older_models_trace_what_they_did(model, program, tmp_path):
    """One pin for the served programs of the latent models this file can
    build (GLM-5's are ``tests/test_sarvam_serving.py
    test_glm5_traces_what_it_did``): to the letter what the parent of the
    PR that last meant to move them traced.  A change that means to move
    them, or a JAX that prints them otherwise, re-pins: the text is left
    in a file to diff."""
    pins, text_of = {"kimi": (_KIMI, kimi_program_text),
                     "sarvam": (_SARVAM, sarvam_program_text)}[model]
    text = text_of(program)
    (tmp_path / f"{model}.{program}.txt").write_text(text)
    got = (len(text.splitlines()), hashlib.sha256(text.encode()).hexdigest())
    assert got == pins[program], \
        f"jax {jax.__version__}; the trace: {tmp_path}/{model}.{program}.txt"
