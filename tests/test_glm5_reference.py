"""GLM-5's layer (``glm_moe_dsa``) at a tiny size on the CPU, float32,
seeded: the plain reference (``perfbench/reference_glm5.py``) against the
served blocks (``serving/latent.py``), the shares of a sparse layer
against the uncut layer, the router's selection bias, and the preset's
arithmetic."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import moe
from dlrover_tpu.models.llama import LlamaConfig, LlamaModel, RopeSpec
from dlrover_tpu.serving import latent
from dlrover_tpu.serving.params import serving_params_from_llama
from perfbench import reference_glm5 as ref
from perfbench.weights_glm5 import SeededGlm5Params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny(**kw):
    base = dict(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_layers=3,
        num_heads=4, num_kv_heads=4, max_seq_len=96, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=12, index_n_heads=8, index_head_dim=16, index_topk=8,
        num_experts=8, moe_top_k=2, moe_intermediate_size=32,
        moe_shared_width=32, moe_experts_held=(2, 3), moe_first_dense=1,
        dtype=jnp.float32, param_dtype=jnp.float32, rope_theta=1e4)
    base.update(kw)
    return LlamaConfig.glm5(**base)


def config_of(cfg):
    """``cfg`` under the keys of a configuration file that
    ``reference_glm5.dims_of`` reads."""
    first, held = cfg.moe_experts_held or (0, cfg.num_experts)
    return {
        "num_attention_heads": cfg.num_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "kv_lora_rank": cfg.kv_lora_rank, "v_head_dim": cfg.v_head_dim,
        "index_n_heads": cfg.index_n_heads,
        "index_head_dim": cfg.index_head_dim,
        "index_topk": cfg.index_topk,
        "rope_parameters": {"rope_theta": cfg.rope_theta},
        "rms_norm_eps": cfg.rms_norm_eps,
        "n_routed_experts_published": cfg.num_experts,
        "n_routed_experts": held, "experts_held": [first, held],
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
        "index_pool": [jnp.zeros((blocks, bs, cfg.index_head_dim))
                       for _ in range(cfg.num_layers)],
        "table": jnp.asarray(table),
        "moe_picks": jnp.zeros(4, jnp.uint32)}


def reference_logits(cfg, params, seq, **kw):
    x = ref.hidden_states(seq, params.layer, params.top(), cfg.num_layers,
                          dims(cfg), **kw)
    picked = None
    if kw:
        x, picked = x
    return ref.head_logits(x, params.top(), cfg.rms_norm_eps), picked


def test_absorbed_attention_is_the_unabsorbed_one():
    """One layer, no selection: the served block scores the latent row
    with ``W_kvb[K]^T q_nope`` and projects the attended latent; the
    reference makes every head's keys and values."""
    cfg = tiny(num_layers=1, index_topk=4096)
    params = SeededGlm5Params(cfg, 11)
    sp = serving_params_from_llama({"params": params}, cfg)
    seq = np.random.RandomState(0).randint(0, 128, 40).astype(np.int32)
    want, _ = reference_logits(cfg, params, seq)
    got, _ = latent.verify_step(
        sp, cfg, fresh_cache(cfg), jnp.asarray(seq[None]),
        jnp.zeros(1, jnp.int32), slots=jnp.zeros(1, jnp.int32))
    np.testing.assert_allclose(got[0], want, atol=1e-4)


def test_a_context_under_the_selection_is_causal_attention():
    cfg = tiny(num_layers=1)
    params = SeededGlm5Params(cfg, 5)
    seq = np.random.RandomState(1).randint(0, 128, 24).astype(np.int32)
    _, picked = reference_logits(cfg, params, seq, selection_of=(0, 24))
    scores, chosen = picked[0]
    t = np.arange(24)
    causal = t[None, :] <= t[:, None]
    # the first ``index_topk`` queries see no more keys than they may choose
    assert (np.asarray(chosen)[:8] == causal[:8]).all()
    assert (np.asarray(chosen).sum(-1)[8:] == 8).all()
    assert not np.asarray(chosen)[~causal].any()
    assert np.isneginf(np.asarray(scores)[~causal]).all()


def test_rope_rotates_adjacent_pairs():
    x = jnp.asarray(np.random.RandomState(2).randn(5, 3, 12), jnp.float32)
    pos = jnp.arange(5)
    y = np.asarray(ref.rope(x, pos, 1e4, 8))
    np.testing.assert_allclose(y[0], x[0], atol=1e-6)       # position 0
    np.testing.assert_allclose(y[..., 8:], x[..., 8:])      # the rest pass
    pairs = lambda a: (np.asarray(a)[..., :8].reshape(5, 3, 4, 2) ** 2
                       ).sum(-1)
    np.testing.assert_allclose(pairs(y), pairs(x), rtol=1e-5)
    np.testing.assert_allclose(
        latent.rope_pairs(x, pos, RopeSpec(theta=1e4), 8), y, atol=1e-6)
    # the first pair turns by the position itself
    np.testing.assert_allclose(
        y[1, 0, 0], x[1, 0, 0] * np.cos(1.0) - x[1, 0, 1] * np.sin(1.0),
        rtol=1e-5)


def test_selection_by_score_plus_bias_weights_by_score():
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0]], jnp.float32)
    sc = jax.nn.sigmoid(logits)
    plain = moe.route(logits, 2, "sigmoid", True, 2.5)
    assert plain[1].tolist() == [[0, 1]]
    bias = jnp.asarray([0.0, 0.0, 0.0, 1.0])
    w, e, _ = moe.route(logits, 2, "sigmoid", True, 2.5, bias)
    assert e.tolist() == [[3, 0]]           # chosen by score + bias
    picked = sc[0, jnp.asarray([3, 0])]     # weighted by the score alone
    np.testing.assert_allclose(w[0], picked / picked.sum() * 2.5,
                               rtol=1e-6)
    same = moe.route(logits, 2, "sigmoid", True, 2.5, jnp.zeros(4))
    np.testing.assert_allclose(same[0], plain[0])


@pytest.mark.parametrize("program", ["reference", "served"])
def test_the_shares_of_a_sparse_layer_add_up_to_the_uncut_layer(program):
    """Four shares of two experts each, the shared expert counted once,
    give what the layer with all eight experts gives."""
    whole = tiny(num_layers=2, moe_experts_held=None)
    layer = SeededGlm5Params(whole, 3).layer(1)
    x = jnp.asarray(np.random.RandomState(4).randn(10, 64), jnp.float32)
    d = dims(whole)
    uncut = ref.mlp(x, layer["mlp"], d, held=(0, 8))
    shared = ref._swiglu(x, *(layer["mlp"][n]["kernel"] for n in (
        "shared_gate", "shared_up", "shared_down")))
    total = shared
    for first in range(0, 8, 2):
        m = dict(layer["mlp"])
        for name in ("w_gate", "w_up", "w_down"):
            m[name] = layer["mlp"][name][first:first + 2]
        if program == "reference":
            part = ref.mlp(x, m, d, held=(first, 2))
        else:
            cfg = dataclasses.replace(whole, moe_experts_held=(first, 2))
            lp = serving_params_from_llama(
                {"params": {"layer_0": dict(layer, mlp=m),
                            **SeededGlm5Params(cfg, 3).top()}},
                dataclasses.replace(cfg, num_layers=1))["layers"][0]
            part, picks = latent.sparse_mlp(
                lp, x[None], cfg, jnp.float32, jnp.ones((1, 10), bool))
            part = part[0]
            assert int(picks[0]) == 20 and 0 <= int(picks[1]) <= 20
        total = total + (part - shared)
    np.testing.assert_allclose(total, uncut, atol=2e-5)


def test_the_preset_counts_what_the_model_has():
    cfg = LlamaConfig.glm5()
    assert cfg.num_layers == 78 and cfg.head_dim_ == 256
    assert [s.mlp for s in cfg.layer_specs[:4]] == ["dense"] * 3 + ["sparse"]
    assert abs(cfg.num_params / 1e9 - 743.9) < 0.1
    assert cfg.layer_params(cfg.layer_specs[0]) == 400898816
    cut = dataclasses.replace(
        cfg, num_layers=5, moe_first_dense=1, moe_experts_held=(0, 16),
        vocab_size=19360)
    assert cut.num_params == 3909632768
    assert latent.latent_row_width(cut) == 640    # 576 padded to lanes
    assert "glm5" in __import__(
        "dlrover_tpu.models.llama", fromlist=["PRESETS"]).PRESETS


def test_training_refuses_the_layer_by_what_it_lacks():
    cfg = tiny()
    with pytest.raises(NotImplementedError, match="latent"):
        LlamaModel(cfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))


def test_the_configuration_file_keeps_the_published_widths():
    with open(os.path.join(ROOT, "perfbench/configs/glm5-serve.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "glm5-serve")
    published = {
        "hidden_size": 6144, "intermediate_size": 12288,
        "moe_intermediate_size": 2048, "num_attention_heads": 64,
        "num_key_value_heads": 64, "q_lora_rank": 2048, "kv_lora_rank": 512,
        "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "qk_head_dim": 256,
        "v_head_dim": 256, "head_dim": 64, "index_n_heads": 32,
        "index_head_dim": 128, "index_topk": 2048, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "routed_scaling_factor": 2.5,
        "rms_norm_eps": 1e-5, "max_position_embeddings": 202752,
        "n_group": 1, "topk_group": 1, "moe_layer_freq": 1, "ep_size": 1}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["rope_parameters"] == {"rope_theta": 1000000,
                                         "rope_type": "default"}
    cut = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 16, "vocab_size": 19360,
           "num_nextn_predict_layers": 0}
    assert {k: config[k] for k in cut} == cut
    assert sorted(entry["reduced"]) == sorted(cut) == sorted(
        config["reduced"])
    assert config["n_routed_experts_published"] == 256
    assert "16" in config["deployment"]["stands_for"]
    from perfbench.drivers import serve_sparse  # noqa: F401  (importable)

    cfg = serve_sparse.model_config(config, 33024)
    assert cfg.num_params == config["parameters"]["total_as_run"]
