"""The LFM2-shaped model (gated short convolutions three to one beside
grouped-query layers with a QK-norm a head, in one scan over periods;
sigmoid-routed experts chosen under a selection bias, a share held; a tied
head) against the benchmark's plain reference,
``perfbench/reference_lfm2.py``, on seeded weights: tiny widths, float32,
on the CPU.

Tolerance 1e-5 (absolute on the loss; on a gradient 1e-5 of the leaf's
largest entry, and relative): both sides compute in float32 and differ only
in the order of their sums.  The driver's own comparison
(``perfbench/drivers/train_conv.py reference_check``) runs here too, right
and with each fault of ``perfbench/controls_lfm2.py`` planted.
"""

import dataclasses
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.llama import (LayerSpec, LlamaConfig, LlamaModel,
                                      layer_pattern)
from dlrover_tpu.models.moe import MoEMLP, route
from perfbench import controls_lfm2
from perfbench import reference_lfm2 as ref
from perfbench.drivers import train_conv
from perfbench.drivers.train_hybrid import layer_getter
from perfbench.harness import merged

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-5
TOP = ("embed_tokens", "final_norm")

with open(os.path.join(
        ROOT, "perfbench/configs/lfm2-8b-a1b-train.json")) as _f:
    CONF = json.load(_f)


def _tiny_conf(**over):
    """The configuration file at toy widths: its own ``rehearse`` block,
    which keeps the cut's pattern (a dense conv layer, then 3 x
    (attention, conv, conv, conv)), narrower still."""
    conf = merged(CONF, CONF["rehearse"])
    conf = merged(conf, {"hidden_size": 32, "intermediate_size": 48,
                         "head_dim": 8, "vocab_size": 96,
                         "moe_intermediate_size": 16,
                         "deployment": {"seq_len": 32, "remat": True}})
    return merged(conf, over)


def _loss_fn(model):
    def loss(params, ids):
        logits, _ = model.apply({"params": params}, ids,
                                mutable=["moe_losses"])
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
        return -jnp.take_along_axis(
            logp, ids[:, 1:, None], axis=-1).mean()

    return loss


def _reference_loss(conf, cfg, get_of):
    def loss(params, ids):
        return ref.lm_loss(
            np.asarray(ids), get_of(params), {k: params[k] for k in TOP},
            conf, tuple(cfg.moe_experts_held))["total"]

    return loss


@pytest.fixture(scope="module")
def tiny():
    conf = _tiny_conf()
    cfg = train_conv.conv_config(conf, max_seq_len=32)
    model = LlamaModel(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                             cfg.vocab_size).astype(jnp.int32)
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(0), ids)["params"])
    want = jax.value_and_grad(_reference_loss(
        conf, cfg, lambda p: layer_getter(p, cfg)[0]))(params, ids)
    return conf, cfg, params, ids, want


def test_preset_counts_the_published_parameters():
    """The arithmetic of ISSUE 55, by ``LlamaConfig.num_params``: the
    whole model, each kind of layer as run, the cut and its fallback, and
    the configuration file's ``parameters``."""
    full = LlamaConfig.lfm2_8b_a1b()
    assert full.num_params == 8_339_930_560
    assert dataclasses.replace(
        full, tie_embeddings=False).num_params == 8_474_148_288
    conv_dense, _, attn_sparse, conv_sparse = full.layer_specs[:4]
    assert full.layer_params(conv_dense) == 60_827_648
    assert full.layer_params(conv_sparse) == 369_174_560
    assert full.layer_params(attn_sparse) == 362_877_088
    assert layer_pattern(full.layer_specs) == (18, 3)

    def cut(depth):
        # the driver's config of the file: a run of the published layers
        # from layer 1 on
        return train_conv.conv_config(
            merged(CONF, {"num_hidden_layers": depth}), max_seq_len=8192)

    run = cut(13)
    assert layer_pattern(run.layer_specs) == (1, 4)
    assert [s.mixer for s in run.layer_specs] == \
        ["conv"] + ["attn", "conv", "conv", "conv"] * 3
    assert [s.mlp for s in run.layer_specs] == ["dense"] + ["sparse"] * 12
    assert run.layer_specs == full.layer_specs[1:14]
    assert cut(9).num_params == 921_256_448
    p = CONF["parameters"]
    assert p["published_total"] == full.num_params
    assert p["total_as_run"] == run.num_params == 1_334_692_608
    assert p["dense_conv_layer_as_run"] == run.layer_params(
        run.layer_specs[0])
    assert p["sparse_attention_layer_as_run"] == run.layer_params(
        run.layer_specs[1]) == 98_635_936
    assert p["sparse_conv_layer_as_run"] == run.layer_params(
        run.layer_specs[2]) == 104_933_408
    assert p["period_as_run"] == sum(
        map(run.layer_params, run.layer_specs[1:5])) == 413_436_160
    assert p["embedding_as_run"] == 16384 * 2048
    # but for its cut, the driver's config of the file IS the preset
    dep = CONF["deployment"]
    assert run == dataclasses.replace(
        full, layers=full.layer_specs[1:14], num_layers=13,
        moe_experts_held=tuple(dep["experts_held"]),
        vocab_size=CONF["vocab_size"], max_seq_len=8192, dtype=run.dtype,
        param_dtype=run.param_dtype, remat=run.remat,
        scan_layers=run.scan_layers, remat_policy=run.remat_policy,
        moe_select_bias_std=dep["select_bias_std"])


def test_the_file_holds_the_published_widths():
    cfg = LlamaConfig.lfm2_8b_a1b()
    published = {
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim_,
        "conv_L_cache": cfg.conv_taps,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "num_experts_per_tok": cfg.moe_top_k,
        "norm_topk_prob": cfg.moe_norm_topk_prob,
        "use_expert_bias": cfg.moe_select_bias,
        "routed_scaling_factor": cfg.moe_routed_scale,
        "rope_theta": cfg.rope_theta,
        "norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "max_position_embeddings": cfg.max_seq_len,
        "num_dense_layers": sum(s.mlp == "dense" for s in cfg.layer_specs),
        "layer_types": ["conv" if s.mixer == "conv" else "full_attention"
                        for s in cfg.layer_specs],
    }
    assert {k: CONF[k] for k in published} == published
    assert len(CONF["layer_types"]) == 24 and CONF["conv_bias"] is False
    assert list(CONF["reduced"]) == ["num_hidden_layers", "num_experts",
                                     "vocab_size"]
    dep = CONF["deployment"]
    assert (dep["experts_published"], dep["experts_held"],
            dep["chips_sharing_a_layer"], dep["first_layer"]) == (
                cfg.num_experts, [0, 8], 4, 1)
    assert CONF["num_experts"] == 8 and CONF["vocab_size"] == 16384 \
        and CONF["num_hidden_layers"] == 13


def test_pattern_and_parameter_tree(tiny):
    _, cfg, params, _, _ = tiny
    assert layer_pattern(cfg.layer_specs) == (1, 4)
    # tied: no lm_head
    assert set(params) == {"embed_tokens", "final_norm", "layer_0",
                           "periods"}
    assert set(params["layer_0"]) == {"conv", "input_norm", "mlp",
                                      "post_norm"}
    assert "gate_proj" in params["layer_0"]["mlp"]
    # a period's layers have DIFFERENT trees: attention, then three convs
    period = params["periods"]
    assert "attn" in period["layer_0"] and "conv" not in period["layer_0"]
    assert period["layer_0"]["attn"]["q_norm"]["scale"].shape == (3, 8)
    assert period["layer_0"]["attn"]["q_proj"]["kernel"].shape \
        == (3, 32, 4, 8)
    for j in (1, 2, 3):
        conv = period[f"layer_{j}"]["conv"]
        assert conv["in_proj"]["kernel"].shape == (3, 32, 3, 32)
        assert conv["taps"].shape == (3, 3, 32)
    mlp = period["layer_2"]["mlp"]
    assert mlp["w_gate"].shape == (3, 4, 32, 16)        # 4 of 16 held
    assert mlp["router"]["kernel"].shape == (3, 32, 16)
    assert mlp["select_bias"].shape == (3, 16)
    assert float(jnp.abs(mlp["select_bias"]).max()) > 0   # seeded
    assert "shared_gate" not in mlp
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == cfg.num_params


@pytest.mark.parametrize("form", ["scanned_remat", "unrolled"])
def test_loss_and_gradients_match_the_reference(tiny, form):
    conf, cfg, params, ids, (want_loss, want_grads) = tiny
    if form == "unrolled":
        cfg = dataclasses.replace(cfg, scan_layers=False, remat=False)
        get, _, _ = layer_getter(params, tiny[1])
        params = {**{k: params[k] for k in TOP},
                  **{f"layer_{i}": get(i) for i in range(cfg.num_layers)}}
        want_loss, want_grads = jax.value_and_grad(_reference_loss(
            conf, cfg, lambda p: lambda i: p[f"layer_{i}"]))(params, ids)
    got_loss, got_grads = jax.jit(jax.value_and_grad(
        _loss_fn(LlamaModel(cfg))))(params, ids)
    assert abs(float(got_loss) - float(want_loss)) < TOL
    flat_w = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    names = set()
    for path, g in jax.tree_util.tree_flatten_with_path(got_grads)[0]:
        scale = max(1.0, float(jnp.abs(flat_w[path]).max()))
        np.testing.assert_allclose(
            g, flat_w[path], atol=TOL * scale, rtol=TOL,
            err_msg=jax.tree_util.keystr(path))
        names.add(jax.tree_util.keystr(path[-2:]))
    assert {"['embed_tokens']['embedding']", "['conv']['taps']",
            "['in_proj']['kernel']", "['q_norm']['scale']",
            "['mlp']['w_down']", "['mlp']['select_bias']"} <= names
    # the tied embedding's gradient is the look-up's AND the head's
    untied = jax.grad(lambda p, ids: ref.lm_loss(
        np.asarray(ids), layer_getter(p, tiny[1])[0],
        {"embed_tokens": {"embedding": jax.lax.stop_gradient(
            p["embed_tokens"]["embedding"])}, "final_norm": p["final_norm"]},
        conf, tuple(cfg.moe_experts_held))["total"])(tiny[2], ids)
    assert float(jnp.abs(untied["embed_tokens"]["embedding"]).max()) == 0
    assert float(jnp.abs(
        got_grads["embed_tokens"]["embedding"]).max()) > 100 * TOL


def test_hidden_state_and_counts_match_the_reference(tiny):
    conf, cfg, params, ids, _ = tiny
    model = LlamaModel(cfg)
    hidden, sown = model.apply({"params": params}, ids, return_hidden=True,
                               mutable=["moe_losses"])
    get, lead, period = layer_getter(params, cfg)
    want = ref.lm_loss(np.asarray(ids), get, {k: params[k] for k in TOP},
                       conf, tuple(cfg.moe_experts_held))
    # 1e-5 of the state's scale (a normed row's largest entry is ~4)
    np.testing.assert_allclose(
        hidden, want["hidden"], rtol=TOL,
        atol=TOL * float(jnp.abs(want["hidden"]).max()))
    counts = train_conv.counts_in_layer_order(
        sown["moe_losses"], cfg, lead, period)
    assert np.array_equal(counts, np.asarray(want["counts"]))
    assert (counts.sum(-1) == ids.size * cfg.moe_top_k).all()
    # the seeded bias moves some picks, and not most of them
    moved = np.asarray(want["moved_by_bias"]) / (ids.size * cfg.moe_top_k)
    assert (moved > 0).all() and (moved < 0.5).all()


def test_the_reference_gradient_a_layer_at_a_time(tiny):
    """What the chip's comparison runs is ``jax.grad`` of ``lm_loss``."""
    conf, cfg, params, ids, (want_loss, want_grads) = tiny
    get, _, _ = layer_getter(params, cfg)
    want, _, _ = layer_getter(want_grads, cfg)
    seen = []

    def visit(i, grads):
        seen.append(i)
        expect = ({k: want_grads[k] for k in TOP} if i == "top"
                  else want(i))
        for (path, g), w in zip(
                jax.tree_util.tree_flatten_with_path(grads)[0],
                jax.tree_util.tree_leaves(expect)):
            np.testing.assert_allclose(
                g, w, atol=TOL * max(1.0, float(jnp.abs(w).max())),
                rtol=TOL, err_msg=f"{i}{jax.tree_util.keystr(path)}")

    got = ref.lm_loss_and_grads(
        np.asarray(ids), get, {k: params[k] for k in TOP}, conf,
        tuple(cfg.moe_experts_held), visit)
    assert seen == list(reversed(range(cfg.num_layers))) + ["top"]
    assert abs(float(got["total"]) - float(want_loss)) < 1e-6


def test_the_bias_changes_picks_and_never_a_weight():
    logits = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    bias = 0.03 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    plain_w, plain_e, _ = route(logits, 4, "sigmoid", True, 1.0)
    w, e, _ = route(logits, 4, "sigmoid", True, 1.0, select_bias=bias)
    assert not np.array_equal(np.sort(e, -1), np.sort(plain_e, -1))
    scores = np.asarray(jax.nn.sigmoid(logits))
    picked = np.take_along_axis(scores, np.asarray(e), -1)
    np.testing.assert_allclose(
        w, picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
    # a token whose picks the bias did not move keeps its weights
    same = (np.sort(e, -1) == np.sort(plain_e, -1)).all(-1)
    assert same.any() and not same.all()
    np.testing.assert_allclose(
        np.sort(np.asarray(w)[same], -1),
        np.sort(np.asarray(plain_w)[same], -1), rtol=1e-6)
    # ... and no gradient reaches it through the layer
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 16))
    layer = MoEMLP(hidden_size=16, intermediate_size=8, num_experts=8,
                   top_k=2, score_fn="sigmoid", select_bias=True,
                   select_bias_std=0.5, experts_held=(0, 4),
                   dtype=jnp.float32)
    params = nn.meta.unbox(layer.init(jax.random.PRNGKey(3), x))["params"]
    assert float(jnp.abs(params["select_bias"]).max()) > 0
    grads = jax.grad(lambda p: layer.apply(
        {"params": p}, x, mutable=["moe_losses"])[0].sum())(params)
    assert float(jnp.abs(grads["select_bias"]).max()) == 0
    assert float(jnp.abs(grads["router"]["kernel"]).max()) > 0


def test_the_qk_norm_a_head_is_not_the_whole_projections(tiny):
    """``qk_norm_kind``: "head" norms each head's values under one scale of
    ``head_dim``; "projection" norms a token's heads together under a scale
    of heads x head_dim.  The reference has the first; the second is off."""
    _, cfg, params, ids, (want_loss, _) = tiny
    head = params["periods"]["layer_0"]["attn"]
    assert head["q_norm"]["scale"].shape[-1] == cfg.head_dim_
    whole_cfg = dataclasses.replace(cfg, qk_norm_kind="projection")
    whole = nn.meta.unbox(LlamaModel(whole_cfg).init(
        jax.random.PRNGKey(0), ids)["params"])
    attn = whole["periods"]["layer_0"]["attn"]
    assert attn["q_norm"]["scale"].shape[-1] == cfg.num_heads * cfg.head_dim_
    assert attn["k_norm"]["scale"].shape[-1] \
        == cfg.num_kv_heads * cfg.head_dim_
    n = sum(x.size for x in jax.tree_util.tree_leaves(whole))
    assert n == whole_cfg.num_params
    # same weights (the scales are ones on both sides), another model
    for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
        attn[name] = head[name]
    swapped = dict(params, periods=dict(
        params["periods"], layer_0=dict(
            params["periods"]["layer_0"], attn=attn)))
    got = jax.jit(_loss_fn(LlamaModel(whole_cfg)))(swapped, ids)
    assert abs(float(got) - float(want_loss)) > 10 * TOL
    with pytest.raises(ValueError, match="qk_norm_kind"):
        dataclasses.replace(cfg, qk_norm_kind="a_head")


def test_the_four_shares_add_up_to_the_uncut_layer():
    """4 shares of 16 experts chosen under a bias: the routed parts of all
    shares are what the uncut reference gives for the whole layer (there
    is no shared expert to count once); a pick on an absent expert adds
    nothing, forward or backward."""
    t, m, w, e, k = 24, 32, 16, 16, 4
    x = jax.random.normal(jax.random.PRNGKey(2), (1, t, m), jnp.float32)
    conf = {"use_expert_bias": True, "num_experts_per_tok": k,
            "routed_scaling_factor": 1.0, "norm_topk_prob": True}

    def layer(held):
        return MoEMLP(hidden_size=m, intermediate_size=w, num_experts=e,
                      top_k=k, norm_topk_prob=True, score_fn="sigmoid",
                      select_bias=True, select_bias_std=0.1,
                      experts_held=held, dtype=jnp.float32)

    whole = nn.meta.unbox(layer(None).init(jax.random.PRNGKey(3), x))[
        "params"]

    def share_params(first, count):
        cut = dict(whole)
        for name in ("w_gate", "w_up", "w_down"):
            cut[name] = whole[name][first:first + count]
        return cut

    def apply(held, params, x):
        return layer(held).apply({"params": params}, x,
                                 mutable=["moe_losses"])[0]

    norm = {"scale": jnp.ones((m,))}
    xn = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))
    routed, counts, moved, elsewhere = ref.sparse_parts(
        xn[0], {"post_norm": norm, "mlp": whole}, 0.0, conf, (0, e))
    assert int(counts.sum()) == t * k and int(moved) > 0
    assert int(elsewhere) == 0
    parts = [apply((f, 4), share_params(f, 4), xn)[0] for f in range(0, e, 4)]
    np.testing.assert_allclose(sum(parts), routed, atol=TOL)
    np.testing.assert_allclose(apply(None, whole, xn)[0], routed, atol=TOL)
    for first in (0, 8):
        r, *_ = ref.sparse_parts(
            xn[0], {"post_norm": norm, "mlp": share_params(first, 4)}, 0.0,
            conf, (first, 4))
        np.testing.assert_allclose(parts[first // 4], r, atol=TOL)
    g_sys = jax.grad(lambda p: apply((4, 4), p, xn).sum())(
        share_params(4, 4))
    g_ref = jax.grad(lambda p: ref.sparse_parts(
        xn[0], {"post_norm": norm, "mlp": p}, 0.0, conf, (4, 4))[0].sum())(
            share_params(4, 4))
    for path, g in jax.tree_util.tree_flatten_with_path(g_sys)[0]:
        want = dict(jax.tree_util.tree_flatten_with_path(g_ref)[0])[path]
        np.testing.assert_allclose(g, want, atol=TOL, rtol=TOL,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("mixer", ["kda", "ssm"])
def test_a_recurrent_mixer_is_still_refused_in_training(mixer):
    cfg = LlamaConfig.tiny(layers=(
        LayerSpec(num_heads=4), LayerSpec(num_heads=4, mixer=mixer)))
    with pytest.raises(NotImplementedError,
                       match="the chunk kernel's backward"):
        LlamaModel(cfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))


@pytest.mark.parametrize("what,message", [
    ("conv", "a convolution state a slot"),
    ("qk_norm_a_head", "no QK-norm"),
])
def test_serving_refuses_what_it_lacks(tiny, what, message):
    from dlrover_tpu.serving.params import serving_params_from_llama

    _, cfg, params, _, _ = tiny
    if what == "qk_norm_a_head":     # attention layers alone, the norm on
        cfg = LlamaConfig.tiny(qk_norm=True, qk_norm_kind="head")
        params = LlamaModel(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    with pytest.raises(ValueError, match=message):
        serving_params_from_llama(params, cfg)


def test_the_engine_refuses_a_convolution_mixer(tiny):
    from dlrover_tpu.serving.engine import InferenceEngine

    _, cfg, params, _, _ = tiny
    with pytest.raises(ValueError, match="trained, not served"):
        InferenceEngine(cfg, params, max_slots=2, max_len=32,
                        prefill_chunk=8, prefix_sharing=False)


def test_kv_cache_decode_still_refuses_mixed_layers(tiny):
    _, cfg, params, ids, _ = tiny
    with pytest.raises(NotImplementedError, match="one kind of layer"):
        LlamaModel(dataclasses.replace(cfg, scan_layers=False)).apply(
            {"params": params}, ids[:, :4], decode=True, mutable=["cache"])


# ---- the driver's comparison, right and with each fault planted
LEARNING_RATE = 1e-5          # the traffic file's


@pytest.fixture(scope="module")
def checked(tiny):
    """What the driver hands its comparison: the system's forward, and the
    state behind one step of the trainer's chain (clip, AdamW) on the
    gradient of the model's own loss."""
    import optax

    conf, cfg, params, ids, _ = tiny
    model = LlamaModel(cfg)
    batch = np.asarray(ids)
    at = train_conv.hidden_positions(7, *batch.shape)
    got = train_conv.system_forward(model, params, batch, at)
    loss, grads = jax.jit(jax.value_and_grad(_loss_fn(model)))(params, ids)
    chain = optax.chain(
        optax.clip_by_global_norm(train_conv.CLIP_NORM),
        optax.adamw(LEARNING_RATE, **train_conv.ADAMW))
    updates, state = chain.update(grads, chain.init(params), params)
    got.update(train_conv.first_step_state(_State(
        optax.apply_updates(params, updates), state)))
    first_held, held = cfg.moe_experts_held
    first = {"loss": float(loss),
             "grad_norm": float(optax.global_norm(grads)),
             "moe_picks_held": float(
                 got["counts"][:, first_held:first_held + held].sum())}
    return conf, model, params, batch, got, first, at


@dataclasses.dataclass
class _State:
    params: dict
    opt_state: tuple


def _check(checked, got=None, first=None, **kw):
    conf, model, params, batch, got_, first_, at = checked
    return train_conv.reference_check(
        conf, model.config, params, batch, got or got_, first or first_, at,
        LEARNING_RATE, **kw)


def test_the_drivers_check_passes_on_the_right_program(checked):
    model = checked[1]
    checks = _check(checked)
    out = controls_lfm2.summary(checks)
    assert out["correct"], out
    assert set(controls_lfm2.VERDICTS) <= set(checks)
    assert set(checks["grad_rel_err_worst"]) == {
        "plain", "attention", "conv", "routed"}
    # the first moment is (1 - b1) x the clipped gradient: float32 here
    assert max(checks["grad_rel_err_worst"].values()) < 10 * TOL
    assert train_conv.leaf_class(
        checks["grad_rel_err_worst_leaf"]["routed"], model.config) == "routed"
    assert checks["hidden_rel_err_max"] < TOL
    assert checks["loss_abs_diff"] < TOL
    assert checks["grad_norm_rel_diff"] < TOL
    # 3 attention layers of 12 leaves, 9 + 1 convolution layers of 10 (the
    # dense one 8), the embedding and the last norm
    assert checks["grad_leaves"] == 3 * 12 + 9 * 10 + 8 + 2 + 3
    assert checks["grad_rel_err_head_rows"] < 10 * TOL
    assert checks["vocab_rows_unseen"] > 0
    assert min(checks["picks_moved_by_bias_share"]) > 0
    # every leaf moved as the reference's AdamW moves it (the step is 1e-5
    # on float32 weights of ~0.1: the change itself is rounded at 1e-3)
    assert checks["update_rel_err"] < 0.01
    assert checks["update_rel_err_decay_alone"] < 0.1
    assert checks["parameters_moved_share"] > 0.9
    # in float32 the system's visits ARE the reference's own choice
    assert checks["picks_moved_per_layer"] == [0.0] * 12
    own = _check(checked, own_choice=True)
    assert own["picks_moved_per_layer"] == [0.0] * 12
    assert own["grad_rel_err_worst"] == checks["grad_rel_err_worst"]


def test_the_reference_visits_the_experts_it_is_given(tiny):
    """``chosen``: every number is computed on the given visits, and the
    reference's own choice is still made and counted."""
    conf, cfg, params, ids, (want_loss, _) = tiny
    get, _, _ = layer_getter(params, cfg)
    args = (np.asarray(ids), get, {k: params[k] for k in TOP}, conf,
            tuple(cfg.moe_experts_held))
    own = ref.lm_loss(*args)
    tokens = ids.size
    elsewhere = np.zeros((12, tokens, cfg.num_experts), bool)
    elsewhere[:, :, :cfg.moe_top_k] = True        # everyone visits 0-3
    got = ref.lm_loss(*args, chosen=elsewhere)
    # the choice is its own in the first sparse layer, where the visits
    # have not yet moved what it is made of
    assert np.array_equal(got["counts"][0], own["counts"][0])
    assert not np.array_equal(got["counts"][1:], own["counts"][1:])
    assert abs(float(got["total"]) - float(own["total"])) > 100 * TOL
    outside = np.asarray(got["not_as_chosen"])
    assert (outside > 0).all() and (outside <= tokens * cfg.moe_top_k).all()
    assert np.asarray(own["not_as_chosen"]).tolist() == [0] * 12
    assert abs(float(own["total"]) - float(want_loss)) < 1e-6


@pytest.mark.parametrize("fault", sorted(controls_lfm2.FAULTS))
def test_a_planted_fault_fails_the_drivers_check(checked, fault):
    with controls_lfm2.FAULTS[fault]():
        out = controls_lfm2.summary(_check(checked))
    assert not out["correct"], (fault, out)
    failed = [k for k in controls_lfm2.VERDICTS if not out[k]]
    assert failed, fault
    if fault == "head_untied":
        # the loss and the hidden state are the right ones: only the
        # embedding's gradient says that the head was not the embedding
        assert failed == ["tied_head_gradient_matches"]
    if fault == "choice_without_bias":
        assert not out["counts_match_reference"]
    if fault == "half_batch":
        # the forward is the right one: the loss and what comes back of it
        assert out["hidden_matches_reference"]
        assert out["counts_match_reference"]
        assert not out["grads_match_reference"]
        assert not out["step_grad_norm_is_the_references"]


def test_a_state_left_unchanged_fails_the_drivers_check(checked):
    _, _, params, _, got, _, _ = checked
    out = controls_lfm2.summary(_check(
        checked, got=controls_lfm2.state_unchanged(params, got)))
    assert not out["state_moved_as_adamw"]
    assert out["update_rel_err"] == pytest.approx(1.0, abs=1e-6)
    assert out["update_rel_err_decay_alone"] == pytest.approx(1.0, abs=1e-6)
    assert not out["grads_match_reference"]
    assert min(out["grad_rel_err_worst"].values()) == pytest.approx(
        1.0, abs=1e-6)
    # the forward is the right one
    assert out["loss_matches_reference"] and out["hidden_matches_reference"]


@pytest.mark.parametrize("wrong", ["rate_doubled", "no_decay", "no_clip"])
def test_a_wrong_optimizer_fails_the_drivers_check(checked, wrong):
    """The update is held to the reference's AdamW, and the moment to the
    clipped gradient."""
    import optax

    conf, model, params, batch, got, first, at = checked
    grads = jax.jit(jax.grad(_loss_fn(model)))(params, jnp.asarray(batch))
    adamw = dict(train_conv.ADAMW)
    rate, clip = LEARNING_RATE, train_conv.CLIP_NORM
    if wrong == "rate_doubled":
        rate *= 2
    elif wrong == "no_decay":
        adamw["weight_decay"] = 0.0
    else:
        clip = 1e9
    chain = optax.chain(optax.clip_by_global_norm(clip),
                        optax.adamw(rate, **adamw))
    updates, state = chain.update(grads, chain.init(params), params)
    out = controls_lfm2.summary(_check(checked, got={
        **got, **train_conv.first_step_state(_State(
            optax.apply_updates(params, updates), state))}))
    assert not out["correct"], out
    if wrong == "no_clip":
        assert not out["grads_match_reference"]
        assert out["state_moved_as_adamw"]      # AdamW's step has no scale
    else:
        assert out["grads_match_reference"]
        assert not out["state_moved_as_adamw"]


def test_fp8_matmuls_fail_the_drivers_check(checked):
    conf, model, params, batch, _, _, at = checked
    out = controls_lfm2.summary(_check(
        checked, *controls_lfm2.fp8_outputs(model, params, batch, at)))
    assert not out["correct"], out
