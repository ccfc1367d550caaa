"""The Laguna-shaped model (window and full attention layers with two head
counts in one scan over periods, partial rotary, YaRN, a head gate, a
shared expert beside sigmoid-routed experts of which a share is held)
against the benchmark's plain reference, ``perfbench/reference_laguna.py``,
on seeded weights: tiny widths, float32, on the CPU.

Tolerance 1e-5 (absolute on the loss; on a gradient 1e-5 of the leaf's
largest entry, and relative): both sides compute in float32 and differ only
in the order of their sums.  Each mechanism
switched off alone in the system is off by more than that, which
``test_a_mechanism_switched_off_fails_it`` shows one by one.
"""

import dataclasses
import hashlib
import json
import os
from collections import Counter

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.accel.accelerate import (AccelerateConfig, accelerate)
from dlrover_tpu.accel.parallel.mesh import MeshSpec
from dlrover_tpu.models import moe
from dlrover_tpu.models.llama import (LayerSpec, LlamaConfig, LlamaModel,
                                      RopeSpec, layer_pattern,
                                      rope_inverse_frequencies)
from dlrover_tpu.models.moe import MoEMLP
from perfbench import reference_laguna as ref
from perfbench.drivers.train_hybrid import (counts_in_layer_order,
                                            hybrid_config, layer_getter)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-5

with open(os.path.join(ROOT, "perfbench/configs/laguna-xs2-train.json")) as _f:
    CONF = json.load(_f)


def _tiny_conf(**over):
    """The configuration file at toy widths: its own ``rehearse`` block,
    which keeps the pattern dense-full + 2 x (s, s, s, full) and the two
    head counts (6 and 4 over 2 KV heads)."""
    from perfbench.harness import merged

    conf = merged(CONF, CONF["rehearse"])
    conf = merged(conf, {"hidden_size": 32, "intermediate_size": 48,
                         "head_dim": 8, "vocab_size": 96,
                         "moe_intermediate_size": 16,
                         "shared_expert_intermediate_size": 16,
                         "sliding_window": 8,
                         "deployment": {"seq_len": 32, "remat": True}})
    return merged(conf, over)


def _system(conf, **replace):
    cfg = hybrid_config(conf, max_seq_len=conf["deployment"]["seq_len"])
    return dataclasses.replace(cfg, **replace)


def _loss_fn(model):
    def loss(params, ids):
        logits, _ = model.apply({"params": params}, ids,
                                mutable=["moe_losses"])
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
        return -jnp.take_along_axis(
            logp, ids[:, 1:, None], axis=-1).mean()

    return loss


def _reference_loss(conf, cfg):
    def loss(params, ids):
        get, _, _ = layer_getter(params, cfg)
        top = {k: params[k] for k in
               ("embed_tokens", "final_norm", "lm_head")}
        return ref.lm_loss(np.asarray(ids), get, top, conf,
                           tuple(cfg.moe_experts_held))["total"]

    return loss


@pytest.fixture(scope="module")
def tiny():
    conf = _tiny_conf()
    cfg = _system(conf)
    model = LlamaModel(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                             cfg.vocab_size).astype(jnp.int32)
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(0), ids)["params"])
    want = jax.value_and_grad(_reference_loss(conf, cfg))(params, ids)
    return conf, cfg, params, ids, want


def test_pattern_and_parameter_tree(tiny):
    _, cfg, params, _, _ = tiny
    assert layer_pattern(cfg.layer_specs) == (1, 4)
    assert set(params) == {"embed_tokens", "final_norm", "layer_0",
                           "lm_head", "periods"}
    assert set(params["periods"]) == {f"layer_{j}" for j in range(4)}
    # layer 0 is dense with 6 heads; the period is 3 window layers of 4
    # heads and a full one of 6, all sparse, stacked over 2 periods
    assert params["layer_0"]["attn"]["q_proj"]["kernel"].shape == (32, 6, 8)
    assert "gate_proj" in params["layer_0"]["mlp"]
    heads = [params["periods"][f"layer_{j}"]["attn"]["q_proj"]["kernel"].shape
             for j in range(4)]
    assert heads == [(2, 32, 4, 8)] * 3 + [(2, 32, 6, 8)]
    mlp = params["periods"]["layer_0"]["mlp"]
    assert mlp["w_gate"].shape == (2, 4, 32, 16)        # 4 of 16 held
    assert mlp["router"]["kernel"].shape == (2, 32, 16)  # routes over 16
    assert mlp["shared_down"]["kernel"].shape == (2, 16, 32)
    assert params["periods"]["layer_3"]["attn"]["g_proj"]["kernel"].shape \
        == (2, 32, 6)
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == cfg.num_params
    # the full model: layers 0-3 lead, then 9 periods of (f, s, s, s)
    assert layer_pattern(LlamaConfig.laguna_xs2().layer_specs) == (4, 4)
    assert layer_pattern(LlamaConfig.tiny().layer_specs) == (0, 1)


@pytest.mark.parametrize("form", ["scanned_remat", "unrolled"])
def test_loss_and_gradients_match_the_reference(tiny, form):
    conf, cfg, params, ids, (want_loss, want_grads) = tiny
    if form == "unrolled":
        cfg = dataclasses.replace(cfg, scan_layers=False, remat=False)
        # the same weights in the unrolled tree
        get, _, _ = layer_getter(params, tiny[1])
        params = {**{k: params[k] for k in
                     ("embed_tokens", "final_norm", "lm_head")},
                  **{f"layer_{i}": get(i) for i in range(cfg.num_layers)}}
        want_loss, want_grads = jax.value_and_grad(
            lambda p, ids: _reference_unrolled(conf, cfg, p, ids))(
                params, ids)
    got_loss, got_grads = jax.jit(jax.value_and_grad(
        _loss_fn(LlamaModel(cfg))))(params, ids)
    assert abs(float(got_loss) - float(want_loss)) < TOL
    flat_w = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    for path, g in jax.tree_util.tree_flatten_with_path(got_grads)[0]:
        # 1e-5 of the leaf's scale: the embedding's gradient reaches 9
        # (the first norm divides a row of N(0, 0.02) by its RMS)
        scale = max(1.0, float(jnp.abs(flat_w[path]).max()))
        np.testing.assert_allclose(
            g, flat_w[path], atol=TOL * scale, rtol=TOL,
            err_msg=jax.tree_util.keystr(path))


def test_the_reference_gradient_a_layer_at_a_time(tiny):
    """What the chip's comparison runs (``jax.grad`` of ``lm_loss`` does
    not fit at the timed sizes) is ``jax.grad`` of ``lm_loss``."""
    conf, cfg, params, ids, (want_loss, want_grads) = tiny
    get, _, _ = layer_getter(params, cfg)
    want, _, _ = layer_getter(want_grads, cfg)
    top = ("embed_tokens", "final_norm", "lm_head")
    seen = []

    def visit(i, grads):
        seen.append(i)
        expect = ({k: want_grads[k] for k in top} if i == "top"
                  else want(i))
        for (path, g), w in zip(
                jax.tree_util.tree_flatten_with_path(grads)[0],
                jax.tree_util.tree_leaves(expect)):
            np.testing.assert_allclose(
                g, w, atol=TOL * max(1.0, float(jnp.abs(w).max())),
                rtol=TOL, err_msg=f"{i}{jax.tree_util.keystr(path)}")

    got = ref.lm_loss_and_grads(
        np.asarray(ids), get, {k: params[k] for k in top}, conf,
        tuple(cfg.moe_experts_held), visit)
    assert seen == list(reversed(range(cfg.num_layers))) + ["top"]
    assert abs(float(got["total"]) - float(want_loss)) < 1e-6


def _reference_unrolled(conf, cfg, params, ids):
    top = {k: params[k] for k in ("embed_tokens", "final_norm", "lm_head")}
    return ref.lm_loss(np.asarray(ids), lambda i: params[f"layer_{i}"], top,
                       conf, tuple(cfg.moe_experts_held))["total"]


def _off(cfg, what):
    """``cfg`` with one mechanism switched off in the SYSTEM."""
    def layers(fn):
        return dataclasses.replace(cfg, layers=tuple(map(fn, cfg.layers)))

    def rope(**kw):
        return layers(lambda s: dataclasses.replace(
            s, rope=dataclasses.replace(s.rope, **kw))
            if not s.window else s)

    return {
        "window": lambda: layers(
            lambda s: dataclasses.replace(s, window=0)),
        "window_twice_as_wide": lambda: layers(
            lambda s: dataclasses.replace(s, window=2 * s.window)),
        "partial_rotary": lambda: rope(rotary_fraction=1.0),
        "yarn_ramp": lambda: rope(yarn_factor=0.0),
        "attention_factor": lambda: rope(attention_factor=1.0),
        "plain_rope_in_full_layers": lambda: layers(
            lambda s: dataclasses.replace(s, rope=RopeSpec(theta=10000.0))),
        "head_gate": lambda: dataclasses.replace(cfg, attn_head_gate=False),
        "shared_expert": lambda: dataclasses.replace(
            cfg, moe_shared_width=0),
        "routed_scale": lambda: dataclasses.replace(
            cfg, moe_routed_scale=1.0),
        "a_dropped_pick": lambda: dataclasses.replace(
            cfg, moe_top_k=cfg.moe_top_k - 1),
        "softmax_router": lambda: dataclasses.replace(
            cfg, moe_score_fn="softmax"),
        "sum_not_normalised": lambda: dataclasses.replace(
            cfg, moe_norm_topk_prob=False),
    }[what]()


@pytest.mark.parametrize("what", [
    "window", "window_twice_as_wide", "partial_rotary", "yarn_ramp",
    "attention_factor", "plain_rope_in_full_layers", "head_gate",
    "shared_expert", "routed_scale", "a_dropped_pick", "softmax_router",
    "sum_not_normalised"])
def test_a_mechanism_switched_off_fails_it(tiny, what):
    """The same weights through a system that lacks ONE mechanism: its
    loss leaves the reference's by far more than the tolerance."""
    _, cfg, params, ids, (want_loss, _) = tiny
    got = jax.jit(_loss_fn(LlamaModel(_off(cfg, what))))(params, ids)
    assert abs(float(got) - float(want_loss)) > 10 * TOL, what


def test_routing_counts_and_step_metrics(tiny):
    """``expert_counts`` over all 16 experts equal the reference's picks,
    ``moe_picks_held`` is their held entries, the load is over the held
    groups, and ``accelerate()`` exports all of it as step metrics."""
    conf, cfg, params, ids, _ = tiny
    model = LlamaModel(cfg)
    _, sown = model.apply({"params": params}, ids, mutable=["moe_losses"])
    get, lead, period = layer_getter(params, cfg)
    counts = counts_in_layer_order(sown["moe_losses"], cfg, lead, period)
    top = {k: params[k] for k in ("embed_tokens", "final_norm", "lm_head")}
    want = ref.lm_loss(np.asarray(ids), get, top, conf,
                       tuple(cfg.moe_experts_held))
    assert np.array_equal(counts, np.asarray(want["counts"]))
    assert (counts.sum(-1) == ids.size * cfg.moe_top_k).all()
    first, held = cfg.moe_experts_held
    stats = moe.routing_stats(sown["moe_losses"])
    held_counts = counts[:, first:first + held]
    assert float(stats["moe_picks_held"]) == held_counts.sum()
    assert float(stats["moe_held_share"]) == pytest.approx(
        held_counts.sum() / counts.sum())
    load = held_counts / held_counts.mean(-1, keepdims=True)
    assert float(stats["moe_load_max"]) == pytest.approx(load.max())
    assert float(stats["moe_load_min"]) == pytest.approx(load.min())


def test_accelerate_exports_the_held_picks_of_every_microbatch():
    """A smaller pattern (dense-full, then two window layers: one leading
    layer and a period of one) through ``accelerate()`` with two
    microbatches: the step's ``moe_picks_held`` is the SUM over them."""
    conf = _tiny_conf(num_hidden_layers=3)
    cfg = _system(conf)
    assert layer_pattern(cfg.layer_specs) == (1, 1)
    model = LlamaModel(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                             cfg.vocab_size).astype(jnp.int32)
    res = accelerate(
        model, config=AccelerateConfig(
            mesh_spec=MeshSpec.for_device_count(1), grad_accum_steps=2),
        batch_shape=(2, 32), devices=jax.devices()[:1])
    state = res.init_fn(jax.random.PRNGKey(0))
    _, sown = model.apply({"params": state.params}, ids,
                          mutable=["moe_losses"])
    stats = moe.routing_stats(sown["moe_losses"])
    _, metrics = res.train_step(state, {"input_ids": jnp.stack([ids, ids])})
    assert {"moe_picks_held", "moe_held_share", "moe_load_max",
            "moe_load_min", "loss", "grad_norm"} <= set(metrics)
    assert float(metrics["moe_picks_held"]) == 2 * float(
        stats["moe_picks_held"])
    assert float(metrics["moe_held_share"]) == pytest.approx(
        float(stats["moe_held_share"]))
    assert float(metrics["moe_load_max"]) == pytest.approx(
        float(stats["moe_load_max"]))


def test_accelerate_sums_the_overflowed_layers_of_every_microbatch(
        monkeypatch):
    """The same model with a sorted buffer of 8 rows (PR 57: a share's
    compact buffer, here far under what the routing sends it): every
    sparse layer walks the rest behind its ``cond``, under the scan and
    the remat, and the step's ``moe_overflow_layers`` is the SUM over the
    microbatches of the layers that did."""
    monkeypatch.setattr(moe, "buffer_rows",
                        lambda picks, held, experts: min(picks, 8))
    conf = _tiny_conf(num_hidden_layers=3)
    cfg = _system(conf)
    model = LlamaModel(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                             cfg.vocab_size).astype(jnp.int32)
    res = accelerate(
        model, config=AccelerateConfig(
            mesh_spec=MeshSpec.for_device_count(1), grad_accum_steps=2),
        batch_shape=(2, 32), devices=jax.devices()[:1])
    state = res.init_fn(jax.random.PRNGKey(0))
    _, sown = model.apply({"params": state.params}, ids,
                          mutable=["moe_losses"])
    stats = moe.routing_stats(sown["moe_losses"])
    sparse = sum(s.mlp == "sparse" for s in cfg.layer_specs)
    assert float(stats["moe_overflow_layers"]) == sparse > 0
    _, metrics = res.train_step(state, {"input_ids": jnp.stack([ids, ids])})
    assert float(metrics["moe_overflow_layers"]) == 2 * sparse
    assert float(metrics["moe_picks_held"]) == 2 * float(
        stats["moe_picks_held"])
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """4 shares of 16 experts: the routed parts of all shares, and the
    shared expert counted ONCE, are what the uncut reference gives for
    the whole layer; a pick on an absent expert adds nothing, forward or
    backward."""
    t, m, w, e, k = 24, 32, 16, 16, 4
    x = jax.random.normal(jax.random.PRNGKey(2), (1, t, m), jnp.float32)

    def layer(held):
        return MoEMLP(hidden_size=m, intermediate_size=w, num_experts=e,
                      top_k=k, norm_topk_prob=True, score_fn="sigmoid",
                      routed_scale=2.5, shared_width=w, experts_held=held,
                      dtype=jnp.float32)

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

    lp = {"post_norm": {"scale": jnp.ones((m,))}, "mlp": whole}
    # the reference norms its input; undo that by feeding it x already
    # of unit RMS with eps 0
    xn = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))
    routed, shared, counts = ref.sparse_parts(
        xn[0], lp, 0.0, k, 2.5, (0, e))
    parts = [apply((f, 4), share_params(f, 4), xn)[0] for f in range(0, e, 4)]
    np.testing.assert_allclose(sum(parts) - 3 * shared, routed + shared,
                               atol=TOL)
    np.testing.assert_allclose(apply(None, whole, xn)[0], routed + shared,
                               atol=TOL)
    # each share against the reference GIVEN that share, gradients too
    for first in (0, 8):
        cut = share_params(first, 4)
        r, s, _ = ref.sparse_parts(
            xn[0], {"post_norm": lp["post_norm"], "mlp": cut}, 0.0, k, 2.5,
            (first, 4))
        np.testing.assert_allclose(parts[first // 4], r + s, atol=TOL)
    g_sys = jax.grad(lambda p, x: apply((4, 4), p, x).sum(), argnums=(0, 1))(
        share_params(4, 4), xn)
    g_ref = jax.grad(lambda p, x: sum(
        part.sum() for part in ref.sparse_parts(
            x[0] / jnp.sqrt(jnp.mean(x[0] ** 2, -1, keepdims=True)),
            {"post_norm": lp["post_norm"], "mlp": p}, 0.0, k, 2.5,
            (4, 4))[:2]), argnums=(0, 1))(share_params(4, 4), xn)
    # (the reference norms its input: at unit RMS and eps 0 that is the
    # identity, and its gradient's part along x is what is left out here)
    for path, g in jax.tree_util.tree_flatten_with_path(g_sys[0])[0]:
        want = dict(jax.tree_util.tree_flatten_with_path(g_ref[0])[0])[path]
        np.testing.assert_allclose(g, want, atol=TOL, rtol=TOL,
                                   err_msg=jax.tree_util.keystr(path))
    assert np.isfinite(np.asarray(g_sys[1])).all()


def test_yarn_frequencies_are_the_published_ones():
    """Of the 32 pairs of a full layer's 64 rotating dimensions, those up
    to index 5 keep theta^(-2i/64), those from 16 on are divided by 64, a
    linear ramp between; cos and sin are scaled by 1.4158883."""
    full = LlamaConfig.laguna_xs2().layer_specs[0].rope
    assert full.attention_factor == pytest.approx(0.1 * np.log(64) + 1)
    assert full.attention_factor == pytest.approx(1.4158883, abs=1e-7)
    got = np.asarray(rope_inverse_frequencies(full, 128), np.float64)
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    ratio = got / plain
    assert got.shape == (32,)
    np.testing.assert_allclose(ratio[:6], 1.0, rtol=1e-6)
    np.testing.assert_allclose(ratio[16:], 1 / 64, rtol=1e-6)
    ramp = (np.arange(6, 16) - 5) / 11.0
    np.testing.assert_allclose(ratio[6:16], 1 - ramp + ramp / 64, rtol=1e-5)
    # the reference computes them as transformers does: the same numbers
    want, factor = ref.inverse_frequencies(
        CONF["rope_parameters"]["full_attention"], 128)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-6)
    assert factor == full.attention_factor
    # a window layer: plain, the whole head
    window = LlamaConfig.laguna_xs2().layer_specs[1].rope
    np.testing.assert_allclose(
        rope_inverse_frequencies(window, 128),
        10000.0 ** (-np.arange(0, 128, 2) / 128), rtol=1e-6)


def test_preset_has_the_published_widths():
    """``LlamaConfig.laguna_xs2()`` against the benchmark's configuration
    file, key by key; depth, experts held and vocabulary are cut there."""
    cfg = LlamaConfig.laguna_xs2()
    dep = CONF["deployment"]
    published = {
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim_,
        "num_experts_per_tok": cfg.moe_top_k,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "shared_expert_intermediate_size": cfg.moe_shared_width,
        "moe_routed_scaling_factor": cfg.moe_routed_scale,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "attention_bias": cfg.attention_bias,
        "gating": cfg.attn_head_gate,
        "num_attention_heads_per_layer": [
            s.num_heads for s in cfg.layer_specs],
        "layer_types": [
            "sliding_attention" if s.window else "full_attention"
            for s in cfg.layer_specs],
        "mlp_layer_types": [s.mlp for s in cfg.layer_specs],
    }
    assert {k: CONF[k] for k in published} == published
    assert list(CONF["reduced"]) == ["num_hidden_layers", "num_experts",
                                     "vocab_size"]
    assert (cfg.num_layers, cfg.num_experts, cfg.vocab_size) == (
        40, dep["experts_published"], dep["vocab_published"]) == (
        40, 256, 100352)
    assert {s.window for s in cfg.layer_specs} == {0, CONF["sliding_window"]}
    assert cfg.num_params == CONF["parameters"]["published_total"]
    # the file as the driver reads it is the preset cut to the chip's share
    run = hybrid_config(CONF, max_seq_len=dep["seq_len"])
    cut = LlamaConfig.from_preset(
        "laguna_xs2", num_layers=CONF["num_hidden_layers"],
        vocab_size=CONF["vocab_size"],
        moe_experts_held=tuple(dep["experts_held"]),
        dtype=run.dtype, param_dtype=run.param_dtype)
    assert run == cut
    assert run.num_params == CONF["parameters"]["total_as_run"]
    per = [run.layer_params(s) for s in run.layer_specs]
    p = CONF["parameters"]
    assert per == [p["dense_layer_0_as_run"]] + 2 * (
        3 * [p["sparse_layer_64_heads_as_run"]]
        + [p["sparse_layer_48_heads_as_run"]])
    assert 2 * run.vocab_size * run.hidden_size == \
        p["embedding_and_head_as_run"]


def _primitives(jaxpr, into):
    for eqn in jaxpr.eqns:
        into[eqn.primitive.name] += 1
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _primitives(inner, into)
    return into


@pytest.mark.parametrize("shape,tree_hash,primitives", [
    ("mistral", "7378dd15e57b", 440), ("olmoe", "0af6ab5ee26f", 4993)])
def test_the_uniform_models_are_traced_as_before(shape, tree_hash,
                                                 primitives):
    """A tiny Mistral-shaped and a tiny OLMoE-shaped model, scanned with
    remat: the parameter tree and the number of primitives in the jaxpr of
    loss and gradient are the parent commit's (PR 30's tree, counted there
    by this same code)."""
    kw = {"mistral": dict(num_heads=4, num_kv_heads=2),
          "olmoe": dict(num_heads=4, num_kv_heads=4, num_experts=4,
                        moe_top_k=2, moe_norm_topk_prob=False, qk_norm=True,
                        intermediate_size=32)}[shape]
    cfg = LlamaConfig.tiny(scan_layers=True, remat=True, **kw)
    model = LlamaModel(cfg)
    ids = jnp.arange(64, dtype=jnp.int32).reshape(2, 32) % 256
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(0), ids)["params"])
    tree = sorted((jax.tree_util.keystr(k), tuple(x.shape)) for k, x in
                  jax.tree_util.tree_flatten_with_path(params)[0])
    assert hashlib.sha1(repr(tree).encode()).hexdigest()[:12] == tree_hash
    assert "layers" in params and "periods" not in params

    def loss(p):
        out, _ = model.apply({"params": p}, ids, mutable=["moe_losses"])
        return out.astype(jnp.float32).mean()

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(params)
    assert sum(_primitives(jaxpr.jaxpr, Counter()).values()) == primitives
    if shape == "olmoe":   # and its step metrics are the four it had
        _, sown = model.apply({"params": params}, ids,
                              mutable=["moe_losses"])
        assert set(moe.routing_stats(sown["moe_losses"])) == {
            "moe_load_max", "moe_load_min", "moe_balance_loss",
            "moe_z_loss"}


def test_serving_and_decode_refuse_more_than_one_kind_of_layer(tiny):
    from dlrover_tpu.serving.params import serving_params_from_llama

    _, cfg, params, ids, _ = tiny
    with pytest.raises(ValueError, match="ONE kind of layer"):
        serving_params_from_llama(
            {"params": params},
            dataclasses.replace(cfg, num_experts=0))
    unrolled = dataclasses.replace(cfg, scan_layers=False, remat=False)
    with pytest.raises(NotImplementedError, match="one kind of layer"):
        LlamaModel(unrolled).init(jax.random.PRNGKey(0), ids, decode=True)
    with pytest.raises(ValueError, match="layer descriptions"):
        dataclasses.replace(cfg, num_layers=3)
    assert LayerSpec() == LayerSpec(window=0, num_heads=32)
