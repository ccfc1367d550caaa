"""The OLMoE-shaped model (QK-norm, dropless top-k experts) against the
benchmark's plain reference, ``perfbench/reference_olmoe.py``, on seeded
weights: tiny widths, float32, on the CPU.

Tolerance 1e-5 (absolute, and relative for gradients): both sides compute
in float32 and differ only in the order of their sums (the system adds a
token's experts in pick order after a grouped matmul, the reference adds
all experts in index order; a loss is a mean over ~60 positions of values
near 5.5, a gradient a sum of as many terms).  A dropped pick, a
renormalised weight, a per-head QK-norm or a top-1 balance loss is off by
at least ten times that, which the tests below show one by one.
"""

import dataclasses
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.accel.accelerate import (AccelerateConfig, accelerate,
                                          default_loss_fn)
from dlrover_tpu.accel.parallel.mesh import MeshSpec
from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
from dlrover_tpu.models.moe import MoEMLP
from perfbench import reference_olmoe as ref
from perfbench.drivers.train_moe import zipf_batches

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-5


def _tiny(**kw):
    base = dict(vocab_size=128, hidden_size=32, intermediate_size=16,
                num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=32,
                num_experts=8, moe_top_k=3, moe_norm_topk_prob=False,
                qk_norm=True, dtype=jnp.float32, param_dtype=jnp.float32,
                scan_layers=True, remat=True)
    base.update(kw)
    return LlamaConfig(**base)


def _seeded(cfg, batch_shape=(2, 32), seed=0):
    model = LlamaModel(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), batch_shape, 0,
                             cfg.vocab_size).astype(jnp.int32)
    params = nn.unbox(model.init(jax.random.PRNGKey(seed), ids))["params"]
    # norm scales off 1.0, so that a norm in the wrong place shows
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 2), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        1.0 + 0.3 * jax.random.normal(k, x.shape) if any(
            getattr(p, "key", "") == "scale" for p in path) else x
        for k, (path, x) in zip(keys, leaves)])
    return model, params, ids


def _reference_loss(cfg, params, ids, part="total"):
    if cfg.scan_layers:
        stacked = params["layers"]["layer"]

        def get_layer(i):
            return jax.tree_util.tree_map(lambda x: x[i], stacked)
    else:
        def get_layer(i):
            return params[f"layer_{i}"]
    out = ref.lm_loss(ids, get_layer, params, cfg.num_layers,
                      cfg.rope_theta, cfg.rms_norm_eps, cfg.moe_top_k,
                      cfg.moe_norm_topk_prob, cfg.moe_aux_loss_coef,
                      cfg.moe_z_loss_coef)
    return out if part is None else out[part]


@pytest.mark.parametrize("norm_topk_prob", [False, True],
                         ids=["weights_as_softmax", "weights_renormalised"])
def test_loss_and_gradients_match_the_reference(norm_topk_prob):
    """Two OLMoE-shaped layers under nn.scan + full remat: the loss in its
    parts and the gradient of every parameter."""
    cfg = _tiny(moe_norm_topk_prob=norm_topk_prob)
    model, params, ids = _seeded(cfg)
    loss_fn = default_loss_fn(model)
    (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, {"input_ids": ids})
    want = _reference_loss(cfg, params, ids, part=None)
    assert abs(float(loss) - float(want["total"])) < TOL
    stats = aux["moe"]
    assert abs(float(stats["moe_balance_loss"]) - float(want["balance"])) < TOL
    assert abs(float(stats["moe_z_loss"]) - float(want["z"])) < TOL
    counts = np.asarray(want["counts"])
    assert (counts.sum(axis=-1) == ids.size * cfg.moe_top_k).all()
    mean = ids.size * cfg.moe_top_k / cfg.num_experts
    assert float(stats["moe_load_max"]) == pytest.approx(
        counts.max() / mean)
    assert float(stats["moe_load_min"]) == pytest.approx(
        counts.min() / mean)
    want_grads = jax.grad(
        lambda p: _reference_loss(cfg, p, ids))(params)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    flat_want = jax.tree_util.tree_leaves(want_grads)
    assert len(flat) == len(flat_want)
    for (path, g), w in zip(flat, flat_want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=TOL, rtol=TOL,
            err_msg=jax.tree_util.keystr(path))
    # the other setting of norm_topk_prob is another model
    other = _reference_loss(
        dataclasses.replace(cfg, moe_norm_topk_prob=not norm_topk_prob),
        params, ids)
    assert abs(float(loss) - float(other)) > 10 * TOL


def _moe_block(norm_topk_prob=False, top_k=2, experts=8, hidden=16, width=8):
    return MoEMLP(hidden_size=hidden, intermediate_size=width,
                  num_experts=experts, top_k=top_k,
                  norm_topk_prob=norm_topk_prob, dtype=jnp.float32,
                  param_dtype=jnp.float32)


def _reference_block(params, x, top_k, norm_topk_prob):
    """The reference's expert block on the block's own parameters (the
    residual and the norm taken out: scale 1 at eps 0 on unit-RMS rows)."""
    t = x.reshape(-1, x.shape[-1])
    t = t / jnp.sqrt(jnp.mean(t * t, axis=-1, keepdims=True))
    lp = {"post_norm": {"scale": jnp.ones((t.shape[-1],))}, "mlp": params}
    y, balance, z, counts = ref.expert_block(t, lp, 0.0, top_k,
                                             norm_topk_prob)
    return t.reshape(x.shape), (y - t).reshape(x.shape), balance, z, counts


def test_skewed_routing_drops_nothing():
    """One expert takes half of all picks and several take none: every
    pick is computed (a capacity of 1.25 x the mean would have dropped
    most of that expert's), and the output equals the reference's."""
    layer = _moe_block(top_k=2)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (2, 32, 16)))
    params = nn.unbox(layer.init(jax.random.PRNGKey(1), x))["params"]
    # on positive rows: expert 0's logit is far above the rest, so it is
    # every token's first pick; the second is one of experts 1..3 by the
    # token's leading features (logits > 0); experts 4..7 stay at 0
    kernel = np.zeros((16, 8), np.float32)
    kernel[:, 0] = 2.0
    kernel[:3, 1:4] = 4.0 * np.eye(3)
    params["router"]["kernel"] = jnp.asarray(kernel)
    xin, want, _, _, counts = _reference_block(params, x, 2, False)
    counts = np.asarray(counts)
    assert counts[0] == 64 and counts.sum() == 128
    assert (counts[4:] == 0).all()
    out, sown = layer.apply({"params": params}, xin, mutable=["moe_losses"])
    assert np.array_equal(
        np.asarray(sown["moe_losses"]["expert_counts"]), counts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=TOL, rtol=TOL)


def test_balance_loss_counts_all_picks():
    """f_e is the share of ALL top-k picks, not of the first picks only:
    the reference's term with top-1 counts is another number."""
    layer = _moe_block(top_k=4)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 16))
    params = nn.unbox(layer.init(jax.random.PRNGKey(4), x))["params"]
    xin, _, balance, z, counts = _reference_block(params, x, 4, False)
    _, sown = layer.apply({"params": params}, xin, mutable=["moe_losses"])
    sown = sown["moe_losses"]
    assert abs(float(sown["balance_loss"]) - float(balance)) < TOL
    assert abs(float(sown["z_loss"]) - float(z)) < TOL
    assert abs(float(sown["aux_loss"])
               - (0.01 * float(balance) + 1e-3 * float(z))) < TOL
    _, _, top1_balance, _, _ = _reference_block(params, x, 1, False)
    assert abs(float(sown["balance_loss"]) - float(top1_balance)) > 1e-3


def test_qk_norm_is_over_the_whole_projection():
    """The system equals the reference, whose RMSNorm_q / RMSNorm_k run
    over all heads at once; normalising each head by itself (same scale
    vector) is a different model and must not match."""
    cfg = _tiny(num_layers=1, scan_layers=False, remat=False)
    model, params, ids = _seeded(cfg, seed=5)
    hidden = model.apply({"params": params}, ids, return_hidden=True,
                         mutable=["moe_losses"])[0]
    assert "q_norm" in params["layer_0"]["attn"]
    assert params["layer_0"]["attn"]["q_norm"]["scale"].shape == (
        cfg.num_heads * cfg.head_dim_,)
    xs, _ = ref.forward(ids, lambda i: params[f"layer_{i}"], params, 1,
                        cfg.rope_theta, cfg.rms_norm_eps, cfg.moe_top_k,
                        cfg.moe_norm_topk_prob)
    want = ref.rmsnorm(jnp.stack(xs), params["final_norm"]["scale"],
                       cfg.rms_norm_eps)
    np.testing.assert_allclose(np.asarray(hidden), np.asarray(want),
                               atol=TOL, rtol=TOL)

    original = ref.rmsnorm

    def per_head(x, w, eps):
        if x.shape[-1] != cfg.num_heads * cfg.head_dim_:
            return original(x, w, eps)
        h = x.reshape(x.shape[0], cfg.num_heads, cfg.head_dim_)
        h = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + eps)
        return h.reshape(x.shape) * w

    try:
        ref.rmsnorm = per_head
        ref.attention_block.clear_cache()
        xs, _ = ref.forward(ids, lambda i: params[f"layer_{i}"], params, 1,
                            cfg.rope_theta, cfg.rms_norm_eps,
                            cfg.moe_top_k, cfg.moe_norm_topk_prob)
    finally:
        ref.rmsnorm = original
        ref.attention_block.clear_cache()
    wrong = original(jnp.stack(xs), params["final_norm"]["scale"],
                     cfg.rms_norm_eps)
    assert float(jnp.abs(hidden - wrong).max()) > 1e-3


def test_routing_metrics_leave_the_train_step():
    """``accelerate()``'s step reports the routing beside loss and
    grad_norm, also under gradient accumulation."""
    cfg = _tiny(vocab_size=64)
    for accum in (1, 2):
        res = accelerate(
            LlamaModel(cfg),
            config=AccelerateConfig(mesh_spec=MeshSpec(dp=1),
                                    grad_accum_steps=accum),
            batch_shape=(2, 32), devices=jax.devices()[:1])
        state = res.init_fn(jax.random.PRNGKey(0))
        shape = (2, 32) if accum == 1 else (accum, 2, 32)
        ids = jax.random.randint(jax.random.PRNGKey(1), shape, 0,
                                 64).astype(jnp.int32)
        _, metrics = res.train_step(state, {"input_ids": ids})
        assert {"loss", "grad_norm", "moe_load_max", "moe_load_min",
                "moe_balance_loss", "moe_z_loss"} <= set(metrics)
        assert float(metrics["moe_load_max"]) >= 1.0 >= float(
            metrics["moe_load_min"]) >= 0.0
        assert float(metrics["moe_balance_loss"]) >= 1.0 - 1e-5


def test_preset_has_the_published_widths():
    """``LlamaConfig.olmoe_1b_7b()`` against the benchmark's configuration
    file, key by key (only the depth is cut there)."""
    with open(os.path.join(
            ROOT, "perfbench/configs/olmoe-1b-7b-train.json")) as f:
        conf = json.load(f)
    cfg = LlamaConfig.olmoe_1b_7b()
    published = {
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim_, "num_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.moe_top_k,
        "norm_topk_prob": cfg.moe_norm_topk_prob,
        "max_position_embeddings": cfg.max_seq_len,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "attention_bias": cfg.attention_bias,
        "router_aux_loss_coef": cfg.moe_aux_loss_coef,
        "router_z_loss_coef": cfg.moe_z_loss_coef,
    }
    assert {k: conf[k] for k in published} == published
    assert cfg.num_layers == 16 and cfg.qk_norm
    assert list(conf["reduced"]) == ["num_hidden_layers"]
    per_layer = conf["parameters"]["per_layer"]
    assert cfg.num_params == (16 * per_layer
                              + conf["parameters"]["embedding_and_head"]
                              + cfg.hidden_size)
    assert conf["parameters"]["total_as_run"] == (
        conf["num_hidden_layers"] * per_layer
        + conf["parameters"]["embedding_and_head"] + cfg.hidden_size)


def test_zipf_batches_replay():
    """The cell's batches: the same seeds give the same bytes, ``--seed``
    changes the content, ``base_seed`` which ids are frequent; the ids
    cover the vocabulary with a Zipfian head."""
    a = zipf_batches(3000000019, 7, 1.0, 50304, 1, 4096, 2)
    b = zipf_batches(3000000019, 7, 1.0, 50304, 1, 4096, 2)
    assert all(x.tobytes() == y.tobytes() for x, y in zip(a, b))
    assert a[0].dtype == np.int32 and a[0].shape == (1, 4096)
    assert a[0].tobytes() != a[1].tobytes()
    c = zipf_batches(3000000020, 7, 1.0, 50304, 1, 4096, 2)
    assert c[0].tobytes() != a[0].tobytes()
    top_a = np.bincount(np.concatenate(a).ravel(), minlength=50304).argmax()
    top_c = np.bincount(np.concatenate(c).ravel(), minlength=50304).argmax()
    assert top_a == top_c          # the same id has rank 1 under one base
    d = zipf_batches(3000000019, 8, 1.0, 50304, 1, 4096, 2)
    assert np.bincount(np.concatenate(d).ravel(),
                       minlength=50304).argmax() != top_a
    ids = np.concatenate(a).ravel()
    assert 0 <= ids.min() and ids.max() < 50304
    # rank 1 holds 1 / H(50304) = 8.8 % of the draws
    assert 0.06 < (ids == top_a).mean() < 0.12


@pytest.mark.parametrize("lacks, match", [
    ("qk_norm", "QK-norm"),
    ("window", "ONE kind of layer"),
    ("head_gate", "no head gate"),
    ("experts_int8", "no int8 weights"),
])
def test_serving_refuses_a_model_by_what_it_lacks(lacks, match):
    """Sparse experts are served (behind latent attention:
    tests/test_sparse_serving.py; behind the grouped-query block since PR
    50: tests/test_granite_serving.py); what the serving blocks still lack
    is refused by name: QK-norm, a window, a head gate, and int8 weights
    for a model of layer kinds."""
    from dlrover_tpu.models.llama import LayerSpec
    from dlrover_tpu.serving.params import serving_params_from_llama

    cfg = _tiny(scan_layers=False, remat=False)
    _, params, _ = _seeded(cfg)
    if lacks == "window":
        cfg = dataclasses.replace(
            cfg, qk_norm=False, num_experts=0, layers=tuple(
                LayerSpec(num_heads=cfg.num_heads, window=8 * (i % 2))
                for i in range(cfg.num_layers)))
    elif lacks == "head_gate":
        cfg = dataclasses.replace(cfg, qk_norm=False, num_experts=0,
                                  attn_head_gate=True)
    elif lacks == "experts_int8":
        cfg = dataclasses.replace(cfg, qk_norm=False)
    with pytest.raises(ValueError, match=match):
        serving_params_from_llama({"params": params}, cfg,
                                  int8=lacks == "experts_int8")
