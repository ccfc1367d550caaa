"""MoE / expert-parallel tests (reference parity:
atorch/atorch/modules/moe/ — MOELayer token dispatch, top-k gating,
grouped-GEMM experts — tested in tiny worlds the same way the reference's
moe tests run 2-4 proc gloo worlds; here an 8-device CPU mesh).  The
dropless layer against a plain reference: tests/test_olmoe_reference.py."""

import functools
import hashlib
import os
import re
import uuid

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.accel.accelerate import AccelerateConfig, accelerate
from dlrover_tpu.accel.parallel.mesh import MeshSpec
from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
from dlrover_tpu.models.moe import MoEMLP


def test_moe_mlp_forward_shape():
    layer = MoEMLP(
        hidden_size=32, intermediate_size=64, num_experts=4, top_k=2
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(1), x)
    out, updates = layer.apply(
        nn.unbox(variables), x, mutable=["moe_losses"]
    )
    assert out.shape == x.shape
    assert jnp.isfinite(out.astype(jnp.float32)).all()
    sown = updates["moe_losses"]
    assert set(sown) == {"aux_loss", "balance_loss", "z_loss",
                         "expert_counts"}
    # every pick of every token is in a group: nothing is dropped
    assert int(sown["expert_counts"].sum()) == 2 * 16 * 2


@pytest.mark.parametrize(
    "mesh_spec",
    [MeshSpec(dp=4, ep=2), MeshSpec(dp=2, fsdp=2, ep=2)],
    ids=["dp4ep2", "dp2fsdp2ep2"],
)
def test_moe_train_step_learns_on_ep_mesh(mesh_spec):
    cfg = LlamaConfig.tiny(num_experts=4, scan_layers=True)
    model = LlamaModel(cfg)
    res = accelerate(
        model,
        config=AccelerateConfig(mesh_spec=mesh_spec),
        batch_shape=(8, 32),
    )
    state = res.init_fn(jax.random.PRNGKey(0))
    # expert params actually sharded over ep
    wg = state.params["layers"]["layer"]["mlp"]["w_gate"]
    assert "ep" in str(wg.sharding.spec), wg.sharding.spec
    ids = jax.random.randint(
        jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size
    ).astype(jnp.int32)
    losses = []
    for _ in range(4):
        state, metrics = res.train_step(state, {"input_ids": ids})
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_moe_ep_parity_with_dp():
    """ep=2 sharding must reproduce the dp-only loss trajectory (same
    computation, different partitioning)."""
    cfg = LlamaConfig.tiny(num_experts=4, scan_layers=False, num_layers=1)
    model = LlamaModel(cfg)
    res_ep = accelerate(
        model,
        config=AccelerateConfig(mesh_spec=MeshSpec(dp=2, fsdp=2, ep=2)),
        batch_shape=(8, 32),
    )
    res_dp = accelerate(
        model,
        config=AccelerateConfig(mesh_spec=MeshSpec(dp=8)),
        batch_shape=(8, 32),
    )
    s_ep = res_ep.init_fn(jax.random.PRNGKey(0))
    s_dp = res_dp.init_fn(jax.random.PRNGKey(0))
    ids = jax.random.randint(
        jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size
    ).astype(jnp.int32)
    for _ in range(2):
        s_ep, m_ep = res_ep.train_step(s_ep, {"input_ids": ids})
        s_dp, m_dp = res_dp.train_step(s_dp, {"input_ids": ids})
        assert np.isclose(
            float(m_ep["loss"]), float(m_dp["loss"]), rtol=2e-3
        ), (float(m_ep["loss"]), float(m_dp["loss"]))


def test_moe_aux_loss_reaches_router_grad():
    """The load-balance loss must backprop into the router kernel — if the
    sown losses were dropped, the router would get gradient only through
    the combine weights."""
    from dlrover_tpu.accel.accelerate import default_loss_fn

    cfg = LlamaConfig.tiny(
        num_experts=4, scan_layers=False, num_layers=1, moe_aux_loss_coef=1.0
    )
    model = LlamaModel(cfg)
    ids = jax.random.randint(
        jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size
    ).astype(jnp.int32)
    params = nn.unbox(model.init(jax.random.PRNGKey(0), ids))["params"]
    loss_fn = default_loss_fn(model)
    loss_with, _ = loss_fn(params, {"input_ids": ids})

    cfg0 = LlamaConfig.tiny(
        num_experts=4, scan_layers=False, num_layers=1, moe_aux_loss_coef=0.0
    )
    loss_without, _ = default_loss_fn(LlamaModel(cfg0))(
        params, {"input_ids": ids}
    )
    # aux coefficient changes the loss => sown losses are being collected
    assert abs(float(loss_with) - float(loss_without)) > 1e-4


# --- the grouped matmul reads a layer's weights in the scan's stack --------

# for the CPU's compiler, which takes most of these tests' time: both sides
# of a comparison are compiled alike
_QUICK = {"xla_backend_optimization_level": 0,
          "xla_llvm_disable_expensive_passes": True}
_STACK = (3, 4, 32, 48)       # layers, groups, k, n
# 64 rows: uneven, with an EMPTY group and one of a single row; and the
# groups of a layer that holds a share of its experts (``experts_held``),
# which end before the rows do
_GROUPS = {"uneven": (0, 37, 1, 26), "held": (9, 0, 20, 3)}


def _stack_case(transposed):
    """lhs [64, k] and a stack [3, 4, k, n] in bf16; ``transposed`` swaps
    k and n (``w_down`` beside ``w_gate``)."""
    layers, groups, k, n = _STACK
    if transposed:
        k, n = n, k
    key_l, key_s = jax.random.split(jax.random.PRNGKey(7))
    lhs = jax.random.normal(key_l, (64, k), jnp.float32)
    stack = jax.random.normal(key_s, (layers, groups, k, n), jnp.float32)
    return lhs.astype(jnp.bfloat16), (stack / 8).astype(jnp.bfloat16)


@functools.lru_cache(maxsize=None)
def _stack_grads(groups, transposed):
    """For one case: the operands, and the jitted value and gradients of a
    loss over ``grouped_matmul`` at a TRACED layer index (as in a scan),
    with the stack as the kernels' operand and with its slice.  Compiled
    once a case: the three layers share it."""
    from dlrover_tpu.models.moe import grouped_matmul

    lhs, stack = _stack_case(transposed)
    sizes = jnp.asarray(_GROUPS[groups], jnp.int32)
    live = (jnp.arange(64) < sizes.sum())[:, None]

    def loss(lhs, stack, index, in_stack):
        where = (jax.lax.stop_gradient(stack), index) if in_stack else None
        out = grouped_matmul(lhs, stack[index], sizes, where)
        out = jnp.where(live, out, 0).astype(jnp.float32)
        return jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(
            out.shape))), out

    grad = jax.grad(loss, argnums=(0, 1), has_aux=True)
    both = jax.jit(lambda lhs, stack, index: (
        grad(lhs, stack, index, True), grad(lhs, stack, index, False)))
    return lhs, stack, live, both.lower(
        lhs, stack, jnp.int32(0)).compile(_QUICK)


@pytest.mark.parametrize("transposed", [False, True], ids=["gate", "down"])
@pytest.mark.parametrize("groups", sorted(_GROUPS))
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_grouped_matmul_in_the_stack_equals_its_slice(layer, groups,
                                                      transposed):
    """The stacked call at layer ``l`` is ``grouped_matmul`` on
    ``stack[l]``, bit for bit: the value, the rows' gradient (the kernel
    with the transposed right-hand side) and the weights' gradient, which
    lands in row ``l`` of a zero stack."""
    lhs, stack, live, both = _stack_grads(groups, transposed)
    ((d_lhs, d_stack), out), ((r_lhs, r_stack), ref) = both(
        lhs, stack, jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    np.testing.assert_array_equal(
        np.asarray(jnp.where(live, d_lhs, 0), np.float32),
        np.asarray(jnp.where(live, r_lhs, 0), np.float32))
    np.testing.assert_array_equal(np.asarray(d_stack, np.float32),
                                  np.asarray(r_stack, np.float32))
    others = np.delete(np.asarray(d_stack, np.float32), layer, axis=0)
    assert not others.any() and np.asarray(d_stack, np.float32).any()


def test_grouped_matmul_takes_its_values_from_the_stack():
    """Given a stack, the layer's own array is not read (so XLA never
    makes it)."""
    from dlrover_tpu.models.moe import grouped_matmul

    lhs, stack = _stack_case(False)
    sizes = jnp.asarray(_GROUPS["uneven"], jnp.int32)
    wrong = jnp.zeros_like(stack[1])
    ref = grouped_matmul(lhs, stack[1], sizes)
    assert np.asarray(ref, np.float32).any()
    np.testing.assert_array_equal(
        np.asarray(grouped_matmul(lhs, wrong, sizes, (stack, jnp.int32(1)))),
        np.asarray(ref))


def _olmoe_shaped(**kw):
    return LlamaConfig.tiny(
        num_experts=4, moe_top_k=2, qk_norm=True, num_layers=3,
        scan_layers=True, remat=True, **kw)


def _laguna_shaped(**kw):
    """Layer 0 dense and unrolled, two periods (window, full) of sparse
    layers that hold experts 2-5 of 8: Laguna's kinds of layer, its period
    of four cut to two for the interpreter's sake."""
    kinds = LlamaConfig.laguna_xs2(num_layers=5).layers
    return LlamaConfig.laguna_xs2(
        num_layers=5, layers=(kinds[0], kinds[3], kinds[4]) + kinds[3:5],
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, moe_shared_width=32, num_experts=8,
        moe_experts_held=(2, 4), moe_top_k=2, max_seq_len=64, head_dim=16,
        remat=True, **kw)


@pytest.mark.parametrize("param_dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("shape", [_olmoe_shaped, _laguna_shaped],
                         ids=["olmoe", "laguna"])
def test_scanned_sparse_model_is_the_sliced_one(shape, param_dtype,
                                                monkeypatch):
    """Under ``nn.scan`` + ``nn.remat`` the model whose grouped matmuls
    read the stack gives the loss and every gradient of the model whose
    scan hands them nothing (the wiring before PR 33), bit for bit; with
    bf16 parameters on one device the stack really is the kernels'
    operand; with f32 parameters (cast a layer at a time) the trace is the
    sliced model's to the letter, and on a mesh of several devices the
    kernels get no stack either."""
    from dlrover_tpu.accel.accelerate import default_loss_fn

    cfg = shape(dtype=jnp.bfloat16, param_dtype=param_dtype)
    in_stack = param_dtype == jnp.bfloat16
    model = LlamaModel(cfg)
    ids = jax.random.randint(
        jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size).astype(jnp.int32)
    # values drawn leaf by leaf: ``init`` would run the whole model in the
    # interpreter for them
    params = nn.unbox(jax.eval_shape(
        model.init, jax.random.PRNGKey(0), ids))["params"]
    if in_stack:
        rng = np.random.RandomState(0)
        params = jax.tree_util.tree_map(
            lambda x: jnp.asarray(
                (x.ndim == 1) + 0.05 * rng.standard_normal(x.shape), x.dtype),
            params)
    # all layers' groups in one row is what a kernel reads in place
    scanned = cfg.num_layers if cfg.layers is None else 2
    held = (cfg.moe_experts_held or (0, cfg.num_experts))[1]
    in_place = f"bf16[{scanned * held},{cfg.hidden_size},"

    def trace():
        traced = jax.jit(jax.value_and_grad(
            lambda p: default_loss_fn(model)(p, {"input_ids": ids})[0])
        ).trace(params)
        return traced, re.sub(r"0x[0-9a-f]+", "0x", str(traced.jaxpr))

    traced, text = trace()
    assert (in_place in text) == in_stack
    if in_stack:
        mine = traced.lower().compile(_QUICK)(params)
        with MeshSpec(dp=2).build_mesh(jax.devices()[:2]):
            assert in_place not in trace()[1]
    monkeypatch.setattr(LlamaModel, "_stacked_experts",
                        lambda self, name, length: (None, None))
    traced, ref_text = trace()
    assert in_place not in ref_text
    if not in_stack:    # the same trace, so the same numbers
        assert text == ref_text
        return
    ref = traced.lower().compile(_QUICK)(params)
    assert float(mine[0]) == float(ref[0])
    for (path, g), r in zip(
            jax.tree_util.tree_flatten_with_path(mine[1])[0],
            jax.tree_util.tree_leaves(ref[1])):
        np.testing.assert_array_equal(
            np.asarray(g, np.float32), np.asarray(r, np.float32),
            err_msg=jax.tree_util.keystr(path))
        # the drawn weights do reach the experts of every scanned layer
        assert "w_gate" not in jax.tree_util.keystr(path) or all(
            np.asarray(layer, np.float32).any() for layer in g)


_TREES = {   # leaves, and the digest of the listing below, as of PR 31
    "olmoe_1b_7b": (15, "0d8fcff66f108fdea99e3f2ca6af9775219718097c26b76e"
                        "06fcf2abb0006f8d"),
    "laguna_xs2": (111, "2acd4a64855d7b8cf50e362f704d5dbe1ed9fe997c7b6f10"
                        "f67bcb73a94fb952"),
    "llama2_7b": (12, "56c116d89e062dcf30c3191d5ec608ec27f622e328ddd79558"
                      "4a2b283c2ceef3"),
}
# the tiny dense scanned model's gradient, traced (addresses scrubbed);
# re-pinned in PR 49, which changed the LOSS behind the model (labels
# shifted in place of sliced logits, the custom-VJP cross entropy): the
# trace up to the logits is, line for line, the one pinned before
_DENSE_JAXPR = "192dff4570b869795eca753f2a33f77e6851ad454cf74e49e881807417c572a6"


# ... and a tiny sparse one's whose layers hold EVERY expert, with each
# equation's scopes (``name_stack``): taken on the parent of PR 57
# (97b77cf), to the letter (``train-moe-dropless`` runs this layer)
_ALL_HELD_JAXPR = "5fc92c02ef859b47cb161e79ad5e952b44bbcfc32b57a8c90974a1cb380500e3"


def _digest(text):
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("preset", sorted(_TREES))
def test_parameter_tree_is_what_it_was(preset):
    """Every parameter's path, shape, dtype and logical axes at the
    preset's full size (shapes only), against the listing of the tree
    BEFORE the grouped matmuls read the stack: the benchmark's drivers
    rebuild their references from this tree and a checkpoint is laid out
    by it.  A change that means to move it re-pins the digest."""
    ids = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    boxed = jax.eval_shape(LlamaModel(LlamaConfig.from_preset(preset)).init,
                           jax.random.PRNGKey(0), ids)["params"]
    flat = jax.tree_util.tree_flatten_with_path(
        boxed, is_leaf=lambda x: isinstance(x, nn.Partitioned))[0]
    lines = sorted(
        "/".join(str(getattr(p, "key", p)) for p in path)
        + f" {leaf.value.shape} {leaf.value.dtype} {leaf.names}"
        for path, leaf in flat)
    if preset == "olmoe_1b_7b":
        assert ("layers/layer/mlp/w_gate (16, 64, 2048, 1024) float32 "
                "('layers', 'expert', 'embed', 'mlp')") in lines
    assert (len(lines), _digest("\n".join(lines))) == _TREES[preset], \
        "\n".join(lines)


def test_dense_model_traces_what_it_did(tmp_path):
    """A model without experts hands its scan nothing new: the traced
    gradient of the tiny dense scanned model is, to the letter, the one of
    the wiring before PR 33 (so the dense cells' compiled steps are the
    same programs).  A change that means to move a dense model's trace, or
    a JAX that prints one otherwise, re-pins the digest: the text is left
    in a file to diff with the one an older tree leaves."""
    from dlrover_tpu.accel.accelerate import default_loss_fn

    cfg = LlamaConfig.tiny(scan_layers=True, remat=True, num_layers=3,
                           dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    model = LlamaModel(cfg)
    ids = jnp.zeros((2, 32), jnp.int32)
    params = jax.eval_shape(
        lambda: nn.unbox(model.init(jax.random.PRNGKey(0), ids))["params"])
    text = str(jax.make_jaxpr(jax.grad(lambda p: default_loss_fn(model)(
        p, {"input_ids": ids})[0]))(params))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    (tmp_path / "dense_jaxpr.txt").write_text(text)
    assert _digest(text) == _DENSE_JAXPR, \
        f"jax {jax.__version__}; the trace: {tmp_path / 'dense_jaxpr.txt'}"


def test_a_layer_that_holds_every_expert_traces_what_it_did(tmp_path):
    """PR 57 gave a layer that holds a SHARE of its experts a compact
    sorted buffer; one that holds them all keeps the buffer of every pick
    and no ``cond``: the traced gradient of a tiny sparse scanned model,
    the kernels' own jaxprs and every equation's scopes in it (the
    benchmark reads device time by them), is the one of the tree before.
    Re-pinned like ``_DENSE_JAXPR``, by a change that means to move it."""
    from dlrover_tpu.accel.accelerate import default_loss_fn

    cfg = LlamaConfig.tiny(scan_layers=True, remat=True, num_layers=3,
                           num_experts=4, moe_top_k=2, dtype=jnp.bfloat16,
                           param_dtype=jnp.bfloat16)
    model = LlamaModel(cfg)
    ids = jnp.zeros((2, 32), jnp.int32)
    params = jax.eval_shape(
        lambda: nn.unbox(model.init(jax.random.PRNGKey(0), ids))["params"])
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: default_loss_fn(model)(
        p, {"input_ids": ids})[0]))(params)
    text = re.sub(r"0x[0-9a-f]+", "0x", jaxpr.pretty_print(name_stack=True))
    assert "pallas_call" in text and "moe_dispatch" in text
    (tmp_path / "all_held_jaxpr.txt").write_text(text)
    assert _digest(text) == _ALL_HELD_JAXPR, \
        f"jax {jax.__version__}; the trace: {tmp_path / 'all_held_jaxpr.txt'}"


def test_flash_checkpoint_of_the_sliced_model_restores(tmp_path,
                                                       monkeypatch):
    """A flash checkpoint written by a trainer whose grouped matmuls get a
    layer's slice (the wiring before PR 33) restores into one whose
    kernels read the stack: same tree, same bytes, and the next step is
    the one the first trainer would have taken."""
    from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
    from dlrover_tpu.trainer.elastic.trainer import ElasticTrainer
    from dlrover_tpu.trainer.flash_checkpoint import SaverMode, StorageType

    job = uuid.uuid4().hex[:8]
    monkeypatch.setenv("DLROVER_JOB_UID", job)
    cfg = _olmoe_shaped(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    rng = np.random.RandomState(3)
    batches = [rng.randint(0, cfg.vocab_size, (2, 32)).astype(np.int32)
               for _ in range(3)]

    def trainer():
        tr = ElasticTrainer(
            LlamaModel(cfg), global_batch_size=2, micro_batch_per_shard=2,
            seq_len=32, checkpoint_dir=str(tmp_path / "ckpt"),
            saver_mode=SaverMode.LOCAL)
        tr.prepare(devices=jax.devices()[:1])
        return tr

    try:
        with monkeypatch.context() as sliced:
            sliced.setattr(LlamaModel, "_stacked_experts",
                           lambda self, name, length: (None, None))
            first = trainer()
            assert first.restore_or_init(jax.random.PRNGKey(0)) == 0
            for batch in batches[:2]:
                first.train_step(batch)
            assert first.save(StorageType.MEMORY)
            saved = jax.tree_util.tree_map(np.asarray, first.state.params)
            expected = float(first.train_step(batches[2])["loss"])
            first.close()
        second = trainer()
        assert second.restore_or_init(jax.random.PRNGKey(9)) == 2
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(saved)[0],
                jax.tree_util.tree_leaves(second.state.params)):
            np.testing.assert_array_equal(
                a, np.asarray(b), err_msg=jax.tree_util.keystr(path))
        assert float(second.train_step(batches[2])["loss"]) == expected
        second.close()
    finally:
        AsyncCheckpointSaver.reset()
        for f in os.listdir("/dev/shm"):
            if job in f:
                os.unlink(os.path.join("/dev/shm", f))
