"""MoE / expert-parallel tests (reference parity:
atorch/atorch/modules/moe/ — MOELayer token dispatch, top-k gating,
grouped-GEMM experts — tested in tiny worlds the same way the reference's
moe tests run 2-4 proc gloo worlds; here an 8-device CPU mesh).  The
dropless layer against a plain reference: tests/test_olmoe_reference.py."""

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
