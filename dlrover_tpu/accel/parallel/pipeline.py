"""Pipeline parallelism: SPMD GPipe over the ``pp`` mesh axis.

Parity target: the reference's pipeline strategy
(atorch/atorch/auto/opt_lib/pipeline_parallel_optimization.py:244, built on
PiPPy torch.rpc stage graphs, and the DeepSpeed 3D variant
ds_3d_parallel_optimization.py).  TPU-native design — no RPC, no stage
processes:

- The decoder-layer stack params (leading ``layers`` axis, created by the
  model's ``nn.scan``) are sharded over ``pp`` and viewed as
  ``[num_stages, layers_per_stage, ...]``.
- One ``shard_map`` manual over ONLY the ``pp`` axis (every other mesh axis
  stays in GSPMD "auto" mode, so dp/fsdp/tp/sp shardings inside each stage
  are still compiler-managed).
- A ``lax.scan`` over ``num_microbatches + num_stages - 1`` ticks runs the
  GPipe schedule: every stage applies its layer block to its current
  microbatch, then activations shift stage->stage+1 via
  ``lax.ppermute`` (rides ICI).
- Backward comes from plain AD through the scan (ppermute transposes to
  the reverse shift); stage blocks run under ``jax.checkpoint`` so the
  pipeline's live memory is per-tick, not per-schedule.

The bubble fraction is (S-1)/(M+S-1), as in GPipe — choose
``num_microbatches >= 4 * pp`` for <20%% bubble.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from dlrover_tpu.accel.parallel.mesh import MESH_AXES


def _stage_view(p: jax.Array, num_stages: int) -> jax.Array:
    """[L, ...] -> [S, L/S, ...] (contiguous blocks — layout-compatible with
    a PartitionSpec('pp') sharding of the leading axis)."""
    return p.reshape(num_stages, p.shape[0] // num_stages, *p.shape[1:])


def pipeline_blocks(
    stage_fn: Callable[[Any, jax.Array, Any], jax.Array],
    stacked_params: Any,
    x: jax.Array,
    extras: Any,
    *,
    mesh: Mesh,
    num_microbatches: int,
    remat: bool = True,
) -> jax.Array:
    """Run the layer stack over ``x`` through the GPipe schedule.

    stage_fn(stage_params, x_mb, extras_mb) -> (y_mb, aux) applies one
    stage's layers to one microbatch; ``aux`` is a scalar side loss (MoE
    load-balance/z-loss sum over the stage's layers — 0.0 for dense).
    ``x``: [batch, seq, hidden] global; ``extras``: pytree of per-example
    arrays with leading batch dim (or None leaves for broadcast data).
    Returns ``(y, aux_mean)`` with y [batch, seq, hidden] and aux_mean
    the per-microbatch mean of aux summed over stages (bubble ticks
    masked out).
    """
    num_stages = mesh.shape["pp"]
    if num_stages <= 1:
        raise ValueError("pipeline_blocks requires a pp mesh axis of size > 1")
    batch = x.shape[0]
    if batch % num_microbatches:
        raise ValueError(
            f"batch {batch} not divisible by num_microbatches {num_microbatches}"
        )
    mb = batch // num_microbatches
    m_count = num_microbatches

    def to_mb(a):
        if a is None:
            return None
        return a.reshape(m_count, mb, *a.shape[1:])

    # The activations enter the shard_map replicated over pp; their
    # cotangent is psum'ed over pp by shard_map AD.  Keep the BOUNDARY in
    # f32 (XLA CPU's all-reduce-promotion pass aborts on bf16 all-reduce;
    # on TPU the cast fuses away) — the pipeline runs in the original
    # dtype internally.
    orig_dtype = x.dtype
    boundary_dtype = (
        jnp.float32 if orig_dtype == jnp.bfloat16 else orig_dtype
    )
    x_mb = to_mb(x).astype(boundary_dtype)
    extras_mb = jax.tree_util.tree_map(to_mb, extras)

    staged = jax.tree_util.tree_map(
        lambda p: _stage_view(p, num_stages), stacked_params
    )

    body = stage_fn
    if remat:
        body = jax.checkpoint(stage_fn)

    auto_axes = frozenset(a for a in MESH_AXES if a != "pp")
    param_spec = PartitionSpec("pp")
    data_spec = PartitionSpec()  # replicated across pp (sharded over auto axes)

    def pipelined(staged_params, x_mb, extras_mb):
        stage = jax.lax.axis_index("pp")
        x_mb = x_mb.astype(orig_dtype)
        local_params = jax.tree_util.tree_map(lambda p: p[0], staged_params)
        ticks = m_count + num_stages - 1

        def tick_fn(carry, t):
            act, out_buf, aux_sum = carry
            # stage s processes microbatch m = t - s this tick
            m = t - stage
            m_clamped = jnp.clip(m, 0, m_count - 1)
            feed = jax.lax.dynamic_index_in_dim(
                x_mb, m_clamped, axis=0, keepdims=False
            )
            inp = jnp.where(stage == 0, feed, act)
            mb_extras = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, m_clamped, axis=0, keepdims=False
                ),
                extras_mb,
            )
            y, aux = body(local_params, inp, mb_extras)
            # bubble ticks run clamped garbage microbatches whose aux
            # must not count (their activations are already ignored)
            valid = ((m >= 0) & (m < m_count)).astype(jnp.float32)
            aux_sum = aux_sum + valid * aux.astype(jnp.float32)
            # shift to the next stage (last stage's send wraps to 0 and is
            # ignored — stage 0 always reads fresh microbatches)
            shifted = jax.lax.ppermute(
                y,
                "pp",
                [(i, (i + 1) % num_stages) for i in range(num_stages)],
            )
            # last stage finished microbatch m = t - (S-1)
            out_idx = t - (num_stages - 1)
            write = (stage == num_stages - 1) & (out_idx >= 0)
            out_clamped = jnp.clip(out_idx, 0, m_count - 1)
            current = jax.lax.dynamic_index_in_dim(
                out_buf, out_clamped, axis=0, keepdims=False
            )
            new_slice = jnp.where(write, y, current)
            out_buf = jax.lax.dynamic_update_index_in_dim(
                out_buf, new_slice, out_clamped, axis=0
            )
            return (shifted, out_buf, aux_sum), None

        init = (
            jnp.zeros_like(x_mb[0]),
            jnp.zeros_like(x_mb),
            jnp.zeros((), jnp.float32),
        )
        (_, out_buf, aux_sum), _ = jax.lax.scan(
            tick_fn, init, jnp.arange(ticks, dtype=jnp.int32)
        )
        # broadcast the last stage's buffer to every pp peer (f32 for the
        # same boundary reason as above)
        mask = (stage == num_stages - 1).astype(jnp.float32)
        out = jax.lax.psum(out_buf.astype(jnp.float32) * mask, "pp")
        # aux: sum over stages' layers, mean over microbatches (matching
        # the non-pp path where each layer's aux is computed once over
        # the full batch)
        aux_mean = jax.lax.psum(aux_sum, "pp") / m_count
        return out, aux_mean

    sm = jax.shard_map(
        pipelined,
        mesh=mesh,
        in_specs=(
            jax.tree_util.tree_map(lambda _: param_spec, staged),
            data_spec,
            jax.tree_util.tree_map(lambda _: data_spec, extras_mb),
        ),
        out_specs=(data_spec, data_spec),
        check_vma=False,
        axis_names={"pp"},
    )
    out_mb, aux = sm(staged, x_mb, extras_mb)
    out_mb = out_mb.astype(orig_dtype)
    return out_mb.reshape(batch, *out_mb.shape[2:]), aux


def make_pipelined_forward(
    model,
    mesh: Mesh,
    num_microbatches: Optional[int] = None,
    remat: bool = True,
):
    """A drop-in ``forward_fn(params, batch, return_hidden)`` for
    :func:`dlrover_tpu.accel.accelerate.default_loss_fn` that runs the
    model's decoder stack through the pp pipeline.

    Embedding, final norm, and the lm head run under plain GSPMD on every
    stage (they are cheap next to the stack and keeping them SPMD avoids
    special first/last-stage program branches — the TPU analogue of the
    reference's pipe_split graph cuts).  Requires the model to be a
    scan-layers ``LlamaModel`` (the flagship family); the stacked layer
    params live at ``params['layers']['layer']``.
    """
    from dlrover_tpu.accel.parallel.mesh import with_logical_constraint
    from dlrover_tpu.models import moe
    from dlrover_tpu.models.llama import DecoderLayer, RMSNorm

    cfg = model.config
    if not cfg.scan_layers:
        raise ValueError("pipeline parallelism requires scan_layers=True")
    if getattr(cfg, "layers", None) is not None:
        raise ValueError(
            "pipeline parallelism stacks ONE kind of layer per stage; this "
            "model describes its layers one by one (LlamaConfig.layers)")
    num_stages = mesh.shape["pp"]
    if cfg.num_layers % num_stages:
        raise ValueError(
            f"num_layers {cfg.num_layers} not divisible by pp {num_stages}"
        )
    # same default as AccelerateConfig.pp_microbatches: 2*pp — bubble
    # fraction (pp-1)/(2pp-1)
    m_count = num_microbatches or 2 * num_stages

    layer_mod = DecoderLayer(cfg)
    norm_mod = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype)

    def stage_fn(stage_params, x, extras):
        positions, segment_ids = extras

        def one_layer(carry, layer_params):
            h, aux = carry
            if cfg.num_experts:
                # MoE layers sow load-balance/z losses; collect their sum
                # (the loss takes the mean over layers, below) into the
                # pipeline's scalar side channel (pp x ep composition:
                # experts stay ep-sharded inside the stage — GSPMD manages
                # ep while shard_map only manualizes pp)
                h, vu = layer_mod.apply(
                    {"params": layer_params}, h, positions, segment_ids,
                    mutable=["moe_losses"],
                )
                aux = aux + moe.aux_loss(vu["moe_losses"])
            else:
                h = layer_mod.apply(
                    {"params": layer_params}, h, positions, segment_ids
                )
            return (h, aux), None

        (x, aux), _ = jax.lax.scan(
            one_layer, (x, jnp.zeros((), jnp.float32)), stage_params
        )
        return x, aux

    def forward(params: Dict[str, Any], batch: Dict[str, jax.Array],
                return_hidden: bool = False):
        ids = batch["input_ids"]
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.arange(ids.shape[1])
        if positions.ndim == 1:
            # per-example everywhere: extras are microbatched along batch,
            # and shard_map inputs beat closures (no implicit capture)
            positions = jnp.broadcast_to(positions[None], ids.shape)
        segment_ids = batch.get("segment_ids")

        table = params["embed_tokens"]["embedding"]
        x = jnp.asarray(table, cfg.dtype)[ids]
        x = with_logical_constraint(x, ("batch", "seq", "act_embed"))

        extras = (positions, segment_ids)
        stacked = params["layers"]["layer"]

        x, aux = pipeline_blocks(
            stage_fn,
            stacked,
            x,
            extras,
            mesh=mesh,
            num_microbatches=m_count,
            remat=remat,
        )
        # routing statistics do not cross the pipeline's scalar channel
        var_updates = {"moe_losses": {"pipeline": {
            "aux_loss": aux / cfg.num_layers}}} if cfg.num_experts \
            else {}

        x = norm_mod.apply({"params": params["final_norm"]}, x)
        if return_hidden:
            return x, var_updates
        if cfg.tie_embeddings:
            logits = x.astype(cfg.param_dtype) @ table.T
        else:
            kernel = params["lm_head"]["kernel"]
            logits = x @ jnp.asarray(kernel, cfg.dtype)
        logits = with_logical_constraint(logits, ("batch", "seq", "vocab"))
        return logits, var_updates

    return forward
