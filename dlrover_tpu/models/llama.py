"""Llama-family decoder, TPU-native (flax.linen + logical partitioning).

This is the flagship model of the framework — the counterpart of the
reference's headline benchmark model (Llama2-7B FSDP, reference:
atorch/examples/llama2/README.md:395-411 and its HF-module fast-path
replacements in atorch/atorch/modules/transformer/layers.py).  Design is
TPU-first rather than a port:

- Parameters and activations carry *logical* axis names
  (``nn.with_logical_partitioning``); the mesh rules in
  :mod:`dlrover_tpu.accel.parallel.mesh` turn those into GSPMD shardings —
  DP/FSDP/TP/SP are sharding rules, not module wrappers.
- Layers run under ``nn.scan`` (one compiled block body instead of
  n_layers copies) with optional ``nn.remat`` — the analogue of the
  reference's activation-checkpoint wrapping
  (atorch/atorch/auto/opt_lib/checkpoint_optimization.py:217).
- Attention dispatches to the Pallas flash-attention kernel on TPU
  (:func:`dlrover_tpu.ops.attention.dot_product_attention`).
- Matmuls run in ``bfloat16`` with float32 params/accumulators (MXU-native).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.accel.parallel.mesh import with_logical_constraint
from dlrover_tpu.ops.attention import dot_product_attention

Dtype = Any


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    scan_layers: bool = True
    remat: bool = True
    # "nothing_saveable" = full remat; "dots_with_no_batch_dims_saveable"
    # keeps matmul outputs (selective checkpointing).
    remat_policy: str = "nothing_saveable"
    tie_embeddings: bool = False
    # MoE (0 = dense): experts shard over the ep mesh axis (reference:
    # atorch/atorch/modules/moe/moe_layer.py)
    num_experts: int = 0
    moe_top_k: int = 2
    # the top-k router weights renormalised to sum to one (Mixtral) or
    # left as the softmax gave them (OLMoE's ``norm_topk_prob: false``)
    moe_norm_topk_prob: bool = True
    # added to the task loss as a MEAN over the MoE layers
    moe_aux_loss_coef: float = 0.01
    moe_z_loss_coef: float = 1e-3
    # RMSNorm with a learned scale over the WHOLE projected query and the
    # whole projected key, before the split into heads and before RoPE
    # (OLMoE, OLMo-2)
    qk_norm: bool = False
    # q/k/v projection biases (Qwen2-family checkpoints; o_proj stays
    # bias-free in every supported architecture)
    attention_bias: bool = False
    # output-logit multiplier; muP sets this to base_width/width so the
    # logit scale is width-invariant (dlrover_tpu.accel.mup)
    logit_scale: float = 1.0
    # fp8 matmuls (e4m3 operands / e5m2 grads, current scaling) in every
    # projection — the reference's TransformerEngine fp8 AMP equivalent
    # (dlrover_tpu.ops.fp8; reference amp_optimization.py:377)
    fp8: bool = False
    # int8 W8A8 projections on the MXU (2x bf16 rate on v5e) for
    # eval/generation — routes every Dense contraction through the
    # Pallas int8 GEMM (ops/pallas/quant_matmul.int8_dot_general; the
    # reference's csrc int8 GEMM serving path).  Inference-only: the
    # kernel defines no VJP.
    w8a8: bool = False

    @property
    def dot_general(self):
        if self.w8a8:
            from dlrover_tpu.ops.pallas.quant_matmul import (
                int8_dot_general,
            )

            return int8_dot_general
        if self.fp8:
            from dlrover_tpu.ops.fp8 import fp8_dot_general

            return fp8_dot_general
        return jax.lax.dot_general

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def num_params(self) -> int:
        """Approximate parameter count (for MFU accounting)."""
        h, v = self.hidden_size, self.vocab_size
        d = self.head_dim_
        attn = h * d * (self.num_heads * 2 + self.num_kv_heads * 2)
        mlp = 3 * h * self.intermediate_size
        if self.num_experts:
            mlp = mlp * self.num_experts + h * self.num_experts  # + router
        per_layer = attn + mlp + 2 * h
        if self.qk_norm:
            per_layer += d * (self.num_heads + self.num_kv_heads)
        emb = v * h * (1 if self.tie_embeddings else 2)
        return self.num_layers * per_layer + emb + h

    @classmethod
    def llama2_7b(cls, **kw) -> "LlamaConfig":
        return cls(**kw)

    @classmethod
    def olmoe_1b_7b(cls, **kw) -> "LlamaConfig":
        """allenai/OLMoE-1B-7B-0125-Instruct as its config.json has it:
        MHA with QK-norm, 64 experts of width 1024, 8 a token, weights
        not renormalised; the two loss coefficients are OlmoeConfig's
        default and the OLMoE report's."""
        base = dict(
            vocab_size=50304,
            hidden_size=2048,
            intermediate_size=1024,
            num_layers=16,
            num_heads=16,
            num_kv_heads=16,
            max_seq_len=4096,
            rope_theta=10000.0,
            rms_norm_eps=1e-5,
            num_experts=64,
            moe_top_k=8,
            moe_norm_topk_prob=False,
            moe_aux_loss_coef=0.01,
            moe_z_loss_coef=1e-3,
            qk_norm=True,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def from_preset(
        cls, name: str, num_layers: int = 0, **kw
    ) -> "LlamaConfig":
        """A named preset, optionally cut in DEPTH only (``num_layers``
        > 0) — how the entry points name a model: widths are the
        preset's own, depth is what the chip at hand holds."""
        if name not in PRESETS:
            raise ValueError(
                f"unknown model preset {name!r}: use one of {PRESETS}")
        if num_layers:
            kw["num_layers"] = int(num_layers)
        return getattr(cls, name)(**kw)

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        base = dict(
            vocab_size=256,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            max_seq_len=128,
            scan_layers=False,
            remat=False,
        )
        base.update(kw)
        return cls(**base)


#: presets the entry points (examples/, the serving worker) can name
PRESETS = ("tiny", "llama2_7b", "olmoe_1b_7b")


def resolve_remat_policy(name: str):
    """Checkpoint policy by name.

    - ``"names:a,b"`` -> ``save_only_these_names(a, b)`` over the
      model's checkpoint_name tags (qkv_proj / attn_out / mlp_out);
    - ``"offload_names:a,b"`` -> selective activation OFFLOADING: the
      named activations are saved to pinned HOST memory during forward
      and fetched back for backward (XLA overlaps the D2H/H2D with
      compute) instead of occupying HBM — the reference's
      selective_offloading_checkpoint.py:252, TPU-native via XLA memory
      spaces rather than a CUDA stream pool;
    - ``"offload_dots"`` -> offload every matmul output a plain
      ``dots_with_no_batch_dims_saveable`` policy would have kept in
      HBM (the measured seq-16k memory wall, PERF.md);
    - anything else -> the eponymous ``jax.checkpoint_policies`` entry.
    """
    if name.startswith("names:"):
        tags = [t for t in name[len("names:"):].split(",") if t]
        return jax.checkpoint_policies.save_only_these_names(*tags)
    if name.startswith("offload_names:"):
        tags = [t for t in name[len("offload_names:"):].split(",") if t]
        return jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=tags,
            offload_src="device", offload_dst="pinned_host",
        )
    if name == "offload_dots":
        return jax.checkpoint_policies.offload_dot_with_no_batch_dims(
            "device", "pinned_host",
        )
    return getattr(jax.checkpoint_policies, name)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones_init(), ("norm",)),
            (x.shape[-1],),
            self.param_dtype,
        )
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + self.eps)
        return (y * scale.astype(jnp.float32)).astype(self.dtype)


def rope_frequencies(head_dim: int, max_len: int, theta: float) -> jax.Array:
    """[max_len, head_dim//2] rotation angles."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    pos = jnp.arange(max_len, dtype=jnp.float32)
    return jnp.outer(pos, inv)


def apply_rope(x: jax.Array, angles: jax.Array) -> jax.Array:
    """x: [b, s, h, d]; angles: [s, d//2] (shared positions) or
    [b, s, d//2] (per-example positions, e.g. packed sequences)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    # Insert the head axis; a leading batch axis broadcasts either way.
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    if angles.ndim == 2:
        cos, sin = cos[None], sin[None]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


class Attention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        positions: jax.Array,
        segment_ids: Optional[jax.Array] = None,
        decode: bool = False,
        cache_len: Optional[int] = None,
    ) -> jax.Array:
        cfg = self.config
        d = cfg.head_dim_
        init = nn.initializers.lecun_normal()
        q_proj = nn.DenseGeneral(
            (cfg.num_heads, d),
            axis=-1,
            use_bias=cfg.attention_bias,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            dot_general=cfg.dot_general,
            kernel_init=nn.with_logical_partitioning(
                init, ("embed", "heads", "head_dim")
            ),
            name="q_proj",
        )
        kv_features = (cfg.num_kv_heads, d)
        k_proj = nn.DenseGeneral(
            kv_features, axis=-1, use_bias=cfg.attention_bias,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, dot_general=cfg.dot_general,
            kernel_init=nn.with_logical_partitioning(
                init, ("embed", "kv_heads", "head_dim")
            ),
            name="k_proj",
        )
        v_proj = nn.DenseGeneral(
            kv_features, axis=-1, use_bias=cfg.attention_bias,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, dot_general=cfg.dot_general,
            kernel_init=nn.with_logical_partitioning(
                init, ("embed", "kv_heads", "head_dim")
            ),
            name="v_proj",
        )
        o_proj = nn.DenseGeneral(
            cfg.hidden_size,
            axis=(-2, -1),
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            dot_general=cfg.dot_general,
            kernel_init=nn.with_logical_partitioning(
                init, ("heads", "head_dim", "embed")
            ),
            name="o_proj",
        )

        q = q_proj(x)
        k = k_proj(x)
        v = v_proj(x)
        if cfg.qk_norm:
            def whole(name, y):
                flat = y.reshape(*y.shape[:-2], y.shape[-2] * y.shape[-1])
                return RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                               name=name)(flat).reshape(y.shape)

            q, k = whole("q_norm", q), whole("k_norm", k)
        q = with_logical_constraint(q, ("batch", "seq", "heads", "head_dim"))
        k = with_logical_constraint(k, ("batch", "seq", "kv_heads", "head_dim"))
        v = with_logical_constraint(v, ("batch", "seq", "kv_heads", "head_dim"))

        angles = rope_frequencies(d, cfg.max_seq_len, cfg.rope_theta)[positions]
        q = checkpoint_name(apply_rope(q, angles), "qkv_proj")
        k = checkpoint_name(apply_rope(k, angles), "qkv_proj")
        v = checkpoint_name(v, "qkv_proj")

        if decode:
            # KV-cache decode: append this call's K/V at the caller-given
            # positions (prefill writes [0, P); steps write one column)
            # and attend over the whole cache with a position mask.  The
            # write offset is positions[0] — the caller's position stream
            # IS the cache clock, so no separate index variable can skew.
            # ``cache_len`` sizes the cache to the actual generation
            # horizon (prompt+new), not max_seq_len — at 16 new tokens on
            # a 4k-context config that is ~200x less cache memory and
            # attention work per step.
            assert segment_ids is None, (
                "packed sequences are not supported in decode: the cache "
                "mask is position-only and would attend across segments"
            )
            length = cache_len or cfg.max_seq_len
            batch = x.shape[0]
            cache_shape = (batch, length, cfg.num_kv_heads, d)
            ck = self.variable("cache", "cached_key",
                               jnp.zeros, cache_shape, k.dtype)
            cv = self.variable("cache", "cached_value",
                               jnp.zeros, cache_shape, v.dtype)
            offset = positions[0].astype(jnp.int32)
            ck.value = jax.lax.dynamic_update_slice(
                ck.value, k, (0, offset, 0, 0))
            cv.value = jax.lax.dynamic_update_slice(
                cv.value, v, (0, offset, 0, 0))
            key_pos = jnp.arange(length)
            # [q, kv] True where the key is visible to the query
            mask = key_pos[None, :] <= positions[:, None]
            reps = cfg.num_heads // cfg.num_kv_heads
            kk = jnp.repeat(ck.value, reps, axis=2) if reps > 1 else ck.value
            vv = jnp.repeat(cv.value, reps, axis=2) if reps > 1 else cv.value
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", q.astype(jnp.float32),
                kk.astype(jnp.float32)) / jnp.sqrt(float(d))
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum(
                "bhqk,bkhd->bqhd", probs, vv.astype(jnp.float32)
            ).astype(x.dtype)
            return o_proj(out)

        out = dot_product_attention(q, k, v, causal=True, segment_ids=segment_ids)
        out = checkpoint_name(out, "attn_out")
        out = with_logical_constraint(out, ("batch", "seq", "heads", "head_dim"))
        return o_proj(out)


class MLP(nn.Module):
    """SwiGLU feed-forward."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        init = nn.initializers.lecun_normal()
        dense = lambda feat, axes, name: nn.DenseGeneral(  # noqa: E731
            feat, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(init, axes), name=name,
            dot_general=cfg.dot_general,
        )
        # (the lm_head stays bf16 — the last projection is the standard
        # fp8-recipe exclusion: logit quantization hurts loss directly)
        gate = dense(cfg.intermediate_size, ("embed", "mlp"), "gate_proj")(x)
        up = dense(cfg.intermediate_size, ("embed", "mlp"), "up_proj")(x)
        h = nn.silu(gate) * up
        h = with_logical_constraint(h, ("batch", "seq", "mlp"))
        # Deliberately NOT checkpoint-named: the wide [.., intermediate]
        # tensors dominate saved-activation memory; the "names" remat
        # policy recomputes them in backward instead of storing them.
        return checkpoint_name(
            dense(cfg.hidden_size, ("mlp", "embed"), "down_proj")(h), "mlp_out"
        )


class DecoderLayer(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        positions: jax.Array,
        segment_ids: Optional[jax.Array] = None,
        decode: bool = False,
        cache_len: Optional[int] = None,
    ) -> jax.Array:
        cfg = self.config
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="input_norm")(x)
        x = x + Attention(cfg, name="attn")(h, positions, segment_ids,
                                            decode=decode,
                                            cache_len=cache_len)
        x = with_logical_constraint(x, ("batch", "seq", "act_embed"))
        h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="post_norm")(x)
        if cfg.num_experts:
            from dlrover_tpu.models.moe import MoEMLP

            mlp = MoEMLP(
                hidden_size=cfg.hidden_size,
                intermediate_size=cfg.intermediate_size,
                num_experts=cfg.num_experts,
                top_k=cfg.moe_top_k,
                norm_topk_prob=cfg.moe_norm_topk_prob,
                aux_loss_coef=cfg.moe_aux_loss_coef,
                z_loss_coef=cfg.moe_z_loss_coef,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                fp8=cfg.fp8,
                name="mlp",
            )
        else:
            mlp = MLP(cfg, name="mlp")
        x = x + mlp(h)
        return with_logical_constraint(x, ("batch", "seq", "act_embed"))


class _ScanLayer(nn.Module):
    """DecoderLayer adapted to nn.scan's (carry, None) calling convention."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, carry, _):
        x, positions, segment_ids = carry
        x = DecoderLayer(self.config, name="layer")(x, positions, segment_ids)
        return (x, positions, segment_ids), None


class LlamaModel(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(
        self,
        input_ids: jax.Array,
        positions: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
        return_hidden: bool = False,
        decode: bool = False,
        cache_len: Optional[int] = None,
    ) -> jax.Array:
        """``return_hidden=True`` skips the lm-head projection and returns
        the final normed hidden states — used with
        :func:`dlrover_tpu.ops.losses.fused_lm_head_loss` so the full
        logits are never materialized."""
        cfg = self.config
        if positions is None:
            positions = jnp.arange(input_ids.shape[1])
        embed = nn.Embed(
            cfg.vocab_size,
            cfg.hidden_size,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("vocab_tbl", "embed_tbl")
            ),
            name="embed_tokens",
        )
        x = embed(input_ids)
        x = with_logical_constraint(x, ("batch", "seq", "act_embed"))

        if decode and cfg.scan_layers:
            raise NotImplementedError(
                "KV-cache decode needs per-layer cache variables; use "
                "scan_layers=False for generation configs (training keeps "
                "scan_layers=True — the cache never exists under training)"
            )
        if cfg.scan_layers:
            block = _ScanLayer
            if cfg.remat:
                policy = resolve_remat_policy(cfg.remat_policy)
                block = nn.remat(
                    block, policy=policy, prevent_cse=False, static_argnums=()
                )
            scan = nn.scan(
                block,
                variable_axes={"params": 0, "moe_losses": 0},
                split_rngs={"params": True},
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )
            (x, _, _), _ = scan(cfg, name="layers")((x, positions, segment_ids), None)
        elif decode:
            # no remat in decode (nothing to rematerialize — inference);
            # keeping the bool OUT of nn.remat also matters: remat would
            # trace it and `if decode:` would fail at trace time
            for i in range(cfg.num_layers):
                x = DecoderLayer(cfg, name=f"layer_{i}")(
                    x, positions, segment_ids, decode=True,
                    cache_len=cache_len,
                )
        else:
            layer_cls = DecoderLayer
            if cfg.remat:
                policy = resolve_remat_policy(cfg.remat_policy)
                layer_cls = nn.remat(layer_cls, policy=policy, prevent_cse=False)
            for i in range(cfg.num_layers):
                x = layer_cls(cfg, name=f"layer_{i}")(x, positions,
                                                      segment_ids)

        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype, name="final_norm")(x)

        if return_hidden:
            return x

        if cfg.tie_embeddings:
            logits = embed.attend(x.astype(cfg.param_dtype))
        else:
            lm_head = nn.DenseGeneral(
                cfg.vocab_size,
                use_bias=False,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                kernel_init=nn.with_logical_partitioning(
                    nn.initializers.lecun_normal(), ("embed", "vocab")
                ),
                name="lm_head",
            )
            logits = lm_head(x)
        if cfg.logit_scale != 1.0:
            logits = logits * cfg.logit_scale
        return with_logical_constraint(logits, ("batch", "seq", "vocab"))
