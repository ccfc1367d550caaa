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
import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax import struct
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.accel.parallel.mesh import (ambient_mesh,
                                              with_logical_constraint)
from dlrover_tpu.ops.attention import dot_product_attention
from dlrover_tpu.utils.profiler import device_scope

Dtype = Any


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """The rotary embedding of one kind of layer.  ``rotary_fraction`` is
    the share of a head that rotates (its FIRST dimensions; the rest pass
    through); ``yarn_factor`` > 0 scales the frequencies as YaRN does and
    multiplies cos and sin by ``attention_factor``."""

    theta: float = 10000.0
    rotary_fraction: float = 1.0
    yarn_factor: float = 0.0
    yarn_original_max_len: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One decoder layer of a model whose layers are not all alike: what
    the model, ``num_params``, the presets and the benchmark read."""

    num_heads: int = 32
    # keys a query sees, itself counted; 0: every key behind it (full)
    window: int = 0
    rope: RopeSpec = RopeSpec()
    mlp: str = "dense"            # "dense" | "sparse"
    # what mixes tokens: "attn" (the model's attention block) | "conv" (a
    # gated short convolution, ``ShortConv``: LlamaConfig.conv_taps;
    # trained, not served) | "kda" (linear attention with a recurrent
    # state: LlamaConfig.kda_heads) | "ssm" (a Mamba-2 state-space layer:
    # LlamaConfig.ssm_heads) | "retention" (power retention of degree 2
    # over the model's own query and key heads: ops/pallas/retention.py);
    # the last three are served, not trained
    mixer: str = "attn"
    # what differs between two kinds of LATENT layer in one model (0: the
    # config's ``kv_lora_rank`` / ``qk_nope_head_dim``), and whether the
    # config's indexer (``index_topk``) runs in this layer
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    indexer: bool = True


def layer_pattern(specs: Tuple[LayerSpec, ...]) -> Tuple[int, int]:
    """``(leading, period)``: the fewest leading layers behind which the
    rest is one run of layers repeated at least twice, and the shortest
    such run.  Layers all alike are ``(0, 1)``; where nothing repeats
    every layer leads and the period is 0."""
    n = len(specs)
    if all(s == specs[0] for s in specs):
        return 0, 1
    for lead in range(n):
        rest = specs[lead:]
        for period in range(1, len(rest) // 2 + 1):
            if len(rest) % period == 0 and all(
                    s == rest[i % period] for i, s in enumerate(rest)):
                return lead, period
    return n, 0


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
    # an expert's width where it is not the dense layers' (None = theirs)
    moe_intermediate_size: Optional[int] = None
    # the router's scores: "softmax" over all experts, or "sigmoid" of
    # each logit (then norm_topk_prob divides the picked scores by their sum)
    moe_score_fn: str = "softmax"
    # the routed experts' summed output is multiplied by this
    moe_routed_scale: float = 1.0
    # one shared expert of this width on every token (0 = none)
    moe_shared_width: int = 0
    # (first, count): the routed experts THIS device holds of every sparse
    # layer, one share of an expert-parallel group; the router keeps all
    # ``num_experts`` outputs and a pick on an absent expert adds nothing
    moe_experts_held: Optional[Tuple[int, int]] = None
    # initialise each expert as a matrix of its own (MoEMLP.per_expert_init)
    moe_per_expert_init: bool = False
    # a learned bias added to the scores for the CHOICE of the top_k only
    # (``noaux_tc``); the weights stay the scores' own.  Zeros at
    # initialisation, or N(0, ``moe_select_bias_std``) where a seeded run
    # wants a bias that changes picks
    moe_select_bias: bool = False
    moe_select_bias_std: float = 0.0
    # of a model whose layers are otherwise alike: this many leading
    # layers keep the dense MLP, the rest are sparse
    moe_first_dense: int = 0
    # latent attention (MLA; ``kv_lora_rank`` 0 = the grouped-query block):
    # the query through a normed bottleneck of ``q_lora_rank``; one normed
    # latent row of ``kv_lora_rank`` and one rotated key row of
    # ``qk_rope_head_dim`` a token, shared by all heads; a head's key is
    # ``qk_nope_head_dim`` of the latent's up-projection beside that row,
    # its value ``v_head_dim`` of it; the rotary dimensions rotate in
    # adjacent pairs.  ``q_lora_rank`` 0: no bottleneck, one ``W_q`` from
    # the hidden state.  Served only (serving/latent.py)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # a learned selection of keys (``index_topk`` 0 = none): an indexer of
    # ``index_n_heads`` heads of ``index_head_dim`` scores every key
    # behind a query, and the query attends to the ``index_topk`` largest.
    # Its queries come from the query's bottleneck in a latent layer and
    # from the layer's normed input in a grouped-query one.  Served only
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # Kimi Delta Attention (a ``LayerSpec.mixer`` of "kda"): ``kda_heads``
    # heads of ``kda_head_dim`` for keys and values alike, a causal
    # depthwise convolution over the last ``kda_conv`` positions ahead of
    # q, k and v, a log-decay a channel and an output gate through
    # bottlenecks of ``kda_rank``; a head's state is one float32 matrix of
    # ``kda_head_dim`` squared a sequence.  Served only (serving/linear.py)
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_rank: int = 0
    # Mamba-2 (a ``LayerSpec.mixer`` of "ssm"): ``ssm_heads`` heads of
    # ``ssm_head_dim`` channels, each with a float32 state of ``ssm_head_dim
    # x ssm_state`` a sequence that decays by ONE scalar a head and token;
    # ``B`` and ``C`` (``ssm_state`` values each) shared by all heads (one
    # group); a causal depthwise convolution with bias over the last
    # ``ssm_conv`` positions ahead of x, B and C; the gate inside the output
    # norm.  Served only (serving/linear.py)
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_conv: int = 4
    # a gated short convolution (a ``LayerSpec.mixer`` of "conv", LFM2):
    # ``[B | C | u] = W_in h``, three widths of ``hidden_size``; a causal
    # DEPTHWISE convolution of ``conv_taps`` taps over ``B * u``, no bias,
    # no activation, no recurrent state; ``W_out (C * that)``.  Trained
    # only (``ShortConv``)
    conv_taps: int = 3
    # the embedding times this; every residual branch (mixer and MLP) times
    # this before it is added (Granite's ``embedding_multiplier`` and
    # ``residual_multiplier``).  Served by the loop of layer kinds only
    embedding_mult: float = 1.0
    residual_mult: float = 1.0
    # the grouped-query block's softmax scale where the model states one
    # (Granite's ``attention_multiplier``); None: ``head_dim_ ** -0.5``
    attn_scale: Optional[float] = None
    # a sigmoid gate on the attention output, one a query head, from the
    # layer's normed input
    attn_head_gate: bool = False
    # latent attention: the normed query bottleneck times ``sqrt(hidden /
    # q_lora_rank)``, the normed latent times ``sqrt(hidden / its rank)``
    # (``apply_mla_qkv_lora_rescale``); the rotated key row is not scaled
    mla_lora_rescale: bool = False
    # layers that are not all alike, one LayerSpec each (None = every
    # layer is the one the fields above describe)
    layers: Optional[Tuple[LayerSpec, ...]] = None
    # RMSNorm with a learned scale on the projected query and the
    # projected key, before RoPE, of the kind ``qk_norm_kind`` names:
    # "projection", over the WHOLE projection before the split into heads,
    # one scale of heads x head_dim (OLMoE, OLMo-2), or "head", over each
    # head's ``head_dim`` values, ONE scale of ``head_dim`` for all query
    # heads and one for all key heads (LFM2).  Of a latent-attention model
    # (the kind is not read): over each query head's ``qk_nope_head_dim +
    # qk_rope_head_dim`` values, one scale for all heads, before rotation;
    # the key's norm is the latent's RMSNorm
    qk_norm: bool = False
    qk_norm_kind: str = "projection"
    # the rotary embedding of a latent-attention model where it is not
    # plain RoPE at ``rope_theta`` (YaRN: ``rope_inverse_frequencies``)
    rope_scaling: Optional[RopeSpec] = None
    # the softmax scale is ``head_dim_ ** -0.5`` times this (``deepseek_yarn``
    # multiplies it by ``yarn_mscale(factor, mscale_all_dim) ** 2``)
    attn_scale_mult: float = 1.0
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
        if self.kv_lora_rank:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_dim or self.hidden_size // self.num_heads

    def __post_init__(self):
        if self.layers is not None and len(self.layers) != self.num_layers:
            raise ValueError(
                f"{len(self.layers)} layer descriptions for num_layers="
                f"{self.num_layers}")
        if self.qk_norm_kind not in ("projection", "head"):
            raise ValueError(
                f"unknown qk_norm_kind {self.qk_norm_kind!r}: the QK-norm is "
                "over the whole 'projection' or over each 'head'")
        if (self.rope_scaling or self.attn_scale_mult != 1.0) \
                and not self.kv_lora_rank:
            raise ValueError(
                "rope_scaling / attn_scale_mult describe a latent-attention "
                "model (serving/latent.py); a grouped-query model whose "
                "layers scale their frequencies says so a layer "
                "(layers=..., LayerSpec.rope)")

    @property
    def layer_specs(self) -> Tuple[LayerSpec, ...]:
        """Every layer's description; of a uniform model, the one kind."""
        if self.layers is not None:
            return self.layers
        def one(mlp):
            return LayerSpec(num_heads=self.num_heads, rope=self.rope, mlp=mlp)

        if not self.num_experts:
            return (one("dense"),) * self.num_layers
        lead = min(self.moe_first_dense, self.num_layers)
        return (one("dense"),) * lead + (one("sparse"),) * (
            self.num_layers - lead)

    @property
    def layer_kinds(self) -> bool:
        """Whether the served forward is the loop that dispatches on each
        layer's description (``serving/latent.py verify_step``): latent
        attention, or a grouped-query model with sparse experts, with a
        layer that keeps a recurrent state, or with a multiplier or a
        softmax scale of its own.  Any other dense grouped-query model
        keeps ``serving/model.py``'s own loop."""
        return bool(
            self.kv_lora_rank or self.num_experts
            or any(s.mixer != "attn" for s in self.layer_specs)
            or self.embedding_mult != 1.0 or self.residual_mult != 1.0
            or self.attn_scale is not None)

    @property
    def rope(self) -> RopeSpec:
        """The rotary embedding of a model whose layers are all alike."""
        return self.rope_scaling or RopeSpec(theta=self.rope_theta)

    @property
    def rope_kinds(self) -> Tuple[RopeSpec, ...]:
        """The distinct rotary embeddings, in order of first use."""
        return tuple(dict.fromkeys(s.rope for s in self.layer_specs))

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    def latent_dims(self, spec: LayerSpec) -> Tuple[int, int, bool]:
        """``(latent rank, nope size, whether the indexer runs)`` of a
        latent-attention layer: the layer's own where it says so, else the
        config's.  The third is a grouped-query layer's too."""
        return (spec.kv_lora_rank or self.kv_lora_rank,
                spec.qk_nope_head_dim or self.qk_nope_head_dim,
                bool(self.index_topk and spec.indexer))

    def _indexer_params(self, query_from: int) -> int:
        """An indexer whose queries are projected from ``query_from``
        values (the query bottleneck's rank, or the hidden size): ``W_iq``,
        ``W_ik``, the key norm's scale and bias, the heads' weights."""
        h, i = self.hidden_size, self.index_head_dim
        return (query_from * self.index_n_heads * i + h * i + 2 * i
                + h * self.index_n_heads)

    def layer_params(self, spec: LayerSpec) -> int:
        """Parameters of one layer as this device holds it."""
        h, d = self.hidden_size, self.head_dim_
        if spec.mixer == "kda":
            # q, k, v and their convolutions; the decay's and the gate's
            # bottlenecks, the decay's bias and a head's rate; beta; the
            # head norm; the output projection; the block's two norms
            w = self.kda_heads * self.kda_head_dim
            n = (3 * h * w + 3 * self.kda_conv * w
                 + 2 * (h * self.kda_rank + self.kda_rank * w) + w
                 + self.kda_heads + h * self.kda_heads + self.kda_head_dim
                 + w * h + 2 * h)
        elif spec.mixer == "ssm":
            # W_in to [z | x B C | dt]; the convolution's taps and bias over
            # x, B and C; a head's dt bias, rate and skip; the gated norm;
            # W_out; the block's two norms
            w = self.ssm_heads * self.ssm_head_dim
            xbc = w + 2 * self.ssm_state
            n = (h * (w + xbc + self.ssm_heads) + (self.ssm_conv + 1) * xbc
                 + 3 * self.ssm_heads + w + w * h + 2 * h)
        elif spec.mixer == "conv":
            # W_in to [B | C | u]; a channel's taps; W_out; the block's
            # two norms
            n = 3 * h * h + self.conv_taps * h + h * h + 2 * h
        elif spec.mixer == "retention":
            # q, k, v and o as the grouped-query block's; the gate, one
            # scalar a key head with its bias; the two head norms; the
            # block's two norms
            n = (h * d * (spec.num_heads * 2 + self.num_kv_heads * 2)
                 + (h + 1) * self.num_kv_heads + 2 * d + 2 * h)
        elif self.kv_lora_rank:
            heads, q = spec.num_heads, self.q_lora_rank
            c, nope, indexed = self.latent_dims(spec)
            d = nope + self.qk_rope_head_dim
            n = ((h * q + q + q * heads * d if q else h * heads * d)
                 + h * (c + self.qk_rope_head_dim) + c
                 + c * heads * (nope + self.v_head_dim)
                 + heads * self.v_head_dim * h + 2 * h)
            if indexed:
                n += self._indexer_params(q)
        else:
            n = h * d * (spec.num_heads * 2 + self.num_kv_heads * 2) + 2 * h
            if self.latent_dims(spec)[2]:
                n += self._indexer_params(h)
        if self.attn_head_gate and spec.mixer == "attn":
            n += h * spec.num_heads
        if self.qk_norm and spec.mixer == "attn":
            if self.kv_lora_rank:
                n += d
            elif self.qk_norm_kind == "head":
                n += 2 * d
            else:
                n += d * (spec.num_heads + self.num_kv_heads)
        if spec.mlp == "sparse":
            held = (self.moe_experts_held or (0, self.num_experts))[1]
            n += 3 * h * self.expert_width * held + h * self.num_experts
            n += 3 * h * self.moe_shared_width
            if self.moe_select_bias:
                n += self.num_experts
        else:
            n += 3 * h * self.intermediate_size
        return n

    @property
    def num_params(self) -> int:
        """Approximate parameter count (for MFU accounting)."""
        h, v = self.hidden_size, self.vocab_size
        emb = v * h * (1 if self.tie_embeddings else 2)
        return sum(map(self.layer_params, self.layer_specs)) + emb + h

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
    def laguna_xs2(cls, **kw) -> "LlamaConfig":
        """poolside/Laguna-XS.2 as its config.json has it: every fourth
        layer full attention with 48 query heads (half the head rotated,
        theta 5e5, YaRN x64 over 4096), the others a window of 512 with
        64 heads (plain RoPE, theta 1e4), 8 KV heads throughout, a head
        gate; layer 0 a dense SwiGLU of 8192, the rest 256 sigmoid-routed
        experts of 512, 8 a token, scaled by 2.5, beside one shared
        expert of 512.  ``num_layers`` cuts the pattern's depth;
        ``moe_experts_held`` and ``vocab_size`` give one chip its share."""
        num_layers = int(kw.pop("num_layers", 40))
        full = RopeSpec(
            theta=500000.0, rotary_fraction=0.5, yarn_factor=64.0,
            yarn_original_max_len=4096, yarn_beta_fast=64.0,
            yarn_beta_slow=1.0, attention_factor=1.4158883083359672)
        window = RopeSpec(theta=10000.0)
        layers = tuple(
            LayerSpec(
                num_heads=64 if i % 4 else 48,
                window=512 if i % 4 else 0,
                rope=window if i % 4 else full,
                mlp="sparse" if i else "dense")
            for i in range(num_layers))
        base = dict(
            vocab_size=100352,
            hidden_size=2048,
            intermediate_size=8192,
            num_layers=num_layers,
            num_heads=48,
            num_kv_heads=8,
            head_dim=128,
            max_seq_len=8192,
            rope_theta=500000.0,
            rms_norm_eps=1e-6,
            num_experts=256,
            moe_top_k=8,
            moe_norm_topk_prob=True,
            moe_aux_loss_coef=0.0,
            moe_z_loss_coef=0.0,
            moe_intermediate_size=512,
            moe_score_fn="sigmoid",
            moe_routed_scale=2.5,
            moe_shared_width=512,
            moe_per_expert_init=True,
            attn_head_gate=True,
            layers=layers,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def glm5(cls, **kw) -> "LlamaConfig":
        """zai-org/GLM-5 (``glm_moe_dsa``) as its config.json has it: 78
        layers of latent attention (64 heads of 192 + 64 / 256 over a
        latent of 512, the query through 2048) with an indexer (32 heads
        of 128) that picks the 2048 keys a query attends to; 3 leading
        dense layers of 12288, then 256 sigmoid-routed experts of 2048, 8
        a token chosen by score + bias, weights over their sum x 2.5,
        beside one shared expert.  Served, not trained.  ``num_layers``,
        ``moe_first_dense``, ``moe_experts_held`` and ``vocab_size`` give
        one chip its share; the next-token-prediction module is no part
        of the forward pass."""
        base = dict(
            vocab_size=154880,
            hidden_size=6144,
            intermediate_size=12288,
            num_layers=78,
            num_heads=64,
            num_kv_heads=64,
            max_seq_len=202752,
            rope_theta=1000000.0,
            rms_norm_eps=1e-5,
            q_lora_rank=2048,
            kv_lora_rank=512,
            qk_nope_head_dim=192,
            qk_rope_head_dim=64,
            v_head_dim=256,
            index_n_heads=32,
            index_head_dim=128,
            index_topk=2048,
            num_experts=256,
            moe_top_k=8,
            moe_norm_topk_prob=True,
            moe_intermediate_size=2048,
            moe_score_fn="sigmoid",
            moe_routed_scale=2.5,
            moe_shared_width=2048,
            moe_select_bias=True,
            moe_first_dense=3,
            moe_per_expert_init=True,
            moe_aux_loss_coef=0.0,
            moe_z_loss_coef=0.0,
            scan_layers=False,
            remat=False,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def sarvam_105b(cls, **kw) -> "LlamaConfig":
        """sarvamai/sarvam-105b (``sarvam_mla``) as its config.json has it:
        32 layers of latent attention, 64 heads of 128 + 64 / 128 over a
        latent of 512, the query straight from the hidden state (no
        bottleneck), ``deepseek_yarn`` x 40 over 4096 positions (beta 32 /
        1, theta 1e4; ``mscale`` = ``mscale_all_dim`` = 1, so cos and sin
        are scaled by 1 and the softmax scale by ``(0.1 ln 40 + 1)^2``);
        one leading dense SwiGLU of 16384, then 128 sigmoid-routed experts
        of 2048, 8 a token chosen by score + bias, weights over their sum
        x 2.5, beside one shared expert; vocabulary 262144, untied.
        Served, not trained.  ``num_layers``, ``moe_experts_held`` and
        ``vocab_size`` give one chip its share.

        Assumed, where the config names a mechanism and not its equation:
        ``use_qk_norm`` is an RMSNorm with one learned scale over each
        query head's 192 values before rotation, and the RMSNorm on the
        latent is the key's (a norm over a head's up-projected key could
        not be absorbed into the query, and the cache would not be the
        published 576-wide row); the rotated 64 rotate in adjacent
        pairs."""
        import math

        m = 0.1 * 1.0 * math.log(40.0) + 1.0     # mscale_all_dim = 1
        base = dict(
            vocab_size=262144,
            hidden_size=4096,
            intermediate_size=16384,
            num_layers=32,
            num_heads=64,
            num_kv_heads=64,
            max_seq_len=131072,
            rope_theta=10000.0,
            rms_norm_eps=1e-6,
            q_lora_rank=0,
            kv_lora_rank=512,
            qk_nope_head_dim=128,
            qk_rope_head_dim=64,
            v_head_dim=128,
            qk_norm=True,
            rope_scaling=RopeSpec(
                theta=10000.0, yarn_factor=40.0,
                yarn_original_max_len=4096, yarn_beta_fast=32.0,
                yarn_beta_slow=1.0, attention_factor=1.0),
            attn_scale_mult=m * m,
            num_experts=128,
            moe_top_k=8,
            moe_norm_topk_prob=True,
            moe_intermediate_size=2048,
            moe_score_fn="sigmoid",
            moe_routed_scale=2.5,
            moe_shared_width=2048,
            moe_select_bias=True,
            moe_first_dense=1,
            moe_per_expert_init=True,
            moe_aux_loss_coef=0.0,
            moe_z_loss_coef=0.0,
            scan_layers=False,
            remat=False,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def kimi_linear_48b(cls, **kw) -> "LlamaConfig":
        """moonshotai/Kimi-Linear-48B-A3B-Instruct (``kimi_linear``) as its
        config.json has it: 27 layers in the pattern KDA, KDA, KDA, MLA
        (``full_attn_layers`` 4, 8, .., 24, 27).  A KDA layer: 32 heads of
        128 for keys and values, a causal depthwise convolution of 4 ahead
        of q, k and v, a gated delta rule with a decay a channel
        (``ops/pallas/kda.py``).  An MLA layer: 32 heads of 128 + 64 / 128
        over a latent of 512 beside 64 further key values shared by all
        heads, the query straight from the hidden state, NO positional
        encoding (``mla_use_nope``: ``RopeSpec(rotary_fraction=0)``).
        Layer 1's MLP a SwiGLU of 9216, then 256 sigmoid-routed experts of
        1024, 8 a token chosen by score + bias, weights over their sum x
        2.446, beside one shared expert; vocabulary 163840, untied.
        Served, not trained.  ``num_layers`` cuts the pattern's depth;
        ``moe_experts_held`` and ``vocab_size`` give one chip its share.

        Assumed, where the config names a mechanism and not its equation
        (``perfbench/configs/kimi-linear-48b-serve.json``): the
        bottlenecks' width (the head size), the decay ``-exp(A_h) x
        softplus(W_f2 W_f1 x + b)``, SiLU after the convolution and the
        L2 norm of q and k behind it, a float32 state, the selection
        bias."""
        num_layers = int(kw.pop("num_layers", 27))
        nope = RopeSpec(rotary_fraction=0.0)
        layers = tuple(
            LayerSpec(
                num_heads=int(kw.get("num_heads", 32)), rope=nope,
                mixer="attn" if (i + 1) % 4 == 0 or i == 26 else "kda",
                mlp="sparse" if i else "dense")
            for i in range(num_layers))
        base = dict(
            vocab_size=163840,
            hidden_size=2304,
            intermediate_size=9216,
            num_layers=num_layers,
            num_heads=32,
            num_kv_heads=32,
            max_seq_len=1048576,
            rms_norm_eps=1e-5,
            q_lora_rank=0,
            kv_lora_rank=512,
            qk_nope_head_dim=128,
            qk_rope_head_dim=64,
            v_head_dim=128,
            rope_scaling=nope,
            kda_heads=32,
            kda_head_dim=128,
            kda_conv=4,
            kda_rank=128,
            num_experts=256,
            moe_top_k=8,
            moe_norm_topk_prob=True,
            moe_intermediate_size=1024,
            moe_score_fn="sigmoid",
            moe_routed_scale=2.446,
            moe_shared_width=1024,
            moe_select_bias=True,
            moe_first_dense=1,
            moe_per_expert_init=True,
            moe_aux_loss_coef=0.0,
            moe_z_loss_coef=0.0,
            scan_layers=False,
            remat=False,
            layers=layers,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def dots3_note(cls, **kw) -> "LlamaConfig":
        """dots-studio/dots3-note-prev (``dots3_note``), its language model
        as its config.json has it: 46 layers of latent attention in two
        kinds, ``layer_types`` full, full, then (sliding, sliding, sliding,
        full) x 11.  A FULL layer: 128 heads of 128 + 64 / 128 over a
        latent of 512, theta 8e7, GLM-5's indexer (64 heads of 128) that
        picks the 2048 keys a query attends to.  A SLIDING layer: 64 heads
        of 192 + 64 / 128 over a latent of 1024, theta 5e4, a query seeing
        the last 513 keys, itself counted, and no indexer.  Both: the
        query through a bottleneck of 1024, the bottleneck and the latent
        rescaled behind their norms (``mla_lora_rescale``), a sigmoid gate
        a head on the attention's output.  Layer 0's MLP a SwiGLU of
        13824, then 256 sigmoid-routed experts of 1536, 8 a token chosen
        by score + bias, weights over their sum, beside one shared expert;
        vocabulary 152064, untied.  Served, not trained.  ``num_layers``
        cuts the pattern's depth; ``moe_experts_held`` and ``vocab_size``
        give one chip its share.

        Assumed, where the config names a mechanism and not its equation
        (``perfbench/configs/dots3-note-serve.json``): the rescale as above
        and the indexer's query reading the scaled bottleneck, the gate
        from the layer's normed input, the window counting the query, the
        indexer's details as GLM-5's, the selection bias."""
        num_layers = int(kw.pop("num_layers", 46))
        full, sliding = RopeSpec(theta=8e7), RopeSpec(theta=5e4)

        def spec(i):
            mlp = "sparse" if i else "dense"
            if i >= 2 and (i - 2) % 4 != 3:
                return LayerSpec(
                    num_heads=64, window=513, rope=sliding, mlp=mlp,
                    kv_lora_rank=1024, qk_nope_head_dim=192, indexer=False)
            return LayerSpec(num_heads=128, rope=full, mlp=mlp)

        base = dict(
            vocab_size=152064,
            hidden_size=5120,
            intermediate_size=13824,
            num_layers=num_layers,
            num_heads=128,
            num_kv_heads=128,
            max_seq_len=524288,
            rope_theta=8e7,
            rms_norm_eps=1e-5,
            q_lora_rank=1024,
            kv_lora_rank=512,
            qk_nope_head_dim=128,
            qk_rope_head_dim=64,
            v_head_dim=128,
            index_n_heads=64,
            index_head_dim=128,
            index_topk=2048,
            attn_head_gate=True,
            mla_lora_rescale=True,
            num_experts=256,
            moe_top_k=8,
            moe_norm_topk_prob=True,
            moe_intermediate_size=1536,
            moe_score_fn="sigmoid",
            moe_routed_scale=1.0,
            moe_shared_width=1536,
            moe_select_bias=True,
            moe_per_expert_init=True,
            moe_aux_loss_coef=0.0,
            moe_z_loss_coef=0.0,
            scan_layers=False,
            remat=False,
            layers=tuple(spec(i) for i in range(num_layers)),
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def granite_4_h_small(cls, **kw) -> "LlamaConfig":
        """ibm-granite/granite-4.0-h-small (``granitemoehybrid``) as its
        config.json has it: 40 layers, ``layer_types`` attention at 5, 15,
        25 and 35 (0-based) and Mamba-2 everywhere else, a period of ten.
        A MAMBA layer: 128 heads of 64 channels over a state of 128, one
        group (B and C shared by every head), a causal depthwise
        convolution of 4 with bias, one scalar decay a head and token
        (``ops/pallas/ssm.py``).  An ATTENTION layer: 32 query / 8 KV heads
        of 128, NO positional encoding, softmax scale 1/128
        (``attention_multiplier``).  Every layer's MLP: 72 softmax-routed
        experts of 768, 10 a token, weights over the picks' sum, beside one
        shared expert of 1536.  The embedding x 12, every residual branch
        x 0.22, logits / 16; vocabulary 100352, TIED.  Served, not
        trained.  ``num_layers`` cuts the pattern's depth;
        ``moe_experts_held`` and ``vocab_size`` give one chip its share.

        Assumed, where the config names a mechanism and not its equation
        (``perfbench/configs/granite-4.0-h-small-serve.json``):
        ``intermediate_size`` is one expert's width, the split orders ``[z
        | xBC | dt]`` and ``[x | B | C]``, the step unclamped, the gate
        inside the norm and the norm over all 8192 channels, a float32
        state."""
        num_layers = int(kw.pop("num_layers", 40))
        nope = RopeSpec(rotary_fraction=0.0)
        layers = tuple(
            LayerSpec(num_heads=int(kw.get("num_heads", 32)), rope=nope,
                      mixer="attn" if i % 10 == 5 else "ssm", mlp="sparse")
            for i in range(num_layers))
        base = dict(
            vocab_size=100352,
            hidden_size=4096,
            intermediate_size=768,
            num_layers=num_layers,
            num_heads=32,
            num_kv_heads=8,
            head_dim=128,
            max_seq_len=131072,
            rms_norm_eps=1e-5,
            tie_embeddings=True,
            ssm_heads=128,
            ssm_head_dim=64,
            ssm_state=128,
            ssm_conv=4,
            embedding_mult=12.0,
            residual_mult=0.22,
            attn_scale=0.0078125,
            logit_scale=1.0 / 16.0,
            num_experts=72,
            moe_top_k=10,
            moe_norm_topk_prob=True,
            moe_score_fn="softmax",
            moe_shared_width=1536,
            moe_per_expert_init=True,
            moe_aux_loss_coef=0.0,
            moe_z_loss_coef=0.0,
            scan_layers=False,
            remat=False,
            layers=layers,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def lfm2_8b_a1b(cls, **kw) -> "LlamaConfig":
        """LiquidAI/LFM2-8B-A1B (``lfm2_moe``) as its config.json has it: 24
        layers, ``layer_types`` full attention at 2, 6, 10, 14, 18 and 21
        (0-based) and a gated short convolution everywhere else (three to
        one; ``layer_pattern`` of the whole is (18, 3)).  A CONV layer:
        ``[B | C | u] = W_in h``, a causal depthwise convolution of 3 taps
        over ``B * u`` with no bias and no activation, ``W_out (C * that)``
        (``ShortConv``).  An ATTENTION layer: 32 query / 8 KV heads of 64,
        an RMSNorm over each head of q and k with one scale for all heads,
        plain RoPE at theta 1e6.  Layers 0 and 1 keep a dense SwiGLU of
        7168, the rest 32 sigmoid-routed experts of 1792, 4 a token chosen
        by score + bias, weights over their sum, NO shared expert;
        vocabulary 65536, TIED.  Trained, not served.  ``num_layers`` cuts
        the pattern's depth; ``moe_experts_held`` and ``vocab_size`` give one
        chip its share.  (The benchmark's cut is a run of layers from the
        MIDDLE, published layers 1-13: one dense conv layer, then three
        periods of (attention, conv, conv, conv), ``layer_pattern`` (1, 4);
        ``perfbench/drivers/train_conv.py conv_config`` makes it of the
        configuration file's published keys.)

        Assumed, where the config names a mechanism and not its equation
        (``perfbench/configs/lfm2-8b-a1b-train.json``): tied embeddings,
        the split order and the taps' order (oldest first), the QK-norm's
        one scale for all heads, the bias a buffer no optimizer rule moves
        (seeded N(0, ``moe_select_bias_std``))."""
        kinds = ["conv"] * 24
        for i in (2, 6, 10, 14, 18, 21):
            kinds[i] = "attn"
        num_layers = int(kw.pop("num_layers", 24))
        rope = RopeSpec(theta=1000000.0)
        layers = tuple(
            LayerSpec(num_heads=int(kw.get("num_heads", 32)), rope=rope,
                      mixer=kinds[i], mlp="sparse" if i >= 2 else "dense")
            for i in range(num_layers))
        base = dict(
            vocab_size=65536,
            hidden_size=2048,
            intermediate_size=7168,
            num_layers=num_layers,
            num_heads=32,
            num_kv_heads=8,
            head_dim=64,
            max_seq_len=128000,
            rope_theta=1000000.0,
            rms_norm_eps=1e-5,
            tie_embeddings=True,
            qk_norm=True,
            qk_norm_kind="head",
            conv_taps=3,
            num_experts=32,
            moe_top_k=4,
            moe_norm_topk_prob=True,
            moe_intermediate_size=1792,
            moe_score_fn="sigmoid",
            moe_routed_scale=1.0,
            moe_shared_width=0,
            moe_select_bias=True,
            moe_per_expert_init=True,
            moe_aux_loss_coef=0.0,
            moe_z_loss_coef=0.0,
            layers=layers,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def keye_vl2_30b_a3b(cls, **kw) -> "LlamaConfig":
        """Kwai-Keye/Keye-VL-2.0-30B-A3B (``KeyeVL2``), its LANGUAGE MODEL as
        its config.json has it: 48 identical layers, 32 query / 4 KV heads
        of 128 with an RMSNorm over each head of q and k (one scale for all
        query heads, one for all key heads), RoPE at theta 1e7 over the
        whole head (M-RoPE: a text token's three position streams are
        equal, which is plain RoPE), and inside every layer an indexer
        (``sa_config``: 16 heads of 64, ONE index key a token) that picks
        the 2048 keys a query attends to; every MLP 128 softmax-routed
        experts of 768, 8 a token, weights over the picks' sum, no shared
        expert; vocabulary 151936, untied.  Served, not trained (a
        selection has no training loss here).  ``num_layers`` cuts the
        depth; ``moe_experts_held`` and ``vocab_size`` give one chip its
        share.

        Assumed, where the config names a mechanism and not its equation
        (``perfbench/configs/keye-vl2-30b-a3b-serve.json``): the QK-norm,
        the indexer's projections from the layer's normed input, its key
        norm a LayerNorm with scale and bias, all 64 of its dimensions
        rotated (halves paired, as the heads), its head weights scaled
        ``(16 x 64)^-0.5``; the vision tower and the image tokens' two
        further position streams are left out."""
        base = dict(
            vocab_size=151936,
            hidden_size=2048,
            intermediate_size=6144,
            num_layers=48,
            num_heads=32,
            num_kv_heads=4,
            head_dim=128,
            max_seq_len=262144,
            rope_theta=10000000.0,
            rms_norm_eps=1e-6,
            qk_norm=True,
            qk_norm_kind="head",
            index_n_heads=16,
            index_head_dim=64,
            index_topk=2048,
            num_experts=128,
            moe_top_k=8,
            moe_norm_topk_prob=True,
            moe_intermediate_size=768,
            moe_score_fn="softmax",
            moe_shared_width=0,
            moe_per_expert_init=True,
            moe_aux_loss_coef=0.0,
            moe_z_loss_coef=0.0,
            scan_layers=False,
            remat=False,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def brumby_14b(cls, **kw) -> "LlamaConfig":
        """manifestai/Brumby-14B-Base (``brumby``) as its config.json has
        it: 40 identical layers, 40 query / 8 key heads of 128, a dense
        SwiGLU of 17408, vocabulary 151936, untied, theta 1e6, no window
        anywhere (``max_window_layers`` is the depth and
        ``use_sliding_window`` false).  Every layer's mixer is POWER
        RETENTION (``LayerSpec.mixer`` "retention",
        ``ops/pallas/retention.py``): attention whose weight is ``(q .
        k)^2`` under a gate, served as a recurrence over the symmetric
        square of the key, a float32 state of 8 256 x 128 and a sum of
        keys a key head and sequence, NO rows and no cache that grows.
        Served, not trained.  ``num_layers`` cuts the depth.

        Assumed, where the config names a mechanism and not its equation
        (``perfbench/configs/brumby-14b-serve.json``): degree 2; the gate
        ``logsigmoid(W_gate h + b)``, one scalar a KEY head and token,
        float32; the normaliser ``z`` and ``eps`` 1e-6 in the denominator;
        a QK-norm a head (one scale for all query heads, one for all key
        heads) and RoPE over the whole head, halves paired, ahead of the
        power, as the Qwen3 dense family whose widths these are."""
        num_layers = int(kw.pop("num_layers", 40))
        num_heads = int(kw.get("num_heads", 40))
        rope = RopeSpec(theta=float(kw.get("rope_theta", 1000000.0)))
        base = dict(
            vocab_size=151936,
            hidden_size=5120,
            intermediate_size=17408,
            num_layers=num_layers,
            num_heads=num_heads,
            num_kv_heads=8,
            head_dim=128,
            max_seq_len=32768,
            rope_theta=1000000.0,
            rms_norm_eps=1e-6,
            qk_norm=True,
            qk_norm_kind="head",
            scan_layers=False,
            remat=False,
            layers=tuple(
                LayerSpec(num_heads=num_heads, rope=rope, mixer="retention")
                for _ in range(num_layers)),
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
PRESETS = ("tiny", "llama2_7b", "olmoe_1b_7b", "laguna_xs2", "glm5",
           "sarvam_105b", "kimi_linear_48b", "dots3_note",
           "granite_4_h_small", "lfm2_8b_a1b", "keye_vl2_30b_a3b",
           "brumby_14b")


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


def rope_inverse_frequencies(spec: RopeSpec, head_dim: int) -> jax.Array:
    """[rotary/2] inverse frequencies of one kind of layer: plain
    ``theta^(-2i/rotary)`` over the rotating part of the head, or YaRN's
    blend as ``transformers`` computes it: the pairs that turn more than
    ``beta_fast`` times over the original length keep theirs, those that
    turn less than ``beta_slow`` times are divided by the factor, a
    linear ramp between."""
    import math

    rotary = int(head_dim * spec.rotary_fraction)
    exponent = jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary
    inv = 1.0 / (spec.theta ** exponent)
    if not spec.yarn_factor:
        return inv

    def correction_dim(turns):
        return (rotary * math.log(spec.yarn_original_max_len
                                  / (turns * 2 * math.pi))
                / (2 * math.log(spec.theta)))

    low = max(math.floor(correction_dim(spec.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(spec.yarn_beta_slow)), rotary - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(rotary // 2, dtype=jnp.float32) - low) / (high - low),
        0.0, 1.0)
    return inv / spec.yarn_factor * ramp + inv * (1.0 - ramp)


@struct.dataclass
class RopeTable:
    """One kind of layer's rotation at a step's positions: two float32
    rows a position over the WHOLE head of ``d`` lanes, of which the first
    ``2 * half`` rotate: ``cos`` is ``[cos, cos, 1 ...]``, ``sin``
    ``[-sin, sin, 0 ...]``, each ``[s, d]`` or ``[b, s, d]`` with the
    kind's ``attention_factor`` in them (``ops/pallas/rope.py``)."""

    cos: jax.Array
    sin: jax.Array
    half: int = struct.field(pytree_node=False)


def rope_table(spec: RopeSpec, head_dim: int,
               positions: jax.Array) -> Optional[RopeTable]:
    """The :class:`RopeTable` of one kind of layer at ``positions``;
    ``None`` where nothing of a head rotates."""
    inv = rope_inverse_frequencies(spec, head_dim)
    half = inv.shape[0]
    if not half:
        return None
    angles = positions.astype(jnp.float32)[..., None] * inv
    cos = jnp.cos(angles) * spec.attention_factor
    sin = jnp.sin(angles) * spec.attention_factor
    rest = cos.shape[:-1] + (head_dim - 2 * half,)
    return RopeTable(
        cos=jnp.concatenate([cos, cos, jnp.ones(rest, jnp.float32)], -1),
        sin=jnp.concatenate([-sin, sin, jnp.zeros(rest, jnp.float32)], -1),
        half=half)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _rotate(half: int, conj: bool, x, cos, sin):
    """``x`` [b, s, h, d] rotated by a :class:`RopeTable`'s rows (back,
    with ``conj``): the kernel where one device holds heads of 128 on a
    TPU, its ``jnp`` oracle elsewhere."""
    from dlrover_tpu.ops.pallas import rope

    mesh = ambient_mesh()
    # a Mosaic kernel cannot be partitioned by GSPMD: one device's work
    if (jax.default_backend() == "tpu" and rope.kernel_takes(x, cos)
            and (mesh is None or mesh.size == 1)):
        return rope.rope_rotate(x, cos, sin, half, conj)
    return rope.rotate_reference(x, cos, sin, half, conj)


def _rotate_fwd(half, conj, x, cos, sin):
    return _rotate(half, conj, x, cos, sin), (cos, sin)


def _rotate_bwd(half, conj, tables, g):
    # the transpose of a rotation is the rotation back: the same pass.
    # The tables come from integer positions, so nothing is owed to them.
    return _rotate(half, not conj, g, *tables), None, None


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


def apply_rope_table(x: jax.Array, table: Optional[RopeTable]) -> jax.Array:
    """x: [b, s, h, d]; ``table`` from :func:`rope_table`: the first
    ``2 * table.half`` dimensions of a head rotate (in halves, as
    :func:`apply_rope`), the rest pass through.  One pass over ``x``
    forward and one over its cotangent backward, nothing saved between
    them but the table, to which no gradient goes."""
    if table is None:
        return x
    return _rotate(table.half, False, x, table.cos, table.sin)


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
    # this layer's description where the model's layers are not all alike
    spec: Optional[LayerSpec] = None

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        positions: jax.Array,
        segment_ids: Optional[jax.Array] = None,
        decode: bool = False,
        cache_len: Optional[int] = None,
        rope=None,
    ) -> jax.Array:
        cfg, spec = self.config, self.spec
        d = cfg.head_dim_
        num_heads = cfg.num_heads if spec is None else spec.num_heads
        init = nn.initializers.lecun_normal()
        q_proj = nn.DenseGeneral(
            (num_heads, d),
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

        # ``attn_proj`` is everything of the block AROUND the attention
        # call (projections, QK-norm, RoPE, the gate), never the call: an
        # unnamed kernel takes the innermost scope's name (``attn.<n>``,
        # ``attn_full.<n>``), which is how its time is found in a trace
        with device_scope("attn_proj"):
            q = q_proj(x)
            k = k_proj(x)
            v = v_proj(x)
            if cfg.qk_norm:
                def normed(name, y):
                    # "head": over a head's values, one scale for all heads
                    shape = y.shape
                    if cfg.qk_norm_kind == "projection":
                        y = y.reshape(*shape[:-2], shape[-2] * shape[-1])
                    return RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                                   cfg.param_dtype,
                                   name=name)(y).reshape(shape)

                q, k = normed("q_norm", q), normed("k_norm", k)
            q = with_logical_constraint(
                q, ("batch", "seq", "heads", "head_dim"))
            k = with_logical_constraint(
                k, ("batch", "seq", "kv_heads", "head_dim"))
            v = with_logical_constraint(
                v, ("batch", "seq", "kv_heads", "head_dim"))

            if spec is None:
                angles = rope_frequencies(
                    d, cfg.max_seq_len, cfg.rope_theta)[positions]
                q = checkpoint_name(apply_rope(q, angles), "qkv_proj")
                k = checkpoint_name(apply_rope(k, angles), "qkv_proj")
            else:  # the kind's table, made once a step by the model
                q = checkpoint_name(apply_rope_table(q, rope), "qkv_proj")
                k = checkpoint_name(apply_rope_table(k, rope), "qkv_proj")
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
            with device_scope("attn_proj"):
                return o_proj(out)

        if spec is None:
            out = dot_product_attention(
                q, k, v, causal=True, segment_ids=segment_ids)
        else:
            with device_scope(
                    "attn_window" if spec.window else "attn_full"):
                out = dot_product_attention(
                    q, k, v, causal=True, segment_ids=segment_ids,
                    window=spec.window or None)
        with device_scope("attn_proj"):
            if cfg.attn_head_gate:
                gate = nn.DenseGeneral(
                    num_heads, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, dot_general=cfg.dot_general,
                    kernel_init=nn.with_logical_partitioning(
                        init, ("embed", "heads")),
                    name="g_proj",
                )(x)
                out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.astype(jnp.float32))[..., None]).astype(out.dtype)
            out = checkpoint_name(out, "attn_out")
            out = with_logical_constraint(
                out, ("batch", "seq", "heads", "head_dim"))
            return o_proj(out)


def _taps_init(key, shape, dtype):
    """N(0, 1/3) (LeCun-normal by a channel's fan-in of three taps), with 1
    added to the current position's tap: at initialisation a channel
    passes its own token on and sees the ones before it."""
    taps = jax.random.normal(key, shape, jnp.float32) * shape[0] ** -0.5
    return taps.at[-1].add(1.0).astype(dtype)


def _conv_mix(bcu, taps, segment_ids):
    """``C * conv(B * u)`` of ``in_proj``'s output ``bcu`` [b, s, 3, h]:
    one kernel pass each way over its three planes where one device holds
    whole tiles of an unpacked sequence on a TPU
    (``ops/pallas/short_conv.py``), the ``jnp`` oracle elsewhere.  A packed
    row (``segment_ids``) takes the ``jnp`` form: the kernels put zeros
    ahead of a ROW, not of a segment."""
    from dlrover_tpu.ops.pallas import short_conv

    # [b, 3, s, h] is how the projection lies in memory: no pass
    bcu = bcu.transpose(0, 2, 1, 3)
    mesh = ambient_mesh()
    # a Mosaic kernel cannot be partitioned by GSPMD: one device's work
    if (segment_ids is None and jax.default_backend() == "tpu"
            and short_conv.kernel_takes(bcu, taps)
            and (mesh is None or mesh.size == 1)):
        return short_conv.gated_short_conv(bcu, taps)
    return short_conv.gated_conv_reference(bcu, taps, segment_ids)


class ShortConv(nn.Module):
    """A gated short convolution as the token mixer (LFM2):
    ``[B | C | u] = W_in h``; ``W_out (C * conv(B * u))``, the convolution
    causal and depthwise over ``cfg.conv_taps`` positions, no bias, no
    activation, no state beyond those positions.  ``conv_proj`` is the two
    projections, ``conv_mix`` the gates and the taps between them."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, x: jax.Array,
                 segment_ids: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.config
        h = cfg.hidden_size
        init = nn.initializers.lecun_normal()
        with device_scope("conv_proj"):
            bcu = nn.DenseGeneral(
                (3, h), use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, dot_general=cfg.dot_general,
                kernel_init=nn.with_logical_partitioning(
                    init, ("embed", None, "mlp")),
                name="in_proj")(x)
            bcu = with_logical_constraint(
                bcu, ("batch", "seq", None, "mlp"))
        taps = self.param(
            "taps", nn.with_logical_partitioning(_taps_init, (None, "mlp")),
            (cfg.conv_taps, h), cfg.param_dtype)
        with device_scope("conv_mix"):
            y = _conv_mix(bcu, taps, segment_ids)
        with device_scope("conv_proj"):
            y = with_logical_constraint(y, ("batch", "seq", "mlp"))
            return nn.DenseGeneral(
                h, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, dot_general=cfg.dot_general,
                kernel_init=nn.with_logical_partitioning(
                    init, ("mlp", "embed")),
                name="out_proj")(y)


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
        with device_scope("mlp"):
            gate = dense(
                cfg.intermediate_size, ("embed", "mlp"), "gate_proj")(x)
            up = dense(cfg.intermediate_size, ("embed", "mlp"), "up_proj")(x)
            h = nn.silu(gate) * up
            h = with_logical_constraint(h, ("batch", "seq", "mlp"))
            # Deliberately NOT checkpoint-named: the wide [.., intermediate]
            # tensors dominate saved-activation memory; the "names" remat
            # policy recomputes them in backward instead of storing them.
            return checkpoint_name(
                dense(cfg.hidden_size, ("mlp", "embed"), "down_proj")(h),
                "mlp_out")


class DecoderLayer(nn.Module):
    config: LlamaConfig
    spec: Optional[LayerSpec] = None

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        positions: jax.Array,
        segment_ids: Optional[jax.Array] = None,
        decode: bool = False,
        cache_len: Optional[int] = None,
        rope=None,
        stacked=None,
    ) -> jax.Array:
        """``stacked``: a sparse layer's expert weights as the scan over
        layers stacks them, and the layer's index (``MoEMLP``)."""
        cfg, spec = self.config, self.spec
        conv = spec is not None and spec.mixer == "conv"
        # the layer's two norms count with the mixer's projections
        # (``attn_proj``, ``conv_proj``)
        proj_scope = "conv_proj" if conv else "attn_proj"
        with device_scope(proj_scope):
            h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                        name="input_norm")(x)
        if conv:
            x = x + ShortConv(cfg, name="conv")(h, segment_ids)
        else:
            x = x + Attention(cfg, spec, name="attn")(
                h, positions, segment_ids, decode=decode,
                cache_len=cache_len, rope=rope)
        x = with_logical_constraint(x, ("batch", "seq", "act_embed"))
        with device_scope(proj_scope):
            h = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                        name="post_norm")(x)
        if cfg.num_experts and (spec is None or spec.mlp == "sparse"):
            from dlrover_tpu.models.moe import MoEMLP

            mlp = MoEMLP(
                hidden_size=cfg.hidden_size,
                intermediate_size=cfg.expert_width,
                num_experts=cfg.num_experts,
                top_k=cfg.moe_top_k,
                norm_topk_prob=cfg.moe_norm_topk_prob,
                aux_loss_coef=cfg.moe_aux_loss_coef,
                z_loss_coef=cfg.moe_z_loss_coef,
                score_fn=cfg.moe_score_fn,
                routed_scale=cfg.moe_routed_scale,
                shared_width=cfg.moe_shared_width,
                experts_held=cfg.moe_experts_held,
                per_expert_init=cfg.moe_per_expert_init,
                select_bias=cfg.moe_select_bias,
                select_bias_std=cfg.moe_select_bias_std,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                fp8=cfg.fp8,
                name="mlp",
            )
            x = x + mlp(h, stacked)
        else:
            x = x + MLP(cfg, name="mlp")(h)
        return with_logical_constraint(x, ("batch", "seq", "act_embed"))


class _ScanLayer(nn.Module):
    """DecoderLayer adapted to nn.scan's (carry, None) calling convention."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, carry, index, stacks):
        x, positions, segment_ids = carry
        x = DecoderLayer(self.config, name="layer")(
            x, positions, segment_ids,
            stacked=(stacks["layer"], index) if stacks else None)
        return (x, positions, segment_ids), None


def _layer_class(cfg: LlamaConfig, in_scan: bool):
    """``DecoderLayer``, under ``nn.remat`` where the config asks.  A
    layer OUTSIDE a scan keeps ``prevent_cse``: without it XLA merges the
    recomputed forward with the first one and nothing is rematerialised
    (read off a v5e capture, PR 31: three flash calls for the leading
    layer where the scanned ones have four)."""
    if not cfg.remat:
        return DecoderLayer
    # decode and cache_len (positions 4 and 5, self counted) stay Python
    # values under the trace
    return nn.remat(DecoderLayer, policy=resolve_remat_policy(cfg.remat_policy),
                    prevent_cse=not in_scan, static_argnums=(4, 5))


class _ScanPeriod(nn.Module):
    """One period of a model whose layers are not all alike, as
    ``nn.scan``'s body: the period's layers in order, each under its OWN
    remat, so that the backward's live set stays one layer's."""

    config: LlamaConfig
    specs: Tuple[LayerSpec, ...]

    @nn.compact
    def __call__(self, carry, index, stacks):
        x, positions, segment_ids, ropes = carry
        cfg = self.config
        layer_cls = _layer_class(cfg, in_scan=True)
        kinds = cfg.rope_kinds
        for j, spec in enumerate(self.specs):
            name = f"layer_{j}"
            x = layer_cls(cfg, spec, name=name)(
                x, positions, segment_ids, False, None,
                ropes[kinds.index(spec.rope)],
                (stacks[name], index) if name in (stacks or ()) else None)
        return (x, positions, segment_ids, ropes), None


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
        if any(s.mixer not in ("attn", "conv") for s in cfg.layer_specs):
            raise NotImplementedError(
                "LlamaModel trains attention layers and gated short "
                "convolutions (LayerSpec.mixer='attn', 'conv'): a layer "
                "whose mixer is linear attention ('kda'), a state-space "
                "scan ('ssm') or power retention ('retention') is served "
                "only (serving/linear.py).  Missing: "
                "the chunk kernel's backward (ops/pallas/kda.py, "
                "ops/pallas/ssm.py, ops/pallas/retention.py) and a "
                "training layer around it (ROADMAP Reach A6)")
        if cfg.embedding_mult != 1.0 or cfg.residual_mult != 1.0 \
                or cfg.attn_scale is not None:
            raise NotImplementedError(
                "LlamaModel has no embedding or residual multiplier and no "
                f"stated softmax scale (embedding_mult={cfg.embedding_mult}, "
                f"residual_mult={cfg.residual_mult}, attn_scale="
                f"{cfg.attn_scale}): they are served only "
                "(serving/latent.py's loop of layer kinds)")
        if cfg.index_topk and not cfg.kv_lora_rank:
            raise NotImplementedError(
                "LlamaModel's grouped-query block has no indexer "
                f"(index_topk={cfg.index_topk}): a learned selection of keys "
                "is served only (serving/latent.py _gqa_layer).  Missing: the "
                "selection's own training loss, which aligns the index "
                "scores with the attention they stand for (ROADMAP Reach A5)")
        if cfg.kv_lora_rank or cfg.moe_first_dense:
            raise NotImplementedError(
                "LlamaModel trains the grouped-query block and the gated "
                "short convolution: latent "
                f"attention (kv_lora_rank={cfg.kv_lora_rank}), its indexer "
                "(latent layers of two geometries and a window among them, "
                "the rescale behind their norms), "
                "and leading dense layers by count (moe_first_dense="
                f"{cfg.moe_first_dense}; a model trained here describes "
                "its layers one by one, LlamaConfig.layers) are served only "
                "(serving/latent.py); a training layer for them is "
                "ROADMAP Reach A5")
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
        with device_scope("embed"):
            x = embed(input_ids)
        x = with_logical_constraint(x, ("batch", "seq", "act_embed"))

        if decode and cfg.scan_layers:
            raise NotImplementedError(
                "KV-cache decode needs per-layer cache variables; use "
                "scan_layers=False for generation configs (training keeps "
                "scan_layers=True — the cache never exists under training)"
            )
        if cfg.layers is not None:
            x = self._mixed_layers(x, positions, segment_ids, decode)
        elif cfg.scan_layers:
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
                in_axes=(0, nn.broadcast),
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )
            (x, _, _), _ = scan(cfg, name="layers")(
                (x, positions, segment_ids),
                *self._stacked_experts("layers", cfg.num_layers))
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

        with device_scope("head"):
            x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, cfg.param_dtype,
                        name="final_norm")(x)

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
            return with_logical_constraint(
                logits, ("batch", "seq", "vocab"))

    def _mixed_layers(self, x, positions, segment_ids, decode):
        """The layers of a model that has more than one kind: the leading
        layers that do not repeat run unrolled (``layer_<i>``), the rest
        is ``nn.scan`` over PERIODS (``periods/layer_<j>``).  Each kind's
        rotary table is made here, once a step."""
        cfg = self.config
        if decode:
            raise NotImplementedError(
                "KV-cache decode knows one kind of layer (ROADMAP A3, A4)")
        specs, kinds = cfg.layer_specs, cfg.rope_kinds
        with device_scope("attn_proj"):
            ropes = tuple(rope_table(kind, cfg.head_dim_, positions)
                          for kind in kinds)
        lead, period = (layer_pattern(specs) if cfg.scan_layers
                        else (len(specs), 0))
        layer_cls = _layer_class(cfg, in_scan=False)
        for i in range(lead):
            x = layer_cls(cfg, specs[i], name=f"layer_{i}")(
                x, positions, segment_ids, False, None,
                ropes[kinds.index(specs[i].rope)])
        if period:
            scan = nn.scan(
                _ScanPeriod,
                variable_axes={"params": 0, "moe_losses": 0},
                split_rngs={"params": True},
                in_axes=(0, nn.broadcast),
                length=(len(specs) - lead) // period,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )
            (x, _, _, _), _ = scan(
                cfg, specs[lead:lead + period], name="periods")(
                    (x, positions, segment_ids, ropes),
                    *self._stacked_experts(
                        "periods", (len(specs) - lead) // period))
        return x

    def _stacked_experts(self, name: str, length: int):
        """What the scan ``name`` over ``length`` layers (or periods) gets
        beside its carry: each step's index and, to every step alike, the
        expert weights of its sparse layers as the parameter tree stacks
        them (``moe.stacked_expert_weights``), so that the grouped matmuls
        read a layer's tiles in the stack (``moe.grouped_matmul``) where
        XLA would copy the layer out for them.  The gradient goes through
        the scan's own slices, as ever.

        ``(None, None)``, and the scan is traced as it always was, where
        there is no stack the kernels could read: a model without experts;
        ``init``; weights quantised for the matmuls or kept in another
        dtype than theirs; a mesh of several devices, where the kernel's
        operands are replicated first and a stack would be gathered
        whole."""
        cfg = self.config
        mesh = ambient_mesh()
        if (not cfg.num_experts or cfg.fp8
                or not self.has_variable("params", name)
                or (mesh is not None and mesh.size > 1)):
            return None, None
        from dlrover_tpu.models.moe import stacked_expert_weights

        stacks = stacked_expert_weights(
            {layer: tree["mlp"] for layer, tree in nn.unbox(
                self.get_variable("params", name)).items()}, cfg.dtype)
        if stacks is None:
            return None, None
        return jnp.arange(length), stacks
