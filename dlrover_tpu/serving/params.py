"""Serving-layout parameters: the training tree flattened for decode.

The serving engine (`dlrover_tpu.serving.engine`) runs a dedicated
functional forward (`dlrover_tpu.serving.model`) instead of the flax
training module — the same split the reference makes between its
training model and the vLLM inference backend it hands RL rollouts to
(reference: atorch/atorch/rl/inference_backend/vllm_backend.py:11-24,
which wraps weights into a purpose-built inference engine rather than
reusing the trainer's module).

Why a separate layout:

- every projection becomes a plain 2D ``[K, N]`` matrix so the int8
  serving path can PRE-quantize it once into the exact layout the
  Pallas kernel reads (``ops/pallas/quant_matmul.prequantize_weight``)
  — fixing the measured 0.6x w8a8 shortfall whose cause was per-call
  dynamic weight quantization;
- layers are stacked along a leading axis so prefill/decode scan over
  them with one compiled body (same trick as training ``nn.scan``);
- the tree is a plain dict of arrays — no flax module state, trivially
  shardable/donatable.

Weight entries are either an fp array ``[K, N]`` or a
``{"q": int8 [K, N], "scale": f32 [1, N]}`` pair.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.models.llama import LlamaConfig
from dlrover_tpu.ops.pallas.quant_matmul import prequantize_weight

# weights quantized when int8=True; norms/embedding always stay fp.
# wqkv / wgu are load-time fusions: one [E, H*D+2*KV*D] matmul instead
# of three and one [E, 2F] instead of two — fewer, larger kernels (the
# standard serving fusion; decode is launch/bandwidth-bound)
_LAYER_MATS = ("wqkv", "wo", "wgu", "down",
               "wq", "wk", "wv", "wgate", "wup")


def _maybe_quant(w: jax.Array, int8: bool):
    if not int8:
        return w
    q, scale = prequantize_weight(jnp.asarray(w, jnp.float32))
    return {"q": q, "scale": scale}


def _layer_tree(
    p: Dict[str, Any], cfg: LlamaConfig, fuse: bool = True
) -> Dict[str, Any]:
    """One flax DecoderLayer param subtree -> serving 2D matrices.

    Handles both the per-layer form ([E, H, D] kernels) and the
    ``nn.scan`` stacked form ([L, E, H, D]): only trailing dims
    collapse, any leading layer axis passes through.
    """
    attn = p["attn"]

    def merge_last2(w):   # [..., E, H, D] -> [..., E, H*D]
        return w.reshape(*w.shape[:-2], w.shape[-2] * w.shape[-1])

    def merge_head_in(w):  # [..., H, D, E] -> [..., H*D, E]
        return w.reshape(*w.shape[:-3], w.shape[-3] * w.shape[-2],
                         w.shape[-1])

    wq = merge_last2(attn["q_proj"]["kernel"])
    wk = merge_last2(attn["k_proj"]["kernel"])
    wv = merge_last2(attn["v_proj"]["kernel"])

    def flat(b):  # [..., H, D] -> [..., H*D]
        return jnp.asarray(b).reshape(
            *b.shape[:-2], b.shape[-2] * b.shape[-1]
        )

    out = {
        "input_norm": p["input_norm"]["scale"],
        "post_norm": p["post_norm"]["scale"],
        "wo": merge_head_in(attn["o_proj"]["kernel"]),
        "down": p["mlp"]["down_proj"]["kernel"],
    }
    if fuse:
        out["wqkv"] = jnp.concatenate(
            [jnp.asarray(wq), jnp.asarray(wk), jnp.asarray(wv)],
            axis=-1)
        out["wgu"] = jnp.concatenate(
            [jnp.asarray(p["mlp"]["gate_proj"]["kernel"]),
             jnp.asarray(p["mlp"]["up_proj"]["kernel"])], axis=-1)
        if "bias" in attn["q_proj"]:
            # Qwen2-family qkv biases, fused to match the wqkv layout
            out["bqkv"] = jnp.concatenate(
                [flat(attn["q_proj"]["bias"]),
                 flat(attn["k_proj"]["bias"]),
                 flat(attn["v_proj"]["bias"])], axis=-1,
            )
    else:
        # UNFUSED layout for tensor-parallel serving: a fused
        # [q|k|v] (or [gate|up]) column block sharded down its last
        # axis hands device 0 all the q heads — per-matrix weights
        # shard head-correctly with a plain P(None, "tp")
        out["wq"], out["wk"], out["wv"] = (
            jnp.asarray(wq), jnp.asarray(wk), jnp.asarray(wv))
        out["wgate"] = jnp.asarray(p["mlp"]["gate_proj"]["kernel"])
        out["wup"] = jnp.asarray(p["mlp"]["up_proj"]["kernel"])
        if "bias" in attn["q_proj"]:
            out["bq"] = flat(attn["q_proj"]["bias"])
            out["bk"] = flat(attn["k_proj"]["bias"])
            out["bv"] = flat(attn["v_proj"]["bias"])
    return out


def serving_params_from_llama(
    variables: Any,
    cfg: LlamaConfig,
    int8: bool = False,
    dtype=None,
    fuse: bool = True,
) -> Dict[str, Any]:
    """Convert a ``LlamaModel`` variables dict (either per-layer
    ``layer_{i}`` naming or the ``nn.scan`` stacked form) into the
    serving layout; ``int8=True`` pre-quantizes every projection into
    the Pallas kernel layout at load time."""
    import flax.linen as nn

    if any(s.mixer == "conv" for s in cfg.layer_specs):
        raise ValueError(
            "a gated short convolution (LayerSpec.mixer='conv') is trained, "
            "not served.  Missing: a convolution state a slot (the last "
            f"conv_taps - 1 = {cfg.conv_taps - 1} gated rows of a layer) "
            "with NO recurrent state beside it in serving/linear.py "
            "state_shapes, and its decode and prefill-chunk steps in "
            "serving/latent.py::_state_mixer (ROADMAP Reach A4)")
    if cfg.qk_norm and not cfg.kv_lora_rank and (
            cfg.qk_norm_kind != "head" or not cfg.layer_kinds):
        raise ValueError(
            "the serving engine's grouped-query blocks have no QK-norm of "
            f"this kind (qk_norm={cfg.qk_norm}, qk_norm_kind="
            f"{cfg.qk_norm_kind!r}, layer_kinds={cfg.layer_kinds}): the "
            "model would be served as a different one.  Served: each head "
            "under one scale ('head') behind the loop of layer kinds "
            "(serving/latent.py::_gqa_layer).  Missing: the norm over the "
            "whole projection ('projection') there, and either kind in the "
            "dense decoder's own loop, serving/model.py::_attn_proj "
            "(ROADMAP Reach A3)")
    attn = {dataclasses.replace(s, mlp="dense") for s in cfg.layer_specs
            if s.mixer == "attn"}
    if not cfg.kv_lora_rank and (
            cfg.attn_head_gate or len(attn) > 1 or any(
                s.window or s.rope.rotary_fraction not in (0.0, 1.0)
                or s.rope.yarn_factor for s in attn)
            or (cfg.layers is not None and not cfg.layer_kinds)):
        raise ValueError(
            "the serving engine's grouped-query layers are ONE kind of "
            "layer: full causal attention with one head count, "
            "rotated whole or not at all, no head gate (attn_head_gate="
            f"{cfg.attn_head_gate}), with or without a QK-norm a head and "
            f"an indexer; this model describes {len(attn)} kinds "
            "of attention layer.  Beside them a model may have layers that "
            "keep a recurrent state (LayerSpec.mixer) and sparse MLPs "
            "(serving/latent.py's loop).  Missing behind the grouped-query "
            "block: a lower bound on the keys in "
            "ops/pallas/paged_attention.py and window rows in its cache "
            "(ROADMAP A4), per-layer head counts, an indexer in some layers "
            "only, a head gate, partial rotary and YaRN in "
            "serving/latent.py::_gqa_layer (A3)")
    if cfg.index_topk and not cfg.kv_lora_rank and not cfg.layer_kinds:
        raise ValueError(
            "a learned selection of keys behind the grouped-query block "
            f"(index_topk={cfg.index_topk}) is served by the loop of layer "
            "kinds (serving/latent.py::_gqa_layer), and this dense model is "
            "served by serving/model.py's own loop, which has no indexer, "
            "no index-key pool and no mask on its attention (ROADMAP Reach "
            "A12)")
    if cfg.layer_kinds:
        if int8 or not fuse:
            raise ValueError(
                "a model of layer kinds (latent attention, a recurrent "
                "state a slot, sparse experts) is served in its own dtype "
                "on one device: no int8 weights, no tensor-parallel mesh")
        return _latent_params(variables, cfg, dtype or cfg.dtype)
    if dtype is None:
        dtype = cfg.dtype
    variables = nn.meta.unbox(variables)
    params = variables["params"] if "params" in variables else variables
    if "layers" in params:  # scan form: unstack the leading layer axis
        stacked = _layer_tree(params["layers"]["layer"], cfg, fuse)
        per_layer = [
            {k: v[i] for k, v in stacked.items()}
            for i in range(cfg.num_layers)
        ]
    else:
        per_layer = [
            _layer_tree(params[f"layer_{i}"], cfg, fuse)
            for i in range(cfg.num_layers)
        ]

    # layers stay a LIST of per-layer trees — the decode loop is
    # unrolled, and an unstacked weight is a buffer the Pallas int8
    # kernel (and XLA) reads directly; a stacked array would force a
    # materialized slice copy per layer per step (measured: the copies
    # cost as much as the int8 matmuls they feed)
    def finish(name: str, w):
        if name not in _LAYER_MATS:
            return jnp.asarray(w)
        if int8:
            return _maybe_quant(w, True)
        return jnp.asarray(w, dtype)

    layers = [
        {k: finish(k, v) for k, v in lt.items()} for lt in per_layer
    ]
    embed = jnp.asarray(params["embed_tokens"]["embedding"], dtype)
    out: Dict[str, Any] = {
        "embed": embed,
        "layers": layers,
        "final_norm": params["final_norm"]["scale"],
    }
    if cfg.tie_embeddings:
        out["lm_head"] = None
    else:
        out["lm_head"] = _maybe_quant(
            jnp.asarray(params["lm_head"]["kernel"], dtype), int8
        )
    return out


def _latent_params(variables: Any, cfg: LlamaConfig, dtype
                   ) -> Dict[str, Any]:
    """The serving tree of a model of layer kinds (serving/latent.py): a
    grouped-query model's attention layers as :func:`_layer_tree` fuses
    them (``wqkv``, ``wo``; with a QK-norm a head the two scales ``q_norm``
    and ``k_norm`` [D]; with ``index_topk`` an ``indexer`` named as below
    but for its query projection ``wq`` [E, Hi, Di], from the layer's
    input), beside ``ssm`` layers (``serving/linear.py
    ssm_params``) and the MLPs below; or a latent-attention model's,
    from a ``layer_{i}`` tree named as ``perfbench/reference_glm5.py``,
    ``perfbench/reference_sarvam.py`` and the tests make it: ``attn`` (the
    query through a bottleneck, ``q_a_proj``, ``q_a_norm``, ``q_b_proj``
    [Q, H, nope + rope] (a layer's own ``nope``, heads and latent rank where
    its ``LayerSpec`` has them), or with ``q_lora_rank`` 0 straight from the
    hidden state, ``q_proj`` [E, H, nope + rope]; with ``qk_norm`` a
    ``q_norm`` scale [nope + rope] for every head; ``kv_a_proj`` [E, C +
    rope], ``kv_a_norm``, ``kv_b_proj`` [C, H, nope + V], ``o_proj`` [H,
    V, E], with ``attn_head_gate`` a ``g_proj`` [E, H]), with
    ``index_topk`` in a layer whose ``LayerSpec.indexer`` says so an ``indexer``
    (``wq_b`` [Q, Hi, Di], ``wk``, ``k_norm`` scale and bias,
    ``weights_proj``); in place of ``attn`` a layer whose
    ``LayerSpec.mixer`` is "kda" has ``kda`` (``serving/linear.py
    kda_params``), one whose mixer is "retention" has ``retention``
    (``retention_params``: its QK-norm a head and its rotation are the
    block's own); and ``mlp`` as ``LlamaModel`` names a dense one or
    ``MoEMLP`` a sparse one (``select_bias`` beside the router).  The
    latent's up-projection is split into the key part, laid out for the
    absorbed query [H, nope, C], and the value part [H, C, V]; the router
    and its bias stay float32."""
    import flax.linen as nn

    if cfg.index_topk and cfg.kv_lora_rank and not cfg.q_lora_rank:
        raise ValueError(
            "a latent layer's indexer takes its queries from the query's "
            f"bottleneck (index_topk={cfg.index_topk}, q_lora_rank=0): no "
            "published latent model has the one without the other (a "
            "grouped-query layer's takes them from the layer's input)")
    variables = nn.meta.unbox(variables)
    params = variables["params"] if "params" in variables else variables

    def mat(w):
        return jnp.asarray(w, dtype)

    def flat_out(w):      # [in, heads, d] -> [in, heads * d]
        return mat(w).reshape(w.shape[0], -1)

    def mixer(p, spec):
        if spec.mixer == "kda":
            from dlrover_tpu.serving.linear import kda_params

            return kda_params(p["kda"], cfg, dtype)
        if spec.mixer == "ssm":
            from dlrover_tpu.serving.linear import ssm_params

            return ssm_params(p["ssm"], cfg, dtype)
        if spec.mixer == "retention":
            from dlrover_tpu.serving.linear import retention_params

            return retention_params(p["retention"], cfg, dtype)
        if spec.mixer != "attn":
            raise ValueError(f"no served mixer {spec.mixer!r}: a served "
                             "layer is 'attn', 'kda', 'ssm' or 'retention' "
                             "(LayerSpec.mixer; 'conv' is trained only)")
        a = p["attn"]
        _, nope, indexed = cfg.latent_dims(spec)

        def indexer(query_proj):
            ix = p["indexer"]
            return dict(
                iwq=flat_out(ix[query_proj]["kernel"]),
                iwk=mat(ix["wk"]["kernel"]),
                ik_norm_scale=ix["k_norm"]["scale"],
                ik_norm_bias=ix["k_norm"]["bias"],
                iw=mat(ix["weights_proj"]["kernel"]))

        if not cfg.kv_lora_rank:         # the grouped-query block
            out = {
                "wqkv": jnp.concatenate(
                    [flat_out(a[n]["kernel"])
                     for n in ("q_proj", "k_proj", "v_proj")], axis=-1),
                "wo": mat(a["o_proj"]["kernel"]).reshape(
                    -1, cfg.hidden_size)}
            if cfg.qk_norm:              # a head at a time, one scale each
                out.update(q_norm=a["q_norm"]["scale"],
                           k_norm=a["k_norm"]["scale"])
            if indexed:                  # its queries from the layer's input
                out.update(indexer("wq"))
            return out
        kv_b = mat(a["kv_b_proj"]["kernel"])             # [C, H, nope+V]
        out = {
            "wkv_a": mat(a["kv_a_proj"]["kernel"]),
            "kv_a_norm": a["kv_a_norm"]["scale"],
            "wkv_b_k": kv_b[..., :nope].transpose(1, 2, 0),
            "wkv_b_v": kv_b[..., nope:].transpose(1, 0, 2),
            "wo": mat(a["o_proj"]["kernel"]).reshape(
                -1, cfg.hidden_size),
        }
        if cfg.q_lora_rank:
            out.update(wq_a=mat(a["q_a_proj"]["kernel"]),
                       q_a_norm=a["q_a_norm"]["scale"],
                       wq_b=flat_out(a["q_b_proj"]["kernel"]))
        else:
            out["wq_t"] = flat_out(a["q_proj"]["kernel"]).T
        if cfg.qk_norm:
            out["q_norm"] = a["q_norm"]["scale"]
        if cfg.attn_head_gate:
            out["head_gate"] = mat(a["g_proj"]["kernel"])     # [E, H]
        if indexed:
            out.update(indexer("wq_b"))
        return out

    def layer(p, spec):
        out = dict(mixer(p, spec),
                   input_norm=p["input_norm"]["scale"],
                   post_norm=p["post_norm"]["scale"])
        m = p["mlp"]
        if "router" in m:
            out.update(
                router=jnp.asarray(m["router"]["kernel"], jnp.float32),
                w_gate=mat(m["w_gate"]), w_up=mat(m["w_up"]),
                w_down=mat(m["w_down"]))
            if "select_bias" in m:
                out["select_bias"] = jnp.asarray(
                    m["select_bias"], jnp.float32)
            if "shared_gate" in m:
                out["shared_wgu"] = jnp.concatenate(
                    [mat(m["shared_gate"]["kernel"]),
                     mat(m["shared_up"]["kernel"])], axis=-1)
                out["shared_down"] = mat(m["shared_down"]["kernel"])
        else:
            out["wgu"] = jnp.concatenate(
                [mat(m["gate_proj"]["kernel"]),
                 mat(m["up_proj"]["kernel"])], axis=-1)
            out["down"] = mat(m["down_proj"]["kernel"])
        return out

    return {
        "embed": mat(params["embed_tokens"]["embedding"]),
        "layers": [layer(params[f"layer_{i}"], spec)
                   for i, spec in enumerate(cfg.layer_specs)],
        "final_norm": params["final_norm"]["scale"],
        "lm_head": None if cfg.tie_embeddings
        else mat(params["lm_head"]["kernel"]),
    }


def serving_params_nbytes(sp: Dict[str, Any]) -> int:
    from dlrover_tpu.optimizers.low_bit import state_nbytes

    return state_nbytes(sp)


# -- tensor-parallel serving ------------------------------------------------

# output-dim-sharded matrices (column parallel) vs input-dim-sharded
# (row parallel, psum after): the Megatron split, realized here purely
# through input placement — jit propagates the shardings and GSPMD
# inserts the collectives (scaling-book recipe; no hand-written
# collectives anywhere)
_COL_PARALLEL = ("wq", "wk", "wv", "wgate", "wup")
_ROW_PARALLEL = ("wo", "down")


def _mat_spec(name: str, P):
    if name in _COL_PARALLEL:
        return P(None, "tp")
    if name in _ROW_PARALLEL:
        return P("tp", None)
    return P()  # norms, biases of replicated mats


def shard_serving_state(
    params: Dict[str, Any], cache: Dict[str, Any], mesh, cfg: LlamaConfig
) -> tuple:
    """Place the serving params + KV cache onto a ``tp`` mesh.

    Column-parallel q/k/v/gate/up, row-parallel o/down, tp-sharded
    lm_head columns, kv-heads-sharded cache; requires the UNFUSED param
    layout (``serving_params_from_llama(fuse=False)``) and
    ``num_kv_heads % tp == 0``.  int8 ``{"q","scale"}`` pairs shard the
    codes like the fp matrix and the per-column scales with the output
    dim.  Everything else replicates."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    tp = mesh.shape.get("tp", 1)
    if cfg.num_kv_heads % tp or cfg.num_heads % tp:
        raise ValueError(
            f"tp={tp} must divide num_heads={cfg.num_heads} and "
            f"num_kv_heads={cfg.num_kv_heads}"
        )
    if any("wqkv" in lt for lt in params["layers"]):
        raise ValueError(
            "sharded serving needs the unfused param layout: build "
            "with serving_params_from_llama(..., fuse=False)"
        )

    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    def place_mat(name: str, w):
        spec = _mat_spec(name, P)
        if isinstance(w, dict):  # int8 {"q","scale"}
            scale_spec = P(None, "tp") if name in _COL_PARALLEL else P()
            return {"q": put(w["q"], spec),
                    "scale": put(w["scale"], scale_spec)}
        return put(w, spec)

    layers = [
        {k: place_mat(k, v) for k, v in lt.items()}
        for lt in params["layers"]
    ]
    out = {
        "embed": put(params["embed"], P()),
        "final_norm": put(params["final_norm"], P()),
        "layers": layers,
    }
    head = params.get("lm_head")
    out["lm_head"] = (
        None if head is None else place_mat("wgate", head)  # col spec
    )

    kv_spec = P(None, None, "tp", None)  # [.., .., KV, D]
    scale_spec = P(None, None, "tp")     # [NB, bs, KV] int8 KV scales
    sharded_cache = {}
    for key, val in cache.items():
        if key in ("k", "v", "k_pool", "v_pool"):
            sharded_cache[key] = [put(x, kv_spec) for x in val]
        elif key in ("k_scale", "v_scale"):
            sharded_cache[key] = [put(x, scale_spec) for x in val]
        else:
            sharded_cache[key] = put(val, P())
    return out, sharded_cache
