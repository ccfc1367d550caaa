"""The served blocks that keep a recurrent STATE A SLOT and no rows: Kimi
Delta Attention (``LayerSpec.mixer`` "kda"), the Mamba-2 state-space
layer ("ssm") and power retention ("retention", at the end of this file),
what ``serving/latent.py``'s layer loop runs in place of attention for
such a layer.  :func:`state_shapes` is the ONE place that says which
arrays a kind keeps a slot (a float32 state each; beside it the first two
keep a convolution's last inputs, the third a float32 sum of keys and NO
convolution); :data:`BLOCKS` gives the kind's two functions, one token a
slot and a run of one slot's tokens.

Kimi Delta Attention.

On its normed input ``x`` (one token a slot at decode, a run of one
slot's tokens in a prompt chunk):

- ``[q' | k' | v] = SiLU(conv(W_qkv x))``: ONE matmul, then a causal
  depthwise convolution over the last ``kda_conv`` positions a channel
  (the positions ahead of this call are the slot's ``conv`` state, its
  last ``kda_conv - 1`` inputs), SiLU; ``q = q' / |q'| x d^-0.5``, ``k =
  k' / |k'|`` a head;
- the log-decay a channel ``g = -exp(A_h) softplus(W_f2 W_f1 x + b)``
  and ``beta = sigmoid(W_b x)`` a head, both float32;
- the delta rule over the slot's ``state`` (one float32 ``[d, d]`` a
  head): ``ops/pallas/kda.py``, the kernels with ``impl == "pallas"``,
  the ``jnp`` recurrence otherwise;
- ``y = W_o [RMSNorm_head(o) x sigmoid(W_g2 W_g1 x)]``.

The state belongs to a SLOT, not to blocks: ``state`` [slots, H, d, d]
float32 and ``conv`` [kda_conv - 1, slots, 3 H d] (the model's dtype: the
projection's own rounding, so a prompt chunk and a decode forward see the
same inputs; taps ahead of slots, the order the chip's compiler keeps it
in: with slots first it copied every layer's rows into that order and
back around each decode chunk, compiled for a described v5e, PR 43).  A
decode forward advances the slots ``active`` marks and leaves the others
as they were; a prompt chunk advances ITS slot, from
zeros where it is the prompt's first (``fresh``: no host-side write
between steps), to the state after its last real token (``n_real``).

Device scopes: ``kda_proj`` (projections, convolution, norms of q and k,
decay and beta), ``kda_scan`` (the delta rule), ``kda_out`` (gated norm
and ``W_o``).

Mamba-2 (:func:`ssm_decode`, :func:`ssm_run`), on its normed input ``u``:

- ``[z | xBC | dt] = W_in u``: ONE matmul, float32 sums (``dt`` and the
  gate ``z`` are not rounded on the way; ``xBC`` is, to the model's dtype,
  the convolution rows' own); ``xBC <- SiLU(conv(xBC) + bias)`` over the
  last ``ssm_conv`` positions a channel; ``[x | B | C] = xBC``, B and C
  shared by all heads;
- ``Delta = softplus(dt + dt_bias)`` and the log-decay ``-exp(A_log)
  Delta``, ONE scalar a head and token, float32;
- the scan over the slot's ``state`` (one float32 ``[P, N]`` a head):
  ``ops/pallas/ssm.py``, the kernels with ``impl == "pallas"``, the
  ``jnp`` recurrence otherwise; ``y = S C + D x``;
- ``W_out RMSNorm(y x SiLU(z))``: the gate INSIDE the norm, one learned
  scale over all ``H x P`` channels.

``state`` [slots, H / pack, N, pack x P] float32 (the kernels' kept layout:
``ops/pallas/ssm.py packed_shape``) and ``conv`` [ssm_conv - 1, slots, H P
+ 2 N], with the life KDA's have.  Device scopes: ``ssm_proj``,
``ssm_scan`` (the kernel alone), ``ssm_out``.

Power retention (:func:`retention_decode`, :func:`retention_run`), on its
normed input ``x``, over the model's OWN heads (``num_heads`` queries on
``num_kv_heads`` keys of ``head_dim``):

- ``[q | k | v] = W_qkv x``: ONE matmul; each head of q and of k
  RMS-normed (one learned scale for all query heads, one for all key
  heads) and rotated over the whole head, halves paired, at the layer's
  theta: the first state mixer that reads POSITIONS;
- the gate ``log g = logsigmoid(W_gate x + b)``, ONE scalar a KEY head and
  token, float32 sums;
- the recurrence over the slot's ``state`` and ``keysum`` (``S`` and
  ``z`` of ``ops/pallas/retention.py``: a float32 ``[tiles, d, d]`` and
  ``[tiles, d]`` a key head, the symmetric square's unordered pairs once
  each, shared by the ``num_heads / num_kv_heads`` query heads of the key
  head): the kernels with ``impl == "pallas"``, the ``jnp`` recurrence
  otherwise; the division is the kernels';
- ``W_o y``.

Device scopes: ``ret_proj`` (projections, head norms, rotation, gate),
``ret_scan`` (the kernel alone), ``ret_out``.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from dlrover_tpu.models.llama import (LlamaConfig, apply_rope,
                                      rope_inverse_frequencies)
from dlrover_tpu.ops.pallas import kda, retention, ssm
from dlrover_tpu.serving.model import _mm, _rmsnorm
from dlrover_tpu.utils.profiler import device_scope

#: added to a head's squared norm of q and k before the root
_L2_EPS = 1e-6


def kda_params(p: Dict[str, Any], cfg: LlamaConfig, dtype
               ) -> Dict[str, Any]:
    """A layer's ``kda`` subtree, named as ``perfbench/reference_kimi_
    linear.py`` and the tests make it (``q_proj`` / ``k_proj`` /
    ``v_proj`` [E, H, d]; ``q_conv`` / ``k_conv`` / ``v_conv`` [taps, H x
    d]; ``f_a_proj``, ``f_b_proj``, ``dt_bias`` [H x d], ``A_log`` [H];
    ``b_proj`` [E, H]; ``g_a_proj``, ``g_b_proj``; ``o_norm`` [d];
    ``o_proj`` [H, d, E]), as the serving tree: q, k and v fused into one
    matrix and one filter; the decay's constants float32."""
    def mat(w):
        return jnp.asarray(w, dtype)

    def flat(name):
        w = mat(p[name]["kernel"])
        return w.reshape(w.shape[0], -1)

    f32 = jnp.float32
    return {
        "kda_wqkv": jnp.concatenate(
            [flat("q_proj"), flat("k_proj"), flat("v_proj")], axis=-1),
        "kda_conv": jnp.concatenate(
            [jnp.asarray(p[n]["kernel"], f32)
             for n in ("q_conv", "k_conv", "v_conv")], axis=-1),
        "kda_wf1": mat(p["f_a_proj"]["kernel"]),
        "kda_wf2": mat(p["f_b_proj"]["kernel"]),
        "kda_dt_bias": jnp.asarray(p["dt_bias"], f32),
        "kda_rate": jnp.exp(jnp.asarray(p["A_log"], f32)),
        "kda_wb": mat(p["b_proj"]["kernel"]),
        "kda_wg1": mat(p["g_a_proj"]["kernel"]),
        "kda_wg2": mat(p["g_b_proj"]["kernel"]),
        "kda_o_norm": p["o_norm"]["scale"],
        "kda_wo": mat(p["o_proj"]["kernel"]).reshape(-1, cfg.hidden_size),
    }


class Held(NamedTuple):
    """One array a layer keeps for every slot: its shape over ``slots``
    slots, its dtype, and which axis counts the slots."""

    shape: Tuple[int, ...]
    dtype: Any
    slot_axis: int


def state_shapes(cfg: LlamaConfig, slots: int,
                 kind: str = "kda") -> Dict[str, Held]:
    """What one layer of ``kind`` ("kda" | "ssm" | "retention") keeps for
    ``slots`` slots, by name, in the order :data:`BLOCKS`' functions take
    and return them (the engine's cache holds ``<kind>_<name>``, a list
    over the layers of that kind).  Every kind keeps a float32 ``state``.
    Beside it "kda" and "ssm" keep ``conv``, the convolution's last
    inputs in the model's dtype, taps ahead of slots; "retention" keeps
    ``keysum``, the float32 sum of keys that normalises its read-out, and
    NO convolution."""
    f32 = jnp.float32
    if kind == "retention":
        hk, d = cfg.num_kv_heads, cfg.head_dim_
        tiles = retention.kept_tiles(d)
        return {"state": Held((slots, hk, tiles, d, d), f32, 0),
                "keysum": Held((slots, hk, tiles, d), f32, 0)}
    if kind == "ssm":
        h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        return {"state": Held((slots,) + ssm.packed_shape(h, p, n), f32, 0),
                "conv": Held((cfg.ssm_conv - 1, slots, h * p + 2 * n),
                             cfg.dtype, 1)}
    h, d = cfg.kda_heads, cfg.kda_head_dim
    return {"state": Held((slots, h, d, d), f32, 0),
            "conv": Held((cfg.kda_conv - 1, slots, 3 * h * d), cfg.dtype,
                         1)}


def _mm32(x, w, dtype):
    """``x @ w`` with operands in ``dtype`` and the float32 sums kept: the
    decay, beta and the gate are functions of these, and are not rounded
    to ``dtype`` on the way."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32)


def _l2(x):
    return x * jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)


def _inputs(lp, x, mixed, cfg: LlamaConfig, dtype):
    """``x`` [T, E] (the block's normed input) and ``mixed`` [T, 3 H d]
    float32 (the projection's outputs behind the convolution) -> ``q k v
    g`` [T, H, d] and ``beta`` [T, H], float32, and ``f`` [T, H, d], what
    the decay is the function of (for a witness: ``g`` against ``f`` says
    in which precision the decay was computed, whatever ``x`` was)."""
    t = x.shape[0]
    h, d = cfg.kda_heads, cfg.kda_head_dim
    qkv = jax.nn.silu(mixed).reshape(t, 3, h, d)
    q = _l2(qkv[:, 0]) * float(d ** -0.5)
    k = _l2(qkv[:, 1])
    f = _mm32(_mm(x, lp["kda_wf1"], dtype), lp["kda_wf2"], dtype) \
        + lp["kda_dt_bias"]
    f = f.reshape(t, h, d)
    g = -lp["kda_rate"][:, None] * jax.nn.softplus(f)
    beta = jax.nn.sigmoid(_mm32(x, lp["kda_wb"], dtype))
    return q, k, qkv[:, 2], g, beta, f


def _output(lp, x, o, cfg: LlamaConfig, dtype):
    """``o`` [T, H, d] float32 -> the block's output [T, E]."""
    with device_scope("kda_out"):
        gate = jax.nn.sigmoid(_mm32(
            _mm(x, lp["kda_wg1"], dtype), lp["kda_wg2"], dtype)
        ).reshape(o.shape)
        y = _rmsnorm(o, lp["kda_o_norm"], cfg.rms_norm_eps) * gate
        return _mm(y.reshape(o.shape[0], -1).astype(dtype), lp["kda_wo"],
                   dtype)


def kda_decode(lp, x, state, conv, active, cfg: LlamaConfig, dtype,
               impl: str, interpret: bool, pos=None):
    """One token a slot: ``x`` [B, E], ``state`` [B, H, d, d], ``conv``
    [taps - 1, B, 3 H d], ``active`` [B] bool (``pos``, the tokens'
    positions, is not read: nothing here is rotated).  Returns ``(y [B, E],
    state, conv, decay)``; a slot that is not active keeps state and conv
    as they were; ``decay`` [B, 2, H, d] is ``(f, g)`` of :func:`_inputs`."""
    with device_scope("kda_proj"):
        new = _mm(x, lp["kda_wqkv"], dtype)
        window = jnp.concatenate([conv, new[None]], axis=0)
        mixed = jnp.sum(window.astype(jnp.float32)
                        * lp["kda_conv"][:, None, :], axis=0)
        q, k, v, g, beta, f = _inputs(lp, x, mixed, cfg, dtype)
        conv = jnp.where(active[None, :, None], window[1:], conv)
    with device_scope("kda_scan"):
        if impl == "pallas":
            o, state = kda.kda_decode_step(state, q, k, v, g, beta, active,
                                           interpret=interpret)
        else:
            o, new_state = kda.kda_step(state, q, k, v, g, beta)
            state = jnp.where(active[:, None, None, None], new_state, state)
    return _output(lp, x, o, cfg, dtype), state, conv, jnp.stack(
        [f, g], axis=1)


def kda_run(lp, x, state, conv, fresh, n_real, cfg: LlamaConfig, dtype,
            impl: str, interpret: bool, pos=None):
    """A run of one slot's tokens: ``x`` [K, E], ``state`` [H, d, d] and
    ``conv`` [taps - 1, 3 H d] the slot's own, ``fresh`` (bool scalar:
    the prompt's first chunk starts from zeros), ``n_real`` (int32
    scalar: behind it the run is padding).  Returns ``(y [K, E], state,
    conv, decay)``, state and conv after the last real token, ``decay``
    [K, 2, H, d] as :func:`kda_decode`'s."""
    taps = cfg.kda_conv
    with device_scope("kda_proj"):
        state = jnp.where(fresh, 0.0, state)
        conv = jnp.where(fresh, jnp.zeros((), conv.dtype), conv)
        new = _mm(x, lp["kda_wqkv"], dtype)
        window = jnp.concatenate([conv, new], axis=0)
        wf = window.astype(jnp.float32)
        mixed = sum(wf[j:j + x.shape[0]] * lp["kda_conv"][j]
                    for j in range(taps))
        q, k, v, g, beta, f = _inputs(lp, x, mixed, cfg, dtype)
        decay = jnp.stack([f, g], axis=1)
        # the last taps - 1 inputs up to the last real token
        conv = jax.lax.dynamic_slice_in_dim(window, n_real, taps - 1)
        kernel = impl == "pallas" and x.shape[0] % kda.CHUNK == 0
        if kernel:
            # every consumer of the projection's output under this scope,
            # the kernel alone under the next
            kb, vb, g = kda.chunk_operands(k, v, g, beta, n_real)
    with device_scope("kda_scan"):
        if kernel:
            o, state = kda.kda_chunk_call(state, q, k, kb, vb, g, n_real,
                                          interpret=interpret)
        else:
            o, state = kda.kda_recurrence(state, q, k, v, g, beta, n_real)
    return _output(lp, x, o, cfg, dtype), state, conv, decay


# ------------------------------------------------------------- Mamba-2
def ssm_params(p: Dict[str, Any], cfg: LlamaConfig, dtype
               ) -> Dict[str, Any]:
    """A layer's ``ssm`` subtree, named as ``perfbench/reference_granite.py``
    and the tests make it (``in_proj`` [E, 2 H P + 2 N + H] to ``[z | xBC |
    dt]``; ``conv`` kernel [taps, H P + 2 N] and bias; ``dt_bias``,
    ``A_log``, ``D`` [H]; ``norm`` [H P]; ``out_proj`` [H P, E]), as the
    serving tree: the scan's constants float32."""
    f32 = jnp.float32
    return {
        "ssm_win": jnp.asarray(p["in_proj"]["kernel"], dtype),
        "ssm_conv": jnp.asarray(p["conv"]["kernel"], f32),
        "ssm_conv_bias": jnp.asarray(p["conv"]["bias"], f32),
        "ssm_dt_bias": jnp.asarray(p["dt_bias"], f32),
        "ssm_rate": jnp.exp(jnp.asarray(p["A_log"], f32)),
        "ssm_skip": jnp.asarray(p["D"], f32),
        "ssm_norm": p["norm"]["scale"],
        "ssm_wout": jnp.asarray(p["out_proj"]["kernel"], dtype),
    }


def _ssm_project(lp, u, cfg: LlamaConfig, dtype):
    """``u`` [T, E] -> the gate ``z`` [T, H P] and ``dt`` [T, H] as the
    float32 sums gave them, ``xBC`` [T, H P + 2 N] in ``dtype``."""
    w = cfg.ssm_heads * cfg.ssm_head_dim
    full = _mm32(u, lp["ssm_win"], dtype)
    return full[:, :w], full[:, w:-cfg.ssm_heads].astype(dtype), \
        full[:, -cfg.ssm_heads:]


def _ssm_inputs(lp, mixed, dt, cfg: LlamaConfig):
    """``mixed`` [T, H P + 2 N] float32 (behind the convolution, bias not
    yet added) and ``dt`` [T, H] -> ``x`` [T, H, P], ``b c`` [T, N], the
    step and the log-decay [T, H], float32."""
    t = mixed.shape[0]
    h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    act = jax.nn.silu(mixed + lp["ssm_conv_bias"])
    step = jax.nn.softplus(dt + lp["ssm_dt_bias"])
    return (act[:, :h * p].reshape(t, h, p), act[:, h * p:h * p + n],
            act[:, h * p + n:], step, -lp["ssm_rate"] * step)


def _ssm_output(lp, y, x, z, cfg: LlamaConfig, dtype):
    """``y`` [T, H, P] (the scan's ``S C``), the scan's input ``x`` and the
    gate ``z`` [T, H P] -> the block's output [T, E]."""
    with device_scope("ssm_out"):
        y = (y + lp["ssm_skip"][:, None] * x).reshape(z.shape)
        o = _rmsnorm(y * jax.nn.silu(z), lp["ssm_norm"], cfg.rms_norm_eps)
        return _mm(o.astype(dtype), lp["ssm_wout"], dtype)


def ssm_decode(lp, u, state, conv, active, cfg: LlamaConfig, dtype,
               impl: str, interpret: bool, pos=None):
    """One token a slot: ``u`` [B, E], ``state`` [B, H / pack, N, pack x
    P], ``conv`` [taps - 1, B, H P + 2 N], ``active`` [B] bool.  Returns ``(y [B, E],
    state, conv, None)``; a slot that is not active keeps state and conv
    as they were."""
    with device_scope("ssm_proj"):
        z, new, dt = _ssm_project(lp, u, cfg, dtype)
        window = jnp.concatenate([conv, new[None]], axis=0)
        mixed = jnp.sum(window.astype(jnp.float32)
                        * lp["ssm_conv"][:, None, :], axis=0)
        x, b, c, dt, la = _ssm_inputs(lp, mixed, dt, cfg)
        conv = jnp.where(active[None, :, None], window[1:], conv)
    with device_scope("ssm_scan"):
        if impl == "pallas":
            y, state = ssm.ssm_decode_step(state, x, dt, la, b, c, active,
                                           interpret=interpret)
        else:
            y, new_state = ssm.ssm_step(
                ssm.unpack_state(state, cfg.ssm_head_dim), x, dt, la, b, c)
            state = jnp.where(active[:, None, None, None],
                              ssm.pack_state(new_state), state)
    return _ssm_output(lp, y, x, z, cfg, dtype), state, conv, None


def ssm_run(lp, u, state, conv, fresh, n_real, cfg: LlamaConfig, dtype,
            impl: str, interpret: bool, pos=None):
    """A run of one slot's tokens: ``u`` [K, E], ``state`` [H / pack, N,
    pack x P] and ``conv`` [taps - 1, H P + 2 N] the slot's own, ``fresh`` and ``n_real``
    as :func:`kda_run`'s.  Returns ``(y [K, E], state, conv, None)``,
    state and conv after the last real token."""
    taps = cfg.ssm_conv
    with device_scope("ssm_proj"):
        state = jnp.where(fresh, 0.0, state)
        conv = jnp.where(fresh, jnp.zeros((), conv.dtype), conv)
        z, new, dt = _ssm_project(lp, u, cfg, dtype)
        window = jnp.concatenate([conv, new], axis=0)
        wf = window.astype(jnp.float32)
        mixed = sum(wf[j:j + u.shape[0]] * lp["ssm_conv"][j]
                    for j in range(taps))
        x, b, c, dt, la = _ssm_inputs(lp, mixed, dt, cfg)
        # the last taps - 1 inputs up to the last real token
        conv = jax.lax.dynamic_slice_in_dim(window, n_real, taps - 1)
        kernel = impl == "pallas" and u.shape[0] % ssm.CHUNK == 0
        if kernel:
            # every consumer of the projection's output under this scope,
            # the kernel alone under the next
            ops = ssm.chunk_operands(x, dt, la, b, c, n_real)
    with device_scope("ssm_scan"):
        if kernel:
            y, state = ssm.ssm_chunk_call(state, *ops, n_real,
                                          interpret=interpret)
        else:
            y, state = ssm.ssm_recurrence(
                ssm.unpack_state(state, cfg.ssm_head_dim), x, dt, la, b, c,
                n_real)
            state = ssm.pack_state(state)
    return _ssm_output(lp, y, x, z, cfg, dtype), state, conv, None


# ------------------------------------------------------ power retention
def retention_params(p: Dict[str, Any], cfg: LlamaConfig, dtype
                     ) -> Dict[str, Any]:
    """A layer's ``retention`` subtree, named as
    ``perfbench/reference_brumby.py`` and the tests make it (``q_proj`` [E,
    Hq, d], ``k_proj`` / ``v_proj`` [E, Hk, d]; ``q_norm`` / ``k_norm``
    scale [d]; ``gate_proj`` kernel [E, Hk] and bias [Hk]; ``o_proj`` [Hq,
    d, E]), as the serving tree: q, k and v fused into one matrix, the
    gate's bias float32."""
    def flat(name):
        w = jnp.asarray(p[name]["kernel"], dtype)
        return w.reshape(w.shape[0], -1)

    return {
        "ret_wqkv": jnp.concatenate(
            [flat("q_proj"), flat("k_proj"), flat("v_proj")], axis=-1),
        "ret_q_norm": p["q_norm"]["scale"],
        "ret_k_norm": p["k_norm"]["scale"],
        "ret_wgate": jnp.asarray(p["gate_proj"]["kernel"], dtype),
        "ret_bgate": jnp.asarray(p["gate_proj"]["bias"], jnp.float32),
        "ret_wo": jnp.asarray(p["o_proj"]["kernel"], dtype).reshape(
            -1, cfg.hidden_size),
    }


def _retention_inputs(lp, x, pos, cfg: LlamaConfig, dtype):
    """``x`` [T, E] (the block's normed input) at positions ``pos`` [T] ->
    ``q`` [T, Hq, d] and ``k`` [T, Hk, d] (normed a head, rotated), ``v``
    [T, Hk, d], the gate's logarithm [T, Hk] and the float32 sums ``f`` it
    is the ``logsigmoid`` of, all float32."""
    t = x.shape[0]
    hq, hk, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    qkv = _mm(x, lp["ret_wqkv"], dtype)
    q = _rmsnorm(qkv[:, :hq * d].reshape(t, hq, d), lp["ret_q_norm"],
                 cfg.rms_norm_eps)
    k = _rmsnorm(qkv[:, hq * d:(hq + hk) * d].reshape(t, hk, d),
                 lp["ret_k_norm"], cfg.rms_norm_eps)
    v = qkv[:, (hq + hk) * d:].reshape(t, hk, d).astype(jnp.float32)
    rope = next(s.rope for s in cfg.layer_specs if s.mixer == "retention")
    if rope.rotary_fraction:
        angles = pos.astype(jnp.float32)[:, None] \
            * rope_inverse_frequencies(rope, d)
        q, k = apply_rope(q[None], angles)[0], apply_rope(k[None], angles)[0]
    f = _mm32(x, lp["ret_wgate"], dtype) + lp["ret_bgate"]
    return q, k, v, jax.nn.log_sigmoid(f), f


def _retention_output(lp, y, cfg: LlamaConfig, dtype):
    """``y`` [T, Hq, d] float32 -> the block's output [T, E]."""
    with device_scope("ret_out"):
        return _mm(y.reshape(y.shape[0], -1).astype(dtype), lp["ret_wo"],
                   dtype)


def retention_decode(lp, x, state, keysum, active, cfg: LlamaConfig, dtype,
                     impl: str, interpret: bool, pos=None):
    """One token a slot: ``x`` [B, E] at positions ``pos`` [B], ``state``
    [B, Hk, tiles, d, d], ``keysum`` [B, Hk, tiles, d], ``active`` [B]
    bool.  Returns ``(y [B, E], state, keysum, gate)``; a slot that is not
    active keeps both as they were; ``gate`` [B, 2, Hk] is ``(f, log g)``
    of :func:`_retention_inputs`."""
    with device_scope("ret_proj"):
        q, k, v, lg, f = _retention_inputs(lp, x, pos, cfg, dtype)
    with device_scope("ret_scan"):
        if impl == "pallas":
            y, state, keysum = retention.retention_decode_step(
                state, keysum, q, k, v, lg, active, interpret=interpret)
        else:
            y, s_new, z_new = retention.retention_step(
                state, keysum, q, k, v, lg)
            state = jnp.where(active[:, None, None, None, None], s_new,
                              state)
            keysum = jnp.where(active[:, None, None, None], z_new, keysum)
    return _retention_output(lp, y, cfg, dtype), state, keysum, jnp.stack(
        [f, lg], axis=1)


def retention_run(lp, x, state, keysum, fresh, n_real, cfg: LlamaConfig,
                  dtype, impl: str, interpret: bool, pos=None):
    """A run of one slot's tokens: ``x`` [K, E] at positions ``pos``
    onwards (a scalar: the first's), ``state`` [Hk, tiles, d, d] and
    ``keysum`` [Hk, tiles, d] the slot's own, ``fresh`` and ``n_real`` as :func:`kda_run`'s.  Returns ``(y [K,
    E], state, keysum, gate)``, both after the last real token, ``gate``
    [K, 2, Hk] as :func:`retention_decode`'s."""
    with device_scope("ret_proj"):
        state = jnp.where(fresh, 0.0, state)
        keysum = jnp.where(fresh, 0.0, keysum)
        q, k, v, lg, f = _retention_inputs(
            lp, x, pos + jnp.arange(x.shape[0]), cfg, dtype)
        chunk = retention.chunk_of(x.shape[0]) if impl == "pallas" else 0
        if chunk:
            # every consumer of the projection's output under this scope,
            # the kernel alone under the next
            ops = retention.chunk_operands(q, k, v, lg, n_real, chunk=chunk)
    with device_scope("ret_scan"):
        if chunk:
            # the two large products' operands in the model's dtype, as
            # every other matmul's; the sums float32 either way
            y, state, keysum = retention.retention_chunk_call(
                state, keysum, *ops, n_real, chunk=chunk,
                operands=jnp.dtype(dtype).name,
                interpret=interpret)
        else:
            y, state, keysum = retention.retention_recurrence(
                state, keysum, q, k, v, lg, n_real)
    return _retention_output(lp, y, cfg, dtype), state, keysum, jnp.stack(
        [f, lg], axis=1)


#: a kind's ``(one token a slot, a run of one slot's tokens)``
BLOCKS = {"kda": (kda_decode, kda_run), "ssm": (ssm_decode, ssm_run),
          "retention": (retention_decode, retention_run)}
