"""The plain reference of Laguna-XS.2's decoder and training loss, over one
chip's share of it.

Straight ``jax.numpy`` in float32 with
``jax.default_matmul_precision("highest")``; no kernels, no scan, no sort,
no grouped matmul, no code of the program.  It reads the PUBLISHED keys of
the configuration file (``layer_types``, ``num_attention_heads_per_layer``,
``rope_parameters``, ``sliding_window``, ``mlp_layer_types``,
``num_experts_per_tok``, ``moe_routed_scaling_factor``), not the program's
``LlamaConfig``.  Equations (poolside/Laguna-XS.2 ``config.json``; what the
configuration file lists under ``assumed`` is marked *):

- RMSNorm:  ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w``
- attention of layer ``l``, ``n = RMSNorm(x)``, ``H_l`` query heads
  (48 in a ``full_attention`` layer, 64 in a ``sliding_attention`` one), 8
  key/value heads, head size 128:
  ``o = Attn(RoPE_l(W_q n), RoPE_l(W_k n), W_v n)``;
  ``g = sigmoid(n W_g)`` [H_l], ``o_h <- g_h o_h`` (* ``gating: true``
  read as one gate a query head);  ``x + W_o o``.
  ``Attn`` is ``softmax(q k^T / sqrt(128)) v`` with key ``j`` visible to
  query ``i`` when ``j <= i`` and, in a sliding layer,
  ``j > i - sliding_window`` (the key itself counted); query head ``h``
  reads key/value head ``h // (H_l / 8)``.
- ``RoPE_l``, by ``rope_parameters[layer_types[l]]``: the FIRST
  ``r = 128 x partial_rotary_factor`` dimensions of a head rotate in the
  "rotate_half" layout (halves ``x1, x2`` of those ``r``:
  ``(x1 cos a - x2 sin a, x2 cos a + x1 sin a)``), the rest pass through;
  ``a[p, i] = p f_i`` with ``f_i = theta^(-2i/r)``, or under
  ``rope_type: yarn`` as ``transformers`` computes it: with
  ``c(t) = r ln(L / (2 pi t)) / (2 ln theta)``, ``L`` the original length,
  ``lo = floor(c(beta_fast))``, ``hi = ceil(c(beta_slow))``,
  ``ramp_i = clip((i - lo) / (hi - lo), 0, 1)``:
  ``f_i = theta^(-2i/r) (1 - ramp_i) + theta^(-2i/r) / factor x ramp_i``,
  and cos and sin are multiplied by ``attention_factor``.
- dense MLP (``mlp_layer_types[l] == "dense"``), ``m = RMSNorm(h)``:
  ``h + W_down (silu(W_gate m) * W_up m)``.
- sparse MLP: ``s = sigmoid(m W_r)`` over all 256 experts in float32 (*);
  ``i_1..i_8`` the 8 largest of ``s``; ``w_j = 2.5 s[i_j] / sum_j s[i_j]``
  (* the sum over all eight, held here or not);
  ``y = h + sum_{j: i_j held} w_j E_{i_j}(m) + S(m)``, ``E`` and the
  shared expert ``S`` both ``W_down (silu(W_gate m) * W_up m)``.  ONE
  SHARE: only the experts ``first .. first + count - 1`` that this chip
  holds add to the sum; what the absent ones would add is left out, as in
  the program, and that partial result goes on to the next layer.
- final RMSNorm, untied output head over the chip's slice of the
  vocabulary; loss = mean next-token cross entropy over positions 0..T-2
  (* no auxiliary term: the config gives no coefficient).

Weights arrive one layer at a time in the run's own dtype and are up-cast
here.  Attention runs one key/value head and ``Q_BLOCK`` query rows at a
time (``lax.map`` over one small function), so that the scores of 64
heads over 8192 x 8192 never exist at once; the held experts run one at
a time over every token (``lax.scan``).

The gradient (``lm_loss_and_grads``) is ``jax.vjp`` of these same
functions, a layer at a time from the last to the first.  The two pieces
whose backward would keep more than a chip holds (a block's scores, an
expert's activations over every token) are under ``jax.checkpoint``,
which changes what a backward keeps and nothing that is computed.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perfbench.reference import _f32, embed, head_logits, rmsnorm

Q_BLOCK = 1024


def inverse_frequencies(rope: dict, head_dim: int):
    """``(f [r/2], attention_factor)`` of one kind of layer."""
    r = int(head_dim * rope.get("partial_rotary_factor", 1))
    theta = float(rope["rope_theta"])
    pos_freqs = theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    if rope.get("rope_type", "default") != "yarn":
        return 1.0 / pos_freqs, 1.0
    factor = float(rope["factor"])
    length = rope["original_max_position_embeddings"]

    def c(turns):
        return (r * math.log(length / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(c(rope["beta_fast"])), 0)
    hi = min(math.ceil(c(rope["beta_slow"])), r - 1)
    if lo == hi:
        hi += 0.001
    ramp = jnp.clip((jnp.arange(r // 2, dtype=jnp.float32) - lo) / (hi - lo),
                    0.0, 1.0)
    keep = 1.0 - ramp
    f = 1.0 / (factor * pos_freqs) * (1.0 - keep) + 1.0 / pos_freqs * keep
    return f, float(rope["attention_factor"])


def rope(x, positions, rope_params: dict):
    """x [T, heads, d]; positions [T]."""
    f, scale = inverse_frequencies(rope_params, x.shape[-1])
    r = 2 * f.shape[0]
    ang = positions.astype(jnp.float32)[:, None] * f[None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., : r // 2], x[..., r // 2: r]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., r:]], -1)


@functools.partial(jax.checkpoint, static_argnums=(3,))
@functools.partial(jax.jit, static_argnames=("window",))
def attention(q, k, v, window):
    """Causal grouped-query attention, ``window`` keys wide where it is
    not None.  q [T, H, d]; k, v [T, KV, d].  One loop over (key/value
    head, block of ``Q_BLOCK`` query rows): the query heads that share the
    key/value head, against all of its keys."""
    t, h, d = q.shape
    kv = k.shape[1]
    rep = h // kv
    block = min(Q_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} rows are not whole blocks of {block}")
    kpos = jnp.arange(t)[None, :]

    @jax.checkpoint
    def attend_rows(at):
        g, start = at
        with jax.default_matmul_precision("highest"):
            qb = jax.lax.dynamic_slice(
                q, (start, g * rep, 0), (block, rep, d))      # [Q, rep, d]
            kg = jax.lax.dynamic_index_in_dim(k, g, 1, keepdims=False)
            vg = jax.lax.dynamic_index_in_dim(v, g, 1, keepdims=False)
            scores = jnp.einsum("qrd,kd->rqk", qb, kg) / math.sqrt(d)
            qpos = start + jnp.arange(block)[:, None]
            mask = kpos <= qpos
            if window is not None:
                mask = mask & (kpos > qpos - window)
            scores = jnp.where(mask[None], scores, -jnp.inf)
            return jnp.einsum(
                "rqk,kd->qrd", jax.nn.softmax(scores, axis=-1), vg)

    groups, starts = jnp.meshgrid(
        jnp.arange(kv), jnp.arange(0, t, block), indexing="ij")
    out = jax.lax.map(attend_rows, (groups.ravel(), starts.ravel()))
    # [KV x blocks, Q, rep, d] -> [T, KV x rep, d]
    return out.reshape(kv, t, rep, d).transpose(1, 0, 2, 3).reshape(t, h, d)


def layer_kind(config: dict, i: int) -> dict:
    """What the published keys say of layer ``i``."""
    kind = config["layer_types"][i]
    return {
        "rope": config["rope_parameters"][kind],
        "window": (config["sliding_window"]
                   if kind == "sliding_attention" else None),
        "sparse": config["mlp_layer_types"][i] == "sparse",
    }


@functools.partial(jax.jit, static_argnames=("eps", "rope_key"))
def _qkv_gate(x, lp, eps, rope_key):
    """The layer's rotated queries and keys, its values and its head
    gate, from x [T, E]."""
    with jax.default_matmul_precision("highest"):
        a = _f32(lp["attn"])
        pos = jnp.arange(x.shape[0])
        rope_params = dict(rope_key)
        n = rmsnorm(x, lp["input_norm"]["scale"].astype(jnp.float32), eps)
        q = jnp.einsum("te,ehd->thd", n, a["q_proj"]["kernel"])
        k = jnp.einsum("te,ehd->thd", n, a["k_proj"]["kernel"])
        v = jnp.einsum("te,ehd->thd", n, a["v_proj"]["kernel"])
        gate = jax.nn.sigmoid(n @ a["g_proj"]["kernel"])     # [T, H]
        return rope(q, pos, rope_params), rope(k, pos, rope_params), v, gate


@jax.jit
def _gated_out(x, o, gate, w_o):
    with jax.default_matmul_precision("highest"):
        return x + jnp.einsum("thd,hde->te", o * gate[..., None],
                              w_o.astype(jnp.float32))


def attention_block(x, lp, kind, eps):
    """x [T, E] float32 -> h [T, E]."""
    q, k, v, gate = _qkv_gate(x, {"attn": lp["attn"],
                                  "input_norm": lp["input_norm"]}, eps,
                              tuple(sorted(kind["rope"].items())))
    return _gated_out(x, attention(q, k, v, kind["window"]), gate,
                      lp["attn"]["o_proj"]["kernel"])


@jax.jit
def swiglu(m, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        w_gate, w_up, w_down = _f32((w_gate, w_up, w_down))
        return (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


@jax.checkpoint
@jax.jit
def routed_sum(weights, m, w_gate, w_up, w_down):
    """``sum_e weights[:, e, None] * E_e(m)`` over the held experts, one
    at a time, each over EVERY token: weights [N, count] (0 where the
    token did not pick the expert), the stacks [count, ...]."""
    @jax.checkpoint
    def add_expert(acc, expert):
        w, wg, wu, wd = expert
        return acc + w[:, None] * swiglu(m, wg, wu, wd), None

    return jax.lax.scan(add_expert, jnp.zeros_like(m),
                        (weights.T, w_gate, w_up, w_down))[0]


@functools.partial(jax.jit, static_argnames=("eps",))
def router(h, lp, eps):
    """m = RMSNorm(h) and the sigmoid scores of all experts, [N, .]."""
    with jax.default_matmul_precision("highest"):
        m = rmsnorm(h, lp["post_norm"]["scale"].astype(jnp.float32), eps)
        logits = m @ lp["mlp"]["router"]["kernel"].astype(jnp.float32)
        return m, jax.nn.sigmoid(logits)


def sparse_parts(tokens_h, lp, eps, top_k, scale, held):
    """The sparse MLP over ALL the batch's tokens ``tokens_h`` [N, E], in
    parts: (what the held routed experts add, what the shared expert
    adds, picks per expert over all of them).  ``held = (first, count)``:
    ``lp``'s expert weights are those ``count`` experts'."""
    m, s = router(tokens_h, lp, eps)
    top = jnp.argsort(-s, axis=-1)[:, :top_k]                # [N, k]
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], top].set(True)
    weights = jnp.where(chosen, s, 0.0)
    weights = scale * weights / weights.sum(axis=-1, keepdims=True)
    mlp = lp["mlp"]
    first, count = held
    routed = routed_sum(weights[:, first:first + count], m,
                        mlp["w_gate"], mlp["w_up"], mlp["w_down"])
    shared = swiglu(m, mlp["shared_gate"]["kernel"],
                    mlp["shared_up"]["kernel"], mlp["shared_down"]["kernel"])
    return routed, shared, chosen.sum(axis=0)


@functools.partial(jax.jit, static_argnames=("eps",))
def dense_mlp(h, lp, eps):
    with jax.default_matmul_precision("highest"):
        m = rmsnorm(h, lp["post_norm"]["scale"].astype(jnp.float32), eps)
    mlp = lp["mlp"]
    return h + swiglu(m, mlp["gate_proj"]["kernel"],
                      mlp["up_proj"]["kernel"], mlp["down_proj"]["kernel"])


def layer_step(xs, lp, kind, config, held):
    """One decoder layer over the batch's sequences ``xs`` ([T, E] each):
    the sequences behind it, and the picks per expert of a sparse layer
    (else None)."""
    eps = float(config["rms_norm_eps"])
    hs = [attention_block(x, lp, kind, eps) for x in xs]
    if not kind["sparse"]:
        return [dense_mlp(h, lp, eps) for h in hs], None
    h = jnp.concatenate(hs)
    routed, shared, c = sparse_parts(
        h, lp, eps, config["num_experts_per_tok"],
        float(config["moe_routed_scaling_factor"]), held)
    return list((h + routed + shared).reshape(len(xs), *xs[0].shape)), c


def cross_entropy(xs, top, batch, eps):
    """Mean next-token cross entropy over every row's positions 0..T-2,
    from the hidden states ``xs`` before the last norm."""
    total, n = 0.0, 0
    for row, x in zip(batch, xs):
        logp = jax.nn.log_softmax(head_logits(x, top, eps)[:-1], axis=-1)
        labels = jnp.asarray(row)[1:]
        total = total - jnp.take_along_axis(
            logp, labels[:, None], axis=-1).sum()
        n += int(labels.shape[0])
    return total / n


def _answer(ce, xs, counts, top, eps):
    scale = top["final_norm"]["scale"].astype(jnp.float32)
    return {"total": ce, "ce": ce, "counts": jnp.stack(counts),
            "hidden": jnp.stack([rmsnorm(x, scale, eps) for x in xs])}


def lm_loss(batch, get_layer, top, config, held):
    """The training objective on ``batch`` [B, T]: ``{"total", "ce",
    "counts" [sparse layers, experts], "hidden" [B, T, E] (after the last
    norm)}``.  Traceable: ``jax.grad`` of ``["total"]`` is the reference's
    gradient."""
    eps = float(config["rms_norm_eps"])
    xs = [embed(jnp.asarray(row), top) for row in batch]
    counts = []
    for i in range(config["num_hidden_layers"]):
        xs, c = layer_step(xs, get_layer(i), layer_kind(config, i), config,
                           held)
        if c is not None:
            counts.append(c)
    return _answer(cross_entropy(xs, top, batch, eps), xs, counts, top, eps)


def lm_loss_and_grads(batch, get_layer, top, config, held, visit):
    """``lm_loss``'s answer, and the gradient of its ``"total"``: reverse
    mode by hand over the same functions, one layer's backward alive at a
    time (what ``jax.grad`` of ``lm_loss`` keeps of 9 layers at 2 x 8192
    tokens no chip holds).  ``visit(i, grads)`` is given layer ``i``'s
    gradient in ``get_layer(i)``'s tree, from the last layer to the first,
    then ``visit("top", grads)`` in ``top``'s, each in its leaf's dtype."""
    eps = float(config["rms_norm_eps"])
    rows = [jnp.asarray(row) for row in batch]
    xs = [embed(row, top) for row in rows]
    inputs, counts = [], []
    for i in range(config["num_hidden_layers"]):
        inputs.append(xs)
        xs, c = layer_step(xs, get_layer(i), layer_kind(config, i), config,
                           held)
        if c is not None:
            counts.append(c)
    ce, pull = jax.vjp(
        lambda xs, top: cross_entropy(xs, top, batch, eps), xs, top)
    answer = _answer(ce, xs, counts, top, eps)
    dxs, dtop = pull(jnp.ones_like(ce))
    del xs, pull
    for i in reversed(range(config["num_hidden_layers"])):
        kind = layer_kind(config, i)
        _, pull = jax.vjp(
            lambda xs, lp: layer_step(xs, lp, kind, config, held)[0],
            inputs.pop(), get_layer(i))
        dxs, dlp = pull(dxs)
        visit(i, dlp)      # reads the leaves: the host waits for the layer
        del pull, dlp
    _, pull = jax.vjp(lambda top: [embed(row, top) for row in rows], top)
    visit("top", jax.tree_util.tree_map(jnp.add, dtop, pull(dxs)[0]))
    return answer
