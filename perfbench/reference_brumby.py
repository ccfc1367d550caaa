"""The plain reference: Brumby-14B-Base's decoder layers (``brumby``) as its
config.json and the public description of power retention give them,
nothing else.

Straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no cache, no kernel, no state,
no batching, no code of ``dlrover_tpu``.  ``h`` is a layer's input after
``input_layernorm`` (RMSNorm, eps 1e-6), one sequence, tokens ``t``, ``s``.

1. Projections: ``q = W_q h`` in 40 heads of 128, ``k = W_k h``, ``v = W_v
   h`` in 8 heads of 128, no bias; each head of q and of k RMS-normed over
   its 128 values (one learned scale for all query heads, one for all key
   heads), then rotated over all 128 dimensions, HALVES paired (dimension
   i with i + 64), at ``theta^(-2i/128)``, theta 1e6.
2. The gate: ``log g_t = logsigmoid(W_gate h_t + b_gate)``, one scalar a
   KEY head and token.
3. Power retention of degree p = 2, in its ATTENTION form (the
   definition; the served program keeps a state instead, and shares no
   line with this): query head ``i`` on key head ``i // 5``; for ``s <=
   t``, ``a[t, s] = (q_t . k_s)^p x exp(G_t - G_s)`` with ``G`` the
   running sum of ``log g``; ``y_t = sum_s a[t, s] v_s / (sum_s a[t, s] +
   eps)``, eps 1e-6.  No scale on ``q . k`` (any cancels between the two
   sums).  In blocks of queries, so that 5 000 positions fit.
4. ``W_o`` over the 40 heads' ``y``; ``x <- x + mix(RMSNorm(x))``; ``x <- x
   + W_d (SiLU(W_g h) x W_u h)``, width 17 408; a final RMSNorm; logits =
   ``h W_head`` over the whole vocabulary, untied.

What the served program keeps, for the comparison of its state:
:func:`pair_state` gives, from the same q, k, v and gate, ``S[v, i, j] =
sum_s exp(G_T - G_s) k_s[i] k_s[j] v_s[v]`` and ``z[i, j]`` alike, the FULL
symmetric square (128 x 128 pairs, every unordered pair twice, unweighted):
the driver unfolds the program's kept layout into the same and compares.

Departures from the published description, each noted where it is made:
none in the mathematics; everything the config leaves to inference (the
degree, the gate's form, the normaliser, QK-norm and RoPE ahead of the
power) is under ``assumed`` in ``perfbench/configs/brumby-14b-serve.json``.
The public inference path keeps K/V rows below a ``switch_over_seq_len``
and builds the state there: the same mathematics, which this form
computes at every length.

Sizes come as a plain dict ``dims`` (:func:`dims_of`).  Weights arrive a
layer at a time in the run's dtype and are up-cast here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISION = "highest"   # every matmul here; read when a program is traced
QUERY_BLOCK = 256


def dims_of(config: dict) -> dict:
    """The sizes the reference reads, from a configuration file's keys."""
    if config["model_type"] != "brumby" or config["attention_bias"] \
            or config["tie_word_embeddings"] or config["use_sliding_window"] \
            or config["rope_scaling"] or config["hidden_act"] != "silu" \
            or config["max_window_layers"] < config.get(
                "num_hidden_layers_published", config["num_hidden_layers"]):
        raise ValueError("not what perfbench/reference_brumby.py computes")
    return {
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "degree": int(config["assumed_sizes"]["retention_degree"]),
        "den_eps": float(config["assumed_sizes"]["retention_eps"]),
    }


class _Dims(dict):
    """``dims`` as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


# ------------------------------------------------------- power retention
def rotate(x, pos, theta):
    """RoPE over the whole head, halves paired: ``x`` [T, H, D] at
    positions ``pos`` [T]."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def log_gate(f):
    """The gate's logarithm from the float32 sums ``f = W_gate h + b``."""
    return jax.nn.log_sigmoid(f)


def power(qk, degree: int):
    """``(q . k)^p``: qk [..., T, S]."""
    return qk ** degree


def scores(q, k, degree: int):
    """``(q_t . k_s)^p`` of one key head's queries: q [G, T, D], k [S, D]
    -> [G, T, S]."""
    return power(jnp.einsum("gtd,sd->gts", q, k), degree)


def normaliser(a, eps):
    """The denominator of a row of weights: a [..., S] -> [..., 1]."""
    return jnp.sum(a, axis=-1, keepdims=True) + eps


def inputs(h, lp, pos, d):
    """``h`` [T, E] -> q [T, Hq, D], k v [T, Hk, D], the running sum of the
    gate's logarithm [T, Hk] and the sums ``f`` it comes from."""
    a = _f32(lp["retention"])
    q = jnp.einsum("te,ehd->thd", h, a["q_proj"]["kernel"])
    k = jnp.einsum("te,ehd->thd", h, a["k_proj"]["kernel"])
    v = jnp.einsum("te,ehd->thd", h, a["v_proj"]["kernel"])
    q = rotate(rmsnorm(q, a["q_norm"]["scale"], d["eps"]), pos, d["theta"])
    k = rotate(rmsnorm(k, a["k_norm"]["scale"], d["eps"]), pos, d["theta"])
    f = h @ a["gate_proj"]["kernel"] + a["gate_proj"]["bias"]
    return q, k, v, jnp.cumsum(log_gate(f), axis=0), f


def attend(q, k, v, cum, d):
    """The attention form (3 of the module docstring), a block of queries
    at a time: q [T, Hq, D], k v [T, Hk, D], cum [T, Hk] -> y [T, Hq,
    D]."""
    t, hq, dim = q.shape
    hk = k.shape[1]
    group = hq // hk
    qg = q.reshape(t, hk, group, dim)
    out = []
    for t0 in range(0, t, QUERY_BLOCK):
        rows = slice(t0, min(t0 + QUERY_BLOCK, t))
        n = rows.stop                       # keys this block can see
        sees = jnp.arange(n)[None, :] <= jnp.arange(t0, n)[:, None]
        ys = []
        for h in range(hk):
            a = scores(jnp.moveaxis(qg[rows, h], 1, 0), k[:n, h],
                       d["degree"])
            decay = jnp.exp(jnp.where(
                sees, cum[rows, h][:, None] - cum[:n, h][None, :], -jnp.inf))
            a = a * decay[None]
            ys.append(jnp.einsum("gts,sd->tgd", a, v[:n, h])
                      / jnp.moveaxis(normaliser(a, d["den_eps"]), 0, 1))
        out.append(jnp.stack(ys, axis=1).reshape(rows.stop - t0, hq, dim))
    return jnp.concatenate(out, axis=0)


def pair_state(k, v, cum):
    """What a state behind the LAST token holds, in the full symmetric
    square: ``S`` [Hk, D (v), D, D] and ``z`` [Hk, D, D] (module
    docstring), a key head at a time."""
    left = jnp.exp(cum[-1][None, :] - cum)                     # [T, Hk]

    def head(x):
        kh, vh, w = x
        kk = (kh[:, :, None] * kh[:, None, :]).reshape(kh.shape[0], -1)
        dim = kh.shape[1]
        return ((vh * w[:, None]).T @ kk).reshape(dim, dim, dim), \
            (w @ kk).reshape(dim, dim)

    return jax.lax.map(head, (jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0),
                              left.T))


def mix(h, lp, pos, d, want_state: bool):
    """A layer's retention block on its normed input ``h`` [T, E]:
    ``(output [T, E], (S, z) of :func:`pair_state` or None)``."""
    q, k, v, cum, f = inputs(h, lp, pos, d)
    y = attend(q, k, v, cum, d)
    o = jnp.einsum("thd,hde->te", y,
                   lp["retention"]["o_proj"]["kernel"].astype(jnp.float32))
    return o, pair_state(k, v, cum) if want_state else None


@functools.partial(jax.jit, static_argnames=("d", "want_state"))
def retention(h, lp, pos, d, want_state=False):
    with jax.default_matmul_precision(PRECISION):
        return mix(h, lp, pos, d, want_state)


# ------------------------------------------------------------------- MLP
@jax.jit
def mlp(x, m):
    with jax.default_matmul_precision(PRECISION):
        m = _f32(m)
        return (jax.nn.silu(x @ m["gate_proj"]["kernel"])
                * (x @ m["up_proj"]["kernel"])) @ m["down_proj"]["kernel"]


# ----------------------------------------------------------------- model
@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, eps):
    return rmsnorm(x, w.astype(jnp.float32), eps)


def layer_forward(x, lp, d, keep=None):
    """One decoder layer on one sequence: x [T, E] float32 -> [T, E].
    ``keep`` (a dict) is given the state behind the last token
    (``state``, ``keysum``: :func:`pair_state`)."""
    h = _norm(x, lp["input_norm"]["scale"], d["eps"])
    y, state = retention(h, lp, jnp.arange(x.shape[0]), _Dims(d),
                         want_state=keep is not None)
    if keep is not None:
        keep.update(state=state[0], keysum=state[1])
    x = x + y
    h = _norm(x, lp["post_norm"]["scale"], d["eps"])
    return x + mlp(h, lp["mlp"])


def embed(tokens, top):
    return top["embed_tokens"]["embedding"].astype(jnp.float32)[
        jnp.asarray(tokens)]


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logits(x, top, eps):
    """Logits over the whole vocabulary, untied."""
    with jax.default_matmul_precision(PRECISION):
        h = rmsnorm(x, top["final_norm"]["scale"].astype(jnp.float32), eps)
        return h @ top["lm_head"]["kernel"].astype(jnp.float32)


def hidden_states(seq, get_layer, top, num_layers, d, keep=None,
                  state_layers=()):
    """Final hidden states (before the last norm) of one token sequence.
    ``keep`` (a dict) is given, for each layer of ``state_layers``, the
    state and the sum of keys behind the last token (``states``,
    ``keysums``, by 0-based layer number)."""
    x = embed(seq, top)
    for i in range(num_layers):
        mine = {} if keep is not None and i in state_layers else None
        x = layer_forward(x, get_layer(i), d, mine)
        if mine:
            keep.setdefault("states", {})[i] = mine["state"]
            keep.setdefault("keysums", {})[i] = mine["keysum"]
    return x
