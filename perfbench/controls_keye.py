"""The controls of ``serve-docqa-sparse-gqa``'s comparison: the reference of
``perfbench/reference_keye.py`` with ONE fault planted, for
``drivers/serve_sparse_gqa.py reference_check`` to hold the engine's timed
programs against (``perfbench/controls_glm5.py``'s build and reasons: a
program is as far from a wrong reference as a wrong program is from the
right one, so every fault here has to come out as NOT correct, by the
driver's own verdicts).

``bf16_as_served`` is the other kind: no fault, the reference with bf16
where the program has it (matmul operands in one bf16 pass with float32
sums; the cached rows, the indexer's queries and keys, every block's
normed input and its output rounded to bf16).  It has to stay correct,
and it is read against the FLOAT32 REFERENCE as well (``against_f32``).

``UNSEEN`` is the third kind: two statements of the configuration file
that the comparison CANNOT hold, planted alone and read so that the record
says by how much (my chip runs, PR 58, seeds 2147485905 / -06: both come
out CORRECT).  A router in bfloat16 (logits and softmax) picks otherwise
where the 8th and 9th scores stand within 0.006-0.017 of each other; the
program's float32 router and the reference's already do within 0.001-
0.012 (``misroute_gap_max``: the chip's compiler elides the round trip
through bfloat16 of the rows the program's router multiplies, which the
witness hands back rounded; PERF.md section 6), and its weights move a
token's output by 0.0070 where bfloat16 experts move it by 0.0065.
bfloat16 SUMS of the index products move layer 0's overlap from 0.9963 to
0.9959 and no count.  What fails in ``bf16_accumulation`` is the index
ARITHMETIC in bfloat16 (8-bit scores tie by the dozen at the threshold:
the count).

On the chip: ``PERFBENCH_CONTROLS=1 python3 perfbench/run.py --workload
serve-docqa-sparse-gqa ...`` adds ``checks.controls`` to the run's
``perfbench detail`` line (a reference pass a control; readings only, the
run's ``correct`` is its own).  On the CPU ``tests/test_keye_serving.py``
plants each at tiny sizes.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from perfbench import reference_keye as ref
from perfbench.controls_glm5 import _bf16, _fp8, against


@contextlib.contextmanager
def _patched(retrace=False, **attrs):
    """``reference_keye``'s names rebound; ``retrace`` where a traced
    program reads the name (it read it once, when it was traced)."""
    old = {k: getattr(ref, k) for k in attrs}
    for k, v in attrs.items():
        setattr(ref, k, v)
    if retrace:
        jax.clear_caches()
    try:
        yield
    finally:
        for k, v in old.items():
            setattr(ref, k, v)
        if retrace:
            jax.clear_caches()


def _dims_with(**changes):
    inner = ref.dims_of

    def dims_of(config):
        d = inner(config)
        return dict(d, **{k: f(d[k]) for k, f in changes.items()})

    return dims_of


def _next_group(head, d):
    """Every query head reads the KV head BEHIND its own."""
    group = d["heads"] // d["kv_heads"]
    return (head // group + 1) % d["kv_heads"]


def _index_key_not_rotated(x, ix, pos, d):
    return ref.layernorm(x @ ix["wk"]["kernel"], ix["k_norm"]["scale"],
                         ix["k_norm"]["bias"])


def _index_products_summed_in_bf16(q_i, w, k_i):
    """``q_i . k_i`` summed in bfloat16 and NOTHING else: the ReLU, the
    heads' weights and the sum over heads stay float32."""
    s = jnp.einsum("qhd,sd->qhs", q_i.astype(jnp.bfloat16),
                   k_i.astype(jnp.bfloat16),
                   preferred_element_type=jnp.bfloat16).astype(jnp.float32)
    return jnp.sum(jnp.maximum(s, 0) * w[:, :, None], axis=1)


def _index_scores_in_bf16(q_i, w, k_i):
    """The index scores with bfloat16 where the file says float32: ``q_i .
    k_i`` SUMMED in bfloat16, the ReLU, the heads' weights and the sum
    over heads in bfloat16 (the file: bf16 operands, float32 sums, float32
    ReLU, weights and sum).  A score of 8 bits ties its neighbours at the
    threshold, and ``I >= the topk-th`` keeps every one of them."""
    s = jnp.einsum("qhd,sd->qhs", q_i.astype(jnp.bfloat16),
                   k_i.astype(jnp.bfloat16),
                   preferred_element_type=jnp.bfloat16)
    return jnp.sum(jnp.maximum(s, 0) * w.astype(jnp.bfloat16)[:, :, None],
                   axis=1).astype(jnp.float32)


def _router_in_bf16(x, router):
    """The router's logits and softmax in bfloat16 (the file: float32)."""
    logits = jnp.dot(x.astype(jnp.bfloat16), router.astype(jnp.bfloat16),
                     preferred_element_type=jnp.bfloat16)
    return jax.nn.softmax(logits, axis=-1).astype(jnp.float32)


def _route_with(change):
    """``_route``'s weights [T, experts] passed through ``change``."""
    inner = ref._route
    return lambda x, router, d: change(inner(x, router, d), d)


def _experts_swapped(weights, d):
    """The first two experts held change places (a token that picked one
    of them is multiplied by the other's matrices)."""
    a, b = d["first"], d["first"] + 1
    return weights.at[:, a].set(weights[:, b]).at[:, b].set(weights[:, a])


def _f32_through_fp8(tree):
    """Every matrix ``_f32`` up-casts rounded to fp8 e4m3 first, scaled a
    tensor: the nearest precision below the configuration's bf16."""
    return jax.tree_util.tree_map(
        lambda a: _fp8(a) if a.ndim >= 2 else a.astype(jnp.float32), tree)


#: name -> a context manager under which the reference computes the fault
FAULTS = {
    "selection_off": lambda: _patched(
        chosen_of=lambda scores, kth: scores > -jnp.inf),
    "topk_2047": lambda: _patched(
        dims_of=_dims_with(topk=lambda k: k - 1)),
    "qk_norm_off": lambda: _patched(
        retrace=True, qk_norm=lambda q, k, a, d: (q, k)),
    "head_group_wrong": lambda: _patched(kv_head_of=_next_group),
    "router_not_renormalised": lambda: _patched(
        dims_of=_dims_with(norm_topk=lambda _: False)),
    "index_key_not_rotated": lambda: _patched(
        retrace=True, index_key=_index_key_not_rotated),
    "bf16_accumulation": lambda: _patched(
        retrace=True, index_scores=_index_scores_in_bf16),
    "weights_fp8": lambda: _patched(retrace=True, _f32=_f32_through_fp8),
    # two faults that change the PICKS: renormalised over seven, a token's
    # eighth expert gets nothing; two held experts answer for each other
    "eighth_pick_dropped": lambda: _patched(
        dims_of=_dims_with(top_k=lambda k: k - 1)),
    "experts_swapped": lambda: _patched(
        _route=_route_with(_experts_swapped)),
}

#: below the comparison's floor (module docstring): read, not held
UNSEEN = {
    "router_bf16": lambda: _patched(
        retrace=True, router_scores=_router_in_bf16),
    "index_products_summed_in_bf16": lambda: _patched(
        retrace=True, index_scores=_index_products_summed_in_bf16),
}


def _as_served():
    project, attention, mlp, norm = (ref._project, ref.attention, ref.mlp,
                                     ref._norm)

    def rounded(fn, last_stays=False):
        def call(*a, **kw):
            out = fn(*a, **kw)
            if isinstance(out, tuple) and last_stays:   # w stays float32
                return (*map(_bf16, out[:-1]), out[-1])
            if isinstance(out, tuple):      # (output, the selection)
                return (_bf16(out[0]), *out[1:])
            return _bf16(out)
        return call

    return _patched(retrace=True, PRECISION="bfloat16",
                    _project=rounded(project, last_stays=True),
                    attention=rounded(attention), mlp=rounded(mlp),
                    _norm=rounded(norm))


#: no fault: has to stay correct
WITNESSES = {"bf16_as_served": _as_served}

VERDICTS = ("logits_match_reference", "selection_matches_reference",
            "sparse_layer_matches_reference")


def summary(checks: dict) -> dict:
    """The verdicts and the numbers they were made from."""
    out = {k: checks[k] for k in VERDICTS}
    out["correct"] = all(out.values())
    out["logit_deficit_p90_worst"] = (checks.get("p90_logit_deficit"),
                                      checks.get("worst_logit_deficit"))
    for kind in ("run", "decode"):
        stats = checks.get(f"selection_{kind}") or []
        if stats:
            out[f"selection_{kind}"] = {
                "first_overlap": stats[0]["overlap"],
                "first_margin_max": stats[0]["margin_max"],
                "deeper_overlap_min": min(s["overlap"] for s in stats),
                "count_equal_min": min(s["count_equal"] for s in stats),
                "unseen": sum(s["unseen"] for s in stats)}
        out[f"sparse_{kind}"] = checks.get(f"sparse_{kind}")
    return out


def readings(ctx, base: dict, check) -> dict:
    """``check(keep)`` under every fault and witness; ``base``: what the
    unplanted reference kept."""
    out = {}
    for name, planted in {**FAULTS, **UNSEEN}.items():
        ctx.say(f"control {name}")
        with planted():
            out[name] = summary(check())
    for name, planted in WITNESSES.items():
        ctx.say(f"witness {name}")
        kept = {}
        with planted():
            out[name] = summary(check(kept))
        out[name]["against_f32"] = against(kept, base)
    return out
