"""The controls of ``serve-docqa-sparse``'s comparison: the reference of
``perfbench/reference_glm5.py`` with ONE fault planted, for
``drivers/serve_sparse.py reference_check`` to hold the engine's timed
programs against.  A limit of that comparison is only worth its name if a
wrong program reads on the far side of it, and a program is as far from a
wrong reference as a wrong program is from the right one: so every fault
here has to come out as NOT correct, by the driver's own verdicts.

``bf16_as_served`` is the other kind: no fault, the reference with bf16
where the program has it (matmul operands in one bf16 pass with float32
sums; the cached rows, the indexer's queries and keys, every block's
normed input and its output rounded to bf16).  It has to stay correct,
and it is read against the FLOAT32 REFERENCE as well (``against_f32``):
how far bf16 alone moves this model's selection and logits, which is what
the program's distance from the reference is held to be.

On the chip: ``PERFBENCH_CONTROLS=1 python3 perfbench/run.py --workload
serve-docqa-sparse ...`` adds ``checks.controls`` to the run's ``perfbench
detail`` line (a reference pass a control; readings only, the run's
``correct`` is its own).  On the CPU ``tests/test_sparse_serving.py``
plants each at tiny sizes.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from perfbench import reference_glm5 as ref


@contextlib.contextmanager
def _patched(retrace=False, **attrs):
    """``reference_glm5``'s names rebound; ``retrace`` where a traced
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


def _project_then(change):
    """``_project`` with its indexer outputs ``(q_i, k_i, w)`` changed."""
    inner = ref._project

    def project(x, lp, pos, d):
        *attn, q_i, k_i, w = inner(x, lp, pos, d)
        return (*attn, *change(q_i, k_i, w, pos, d))

    return project


def _fp8(x):
    """``x`` through an fp8 of 4 exponent and 3 mantissa bits with a scale
    a tensor (its largest value on 240, the largest that
    ``reduce_precision``'s IEEE reading of the format holds): a convert
    to ``float8_e4m3fn`` and back is dropped by the compiler where it may
    keep excess precision (read on the chip, PR 34: the experts' weights
    came back unrounded)."""
    x = x.astype(jnp.float32)
    scale = 240.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return jax.lax.reduce_precision(x * scale, exponent_bits=4,
                                    mantissa_bits=3) / scale


def _bf16(x):
    return jax.lax.reduce_precision(x.astype(jnp.float32), exponent_bits=8,
                                    mantissa_bits=7)


@functools.partial(jax.jit, static_argnames=("d",))
def _index_block_no_relu(q_i, w, k_i, start, seg_q, seg_k, d):
    """``reference_glm5._index_block`` less its ``max(., 0)``."""
    with jax.default_matmul_precision(ref.PRECISION):
        s = jnp.einsum("qhd,sd->qhs", q_i, k_i)
        scores = jnp.sum(s * w[:, :, None], axis=1)
        t = start + jnp.arange(q_i.shape[0])
        sees = (jnp.arange(k_i.shape[0])[None, :] <= t[:, None]) & (
            (seg_k[None, :] == 0) | (seg_k[None, :] == seg_q[:, None]))
        scores = jnp.where(sees, scores, -jnp.inf)
        k = min(d["topk"], k_i.shape[0])
        return scores, jax.lax.top_k(scores, k)[0][:, -1]


def _index_block_not_causal():
    inner = ref._index_block

    def block(q_i, w, k_i, start, seg_q, seg_k, d):
        # every key handed in counts as behind the query, and of the head
        return inner(q_i, w, k_i, 1 << 30, seg_q, jnp.zeros_like(seg_k), d)

    return block


def _dims_with(**changes):
    inner = ref.dims_of

    def dims_of(config):
        d = inner(config)
        return dict(d, **{k: f(d[k]) for k, f in changes.items()})

    return dims_of


def _mlp_without_shared():
    inner = ref.mlp

    def mlp(x, m, d, held=None):
        y = inner(x, m, d, held)
        if "router" not in m:
            return y
        return y - ref._swiglu(x, m["shared_gate"]["kernel"],
                               m["shared_up"]["kernel"],
                               m["shared_down"]["kernel"])

    return mlp


def _f32_through_fp8(tree):
    """Every weight ``_f32`` up-casts (the projections into the latent
    and query bottlenecks, the indexer's, every MLP's and expert's)
    rounded to fp8 e4m3 first, scaled a tensor: the nearest precision
    below the configuration's bf16."""
    return jax.tree_util.tree_map(
        lambda a: _fp8(a) if a.ndim >= 2 else a.astype(jnp.float32), tree)


#: name -> a context manager under which the reference computes the fault
FAULTS = {
    "weights_fp8": lambda: _patched(retrace=True, _f32=_f32_through_fp8),
    "index_keys_fp8": lambda: _patched(_project=_project_then(
        lambda q, k, w, pos, d: (q, _fp8(k), w))),
    "index_no_relu": lambda: _patched(_index_block=_index_block_no_relu),
    "index_no_rope": lambda: _patched(_project=_project_then(
        lambda q, k, w, pos, d: (
            ref.rope(q, -pos, d["theta"], d["rope"]),
            ref.rope(k, -pos, d["theta"], d["rope"]), w))),
    "index_no_head_weights": lambda: _patched(_project=_project_then(
        lambda q, k, w, pos, d: (q, k, jnp.full_like(
            w, (d["index_heads"] * d["index_dim"]) ** -0.5)))),
    "selection_not_causal": lambda: _patched(
        _index_block=_index_block_not_causal()),
    "picks_7_of_8": lambda: _patched(
        dims_of=_dims_with(top_k=lambda k: k - 1)),
    "no_routed_scale": lambda: _patched(
        dims_of=_dims_with(scale=lambda s: 1.0)),
    "no_shared_expert": lambda: _patched(mlp=_mlp_without_shared()),
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
                "unseen": sum(s["unseen"] for s in stats)}
        out[f"sparse_{kind}"] = checks.get(f"sparse_{kind}")
    return out


def against(kept: dict, base: dict) -> dict:
    """One reference's own selection and logits (``reference_check``'s
    ``keep``) against another's: a kind of program, a layer's mean share
    of ``base``'s chosen rows that are chosen here too; and ``base``'s
    deficit of the token this one's logits would have emitted."""
    import numpy as np

    out = {}
    for kind, layers in kept.get("chosen", {}).items():
        out[f"overlap_{kind}"] = [
            float(np.mean((a & b).sum(-1) / np.maximum(b.sum(-1), 1)))
            for a, b in zip(layers, base["chosen"][kind])]
    mine = np.concatenate(kept["logits"])
    theirs = np.concatenate(base["logits"])
    deficit = theirs.max(-1) - np.take_along_axis(
        theirs, mine.argmax(-1)[:, None], axis=-1)[:, 0]
    out["own_argmax_deficit_p90_worst"] = (
        float(np.percentile(deficit, 90)), float(deficit.max()))
    return out


def readings(ctx, base: dict, check) -> dict:
    """``check(keep)`` under every fault and witness; ``base``: what the
    unplanted reference kept."""
    out = {}
    for name, planted in FAULTS.items():
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
