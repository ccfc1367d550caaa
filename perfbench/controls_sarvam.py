"""The controls of ``serve-longctx-decode``'s comparison: the reference of
``perfbench/reference_sarvam.py`` with ONE fault planted, for
``drivers/serve_latent.py reference_check`` to hold the engine's timed
programs against.  A limit of that comparison is only worth its name if a
wrong program reads on the far side of it, and a program is as far from a
wrong reference as a wrong program is from the right one: so every fault
here has to come out as NOT correct, by the driver's own verdicts.

``bf16_as_served`` is the other kind: no fault, the reference with bf16
where the program has it (matmul operands in one bf16 pass with float32
sums; the cached rows, each block's normed input and its output rounded to
bf16; the softmax, the norms and the router stay float32).  It is read
against the FLOAT32 REFERENCE (``against_f32``): how far bf16 alone moves
this model's logits behind five routed layers, which is what the
program's distance from the reference is held to be.

On the chip: ``PERFBENCH_CONTROLS=1 python3 perfbench/run.py --workload
serve-longctx-decode ...`` adds ``checks.controls`` to the run's
``perfbench detail`` line (a reference pass a control; readings only, the
run's ``correct`` is its own).  On the CPU ``tests/test_sarvam_serving.py``
plants each at tiny sizes.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from perfbench import reference_sarvam as ref
from perfbench.controls_glm5 import _bf16

#: what the benchmark fills the latent pools with before a row is written
#: (``drivers/serve_latent.py``): a row the program may not attend (behind
#: a slot's length in its last page, or of a page nobody wrote) is LOUD.
#: At 24 k live rows one stale row is a 24 000th of a softmax, far under
#: bf16's noise: a quiet dead row could be attended and never seen.  A row
#: of 64s scores +-100 with half the heads and takes their whole softmax.
POISON = 64.0


@contextlib.contextmanager
def _patched(retrace=False, **attrs):
    """``reference_sarvam``'s names rebound; ``retrace`` where a traced
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


def _softmax_bf16(s):
    """The softmax in bfloat16, the nearest precision below the float32
    the configuration states for it: scores, exponentials and their sum."""
    return jax.nn.softmax(s.astype(jnp.bfloat16), axis=-1
                          ).astype(jnp.float32)


def _plain_frequencies(d):
    rotary = d["rope"]
    return 1.0 / (d["theta"] ** (
        jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary))


def _project_with_a_dead_row():
    """The first row of the batch (every query sees it) holds what a row
    nobody wrote holds: to the softmax, a dead row attended."""
    inner = ref._project

    def project(x, lp, pos, d):
        q_nope, q_rope, c_kv, k_r = inner(x, lp, pos, d)
        return (q_nope, q_rope, c_kv.at[0].set(POISON),
                k_r.at[0].set(POISON))

    return project


#: name -> a context manager under which the reference computes the fault
FAULTS = {
    "softmax_bf16": lambda: _patched(retrace=True, softmax=_softmax_bf16),
    "plain_rope": lambda: _patched(
        retrace=True, inverse_frequencies=_plain_frequencies),
    "scale_without_mscale": lambda: _patched(
        retrace=True,
        softmax_scale=lambda d: (d["nope"] + d["rope"]) ** -0.5),
    "no_shared_expert": lambda: _patched(
        shared_expert=lambda x, m: jnp.zeros_like(x)),
    "dead_row_attended": lambda: _patched(
        _project=_project_with_a_dead_row()),
}

def _as_served():
    project, attention, mlp, norm = (ref._project, ref.attention, ref.mlp,
                                     ref._norm)

    def rounded(fn):
        def call(*a, **kw):
            out = fn(*a, **kw)
            return tuple(map(_bf16, out)) if isinstance(out, tuple) \
                else _bf16(out)
        return call

    return _patched(retrace=True, PRECISION="bfloat16",
                    _project=rounded(project), attention=rounded(attention),
                    mlp=rounded(mlp), _norm=rounded(norm))


#: no fault: what bf16 alone does
WITNESSES = {"bf16_as_served": _as_served}

VERDICTS = ("logits_match_reference", "sparse_layer_matches_reference",
            "tokens_match_reference")
NUMBERS = ("logit_rms_p90", "logit_rms_worst", "logit_abs_worst",
           "token_deficit_p90", "token_deficit_worst", "sparse_run",
           "sparse_decode", "sparse_in_rel_run", "sparse_in_rel_decode")


def summary(checks: dict) -> dict:
    """The verdicts and the numbers they were made from."""
    out = {k: checks.get(k) for k in VERDICTS}
    out["correct"] = all(out.values())
    out.update({k: checks.get(k) for k in NUMBERS})
    return out


def against(kept: dict, base: dict) -> dict:
    """One reference's own logits and first-sparse-layer input
    (``reference_check``'s ``keep``) against another's, in the driver's
    own measures."""
    import numpy as np

    diff = np.concatenate(kept["logits"]) - np.concatenate(base["logits"])
    rms = np.sqrt(np.mean(diff * diff, axis=-1))
    out = {"logit_rms_p90": float(np.percentile(rms, 90)),
           "logit_rms_worst": float(rms.max())}
    for kind, mine in kept.get("sparse_in", {}).items():
        theirs = base["sparse_in"][kind]
        out[f"sparse_in_rel_{kind}"] = float(np.median(
            np.linalg.norm(mine - theirs, axis=-1)
            / np.maximum(np.linalg.norm(theirs, axis=-1), 1e-30)))
    return out


def readings(ctx, check) -> dict:
    """``check(keep)`` under every fault and witness."""
    out, base = {}, {}
    check(base)                      # what the unplanted reference computes
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
