"""The controls of ``serve-gen-retention``'s comparison: the reference of
``perfbench/reference_brumby.py`` with ONE fault planted, for
``drivers/serve_retention.py reference_check`` to hold the engine's timed
programs against (``perfbench/controls_kimi_linear.py``'s build and
reason: a program is as far from a wrong reference as a wrong program is
from the right one, so every fault here has to come out as NOT correct, by
the driver's own verdicts).

- ``state_bf16``: the retention computed as a RECURRENCE whose state and
  sum of keys are rounded to bfloat16 behind their decay and behind their
  update, every token (the nearest precision below the float32 the
  configuration states for them); the full symmetric square, pairs twice;
- ``no_normaliser``: ``y_t = sum_s a[t, s] v_s``, the division dropped;
- ``gate_bf16``: the gate's ``logsigmoid`` computed in bfloat16;
- ``degree_1``: ``a[t, s] = (q_t . k_s) x decay``, the power dropped;
- ``no_rope``: q and k not rotated ahead of the power;
- ``pair_weight_1``: the symmetric square with weight 1 on a pair of
  different dimensions in place of ``sqrt 2``: ``phi(q) . phi(k) = ((q .
  k)^2 + sum_i q_i^2 k_i^2) / 2``.

On the chip: ``PERFBENCH_CONTROLS=1 python3 perfbench/run.py --workload
serve-gen-retention ...`` adds ``checks.controls`` to the run's
``perfbench detail`` line (a reference pass a control; readings only, the
run's ``correct`` is its own; a comma-separated list of names runs only
those).  On the CPU ``tests/test_brumby_serving.py`` plants each at tiny
sizes.
"""

from __future__ import annotations

import contextlib
import os

import jax
import jax.numpy as jnp

from perfbench import reference_brumby as ref
from perfbench.controls_kimi_linear import _bf16

VERDICTS = ("logits_match_reference", "tokens_match_reference",
            "state_matches_reference", "keysum_matches_reference",
            "gate_matches_reference")
NUMBERS = ("logit_rms_p90", "logit_rms_worst", "logit_abs_worst",
           "token_deficit_p90", "token_deficit_worst", "state_rel_first",
           "state_rel_last", "keysum_rel_first", "keysum_rel_last",
           "gate_rel")


def _recurrent_mix(h, lp, pos, d, want_state, held=_bf16):
    """``reference_brumby.mix`` as a recurrence over the full symmetric
    square, ``held`` applied to the state and the sum of keys behind the
    decay and behind the update."""
    q, k, v, cum, f = ref.inputs(h, lp, pos, d)
    gate = jnp.exp(ref.log_gate(f))                          # [T, Hk]
    t, hq, dim = q.shape
    hk = k.shape[1]
    qg = q.reshape(t, hk, hq // hk, dim)

    def step(carry, x):
        s, z = carry
        qt, kt, vt, gt = x
        kk = kt[:, :, None] * kt[:, None, :]                 # [Hk, D, D]
        s = held(held(s * gt[:, None, None, None])
                 + vt[:, :, None, None] * kk[:, None])
        z = held(held(z * gt[:, None, None]) + kk)
        qq = qt[:, :, :, None] * qt[:, :, None, :]           # [Hk, G, D, D]
        num = jnp.einsum("hvij,hgij->hgv", s, qq)
        den = jnp.einsum("hij,hgij->hg", z, qq) + d["den_eps"]
        return (s, z), num / den[..., None]

    (s, z), y = jax.lax.scan(
        step, (jnp.zeros((hk, dim, dim, dim), jnp.float32),
               jnp.zeros((hk, dim, dim), jnp.float32)), (qg, k, v, gate))
    o = jnp.einsum("thd,hde->te", y.reshape(t, hq, dim),
                   lp["retention"]["o_proj"]["kernel"].astype(jnp.float32))
    return o, (s, z) if want_state else None


def _weight_1_scores(q, k, degree):
    """``phi(q) . phi(k)`` with weight 1 on every pair."""
    return 0.5 * (jnp.einsum("gtd,sd->gts", q, k) ** 2
                  + jnp.einsum("gtd,sd->gts", q * q, k * k))


#: name -> a context manager under which the reference computes the fault
FAULTS = {
    "state_bf16": lambda: _patched(mix=_recurrent_mix),
    "no_normaliser": lambda: _patched(
        normaliser=lambda a, eps: jnp.ones(a.shape[:-1] + (1,), a.dtype)),
    "gate_bf16": lambda: _patched(
        log_gate=lambda f: _bf16(jax.nn.log_sigmoid(_bf16(f)))),
    "degree_1": lambda: _patched(power=lambda qk, degree: qk),
    "no_rope": lambda: _patched(rotate=lambda x, pos, theta: x),
    "pair_weight_1": lambda: _patched(scores=_weight_1_scores),
}


@contextlib.contextmanager
def _patched(**attrs):
    """``reference_brumby``'s names rebound and every traced program traced
    again (a program read the name once, when it was traced)."""
    old = {k: getattr(ref, k) for k in attrs}
    for k, v in attrs.items():
        setattr(ref, k, v)
    jax.clear_caches()
    try:
        yield
    finally:
        for k, v in old.items():
            setattr(ref, k, v)
        jax.clear_caches()


def summary(checks: dict) -> dict:
    """The verdicts and the numbers they were made from."""
    out = {k: checks.get(k) for k in VERDICTS}
    out["correct"] = all(out.values())
    out.update({k: checks.get(k) for k in NUMBERS})
    return out


def readings(ctx, check) -> dict:
    """``check()`` under every fault (``PERFBENCH_CONTROLS`` a
    comma-separated list of names: under those alone)."""
    only = [n for n in os.environ.get("PERFBENCH_CONTROLS", "").split(",")
            if n in FAULTS]
    out = {}
    for name, planted in FAULTS.items():
        if only and name not in only:
            continue
        ctx.say(f"control {name}")
        with planted():
            out[name] = summary(check())
    return out
