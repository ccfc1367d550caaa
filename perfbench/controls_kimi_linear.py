"""The controls of ``serve-reasoning-linear``'s comparison: the reference
of ``perfbench/reference_kimi_linear.py`` with ONE fault planted, for
``drivers/serve_linear.py reference_check`` to hold the engine's timed
programs against (``perfbench/controls_sarvam.py``'s build and reason: a
program is as far from a wrong reference as a wrong program is from the
right one, so every fault here has to come out as NOT correct, by the
driver's own verdicts).

- ``state_bf16``: the recurrent state rounded to bfloat16 behind its
  decay and behind its update, every token (the nearest precision below
  the float32 the configuration states for it);
- ``decay_bf16``: the log-decay computed and kept in bfloat16;
- ``no_delta_correction``: ``u = beta v``, the state's own answer at the
  key not subtracted (plain gated linear attention);
- ``no_conv``: the convolution's three earlier taps dropped;
- ``no_shared_expert``.

On the chip: ``PERFBENCH_CONTROLS=1 python3 perfbench/run.py --workload
serve-reasoning-linear ...`` adds ``checks.controls`` to the run's
``perfbench detail`` line (a reference pass a control; readings only, the
run's ``correct`` is its own).  On the CPU
``tests/test_kimi_linear_serving.py`` plants each at tiny sizes.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from perfbench import reference_kimi_linear as ref


@contextlib.contextmanager
def _patched(**attrs):
    """``reference_kimi_linear``'s names rebound and every traced program
    traced again (a program read the name once, when it was traced)."""
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


def _bf16(x):
    """``x`` rounded to bfloat16's 8 bits of exponent and 7 of mantissa,
    kept float32.  NOT ``astype`` there and back: the chip's compiler
    takes such a pair of converts away (excess precision is allowed), and
    the control then plants nothing (my chip run, PR 43: both read as the
    unplanted reference to four digits)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _decay_bf16(f, a_log):
    rate = _bf16(jnp.exp(_bf16(a_log)))
    return _bf16(-rate[:, None] * _bf16(jax.nn.softplus(_bf16(f))))


def _delta_rule_uncorrected(q, k, v, g, beta):
    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[:, :, None]
        s = s + kt[:, :, None] * (bt[:, None] * vt)[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", qt, s)

    heads, dim = q.shape[1:]
    s, o = jax.lax.scan(step, jnp.zeros((heads, dim, dim), jnp.float32),
                        (q, k, v, g, beta))
    return o, s


#: name -> a context manager under which the reference computes the fault
FAULTS = {
    "state_bf16": lambda: _patched(state_dtype=_bf16),
    "decay_bf16": lambda: _patched(decay_of=_decay_bf16),
    "no_delta_correction": lambda: _patched(
        delta_rule=_delta_rule_uncorrected),
    "no_conv": lambda: _patched(causal_conv=lambda x, w: x * w[-1]),
    "no_shared_expert": lambda: _patched(
        shared_expert=lambda x, m: jnp.zeros_like(x)),
}

VERDICTS = ("logits_match_reference", "tokens_match_reference",
            "state_matches_reference", "decay_matches_reference")
NUMBERS = ("logit_rms_p90", "logit_rms_worst", "logit_abs_worst",
           "token_deficit_p90", "token_deficit_worst", "state_rel_first",
           "state_rel_last", "decay_rel")


def summary(checks: dict) -> dict:
    """The verdicts and the numbers they were made from."""
    out = {k: checks.get(k) for k in VERDICTS}
    out["correct"] = all(out.values())
    out.update({k: checks.get(k) for k in NUMBERS})
    return out


def readings(ctx, check) -> dict:
    """``check()`` under every fault."""
    out = {}
    for name, planted in FAULTS.items():
        ctx.say(f"control {name}")
        with planted():
            out[name] = summary(check())
    return out
