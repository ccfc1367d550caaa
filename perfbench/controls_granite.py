"""The controls of ``serve-rag-ssm``'s comparison: the reference of
``perfbench/reference_granite.py`` with ONE fault planted, for
``drivers/serve_ssm.py reference_check`` to hold the engine's timed
programs against (``perfbench/controls_kimi_linear.py``'s build and
reason: a program is as far from a wrong reference as a wrong program is
from the right one, so every fault here has to come out as NOT correct, by
the driver's own verdicts).

- ``state_bf16``: the recurrent state rounded to bfloat16 behind its
  decay and behind its update, every token (the nearest precision below
  the float32 the configuration states for it);
- ``gate_after_norm``: ``RMSNorm(y) x SiLU(z)``, the gate OUTSIDE the norm;
- ``no_skip``: ``D x`` dropped from the scan's output;
- ``softmax_scale_rsqrt``: the attention layer's scores x 128^-0.5 in
  place of the published 1/128;
- ``no_residual_multiplier``: every branch added whole, not x 0.22;
- ``no_embedding_multiplier``: ``x_0 = embed[id]``, not x 12 (the one
  fault the FIRST layer's convolution rows can see: its input norm's
  epsilon weighs 2.5 % against an embedding of N(0, 0.02) and nothing
  against twelve times it).

On the chip: ``PERFBENCH_CONTROLS=1 python3 perfbench/run.py --workload
serve-rag-ssm ...`` adds ``checks.controls`` to the run's ``perfbench
detail`` line (a reference pass a control; readings only, the run's
``correct`` is its own).  On the CPU ``tests/test_granite_serving.py``
plants each at tiny sizes.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from perfbench import reference_granite as ref
from perfbench.controls_kimi_linear import _bf16

VERDICTS = ("logits_match_reference", "tokens_match_reference",
            "state_matches_reference", "conv_matches_reference")
NUMBERS = ("logit_rms_p90", "logit_rms_worst", "logit_abs_worst",
           "token_deficit_p90", "token_deficit_worst", "state_rel_first",
           "state_rel_last", "conv_rel_first", "conv_rel_last")


@contextlib.contextmanager
def _patched(**attrs):
    """``reference_granite``'s names rebound and every traced program
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


#: name -> a context manager under which the reference computes the fault
FAULTS = {
    "state_bf16": lambda: _patched(state_dtype=_bf16),
    "gate_after_norm": lambda: _patched(
        gated_norm=lambda y, z, scale, eps: ref.rmsnorm(y, scale, eps)
        * jax.nn.silu(z)),
    "no_skip": lambda: _patched(skip_of=lambda d_skip, x: jnp.zeros_like(x)),
    "softmax_scale_rsqrt": lambda: _patched(
        softmax_scale=lambda d: d["head_dim"] ** -0.5),
    "no_residual_multiplier": lambda: _patched(
        residual=lambda x, y, d: x + y),
    "no_embedding_multiplier": lambda: _patched(
        embed=lambda tokens, top, d, _embed=ref.embed: _embed(
            tokens, top, dict(d, embedding_mult=1.0))),
}


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
