"""The controls of ``train-conv-moe-8k``'s comparison: the same
comparison (``drivers/train_conv.py reference_check``) with ONE fault
planted, which has to come out as NOT correct by at least one of the
driver's own verdicts.

Nine faults are planted in the reference of
``perfbench/reference_lfm2.py`` (``controls_granite.py``'s build and
reason: a program is as far from a wrong reference as a wrong program is
from the right one):

- ``taps_reversed``: the convolution reads the positions AFTER a token,
  ``c_t = w_0 v_{t+2} + w_1 v_{t+1} + w_2 v_t``;
- ``no_out_gate``: ``y = c``, ``C`` dropped;
- ``no_in_gate``: ``v = u``, ``B`` dropped;
- ``no_qk_norm``: q and k go to the rotation as projected;
- ``qk_norm_whole_projection``: one RMS over all of a token's heads (the
  other kind of ``LlamaConfig.qk_norm_kind``), the scale a head's, tiled;
- ``choice_without_bias``: the 4 largest of ``s``, not of ``s + b``;
- ``weights_not_normalised``: the four weights as the sigmoid gave them;
- ``head_untied``: the head a matrix of its own with the embedding's
  values, so that the embedding's gradient lacks the head's part;
- ``half_batch``: the loss is the mean over the first half of the batch's
  rows alone (the forward of the other half is as it was).

Two are planted in what the SYSTEM handed over:

- ``state_unchanged``: the state behind the first step is the state it
  started from: the parameters as they were, the first moment zero;
- ``fp8_matmuls``, the nearest precision below the stated one:
  ``LlamaConfig.fp8`` (e4m3 operands in every projection and expert
  matmul): its forward and its visits, and the first moment and grad norm
  its gradient would leave, against the right reference.

And two readings that are no faults (PERF.md section 6, PR 55, has what
they showed): ``own_choice``, the right program against the reference on
the visits of the reference's OWN choice and not on the system's (what the
comparison read before it took the system's visits); and
``float32_compute``, the system computing in float32 (``LlamaConfig.dtype``,
every matmul under ``highest``; the stored parameters are the same
bfloat16 values) against the reference on ITS visits: how much of the
right program's distance from the reference is the precision the
configuration states.

On the chip: ``PERFBENCH_CONTROLS=1 python3 perfbench/run.py --workload
train-conv-moe-8k ...`` (or a comma-separated list of the names above)
adds ``checks.controls`` to the run's ``perfbench detail`` line (a
reference pass a control; readings only, the run's ``correct`` is its
own).  On the CPU ``tests/test_lfm2_reference.py``
plants each at tiny sizes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from perfbench import reference_lfm2 as ref

VERDICTS = ("loss_matches_reference", "grads_match_reference",
            "tied_head_gradient_matches", "step_grad_norm_is_the_references",
            "state_moved_as_adamw", "hidden_matches_reference",
            "every_pick_routed", "visits_are_the_counted_picks",
            "counts_match_reference", "picks_held_is_the_held_counts")
NUMBERS = ("loss_abs_diff", "hidden_rel_err_median", "hidden_rel_err_max",
           "grad_rel_err_worst", "grad_rel_err_worst_leaf",
           "grad_rel_err_median_of_class", "grad_rel_err_median",
           "grad_rel_err_all", "grad_rel_err_embedding",
           "grad_rel_err_head_rows", "grad_norm_rel_diff",
           "grad_norm_reference", "grad_norm_first_step", "update_rel_err",
           "update_rel_err_decay_alone", "parameters_moved_share", "picks_moved_per_layer")


@contextlib.contextmanager
def _patched(**attrs):
    """``reference_lfm2``'s names rebound and every traced program traced
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


def _from_the_future(v, back: int):
    return jnp.concatenate(
        [v[back:], jnp.zeros((back, v.shape[1]), v.dtype)])


def _whole_projection_norm(x, scale, eps):
    flat = x.reshape(x.shape[0], -1)
    return ref.rmsnorm(flat, jnp.tile(scale, x.shape[1]), eps).reshape(
        x.shape)


#: name -> a context manager under which the reference computes the fault
FAULTS = {
    "taps_reversed": lambda: _patched(shifted=_from_the_future),
    "no_out_gate": lambda: _patched(gate_out=lambda c, conv: conv),
    "no_in_gate": lambda: _patched(gate_in=lambda b, u: u),
    "no_qk_norm": lambda: _patched(head_norm=lambda x, scale, eps: x),
    "qk_norm_whole_projection": lambda: _patched(
        head_norm=_whole_projection_norm),
    "choice_without_bias": lambda: _patched(
        choice=lambda scores, bias: scores),
    "weights_not_normalised": lambda: _patched(
        pick_weights=lambda weights, scale: scale * weights),
    "head_untied": lambda: _patched(
        head_matrix=lambda top: jax.lax.stop_gradient(
            top["embed_tokens"]["embedding"].astype(jnp.float32))),
    "half_batch": lambda: _patched(
        loss_rows=lambda batch: range(len(batch) // 2)),
}
#: the faults planted in what the system handed over
UNCHANGED = "state_unchanged"
FP8 = "fp8_matmuls"
#: the readings that are no faults
OWN_CHOICE = "own_choice"
FLOAT32 = "float32_compute"


def summary(checks: dict) -> dict:
    """The verdicts and the numbers they were made from (a verdict the
    comparison did not make, ``state_moved_as_adamw`` of a control that
    has no state, counts as held)."""
    out = {k: checks.get(k, True) for k in VERDICTS}
    out["correct"] = all(out.values())
    out.update({k: checks.get(k) for k in NUMBERS})
    return out


def state_unchanged(params, got: dict) -> dict:
    """``got`` of a step that left the state as it was."""
    import numpy as np

    return {**got, "params_after": params,
            "moment": jax.tree_util.tree_map(
                lambda m: np.broadcast_to(np.zeros((), m.dtype), m.shape),
                got["moment"])}


def outputs_of(variant, params, batch, at) -> tuple:
    """``(got, first)`` of the model ``variant`` on the same parameters:
    its forward, and the first moment, grad norm and held picks that the
    gradient of its own loss function would leave behind a first step (no
    state: the update is not read)."""
    from dlrover_tpu.accel.accelerate import default_loss_fn
    from perfbench.drivers import train_conv

    got = train_conv.system_forward(variant, params, batch, at)
    loss_fn = default_loss_fn(variant)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p, ids: loss_fn(p, {"input_ids": ids})[0]))(
            params, jnp.asarray(batch))
    norm = math.sqrt(sum(
        float(jnp.sum(jnp.square(g.astype(jnp.float32))))
        for g in jax.tree_util.tree_leaves(grads)))
    scale = (1 - train_conv.ADAMW["b1"]) * min(
        1.0, train_conv.CLIP_NORM / norm)
    got["moment"] = jax.device_get(jax.tree_util.tree_map(
        lambda g: (scale * g.astype(jnp.float32)).astype(g.dtype), grads))
    first_held, held = variant.config.moe_experts_held
    return got, {
        "loss": float(loss), "grad_norm": norm,
        "moe_picks_held": float(
            got["counts"][:, first_held:first_held + held].sum())}


def fp8_outputs(model, params, batch, at) -> tuple:
    return outputs_of(
        type(model)(dataclasses.replace(model.config, fp8=True)),
        params, batch, at)


def float32_outputs(model, params, batch, at) -> tuple:
    """The grouped matmuls at half their tiles' sides: float32 tiles of
    (256, 1024, 1024) do not fit the chip's fast memory (17.09 MB of 16: my
    chip run, PR 55)."""
    from dlrover_tpu.models import moe

    tiling, moe.GMM_TILING = moe.GMM_TILING, (256, 512, 512)
    try:
        with jax.default_matmul_precision("highest"):
            return outputs_of(
                type(model)(dataclasses.replace(
                    model.config, dtype=jnp.dtype("float32"))),
                params, batch, at)
    finally:
        moe.GMM_TILING = tiling


def readings(ctx, model, params, batch, got, first, at, learning_rate,
             names: str = "1") -> dict:
    """The comparison under every fault and in both readings, or under
    those of the comma-separated ``names``."""
    from perfbench.drivers import train_conv

    def check(got, first, **kw):
        return summary(train_conv.reference_check(
            ctx.config, model.config, params, batch, got, first, at,
            learning_rate, **kw))

    def planted(name):
        with FAULTS[name]():
            return check(stateless, first)

    def float32():
        try:
            return check(*float32_outputs(model, params, batch, at))
        except Exception as e:  # noqa: BLE001 - the chip may refuse its size
            return {"error": f"{type(e).__name__}: {e}"[:2000]}

    # the update is compared with the state's own moment: no fault planted
    # in the reference's model moves it, so it is read where the state is
    # the fault
    stateless = {k: v for k, v in got.items() if k != "params_after"}
    every = {
        OWN_CHOICE: lambda: check(stateless, first, own_choice=True),
        **{name: functools.partial(planted, name) for name in FAULTS},
        UNCHANGED: lambda: check(state_unchanged(params, got), first),
        FP8: lambda: check(*fp8_outputs(model, params, batch, at)),
        FLOAT32: float32,
    }
    out = {}
    for name in (every if names == "1" else names.split(",")):
        ctx.say(f"control {name}")
        out[name] = every[name]()
    return out
