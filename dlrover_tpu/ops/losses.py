"""Loss ops.

Parity target: the reference's fused / vocab-parallel cross-entropy losses
(reference: atorch/atorch/modules/transformer/losses.py and
modules/distributed_modules/cross_entropy.py — a Megatron-style
vocab-parallel loss).  On TPU the logits stay sharded over the ``tp`` mesh
axis (logical axis ``vocab``); written as plain XLA ops, GSPMD partitions
the log-sum-exp and the label's compare-and-sum per shard and inserts the
same reduce-scatter/all-reduce pattern the reference implements by hand.

Two paths, and what each costs:

- **plain** (:func:`masked_language_model_loss` over the model's logits;
  what ``accelerate()`` runs unless ``loss_chunk_size`` is set): the full
  ``[batch, seq, vocab]`` logits are resident ONCE, in the dtype the model
  wrote (bf16), forward and backward.  The cross entropy rests on
  :func:`log_z_and_label_logit`, a ``jax.custom_vjp``: one pass over those
  logits each way (the row max rides on the matmul that writes them; the
  backward's ``d_logits`` is a fused operand of the two matmuls it
  feeds), no float32 array of their shape.  The head's matmul runs once.
- **chunked** (:func:`fused_lm_head_loss`): the logits exist one
  ``[batch, chunk, vocab]`` block at a time, so the memory is bounded,
  for the price of ONE MORE head matmul a step (each chunk's logits are
  recomputed in the backward): 4.4-5.9 ms at the benchmark's widths,
  where the plain path stands 2.3-2.4 ms over its three matmuls (PERF.md
  section 6, PR 49).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@jax.custom_vjp
def log_z_and_label_logit(
    logits: jax.Array, labels: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """``(log Z, the label's logit)`` of ``logits`` [..., vocab] in THEIR OWN
    dtype and ``labels`` [...] int, both results [...] float32.

    The core of the cross entropy, with its own backward rule, so that the
    logits are read once or twice forward and once backward and no float32
    array of their shape is ever held:

    - forward: each element is upcast to float32 where it is read; the max,
      the sum of ``exp`` and the label's logit (a compare against an
      ``iota`` inside the reduction: no gather) are reductions over the
      logits as given.  The residuals are those logits, the labels and
      ``log_z``;
    - backward: ``d_logits = g_logz * exp(logits - log_z) + g_label *
      onehot``, float32 an element, cast once to the logits' dtype (on
      the chip a fused operand of the two matmuls behind it, never an
      array in memory: see the rule).

    A label outside ``[0, vocab)`` picks nothing (its logit reads 0): mask
    such a position.  Plain ``jnp`` on purpose: where ``vocab`` is sharded
    over ``tp`` GSPMD partitions the reductions (module docstring).
    """
    return _log_z_fwd(logits, labels)[0]


def _label_onehot(logits: jax.Array, labels: jax.Array) -> jax.Array:
    vocab_ids = jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, logits.ndim - 1)
    return vocab_ids == labels[..., None].astype(jnp.int32)


def _log_z_fwd(logits, labels):
    x = logits.astype(jnp.float32)
    max_logit = jnp.max(x, axis=-1)
    sum_exp = jnp.sum(jnp.exp(x - max_logit[..., None]), axis=-1)
    label_logit = jnp.sum(
        jnp.where(_label_onehot(logits, labels), x, 0.0), axis=-1)
    log_z = jnp.log(sum_exp) + max_logit
    return (log_z, label_logit), (logits, labels, log_z)


def _log_z_bwd(residuals, cotangents):
    logits, labels, log_z = residuals
    g_log_z, g_label = cotangents
    softmax = jnp.exp(logits.astype(jnp.float32) - log_z[..., None])
    d_logits = g_log_z[..., None] * softmax + jnp.where(
        _label_onehot(logits, labels), g_label[..., None], 0.0)
    # cast once to the logits' dtype and NOT pinned to memory: XLA makes
    # this a fused operand of the two matmuls behind it and never writes
    # it.  An ``optimization_barrier`` here (the array written, 1.2 / 0.8 ms,
    # and both matmuls reading it) took 0.4-1.6 ms more off a step where
    # memory is free, and ADDED 8.9 ms to a step the compiler already
    # rematerialises to fit (0.41 GB held through the layers' backward):
    # the smaller worst case stands.  PERF.md section 6, PR 49
    return d_logits.astype(logits.dtype), None


log_z_and_label_logit.defvjp(_log_z_fwd, _log_z_bwd)


def cross_entropy_with_integer_labels(
    logits: jax.Array,
    labels: jax.Array,
    *,
    z_loss_weight: float = 0.0,
    label_smoothing: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """Numerically-stable token cross entropy.

    logits: [..., vocab] (any dtype; computed in float32)
    labels: [...] int32
    Returns (loss [...], z_loss [...]) — z_loss is the (log Z)^2 stabiliser
    (0 when z_loss_weight == 0).

    Rests on :func:`log_z_and_label_logit` (one pass over the logits each
    way).  ``label_smoothing > 0`` needs the mean logit as well and keeps
    the plain autodiff form, which holds the logits in float32.
    """
    if label_smoothing > 0.0:
        logits = logits.astype(jnp.float32)
        max_logit = jax.lax.stop_gradient(
            jnp.max(logits, axis=-1, keepdims=True))
        log_z = jnp.log(
            jnp.sum(jnp.exp(logits - max_logit), axis=-1)) + max_logit[..., 0]
        label_logit = jnp.take_along_axis(
            logits, labels[..., None].astype(jnp.int32), axis=-1
        )[..., 0]
        mean_logit = jnp.mean(logits, axis=-1)
        loss = (1.0 - label_smoothing) * (log_z - label_logit) \
            + label_smoothing * (log_z - mean_logit)
    else:
        log_z, label_logit = log_z_and_label_logit(logits, labels)
        loss = log_z - label_logit
    z_loss = jnp.zeros_like(loss)
    if z_loss_weight > 0.0:
        z_loss = z_loss_weight * jnp.square(log_z)
    return loss, z_loss


def fused_lm_head_loss(
    hidden: jax.Array,
    kernel: jax.Array,
    labels: jax.Array,
    mask: Optional[jax.Array] = None,
    *,
    chunk_size: int = 512,
    z_loss_weight: float = 0.0,
    logit_scale: float = 1.0,
):
    """LM-head projection + cross entropy without materializing the full
    ``[batch, seq, vocab]`` logits.

    The fused-loss counterpart of the reference's fused cross-entropy
    (reference: atorch/atorch/modules/transformer/losses.py): sequence
    chunks are scanned with rematerialization, so peak memory holds one
    ``[batch, chunk, vocab]`` block instead of the full logits (fwd AND
    bwd) — where the plain path keeps them whole in bf16 (0.27 GB at 4 096
    tokens x 32 768, 0.41 GB at 50 304).  The price is the head's matmul
    a second time in the backward (module docstring); take this path when
    the logits do not fit, not for speed.  Each chunk's cross entropy is
    the plain path's own (:func:`log_z_and_label_logit`).

    hidden: [batch, seq, hidden] final transformer states
    kernel: [hidden, vocab] lm-head weight
    labels: [batch, seq] int targets; mask: [batch, seq] validity.
    Returns (mean loss over valid tokens, valid-token count).
    """
    b, s, h = hidden.shape
    if s % chunk_size:
        # keep the memory bound: largest divisor of s not above chunk_size
        chunk_size = next(
            c for c in range(min(chunk_size, s), 0, -1) if s % c == 0
        )
    nchunk = s // chunk_size
    xs = hidden.reshape(b, nchunk, chunk_size, h).transpose(1, 0, 2, 3)
    labels_r = labels.reshape(b, nchunk, chunk_size).transpose(1, 0, 2)
    if mask is None:
        mask_r = jnp.ones((nchunk, b, chunk_size), jnp.float32)
    else:
        mask_r = (
            mask.astype(jnp.float32)
            .reshape(b, nchunk, chunk_size)
            .transpose(1, 0, 2)
        )

    def body(carry, x):
        loss_acc, w_acc = carry
        hid, lab, msk = x
        logits = jax.lax.dot_general(
            hid, kernel.astype(hid.dtype),
            (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if logit_scale != 1.0:
            # the model's output multiplier (e.g. muP's explicit 1/m
            # convention) must match the non-fused logits path
            logits = logits * logit_scale
        loss, z_loss = cross_entropy_with_integer_labels(
            logits, lab, z_loss_weight=z_loss_weight
        )
        return (
            loss_acc + jnp.sum((loss + z_loss) * msk),
            w_acc + jnp.sum(msk),
        ), None

    (loss_sum, w_sum), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False),
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xs, labels_r, mask_r),
    )
    weight = jnp.maximum(w_sum, 1.0)
    return loss_sum / weight, weight


def masked_language_model_loss(
    logits: jax.Array,
    labels: jax.Array,
    mask: Optional[jax.Array] = None,
    *,
    z_loss_weight: float = 0.0,
    return_weight: bool = False,
):
    """Mean next-token loss over valid (mask != 0) positions.

    With ``return_weight=True`` also returns the denominator (valid-token
    count) — gradient accumulation weights microbatches by it so that
    accumulated steps exactly match the full-batch step.
    """
    loss, z_loss = cross_entropy_with_integer_labels(
        logits, labels, z_loss_weight=z_loss_weight
    )
    total = loss + z_loss
    if mask is None:
        weight = jnp.float32(total.size)
        mean = jnp.mean(total)
    else:
        mask = mask.astype(jnp.float32)
        weight = jnp.maximum(jnp.sum(mask), 1.0)
        mean = jnp.sum(total * mask) / weight
    if return_weight:
        return mean, weight
    return mean
