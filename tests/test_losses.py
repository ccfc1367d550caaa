"""The training loss (``ops/losses.py``, ``accel/accelerate.py loss_fn``):
one custom-VJP cross entropy over unsliced logits.

The forms this PR replaced are KEPT HERE as the references: the plain
autodiff cross entropy over float32 logits (``_plain_cross_entropy``) and
the loss over SLICED logits (``_sliced_loss_fn``).  Values and gradients
of the new code are held to them; a structural guard holds the residuals
of the new core to the logits' own dtype.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.losses import (
    cross_entropy_with_integer_labels,
    fused_lm_head_loss,
    log_z_and_label_logit,
    masked_language_model_loss,
)


# ------------------------------------------------------------------ oracles
def _plain_cross_entropy(logits, labels, *, z_loss_weight=0.0,
                         label_smoothing=0.0):
    """``cross_entropy_with_integer_labels`` as it stood before the custom
    VJP: float32 logits, autodiff's backward."""
    logits = logits.astype(jnp.float32)
    max_logit = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - max_logit
    log_z = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + max_logit[..., 0]
    label_logit = jnp.take_along_axis(
        logits, labels[..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    loss = log_z - label_logit
    if label_smoothing > 0.0:
        mean_logit = jnp.mean(logits, axis=-1)
        loss = (1.0 - label_smoothing) * loss \
            + label_smoothing * (log_z - mean_logit)
    z_loss = jnp.zeros_like(loss)
    if z_loss_weight > 0.0:
        z_loss = z_loss_weight * jnp.square(log_z)
    return loss, z_loss


def _plain_masked_loss(logits, labels, mask=None, *, z_loss_weight=0.0):
    loss, z_loss = _plain_cross_entropy(
        logits, labels, z_loss_weight=z_loss_weight)
    total = loss + z_loss
    if mask is None:
        return jnp.mean(total)
    mask = mask.astype(jnp.float32)
    return jnp.sum(total * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _sliced_loss_fn(model):
    """``default_loss_fn``'s plain path as it stood: the LOGITS sliced to
    ``seq - 1`` rows, the plain cross entropy over them."""

    def loss_fn(params, batch):
        logits, _ = model.apply(
            {"params": params}, batch["input_ids"], mutable=["moe_losses"])
        labels = batch["input_ids"][:, 1:]
        logits = logits[:, :-1]
        mask = batch.get("loss_mask")
        mask = mask[:, 1:] if mask is not None else None
        return _plain_masked_loss(logits, labels, mask)

    return loss_fn


def _case(vocab, dtype, seed=0, rows=(3, 8)):
    """Logits of unit scale with the rows the issue names: labels at 0 and
    ``vocab - 1``, a row whose logits are all equal, one with a +60 and one
    with a -60 outlier."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    logits = jax.random.normal(k1, rows + (vocab,), jnp.float32) * 2.0
    labels = jax.random.randint(k2, rows, 0, vocab).astype(jnp.int32)
    labels = labels.at[0, 0].set(0).at[0, 1].set(vocab - 1)
    logits = logits.at[0, 2].set(0.75)                  # all equal
    logits = logits.at[0, 3, 5].set(60.0)               # an outlier up
    logits = logits.at[0, 4, 7].set(-60.0)              # and one down
    labels = labels.at[0, 3].set(5).at[0, 4].set(7)     # the label ON it
    logits = logits.at[1, 3, 9].set(60.0)               # and beside it
    return logits.astype(dtype), labels


def _mask(rows, seed=5):
    mask = jax.random.uniform(jax.random.PRNGKey(seed), rows) > 0.4
    return mask.astype(jnp.float32)


def _tol(dtype):
    # float32: rounding of exp(x - log_z) against exp(x - max) / sum;
    # bf16: the gradient is ROUNDED to bf16 on both sides (8 bits), so a
    # last-bit difference of the float32 value can move one bf16 ulp
    return dict(rtol=2e-6, atol=2e-7) if dtype == jnp.float32 \
        else dict(rtol=2 ** -7, atol=1e-6)


# ------------------------------------------------- the core, value and grad
@pytest.mark.parametrize("vocab", [512, 1000, 131])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_core_matches_plain_log_z_and_label_logit(dtype, vocab):
    logits, labels = _case(vocab, dtype)
    log_z, label_logit = log_z_and_label_logit(logits, labels)
    assert log_z.dtype == label_logit.dtype == jnp.float32
    x = logits.astype(jnp.float32)
    np.testing.assert_allclose(
        log_z, jax.scipy.special.logsumexp(x, axis=-1), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(
        label_logit, jnp.take_along_axis(x, labels[..., None], -1)[..., 0])
    # the row of equal logits: log Z is that logit plus log(vocab)
    np.testing.assert_allclose(
        log_z[0, 2], float(x[0, 2, 0]) + np.log(vocab), rtol=1e-6)


@pytest.mark.parametrize("z_loss_weight", [0.0, 1e-3], ids=["noz", "z"])
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "mask"])
@pytest.mark.parametrize("vocab", [512, 1000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_masked_loss_value_and_grad_match_plain(
        dtype, vocab, masked, z_loss_weight):
    logits, labels = _case(vocab, dtype, seed=1)
    mask = _mask(labels.shape) if masked else None

    new, g_new = jax.value_and_grad(
        lambda l: masked_language_model_loss(
            l, labels, mask, z_loss_weight=z_loss_weight))(logits)
    old, g_old = jax.value_and_grad(
        lambda l: _plain_masked_loss(
            l, labels, mask, z_loss_weight=z_loss_weight))(logits)
    assert g_new.dtype == logits.dtype and g_new.shape == logits.shape
    np.testing.assert_allclose(float(new), float(old), rtol=1e-6)
    np.testing.assert_allclose(
        g_new.astype(jnp.float32), g_old.astype(jnp.float32), **_tol(dtype))
    assert bool(jnp.all(jnp.isfinite(g_new.astype(jnp.float32))))
    if masked:
        # a masked position takes no gradient at all
        dead = np.asarray(mask) == 0
        assert not np.asarray(g_new.astype(jnp.float32))[dead].any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_per_token_loss_and_z_loss_match_plain(dtype):
    logits, labels = _case(1000, dtype, seed=2)
    loss, z = cross_entropy_with_integer_labels(
        logits, labels, z_loss_weight=1e-2)
    loss0, z0 = _plain_cross_entropy(logits, labels, z_loss_weight=1e-2)
    np.testing.assert_allclose(loss, loss0, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(z, z0, rtol=1e-6, atol=1e-6)
    # the cotangents of the two outputs are independent: a weight on the
    # z-loss alone flows through log_z only
    g = jax.grad(lambda l: jnp.sum(cross_entropy_with_integer_labels(
        l, labels, z_loss_weight=1e-2)[1]))(logits)
    g0 = jax.grad(lambda l: jnp.sum(_plain_cross_entropy(
        l, labels, z_loss_weight=1e-2)[1]))(logits)
    np.testing.assert_allclose(
        g.astype(jnp.float32), g0.astype(jnp.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_label_smoothing_keeps_the_plain_form(dtype):
    logits, labels = _case(1000, dtype, seed=3)

    def total(fn):
        return lambda l: jnp.sum(sum(fn(
            l, labels, z_loss_weight=1e-3, label_smoothing=0.1)))

    new, g_new = jax.value_and_grad(
        total(cross_entropy_with_integer_labels))(logits)
    old, g_old = jax.value_and_grad(total(_plain_cross_entropy))(logits)
    assert float(new) == float(old)
    np.testing.assert_array_equal(
        g_new.astype(jnp.float32), g_old.astype(jnp.float32))
    # and the custom VJP is not on that path
    text = str(jax.make_jaxpr(total(cross_entropy_with_integer_labels))(
        logits))
    assert "custom_vjp" not in text


def test_outlier_rows_are_finite_and_exact():
    """+-60 beside logits of unit scale: exp underflows to 0 for the rest
    of the row (up) or for the outlier (down), nothing overflows."""
    logits, labels = _case(512, jnp.float32)
    loss, _ = cross_entropy_with_integer_labels(logits, labels)
    assert bool(jnp.all(jnp.isfinite(loss)))
    assert float(loss[0, 3]) < 1e-6            # the label IS the +60 one
    assert float(loss[0, 4]) > 60.0            # the label is the -60 one
    assert float(loss[1, 3]) > 50.0            # the +60 one is another's
    g = jax.grad(lambda l: jnp.sum(
        cross_entropy_with_integer_labels(l, labels)[0]))(logits)
    np.testing.assert_allclose(jnp.sum(g, axis=-1), 0.0, atol=1e-5)


def test_core_under_jit_and_vmap_and_second_call():
    logits, labels = _case(131, jnp.bfloat16, seed=4)
    f = jax.jit(jax.value_and_grad(
        lambda l: masked_language_model_loss(l, labels)))
    a, ga = f(logits)
    b, gb = jax.value_and_grad(
        lambda l: _plain_masked_loss(l, labels))(logits)
    np.testing.assert_allclose(float(a), float(b), rtol=1e-6)
    np.testing.assert_allclose(ga.astype(jnp.float32),
                               gb.astype(jnp.float32), **_tol(jnp.bfloat16))
    per_row = jax.vmap(lambda l, y: log_z_and_label_logit(l, y)[0])(
        logits, labels)
    np.testing.assert_array_equal(
        per_row, log_z_and_label_logit(logits, labels)[0])


def test_fused_lm_head_loss_rests_on_the_same_core():
    k = jax.random.split(jax.random.PRNGKey(6), 3)
    hidden = jax.random.normal(k[0], (2, 16, 32), jnp.float32)
    kernel = jax.random.normal(k[1], (32, 200), jnp.float32) * 0.2
    labels = jax.random.randint(k[2], (2, 16), 0, 200).astype(jnp.int32)
    mask = _mask((2, 16))

    def chunked(h, w):
        return fused_lm_head_loss(
            h, w, labels, mask, chunk_size=4, z_loss_weight=1e-3)[0]

    def plain(h, w):
        return _plain_masked_loss(h @ w, labels, mask, z_loss_weight=1e-3)

    a, ga = jax.value_and_grad(chunked, argnums=(0, 1))(hidden, kernel)
    b, gb = jax.value_and_grad(plain, argnums=(0, 1))(hidden, kernel)
    np.testing.assert_allclose(float(a), float(b), rtol=1e-5)
    for x, y in zip(ga, gb):
        np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------- the structural guard
def _arrays_of(tree):
    return [x for x in jax.tree_util.tree_leaves(tree)
            if hasattr(x, "shape") and hasattr(x, "dtype")]


@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "mask"])
def test_residuals_hold_no_float32_array_of_the_logits_shape(masked):
    """What ``jax.vjp`` of the loss over bf16 logits keeps for the
    backward: the logits in their own dtype, integer labels and float32
    arrays of ``[B, S]`` at most.  A later edit that saves a float32 copy
    of the logits (or the softmax) fails here."""
    logits, labels = _case(512, jnp.bfloat16, rows=(2, 16))
    mask = _mask(labels.shape) if masked else None

    def loss(l):
        return masked_language_model_loss(l, labels, mask, z_loss_weight=1e-3)

    _, pullback = jax.vjp(loss, logits)
    residuals = _arrays_of(pullback)
    assert residuals, "the pullback holds no residual at all"
    full = [x for x in residuals if x.shape == logits.shape]
    assert [x.dtype for x in full] == [logits.dtype], \
        [(x.shape, x.dtype) for x in residuals]
    for x in residuals:
        if x.shape != logits.shape:
            assert x.size <= labels.size, (x.shape, x.dtype)
    # the same of the compiled program's view: the backward's jaxpr reads
    # no float32 input of the logits' shape
    jaxpr = jax.make_jaxpr(pullback)(jnp.float32(1.0))
    consts = [v.aval for v in jaxpr.jaxpr.constvars]
    assert not [a for a in consts
                if a.shape == logits.shape and a.dtype == jnp.float32]


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters
    (``pjit``, ``scan``, ``remat``, a custom VJP's forward)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def _moves_of_the_logits(fn, params, vocab):
    """Names of the data-movement operations in ``grad(fn)`` that touch an
    array ``[.., .., vocab]``."""
    moves = ("slice", "pad", "gather", "scatter", "scatter-add",
             "scatter_add", "dynamic_slice", "concatenate")
    found = []
    for eqn in _equations(jax.make_jaxpr(jax.grad(fn))(params).jaxpr):
        shapes = [v.aval.shape for v in list(eqn.invars) + list(eqn.outvars)
                  if hasattr(getattr(v, "aval", None), "shape")]
        if eqn.primitive.name in moves and any(
                len(s) == 3 and s[-1] == vocab for s in shapes):
            found.append(eqn.primitive.name)
    return sorted(set(found))


def test_gradient_program_has_no_slice_pad_or_gather_of_the_logits():
    """In the gradient of the plain ``loss_fn`` nothing moves the logits:
    no ``slice`` / ``pad`` (the labels are shifted, not the logits), no
    ``gather`` / ``scatter`` (the label's logit is a compare inside the
    reduction).  The sliced form, through the same reader, shows them."""
    from dlrover_tpu.accel.accelerate import default_loss_fn

    model, params, batch = _tiny_llama(jnp.bfloat16)
    vocab = model.config.vocab_size
    assert _moves_of_the_logits(
        lambda p: default_loss_fn(model)(p, batch)[0], params, vocab) == []
    old = _moves_of_the_logits(
        lambda p: _sliced_loss_fn(model)(p, batch), params, vocab)
    assert {"slice", "pad", "gather"} <= set(old), old


# ------------------------------------------- the plain loss_fn on a tiny Llama
def _tiny_llama(dtype, with_mask=False):
    import flax.linen as nn

    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.dtype(dtype))
    model = LlamaModel(cfg)
    ids = jax.random.randint(
        jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size).astype(jnp.int32)
    params = nn.unbox(model.init(jax.random.PRNGKey(0), ids))["params"]
    batch = {"input_ids": ids}
    if with_mask:
        batch["loss_mask"] = _mask((2, 32), seed=4)
    return model, params, batch


@pytest.mark.parametrize("with_mask", [False, True], ids=["nomask", "mask"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_plain_loss_fn_matches_the_sliced_form(dtype, with_mask):
    from dlrover_tpu.accel.accelerate import default_loss_fn

    model, params, batch = _tiny_llama(dtype, with_mask)
    new_fn, old_fn = default_loss_fn(model), _sliced_loss_fn(model)
    (new, aux), g_new = jax.value_and_grad(new_fn, has_aux=True)(
        params, batch)
    old, g_old = jax.value_and_grad(old_fn)(params, batch)
    mask = batch.get("loss_mask")
    targets = float(jnp.sum(mask[:, 1:])) if with_mask else 2 * 31
    assert float(aux["weight"]) == targets
    f32 = dtype == jnp.float32
    np.testing.assert_allclose(float(new), float(old),
                               rtol=1e-6 if f32 else 1e-5)
    scale = max(float(jnp.max(jnp.abs(x)))
                for x in jax.tree_util.tree_leaves(g_old))
    for a, b in zip(jax.tree_util.tree_leaves(g_new),
                    jax.tree_util.tree_leaves(g_old)):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(
            a.astype(jnp.float32), b.astype(jnp.float32),
            rtol=1e-5 if f32 else 2 ** -6,
            atol=(1e-6 if f32 else 2 ** -8) * scale)


def test_plain_loss_fn_with_labels_given_is_unshifted():
    """``labels`` in the batch: logits and labels stand position for
    position, and ``loss_mask`` with them (no shift, as before)."""
    from dlrover_tpu.accel.accelerate import default_loss_fn

    model, params, batch = _tiny_llama(jnp.float32, with_mask=True)
    labels = jnp.roll(batch["input_ids"], 3, axis=1)
    loss, aux = default_loss_fn(model)(params, dict(batch, labels=labels))
    logits, _ = model.apply({"params": params}, batch["input_ids"],
                            mutable=["moe_losses"])
    want = _plain_masked_loss(logits, labels, batch["loss_mask"])
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)
    assert float(aux["weight"]) == float(jnp.sum(batch["loss_mask"]))


# ------------------------------------------------- vocab sharded over ``tp``
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_loss_and_grad_with_vocab_sharded_over_tp(dtype):
    """GSPMD partitions the core's reductions where ``vocab`` is sharded:
    the loss and the logits' gradient equal the single-device ones, and
    the gradient comes back sharded as the logits were."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devices, ("dp", "tp"))
    logits, labels = _case(512, dtype, seed=7, rows=(4, 8))
    mask = _mask(labels.shape)

    def loss(l, y, m):
        return masked_language_model_loss(l, y, m, z_loss_weight=1e-3)

    want, g_want = jax.value_and_grad(loss)(logits, labels, mask)
    sh = NamedSharding(mesh, P("dp", None, "tp"))
    rows = NamedSharding(mesh, P("dp", None))
    f = jax.jit(jax.value_and_grad(loss),
                in_shardings=(sh, rows, rows),
                out_shardings=(NamedSharding(mesh, P()), sh))
    got, g_got = f(jax.device_put(logits, sh), jax.device_put(labels, rows),
                   jax.device_put(mask, rows))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(g_got.astype(jnp.float32)),
        np.asarray(g_want.astype(jnp.float32)), **_tol(dtype))
    assert g_got.sharding.is_equivalent_to(sh, g_got.ndim)
