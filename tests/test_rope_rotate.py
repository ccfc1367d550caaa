"""RoPE over whole heads (``models/llama.py apply_rope_table``, the form
``ops/pallas/rope.py`` gives it) against the formula it replaced, kept
here as the plain reference: slice the rotating part off the head, split
it, rotate the halves in float32, concatenate.

Values: the new form does the same float32 products and sums lane for
lane, so it may differ from the reference by how a compiler contracts a
product and a sum: a unit in the last place of the OUTPUT type at the
size of the products, no more (``_assert_last_bit``).  Gradients go
through the ``custom_vjp`` (the rotation back) and are held against
``jax.grad`` of the reference.  The kernel runs in Pallas's interpreter
here; on the chip it reads bit for bit what its ``jnp`` oracle reads
(PERF.md section 6, PR 45).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.llama import (RopeSpec, apply_rope_table,
                                      rope_inverse_frequencies, rope_table)
from dlrover_tpu.ops.pallas import rope

D, B, S = 128, 2, 64
# YaRN's factor on cos and sin, so that a table without it would fail
SPECS = {
    "whole": RopeSpec(theta=1e4),
    "half": RopeSpec(theta=5e5, rotary_fraction=0.5, yarn_factor=64.0,
                     yarn_original_max_len=16, attention_factor=1.4),
    "none": RopeSpec(rotary_fraction=0.0),
}


def reference(x, spec, positions):
    """What ``apply_rope_table`` was before PR 45."""
    angles = positions.astype(jnp.float32)[..., None] * \
        rope_inverse_frequencies(spec, x.shape[-1])
    cos, sin = (t[..., None, :] * spec.attention_factor
                for t in (jnp.cos(angles), jnp.sin(angles)))
    if cos.ndim == 3:
        cos, sin = cos[None], sin[None]
    rotary = 2 * cos.shape[-1]
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf[..., :rotary], 2, axis=-1)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, xf[..., rotary:]], axis=-1)
    return out.astype(x.dtype)


def _case(kind, per_row, dtype, heads):
    spec = SPECS[kind]
    x = jax.random.normal(jax.random.PRNGKey(heads), (B, S, heads, D), dtype)
    positions = jnp.arange(S)
    if per_row:  # packed sequences: every row its own positions
        positions = jnp.stack([positions, (positions * 7 + 3) % 97])
    return spec, x, positions


def _assert_last_bit(got, want):
    """Equal to a unit in the last place of the output type AT THE SIZE OF
    THE HEAD'S LARGEST VALUE: a lane is the sum of two products of that
    size and may itself be far smaller."""
    assert got.dtype == want.dtype and got.shape == want.shape
    eps = float(jnp.finfo(want.dtype).eps)
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    size = np.abs(want).max(axis=-1, keepdims=True)
    assert (np.abs(got - want) <= 2 * eps * size).all(), \
        (np.abs(got - want) / np.maximum(size, 1e-30)).max() / eps


def cases(test):
    """Tables ``[s, ..]`` and ``[b, s, ..]``, both dtypes, the cell's two
    head counts (its key's and a window layer's query's)."""
    for name, values, ids in (
            ("per_row", [False, True], ["s", "bs"]),
            ("dtype", [jnp.bfloat16, jnp.float32], ["bf16", "f32"]),
            ("heads", [8, 64], None)):
        test = pytest.mark.parametrize(name, values, ids=ids)(test)
    return test


@cases
@pytest.mark.parametrize("kind", ["whole", "half", "none"])
def test_the_rotation_is_the_split_and_concatenate_formula(
        kind, per_row, dtype, heads):
    """``apply_rope_table`` as the model calls it: values to the last bit
    of the output type, the gradient of a weighted sum through the
    ``custom_vjp`` against ``jax.grad`` of the reference."""
    spec, x, positions = _case(kind, per_row, dtype, heads)
    table = rope_table(spec, D, positions)
    if kind == "none":  # nothing rotates: no table, no pass
        assert table is None
        assert apply_rope_table(x, table) is x
        return
    assert table.half == {"whole": D // 2, "half": D // 4}[kind]
    assert table.cos.shape == table.sin.shape == positions.shape + (D,)
    want = reference(x, spec, positions)
    _assert_last_bit(jax.jit(apply_rope_table)(x, table), want)

    w = jax.random.normal(jax.random.PRNGKey(1), x.shape, jnp.float32)

    def loss(fn):
        return lambda x: (fn(x).astype(jnp.float32) * w).sum()

    got = jax.jit(jax.grad(loss(lambda x: apply_rope_table(x, table))))(x)
    ref = jax.grad(loss(lambda x: reference(x, spec, positions)))(x)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    else:
        _assert_last_bit(got, ref)
    # nothing is owed to a table: it comes from integer positions
    zero = jax.grad(lambda t: loss(lambda x: apply_rope_table(x, t))(x))(
        table)
    assert not np.asarray(zero.cos).any() and not np.asarray(zero.sin).any()


@cases
@pytest.mark.parametrize("kind", ["whole", "half"])
def test_the_kernel_interpreted_is_the_formula_and_its_transpose(
        kind, per_row, dtype, heads):
    """``rope_rotate`` in Pallas's interpreter: forward against the
    reference, and ``conj`` against the reference's own transpose."""
    spec, x, positions = _case(kind, per_row, dtype, heads)
    table = rope_table(spec, D, positions)
    want, transpose = jax.vjp(lambda x: reference(x, spec, positions), x)
    got = rope.rope_rotate(x, table.cos, table.sin, table.half,
                           interpret=True)
    _assert_last_bit(got, want)
    g = jax.random.normal(jax.random.PRNGKey(2), x.shape, dtype)
    back = rope.rope_rotate(g, table.cos, table.sin, table.half, True,
                            interpret=True)
    if dtype == jnp.float32:
        np.testing.assert_allclose(back, transpose(g)[0], rtol=1e-5,
                                   atol=1e-5)
    else:
        _assert_last_bit(back, transpose(g)[0])
    # and the oracle the program runs off the chip is the same numbers
    _assert_last_bit(
        rope.rotate_reference(g, table.cos, table.sin, table.half, True),
        back)


def test_the_kernel_refuses_what_it_cannot_tile():
    """Heads of another size than a vector's lanes, or rows that are no
    whole inner step, go to the ``jnp`` form (``kernel_takes``), and the
    kernel says so when called with them."""
    table = rope_table(SPECS["whole"], 64, jnp.arange(32))
    x = jnp.zeros((1, 32, 2, 64), jnp.bfloat16)
    assert not rope.kernel_takes(x, table.cos)
    with pytest.raises(ValueError, match="heads of 128"):
        rope.rope_rotate(x, table.cos, table.sin, table.half)
    table = rope_table(SPECS["whole"], D, jnp.arange(24))
    assert not rope.kernel_takes(jnp.zeros((1, 24, 2, D)), table.cos)
    _assert_last_bit(
        apply_rope_table(jnp.ones((1, 24, 2, D)), table),
        reference(jnp.ones((1, 24, 2, D)), SPECS["whole"], jnp.arange(24)))
