"""The two kernels of ``dlrover_tpu/ops/pallas/kda.py`` in interpret mode
against the recurrence, at the published head size (128) with channels
that decay to ~0 within a chunk, channels that hardly decay and channels
that do not decay at all; what the wrappers promise about slots that do
not decode and rows behind the last real token; and the host arithmetic
the engine books."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.pallas import kda

H, D = 2, 128


def _inputs(key, t, rates=(0.0, 0.02, 12.0)):
    """``t`` tokens of ``H`` heads: q scaled and normed, k normed, a third
    of the channels at each of ``rates`` (x softplus of a normal: 0 never
    decays; 12 is e^-8 a token, gone within a few)."""
    ks = jax.random.split(key, 5)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (t, H, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (t, H, D)))
    v = jax.random.normal(ks[2], (t, H, D))
    rate = jnp.repeat(jnp.asarray(rates, jnp.float32), -(-D // len(rates)))[:D]
    g = -rate * jax.nn.softplus(jax.random.normal(ks[3], (t, H, D)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (t, H)))
    return q, k, v, g, beta


def _close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


@pytest.mark.parametrize("n_real", [None, 128, 65, 64, 63, 1, 0])
def test_chunk_kernel_is_the_recurrence(n_real):
    """Two chunks of 64 from a state that is not zero; behind ``n_real``
    nothing changes the state, and a chunk wholly behind it is skipped."""
    q, k, v, g, beta = _inputs(jax.random.PRNGKey(0), 128)
    s0 = jax.random.normal(jax.random.PRNGKey(1), (H, D, D))
    want_o, want_s = kda.kda_recurrence(s0, q, k, v, g, beta, n_real)
    o, s = kda.kda_chunk_fwd(s0, q, k, v, g, beta, n_real, interpret=True)
    n = 128 if n_real is None else n_real
    _close(s, want_s)
    if n:
        _close(o[:n], want_o[:n])
    if n_real == 0:
        assert jnp.array_equal(s, s0)


def test_chunk_kernel_never_exponentiates_a_positive_sum():
    """Every channel at e^-30 a token: ``exp(+cumsum)`` over a chunk is
    e^1920, far past float32, and the kernel's answer is finite and the
    recurrence's."""
    q, k, v, g, beta = _inputs(jax.random.PRNGKey(2), 64, rates=(30.0,))
    g = jnp.minimum(g, -30.0)
    s0 = jnp.zeros((H, D, D))
    want_o, want_s = kda.kda_recurrence(s0, q, k, v, g, beta)
    o, s = kda.kda_chunk_fwd(s0, q, k, v, g, beta, interpret=True)
    assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(s)))
    _close(o, want_o)
    _close(s, want_s)


def test_chunk_kernel_with_no_decay_and_equal_keys():
    """No decay at all and every key the same: (I + A) is the all-ones
    lower triangle, whose inverse the doubling has to build exactly."""
    q, k, v, g, beta = _inputs(jax.random.PRNGKey(3), 64, rates=(0.0,))
    k = jnp.broadcast_to(k[:1], k.shape)
    s0 = jnp.zeros((H, D, D))
    want_o, want_s = kda.kda_recurrence(s0, q, k, v, g, beta)
    o, s = kda.kda_chunk_fwd(s0, q, k, v, g, beta, interpret=True)
    _close(o, want_o, 1e-4)
    _close(s, want_s, 1e-4)


@pytest.mark.parametrize("active", [
    [True, False, True, True, False],
    [False, False, False, False, True],
    [True] * 5,
    [False] * 5,
])
def test_decode_kernel_is_one_step_for_the_active_slots(active):
    """An active slot's state takes one step of the recurrence; an
    inactive slot's state comes back bit for bit and its ``o`` is zeros."""
    b = len(active)
    q, k, v, g, beta = _inputs(jax.random.PRNGKey(4), b)
    state = jax.random.normal(jax.random.PRNGKey(5), (b, H, D, D))
    on = jnp.asarray(active)
    want_o, want_s = kda.kda_step(state, q, k, v, g, beta)
    o, s = kda.kda_decode_step(state + 0.0, q, k, v, g, beta, on,
                               interpret=True)
    for i, a in enumerate(active):
        if a:
            _close(o[i], want_o[i], 1e-5)
            _close(s[i], want_s[i], 1e-5)
        else:
            assert jnp.array_equal(s[i], state[i])
            assert not bool(jnp.any(o[i]))


def test_decode_steps_then_a_chunk_are_one_sequence():
    """Token by token through the decode kernel and then a chunk through
    the chunk kernel is the recurrence over the whole sequence: the two
    kernels keep the state in one layout."""
    q, k, v, g, beta = _inputs(jax.random.PRNGKey(6), 5 + 64)
    want_o, want_s = kda.kda_recurrence(
        jnp.zeros((H, D, D)), q, k, v, g, beta)
    state = jnp.zeros((1, H, D, D))
    on = jnp.ones((1,), bool)
    for t in range(5):
        o, state = kda.kda_decode_step(
            state, q[t][None], k[t][None], v[t][None], g[t][None],
            beta[t][None], on, interpret=True)
        _close(o[0], want_o[t], 1e-5)
    o, s = kda.kda_chunk_fwd(state[0], q[5:], k[5:], v[5:], g[5:], beta[5:],
                             interpret=True)
    _close(o, want_o[5:])
    _close(s, want_s)


def test_the_books_count_what_the_kernels_walk():
    assert kda.decode_states_walked(np.array([True, False, True])) == 2
    # runs of 512 rows: whole 64-token chunks up to the last real token
    assert kda.chunk_rows([512, 65, 64, 1, 0], 512) == (642, 512 + 128
                                                        + 64 + 64)
    # the recurrence walks a program's every row
    assert kda.chunk_rows([10, 3], 16, 16) == (13, 32)
