"""The two kernels of ``dlrover_tpu/ops/pallas/ssm.py`` in interpret mode
against the recurrence, at the published head size and state (P = 64, N =
128: two heads a tile of the kept layout) with heads that never decay and heads at e^-30 a token; what the
wrappers promise about slots that do not decode and rows behind the last
real token (inside a chunk and at its edge); decode chained behind chunk;
and the host arithmetic the engine books."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops.pallas import kda, ssm

H, P, N = 8, 64, 128


def _inputs(key, t, rates=(0.0, 0.02, 2.0, 30.0)):
    """``t`` tokens of ``H`` heads: a quarter of the heads at each of
    ``rates`` (x the step: 0 never decays; 30 is gone within a token)."""
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (t, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (t, H)))
    rate = jnp.repeat(jnp.asarray(rates, jnp.float32),
                      -(-H // len(rates)))[:H]
    b = jax.random.normal(ks[2], (t, N))
    c = jax.random.normal(ks[3], (t, N))
    return x, dt, -rate * dt, b, c


def _close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


@pytest.mark.parametrize("n_real", [None, 256, 129, 128, 127, 1, 0])
def test_chunk_kernel_is_the_recurrence(n_real):
    """Two chunks of 128 from a state that is not zero; behind ``n_real``
    nothing changes the state, and a chunk wholly behind it is skipped."""
    x, dt, la, b, c = _inputs(jax.random.PRNGKey(0), 256)
    s0 = jax.random.normal(jax.random.PRNGKey(1), (H, P, N))
    want_y, want_s = ssm.ssm_recurrence(s0, x, dt, la, b, c, n_real)
    y, s = ssm.ssm_chunk_fwd(
        ssm.pack_state(s0), x, dt, la, b, c,
        None if n_real is None else jnp.asarray(n_real, jnp.int32),
        interpret=True)
    assert s.shape == ssm.packed_shape(H, P, N) == (H // 2, N, 2 * P)
    s = ssm.unpack_state(s, P)
    n = 256 if n_real is None else n_real
    _close(s, want_s)
    if n:
        _close(y[:n], want_y[:n])
    if n_real == 0:
        assert jnp.array_equal(s, s0)


def test_chunk_kernel_never_exponentiates_a_positive_sum():
    """Every head at e^-30 a token or faster: ``exp(+cumsum)`` over a
    chunk is e^3840, far past float32, and the kernel's answer is finite
    and the recurrence's."""
    x, dt, la, b, c = _inputs(jax.random.PRNGKey(2), 128, rates=(30.0,))
    la = jnp.minimum(la, -30.0)
    s0 = jax.random.normal(jax.random.PRNGKey(3), (H, P, N))
    want_y, want_s = ssm.ssm_recurrence(s0, x, dt, la, b, c)
    y, s = ssm.ssm_chunk_fwd(ssm.pack_state(s0), x, dt, la, b, c,
                             interpret=True)
    assert bool(jnp.all(jnp.isfinite(y))) and bool(jnp.all(jnp.isfinite(s)))
    _close(y, want_y)
    _close(ssm.unpack_state(s, P), want_s)


def test_chunk_kernel_with_no_decay_sums_every_token():
    """No decay at all: the state is the plain sum of the tokens' outer
    products, 256 of them, on top of the one it started from."""
    x, dt, la, b, c = _inputs(jax.random.PRNGKey(4), 256, rates=(0.0,))
    s0 = jax.random.normal(jax.random.PRNGKey(5), (H, P, N))
    want = s0 + jnp.einsum("thp,tn->hpn", dt[..., None] * x, b,
                           precision="highest")
    _, s = ssm.ssm_chunk_fwd(ssm.pack_state(s0), x, dt, la, b, c,
                             interpret=True)
    _close(ssm.unpack_state(s, P), want)


@pytest.mark.parametrize("active", [
    [True, False, True, True, False],
    [False, False, False, False, True],
    [True] * 5,
    [False] * 5,
])
def test_decode_kernel_is_one_step_for_the_active_slots(active):
    """An active slot's state takes one step of the recurrence (B and C
    the slot's own, shared by its heads); an inactive slot's state comes
    back bit for bit and its ``y`` is zeros."""
    n = len(active)
    x, dt, la, b, c = _inputs(jax.random.PRNGKey(6), n)
    state = jax.random.normal(jax.random.PRNGKey(7), (n, H, P, N))
    on = jnp.asarray(active)
    want_y, want_s = ssm.ssm_step(state, x, dt, la, b, c)
    y, s = ssm.ssm_decode_step(ssm.pack_state(state), x, dt, la, b, c, on,
                               interpret=True)
    s = ssm.unpack_state(s, P)
    for i, a in enumerate(active):
        if a:
            _close(y[i], want_y[i], 1e-5)
            _close(s[i], want_s[i], 1e-5)
        else:
            assert jnp.array_equal(s[i], state[i])
            assert not bool(jnp.any(y[i]))


def test_a_chunk_then_decode_steps_are_one_sequence():
    """A prompt chunk through the chunk kernel (its last 28 rows padding)
    and then token by token through the decode kernel is the recurrence
    over the whole sequence: the two kernels keep the state in one
    layout."""
    x, dt, la, b, c = _inputs(jax.random.PRNGKey(8), 100 + 5)
    want_y, want_s = ssm.ssm_recurrence(
        jnp.zeros((H, P, N)), x, dt, la, b, c)

    def padded(a):
        return jnp.concatenate(
            [a[:100], jnp.ones((28,) + a.shape[1:], a.dtype)])

    y, s = ssm.ssm_chunk_fwd(
        jnp.zeros(ssm.packed_shape(H, P, N)), *map(padded, (x, dt, la, b, c)),
        jnp.asarray(100, jnp.int32), interpret=True)
    _close(y[:100], want_y[:100])
    state, on = s[None], jnp.ones((1,), bool)
    for t in range(100, 105):
        y, state = ssm.ssm_decode_step(
            state, x[t][None], dt[t][None], la[t][None], b[t][None],
            c[t][None], on, interpret=True)
        _close(y[0], want_y[t], 1e-5)
    _close(ssm.unpack_state(state[0], P), want_s)


def test_the_layout_round_trips_and_packs_what_fits():
    """Two heads of 64 channels a 128-lane tile; a head of 128 alone; as
    many as divide the heads where more would fit."""
    assert ssm.packed_shape(128, 64, 128) == (64, 128, 128)
    assert ssm.packed_shape(8, 128, 64) == (8, 64, 128)
    assert ssm.packed_shape(4, 8, 16) == (1, 16, 32)
    assert ssm.packed_shape(6, 8, 16) == (1, 16, 48)
    s = jax.random.normal(jax.random.PRNGKey(9), (3, H, P, N))
    packed = ssm.pack_state(s)
    assert packed.shape == (3,) + ssm.packed_shape(H, P, N)
    assert jnp.array_equal(ssm.unpack_state(packed, P), s)
    # head 1's channel 5 and state row 7 sit on tile 0, row 7, lane 64 + 5
    assert packed[2, 0, 7, P + 5] == s[2, 1, 5, 7]


def test_the_books_count_what_the_kernels_walk():
    """The books' host arithmetic is ``ops/pallas/kda.py``'s for both
    kinds of state a slot, at this kernel's chunk."""
    assert kda.decode_states_walked(np.array([True, False, True])) == 2
    # runs of 512 rows: whole 128-token chunks up to the last real token
    assert kda.chunk_rows([512, 129, 128, 1, 0], 512, ssm.CHUNK) == (
        770, 512 + 256 + 128 + 128)
