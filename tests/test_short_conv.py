"""The gated short convolution (``models/llama.py ShortConv`` and
``ops/pallas/short_conv.py``: ``causal_depthwise_conv``, the ``jnp`` form,
and the kernel pair in Pallas's interpreter), the first mixer
``LlamaModel`` trains that is not attention, against a token-by-token
loop: forward and gradient in float32 to 1e-6, causality, the zeros ahead
of a sequence and of a segment, the taps' order, bf16 in and float32 sums,
and which form ``ShortConv`` takes."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.llama import LlamaConfig, ShortConv
from dlrover_tpu.ops.pallas import short_conv
from dlrover_tpu.ops.pallas.short_conv import causal_depthwise_conv

TOL = 1e-6
B, S, C, K = 2, 12, 8, 3


def _loop_conv(v, taps, segment_ids=None):
    """out[b, t] = sum_j taps[j] * v[b, t - (K - 1 - j)], one token at a
    time; a position ahead of the row, or of another segment, adds 0."""
    v, taps = np.asarray(v, np.float64), np.asarray(taps, np.float64)
    out = np.zeros_like(v)
    k = taps.shape[0]
    for b in range(v.shape[0]):
        for t in range(v.shape[1]):
            for j in range(k):
                src = t - (k - 1 - j)
                if src < 0:
                    continue
                if segment_ids is not None and \
                        segment_ids[b, src] != segment_ids[b, t]:
                    continue
                out[b, t] += taps[j] * v[b, src]
    return out


def _loop_block(params, x, segment_ids=None):
    """The whole block a token at a time, in float64."""
    w_in = np.asarray(params["in_proj"]["kernel"], np.float64)   # [E, 3, E]
    w_out = np.asarray(params["out_proj"]["kernel"], np.float64)
    x = np.asarray(x, np.float64)
    bcu = np.einsum("bse,ejc->jbsc", x, w_in)
    conv = _loop_conv(bcu[0] * bcu[2], params["taps"], segment_ids)
    return (bcu[1] * conv) @ w_out


@pytest.fixture(scope="module")
def data():
    v = jax.random.normal(jax.random.PRNGKey(0), (B, S, C), jnp.float32)
    taps = jax.random.normal(jax.random.PRNGKey(1), (K, C), jnp.float32)
    return v, taps


@pytest.fixture(scope="module")
def block():
    cfg = LlamaConfig.tiny(hidden_size=C, conv_taps=K, dtype=jnp.float32)
    module = ShortConv(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S, C), jnp.float32)
    params = nn.meta.unbox(module.init(jax.random.PRNGKey(3), x))["params"]
    return module, params, x


def test_conv_matches_a_token_by_token_loop(data):
    v, taps = data
    np.testing.assert_allclose(
        causal_depthwise_conv(v, taps), _loop_conv(v, taps), atol=TOL)


def test_conv_gradient_matches_the_loops(data):
    """The gradient of ``sum(conv * g)``: to ``v`` it is the convolution
    run the other way over ``g``, to the taps the shifted products."""
    v, taps = data
    g = jax.random.normal(jax.random.PRNGKey(4), (B, S, C), jnp.float32)
    dv, dtaps = jax.grad(
        lambda v, taps: jnp.sum(causal_depthwise_conv(v, taps) * g),
        argnums=(0, 1))(v, taps)
    v64, g64, t64 = (np.asarray(a, np.float64) for a in (v, g, taps))
    want_dv, want_dtaps = np.zeros_like(v64), np.zeros_like(t64)
    for t in range(S):
        for j in range(K):
            src = t - (K - 1 - j)
            if src >= 0:
                want_dv[:, src] += t64[j] * g64[:, t]
                want_dtaps[j] += (v64[:, src] * g64[:, t]).sum(0)
    np.testing.assert_allclose(dv, want_dv, atol=TOL)
    np.testing.assert_allclose(dtaps, want_dtaps, atol=10 * TOL)


def test_a_token_changes_no_output_before_it(data):
    v, taps = data
    t = 5
    moved = v.at[:, t].add(1.0)
    a, b = causal_depthwise_conv(v, taps), causal_depthwise_conv(moved, taps)
    assert np.array_equal(a[:, :t], b[:, :t])
    # ... and reaches K positions: itself and the K - 1 behind it
    changed = np.abs(np.asarray(a - b)).max(axis=(0, 2)) > 0
    assert changed.tolist() == [t <= i < t + K for i in range(S)]


def test_zeros_stand_ahead_of_a_sequence_and_of_a_segment(data):
    v, taps = data
    out = causal_depthwise_conv(v, taps)
    # the first token sees itself only, the second itself and one behind
    np.testing.assert_allclose(out[:, 0], taps[K - 1] * v[:, 0], atol=TOL)
    np.testing.assert_allclose(
        out[:, 1], taps[K - 1] * v[:, 1] + taps[K - 2] * v[:, 0], atol=TOL)
    # a packed row: each document is convolved as if it stood alone
    segments = jnp.asarray([[0] * 5 + [1] * 7, [0] * 12])
    packed = causal_depthwise_conv(v, taps, segments)
    np.testing.assert_allclose(
        packed, _loop_conv(v, taps, np.asarray(segments)), atol=TOL)
    np.testing.assert_allclose(
        packed[0, 5:], causal_depthwise_conv(v[:1, 5:], taps)[0], atol=TOL)
    np.testing.assert_allclose(packed[1], out[1], atol=TOL)
    assert not np.allclose(packed[0, 5:7], out[0, 5:7])


def test_the_oldest_positions_tap_is_first():
    v = jnp.zeros((1, 6, 1)).at[0, 2, 0].set(1.0)
    taps = jnp.asarray([[100.0], [10.0], [1.0]])
    out = causal_depthwise_conv(v, taps)[0, :, 0]
    # an impulse at 2 shows under the current tap at 2, the oldest at 4
    assert out.tolist() == [0.0, 0.0, 1.0, 10.0, 100.0, 0.0]


def test_bf16_comes_in_and_the_sums_are_float32(data):
    v, taps = data
    out = causal_depthwise_conv(v.astype(jnp.bfloat16),
                                taps.astype(jnp.bfloat16))
    assert out.dtype == jnp.float32
    want = _loop_conv(v.astype(jnp.bfloat16).astype(jnp.float32),
                      taps.astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_allclose(out, want, atol=TOL)


@pytest.mark.parametrize("packed", [False, True])
def test_block_matches_the_loop_forward_and_gradient(block, packed):
    module, params, x = block
    assert jax.tree_util.tree_map(lambda a: a.shape, params) == {
        "in_proj": {"kernel": (C, 3, C)}, "out_proj": {"kernel": (C, C)},
        "taps": (K, C)}
    segments = jnp.asarray([[0] * 4 + [1] * 8, [0] * 9 + [1] * 3]) \
        if packed else None
    got = module.apply({"params": params}, x, segments)
    want = _loop_block(params, x,
                       None if segments is None else np.asarray(segments))
    np.testing.assert_allclose(got, want, atol=TOL)
    # the gradient of a seeded projection of the output, by differences of
    # the float64 loop along a seeded direction
    g = np.asarray(jax.random.normal(jax.random.PRNGKey(5), got.shape))
    grads, dx = jax.grad(
        lambda p, x: jnp.sum(module.apply({"params": p}, x, segments) * g),
        argnums=(0, 1))(params, x)
    seg = None if segments is None else np.asarray(segments)
    direction = jax.tree_util.tree_map(
        lambda a: np.asarray(jax.random.normal(
            jax.random.PRNGKey(a.size), a.shape), np.float64), params)
    dirx = np.asarray(jax.random.normal(jax.random.PRNGKey(6), x.shape),
                      np.float64)

    def at(eps):
        p = jax.tree_util.tree_map(
            lambda a, d: np.asarray(a, np.float64) + eps * d, params,
            direction)
        return float((_loop_block(p, np.asarray(x, np.float64) + eps * dirx,
                                  seg) * g).sum())

    h = 1e-4
    numeric = (at(h) - at(-h)) / (2 * h)
    analytic = sum(
        float((np.asarray(a, np.float64) * d).sum())
        for a, d in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(direction))) + float(
            (np.asarray(dx, np.float64) * dirx).sum())
    assert abs(numeric - analytic) < 1e-5 * max(1.0, abs(numeric))


def test_the_taps_start_as_a_pass_through_of_the_current_token(block):
    _, params, _ = block
    taps = np.asarray(params["taps"])
    wide = np.asarray(ShortConv(LlamaConfig.tiny(
        hidden_size=512, dtype=jnp.float32)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4, 512)))["params"][
                "taps"].unbox())
    assert taps.shape == (K, C) and wide.shape == (3, 512)
    # N(0, 1/3), with 1 added to the current position's tap (the last)
    assert abs(wide[:2].mean()) < 0.05 and abs(wide[2].mean() - 1) < 0.08
    assert abs(wide.var(axis=1).mean() - 1 / 3) < 0.05


# --- the kernel pair (``gated_conv_fwd`` / ``gated_conv_bwd``) in the
# interpreter: 192 rows are three tiles of 64, each two inner steps of 32,
# so the taps reach across a tile's edge and an inner step's, both ways


def _planes(k, s, h, dtype, b=2):
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    bcu = jax.random.normal(keys[0], (b, 3, s, h), jnp.float32)
    taps = jax.random.normal(keys[1], (k, h), jnp.float32) * k ** -0.5
    g = jax.random.normal(keys[2], (b, s, h), jnp.float32)
    return bcu.astype(dtype), taps.astype(dtype), g.astype(dtype)


def _loop_gated(bcu, taps, g):
    """``y = C * conv(B * u)`` and the gradients of ``sum(y * g)``, a
    token and tap at a time in float64."""
    bcu, taps, g = (np.asarray(a.astype(jnp.float32), np.float64)
                    for a in (bcu, taps, g))
    (b, c, u), k, s = (bcu[:, i] for i in range(3)), taps.shape[0], g.shape[1]
    v = b * u
    conv = _loop_conv(v, taps)
    dconv, dv, dtaps = g * c, np.zeros_like(v), np.zeros_like(taps)
    for t in range(s):
        for j in range(k):
            src = t - (k - 1 - j)
            if src >= 0:
                dv[:, src] += taps[j] * dconv[:, t]
                dtaps[j] += (v[:, src] * dconv[:, t]).sum(0)
    return c * conv, np.stack([dv * u, g * conv, dv * b], 1), dtaps


def _forms(bcu, taps, g):
    """(y, d bcu, d taps) of the kernel pair and of the ``jnp`` form."""
    def of(fn):
        y, vjp = jax.vjp(fn, bcu, taps)
        return (y,) + vjp(g)

    return (of(lambda x, w: short_conv.gated_short_conv(x, w, True)),
            of(short_conv.gated_conv_reference))


@pytest.mark.parametrize("k,h,dtype", [
    (3, 256, jnp.float32), (4, 256, jnp.float32), (3, 384, jnp.float32),
    (3, 256, jnp.bfloat16)],
    ids=["three-taps", "four-taps", "three-channel-blocks", "bf16"])
def test_kernel_pair_matches_the_loop_and_the_jnp_form(k, h, dtype):
    """Forward and the gradients of ``B``, ``C``, ``u`` and the taps.
    bf16 comes in and goes out (the taps' gradient in the taps' dtype), the
    sums are float32: one rounding away from the float64 loop."""
    bcu, taps, g = _planes(k, 192, h, dtype)
    assert short_conv.kernel_takes(bcu, taps)
    assert short_conv._blocks(192, h, 7, bcu.dtype.itemsize) == (
        64, {256: 256, 384: 128}[h])
    kernel, form = _forms(bcu, taps, g)
    want = _loop_gated(bcu, taps, g)
    one_rounding = 2.0 ** -8 if dtype == jnp.bfloat16 else 4e-6
    for got, ref, loop in zip(kernel, form, want):
        assert got.dtype == ref.dtype == dtype and got.shape == loop.shape
        scale = np.abs(loop).max()
        np.testing.assert_allclose(
            np.asarray(got, np.float64), loop, atol=one_rounding * scale)
        np.testing.assert_allclose(
            np.asarray(got, np.float64), np.asarray(ref, np.float64),
            atol=one_rounding * scale)


def test_kernel_a_token_changes_no_output_before_it_and_zeros_lead():
    bcu, taps, _ = _planes(K, 192, 128, jnp.float32, b=1)
    fwd = functools.partial(short_conv.gated_conv_fwd, interpret=True)
    a = fwd(bcu, taps)
    # the first row of the second tile: its reach crosses the tile's edge
    t = 64
    b = fwd(bcu.at[:, 2, t].add(1.0), taps)
    assert np.array_equal(a[:, :t], b[:, :t])
    changed = np.abs(np.asarray(a - b)).max(axis=(0, 2)) > 0
    assert changed.tolist() == [t <= i < t + K for i in range(192)]
    # zeros ahead of row 0: the first token sees itself only
    v = bcu[:, 0] * bcu[:, 2]
    np.testing.assert_allclose(
        a[:, 0], bcu[:, 1, 0] * (taps[K - 1] * v[:, 0]), rtol=1e-6)
    np.testing.assert_allclose(
        a[:, 1], bcu[:, 1, 1] * (taps[K - 1] * v[:, 1]
                                 + taps[K - 2] * v[:, 0]), rtol=1e-5)


@pytest.mark.parametrize("bcu,taps", [
    ((2, 3, 64, 200), (3, 200)), ((2, 3, 100, 128), (3, 128)),
    ((2, 3, 64, 128), (10, 128)), ((2, 64, 384), (3, 128))],
    ids=["odd-channels", "ragged-sequence", "taps-beyond-a-halo",
         "rows-not-planes"])
def test_kernel_takes_refuses(bcu, taps):
    bcu, taps = jnp.zeros(bcu), jnp.zeros(taps)
    assert not short_conv.kernel_takes(bcu, taps)
    with pytest.raises(ValueError, match="gated convolution kernels take"):
        short_conv.gated_conv_fwd(bcu, taps)


@pytest.mark.parametrize("case", ["plain", "packed", "mesh", "odd-shapes"])
def test_block_takes_the_kernel_where_it_may(monkeypatch, case):
    """On a TPU ``ShortConv`` hands whole tiles of an unpacked sequence
    on one device to the kernel pair; a packed row, a mesh of several
    devices and shapes the kernels refuse take the ``jnp`` form, with the
    same numbers."""
    s, h = (12, C) if case == "odd-shapes" else (64, 128)
    module = ShortConv(LlamaConfig.tiny(
        hidden_size=h, conv_taps=K, dtype=jnp.float32))
    x = jax.random.normal(jax.random.PRNGKey(2), (B, s, h), jnp.float32)
    params = nn.meta.unbox(module.init(jax.random.PRNGKey(3), x))["params"]
    segments = jnp.asarray([[0] * 20 + [1] * 44] * B) \
        if case == "packed" else None

    def grads():
        return jax.value_and_grad(lambda p: jnp.sum(
            module.apply({"params": p}, x, segments) ** 2))(params)

    want = grads()
    calls = []

    def kernel(bcu, taps):
        calls.append(bcu.shape)
        return real(bcu, taps, True)

    real = short_conv.gated_short_conv
    monkeypatch.setattr(short_conv, "gated_short_conv", kernel)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if case == "mesh":
        from dlrover_tpu.accel.parallel.mesh import MeshSpec

        with MeshSpec(dp=2).build_mesh(jax.devices()[:2]):
            got = grads()
    else:
        got = grads()
    assert calls == ([(B, 3, s, h)] if case == "plain" else [])
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=4e-6 * np.abs(b).max())
