"""The training layer's compact sorted buffer (``models/moe.py MoEMLP``
with ``experts_held``): ``buffer_rows`` rows that hold the picks on held
experts alone, the rows behind it walked a segment at a time behind ONE
``cond`` each way.  The layer against ITSELF with the buffer of every pick
(the parent's path) under routings FORCED to an exact number of held
picks, on the CPU with the grouped matmuls in Pallas's interpreter; a
token's row gradient summed in float32 across buffer and overflow; the
counter of the layers that overflowed; the rule the served layer shares;
and the jaxpr of forward and backward, which calls no kernel behind the
``cond`` and holds no array of ``T x top_k`` rows."""

import contextlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import moe
from dlrover_tpu.models.moe import MoEMLP, buffer_rows, route, routing_stats
from dlrover_tpu.ops.fp8 import fake_quant_fp8, grad_quant_fp8

M, W, PICKS, BOUND = 40, 48, 2048, 768
LEAVES = ("x", "router", "w_gate", "w_up", "w_down")
# the picks a routing is forced to, by the buffers they fill: half of it,
# ALL of it to the row, 1.4 (the buffer and six segments of three experts)
# and every pick of every token (2 048 of 768: 2.7)
ROUTINGS = {"0.5": 384, "1.0": 768, "1.4": 1075, "2.7": PICKS}
# top 4 of 16 by softmax, top 8 of 32 by sigmoid with a selection bias: the
# two claimed cells' routers; a share holds ``top_k`` experts from the
# fifth on, so that a token can put every pick on it
ROUTERS = {"top4-softmax": dict(top_k=4, score_fn="softmax"),
           "top8-sigmoid-bias": dict(top_k=8, score_fn="sigmoid",
                                     select_bias=True, select_bias_std=0.02,
                                     norm_topk_prob=True)}


def layer(dtype, top_k, fp8=False, **router):
    return MoEMLP(hidden_size=M, intermediate_size=W, num_experts=4 * top_k,
                  top_k=top_k, experts_held=(4, top_k), dtype=dtype,
                  param_dtype=jnp.float32, fp8=fp8, per_expert_init=True,
                  **router)


def inputs(mlp, held_picks, seed=0):
    """``(params, x)`` whose routing puts exactly ``held_picks`` picks on
    the share.  Feature 0 of ``x`` is 1 on every token and its row of the
    router pushes the held experts OUT of every token's choice; feature 1
    marks the tokens that choose all of them, feature 2 those that choose
    the share's second expert alone (so the groups are uneven)."""
    k = mlp.top_k
    tokens, (first, held) = PICKS // k, mlp.experts_held
    singles = 0 if held_picks == PICKS else held_picks % k + 6 * k
    on_all = (held_picks - singles) // k
    assert on_all * k + singles == held_picks and on_all + singles <= tokens
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(seed),
                                (2, tokens // 2, M), jnp.float32)
    mark = np.zeros((tokens, 3), np.float32)
    mark[:, 0] = 1.0
    where = np.random.RandomState(seed).permutation(tokens)
    mark[where[:on_all], 1] = 1.0
    mark[where[on_all:on_all + singles], 2] = 1.0
    x = x.at[..., :3].set(mark.reshape(2, tokens // 2, 3))
    params = nn.meta.unbox(mlp.init(jax.random.PRNGKey(seed + 1), x))[
        "params"]
    kernel = params["router"]["kernel"].at[:3].set(0.0)
    kernel = kernel.at[0, first:first + held].set(-8.0)
    kernel = kernel.at[1, first:first + held].set(16.0)
    kernel = kernel.at[2, first + 1].set(16.0)
    return {**params, "router": {"kernel": kernel}}, x


@contextlib.contextmanager
def buffer_of(every_pick):
    """With ``every_pick``, the bound taken away while a layer is traced:
    the parent's buffer of every pick and no ``cond``."""
    rule = moe.buffer_rows
    if every_pick:
        moe.buffer_rows = lambda picks, held, experts: picks
    try:
        yield
    finally:
        moe.buffer_rows = rule


def value_and_grads(mlp, params, x, every_pick=False, as_written=False):
    """``(y, {leaf: gradient}, the sown collection)`` under a cotangent
    that tells every element of ``y`` apart; ``every_pick`` as
    :func:`buffer_of` has it; ``as_written`` has the compiler round where
    the program says (by default it keeps float32 through a fused bfloat16
    chain)."""
    def scalar(params, x):
        with buffer_of(every_pick):
            y, sown = mlp.apply({"params": params}, x,
                                mutable=["moe_losses"])
        mix = jnp.cos(jnp.arange(y.size, dtype=jnp.float32)).reshape(y.shape)
        return jnp.sum(y.astype(jnp.float32) * mix), (y, sown["moe_losses"])

    step = jax.jit(jax.grad(scalar, argnums=(0, 1), has_aux=True))
    if as_written:
        step = step.lower(params, x).compile(
            compiler_options={"xla_allow_excess_precision": False})
    (g_params, g_x), (y, sown) = step(params, x)
    grads = {"x": g_x, "router": g_params["router"]["kernel"],
             **{name: g_params[name] for name in LEAVES[2:]}}
    return y, grads, sown


def plain_fp8(mlp, params, x):
    """``(y, {leaf: gradient})`` of the fp8 layer as a loop over the held
    experts, each over the rows of the tokens that picked it, the results
    weighted and added to their tokens in float32: every tensor that fp8
    scales holds the rows of held picks and NOTHING else.  Which token
    picked which expert is read once, from these parameters; the weights
    are ``route``'s, differentiably."""
    k, dtype, (first, held) = mlp.top_k, mlp.dtype, mlp.experts_held

    def routed(params, x):
        logits = x.reshape(-1, M) @ params["router"]["kernel"]
        return route(logits, k, "softmax", True, 1.0)

    top_e = np.asarray(routed(params, x)[1])
    groups = [np.argwhere(top_e == first + e) for e in range(held)]
    token = np.concatenate([g[:, 0] for g in groups])
    choice = np.concatenate([g[:, 1] for g in groups])
    ends = np.cumsum([len(g) for g in groups])
    starts = ends - [len(g) for g in groups]

    def matmuls(rows, w):
        w = fake_quant_fp8(w.astype(dtype))
        return grad_quant_fp8(jnp.concatenate([
            jnp.dot(rows[lo:hi], w[e],
                    preferred_element_type=jnp.float32).astype(dtype)
            for e, (lo, hi) in enumerate(zip(starts, ends))]))

    def scalar(params, x):
        top_p = routed(params, x)[0]
        xs = fake_quant_fp8(x.reshape(-1, M).astype(dtype)[token])
        act = nn.silu(matmuls(xs, params["w_gate"])) * matmuls(
            xs, params["w_up"])
        out = matmuls(fake_quant_fp8(act), params["w_down"])
        y = jnp.zeros((x.shape[0] * x.shape[1], M), jnp.float32).at[
            token].add(out.astype(jnp.float32)
                       * top_p[token, choice][:, None])
        y = y.astype(dtype).reshape(x.shape)
        mix = jnp.cos(jnp.arange(y.size, dtype=jnp.float32)).reshape(y.shape)
        return jnp.sum(y.astype(jnp.float32) * mix), y

    (g_params, g_x), y = jax.jit(jax.grad(
        scalar, argnums=(0, 1), has_aux=True)).lower(params, x).compile(
            compiler_options={"xla_allow_excess_precision": False})(params, x)
    return y, {"x": g_x, "router": g_params["router"]["kernel"],
               **{name: g_params[name] for name in LEAVES[2:]}}


def close(got, want, dtype, fp8, what):
    """Float32 within 1e-5 of the leaf's RMS.  bfloat16 to one rounding:
    ``gate``, ``up`` and each result are rounded to 8 bits, and a grouped
    matmul and a plain one do not sum in the same order, so a few elements
    round the other way and what is computed FROM them moves by that.
    fp8 turns such a step into one of e4m3 (an eighth of the value) or
    e5m2 (a quarter), one element in thousands, and behind the buffer a
    SEGMENT's rows and gradients take a scale of their own, another grid
    than the whole tensor's: up to a tenth of the RMS in the mean (two
    grids' noise; 0.2 % while the buffer holds every held pick), a whole
    RMS where a large element flips.  A dropped
    or doubled pick moves a token's row, and a leaf's gradient, by tenths
    of the RMS in the MEAN."""
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    assert got.shape == want.shape, what
    rms = float(np.sqrt(np.mean(want ** 2)))
    assert rms > 0, what
    err = np.abs(got - want) / rms
    if dtype == jnp.float32 and not fp8:
        assert err.max() < 1e-5, (what, float(err.max()))
    else:
        mean, worst = (0.12, 2.0) if fp8 else (2.0 ** -8, 0.06)
        assert np.sqrt(np.mean(err ** 2)) < mean and err.max() < worst, \
            (what, float(np.sqrt(np.mean(err ** 2))), float(err.max()))


def compare(mlp, held_picks):
    params, x = inputs(mlp, held_picks)
    assert buffer_rows(PICKS, mlp.top_k, mlp.num_experts) == BOUND
    y, grads, sown = value_and_grads(mlp, params, x)
    y_all, grads_all, sown_all = value_and_grads(mlp, params, x,
                                                 every_pick=True)
    # the routing is what was forced, and the counter says so
    assert int(sown["held_counts"].sum()) == held_picks
    assert len(set(np.asarray(sown["held_counts"]).tolist())) > (
        held_picks < PICKS)
    overflowed = int(held_picks > BOUND)
    assert int(sown["overflowed"]) == overflowed
    stats = routing_stats(sown)
    assert float(stats["moe_overflow_layers"]) == overflowed
    assert float(stats["moe_picks_held"]) == held_picks
    assert "overflowed" not in sown_all
    assert float(routing_stats(sown_all)["moe_overflow_layers"]) == 0.0
    assert y.dtype == mlp.dtype and y.shape == x.shape
    close(y, y_all, mlp.dtype, False, "y")
    for name in LEAVES:
        assert float(jnp.abs(grads_all[name]).max()) > 0, name
        close(grads[name], grads_all[name], mlp.dtype, False, name)


@pytest.mark.parametrize("router", sorted(ROUTERS))
@pytest.mark.parametrize("buffers", sorted(ROUTINGS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_compact_buffer_is_the_buffer_of_every_pick(dtype, buffers,
                                                        router):
    """Value and every gradient leaf against the buffer of every pick,
    with the buffer half full, full to the row, overflowing by 0.4 of
    itself (a second segment of an expert's rows, an expert split between
    buffer and overflow) and holding every pick of every token."""
    compare(layer(dtype, **ROUTERS[router]), ROUTINGS[buffers])


@pytest.mark.parametrize("buffers", ["0.5", "1.4", "2.7"])
def test_fp8_scales_over_rows_that_hold_a_pick(buffers):
    """fp8's scale is a tensor's largest entry: never one of a row that
    no matmul wrote.  ``gate`` and ``up`` are zeroed behind the held
    groups, so ``act`` forward AND their gradients backward are scaled
    over held picks alone (the buffer of every pick zeroes ``act``, and
    where the kernels leave NaN behind the groups its gradients' scale
    falls back to 1: it is no reference here).  Against a loop over the
    held experts whose tensors hold nothing but held picks' rows: while
    the buffer holds every held pick, the same grids."""
    mlp = layer(jnp.bfloat16, fp8=True, **ROUTERS["top4-softmax"])
    params, x = inputs(mlp, ROUTINGS[buffers])
    y, grads, sown = value_and_grads(mlp, params, x, as_written=True)
    assert int(sown["held_counts"].sum()) == ROUTINGS[buffers]
    y_loop, grads_loop = plain_fp8(mlp, params, x)
    assert all(bool(jnp.isfinite(g).all()) for g in grads.values())
    close(y, y_loop, mlp.dtype, True, "y")
    for name in LEAVES:
        close(grads[name], grads_loop[name], mlp.dtype, True, name)
        if buffers == "0.5":
            close(grads[name], grads_loop[name], mlp.dtype, False, name)


def _row_gradient_error(monkeypatch, twice_rounded):
    mlp = layer(jnp.bfloat16, **ROUTERS["top8-sigmoid-bias"])
    params, x = inputs(mlp, ROUTINGS["1.4"], seed=5)
    x = x.astype(jnp.bfloat16)
    if twice_rounded:
        # the fault PR 56 was refused for, planted: the buffer's share of
        # a token's gradient rounded to bfloat16 BEFORE the overflow's is
        # added to it
        plain = moe._sum_of_rows
        monkeypatch.setattr(
            moe, "_sum_of_rows", lambda *a: plain(*a).astype(
                jnp.bfloat16).astype(jnp.float32))
    _, grads, sown = value_and_grads(mlp, params, x, as_written=True)
    monkeypatch.undo()
    _, grads_all, _ = value_and_grads(mlp, params, x, every_pick=True,
                                      as_written=True)
    assert int(sown["overflowed"]) == 1
    got, want = (np.asarray(g["x"], np.float32) for g in (grads, grads_all))
    # the tokens with picks on BOTH sides of the buffer's end
    both = np.asarray(x[..., 1] == 1).reshape(-1)
    got, want = got.reshape(-1, M)[both], want.reshape(-1, M)[both]
    return float(np.mean(got != want)), float(
        np.abs(got - want).sum() / np.abs(want).sum())


def test_a_tokens_row_gradient_is_summed_in_float32_and_rounded_once(
        monkeypatch):
    """A token with six picks in the buffer and two behind it: its row
    gradient is the float32 sum of all eight, rounded to bfloat16 ONCE, as
    the parent's ``_to_expert_order_bwd`` has it.  With both programs
    compiled to round where they say, every element of those tokens'
    gradients EQUALS the buffer of every pick's (0 of 5 120 differ; a
    rare one may where a grouped matmul and a plain one sum in another
    order); with the buffer's share rounded before the overflow's is
    added, a quarter of them differ (0.256, 1.2e-3 of their size)."""
    differ, error = _row_gradient_error(monkeypatch, twice_rounded=False)
    assert differ < 0.01 and error < 1e-4, (differ, error)
    differ, error = _row_gradient_error(monkeypatch, twice_rounded=True)
    assert differ > 0.1 and error > 5e-4, (differ, error)


def test_the_counter_is_summed_over_scanned_layers():
    """Under ``nn.scan`` the collection stacks a layer's 0 / 1; a model
    whose shares keep the buffer of every pick sows none and reads 0."""
    sown = {"layers": {"mlp": {
        "expert_counts": jnp.ones((3, 16), jnp.int32),
        "held_counts": jnp.ones((3, 4), jnp.int32),
        "overflowed": jnp.asarray([0, 1, 1], jnp.int32),
        "balance_loss": jnp.ones((3,)), "z_loss": jnp.ones((3,))}}}
    assert float(routing_stats(sown)["moe_overflow_layers"]) == 2.0
    del sown["layers"]["mlp"]["overflowed"]
    assert float(routing_stats(sown)["moe_overflow_layers"]) == 0.0
    del sown["layers"]["mlp"]["held_counts"]
    assert "moe_overflow_layers" not in routing_stats(sown)


# (picks, held, experts) -> rows: tests/test_sparse_mlp_buffer.py's cases of
# the served layer's ``_buffer_rows``, which this rule was until PR 57
_SERVED = [
    (2048, 16, 64, 768), (2048, 64, 64, 2048), (2048, 1, 64, 256),
    (2100, 16, 64, 1024), (4096, 8, 72, 768), (4096, 72, 72, 4096),
    (10240, 8, 72, 1792), (16384, 32, 256, 3072), (16384, 8, 128, 1536),
    (2048, 2, 8, 768), (8192, 2, 8, 3072), (8192, 3, 8, 4608),
    (65536, 8, 32, 24576),        # train-conv-moe-8k: 2 x 8192 x top 4
    (131072, 32, 256, 24576),     # train-hybrid-8k: 2 x 8192 x top 8
    (32768, 64, 64, 32768),       # train-moe-dropless: every pick
]


@pytest.mark.parametrize("picks, held, experts, rows", _SERVED)
def test_the_rule_is_the_served_layers(picks, held, experts, rows):
    """3 / 2 of the picks an even routing sends the share, up to the
    grouped matmul's row tile, never more than every pick; the served
    layer's ``_buffer_rows`` is this rule from ``WALKED_FROM`` picks on
    and every pick under it."""
    from dlrover_tpu.serving import latent

    assert buffer_rows(picks, held, experts) == rows
    assert rows == picks or rows % moe.GMM_TILING[0] == 0
    assert latent._buffer_rows(picks, held, experts) == rows
    assert latent._buffer_rows(latent.WALKED_FROM - 1, held, experts) \
        == latent.WALKED_FROM - 1


def _arrays_of(jaxpr, seen):
    """Every array's shape in a jaxpr and in the jaxprs its equations
    hold (the ``cond``'s branches, loops, custom derivatives, ``pjit``)."""
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            shape = getattr(v.aval, "shape", None)
            if shape:
                seen.add(tuple(shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _arrays_of(sub, seen)
    return seen


def _named(jaxpr, name, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        if eqn.primitive.name != "pallas_call":     # not the kernels' own
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _named(sub, name, found)
    return found


def test_no_pass_is_over_the_rows_of_dead_picks():
    """The jaxpr of forward and backward: ONE ``cond`` each way with the
    segments' loop in it and NO kernel (the benchmark's reader of the
    kernels' roofline wants 12 calls a layer whatever the routing: 9 here,
    3 more recomputed under a model's remat), and nowhere, in line or
    behind the ``cond``, an array of ``T x top_k`` rows (in any grouping of
    them) by the hidden size or by the experts' width.  The buffer of
    every pick has them."""
    mlp = layer(jnp.bfloat16, **ROUTERS["top8-sigmoid-bias"])
    params, x = inputs(mlp, ROUTINGS["0.5"])

    def traced(every_pick):
        def loss(params, x):
            with buffer_of(every_pick):
                return mlp.apply({"params": params}, x,
                                 mutable=["moe_losses"])[0].astype(
                                     jnp.float32).sum()
        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x)
        return jaxpr.jaxpr, _arrays_of(jaxpr.jaxpr, set())

    def dead_rows(shapes):
        return sorted(s for s in shapes if len(s) >= 2 and s[-1] in (M, W)
                      and int(np.prod(s[:-1])) == PICKS)

    jaxpr, shapes = traced(every_pick=False)
    conds = _named(jaxpr, "cond", [])
    assert len(conds) == 2                          # one each way
    for eqn in conds:
        taken = [b.jaxpr for b in eqn.params["branches"]]
        assert [bool(_named(b, "while", [])) for b in taken] == [False, True]
        assert not _named(taken[1], "pallas_call", [])
        assert len(_named(taken[1], "dot_general", [])) in (3, 9)
    assert len(_named(jaxpr, "pallas_call", [])) == 9
    assert not dead_rows(shapes), dead_rows(shapes)
    assert any(s[0] == BOUND and s[-1] == M for s in shapes)
    assert any(s[0] == BOUND and s[-1] == W for s in shapes)
    assert any(s[0] == moe.SEGMENT_ROWS and s[-1] == W for s in shapes)
    every, shapes = traced(every_pick=True)
    assert dead_rows(shapes) and not _named(every, "cond", [])
