"""The served sparse MLP's sorted buffer (``serving/latent.py sparse_mlp``):
a bound ``C`` of rows that follows the share of the experts held, walked
over the held picks as often as the routing needs.  The layer against a
plain loop over the held experts written here, at granite's rehearse widths
(hidden 64, experts' width 32, a shared expert of 48, top 3) on the CPU,
the grouped matmuls in Pallas's interpreter; the engine's counter of the
walks; and the compiled text of a prompt chunk, which holds no array of
``T x top_k`` rows by the hidden size under ``moe_experts``."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.llama import LlamaConfig
from dlrover_tpu.serving import latent

E, F, SHARED, TOP_K = 64, 32, 48, 3


def config(experts: int, held, dtype=jnp.float32, **kw):
    base = dict(
        vocab_size=256, hidden_size=E, intermediate_size=F, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=512,
        ssm_heads=4, ssm_head_dim=16, ssm_state=32, num_experts=experts,
        moe_top_k=TOP_K, moe_shared_width=SHARED, moe_experts_held=held,
        attn_scale=1.0 / 16, dtype=dtype, param_dtype=dtype)
    base.update(kw)
    return LlamaConfig.granite_4_h_small(**base)


def layer(cfg, seed: int, bias=None):
    """One sparse layer's served parameters, seeded; ``bias`` [experts] is
    added to the scores for the SELECTION alone (``route``'s
    ``select_bias``): the weights stay the scores' own."""
    _, held = cfg.moe_experts_held or (0, cfg.num_experts)
    rng = np.random.RandomState(seed)
    normal = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    lp = {"router": jnp.asarray(normal(E, cfg.num_experts) * 0.5),
          "w_gate": normal(held, E, F) * E ** -0.5,
          "w_up": normal(held, E, F) * E ** -0.5,
          "w_down": normal(held, F, E) * F ** -0.5,
          "shared_wgu": normal(E, 2 * SHARED) * E ** -0.5,
          "shared_down": normal(SHARED, E) * SHARED ** -0.5}
    lp = {k: v if k == "router" else jnp.asarray(v, cfg.dtype)
          for k, v in lp.items()}
    if bias is not None:
        lp["select_bias"] = jnp.asarray(bias, jnp.float32)
    return lp


def plain(lp, x, cfg, counted):
    """The layer as a loop over the held experts, EVERY token through each
    of them and weighted by what the router gave it there (nothing where
    it did not pick the expert): ``(y [T, E] float32 before the last
    cast, shared expert alone, [picks, picks held], held picks)``.  The
    operands are cast where the served layer casts them."""
    dtype = cfg.dtype
    first, held = cfg.moe_experts_held or (0, cfg.num_experts)
    f32 = dict(preferred_element_type=jnp.float32)
    scores = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), lp["router"],
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    _, top_e = jax.lax.top_k(scores + lp.get("select_bias", 0.0), TOP_K)
    top_p = jnp.take_along_axis(scores, top_e, axis=-1)
    top_p = top_p / top_p.sum(axis=-1, keepdims=True)
    y = jnp.zeros(x.shape, jnp.float32)
    for g in range(held):
        weight = jnp.sum(jnp.where(top_e == first + g, top_p, 0.0), axis=-1)
        gate = jnp.dot(x, lp["w_gate"][g], **f32).astype(dtype)
        up = jnp.dot(x, lp["w_up"][g], **f32).astype(dtype)
        out = jnp.dot(jax.nn.silu(gate) * up, lp["w_down"][g],
                      **f32).astype(dtype)
        y = y + weight[:, None] * out.astype(jnp.float32)
    gu = jnp.dot(x, lp["shared_wgu"], **f32).astype(dtype)
    shared = jnp.dot(jax.nn.silu(gu[:, :SHARED]) * gu[:, SHARED:],
                     lp["shared_down"], **f32).astype(dtype)
    is_held = (top_e >= first) & (top_e < first + held)
    rows = np.asarray(counted).reshape(-1)
    return (np.asarray(y + shared.astype(jnp.float32)),
            np.asarray(shared, np.float32),
            [int(rows.sum()) * TOP_K, int(np.asarray(is_held)[rows].sum())],
            int(is_held.sum()))


def _raised(experts, first, held, by):
    bias = np.zeros(experts, np.float32)
    bias[first:first + held] = by
    return bias


# name: (tokens as [B, K], experts, held, selection bias, parked rows,
#        dtype, rows of the buffer, walks)
CASES = {
    # 2 100 picks (no multiple of 256), 525 here when even: one walk of the
    # 1 024 rows
    "even": ((1, 700), 16, (4, 4), None, 0, jnp.float32, 1024, 1),
    "even_bf16": ((1, 700), 16, (4, 4), None, 0, jnp.bfloat16, 1024, 1),
    # every pick on a held expert: 2 100 rows through a buffer of 1 024
    "all_held": ((1, 700), 16, (4, 4), _raised(16, 4, 4, 10.0), 0,
                 jnp.float32, 1024, 3),
    "all_held_bf16": ((1, 700), 16, (4, 4), _raised(16, 4, 4, 10.0), 0,
                      jnp.bfloat16, 1024, 3),
    # two hot experts beside an even rest: their 1 400 picks and ~100 of
    # the other two's overflow the 1 024 rows
    "overflow_by_two_experts": ((2, 350), 16, (4, 4),
                                _raised(16, 5, 2, 10.0), 0, jnp.float32,
                                1024, 2),
    "none_held": ((1, 700), 16, (4, 4), _raised(16, 4, 4, -10.0), 0,
                  jnp.float32, 1024, 0),
    # a model served whole: the buffer is every pick, walked once
    "every_expert_held": ((1, 50), 8, None, None, 0, jnp.float32, 150, 1),
    # a decode forward of 7 slots, 3 of them parked: 21 picks, a buffer of
    # 21 rows (under one tile), the parked rows route and are not counted
    "parked_rows": ((7, 1), 8, (0, 4), None, 3, jnp.float32, 21, 1),
    "parked_rows_chunk": ((1, 700), 16, (4, 4), None, 80, jnp.float32,
                          1024, 1),
    # fewer picks than the buffer is cut from: all of them, in line, and
    # every one may be held
    "small_all_held": ((1, 200), 16, (4, 4), _raised(16, 4, 4, 10.0), 0,
                       jnp.float32, 600, 1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_sparse_mlp_is_the_loop_over_held_experts(case):
    """``y`` to 1e-5 of the output's RMS and the counts equal, whatever
    the routing sends through the buffer: a share of it, all of it in
    several walks, none of it.  (A bfloat16 model rounds ``gate``, ``up``
    and each expert's output to 8 bits, and a grouped matmul and a plain
    one do not round every element alike: there the mean error is held
    to a bfloat16 step and the largest to a dozen, where one pick dropped
    or weighted in bfloat16 moves a token's row by tenths of the RMS.)"""
    (b, klen), experts, held, bias, parked, dtype, rows, walks = CASES[case]
    cfg = config(experts, held, dtype)
    lp = layer(cfg, 11, bias)
    rng = np.random.RandomState(5)
    h = jnp.asarray(rng.standard_normal((b, klen, E)), dtype)
    counted = np.ones((b, klen), bool)
    counted.reshape(-1)[b * klen - parked:] = False
    n_held = held[1] if held else experts
    assert latent._buffer_rows(b * klen * TOP_K, n_held, experts) == rows
    got, picks = jax.jit(lambda lp, h, c: latent.sparse_mlp(
        lp, h, cfg, dtype, c))(lp, h, jnp.asarray(counted))
    want, shared, counts, held_picks = plain(
        lp, h.reshape(-1, E), cfg, counted)
    assert got.dtype == dtype and got.shape == h.shape
    got = np.asarray(got.reshape(-1, E), np.float32)
    rms = float(np.sqrt(np.mean(want ** 2)))
    err = np.abs(got - want) / rms
    if dtype == jnp.bfloat16:
        assert np.sqrt(np.mean(err ** 2)) < 2.0 ** -8 and err.max() < 0.05, \
            (float(np.sqrt(np.mean(err ** 2))), float(err.max()))
    else:
        assert err.max() < 1e-5, float(err.max())
    assert picks.dtype == jnp.uint32
    assert picks.tolist() == counts + [walks, 1]
    assert -(-held_picks // rows) == walks
    if case == "none_held":
        np.testing.assert_array_equal(got, shared)
    else:
        assert np.abs(got - shared).max() > 0.01 * rms


@pytest.mark.parametrize("picks, held, experts, rows", [
    (5120, 18, 72, 2048), (1280, 18, 72, 1280),     # granite: chunk, decode
    (4096, 32, 256, 768), (1024, 32, 256, 1024),    # kimi-linear
    (2048, 32, 128, 768), (256, 32, 128, 256),      # sarvam-105b
    (4096, 32, 256, 768), (256, 32, 256, 256),      # dots3
    (4096, 16, 256, 512), (256, 16, 256, 256),      # glm5
    (5120, 72, 72, 5120), (150, 8, 8, 150), (300, 1, 8, 300),
    (2048, 1, 8, 512), (2047, 1, 8, 2047),
])
def test_buffer_rows_follow_the_held_share(picks, held, experts, rows):
    """The bound for the five served cells' prompt chunks as ISSUE 51
    lists them; every pick where every expert is held, and in a forward
    of fewer than ``WALKED_FROM`` picks (every cell's decode forward)."""
    assert latent._buffer_rows(picks, held, experts) == rows


def test_a_chunk_program_holds_no_row_of_a_dead_pick():
    """The compiled text of a prompt chunk at granite's rehearse widths
    (768 tokens, top 3, a quarter of 8 experts held: a buffer of 1 024
    rows for 2 304 picks): under ``moe_experts`` no array has ``T x
    top_k`` rows by the hidden size or by the experts' width, the guard
    that dead rows do not come back."""
    from dlrover_tpu.serving.linear import state_shapes
    from dlrover_tpu.serving.params import serving_params_from_llama
    from perfbench.weights_granite import SeededGraniteParams

    cfg = config(8, (0, 2), num_layers=2, max_seq_len=1024)
    t = 768
    assert latent._buffer_rows(t * TOP_K, 2, 8) == 1024
    sp = jax.eval_shape(lambda: serving_params_from_llama(
        {"params": SeededGraniteParams(cfg, 3)}, cfg))
    S = jax.ShapeDtypeStruct
    state, conv = (a.shape for a in state_shapes(cfg, 3, "ssm").values())
    layers = sum(s.mixer == "ssm" for s in cfg.layer_specs)
    cache = {
        "k_pool": [S((20, 8, 2, 16), jnp.float32)] * (2 - layers),
        "v_pool": [S((20, 8, 2, 16), jnp.float32)] * (2 - layers),
        "ssm_state": [S(state, jnp.float32)] * layers,
        "ssm_conv": [S(conv, jnp.float32)] * layers,
        "table": S((3, 16), jnp.int32), "moe_picks": S((4,), jnp.uint32)}
    ints = lambda *shape: S(shape, jnp.int32)  # noqa: E731
    text = jax.jit(lambda p, c, tok, pos, sl, li: latent.verify_step(
        p, cfg, c, tok, pos, slots=sl, logits_index=li)).lower(
        sp, cache, ints(1, t), ints(1), ints(1), ints(1)).compile().as_text()
    under = [line for line in text.splitlines()
             if re.search(r'op_name="[^"]*moe_experts', line)]
    assert len(under) > 20
    dead = [line for line in under
            if re.search(rf"\[{t * TOP_K},({E}|{F})\]", line)]
    assert not dead, dead[:3]
    assert any(re.search(rf"\[1024,({E}|{F})\]", line) for line in under)


@pytest.mark.parametrize("routing", ["even", "raised"])
def test_the_engine_books_the_walks(routing):
    """``EngineStats.moe_walks_per_layer`` after a few engine steps at the
    rehearse widths: 1.0 while every layer's held picks fit its buffer,
    above it where the router sends every pick to the held experts (a
    chunk of 768 tokens: 2 304 picks through 1 024 rows, three walks; a
    decode forward's buffer is every pick, one walk)."""
    from dlrover_tpu.serving.engine import InferenceEngine
    from dlrover_tpu.serving.router.replica import InferenceEngineAdapter
    from perfbench.weights_granite import SeededGraniteParams

    cfg = config(16, (4, 4), num_layers=2, max_seq_len=1024)
    params = SeededGraniteParams(cfg, 7)
    engine = InferenceEngine(
        cfg, {"params": params}, max_slots=3, chunk=4, temperature=0.0,
        eos_token=None, max_len=1000, paged=True, block_size=8,
        cache_blocks=300, prefill_chunk=768, prefill_buckets=(1000,),
        attention_impl="xla", seed=0, prefix_sharing=False)
    if routing == "raised":
        bias = jnp.asarray(_raised(16, 4, 4, 10.0))
        engine.params = dict(engine.params, layers=[
            dict(lp, select_bias=bias) for lp in engine.params["layers"]])
    rng = np.random.RandomState(2)
    for n in (800, 40):
        engine.add_request(rng.randint(0, 256, n).astype(np.int32), 6)
    while engine.has_work:
        engine.step()
    st = engine.stats
    assert st.moe_layer_forwards > 0 and st.moe_picks > 0
    # every program ran both sparse layers
    assert st.moe_layer_forwards % 2 == 0
    if routing == "even":
        # (a decode forward of three slots may hold no pick in a layer)
        assert st.moe_layer_forwards - 2 <= st.moe_buffer_walks \
            <= st.moe_layer_forwards
        assert 0.9 < st.moe_walks_per_layer <= 1.0
        assert 0 < st.moe_picks_held < st.moe_picks
    else:
        assert st.moe_picks_held == st.moe_picks
        # three prompt chunks (800 tokens are two, 40 one) walk three
        # times a layer, every other program once
        assert st.moe_buffer_walks == st.moe_layer_forwards + 3 * 2 * 2
        assert st.moe_walks_per_layer > 1.0
    sent = InferenceEngineAdapter(engine).engine_metrics()
    assert (sent["moe_buffer_walks"], sent["moe_layer_forwards"]) == (
        float(st.moe_buffer_walks), float(st.moe_layer_forwards))
