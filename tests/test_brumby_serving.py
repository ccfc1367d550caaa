"""Brumby-14B-Base's layers (``brumby``) at a tiny size on the CPU, float32,
seeded, with EVERY mechanism of the published model: power retention of
degree 2 over 10 query heads on 2 key heads (five queries share a key
head's state), a gate a key head, the normaliser, a QK-norm a head and
RoPE ahead of the power, a dense SwiGLU behind every layer, an untied
head, and NO layer that caches rows.  The plain reference
(``perfbench/reference_brumby.py``, the retention in its ATTENTION form)
against the ``jnp`` recurrence, both kernels (interpreted) and the engine,
LOGITS compared; a slot's state when the slot is reused, idle or
prefilling; every refusal by its message; what the engine books and that it
allocates no pool; the driver's check and each planted fault.

The rule of the serving test files (``tests/test_sparse_serving.py`` has it
whole): the config and the seeded params are module-scoped fixtures, what
several cases compute alike is computed once, and an engine is built once
where a test asks the same of it again."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.llama import LlamaConfig, LlamaModel, PRESETS
from dlrover_tpu.ops.pallas import retention
from dlrover_tpu.serving import latent, linear
from dlrover_tpu.serving.engine import InferenceEngine
from dlrover_tpu.serving.params import serving_params_from_llama
from perfbench import controls_brumby
from perfbench import reference_brumby as ref
from perfbench.drivers import serve_retention
from perfbench.weights_brumby import SeededBrumbyParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 96
DIMS = {"degree": 2, "den_eps": 1e-6}


def tiny(**kw):
    base = dict(
        vocab_size=VOCAB, hidden_size=32, intermediate_size=48, num_layers=3,
        num_heads=10, num_kv_heads=2, head_dim=8, max_seq_len=512,
        dtype=jnp.float32, param_dtype=jnp.float32)
    base.update(kw)
    return LlamaConfig.brumby_14b(**base)


def config_of(cfg):
    """``cfg`` under the keys of a configuration file that
    ``reference_brumby.dims_of`` reads."""
    return {
        "model_type": "brumby", "attention_bias": False,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "rope_scaling": None, "hidden_act": "silu",
        "max_window_layers": 40, "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim_,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
        "assumed_sizes": {"retention_degree": 2, "retention_eps": 1e-6}}


def reference_logits(cfg, params, seq, keep=None, state_layers=()):
    d = ref.dims_of(config_of(cfg))
    x = ref.hidden_states(seq, params.layer, params.top(), cfg.num_layers,
                          d, keep, state_layers)
    return np.asarray(ref.head_logits(x, params.top(), d["eps"]))


@pytest.fixture(scope="module")
def cfg():
    return tiny()


@pytest.fixture(scope="module")
def params_of(cfg):
    """``params_of(seed)``: ``cfg``'s seeded params, made once a seed."""
    return functools.cache(lambda seed: SeededBrumbyParams(cfg, seed))


def _engine(cfg, params, impl="xla", **kw):
    base = dict(max_slots=3, chunk=4, temperature=0.0, eos_token=None,
                max_len=400, prefill_chunk=128 if impl == "pallas" else 8,
                attention_impl=impl, seed=0, prefix_sharing=False)
    base.update(kw)
    return InferenceEngine(cfg, {"params": params}, **base)


def _drain(engine):
    done = {}
    while engine.has_work:
        for r in engine.step():
            done[r.rid] = r
    return done


def _witnessed_logits(engine, req):
    """{position: logits} the engine's own programs handed back for
    ``req``: the prompt's last chunk and every decode forward that fed a
    delivered token."""
    out = {}
    c = engine.prefill_chunk
    for e in engine.witness_log:
        if e["request"] is not req:
            continue
        got = np.asarray(e["seen"]["logits"])
        if e["kind"] == "run":
            if e["start"] + c >= req.prompt.size:
                out[req.prompt.size - 1] = got
        else:
            for j in range(got.shape[0]):
                if e["start"] + j <= req.prompt.size + len(req.output) - 2:
                    out[e["start"] + j] = got[j]
    return out


def _serve_one(engine, prompt, new):
    """``prompt`` through ``engine`` alone: (request, {position: logits})."""
    engine.witness_log.clear()
    engine.watch(lambda r: True)
    rid = engine.add_request(prompt, new)
    req = _drain(engine)[rid]
    return req, _witnessed_logits(engine, req)


def _against_reference(cfg, params, req, logits, atol=1e-4):
    """The tolerance: float32 on both sides, but not the same sums.  The
    reference squares ``q . k``; the program sums 44 products of pairs a
    key, whose signs cancel down to that square: an absolute error of
    ``|q|^2 |k|^2`` ulps, which is a relative one of 1e-5 where a query's
    keys all lie across it (seeded weights: nothing has trained q towards
    k), and the first tokens of a prompt have few keys to average over.
    Measured 4e-5 at the worst position; a bfloat16 state moves the
    logits by 1e-3 and every other planted fault by more
    (``test_every_planted_fault_fails_the_drivers_check``)."""
    seq = np.concatenate([req.prompt, np.asarray(req.output, np.int32)])
    want = reference_logits(cfg, params, seq)
    p = req.prompt.size
    assert sorted(logits) == list(range(p - 1, seq.size - 1))
    for pos, got in logits.items():
        np.testing.assert_allclose(got, want[pos], atol=atol)
    assert req.output == want[p - 1:-1].argmax(-1).tolist()


# ------------------------------------------------------------ the model
def test_the_preset_is_the_published_model():
    """The preset's own count is ISSUE 62's arithmetic and the
    configuration file's ``parameters``, at the published depth and as
    run."""
    cfg = LlamaConfig.brumby_14b()
    assert "brumby_14b" in PRESETS
    assert all(s.mixer == "retention" and s.mlp == "dense"
               and s.num_heads == 40 and s.rope.theta == 1e6
               for s in cfg.layer_specs)
    assert cfg.layer_params(cfg.layer_specs[0]) == 330_352_904
    assert cfg.num_params == 14_769_945_920
    assert cfg.layer_kinds and cfg.qk_norm_kind == "head"
    with open(os.path.join(
            ROOT, "perfbench/configs/brumby-14b-serve.json")) as f:
        config = json.load(f)
    cut = serve_retention.model_config(config, 5248)
    assert cut.num_params == config["parameters"]["total_as_run"] \
        == 3_537_947_184
    assert config["parameters"]["total_published"] == cfg.num_params
    assert config["parameters"]["layer"] == 330_352_904
    assert config["reduced"].keys() == {"num_hidden_layers"}
    assert (cut.num_layers, cut.num_heads, cut.num_kv_heads, cut.head_dim_,
            cut.intermediate_size, cut.vocab_size) == (
                6, 40, 8, 128, 17408, 151936)
    # a slot and layer as kept, and at the exact symmetric count
    state = linear.state_shapes(cut, 1, "retention")
    assert sum(int(np.prod(a.shape)) * 4 for a in state.values()) \
        == 8 * 8320 * 129 * 4
    from perfbench.kernels_retention import state_bytes

    assert state_bytes(8, 128) == 8 * 8256 * 129 * 4


def test_training_refuses_the_model_by_what_it_lacks(cfg):
    with pytest.raises(NotImplementedError,
                       match="power retention.*chunk kernel's backward"):
        LlamaModel(cfg).init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))


# --------------------------------------------------- the symmetric square
@pytest.mark.parametrize("d", [8, 16, 128])
def test_phi_is_the_square_and_holds_each_pair_once(d):
    """(a) ``phi(q) . phi(k) = (q . k)^2`` to float32 rounding (a sum of
    ``d (d + 1) / 2`` products against one of ``d``: a few ulps of the
    larger), and the kept layout's lanes are every unordered pair ONCE:
    ``d / 2`` lanes of zeros, and the driver's ``unfold`` finds every
    place of the full square."""
    rng = np.random.RandomState(d)
    q, k = rng.randn(2, 7, d).astype(np.float32)
    got = np.sum(np.asarray(retention.phi(q) * retention.phi(k)),
                 axis=(-1, -2))
    want = np.sum(q.astype(np.float64) * k, axis=-1) ** 2
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5)
    tiles = retention.kept_tiles(d)
    assert retention.kept_rows(d) == tiles * d == d * (d + 1) // 2 + d // 2
    pairs = set()
    weights = retention.pair_weights(d)
    for s in range(tiles):
        for c in range(d):
            if weights[s, c]:
                pair = frozenset((c, (c - s) % d))
                assert pair not in pairs
                pairs.add(pair)
                assert weights[s, c] == (1.0 if len(pair) == 1
                                         else np.float32(np.sqrt(2.0)))
    assert len(pairs) == d * (d + 1) // 2
    # the symmetric square of k, unfolded, is k k^T
    full, clean = serve_retention.unfold(np.asarray(retention.phi(k)))
    assert clean
    np.testing.assert_allclose(full, k[:, :, None] * k[:, None, :],
                               rtol=1e-6, atol=1e-7)


# ------------------------------------- recurrence and kernels, by the book
def _draw(klen, hk, group, d, seed):
    """Queries and keys that share a direction, so that no ``q . k`` is
    near zero: where a token's weights all but vanish its output is the
    quotient of two roundings, in the reference as in the program."""
    rng = np.random.RandomState(seed)
    shared = rng.randn(d).astype(np.float32)
    q = shared + 0.5 * rng.randn(klen, hk * group, d).astype(np.float32)
    k = shared + 0.5 * rng.randn(klen, hk, d).astype(np.float32)
    v = rng.randn(klen, hk, d).astype(np.float32)
    # memories of ~3 to ~300 tokens over the heads
    lg = -np.abs(rng.randn(klen, hk)).astype(np.float32) \
        * np.logspace(-0.5, -2.5, hk, dtype=np.float32)
    return q, k, v, lg


def _by_the_definition(q, k, v, lg):
    """The reference's attention form and its full symmetric state."""
    cum = jnp.cumsum(jnp.asarray(lg), axis=0)
    with jax.default_matmul_precision("highest"):
        y = ref.attend(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), cum,
                       DIMS)
        s, z = ref.pair_state(jnp.asarray(k), jnp.asarray(v), cum)
    return np.asarray(y), np.asarray(s), np.asarray(z)


def _unfolded(state, keysum):
    s, clean_s = serve_retention.unfold(np.moveaxis(np.asarray(state), 2, 1))
    z, clean_z = serve_retention.unfold(np.asarray(keysum))
    assert clean_s and clean_z
    return s, z


def _zeros(hk, d, lead=()):
    tiles = retention.kept_tiles(d)
    return (jnp.zeros(lead + (hk, tiles, d, d), jnp.float32),
            jnp.zeros(lead + (hk, tiles, d), jnp.float32))


# float32 against float32 in another order; y is O(1), the state's norm
# relative
_Y, _S = 2e-5, 2e-6


@pytest.mark.parametrize("d, klen", [(16, 41), (128, 9)])
def test_the_recurrence_is_the_attention_form(d, klen):
    """(b) the ``jnp`` recurrence, one token at a time, against the
    definition: outputs at every token, state and sum of keys behind the
    last."""
    q, k, v, lg = _draw(klen, 2, 5, d, 1)
    want, s_want, z_want = _by_the_definition(q, k, v, lg)
    y, s, z = retention.retention_recurrence(*_zeros(2, d), q, k, v, lg)
    np.testing.assert_allclose(y, want, atol=_Y)
    s, z = _unfolded(s, z)
    assert serve_retention._rel(s, s_want) < _S
    assert serve_retention._rel(z, z_want) < _S


@pytest.mark.parametrize("d, steps", [(16, 12), (128, 3)])
def test_the_decode_kernel_is_the_attention_form(d, steps):
    """(b) the decode kernel (interpreted), one token at a time over three
    slots of which the middle one idles: the decoding slots' outputs and
    states are the definition's, the idle slot's state stays bit for bit
    and its output is zeros."""
    hk, group, b = 2, 5, 3
    draws = [_draw(steps, hk, group, d, 10 + i) for i in range(b)]
    q, k, v, lg = (np.stack([x[j] for x in draws], axis=1)
                   for j in range(4))                     # [steps, B, ...]
    active = jnp.asarray([True, False, True])
    s, z = _zeros(hk, d, (b,))
    s, z = s.at[1].set(0.25), z.at[1].set(0.5)
    ys = []
    for t in range(steps):
        y, s, z = retention.retention_decode_step(
            s, z, q[t], k[t], v[t], lg[t], active, interpret=True)
        ys.append(np.asarray(y))
    ys = np.stack(ys)
    assert not ys[:, 1].any()
    assert float(jnp.abs(s[1] - 0.25).max()) == 0.0
    assert float(jnp.abs(z[1] - 0.5).max()) == 0.0
    for i in (0, 2):
        want, s_want, z_want = _by_the_definition(*draws[i])
        np.testing.assert_allclose(ys[:, i], want, atol=_Y)
        got_s, got_z = _unfolded(s[i], z[i])
        assert serve_retention._rel(got_s, s_want) < _S
        assert serve_retention._rel(got_z, z_want) < _S


@pytest.mark.parametrize("d, chunk, splits, operands, tol", [
    (16, 32, (64, 32, 96), "float32", 1.0),
    (16, 32, (96, 96), "float32", 1.0),
    (128, 16, (32, 16), "float32", 1.0),
    # bfloat16 operands of the two large products: 2^-9 a term
    (16, 32, (64, 32, 96), "bfloat16", 3e3),
])
def test_the_chunk_kernel_is_the_attention_form(d, chunk, splits, operands,
                                                tol):
    """(b) the chunk kernel (interpreted) over a prompt split into runs at
    uneven boundaries, the last run PADDED behind its last real token (and
    a whole step of padding behind that where the run has room): outputs
    at every real token, state and sum of keys behind the last."""
    hk, group = 2, 5
    real = sum(splits) - chunk - 5          # the last run's padding
    q, k, v, lg = _draw(sum(splits), hk, group, d, 3)
    want, s_want, z_want = _by_the_definition(
        q[:real], k[:real], v[:real], lg[:real])
    s, z = _zeros(hk, d)
    ys, at = [], 0
    for n in splits:
        rows = slice(at, at + n)
        y, s, z = retention.retention_chunk_fwd(
            s, z, q[rows], k[rows], v[rows], lg[rows],
            jnp.asarray(min(n, real - at), jnp.int32), chunk=chunk,
            operands=operands, interpret=True)
        ys.append(np.asarray(y))
        at += n
    np.testing.assert_allclose(np.concatenate(ys)[:real], want,
                               atol=_Y * tol)
    s, z = _unfolded(s, z)
    assert serve_retention._rel(s, s_want) < _S * tol
    assert serve_retention._rel(z, z_want) < _S * tol


def test_the_kernels_need_what_the_mathematics_needs():
    """``perfbench/kernels_retention.py``: the state at the EXACT symmetric
    count, read once and written once a decoding slot, key head and layer;
    the kept layout holds 64 rows more a key head and reads lower."""
    from perfbench import kernels_retention as need

    assert need.pairs(128) == 8256 < retention.kept_rows(128) == 8320
    assert need.retention_decode_bytes(24, 8, 128, layers=6) \
        == 24 * 6 * 2 * 8 * 8256 * 129 * 4
    assert need.retention_chunk_flops(1, 40, 8, 128) \
        == 48 * 2 * 8256 * 129


# --------------------------------------------- engine against reference
@pytest.mark.parametrize("impl, lengths", [
    ("xla", (1, 7, 8, 9, 29)),
    ("pallas", (1, 127, 128, 129, 300)),
])
def test_prefill_then_decode_through_the_engine_is_the_reference(
        impl, lengths, cfg, params_of):
    """(c) prompts of 1, chunk - 1, chunk, chunk + 1 and several chunks,
    each decoded for two chunks and a bit: the logits of the prompt's last
    chunk and of every decode forward are the reference's full forward
    (the ``jnp`` recurrence at a chunk of 8; both kernels, interpreted, at
    a chunk of 128)."""
    params = params_of(3)
    engine = _engine(cfg, params, impl)
    rng = np.random.RandomState(0)
    for n in lengths:
        prompt = rng.randint(0, VOCAB, n).astype(np.int32)
        req, logits = _serve_one(engine, prompt, 10)
        _against_reference(cfg, params, req, logits)
    # every admission started from zeros, and only the decoding slot's
    # state was walked by the kernel
    s = engine.stats
    assert s.state_resets_total == len(lengths)
    assert s.state_stream_ratio == (1.0 if impl == "pallas" else 3.0)
    assert s.retention_chunk_rows_real == sum(lengths)
    assert not s.kda_chunk_rows_real and not s.ssm_chunk_rows_real
    if impl == "pallas":       # whole 128-token chunks up to the last token
        assert s.retention_chunk_rows_padded == sum(
            -(-n // 128) * 128 for n in lengths)
    # BOTH arrays, at the bytes the kept layout holds, read and written
    one = 2 * (2 * 5 * 8 * 9) * 4 * 3          # Hk x tiles x d x (d + 1)
    assert s.state_bytes_live == one * s.decode_forwards


def test_a_reused_slot_gives_what_a_fresh_engine_gives(cfg, params_of):
    """(c) the second request lands in the slot the first one left (one
    slot), whose state and sum of keys are the first one's last: the
    prompt's first chunk starts from zeros inside its own program, not by
    the host."""
    params = params_of(5)
    rng = np.random.RandomState(1)
    first = rng.randint(0, VOCAB, 21).astype(np.int32)
    second = rng.randint(0, VOCAB, 13).astype(np.int32)
    used = _engine(cfg, params, max_slots=1)
    _serve_one(used, first, 9)
    assert float(jnp.abs(used._cache["retention_state"][0]).max()) > 0
    assert float(jnp.abs(used._cache["retention_keysum"][0]).max()) > 0
    got_req, got = _serve_one(used, second, 9)
    fresh_req, want = _serve_one(_engine(cfg, params, max_slots=1), second,
                                 9)
    assert got_req.output == fresh_req.output
    for pos in want:
        np.testing.assert_array_equal(got[pos], want[pos])


def test_a_poisoned_state_is_zeroed_by_the_first_chunk(cfg, params_of):
    """What the benchmark does in set-up: every slot's state and sum of
    keys LOUD before any request; the answers are the reference's."""
    params = params_of(5)
    engine = _engine(cfg, params)
    engine.warmup()
    serve_retention._poison(engine)
    prompt = np.random.RandomState(2).randint(0, VOCAB, 19).astype(np.int32)
    req, logits = _serve_one(engine, prompt, 6)
    _against_reference(cfg, params, req, logits)


def test_requests_admitted_at_different_steps_equal_their_solo_runs(
        cfg, params_of):
    """(c), (d) three requests admitted at different engine steps, so that
    each slot sits idle, prefills and decodes while the others do
    something else, and a FOURTH into the slot the shortest one freed:
    every request's tokens are its solo run's."""
    params = params_of(6)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, VOCAB, n).astype(np.int32)
               for n in (27, 9, 18, 14)]
    alone = _engine(cfg, params)
    solo = [_serve_one(alone, p, 11)[0].output for p in prompts]
    engine = _engine(cfg, params)
    rids, done, step = [], {}, 0
    while len(done) < 4:
        if step in (0, 2, 5, 6):
            rids.append(engine.add_request(prompts[len(rids)], 11))
        for r in engine.step():
            done[r.rid] = r
        step += 1
    assert [done[r].output for r in rids] == solo


def test_an_idle_or_prefilling_slot_keeps_its_state_bit_for_bit(cfg,
                                                                params_of):
    """(d) slot 1 holds a finished request's state, slot 2 a prompt that
    is still prefilling, while slot 0 decodes: a decode chunk leaves both
    slots' states and sums of keys bit for bit; the prompt's next chunk
    then moves slot 2's alone."""
    engine = _engine(cfg, params_of(6))
    rng = np.random.RandomState(4)
    engine.add_request(rng.randint(0, VOCAB, 6).astype(np.int32), 40)
    engine.add_request(rng.randint(0, VOCAB, 5).astype(np.int32), 2)
    for _ in range(4):                      # slot 1's request ends
        engine.step()
    engine.add_request(rng.randint(0, VOCAB, 30).astype(np.int32), 4)
    engine.step()                           # slot 1 takes it: chunk 1 of 4
    assert engine._prefilling[1] and not engine._prefilling[0]

    def held(slot):
        return [np.asarray(a[slot]) for name in serve_retention.STATES
                for a in engine._cache[name]]

    idle, filling = held(2), held(1)
    active = engine._decoding()
    assert active.tolist() == [True, False, False]
    engine._dispatch_decode(active)
    engine._read_results()
    for before, after in zip(idle + filling, held(2) + held(1)):
        np.testing.assert_array_equal(before, after)
    engine._advance_prefill()
    engine._read_results()
    assert any((b != a).any() for b, a in zip(filling, held(1)))
    for before, after in zip(idle, held(2)):
        np.testing.assert_array_equal(before, after)


# ------------------------------------------------ no pool, by slots alone
def test_the_engine_allocates_no_pool_and_admits_by_slots(cfg, params_of):
    """(g) nothing but the slots' states is kept: no pool, no table, no
    block manager; ``paged``, ``cache_blocks`` and ``block_size`` size
    nothing; a request longer than any pool of blocks could have held is
    taken while a slot is free, and the router's ledger charges nothing."""
    from dlrover_tpu.serving.router.replica import InferenceEngineAdapter

    engine = _engine(cfg, params_of(1), paged=True, cache_blocks=2,
                     block_size=8)
    assert not engine.paged and not hasattr(engine, "_blockmgr")
    assert sorted(engine._cache) == ["retention_keysum", "retention_state",
                                     "watch_slot"]
    one = 3 * 2 * 5 * 8 * 4 * 3            # slots x Hk x tiles x d, 3 layers
    assert engine.cache_nbytes_by_kind == {
        "paged": 0, "window": 0, "state": one * 8 + one}
    adapter = InferenceEngineAdapter(engine)
    assert adapter.block_size == 0 and adapter.blocks_free() == float("inf")
    assert adapter.blocks_needed(300, 90) == 0.0
    text = str(jax.make_jaxpr(lambda c: latent.verify_step(
        engine.params, cfg, c, jnp.zeros((3, 1), jnp.int32),
        jnp.asarray([19, 12, 5], jnp.int32)))(engine._cache))
    assert "scatter" not in text
    rng = np.random.RandomState(5)
    rids = [engine.add_request(rng.randint(0, VOCAB, 300).astype(np.int32),
                               5) for _ in range(4)]
    engine.step()
    assert sum(r is not None for r in engine._slot_req) == 3 \
        and len(engine._queue) == 1        # three slots, the fourth waits
    done = _drain(engine)
    assert sorted(done) == rids and engine.prefix_stats() == {}


# ------------------------------------------------------------- refusals
@pytest.mark.parametrize("kw, match", [
    (dict(prefix_sharing=True),
     "prefix_sharing=True with power-retention layers.*snapshot of "
     "recurrent"),
    (dict(speculative_k=4),
     "with power-retention layers.*roll-back of recurrent state"),
    (dict(mesh=object()), "a mesh with power-retention layers"),
    (dict(prefill_chunk=0), "power-retention layers take their prompts in "
                            "chunks"),
    (dict(int8=True), "no int8 weights"),
])
def test_the_engine_refuses_what_cannot_be_right_yet(kw, match, cfg,
                                                     params_of):
    """(e) by the words the other two kinds of state get."""
    with pytest.raises(ValueError, match=match):
        _engine(cfg, params_of(1), **kw)


def test_the_blocks_refuse_what_is_still_missing(cfg, params_of):
    params = params_of(1)
    sp = serving_params_from_llama({"params": params}, cfg)
    toks = jnp.zeros((2, 4), jnp.int32)
    with pytest.raises(ValueError, match="chunked path"):
        latent.prefill(sp, cfg, toks, jnp.asarray([4, 4]))
    engine = _engine(cfg, params)
    with pytest.raises(ValueError, match="already advanced"):
        latent.verify_step(sp, cfg, engine._cache, jnp.zeros((3, 4),
                           jnp.int32), jnp.zeros(3, jnp.int32))


# ------------------------------------------------- the driver's own check
@pytest.fixture(scope="module")
def seen(cfg, params_of):
    """What the engine's programs hand back for two watched requests, one
    behind the other in one slot, as the driver keeps it."""
    engine = _engine(cfg, params_of(7), max_slots=1)
    rng = np.random.RandomState(8)
    engine.watch(lambda req: True)
    pick = serve_retention._picker()
    for n in (19, 30):
        engine.add_request(rng.randint(0, VOCAB, n).astype(np.int32), 10)
        while engine.has_work:
            engine.step()
            serve_retention._to_host(engine.witness_log, 8, pick)
    return serve_retention.Witnessed(engine.witness_log, 8)


_TIGHT = {name: 1e-4 for name in serve_retention.LIMITS}


def test_the_drivers_check_passes_on_the_engine(cfg, params_of, seen):
    """``drivers/serve_retention.py``'s comparison, on the CPU: the watched
    requests' logits, the first layer's gate, AND the watched slot's state
    and sum of keys of the first and last layer behind its last forward,
    unfolded into the reference's full symmetric square."""
    with open(os.path.join(
            ROOT, "perfbench/traffic/gen-closed-36.json")) as f:
        traffic = json.load(f)
    assert serve_retention.limits_of(traffic).keys() \
        == set(serve_retention.LIMITS)
    got = serve_retention.reference_check(
        cfg, params_of(7), config_of(cfg), seen, _TIGHT)
    assert got["watched_requests"] == 2
    assert got["checked_positions"] == 2 * 10
    assert got["logit_rms_p90"] < 5e-5 and got["state_rel_first"] < 1e-5 \
        and got["state_rel_last"] < 5e-5 and got["keysum_rel_last"] < 5e-5 \
        and got["gate_rel"] < 1e-6 and got["padding_rows_zero"], got
    assert all(got[v] for v in controls_brumby.VERDICTS)


def _no_gate():
    """A dropped gate (a test's own fault: the controls plant a gate in
    bfloat16, which a dropped one is grosser than)."""
    return controls_brumby._patched(log_gate=lambda f: jnp.zeros_like(f))


@pytest.mark.parametrize("fault", sorted(controls_brumby.FAULTS)
                         + ["no_gate"])
def test_every_planted_fault_fails_the_drivers_check(fault, cfg, params_of,
                                                     seen):
    """Each planted fault (a bfloat16 state, a dropped normaliser, the gate
    in bfloat16 or dropped, degree 1, no rotation, weight 1 on a pair)
    reads as NOT correct by the driver's own verdicts (at limits a float32
    engine passes, ``_against_reference``'s), and the reference is itself
    again behind it."""
    params = params_of(7)
    planted = _no_gate if fault == "no_gate" else controls_brumby.FAULTS[
        fault]
    with planted():
        bad = controls_brumby.summary(serve_retention.reference_check(
            cfg, params, config_of(cfg), seen, _TIGHT))
    assert not bad["correct"], bad
    if fault == "state_bf16":
        assert not bad["state_matches_reference"]
    if fault in ("gate_bf16", "no_gate"):
        assert not bad["gate_matches_reference"]
    again = controls_brumby.summary(serve_retention.reference_check(
        cfg, params, config_of(cfg), seen, _TIGHT))
    assert again["correct"], again
