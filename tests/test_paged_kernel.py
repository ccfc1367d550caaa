"""Decode raw-speed round two (ISSUE 14): the fused paged-attention
kernel that reads quantized KV in place, and same-step batched prefill.

What's covered, and why each gate exists:

- **kernel parity** (float32 / bf16 / int8 pools, Pallas
  ``interpret=True`` on CPU): the kernel's multi-page double-buffered
  DMA + in-kernel dequant must match the XLA gather reference exactly
  — tier-1 catches numerics regressions without TPU hardware;
- **auto-pick contract**: ``resolve_attention_impl`` provably never
  selects a slower impl (the pure decision the engine's one-shot
  build-time measurement feeds), and engine validation/resolution
  edges;
- **KV-budget single source**: ``paged.kv_budget_multiplier`` is THE
  formula — the engine's pool scaling, ``InferenceEngine.kv_budget_x``
  and the router-side adapter ledger are pinned to it for int8, so
  admission and placement cannot disagree;
- **same-step batched prefill**: N concurrent long prompts reach first
  token in the SAME number of engine steps (no TTFT serialization),
  greedy outputs match the monolithic path, and cancel mid-batch
  reclaims every slot/block;
- **metric plumbing**: the new ``serving_attention_impl`` (labeled) /
  ``serving_paged_kernel_step_seconds`` / ``serving_kv_quant_blocks``
  families from EngineStats through the adapter to RouterMetrics.

The TPU kernel microbench stub (``-m slow``) skips cleanly off-TPU.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
from dlrover_tpu.models.quantize import quantize_kv_int8
from dlrover_tpu.ops.pallas import mla_decode, paged_index
from dlrover_tpu.ops.pallas.paged_attention import (
    GROUP_ROWS,
    gather_reference,
    kernel_parity,
    measure_paged_attention,
    paged_decode_attention,
    resolve_attention_impl,
    streamed_rows,
)
from dlrover_tpu.serving.engine import InferenceEngine
from dlrover_tpu.serving.paged import kv_budget_multiplier


@pytest.fixture(scope="module")
def setup():
    cfg = LlamaConfig.tiny(max_seq_len=96, dtype=jnp.float32)
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    return cfg, variables


def _prompts(cfg, n, size, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randint(0, cfg.vocab_size, (n, size)).astype(np.int32)


def _engine(setup, **kw):
    cfg, variables = setup
    kw.setdefault("max_slots", 2)
    kw.setdefault("chunk", 4)
    kw.setdefault("temperature", 0.0)
    return InferenceEngine(cfg, variables, **kw)


def _pool_setup(B=3, H=8, KV=2, D=32, bs=8, MB=5, seed=0):
    rng = np.random.RandomState(seed)
    nb = B * MB + 1
    q = jnp.asarray(rng.randn(B, H, D).astype(np.float32) * 0.3)
    kf = jnp.asarray(rng.randn(nb, bs, KV, D).astype(np.float32) * 0.3)
    vf = jnp.asarray(rng.randn(nb, bs, KV, D).astype(np.float32) * 0.3)
    table = jnp.asarray(
        (np.arange(B * MB) + 1).reshape(B, MB).astype(np.int32))
    lengths = jnp.asarray(
        np.array([1, MB * bs // 2 + 3, MB * bs], np.int32)[:B])
    return q, kf, vf, table, lengths


# -- fused kernel parity ----------------------------------------------------


def test_kernel_parity_bf16_pools():
    """Multi-page double-buffered groups (MB=5 does NOT divide an
    8-page group — the trash-padded tail must mask clean) against the
    gather reference, odd lengths included."""
    q, kf, vf, table, lengths = _pool_setup()
    out = paged_decode_attention(q, kf, vf, table, lengths,
                                 pages_per_block=8, interpret=True)
    ref = gather_reference(q, kf, vf, table, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5)


def test_kernel_parity_small_page_groups():
    """pages_per_block smaller than MB exercises >1 double-buffer
    round per slot (the DMA overlap path, not just the warm-up)."""
    q, kf, vf, table, lengths = _pool_setup(MB=6)
    out = paged_decode_attention(q, kf, vf, table, lengths,
                                 pages_per_block=2, interpret=True)
    ref = gather_reference(q, kf, vf, table, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5)


def test_kernel_parity_int8_in_place():
    """int8 code pools + block-shaped scales stream in place; the
    kernel's folded dequant must match the gather path that
    materializes the dequantized view (both read the SAME codes, so
    the comparison is float-exact, not quantization-tolerance)."""
    q, kf, vf, table, lengths = _pool_setup(seed=1)
    k8, ks = quantize_kv_int8(kf)
    v8, vs = quantize_kv_int8(vf)
    out = paged_decode_attention(q, k8, v8, table, lengths,
                                 k_scale=ks, v_scale=vs,
                                 interpret=True)
    ref = gather_reference(q, k8, v8, table, lengths, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5)


def test_kernel_parity_mha_and_block_boundary():
    """MHA (KV == H) and a length on an exact page-group boundary."""
    q, kf, vf, table, _ = _pool_setup(B=2, H=4, KV=4, MB=4, seed=3)
    lengths = jnp.asarray(np.array([32, 8], np.int32))
    out = paged_decode_attention(q, kf, vf, table, lengths,
                                 pages_per_block=4, interpret=True)
    ref = gather_reference(q, kf, vf, table, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5)


# -- the group loop ends at the slot's length --------------------------------

_PAGES, _BS = 2, 8                       # a group = 16 key rows
_ROWS = _PAGES * _BS
_RAGGED = {
    "zero": 0, "one": 1, "rows-1": _ROWS - 1, "rows": _ROWS,
    "rows+1": _ROWS + 1, "mid": 2 * _ROWS + 7, "capacity": 3 * _ROWS,
    "parked": 3 * _ROWS + 1,
}


# ``(pool, scales)`` of what ``_pool_setup`` makes, which is float32:
# as it is, as a TRUE bf16 pool (what the serving cells run: the values
# rounded once, no scales), as int8 codes
_QUANTIZERS = {"f32": lambda x: (x, None),
               "bf16": lambda x: (x.astype(jnp.bfloat16), None),
               "int8": quantize_kv_int8}
# the gather reads the SAME pools, so the bound is the kernel's own
# arithmetic: float32 throughout, or for a bf16 pool p rounded to V's
# dtype once (``test_bf16_tiles_go_to_the_dots_as_stored``'s bound)
_ATOL = {"f32": 3e-5, "bf16": 1e-3, "int8": 3e-5}


def _pools(pool: str, q, kf, vf):
    """``(q, k, v, k_scale, v_scale)`` of the named pool: a bf16 pool's
    query is bf16 too, as the model that writes such a pool hands it."""
    k, ks = _QUANTIZERS[pool](kf)
    v, vs = _QUANTIZERS[pool](vf)
    return (q.astype(k.dtype) if ks is None else q), k, v, ks, vs


def _poison_dead(pools, table, lengths, groups, trash: bool):
    """NaN in every block of every WHOLLY dead group of every slot (and
    in the trash block): what a kernel that stops at the slot's length
    never copies.  Scales are poisoned with their codes."""
    dead = [0] if trash else []
    for b, n in enumerate(lengths):
        live = min(-(-int(n) // _ROWS), groups)
        dead += list(np.asarray(table)[b, live * _PAGES:])
    out = []
    for x in pools:
        if x is None:
            out.append(None)
        elif jnp.issubdtype(x.dtype, jnp.floating):
            out.append(x.at[np.array(dead)].set(jnp.nan))
        else:                            # integer codes have no NaN:
            out.append(x.at[np.array(dead)].set(127))   # their scale has
    return out


@functools.lru_cache(maxsize=None)
def _ragged_run(pool: str, mb: int):
    """One batch holding every length of ``_RAGGED``: the kernel on the
    clean pools, the kernel on the poisoned pools, the gather on the
    clean pools (cached: each case below reads one slot of it)."""
    lengths = np.array(list(_RAGGED.values()), np.int32)
    q, kf, vf, table, _ = _pool_setup(B=len(lengths), bs=_BS, MB=mb,
                                      seed=5)
    q, k, v, ks, vs = _pools(pool, q, kf, vf)
    groups = -(-mb // _PAGES)
    # a padded table's last group names the trash block: a slot at
    # capacity streams it (masked), so only an unpadded table can have
    # it poisoned
    pk, pv, pks, pvs = _poison_dead(
        (k, v, ks, vs), table, lengths, groups, trash=mb % _PAGES == 0)

    def kernel(k, v, ks, vs):
        return np.asarray(paged_decode_attention(
            q, k, v, table, jnp.asarray(lengths), k_scale=ks, v_scale=vs,
            pages_per_block=_PAGES, interpret=True))

    ref = np.asarray(gather_reference(
        q.astype(jnp.float32), k, v, table, jnp.asarray(lengths), ks, vs))
    return kernel(k, v, ks, vs), kernel(pk, pv, pks, pvs), ref


@pytest.mark.parametrize("mb", [6, 7], ids=["even", "padded"])
@pytest.mark.parametrize("length", sorted(_RAGGED))
@pytest.mark.parametrize("pool", sorted(_QUANTIZERS))
def test_kernel_reads_only_live_groups(pool, length, mb):
    """Each slot's group loop runs to ITS length: the output matches
    the gather for every length >= 1 (a parked slot, one past capacity,
    reads the whole table and no further), is zeros for length 0, and
    does not change by one bit when every wholly dead group holds NaN —
    a kernel that streamed them would turn a NaN in a dead V row into a
    NaN output through ``0 x NaN``."""
    clean, poisoned, ref = _ragged_run(pool, mb)
    slot = list(_RAGGED).index(length)
    assert np.isfinite(poisoned[slot]).all()
    np.testing.assert_array_equal(poisoned[slot], clean[slot])
    if _RAGGED[length] == 0:
        np.testing.assert_array_equal(clean[slot], 0.0)
    else:
        np.testing.assert_allclose(clean[slot], ref[slot],
                                   atol=_ATOL[pool])


def test_streamed_rows_is_the_kernels_trip_count():
    """The host arithmetic the engine books with: whole groups up to
    each length, none for 0, never past the (padded) table."""
    lengths = list(_RAGGED.values())
    for mb in (6, 7):
        groups = -(-mb // _PAGES)
        want = sum(min(-(-n // _ROWS), groups) * _ROWS for n in lengths)
        assert streamed_rows(lengths, _BS, mb, _PAGES) == want
    assert streamed_rows([0, 0], _BS, 6, _PAGES) == 0
    # the wrapper's default group: GROUP_ROWS key rows whatever a page
    # is (16 pages of 16, 2 of 128), or the whole of a narrower table
    assert GROUP_ROWS == 256
    for bs, mb in ((16, 145), (128, 41)):
        assert streamed_rows([1, 256, 257, 0], bs, mb) == 256 + 256 + 512
        assert streamed_rows([bs * mb + 9], bs, mb) \
            == -(-bs * mb // 256) * 256
    assert streamed_rows([1, 200], 16, 5) == 80 + 80
    assert streamed_rows([1, 300], 512, 4) == 512 + 512


def test_sibling_kernels_book_what_they_booked():
    """``mla_decode.streamed_rows`` and ``paged_index.scanned_rows`` take
    ``_page_groups`` from the GQA kernel's file with their OWN pages a
    group: the GQA kernel's rule did not move them (figures of the tree
    before it changed, PR 53)."""
    lengths = [0, 1, 1024, 1025, 5000, 40000]
    assert [mla_decode.streamed_rows(lengths, bs, mb)
            for bs, mb in ((128, 259), (16, 145), (128, 5))] \
        == [43008, 7168, 3200]
    assert [paged_index.scanned_rows(lengths, bs, mb)
            for bs, mb in ((128, 259), (16, 145), (128, 5))] \
        == [41472, 6912, 4608]


# -- the slot's edge, at both cells' geometries cut small ---------------------

# 32 query heads over 8 KV heads of 128 and tables of 640 rows: 16-row
# pages (mistral-7b-serve: the head's own scale) and 128-row pages
# (granite-4.0-h-small-serve: the scale its config states), under the
# wrapper's own group rule (256 rows: 3 groups a table, the last one
# padded with the trash block)
_CELLS = {"pages16": dict(bs=16, mb=40, scale=None),
          "pages128": dict(bs=128, mb=5, scale=0.0078125)}
_EDGES = {
    "zero_between_two_live": [300, 0, 0, 77],
    "zero_first_and_last": [0, 257, 640, 0],
    "every_slot_zero": [0, 0, 0, 0],
    "one_group_slots_only": [1, 256, 100, 255],
    "a_slot_fills_its_table": [640, 5, 640, 641],
    "one_past_a_groups_end": [257, 513, 1, 512],
}


@functools.lru_cache(maxsize=None)
def _cell_pools(cell: str, pool: str):
    """Queries, (quantized) pools and a shuffled table at a cell's
    geometry cut small, and the blocks by slot."""
    geo = _CELLS[cell]
    q, kf, vf, table, _ = _pool_setup(B=4, H=32, KV=8, D=128, bs=geo["bs"],
                                      MB=geo["mb"], seed=7)
    rng = np.random.RandomState(11)
    table = jnp.asarray(rng.permutation(4 * geo["mb"]).astype(np.int32)
                        .reshape(4, geo["mb"]) + 1)
    return _pools(pool, q, kf, vf) + (table,)


@pytest.mark.parametrize("edge", sorted(_EDGES))
@pytest.mark.parametrize("pool", sorted(_QUANTIZERS))
@pytest.mark.parametrize("cell", sorted(_CELLS))
def test_stream_crosses_the_slots_edge(cell, pool, edge):
    """The kernel's stream runs on from one slot into the next (a
    slot's first group is started under the last group of the live slot
    before it; a slot of length 0 is stepped over and starts nothing):
    whatever lies between, before or behind the live slots, each slot's
    output is the gather's for its length and zeros for length 0, and
    no block outside a live group is copied (they hold NaN here, and a
    copied NaN reaches the output through ``0 x NaN``)."""
    geo = _CELLS[cell]
    bs, mb = geo["bs"], geo["mb"]
    q, k, v, ks, vs, table = _cell_pools(cell, pool)
    lengths = np.array(_EDGES[edge], np.int32)
    pages = GROUP_ROWS // bs
    live = np.minimum(-(-lengths // GROUP_ROWS), -(-mb // pages)) * pages
    read = {0} if (live > mb).any() else set()      # the padding's block
    for b, n in enumerate(live):
        read |= set(np.asarray(table)[b, :n].tolist())
    unread = np.array(sorted(set(range(k.shape[0])) - read))

    def poisoned(x):
        if x is None:
            return None
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.at[unread].set(jnp.nan)
        return x.at[unread].set(127)     # codes have no NaN: scales do

    out = np.asarray(paged_decode_attention(
        q, poisoned(k), poisoned(v), table, jnp.asarray(lengths),
        k_scale=poisoned(ks), v_scale=poisoned(vs), scale=geo["scale"],
        interpret=True))
    want = q.astype(jnp.float32)
    if geo["scale"] is not None:
        want = want * (geo["scale"] * 128 ** 0.5)
    ref = np.asarray(gather_reference(
        want, k, v, table, jnp.asarray(lengths), ks, vs))
    assert np.isfinite(out).all()
    for b, n in enumerate(lengths):
        if n == 0:
            np.testing.assert_array_equal(out[b], 0.0)
        else:
            np.testing.assert_allclose(out[b], ref[b], atol=_ATOL[pool])


def _dots(jaxpr):
    """Every ``dot_general`` under ``jaxpr``, kernels' bodies included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _dots(sub)


@pytest.mark.parametrize("cell", sorted(_CELLS))
def test_bf16_tiles_go_to_the_dots_as_stored(cell):
    """A bf16 pool's K and V tiles and the query reach both dots in
    bf16 with a float32 result (no float32 copy of a tile is made; p is
    rounded to V's dtype once, which is what the wider bound here
    allows: 2^-9 of a probability), across a slot's edge."""
    geo = _CELLS[cell]
    q, k, v, _, _, table = _cell_pools(cell, "bf16")
    assert {x.dtype for x in (q, k, v)} == {jnp.dtype(jnp.bfloat16)}
    lengths = jnp.asarray(_EDGES["zero_between_two_live"], jnp.int32)

    def run(q, k, v):
        return paged_decode_attention(q, k, v, table, lengths,
                                      scale=geo["scale"], interpret=True)

    dots = list(_dots(jax.make_jaxpr(run)(q, k, v).jaxpr))
    assert len(dots) == 2
    for eqn in dots:
        assert [x.aval.dtype for x in eqn.invars] == [jnp.bfloat16] * 2
        assert eqn.outvars[0].aval.dtype == jnp.float32
    want = q.astype(jnp.float32)
    if geo["scale"] is not None:
        want = want * (geo["scale"] * 128 ** 0.5)
    ref = gather_reference(want, k, v, table, lengths)
    np.testing.assert_allclose(
        np.asarray(run(q, k, v))[[0, 3]], np.asarray(ref)[[0, 3]],
        atol=1e-3)


def test_engine_parked_and_idle_slots_read_nothing(setup):
    """Through the real engine, chunked prefill on: while the long
    prompt prefills its slot is PARKED past its allocation, the third
    slot is idle all along and the short request's slot is idle (at a
    stale position) once it finishes.  The kernel engine is handed
    length 0 for each of them and reproduces the gather engine's greedy
    outputs exactly; what it streamed is within 2x of what its decoding
    slots could see, where every group of every slot on every forward
    (the table's width) is many times that."""
    cfg, _ = setup
    short, long_ = _prompts(cfg, 1, 40)[0], _prompts(cfg, 1, 80, seed=4)[0]

    def run(impl):
        eng = _engine(setup, max_slots=3, paged=True, block_size=8,
                      prefill_chunk=16, attention_impl=impl)
        rids = [eng.add_request(short, 12), eng.add_request(long_, 6)]
        parked_beside_decode = 0
        while eng.has_work:
            eng.step()
            parked_beside_decode += int(
                eng._prefilling.any() and eng.stats.decode_forwards > 0)
        assert parked_beside_decode >= 1
        res = {r.rid: np.asarray(r.output) for r in eng._finished}
        return eng, [res[r] for r in rids]

    xla, base = run("xla")
    kern, outs = run("pallas")
    for a, b in zip(base, outs):
        np.testing.assert_array_equal(a, b)
    assert xla.stats.kv_rows_live == xla.stats.kv_rows_streamed == 0
    st = kern.stats
    # 18 generated tokens less the two that prefill gives: every live
    # row is a key some decoding slot attended to
    assert st.kv_rows_live >= sum(range(41, 52)) + sum(range(81, 86))
    assert 1.0 <= st.kv_stream_ratio < 2.0
    every_group = st.decode_forwards * kern.max_slots * streamed_rows(
        [kern._cache_len], kern.block_size, kern._max_blocks)
    assert every_group / st.kv_rows_live > 4.0
    from dlrover_tpu.serving.router.metrics import RouterMetrics
    from dlrover_tpu.serving.router.replica import InferenceEngineAdapter

    m = RouterMetrics()
    m.observe_engine_metrics([InferenceEngineAdapter(kern).engine_metrics(),
                              InferenceEngineAdapter(xla).engine_metrics()])
    assert m.metrics()["serving_paged_kv_stream_ratio"] \
        == pytest.approx(st.kv_stream_ratio)


# -- auto-pick contract -----------------------------------------------------


def test_resolve_attention_impl_never_picks_slower():
    """THE auto contract, on the pure decision: whichever side
    measures faster is picked; no measurement falls back to the
    always-available gather; explicit requests are honored."""
    assert resolve_attention_impl(
        "auto", {"xla": 2.0, "pallas": 1.0}) == "pallas"
    assert resolve_attention_impl(
        "auto", {"xla": 1.0, "pallas": 2.0}) == "xla"
    assert resolve_attention_impl("auto", None) == "xla"
    assert resolve_attention_impl("auto", {}) == "xla"
    assert resolve_attention_impl("xla", {"pallas": 0.0}) == "xla"
    assert resolve_attention_impl("pallas", None) == "pallas"
    with pytest.raises(ValueError, match="not supported"):
        resolve_attention_impl("fused", None)


def test_engine_attention_impl_resolution(setup):
    """Engine-side edges: auto on a non-TPU backend resolves to the
    gather path (the interpret-mode kernel is a parity harness, not a
    perf candidate), explicit pallas is honored anywhere paged,
    pallas without paging refuses, junk refuses."""
    eng = _engine(setup, paged=True, block_size=8)
    assert eng.attention_impl_requested == "auto"
    assert eng.attention_impl == "xla"       # CPU backend, no timings
    assert eng.attention_impl_us is None
    forced = _engine(setup, paged=True, block_size=8,
                     attention_impl="pallas")
    assert forced.attention_impl == "pallas"
    dense = _engine(setup)
    assert dense.attention_impl == "xla"
    with pytest.raises(ValueError, match="paged=True"):
        _engine(setup, attention_impl="pallas")
    with pytest.raises(ValueError, match="not supported"):
        _engine(setup, paged=True, attention_impl="cudnn")


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_engine_greedy_parity_under_pallas_impl(setup, kv_dtype):
    """End to end through the real engine: forcing the fused kernel
    (interpret mode on CPU) reproduces the gather engine's exact
    greedy outputs — native (float32 here) and int8 pools."""
    cfg, _ = setup
    prompts = [p for p in _prompts(cfg, 2, 20)] + \
        [p for p in _prompts(cfg, 1, 7, seed=3)]

    def run(**kw):
        eng = _engine(setup, paged=True, block_size=8, **kw)
        rids = [eng.add_request(p, 6) for p in prompts]
        res = eng.run()
        return [res[r] for r in rids]

    base = run(kv_dtype=kv_dtype)
    kern = run(kv_dtype=kv_dtype, attention_impl="pallas")
    for a, b in zip(base, kern):
        np.testing.assert_array_equal(a, b)


def test_measure_paged_attention_reports_both_impls():
    """The measurement the auto-pick consumes: one positive wall time
    per impl on the supplied operands (interpret mode here — the
    numbers are meaningless as perf, which is exactly why engine auto
    refuses to use them off-TPU; the SHAPE of the evidence is what
    this pins)."""
    q, kf, vf, table, lengths = _pool_setup(B=2, MB=2)
    t = measure_paged_attention(q, kf, vf, table, lengths, trials=1,
                                interpret=True)
    assert set(t) == {"xla", "pallas"} and all(
        v > 0 for v in t.values())


# -- KV-budget single source ------------------------------------------------


def test_kv_budget_multiplier_is_the_single_source():
    """The formula itself at the serving head dims: bf16 int8 ~2x,
    native 1.0, junk refused."""
    bf16 = jnp.bfloat16
    assert kv_budget_multiplier(bf16, 64, "int8") >= 1.9
    assert kv_budget_multiplier(bf16, 128, "int8") >= 1.9
    assert kv_budget_multiplier(bf16, 64, None) == 1.0
    assert kv_budget_multiplier(bf16, 64, "bf16") == 1.0
    with pytest.raises(ValueError, match="unknown kv_dtype"):
        kv_budget_multiplier(bf16, 64, "fp8")


def test_budget_feeds_pool_engine_and_ledger_identically(setup):
    """The dedupe regression: for an int8 pool, the engine's pool
    scaling, ``InferenceEngine.kv_budget_x`` and the adapter's
    router-side ledger all derive from ``kv_budget_multiplier`` — no
    mirrored arithmetic anywhere to drift apart."""
    from dlrover_tpu.serving.router.replica import InferenceEngineAdapter

    cfg, _ = setup
    budget = 12
    native = _engine(setup, paged=True, block_size=8,
                     cache_blocks=budget)
    free_native = InferenceEngineAdapter(native).blocks_free()
    eng = _engine(setup, paged=True, block_size=8,
                  cache_blocks=budget, kv_dtype="int8")
    x = kv_budget_multiplier(cfg.dtype, cfg.head_dim_, "int8")
    adapter = InferenceEngineAdapter(eng)
    # one source: the engine's multiplier IS the formula, and the
    # pool it scales is the only thing the ledger ever reads
    assert eng.kv_budget_x == x
    assert eng._blockmgr.num_blocks == int(budget * x)
    # and the placement ledger sees the multiplied pool
    assert adapter.blocks_free() == eng._blockmgr.num_blocks - 1
    assert adapter.blocks_free() >= (x / 1.05) * free_native

    def pool_bytes(e):
        c = e._cache
        total = 0
        for key in ("k_pool", "v_pool", "k_scale", "v_scale"):
            if key in c:
                total += sum(
                    x.size * x.dtype.itemsize for x in c[key])
        return total

    # the quantized pool's bytes stay within the native budget's bytes
    assert pool_bytes(eng) <= pool_bytes(native) * 1.05


# -- same-step batched prefill ----------------------------------------------


def test_batched_prefill_deserializes_concurrent_ttft(setup):
    """N long prompts admitted together reach their first tokens in
    the SAME engine step (their chunks ride one batched dispatch per
    step) — the round-robin one-per-step scheme made the i-th prompt
    wait ~i times the first's TTFT.  The cursor invariant holds for
    every prefilling slot every step."""
    cfg, _ = setup
    eng = _engine(setup, max_slots=3, prefill_chunk=16, paged=True,
                  block_size=8)
    longs = [_prompts(cfg, 1, 64, seed=s)[0] for s in (7, 8, 9)]
    rids = [eng.add_request(p, 4) for p in longs]
    ttft_step = {}
    cursors = {r: 0 for r in rids}
    for step_n in range(1, 16):
        finished = eng.step()
        for s, r in enumerate(eng._slot_req):
            if r is None or r.rid not in cursors:
                continue
            if eng._prefilling[s]:
                cur = int(eng._prefill_pos[s])
                assert 0 < cur - cursors[r.rid] <= eng.prefill_chunk
                cursors[r.rid] = cur
        # a short-budget request can finish INSIDE the step its
        # prefill completes (first token + a decode chunk) — first
        # tokens are read from live slots AND the finished list
        for r in list(eng._slot_req) + list(finished):
            if r is not None and r.rid in cursors and r.output \
                    and r.rid not in ttft_step:
                ttft_step[r.rid] = step_n
        if len(ttft_step) == len(rids):
            break
    assert set(ttft_step) == set(rids)
    # all three first tokens on the SAME step: no serialization
    assert len(set(ttft_step.values())) == 1, ttft_step
    # one batched dispatch per step: chunks advanced 3 slot-chunks
    # per dispatch while all three prefilled
    assert eng.stats.prefill_chunk_slots > eng.stats.prefill_chunks
    res = eng.run()
    assert all(len(res[r]) == 4 for r in rids)


def test_batched_prefill_greedy_parity_vs_monolithic(setup):
    """Batched same-step chunks must produce the monolithic prefill's
    exact greedy outputs — the chunk program is verify_step rows,
    independent by construction (dense AND paged)."""
    cfg, _ = setup
    longs = [_prompts(cfg, 1, 48, seed=s)[0] for s in (4, 5)]
    shorts = [p for p in _prompts(cfg, 2, 6, seed=6)]

    def run(**kw):
        eng = _engine(setup, max_slots=4, **kw)
        rids = [eng.add_request(p, 8) for p in longs + shorts]
        res = eng.run()
        return [res[r] for r in rids]

    base = run()
    for extra in (dict(prefill_chunk=16),
                  dict(prefill_chunk=16, paged=True, block_size=8),
                  dict(prefill_chunk=16, paged=True, block_size=8,
                       kv_dtype="int8")):
        for a, b in zip(base, run(**extra)):
            np.testing.assert_array_equal(a, b)


def test_cancel_mid_batched_prefill_reclaims_everything(setup):
    """Cancelling ONE of several batch-prefilling prompts frees its
    slot + lifetime blocks immediately; the surviving prompts keep
    advancing and the books balance after the drain."""
    cfg, _ = setup
    eng = _engine(setup, max_slots=3, prefill_chunk=16, paged=True,
                  block_size=8)
    total = eng._blockmgr.num_blocks - 1
    longs = [_prompts(cfg, 1, 64, seed=s)[0] for s in (1, 2)]
    r1, r2 = [eng.add_request(p, 4) for p in longs]
    eng.step()
    assert int(eng._prefilling.sum()) == 2
    victim_slot = next(s for s, r in enumerate(eng._slot_req)
                       if r is not None and r.rid == r1)
    held = eng._blockmgr.available_blocks
    assert held < total
    assert eng.cancel(r1) is True
    assert eng._slot_req[victim_slot] is None
    assert not eng._prefilling[victim_slot]
    assert eng._blockmgr.available_blocks > held
    res = eng.run()
    assert r1 not in res and len(res[r2]) == 4
    assert eng._blockmgr.available_blocks == total, (
        "cancel mid-batched-prefill leaked blocks")


# -- metric plumbing --------------------------------------------------------


def test_new_metric_families_flow_to_router(setup):
    """attention impl + kernel seconds + quantized blocks: engine ->
    adapter.engine_metrics -> RouterMetrics -> /metrics dict + the
    labeled serving_attention_impl render; all names registered."""
    from dlrover_tpu.serving.router.metrics import RouterMetrics
    from dlrover_tpu.serving.router.replica import InferenceEngineAdapter
    from dlrover_tpu.utils.metric_registry import (
        METRIC_HELP,
        METRIC_LABELS,
    )

    eng = _engine(setup, paged=True, block_size=8, kv_dtype="int8",
                  attention_impl="pallas")
    for p in _prompts(setup[0], 2, 12):
        eng.add_request(p, 4)
    eng.run()
    em = InferenceEngineAdapter(eng).engine_metrics()
    assert em["attention_impl_pallas"] == 1.0
    assert em["paged_kernel_step_seconds"] > 0.0
    assert em["kv_quant_blocks"] == eng.kv_quant_blocks > 0

    m = RouterMetrics()
    m.observe_engine_metrics([em, None])
    out = m.metrics()
    assert out["serving_kv_quant_blocks"] == em["kv_quant_blocks"]
    assert out["serving_paged_kernel_step_seconds"] == \
        em["paged_kernel_step_seconds"]
    text = m.render_labeled()
    assert 'serving_attention_impl{impl="pallas"} 1' in text
    assert 'serving_attention_impl{impl="xla"} 0' in text
    for name in ("serving_attention_impl",
                 "serving_paged_kernel_step_seconds",
                 "serving_kv_quant_blocks"):
        assert name in METRIC_HELP
    assert METRIC_LABELS["serving_attention_impl"] == ("impl",)
    # reporters leaving zeroes the aggregates (no frozen dead-fleet
    # values) and drops both labeled series to 0
    m.observe_engine_metrics([None])
    assert m.metrics()["serving_kv_quant_blocks"] == 0.0
    assert 'serving_attention_impl{impl="pallas"} 0' in \
        m.render_labeled()


def test_dense_replicas_stay_out_of_the_impl_gauge(setup):
    """Review finding: a dense (non-paged) engine has NO paged
    attention path, so it must not report attention_impl keys at all
    — otherwise the labeled xla series could never reach zero and the
    fleet's xla->pallas crossover would be invisible."""
    from dlrover_tpu.serving.router.metrics import RouterMetrics
    from dlrover_tpu.serving.router.replica import InferenceEngineAdapter

    dense = _engine(setup)
    em = InferenceEngineAdapter(dense).engine_metrics()
    assert "attention_impl_pallas" not in em
    assert "paged_kernel_step_seconds" not in em
    m = RouterMetrics()
    m.observe_engine_metrics([em])
    assert m.attention_impls == {}
    assert 'serving_attention_impl{impl="xla"} 0' in m.render_labeled()


def test_worker_flags_reach_the_engine(monkeypatch):
    """--attention-impl / --kv-dtype int8 plumb end-to-end into the
    llama engine build (the worker-side half of the remote fleet's
    knob contract)."""
    import argparse

    from dlrover_tpu.serving.remote import worker as worker_mod

    captured = {}

    class _FakeEngine:
        def __init__(self, *a, **kw):
            captured.update(kw)
            raise RuntimeError("stop after capture")

    monkeypatch.setattr(
        "dlrover_tpu.serving.engine.InferenceEngine", _FakeEngine)
    args = argparse.Namespace(
        max_len=256, seed=0, slots=2, block_size=8,
        kv_dtype="int8", prefill_chunk=32, speculative_k=0,
        attention_impl="pallas", model="tiny", layers=0,
        dtype="float32", blocks=None, report_file="")
    with pytest.raises(RuntimeError, match="stop after capture"):
        worker_mod._build_llama_engine(args)
    assert captured["kv_dtype"] == "int8"
    assert captured["attention_impl"] == "pallas"
    assert captured["prefill_chunk"] == 32


# -- what a worker does before it announces ---------------------------------


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_kernel_parity_self_check_within_the_interpret_bound(kv_dtype):
    """The self-check a serving worker reports before it announces
    (``kernel_parity``: seeded pools at an engine's geometry, odd
    lengths, a random table) holds the interpreted kernel to the bound
    the hand-built cases above use."""
    got = kernel_parity(
        slots=3, max_blocks=5, block_size=8, num_heads=8,
        num_kv_heads=2, head_dim=32, dtype=jnp.float32,
        kv_dtype=kv_dtype, interpret=True, seed=1)
    assert got["finite"] and got["max_abs_err"] <= 3e-5, got
    assert got["table_shape"] == [3, 5] and got["pool_shape"][0] == 16
    assert got["kv_dtype"] == (kv_dtype or "bf16")


@pytest.mark.parametrize("kw,programs", [
    # decode chunk + every bucket (32, 64, 96) at both group sizes
    (dict(), 1 + 2 * 3),
    (dict(paged=True, block_size=8), 1 + 2 * 3),
    # chunked prefill on: only the buckets <= prefill_chunk reach the
    # bucketed program, plus the chunk program at both group sizes
    (dict(paged=True, block_size=8, kv_dtype="int8", prefill_chunk=32),
     1 + 2 * (1 + 1)),
    (dict(paged=True, block_size=8, speculative_k=4), 2 + 2 * 3),
    (dict(speculative_k=4, prefill_chunk=32), 2 + 2 * (1 + 1)),
])
def test_warmup_compiles_every_dispatch_and_changes_no_output(
        setup, kw, programs):
    """``warmup()`` runs every program the engine can dispatch on the
    engine that then serves: it must leave the cache, the table and
    the sampling key as an untouched engine's, so the same requests
    give the same tokens — and the requests that follow compile
    nothing new."""
    cfg, _ = setup
    prompts = [_prompts(cfg, 1, n, seed=n)[0] for n in (48, 7, 20, 70)]

    def run(warm):
        eng = _engine(setup, **kw)
        if warm:
            assert eng.warmup() == programs
            sizes = [f._cache_size() for f in (
                eng._chunk_fn, eng._insert_fn, eng._prefill_chunk_fn,
                eng._spec_fn) if f is not None]
        rids = [eng.add_request(p, 6) for p in prompts]
        res = eng.run()
        if warm:
            assert sizes == [f._cache_size() for f in (
                eng._chunk_fn, eng._insert_fn, eng._prefill_chunk_fn,
                eng._spec_fn) if f is not None], "a request compiled"
        return [res[r] for r in rids]

    for a, b in zip(run(False), run(True)):
        np.testing.assert_array_equal(a, b)


# -- TPU microbench ---------------------------------------------------------


@pytest.mark.slow
def test_tpu_kernel_microbench_stub():
    """TPU-marked kernel microbench: on a TPU backend, measure the
    fused kernel vs the gather at a serving-class geometry and record
    the crossover evidence; anywhere else, skip cleanly — never a
    fake verdict."""
    if jax.default_backend() in ("cpu", "gpu"):
        pytest.skip("paged-attention microbench needs a TPU backend")
    rng = np.random.RandomState(0)
    B, H, KV, D, bs, MB = 8, 16, 4, 128, 16, 96
    nb = B * MB + 1
    q = jnp.asarray(rng.randn(B, H, D).astype(np.float32)).astype(
        jnp.bfloat16)
    kf = jnp.asarray(
        rng.randn(nb, bs, KV, D).astype(np.float32) * 0.3)
    k8, ks = quantize_kv_int8(kf)
    v8, vs = quantize_kv_int8(kf)
    table = jnp.asarray(
        (np.arange(B * MB) % (nb - 1) + 1)
        .reshape(B, MB).astype(np.int32))
    lengths = jnp.full((B,), MB * bs, jnp.int32)
    t = measure_paged_attention(q, k8, v8, table, lengths, ks, vs,
                                trials=5)
    assert t["xla"] > 0 and t["pallas"] > 0
    # the structural claim this PR makes: reading code-width bytes
    # once beats materialize-then-restream on quantized pools
    assert t["pallas"] <= t["xla"] * 1.2, t
