"""Decode raw-speed push (ISSUE 13): chunked prefill, speculative
accept into paged KV, and int8 paged KV blocks.

Covers the three engine optimizations and their contracts:

- **chunked prefill** (``prefill_chunk``): greedy parity with the
  monolithic path, the ordering invariant (no slot ever emits a token
  out of order; every decode slot keeps its cadence while a long
  prompt prefills), partial-prefill cursor state across dispatches,
  and cancel-mid-prefill reclaiming the slot + its KV blocks (the PR 5
  reclamation contract extended to half-prefilled slots);
- **int8 paged KV** (``kv_dtype="int8"``): quantization round-trip
  bound, logit drift bounded vs the native pool on a seeded small
  model, greedy token agreement, and the >=1.9x block-budget
  multiplier feeding the engine pool and the router's placement
  ledger;
- **speculative accept into paged KV**: books balance after a drain
  (blocks allocated == blocks freed) with acceptance actually
  happening, and the ``serving_spec_accept_ratio`` /
  ``serving_kv_quant_blocks`` / ``serving_prefill_chunk_seconds``
  metric plumbing from EngineStats through the adapter to the
  router's /metrics dict.

The nightly soak at the bottom (``-m slow``) drives Pareto heavy-tail
prompt lengths (serving/router/loadgen's distribution) with seeded
mid-flight cancels and asserts the stall bound + books under chaos.
"""

import json
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
from dlrover_tpu.serving.engine import InferenceEngine


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


# -- chunked prefill --------------------------------------------------------


def test_chunked_prefill_greedy_parity_dense_and_paged(setup):
    """Chunked prefill must produce the monolithic path's exact greedy
    outputs — dense cache, paged cache, and paged+int8 all chunk the
    same way (the chunk program is verify_step, i.e. the decode
    program, by construction)."""
    cfg, _ = setup
    prompts = [p for p in _prompts(cfg, 3, 40)] + \
        [p for p in _prompts(cfg, 2, 7, seed=3)]

    def run(**kw):
        eng = _engine(setup, **kw)
        rids = [eng.add_request(p, 10) for p in prompts]
        res = eng.run()
        return [res[r] for r in rids]

    base = run()
    for extra in (
        dict(prefill_chunk=16),
        dict(prefill_chunk=16, paged=True, block_size=8),
    ):
        for a, b in zip(base, run(**extra)):
            np.testing.assert_array_equal(a, b)


def test_chunked_prefill_interleaves_decode_no_stall(setup):
    """THE stall-bound invariant: while a long prompt prefills chunk by
    chunk, every already-decoding slot gains tokens on EVERY step (no
    inter-token gap beyond one step), tokens stay in order, and the
    long prompt's cursor advances monotonically across dispatches."""
    cfg, _ = setup
    eng = _engine(setup, max_slots=3, prefill_chunk=16)
    short = _prompts(cfg, 2, 6)
    long_prompt = _prompts(cfg, 1, 64, seed=7)[0]
    short_reqs = [eng.add_request(p, 40) for p in short]
    # run until both shorts are decoding (small buckets may themselves
    # chunk-admit one slot per step — bounded work IS the contract)
    for _ in range(8):
        eng.step()
        reqs = {r.rid: r for r in eng._slot_req if r is not None}
        if set(reqs) == set(short_reqs) and all(
                not eng._prefilling[s]
                for s, r in enumerate(eng._slot_req) if r is not None):
            break
    assert set(reqs) == set(short_reqs)
    long_rid = eng.add_request(long_prompt, 4)
    prev_counts = {r: len(reqs[r].output) for r in short_reqs}
    prev_cursor = 0
    prefix_snapshots = {r: list(reqs[r].output) for r in short_reqs}
    steps_while_prefilling = 0
    while True:
        eng.step()
        slot = next(
            (s for s, r in enumerate(eng._slot_req)
             if r is not None and r.rid == long_rid), None)
        prefilling = slot is not None and eng._prefilling[slot]
        if prefilling:
            steps_while_prefilling += 1
            # the real_len cursor advances by exactly one bounded chunk
            cursor = int(eng._prefill_pos[slot])
            assert 0 < cursor - prev_cursor <= eng.prefill_chunk
            prev_cursor = cursor
            for r in short_reqs:
                out = reqs[r].output
                # cadence: every decoding slot gained tokens this step
                assert len(out) > prev_counts[r], (
                    "a decode slot stalled while the long prompt "
                    "prefilled"
                )
                # ordering: earlier tokens never rewritten
                assert out[: len(prefix_snapshots[r])] == \
                    prefix_snapshots[r]
                prev_counts[r] = len(out)
                prefix_snapshots[r] = list(out)
        else:
            break
    # a 64-token prompt at chunk 16 needs 4 chunk dispatches; the loop
    # observes the 3 that leave the slot still prefilling
    assert steps_while_prefilling >= 3
    res = eng.run()
    assert len(res[long_rid]) == 4
    for r in short_reqs:
        assert len(res[r]) == 40


def test_chunked_prefill_admissions_vs_dispatch_counters(setup):
    """The satellite fix: ``prefill_calls`` counts dispatches,
    ``prefill_admissions`` counts requests — batched short-prompt
    admission keeps calls < admissions, chunked long prompts push
    calls > admissions.  Both must be visible or the batched-prefill
    win is only inferrable."""
    cfg, _ = setup
    eng = _engine(setup, max_slots=4)
    for p in _prompts(cfg, 4, 12):
        eng.add_request(p, 2)
    eng.run()
    assert eng.stats.prefill_admissions == 4
    assert eng.stats.prefill_calls == 1  # one batched dispatch

    eng2 = _engine(setup, max_slots=2, prefill_chunk=8)
    rid = eng2.add_request(_prompts(cfg, 1, 64, seed=5)[0], 2)
    eng2.run()
    assert eng2.stats.prefill_admissions == 1
    assert eng2.stats.prefill_chunks == 8  # 64 tokens / 8 per chunk
    assert eng2.stats.prefill_calls == eng2.stats.prefill_chunks
    assert eng2.stats.prefill_chunk_seconds > 0.0
    assert rid is not None


def test_cancel_mid_prefill_reclaims_slot_and_blocks(setup):
    """PR 5's reclamation contract extended to half-prefilled slots:
    cancelling a request whose prompt is mid-chunked-prefill frees its
    slot AND its lifetime block allocation immediately, and the books
    still balance after a full drain."""
    from dlrover_tpu.serving.router.replica import InferenceEngineAdapter

    cfg, _ = setup
    eng = _engine(setup, max_slots=2, prefill_chunk=8, paged=True,
                  block_size=8)
    adapter = InferenceEngineAdapter(eng)
    total = eng._blockmgr.num_blocks - 1  # minus the trash sink
    long_rid = eng.add_request(_prompts(cfg, 1, 64, seed=9)[0], 8)
    eng.step()
    slot = next(s for s, r in enumerate(eng._slot_req)
                if r is not None and r.rid == long_rid)
    assert eng._prefilling[slot] and 0 < eng._prefill_pos[slot] < 64
    assert eng._blockmgr.available_blocks < total
    assert adapter.cancel(long_rid) is True
    assert eng._slot_req[slot] is None
    assert not eng._prefilling[slot]
    assert eng._blockmgr.available_blocks == total, (
        "cancel mid-prefill must free the lifetime block allocation"
    )
    # the slot is genuinely reusable: fresh traffic completes cleanly
    rids = [eng.add_request(p, 6) for p in _prompts(cfg, 3, 12)]
    res = eng.run()
    assert all(res[r].size == 6 for r in rids)
    assert eng._blockmgr.available_blocks == total


def test_cancel_queued_and_finished_via_engine(setup):
    """Engine-level cancel covers the queue (never admitted) and the
    already-finished no-op, same True contract as the adapter."""
    cfg, _ = setup
    eng = _engine(setup, max_slots=1)
    p = _prompts(cfg, 2, 8)
    r1 = eng.add_request(p[0], 2)
    r2 = eng.add_request(p[1], 2)  # waits in the engine queue
    assert eng.cancel(r2) is True
    res = eng.run()
    assert r2 not in res and res[r1].size == 2
    assert eng.cancel(r1) is True  # finished: delivered no-op


# -- int8 paged KV ----------------------------------------------------------


def test_kv_int8_roundtrip_bound():
    """Per-vector symmetric int8: |x - dq(q(x))| <= amax/127 plus the
    bf16 scale's rounding (2^-8 relative) — the numeric floor under
    the engine-level drift tests."""
    from dlrover_tpu.models.quantize import (
        dequantize_kv_int8,
        quantize_kv_int8,
    )

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 6, 2, 16).astype(np.float32)) * 3.0
    q, scale = quantize_kv_int8(x)
    assert q.dtype == jnp.int8 and scale.shape == x.shape[:-1]
    back = dequantize_kv_int8(q, scale, jnp.float32)
    amax = np.max(np.abs(np.asarray(x)), axis=-1, keepdims=True)
    bound = amax / 127.0 * (1.0 + 2.0 ** -7) + amax * 2.0 ** -8
    assert np.all(np.abs(np.asarray(back) - np.asarray(x)) <= bound)


def test_kv_int8_logit_drift_bounded_vs_native(setup):
    """Seeded small model, identical prompts admitted into a native
    and an int8 paged engine: the next-token logits off the quantized
    cache must stay within a small fraction of the native logit range
    — right after the prefill AND along a 12-token continuation
    teacher-forced into both engines, so the quantized K/V that decode
    itself appends is held to the same bound.

    The bound is on logits, not on greedy tokens: a random-weight
    model's top-2 margins are smaller than any rounding, so its argmax
    flips on noise the bound allows (token agreement would need a
    model with real margins)."""
    from dlrover_tpu.serving.model import verify_step

    cfg, variables = setup
    prompts = _prompts(cfg, 2, 24, seed=11)
    steps = 12

    def admitted(kv_dtype):
        eng = _engine(setup, paged=True, block_size=8,
                      kv_dtype=kv_dtype)
        for p in prompts:
            eng.add_request(p, steps + 4)
        eng._admit()
        if eng._table_dirty:
            eng._push_table()
        return eng

    ref_eng, quant_eng = admitted(None), admitted("int8")
    np.testing.assert_array_equal(
        ref_eng._positions, quant_eng._positions)
    # the SAME tokens into both: the native engine's committed token,
    # then a seeded continuation
    forced = np.random.RandomState(5).randint(
        0, cfg.vocab_size, (len(prompts), steps)).astype(np.int32)
    forced[:, 0] = ref_eng._tokens

    def logits_along(eng):
        logits, _ = verify_step(
            eng.params, cfg, eng._cache, jnp.asarray(forced),
            jnp.asarray(ref_eng._positions),
        )
        return np.asarray(logits)               # [B, steps, V]

    ref, quant = logits_along(ref_eng), logits_along(quant_eng)
    assert ref.shape == (len(prompts), steps, cfg.vocab_size)
    spread = float(ref.max() - ref.min())
    drift = np.max(np.abs(quant - ref), axis=(0, 2))   # per position
    assert drift[0] <= 0.05 * spread, (drift[0], spread)
    assert drift.max() <= 0.05 * spread, (drift, spread)
    # and the quantized cache is really in play: not bit-identical
    assert drift.max() > 0.0


def test_kv_int8_budget_multiplier_feeds_pool_and_ledger(setup):
    """The HBM story: the same ``cache_blocks`` budget yields >=1.9x
    the blocks under int8 pools, the engine's admission sees them, and
    the adapter's ``blocks_free`` (the router placement ledger's feed)
    reports the multiplied budget."""
    from dlrover_tpu.serving.router.replica import InferenceEngineAdapter

    budget = 12
    native = _engine(setup, paged=True, block_size=8,
                     cache_blocks=budget)
    quant = _engine(setup, paged=True, block_size=8,
                    cache_blocks=budget, kv_dtype="int8")
    assert native._blockmgr.num_blocks == budget
    assert quant.kv_budget_x >= 1.9
    assert quant._blockmgr.num_blocks == int(budget * quant.kv_budget_x)
    assert quant.kv_quant_blocks == quant._blockmgr.num_blocks
    assert native.kv_quant_blocks == 0
    free_n = InferenceEngineAdapter(native).blocks_free()
    free_q = InferenceEngineAdapter(quant).blocks_free()
    assert free_q >= 1.9 * free_n
    # int8 pool bytes stay within the native budget's bytes
    def pool_bytes(eng):
        c = eng._cache
        total = sum(x.size * x.dtype.itemsize for x in c["k_pool"])
        total += sum(x.size * x.dtype.itemsize for x in c["v_pool"])
        for key in ("k_scale", "v_scale"):
            if key in c:
                total += sum(
                    x.size * x.dtype.itemsize for x in c[key])
        return total

    assert pool_bytes(quant) <= pool_bytes(native) * 1.05


def test_kv_dtype_validation(setup):
    with pytest.raises(ValueError, match="paged=True"):
        _engine(setup, kv_dtype="int8")
    with pytest.raises(ValueError, match="not supported"):
        _engine(setup, paged=True, kv_dtype="fp8")
    # the packed pool left the tree (PR 60): refused by name like any
    # unknown value, paged or not
    for paged in (False, True):
        with pytest.raises(ValueError, match="'int4' not supported"):
            _engine(setup, paged=paged, kv_dtype="int4")


# -- speculative accept into paged KV --------------------------------------


def test_paged_spec_accept_books_balance(setup):
    """Speculative rounds commit accepted drafts through
    scatter_tokens into BlockManager blocks (incl. the spec-slack
    overflow): after a full drain every allocated block is back
    (available == usable pool), acceptance actually happened, and the
    accept-ratio stat is live."""
    cfg, _ = setup
    for kv_dtype in (None, "int8"):
        eng = _engine(setup, max_slots=2, speculative_k=4, paged=True,
                      block_size=8, kv_dtype=kv_dtype)
        prompt = np.tile(np.array([5, 6, 7], np.int32), 8)
        rids = [eng.add_request(prompt, 16) for _ in range(4)]
        res = eng.run()
        assert all(res[r].size == 16 for r in rids)
        assert eng.stats.spec_proposed > 0
        assert eng.stats.spec_accepted > 0, (
            "repetitive prompt must yield accepted drafts"
        )
        assert 0.0 < eng.stats.spec_accept_ratio <= 1.0
        assert eng._blockmgr.available_blocks == \
            eng._blockmgr.num_blocks - 1, (
            "paged speculative decode leaked blocks"
        )


def test_spec_chunked_prefill_composes(setup):
    """All three optimizations at once (spec + chunked prefill + int8
    paged KV) drain cleanly with balanced books and exact output
    lengths."""
    cfg, _ = setup
    eng = _engine(setup, max_slots=2, speculative_k=4, paged=True,
                  block_size=8, kv_dtype="int8", prefill_chunk=16)
    prompt = np.tile(np.array([5, 6, 7], np.int32), 16)  # 48 tokens
    rids = [eng.add_request(prompt, 12) for _ in range(3)]
    res = eng.run()
    assert all(res[r].size == 12 for r in rids)
    assert eng._blockmgr.available_blocks == \
        eng._blockmgr.num_blocks - 1


# -- metric plumbing --------------------------------------------------------


def test_engine_metrics_surface_on_router_metrics(setup):
    """EngineStats -> adapter.engine_metrics -> router sweep ->
    RouterMetrics.metrics(): the new families are live on the /metrics
    dict with real values after traffic on a real paged spec engine."""
    from dlrover_tpu.serving.router import (
        ContinuousBatchScheduler,
        RequestGateway,
        ServingRouter,
    )
    from dlrover_tpu.serving.router.replica import InferenceEngineAdapter

    cfg, _ = setup
    eng = _engine(setup, max_slots=2, speculative_k=4, paged=True,
                  block_size=8, kv_dtype="int8", prefill_chunk=16)
    router = ServingRouter(
        gateway=RequestGateway(max_pending=16),
        scheduler=ContinuousBatchScheduler(block_size=8),
    )
    router.join_replica("raw-0", InferenceEngineAdapter(eng))
    prompt = np.tile(np.array([5, 6, 7], np.int32), 16)
    reqs = [router.submit(prompt, 8) for _ in range(3)]
    router.run_until_idle()
    assert all(len(r.output) == 8 for r in reqs)
    m = router.metrics.metrics()
    assert m["serving_spec_accept_ratio"] > 0.0
    assert m["serving_kv_quant_blocks"] == eng.kv_quant_blocks > 0
    assert m["serving_prefill_chunk_seconds"] > 0.0
    # registry: every emitted name is declared with help text (DL006's
    # runtime twin)
    from dlrover_tpu.utils.metric_registry import METRIC_HELP

    for name in ("serving_spec_accept_ratio", "serving_kv_quant_blocks",
                 "serving_prefill_chunk_seconds"):
        assert name in m and name in METRIC_HELP


def test_engine_metrics_zero_when_reporters_leave():
    """Review finding: the fleet aggregates are recomputed every sweep
    — when the last reporting replica leaves, the gauges fall to zero
    instead of freezing at the dead fleet's values."""
    from dlrover_tpu.serving.router.metrics import RouterMetrics

    m = RouterMetrics()
    m.observe_engine_metrics([{"spec_accept_ratio": 0.5,
                               "kv_quant_blocks": 32.0,
                               "prefill_chunk_seconds": 1.5}])
    assert m.spec_accept_ratio == 0.5 and m.kv_quant_blocks == 32.0
    m.observe_engine_metrics([None])  # only non-reporters remain
    out = m.metrics()
    assert out["serving_spec_accept_ratio"] == 0.0
    assert out["serving_kv_quant_blocks"] == 0.0
    assert out["serving_prefill_chunk_seconds"] == 0.0


def test_engine_metrics_ride_stats_frames():
    """Remote twin of the plumbing: a worker whose engine reports
    engine_metrics ships them on STATS, the proxy caches them, and
    absent reporters (FakeEngine) leave the proxy returning None."""
    import threading

    from dlrover_tpu.serving.remote.proxy import RemoteReplicaHandle
    from dlrover_tpu.serving.remote.worker import FakeEngine, WorkerServer

    class MeteredFake(FakeEngine):
        def engine_metrics(self):
            return {"spec_accept_ratio": 0.25,
                    "kv_quant_blocks": 64.0,
                    "prefill_chunk_seconds": 0.5}

    import time as _time

    server = WorkerServer(MeteredFake(), stats_interval=0.05)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        proxy = RemoteReplicaHandle(server.addr, name="m-0")
        em, deadline = None, 100
        while deadline and em is None:
            em = proxy.engine_metrics()
            _time.sleep(0.05)
            deadline -= 1
        assert em == {"spec_accept_ratio": 0.25,
                      "kv_quant_blocks": 64.0,
                      "prefill_chunk_seconds": 0.5}
        proxy.close()
    finally:
        server.crash()
        t.join(timeout=2.0)

    plain = WorkerServer(FakeEngine(), stats_interval=0.05)
    t2 = threading.Thread(target=plain.serve_forever, daemon=True)
    t2.start()
    try:
        proxy2 = RemoteReplicaHandle(plain.addr, name="m-1")
        # a few STATS beats later the non-reporter still returns None
        _time.sleep(0.2)
        assert proxy2.engine_metrics() is None
        proxy2.close()
    finally:
        plain.crash()
        t2.join(timeout=2.0)


# -- nightly heavy-tail soak ------------------------------------------------


@pytest.mark.slow
def test_heavy_tail_chunked_prefill_soak(setup):
    """Long-prompt heavy-tail soak (nightly): Pareto prompt lengths
    from the loadgen distribution stream through a chunked-prefill
    paged int8 engine with seeded mid-flight cancels (the chaos).  The
    stall bound must hold in STEP terms — a decoding slot never goes a
    step without tokens while prompts prefill — and the block books
    must balance at the end."""
    from dlrover_tpu.serving.router.loadgen import (
        LoadgenConfig,
        OpenLoopGenerator,
    )

    cfg, _ = setup
    lg = LoadgenConfig(seed=13, rate_qps=60.0, duration_s=1.0,
                       prompt_mix="heavy_tail", prompt_min=8,
                       prompt_max=80, pareto_alpha=1.2)
    arrivals = list(OpenLoopGenerator(lg).arrivals())
    assert len(arrivals) >= 30
    assert max(a.prompt_len for a in arrivals) > 32, (
        "heavy tail must include long prompts"
    )
    eng = _engine(setup, max_slots=4, prefill_chunk=8, paged=True,
                  block_size=8, kv_dtype="int8", temperature=1.0)
    rng = np.random.RandomState(13)
    chaos = random.Random(13)
    pending = [
        rng.randint(0, cfg.vocab_size, a.prompt_len).astype(np.int32)
        for a in arrivals
    ]
    live = {}
    total = eng._blockmgr.num_blocks - 1
    counts = {}
    cancelled = 0
    while pending or eng.has_work:
        while pending:
            p = pending[0]
            gen = 8 + int(p.size) % 8
            if p.size + gen > eng.max_len:
                p = p[: eng.max_len - gen]
            try:
                rid = eng.add_request(p, gen)
            except ValueError:
                pending.pop(0)
                continue
            live[rid] = gen
            pending.pop(0)
            if len(live) >= 8:
                break
        before = {
            r.rid: len(r.output)
            for s, r in enumerate(eng._slot_req)
            if r is not None and not eng._prefilling[s]
        }
        eng.step()
        # stall bound: every slot that was decoding gained tokens
        # unless it finished this step
        after = {r.rid: len(r.output)
                 for r in eng._slot_req if r is not None}
        for rid, n in before.items():
            if rid in after:
                assert after[rid] > n or after[rid] >= live[rid], (
                    "decode slot stalled during heavy-tail prefill"
                )
        counts.update(after)
        # chaos: occasionally cancel something mid-flight (prefilling
        # slots included — the reclamation contract under fire)
        if chaos.random() < 0.15 and live:
            victim = chaos.choice(list(live))
            eng.cancel(victim)
            live.pop(victim, None)
            cancelled += 1
    assert cancelled > 0
    assert eng._blockmgr.available_blocks == total, (
        "soak leaked KV blocks"
    )
    done = {r.rid for r in eng._finished}
    assert done, "soak finished no requests"
    payload = {"finished": len(done), "cancelled": cancelled,
               "prefill_chunks": eng.stats.prefill_chunks}
    assert eng.stats.prefill_chunks > 0, payload
    json.dumps(payload)  # structured soak record stays serializable
