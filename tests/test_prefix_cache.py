"""Global prefix cache (ISSUE 17): copy-on-write shared KV blocks, the
router prefix-routing table, and the workloads that prove them.

The acceptance bar: sharing ON vs OFF produces byte-identical greedy
token streams with balanced terminal books (the golden equivalence);
random admit/cancel/free sequences never leak or double-free a block
(the refcount fuzz); the router's routing table drops a dead replica's
entries the same step the reap runs; tenant specs round-trip through
JSON and live-reload without dropping in-flight books; and a premium
class burning SLO budget gets a bounded, decaying WFQ boost.
"""

import json
import urllib.request

import numpy as np
import pytest

from dlrover_tpu.serving.paged import BlockManager
from dlrover_tpu.serving.prefixcache import (
    PrefixBlockIndex,
    PrefixRoutingTable,
    chain_key,
    head_key,
)
from dlrover_tpu.serving.remote.worker import FakeEngine
from dlrover_tpu.serving.router import (
    ContinuousBatchScheduler,
    RequestGateway,
    RouterMetrics,
    ServingRouter,
)
from dlrover_tpu.serving.router.loadgen import (
    LoadgenConfig,
    OpenLoopGenerator,
    prompt_tokens,
    run_router_rig,
)
from dlrover_tpu.serving.tenancy import TenantRegistry, TenantSpec
from dlrover_tpu.utils.metric_registry import METRIC_HELP
from dlrover_tpu.utils.profiler import MetricsExporter


def _prompt(i, n=8):
    return np.full(n, i % 251, np.int32)


# ------------------------------------------------------------ digests


def test_chain_key_stable_and_chained():
    a = chain_key(b"", b"abc")
    assert a == chain_key(b"", b"abc")
    assert len(a) == 16
    assert chain_key(a, b"xyz") != chain_key(b"", b"xyz"), \
        "depth-2 digest must cover the whole prefix, not one block"


def test_head_key_normalizes_dtype_and_needs_full_block():
    p32 = np.arange(8, dtype=np.int32)
    p64 = np.arange(8, dtype=np.int64)
    assert head_key(p32, 4) == head_key(p64, 4), \
        "router head must match the engine's int32 digest"
    assert head_key(p32[:3], 4) is None, \
        "sub-block prompt has no head (can never hit the cache)"


# --------------------------------------------------- PrefixBlockIndex


def test_index_hit_is_content_verified():
    idx = PrefixBlockIndex()
    key = chain_key(b"", b"tok-bytes")
    idx.register(key, 3, b"tok-bytes", head=True)
    assert idx.lookup(key, b"tok-bytes") == 3
    assert idx.lookup(key, b"other-bytes") is None, \
        "a key hit with mismatched content must not alias"


def test_index_lru_evicts_oldest_and_stages_head():
    idx = PrefixBlockIndex()
    for bid in (1, 2, 3):
        idx.register(chain_key(b"", b"%d" % bid), bid,
                     b"%d" % bid, head=True)
        idx.linger(bid)
    idx.revive(2)  # back in use: not evictable
    assert idx.evict_one() == 1
    assert idx.evict_one() == 3
    assert idx.evict_one() is None, "block 2 is referenced"
    drained = idx.drain_evicted_heads()
    assert len(drained) == 2
    assert idx.drain_evicted_heads() == [], "drain clears the stage"
    assert idx.stats()["prefix_evictions"] == 2.0
    assert idx.stats()["prefix_revivals"] == 1.0


def test_index_forget_keeps_reregistered_chain():
    """A chain hash re-registered to a NEWER block must survive the
    orphaned old block being forgotten."""
    idx = PrefixBlockIndex()
    key = chain_key(b"", b"t")
    idx.register(key, 1, b"t", head=False)
    idx.register(key, 2, b"t", head=False)  # newer block, same chain
    idx.forget(1)
    assert idx.lookup(key, b"t") == 2


# ---------------------------------------------------- COW + readiness


def test_cow_block_shared_copies_private_forgets():
    m = BlockManager(num_blocks=9, block_size=4)
    p = np.arange(4, dtype=np.int32)
    b1, _ = m.alloc_sequence(p, 8)
    b2, shared = m.alloc_sequence(p, 8)
    assert shared == 4 and b2[0] == b1[0]
    # ref > 1: divergence gets a FRESH block and asks for the copy
    new, copied = m.cow_block(b2[0])
    assert copied and new != b1[0]
    assert m.index.stats()["prefix_cow"] == 1.0
    b2[0] = new
    # ref == 1 committed: same id back, registration dropped
    same, copied = m.cow_block(b1[0])
    assert same == b1[0] and not copied
    b3, shared3 = m.alloc_sequence(p, 8)
    assert shared3 == 0, "a privatized block must not be mappable"
    m.free_sequence(b1)
    m.free_sequence(b2)
    m.free_sequence(b3)
    assert m.check_books()


def test_cow_block_pool_exhaustion_returns_none():
    m = BlockManager(num_blocks=3, block_size=4)  # 2 usable
    p = np.arange(4, dtype=np.int32)
    b1, _ = m.alloc_sequence(p, 4)
    b2, shared = m.alloc_sequence(p, 4)
    assert shared == 4 and m.available_blocks == 1
    m.alloc_sequence(np.arange(90, 94, dtype=np.int32), 4)
    assert m.available_blocks == 0
    assert m.cow_block(b2[0]) is None, \
        "no block for the divergence copy: caller must roll back"


def test_shared_prefix_ready_gates_pending_blocks():
    m = BlockManager(num_blocks=9, block_size=4)
    p = np.arange(8, dtype=np.int32)
    blocks, shared = m.alloc_sequence(p, 8)
    assert shared == 0
    # the chunked writer declares its registrations in-flight
    m.mark_pending(blocks)
    assert not m.shared_prefix_ready(p), \
        "an admission mapping unwritten content must wait"
    assert m.shared_prefix_ready(np.arange(50, 58, dtype=np.int32)), \
        "an unrelated prompt is never held up"
    m.mark_filled(blocks[0])
    assert not m.shared_prefix_ready(p), "second block still pending"
    m.mark_filled(blocks[1])
    assert m.shared_prefix_ready(p)


def test_free_pending_block_forgets_registration():
    """A chunked writer cancelled mid-prefill leaves garbage content:
    its pending blocks must be forgotten on free, never linger for a
    future hit."""
    m = BlockManager(num_blocks=9, block_size=4)
    p = np.arange(8, dtype=np.int32)
    blocks, _ = m.alloc_sequence(p, 8)
    m.mark_pending(blocks)
    m.mark_filled(blocks[0])
    m.free_sequence(blocks)  # cancel: block[1] never filled
    b2, shared = m.alloc_sequence(p, 8)
    assert shared == 4, \
        "the FILLED block lingers and hits; the pending one must not"
    m.free_sequence(b2)
    assert m.check_books()


def test_refcount_fuzz_never_leaks_or_double_frees():
    """Random admit / COW / free over a small pool: the free/live/LRU
    partition holds after every operation, and releasing everything
    returns the pool to full availability."""
    rng = np.random.RandomState(1707)
    m = BlockManager(num_blocks=17, block_size=4)
    prompts = [rng.randint(0, 97, rng.randint(4, 20)).astype(np.int32)
               for _ in range(6)]
    live = []
    for _ in range(400):
        op = rng.randint(3)
        if op == 0:
            p = prompts[rng.randint(len(prompts))]
            a = m.alloc_sequence(p, p.size + int(rng.randint(1, 8)))
            if a is not None:
                live.append(a[0])
        elif op == 1 and live:
            m.free_sequence(live.pop(rng.randint(len(live))))
        elif op == 2 and live:
            seq = live[rng.randint(len(live))]
            j = int(rng.randint(len(seq)))
            r = m.cow_block(seq[j])
            if r is not None:
                seq[j] = r[0]
        assert m.check_books()
    for seq in live:
        m.free_sequence(seq)
    assert m.check_books()
    assert m.available_blocks == m.num_blocks - 1, \
        "terminal books: every block free or lingering-evictable"
    assert (m._ref >= 0).all()


# ------------------------------------------------- PrefixRoutingTable


def test_routing_table_advertise_replaces_and_invalidates():
    t = PrefixRoutingTable()
    t.advertise("r0", ["aa", "bb"])
    assert t.lookup("aa") == "r0" and len(t) == 2
    gen = t.generation("r0")
    # newest advertisement REPLACES: 'bb' was evicted engine-side
    t.advertise("r0", ["aa", "cc"])
    assert t.lookup("bb") is None
    assert t.lookup("cc") == "r0"
    assert t.invalidations == 1
    assert t.generation("r0") == gen + 1


def test_routing_table_last_advertiser_wins_and_death_invalidates():
    t = PrefixRoutingTable()
    t.advertise("r0", ["aa"])
    t.advertise("r1", ["aa"])  # COW sharing: same head hot on both
    assert t.lookup("aa") == "r1"
    t.forget_replica("r1")
    assert t.lookup("aa") is None, "no route may point at a corpse"
    assert t.heads_of("r1") == []
    # r0 still advertises it next cycle and the route heals
    t.advertise("r0", ["aa"])
    assert t.lookup("aa") == "r0"


def test_routing_table_bounded_by_cap():
    t = PrefixRoutingTable(cap=8)
    t.advertise("r0", [f"h{i:03d}" for i in range(32)])
    assert len(t) == 8
    assert len(t.heads_of("r0")) == 8, \
        "the replica's recorded set must shrink with the LRU drop"


def test_routing_table_stats_mirror_router_metric_fields():
    """The router's observe phase does setattr(metrics, key, val) for
    every prefix_route_stats() key — each key must be a real
    RouterMetrics attribute or the mirror writes dead fields."""
    sched = ContinuousBatchScheduler(block_size=4)
    metrics = RouterMetrics(window_seconds=1.0)
    for key in sched.prefix_route_stats():
        assert hasattr(metrics, key), key


def test_prefix_metric_names_registered_dl006():
    m = RouterMetrics(window_seconds=1.0)
    for name in m.metrics():
        if name.startswith("serving_prefix"):
            assert name in METRIC_HELP, name
    assert sum(1 for n in METRIC_HELP if n.startswith("serving_prefix")
               ) >= 17


# every EVENT counter of the ledger (gauges like cached/lru_blocks are
# derived lengths and excluded); shared_tokens rides note_hit and is
# asserted separately
_PREFIX_EVENTS = (
    "prefix_hits", "prefix_misses", "prefix_evictions", "prefix_cow",
    "prefix_revivals", "prefix_lingers", "prefix_forgotten",
    "prefix_evicted_head_drops",
)


def _event_deltas(idx, mutate):
    before = idx.stats()
    mutate()
    after = idx.stats()
    return {k: after[k] - before[k] for k in _PREFIX_EVENTS
            if after[k] != before[k]}


def test_index_every_mutation_moves_its_counter():
    """Metrics-parity audit: each mutation path of the index moves
    exactly the event counters designated for it — a silent path
    (the old counterless linger) cannot come back unnoticed."""
    idx = PrefixBlockIndex()
    key = chain_key(b"", b"tok")
    assert _event_deltas(
        idx, lambda: idx.register(key, 1, b"tok", head=True)) == {}, \
        "register is gauge-only (cached_blocks is a derived length)"
    assert _event_deltas(idx, lambda: idx.note_hit(1, 4)) == {
        "prefix_hits": 1.0}
    assert idx.stats()["prefix_shared_tokens"] == 4.0
    assert _event_deltas(idx, idx.note_miss) == {"prefix_misses": 1.0}
    assert _event_deltas(idx, idx.note_cow) == {"prefix_cow": 1.0}
    assert _event_deltas(idx, lambda: idx.linger(1)) == {
        "prefix_lingers": 1.0}
    assert _event_deltas(idx, lambda: idx.linger(1)) == {}, \
        "a re-linger refreshes recency, it is not a second park event"
    assert _event_deltas(idx, lambda: idx.revive(1)) == {
        "prefix_revivals": 1.0}
    assert _event_deltas(idx, lambda: idx.revive(1)) == {}, \
        "reviving a non-lingering block is a no-op"
    assert _event_deltas(idx, lambda: idx.forget(1)) == {
        "prefix_forgotten": 1.0}
    assert _event_deltas(idx, lambda: idx.forget(1)) == {}, \
        "forgetting an unregistered block moves nothing"
    idx.register(key, 2, b"tok", head=True)
    idx.linger(2)
    assert _event_deltas(idx, idx.evict_one) == {
        "prefix_evictions": 1.0}, \
        "eviction must NOT double-count through forget()"


def test_index_staging_cap_overflow_is_counted():
    idx = PrefixBlockIndex()
    for bid in range(idx.MAX_EVICTED_HEADS + 2):
        idx.register(chain_key(b"", b"%d" % bid), bid,
                     b"%d" % bid, head=True)
        idx.linger(bid)
    for _ in range(idx.MAX_EVICTED_HEADS):
        idx.evict_one()
    # stage is full: the next evictions lose their head invalidation
    # and must say so
    deltas = _event_deltas(
        idx, lambda: (idx.evict_one(), idx.evict_one()))
    assert deltas == {"prefix_evictions": 2.0,
                      "prefix_evicted_head_drops": 2.0}
    assert len(idx.drain_evicted_heads()) == idx.MAX_EVICTED_HEADS


def test_index_event_counters_reach_router_metrics():
    """Every ledger key must survive the observe sweep into a
    registered ``serving_prefix_*`` name — a counter added to the
    index but not plumbed through RouterMetrics would silently
    flatline at 0 fleet-wide."""
    idx = PrefixBlockIndex()
    key = chain_key(b"", b"tok")
    idx.register(key, 1, b"tok", head=True)
    idx.note_hit(1, 4)
    idx.note_miss()
    idx.note_cow()
    idx.linger(1)
    idx.revive(1)
    idx.forget(1)
    stats = idx.stats()
    for k in _PREFIX_EVENTS:
        assert k in stats, k
    m = RouterMetrics(window_seconds=1.0)
    m.observe_engine_metrics([stats])
    out = m.metrics()
    for k in _PREFIX_EVENTS:
        if stats[k] == 0.0:
            continue
        matches = [n for n in out
                   if n.startswith("serving_") and k in n
                   and out[n] == stats[k]]
        assert matches, f"{k} did not reach a serving_prefix_* metric"


# -------------------------------------------- router fast chaos twin


def _fake_fleet(n=2, slots=8):
    router = ServingRouter(
        gateway=RequestGateway(max_pending=4096),
        scheduler=ContinuousBatchScheduler(block_size=4),
        metrics=RouterMetrics(window_seconds=1.0))
    for i in range(n):
        router.join_replica(
            f"p{i}", FakeEngine(slots=slots, tokens_per_step=32,
                                step_delay=0.0))
    return router


def test_replica_death_mid_shared_prefix_invalidates_routes():
    """CHAOS S16 fast twin: kill the replica that owns the hot head's
    routing entry while neighbors still share it — the same step's
    reap drops every route to the corpse, traffic re-routes, and the
    books stay balanced."""
    router = _fake_fleet()
    shared_head = _prompt(7, 16)
    reqs = [router.submit(shared_head, 4) for _ in range(6)]
    for _ in range(50):
        router.step()
        if not router.has_work and len(router.scheduler.prefix_table):
            break
    table = router.scheduler.prefix_table
    hx = head_key(shared_head, 4)
    owner = table.lookup(hx)
    assert owner is not None, "the hot head must be advertised"
    mid = [router.submit(shared_head, 8) for _ in range(4)]
    router.manager.replicas[owner].fail()
    router.step()  # reap: forget_replica -> table invalidation
    assert table.heads_of(owner) == [], owner
    assert table.lookup(hx) != owner
    for _ in range(200):
        if not router.has_work:
            break
        router.step()
    for r in reqs + mid:
        assert len(r.output) > 0, "no request may be lost to the death"
    assert router.metrics.metrics()[
        "serving_prefix_route_invalidations_total"] >= 0.0


def test_sysprompt_workload_feeds_routing_table():
    """The shared-system-prompt flood drives real advertisements end
    to end: FakeEngine counts head hits, STATS observe mirrors them,
    and the scheduler's table fills."""
    router = _fake_fleet()
    cfg = LoadgenConfig(
        seed=7, rate_qps=400.0, duration_s=0.25, arrival="poisson",
        prompt_mix="fixed", prompt_min=8, max_new_tokens=4,
        workload="sysprompt", system_prompt_len=16)
    result = run_router_rig(router, cfg, step_every=8)
    assert result["router_books_ok"], result
    assert result["router_lost"] == 0
    assert len(router.scheduler.prefix_table) >= 1
    sys_head = head_key(
        prompt_tokens(
            next(iter(OpenLoopGenerator(cfg).arrivals())), cfg), 4)
    assert router.scheduler.prefix_table.lookup(sys_head) is not None


# -------------------------------------------------- loadgen workloads


def test_chat_workload_turns_extend_prefix():
    cfg = LoadgenConfig(
        seed=11, rate_qps=600.0, duration_s=0.4, arrival="poisson",
        workload="chat", chat_sessions=4, chat_turn_tokens=8,
        system_prompt_len=16, prompt_max=256, max_new_tokens=4)
    arrivals = list(OpenLoopGenerator(cfg).arrivals())
    assert len(arrivals) > 10
    by_session = {}
    extensions = 0
    for a in arrivals:
        prev = by_session.get(a.session)
        cur = prompt_tokens(a, cfg)
        if prev is not None and len(cur) > len(prev):
            assert (cur[: len(prev)] == prev).all(), \
                "turn t's prompt must extend turn t-1's"
            extensions += 1
        by_session[a.session] = cur
    assert extensions > 0


def test_workloads_replay_deterministically():
    for workload in ("independent", "chat", "sysprompt"):
        cfg = LoadgenConfig(seed=5, rate_qps=300.0, duration_s=0.3,
                            workload=workload)
        a = [(x.at_s, x.prompt_len, x.session, x.turn, x.uid)
             for x in OpenLoopGenerator(cfg).arrivals()]
        b = [(x.at_s, x.prompt_len, x.session, x.turn, x.uid)
             for x in OpenLoopGenerator(cfg).arrivals()]
        assert a == b, workload


def test_sysprompt_prompts_share_one_head():
    cfg = LoadgenConfig(seed=3, rate_qps=200.0, duration_s=0.3,
                        workload="sysprompt", system_prompt_len=32)
    arrivals = list(OpenLoopGenerator(cfg).arrivals())
    heads = {head_key(prompt_tokens(a, cfg), 16) for a in arrivals}
    assert len(heads) == 1, "every user shares the system-prompt head"
    tails = {prompt_tokens(a, cfg)[32:].tobytes() for a in arrivals}
    assert len(tails) == len(arrivals), "user tails must be unique"


# ------------------------------------------------- tenant persistence


def _specs():
    return [
        TenantSpec("prem", quota_qps=9.0, burst=18.0, weight=3.0,
                   tenant_class="premium", shed_class="last"),
        TenantSpec("bg", max_queued=5, max_inflight=2,
                   tenant_class="background", shed_class="first"),
    ]


def test_tenant_registry_json_round_trip(tmp_path):
    reg = TenantRegistry(_specs(), default_tenant="bg")
    path = tmp_path / "tenants.json"
    reg.to_file(str(path))
    loaded = TenantRegistry.from_file(str(path))
    assert loaded.default_tenant == "bg"
    for name in ("prem", "bg"):
        a, b = reg.get(name), loaded.get(name)
        for field in TenantRegistry._SPEC_FIELDS:
            assert getattr(a, field) == getattr(b, field), (name, field)


def test_tenant_reload_keeps_books_drops_absent(tmp_path):
    reg = TenantRegistry(_specs())
    reg.count_admitted("prem")
    reg.count_admitted("prem")
    path = tmp_path / "tenants.json"
    path.write_text(json.dumps({"tenants": [
        {"name": "prem", "weight": 5.0, "tenant_class": "premium"},
        {"name": "newbie"},
    ]}))
    registered, removed = reg.reload_file(str(path))
    assert registered == 2 and removed == 1
    assert reg.get("bg") is None, "absent tenant must drop"
    assert reg.get("newbie") is not None
    assert reg.get("prem").weight == 5.0
    assert reg.admitted.get("prem") == 2, "books survive the reload"
    assert reg.resolve(None).name == "default"


def test_tenant_reload_rejects_bad_file_atomically(tmp_path):
    reg = TenantRegistry(_specs())
    path = tmp_path / "tenants.json"
    path.write_text(json.dumps({"tenants": [
        {"name": "ok"}, {"name": "broken", "tenant_class": "platinum"},
    ]}))
    with pytest.raises(ValueError):
        reg.reload_file(str(path))
    assert reg.get("ok") is None, \
        "a bad file must not half-apply: validate before mutating"
    assert reg.get("prem") is not None


def test_router_live_tenant_reload(tmp_path):
    path = tmp_path / "tenants.json"
    TenantRegistry(_specs()).to_file(str(path))
    router = ServingRouter(
        gateway=RequestGateway(),
        scheduler=ContinuousBatchScheduler(block_size=4),
        tenant_spec_file=str(path))
    router.join_replica("r0", FakeEngine(slots=4))
    assert router.gateway.tenants.get("prem").weight == 3.0
    TenantRegistry([TenantSpec("prem", weight=7.0,
                               tenant_class="premium")]
                   ).to_file(str(path))
    router.request_tenant_reload()  # the SIGHUP/endpoint seam
    router.step()  # file read at top of next step, outside the lock
    assert router.gateway.tenants.get("prem").weight == 7.0
    assert router.gateway.tenants.get("bg") is None


# ------------------------------------------------- usage + SLO boost


def test_tenants_usage_endpoint_serves_per_tenant_books():
    reg = TenantRegistry(_specs())
    gw = RequestGateway(tenants=reg)
    gw.submit(_prompt(0), 4, tenant="prem")
    reg.note_tokens("prem", 12)
    exporter = MetricsExporter()
    exporter.attach_tenants(reg)
    exporter.start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{exporter.port}/tenants/usage",
            timeout=5).read().decode()
    finally:
        exporter.stop()
    doc = json.loads(body)["tenants"]
    assert doc["prem"]["admitted"] == 1
    assert doc["prem"]["tokens"] == 12
    assert doc["prem"]["tenant_class"] == "premium"
    assert set(doc) >= {"prem", "bg", "default"}, \
        "raw tenant ids belong HERE (bounded endpoint), not in labels"


def test_slo_burn_boost_bounded_and_decays():
    reg = TenantRegistry(_specs())
    prem = reg.get("prem")
    base = prem.weight
    # burning: boost tracks the burn rate, bounded at 4x
    reg.update_slo_boosts({"premium": 2.5})
    assert reg.boost_of("premium") == 2.5
    assert reg.boosted_weight(prem) == base * 2.5
    reg.update_slo_boosts({"premium": 80.0})
    assert reg.boost_of("premium") == 4.0, "the multiplier is BOUNDED"
    # recovered: geometric decay back to neutral, then exactly 1.0
    for _ in range(16):
        reg.update_slo_boosts({"premium": 0.2})
    assert reg.boost_of("premium") == 1.0
    assert reg.boosted_weight(prem) == base
    assert reg.boost_of("background") == 1.0, \
        "only the burning class is boosted"


# --------------------------------------- engine golden equivalence


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = LlamaConfig.tiny(max_seq_len=96, dtype=jnp.float32)
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    return cfg, variables


def _equiv_prompts(cfg):
    rng = np.random.RandomState(23)
    head = rng.randint(0, cfg.vocab_size, 16).astype(np.int32)
    prompts = [np.concatenate(
        [head, rng.randint(0, cfg.vocab_size, 8).astype(np.int32)])
        for _ in range(3)]
    prompts.append(rng.randint(0, cfg.vocab_size, 20).astype(np.int32))
    return prompts


def _run_engine(cfg, variables, prompts, sharing, **kw):
    from dlrover_tpu.serving.engine import InferenceEngine

    eng = InferenceEngine(
        cfg, variables, max_slots=2, temperature=0.0, paged=True,
        block_size=8, prefix_sharing=sharing, **kw)
    rids = [eng.add_request(p, 6) for p in prompts]
    res = eng.run()
    assert eng._blockmgr.check_books()
    assert eng._blockmgr.available_blocks == eng._blockmgr.num_blocks - 1
    return [list(np.asarray(res[r]).tolist()) for r in rids], eng


def test_golden_equivalence_batched(tiny_model):
    """THE gate, batched prefill: sharing ON and OFF produce byte-
    identical greedy streams and terminal books, while ON actually
    shared (the ledger proves the path was exercised)."""
    cfg, variables = tiny_model
    prompts = _equiv_prompts(cfg)
    on, eng = _run_engine(cfg, variables, prompts, True, chunk=4)
    off, _ = _run_engine(cfg, variables, prompts, False, chunk=4)
    assert on == off
    assert eng.prefix_stats()["prefix_hits"] > 0


def test_golden_equivalence_chunked_warm_start(tiny_model):
    """THE gate, chunked prefill: the COW + warm-start + pending-wait
    machinery changes nothing about the tokens."""
    cfg, variables = tiny_model
    prompts = _equiv_prompts(cfg)
    on, eng = _run_engine(cfg, variables, prompts, True,
                          chunk=2, prefill_chunk=4)
    off, _ = _run_engine(cfg, variables, prompts, False,
                         chunk=2, prefill_chunk=4)
    assert on == off
    assert eng.prefix_stats()["prefix_hits"] > 0


def test_shared_head_is_stored_once(tiny_model):
    """A flood of users behind one system prompt keeps ONE copy of its
    blocks: the peak of live KV blocks with sharing on stays under the
    sharing-off peak by the copies not made, most lookups hit, and the
    tokens are the same."""
    from dlrover_tpu.serving.engine import InferenceEngine

    cfg, variables = tiny_model
    rng = np.random.RandomState(29)
    users, head_blocks, block_size = 6, 3, 8
    head = rng.randint(
        0, cfg.vocab_size, head_blocks * block_size).astype(np.int32)
    prompts = [np.concatenate(
        [head, rng.randint(0, cfg.vocab_size, 4).astype(np.int32)])
        for _ in range(users)]

    def flood(sharing):
        eng = InferenceEngine(
            cfg, variables, max_slots=users, temperature=0.0,
            paged=True, block_size=block_size, chunk=2,
            prefill_chunk=4, prefix_sharing=sharing)
        rids = [eng.add_request(p, 4) for p in prompts]
        peak = 0
        while eng.has_work:
            eng.step()
            # live blocks, the trash sink not counted; read every step
            # so the high-water mark is caught before the final free
            peak = max(peak, eng._blockmgr.num_blocks
                       - eng._blockmgr.available_blocks - 1)
        res = eng.run()
        assert eng._blockmgr.check_books()
        return [np.asarray(res[r]).tolist() for r in rids], peak, \
            eng.prefix_stats()

    on, peak_on, stats = flood(True)
    off, peak_off, _ = flood(False)
    assert on == off
    # two copies of the head as slack, as the rig this came from had
    assert peak_on <= peak_off - (users - 2) * head_blocks, \
        (peak_on, peak_off)
    hits, misses = stats["prefix_hits"], stats["prefix_misses"]
    assert hits / (hits + misses) >= 0.8, stats


# ---------------------------------------------------------- slow soak


@pytest.mark.slow
def test_prefix_soak_multi_replica_flood_with_deaths():
    """Nightly: three replicas, a sustained shared-system-prompt flood
    with mid-flight cancels and one replica death — zero lost, books
    balanced, and the routing table never points at the corpse."""
    router = _fake_fleet(n=3, slots=8)
    cfg = LoadgenConfig(
        seed=61, rate_qps=500.0, duration_s=8.0, arrival="poisson",
        workload="sysprompt", system_prompt_len=16, max_new_tokens=8)
    import threading
    import time as _time

    def killer():
        _time.sleep(2.0)
        router.manager.replicas["p1"].fail()

    t = threading.Thread(target=killer, daemon=True)
    t.start()
    result = run_router_rig(router, cfg, step_every=16,
                            cancel_every=97)
    t.join()
    assert result["router_books_ok"], result
    assert result["router_lost"] == 0
    table = router.scheduler.prefix_table
    assert table.heads_of("p1") == []
    assert "p1" not in router.manager.replicas
    assert router.metrics.metrics()[
        "serving_prefix_route_placements_total"] >= 0.0
