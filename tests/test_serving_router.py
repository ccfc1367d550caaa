"""Elastic serving gateway tests: admission, placement, failover,
autoscale (serving/router/).

The acceptance bar (ISSUE 1): a 3-replica router under a 200-request
stream loses ZERO requests when a replica is killed mid-flight, its
Prometheus metrics render, and sustained backlog yields a Brain scale
plan executed through the in-memory scheduler with drain-on-scale-down
losing nothing either.
"""

import time

import numpy as np
import pytest

from dlrover_tpu.brain.serving import ServingScalePolicy, ServingSignal
from dlrover_tpu.common.constants import (
    NodeType,
    ReplicaStatus,
    ServingRequestState,
)
from dlrover_tpu.common.node import Node, NodeGroupResource, NodeResource
from dlrover_tpu.master.scaler.base import ScalePlan
from dlrover_tpu.scheduler.in_memory import (
    InMemoryCluster,
    InMemoryNodeWatcher,
    InMemoryScaler,
)
from dlrover_tpu.serving.router import (
    PRIORITY_BATCH,
    PRIORITY_HIGH,
    PRIORITY_NORMAL,
    ContinuousBatchScheduler,
    QueueFullError,
    ReplicaProvisioner,
    RequestGateway,
    ServingAutoScaler,
    ServingRouter,
)
from dlrover_tpu.serving.router.gateway import AdmissionError
from dlrover_tpu.utils.profiler import render_prometheus


# the protocol-conformant in-memory replica engine ships in product
# code (the remote worker hosts it too) — one implementation, no
# test-local copy to drift from the contract the fabric tests exercise
from dlrover_tpu.serving.remote.worker import FakeEngine  # noqa: E402


def _prompt(i, n=8):
    return np.full(n, i % 251, np.int32)


# -- gateway ----------------------------------------------------------------


def test_gateway_bounded_admission():
    gw = RequestGateway(max_pending=2, max_prompt_len=16)
    gw.submit(_prompt(1), 4)
    gw.submit(_prompt(2), 4)
    with pytest.raises(QueueFullError):
        gw.submit(_prompt(3), 4)
    assert gw.rejected == 1
    with pytest.raises(AdmissionError):
        gw.submit(np.zeros(32, np.int32), 4)  # over the prompt bound


def test_gateway_priority_order_and_requeue_front():
    gw = RequestGateway()
    norm = gw.submit(_prompt(1), 4, priority=PRIORITY_NORMAL)
    batch = gw.submit(_prompt(2), 4, priority=PRIORITY_BATCH)
    high = gw.submit(_prompt(3), 4, priority=PRIORITY_HIGH)
    assert gw.schedule_scan(10) == [high, norm, batch]
    # failover requeue goes to the FRONT of its band
    late = gw.submit(_prompt(4), 4, priority=PRIORITY_NORMAL)
    gw.remove(norm)
    gw.requeue_front([norm])
    assert gw.schedule_scan(10) == [high, norm, late, batch]
    assert norm.requeues == 1 and norm.state == ServingRequestState.QUEUED


def test_gateway_deadline_expiry():
    gw = RequestGateway()
    req = gw.submit(_prompt(1), 4, timeout=5.0, now=100.0)
    keep = gw.submit(_prompt(2), 4, now=100.0)  # no deadline
    assert gw.expire(now=104.0) == []
    assert gw.expire(now=106.0) == [req]
    assert req.state == ServingRequestState.TIMED_OUT
    assert gw.depth() == 1 and gw.schedule_scan(10) == [keep]
    with pytest.raises(RuntimeError):
        req.result(timeout=0)


def test_gateway_now_equals_deadline_is_not_expired():
    """Expiry is strict ``>``: a request AT its deadline still gets
    this scheduling round — ``timeout=0`` means "fail unless
    immediately serviceable", and only strictness makes the immediate
    round possible."""
    gw = RequestGateway()
    req = gw.submit(_prompt(1), 4, timeout=5.0, now=100.0)
    assert gw.expire(now=105.0) == [], \
        "now == deadline must NOT expire (strict >)"
    assert req.state == ServingRequestState.QUEUED
    assert gw.expire(now=105.0000001) == [req]


def test_requeue_front_of_cancelled_request_is_noop():
    """A failover racing a cancel must not resurrect the request."""
    gw = RequestGateway()
    req = gw.submit(_prompt(1), 4)
    gw.remove(req)
    req.state = ServingRequestState.RUNNING
    assert req.cancel() is True
    req.abort(ServingRequestState.CANCELLED)   # the router's sweep
    assert gw.requeue_front([req]) == []
    assert gw.depth() == 0
    assert req.state == ServingRequestState.CANCELLED
    assert req.requeues == 0
    # same for every other terminal state — a poisoned/expired corpse
    # must not re-enter the queue either
    for state in (ServingRequestState.TIMED_OUT,
                  ServingRequestState.POISONED,
                  ServingRequestState.DONE):
        other = gw.submit(_prompt(2), 4)
        gw.remove(other)
        other.state = state
        assert gw.requeue_front([other]) == []
        assert gw.depth() == 0 and other.state == state


# -- scheduler --------------------------------------------------------------


class _Cap:
    def __init__(self, name, slots, blocks=1000.0):
        self.name, self._slots, self._blocks = name, slots, blocks

    def slots_free(self):
        return self._slots

    def blocks_free(self):
        return self._blocks


def test_scheduler_least_loaded_and_kv_budget():
    gw = RequestGateway()
    sched = ContinuousBatchScheduler(block_size=4)
    a, b = _Cap("a", 1, blocks=2.0), _Cap("b", 3, blocks=1000.0)
    big = gw.submit(np.zeros(12, np.int32), 8)    # 5 blocks: b only
    small = gw.submit(np.zeros(4, np.int32), 4)   # 2 blocks: either
    placed = dict(
        (r.rid, h.name) for h, r in sched.schedule(gw, [a, b]))
    assert placed[big.rid] == "b", "KV budget must exclude replica a"
    assert placed[small.rid] == "b", "least-loaded placement"
    assert gw.depth() == 0


def test_scheduler_prefix_affinity_beats_load():
    gw = RequestGateway()
    sched = ContinuousBatchScheduler(block_size=4, prefix_tokens=8)
    prompt = np.arange(8, dtype=np.int32)
    a = _Cap("a", 4)
    first = gw.submit(prompt, 4)
    assert sched.schedule(gw, [a])[0][0].name == "a"
    # same prefix again: a is now the LOADED replica, b is idle — the
    # warm prefix cache must still win
    a2, b = _Cap("a", 1), _Cap("b", 4)
    again = gw.submit(prompt.copy(), 4)
    other = gw.submit(np.arange(100, 108, dtype=np.int32), 4)
    placed = dict(
        (r.rid, h.name) for h, r in sched.schedule(gw, [a2, b]))
    assert placed[again.rid] == "a"
    assert placed[other.rid] == "b"


def test_scheduler_leaves_unplaceable_queued():
    gw = RequestGateway()
    sched = ContinuousBatchScheduler(block_size=4)
    req = gw.submit(np.zeros(8, np.int32), 8)
    assert sched.schedule(gw, [_Cap("a", 0)]) == []
    assert gw.depth() == 1 and gw.schedule_scan(1) == [req]


# -- router: completion + failover -----------------------------------------


def _mk_router(n_replicas=3, slots=4, tokens_per_step=4, **gw_kw):
    router = ServingRouter(
        gateway=RequestGateway(**gw_kw),
        scheduler=ContinuousBatchScheduler(block_size=4),
    )
    engines = []
    for i in range(n_replicas):
        eng = FakeEngine(slots=slots, tokens_per_step=tokens_per_step)
        engines.append(eng)
        router.join_replica(f"replica-{i}", eng)
    return router, engines


def test_router_completes_requests():
    router, _ = _mk_router(n_replicas=2)
    reqs = [router.submit(_prompt(i), 8) for i in range(20)]
    router.run_until_idle()
    for r in reqs:
        out = r.result(timeout=0)
        assert r.state == ServingRequestState.DONE
        assert out.size == 8
    m = router.metrics.metrics()
    assert m["serving_requests_completed_total"] == 20
    assert m["serving_requests_requeued_total"] == 0


def test_chaos_replica_kill_loses_zero_requests():
    """THE acceptance test: 3 in-memory replicas, a 200-request stream,
    one replica killed mid-flight — every request completes (requeued,
    none dropped) and the router metrics render as Prometheus text."""
    router, _ = _mk_router(n_replicas=3, slots=4, tokens_per_step=2)
    reqs = [router.submit(_prompt(i), 8) for i in range(200)]
    # warm up until the doomed replica demonstrably holds work
    for _ in range(3):
        router.step()
    victim = router.manager.get("replica-1")
    assert victim is not None and victim.inflight, \
        "kill must be mid-flight to test failover"
    n_inflight = len(victim.inflight)
    router.fail_replica("replica-1")
    router.run_until_idle()

    lost = [r for r in reqs if r.state != ServingRequestState.DONE]
    assert not lost, f"{len(lost)} requests lost in failover"
    for r in reqs:
        assert r.result(timeout=0).size == 8
    m = router.metrics.metrics()
    assert m["serving_requests_completed_total"] == 200
    assert m["serving_requests_requeued_total"] >= n_inflight
    assert m["serving_replica_up"] == 2

    text = render_prometheus(m, labels={"job": "serving"})
    for name in ("serving_queue_depth", "serving_ttft_seconds",
                 "serving_replica_up"):
        assert f'{name}{{job="serving"}}' in text
    assert 'serving_replica_up{job="serving"} 2' in text


def test_router_graceful_drain_finishes_inflight():
    router, engines = _mk_router(n_replicas=2, tokens_per_step=2)
    reqs = [router.submit(_prompt(i), 8) for i in range(8)]
    router.step()
    router.begin_drain("replica-0")
    drained_handle = router.manager.get("replica-0")
    assert drained_handle.status == ReplicaStatus.DRAINING
    router.run_until_idle()
    assert all(r.state == ServingRequestState.DONE for r in reqs)
    # the drained replica retired without dropping anything
    assert "replica-0" not in router.replica_names
    assert [h.name for h in router.drained] == ["replica-0"]
    assert router.metrics.metrics()["serving_requests_requeued_total"] == 0


def test_router_timeout_while_queued():
    router, _ = _mk_router(n_replicas=1, slots=1)
    t0 = time.monotonic()
    fast = router.submit(_prompt(0), 4, now=t0)
    doomed = router.submit(_prompt(1), 4, timeout=0.5, now=t0)
    router.step(now=t0)          # fast occupies the only slot
    router.step(now=t0 + 1.0)    # doomed expires before placement
    assert doomed.state == ServingRequestState.TIMED_OUT
    router.run_until_idle()
    assert fast.state == ServingRequestState.DONE
    assert router.metrics.metrics()["serving_requests_timed_out_total"] == 1


def test_heartbeat_staleness_fails_replica_over():
    router, engines = _mk_router(n_replicas=2)
    router.manager.heartbeat_timeout = 5.0
    t0 = time.monotonic()
    reqs = [router.submit(_prompt(i), 8, now=t0) for i in range(4)]
    router.step(now=t0)
    # replica-1 stops being pumpable without an engine error: silence
    # alone must kill it (simulates a hung remote process)
    h = router.manager.get("replica-1")
    h.last_heartbeat = t0 - 100.0
    had = len(h.inflight)
    router.step(now=t0 + 0.1)
    assert "replica-1" not in router.replica_names
    if had:
        assert router.metrics.requeued >= had
    router.run_until_idle()
    assert all(r.state == ServingRequestState.DONE for r in reqs)


def test_idle_lull_does_not_mass_reap_replicas():
    """A traffic lull longer than the heartbeat timeout (no step()
    calls at all) must NOT read as N simultaneous replica deaths —
    staleness only counts while the router was actually watching."""
    router, _ = _mk_router(n_replicas=2)
    router.manager.heartbeat_timeout = 5.0
    t = time.monotonic()
    for i in range(4):
        router.submit(_prompt(i), 8, now=t)
    while router.has_work:
        router.step(now=t)
    # 120s idle gap, then new traffic
    t += 120.0
    late = router.submit(_prompt(9), 8, now=t)
    router.step(now=t)
    assert sorted(router.replica_names) == ["replica-0", "replica-1"]
    while router.has_work:
        t += 0.01
        router.step(now=t)
    assert late.state == ServingRequestState.DONE


def test_poison_request_rejected_without_killing_replicas():
    """A request the ENGINE refuses as impossible (ValueError) must be
    rejected at placement, not treated as a replica death — otherwise
    one poison request fails every healthy replica over in turn."""

    class Rejecting(FakeEngine):
        def add_request(self, prompt, max_new_tokens):
            if max_new_tokens > 100:
                raise ValueError("exceeds engine max_len")
            return super().add_request(prompt, max_new_tokens)

    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4))
    router.join_replica("r0", Rejecting(slots=2))
    bad = router.submit(_prompt(0), 1000)
    ok = router.submit(_prompt(1), 8)
    router.run_until_idle()
    assert bad.state == ServingRequestState.REJECTED
    assert ok.state == ServingRequestState.DONE
    assert router.replica_names == ["r0"], "replica must survive"
    assert router.metrics.metrics()[
        "serving_requests_rejected_total"] == 1


# -- autoscale loop ---------------------------------------------------------


def _autoscale_rig(max_replicas=3, queue_high=2.0, queue_low=0.2,
                   brain=None, engine_factory=None):
    from dlrover_tpu.serving.router import RouterMetrics

    cluster = InMemoryCluster()
    scaler = InMemoryScaler(cluster)
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4),
        # short signal window so the synthetic clock (0.05s/step) sees
        # load changes inside the test's horizon
        metrics=RouterMetrics(window_seconds=0.5),
    )
    provisioner = ReplicaProvisioner(
        router, InMemoryNodeWatcher(cluster),
        engine_factory=engine_factory or (lambda node: FakeEngine(
            slots=2, tokens_per_step=2)),
    )
    auto = ServingAutoScaler(
        router, scaler,
        policy=ServingScalePolicy(
            min_replicas=1, max_replicas=max_replicas,
            queue_high=queue_high, queue_low=queue_low,
        ),
        brain=brain,
        decide_interval=0.0, cooldown=0.0, min_samples=1,
    )
    # bootstrap replica 0 through the cluster, like a deployment would
    cluster.create_node(Node(NodeType.SERVING_REPLICA, 0, rank_index=0))
    provisioner.poll()
    assert router.manager.up_count() == 1
    return cluster, scaler, router, provisioner, auto


def test_autoscale_backlog_adds_replica_and_drain_down_loses_nothing():
    """Acceptance: sustained queue depth above threshold yields a scale
    plan that adds a replica through the in-memory scheduler, and the
    scale-down drain loses no requests."""
    cluster, scaler, router, provisioner, auto = _autoscale_rig()
    reqs = [router.submit(_prompt(i), 8) for i in range(40)]

    t = time.monotonic()
    peak_up = 1
    for i in range(200):
        t += 0.05
        router.step(now=t)
        provisioner.poll()
        peak_up = max(peak_up, router.manager.up_count())
        if not router.has_work:
            break
    assert not router.has_work

    # backlog drove a scale-up executed through the in-memory scheduler
    up_plans = [p for p in auto.plans if p.node_group_resources]
    assert up_plans, "sustained backlog must emit a scale plan"
    assert max(
        p.node_group_resources[NodeType.SERVING_REPLICA].count
        for p in up_plans
    ) >= 2
    assert peak_up >= 2, \
        "the scale plan must materialize as a joined replica"

    # zero lost requests across the whole elastic episode
    assert all(r.state == ServingRequestState.DONE for r in reqs)
    for r in reqs:
        assert r.result(timeout=0).size == 8

    # idle tail: the policy contracts back toward min_replicas with
    # drain-first removal (remove_nodes plans, never a mid-flight kill)
    for i in range(50):
        t += 0.05
        router.step(now=t)
        provisioner.poll()
        if router.manager.up_count() <= 1:
            break
    assert router.manager.up_count() == 1
    down_plans = [p for p in auto.plans if p.remove_nodes]
    assert down_plans, "scale-down must remove the drained node"
    assert router.metrics.metrics()["serving_requests_requeued_total"] == 0


def test_autoscale_recovers_capacity_after_replica_crash():
    """A crashed replica's cluster node must be retired (remove_nodes
    plan) so the next scale-up actually creates a replacement — a crash
    must not permanently cap the fleet below the policy's answer."""
    cluster, scaler, router, provisioner, auto = _autoscale_rig(
        max_replicas=2, queue_high=1.0)
    reqs = [router.submit(_prompt(i), 8) for i in range(60)]
    t = time.monotonic()
    for _ in range(60):
        t += 0.05
        router.step(now=t)
        provisioner.poll()
        if router.manager.up_count() >= 2:
            break
    assert router.manager.up_count() == 2
    victim = router.replica_names[0]
    victim_node = router.manager.get(victim).node
    router.fail_replica(victim)
    recovered = False
    for _ in range(200):
        t += 0.05
        router.step(now=t)
        provisioner.poll()
        recovered = recovered or (
            victim not in router.replica_names
            and router.manager.up_count() >= 2
        )
        if recovered and not router.has_work:
            break
    assert recovered, "a replacement replica must restore capacity"
    assert any(
        n.name == victim_node.name
        for p in auto.plans for n in p.remove_nodes
    ), "the crashed replica's node must be retired from the cluster"
    assert victim_node.name not in cluster.nodes
    assert all(r.state == ServingRequestState.DONE for r in reqs)


def _span_names(tree):
    """All span names in a trace tree, depth-first."""
    out = []

    def walk(spans):
        for s in spans:
            out.append(s["name"])
            walk(s["children"])

    walk(tree["spans"])
    return out


def _spans_named(tree, name):
    found = []

    def walk(spans):
        for s in spans:
            if s["name"] == name:
                found.append(s)
            walk(s["children"])

    walk(tree["spans"])
    return found


def test_autoscale_scale_up_emits_single_stitched_trace():
    """The control-plane acceptance: ONE scale-up decision produces ONE
    ``autoscale`` trace whose milestone spans cover plan ->
    node_create -> worker_spawn -> hello_join -> first_placement, each
    milestone running from the previous one (stage-to-stage latency is
    the point of the trace)."""

    rig = {}

    def spawning_factory(node):
        # mirror the WorkerSupervisor.engine_factory contract: handing
        # a node an engine is a process spawn, narrated to the flight
        # recorder under the node's name (the rig's bootstrap replica
        # spawns before the router is in hand — nothing to narrate to)
        if "router" in rig:
            rig["router"].recorder.record(
                "worker_spawn", worker=node.name, pid=0)
        return FakeEngine(slots=2, tokens_per_step=2)

    cluster, scaler, router, provisioner, auto = _autoscale_rig(
        max_replicas=2, engine_factory=spawning_factory)
    rig["router"] = router
    reqs = [router.submit(_prompt(i), 8) for i in range(40)]
    t = time.monotonic()
    for _ in range(200):
        t += 0.05
        router.step(now=t)
        provisioner.poll()
        if not router.has_work:
            break
    assert not router.has_work
    assert all(r.state == ServingRequestState.DONE for r in reqs)

    traces = router.tracer.traces_named("autoscale", limit=50)
    ups = [tr for tr in traces
           if tr["status"] == "ok" and "node_create" in _span_names(tr)]
    assert len(ups) == 1, [
        (tr["status"], _span_names(tr)) for tr in traces]
    tree = ups[0]
    # decision-time markers carry the evidence the decision was made on
    (window,) = _spans_named(tree, "load_window")
    assert "queue_depth" in window["attrs"]
    (policy,) = _spans_named(tree, "policy")
    assert policy["attrs"]["desired"] == 2
    assert _spans_named(tree, "scale_plan")
    # milestone chain: exactly one span per stage, stitched in causal
    # order (span append order follows the recorder's event sequence;
    # offsets collapse under the test's synthetic clock, so the
    # sequence — not the timestamps — is the order assertion here)
    names = _span_names(tree)
    stages = ("node_create", "worker_spawn", "hello_join",
              "first_placement")
    for stage in stages:
        (span,) = _spans_named(tree, stage)
        assert span["status"] == "ok"
        assert span["offset_s"] >= 0.0
    assert [n for n in names if n in stages] == list(stages), names
    # the new replica is named on every milestone
    replicas = {s["attrs"]["replica"]
                for stage in ("worker_spawn", "hello_join",
                              "first_placement")
                for s in _spans_named(tree, stage)}
    assert len(replicas) == 1


def test_autoscale_scale_down_traces_drain_to_retired():
    """The idle tail's scale-down decision traces drain -> retired for
    its victim replica and closes ``ok`` once the node is gone."""
    cluster, scaler, router, provisioner, auto = _autoscale_rig()
    reqs = [router.submit(_prompt(i), 8) for i in range(40)]
    t = time.monotonic()
    for _ in range(250):
        t += 0.05
        router.step(now=t)
        provisioner.poll()
        if not router.has_work and router.manager.up_count() <= 1:
            break
    assert router.manager.up_count() == 1
    downs = [
        tr for tr in router.tracer.traces_named("autoscale", limit=50)
        if tr["status"] == "ok" and "drain" in _span_names(tr)
    ]
    assert downs, "the scale-down must have traced"
    tree = downs[-1]
    drains = _spans_named(tree, "drain")
    retireds = _spans_named(tree, "retired")
    assert drains and retireds
    victims = {s["attrs"]["replica"] for s in drains}
    assert victims == {s["attrs"]["replica"] for s in retireds}
    for d, r in zip(sorted(drains, key=lambda s: s["attrs"]["replica"]),
                    sorted(retireds,
                           key=lambda s: s["attrs"]["replica"])):
        assert r["offset_s"] >= d["offset_s"]


def test_gateway_timeout_zero_means_fail_fast():
    gw = RequestGateway()
    req = gw.submit(_prompt(1), 4, timeout=0, now=50.0)
    assert req.deadline == 50.0
    assert gw.expire(now=50.001) == [req]
    assert req.state == ServingRequestState.TIMED_OUT


class _FakeBrain:
    """BrainClient stand-in: fixed answer + captured reports."""

    def __init__(self, answer):
        self.answer = answer
        self.reports = []

    def serving_plan(self, **query):
        self.fleet_query = query
        return self.answer

    def record_serving(self, **report):
        self.reports.append(report)


def test_autoscale_brain_decides_and_receives_reports():
    brain = _FakeBrain(answer=2)
    cluster, scaler, router, provisioner, auto = _autoscale_rig(
        brain=brain)
    for i in range(10):
        router.submit(_prompt(i), 8)
    t = time.monotonic()
    for _ in range(120):
        t += 0.05
        router.step(now=t)
        provisioner.poll()
        if not router.has_work:
            break
    assert router.manager.up_count() >= 2, \
        "the Brain's replica_count must be executed"
    assert brain.reports, "load samples must be reported into the Brain"
    assert {"queue_depth", "ttft_seconds", "tokens_per_sec"} <= set(
        brain.reports[0])


# -- brain policy + service surface ----------------------------------------


def test_serving_scale_policy_hysteresis():
    pol = ServingScalePolicy(min_replicas=1, max_replicas=4,
                             queue_high=4.0, queue_low=0.5)
    hot = [ServingSignal(queue_depth=20.0)] * 3
    idle = [ServingSignal(queue_depth=0.0)] * 3
    mid = [ServingSignal(queue_depth=4.0)] * 3  # 2/replica at 2: hold
    assert pol.decide(hot, 2) == 3
    assert pol.decide(idle, 2) == 1
    assert pol.decide(mid, 2) == 2
    assert pol.decide(hot, 4) == 4, "max_replicas must cap growth"
    assert pol.decide(idle, 1) == 1, "min_replicas must floor shrink"
    # TTFT pressure alone scales up
    slow = [ServingSignal(queue_depth=0.0, ttft_seconds=3.0)] * 3
    pol_ttft = ServingScalePolicy(max_replicas=4, ttft_high=1.0)
    assert pol_ttft.decide(slow, 2) == 3


def test_brain_service_serving_plan_and_history():
    from dlrover_tpu.brain.datastore import JobHistoryStore
    from dlrover_tpu.brain.service import BrainService
    from dlrover_tpu.common.serialize import dumps, loads

    store = JobHistoryStore(":memory:")
    svc = BrainService(store, port=0)
    try:
        out = loads(svc._handle_get(dumps({
            "kind": "serving_plan",
            "current_replicas": 1,
            "max_replicas": 4,
            "queue_high": 2.0,
            "samples": [{"queue_depth": 10.0}],
        }), None))
        assert out["replica_count"] == 2
        svc._handle_report(dumps({
            "kind": "record_serving", "job_uuid": "j1",
            "job_name": "svc", "replicas": 2, "queue_depth": 3.0,
            "ttft_seconds": 0.1, "tokens_per_sec": 500.0,
        }), None)
        hist = store.serving_history("svc")
        assert hist and hist[0]["replicas"] == 2
        assert hist[0]["tokens_per_sec"] == 500.0
    finally:
        svc.stop(close_store=True)


def test_in_memory_scaler_shrinks_group():
    cluster = InMemoryCluster()
    scaler = InMemoryScaler(cluster)
    grow = ScalePlan(node_group_resources={
        "worker": NodeGroupResource(3, NodeResource())})
    scaler.scale(grow)
    assert len(cluster.nodes) == 3
    shrink = ScalePlan(node_group_resources={
        "worker": NodeGroupResource(1, NodeResource())})
    scaler.scale(shrink)
    alive = [n for n in cluster.nodes.values() if not n.is_exited()]
    assert len(alive) == 1
    assert alive[0].rank_index == 0, "highest ranks leave first"


# -- real engine integration ------------------------------------------------


def test_router_over_real_paged_engines():
    """Two real InferenceEngine replicas (tiny model, paged KV) behind
    the router: requests route, batch and complete through the real
    prefill/decode path."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.serving.engine import InferenceEngine
    from dlrover_tpu.serving.router import InferenceEngineAdapter

    cfg = LlamaConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=16))
    for i in range(2):
        eng = InferenceEngine(
            cfg, variables, max_slots=2, chunk=4, paged=True,
            block_size=16, seed=i,
        )
        router.join_replica(f"eng-{i}", InferenceEngineAdapter(eng))
    rng = np.random.RandomState(0)
    prompts = rng.randint(1, cfg.vocab_size, (6, 8)).astype(np.int32)
    reqs = [router.submit(prompts[i], 6) for i in range(6)]
    router.run_until_idle(max_steps=500)
    for r in reqs:
        assert r.state == ServingRequestState.DONE
        assert r.result(timeout=0).size == 6
    assert router.metrics.metrics()[
        "serving_requests_completed_total"] == 6


def test_first_token_is_stamped_at_the_engines_read_not_the_steps_start():
    """The repair (ISSUE 52): an in-process engine's tokens are stamped
    with the engine's own read of the program that sampled them, not
    with the ``now`` their router step began with.  The step is made
    slow in front of its dispatches: the old stamp lay that much before
    any token existed."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.serving.engine import InferenceEngine
    from dlrover_tpu.serving.router import InferenceEngineAdapter

    cfg = LlamaConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    variables = LlamaModel(cfg).init(jax.random.PRNGKey(0),
                                     jnp.zeros((1, 8), jnp.int32))
    eng = InferenceEngine(cfg, variables, max_slots=2, chunk=4,
                          paged=True, block_size=16)
    slow, admit = 0.05, eng._dispatch_admissions

    def slow_admissions():
        time.sleep(slow)
        admit()

    eng._dispatch_admissions = slow_admissions
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=16))
    router.join_replica("slow", InferenceEngineAdapter(eng))
    rng = np.random.RandomState(0)
    reqs = [router.submit(rng.randint(1, cfg.vocab_size, 8)
                          .astype(np.int32), n) for n in (10, 1)]
    began = []
    while router.has_work:
        began.append(time.monotonic())
        router.step(now=began[-1])
        assert len(began) < 50
    ereqs = {r.rid: r for r in eng._finished}
    for req in reqs:
        ereq = ereqs[req.engine_rid]
        assert req.state == ServingRequestState.DONE
        # the engine's stamps, as they are: both requests were placed
        # by the first step, whose start is ``slow`` before any read
        assert req.first_token_at == ereq.first_token_at \
            >= began[0] + slow
        assert req.finished_at == ereq.last_token_at
        assert req.submitted_at < ereq.queued_at <= ereq.admitted_at \
            < ereq.first_token_at <= ereq.last_token_at
    streamed, one_token = reqs
    # a budget of one token ends at its first: never on a slot between
    # two steps, so its one delivery is the flush at its finish
    assert (one_token.deliveries, ereqs[one_token.engine_rid].deliveries) \
        == (1, 1)
    assert one_token.finished_at == one_token.first_token_at
    # the histogram takes one sample a delivery but a request's first,
    # and each gap holds at least one slow step
    gaps = router.metrics.token_gap_hist.snapshot()
    assert gaps["count"] == sum(r.deliveries - 1 for r in reqs) \
        == streamed.deliveries - 1 >= 1
    assert gaps["sum"] >= slow * gaps["count"]
    text = router.metrics.render_histograms()
    assert "# TYPE serving_token_gap_seconds histogram" in text
    assert "serving_decode_step_seconds" not in text
    assert streamed.trace.trace_id in text


# -- ISSUE 7: DL009 terminal-state guards + out-of-lock placement ----------


def test_terminal_state_guards_block_resurrection():
    """The fabric fix dlint DL009 forced: finish()/abort() refuse to
    leave a terminal state.  An engine completing a request whose
    CANCEL frame was lost (or an expiry racing a cancel) must not flip
    the answer the caller was already given."""
    from dlrover_tpu.serving.router.gateway import (
        RequestTimedOut,
        ServingRequest,
    )

    req = ServingRequest(rid=7, prompt=_prompt(1), max_new_tokens=4)
    assert req.cancel()
    req.abort(ServingRequestState.CANCELLED)
    # the engine finishes anyway: DONE must not overwrite CANCELLED
    req.finish([1, 2, 3], now=1.0)
    assert req.state == ServingRequestState.CANCELLED
    assert req.output == []
    with pytest.raises(RequestTimedOut):
        req.result(timeout=0)
    # an expiry racing the cancel must not rewrite the terminal state
    req.abort(ServingRequestState.TIMED_OUT)
    assert req.state == ServingRequestState.CANCELLED

    done = ServingRequest(rid=8, prompt=_prompt(2), max_new_tokens=2)
    done.finish([5, 6], now=1.0)
    # ...and the mirror image: a late abort cannot undo completion
    done.abort(ServingRequestState.TIMED_OUT)
    assert done.state == ServingRequestState.DONE
    assert list(done.result(timeout=0)) == [5, 6]


def test_submit_refuses_non_queued_request():
    """Placement runs OUTSIDE the router step lock now (dlint DL007:
    a remote submit is a frame send + ack wait), so a cancel can race
    it — ReplicaHandle.submit must reject anything not QUEUED instead
    of resurrecting a terminal request onto an engine."""
    from dlrover_tpu.serving.router.gateway import ServingRequest
    from dlrover_tpu.serving.router.replica import (
        ReplicaHandle,
        StaleRequestError,
    )

    handle = ReplicaHandle("r0", FakeEngine(slots=2, tokens_per_step=2))
    handle.mark_up(0.0)
    req = ServingRequest(rid=1, prompt=_prompt(1), max_new_tokens=2)
    req.abort(ServingRequestState.CANCELLED)
    with pytest.raises(StaleRequestError):
        handle.submit(req)
    assert not handle.inflight
    assert req.state == ServingRequestState.CANCELLED


def test_stale_placement_is_not_a_rejection():
    """The router must tell 'this request was answered while its
    submit was in flight' (skip, already accounted by the cancel
    sweep) from 'the engine rejected it' (REJECTED + counter): the
    race, forced by handing step() a placement whose request went
    terminal after the decision, must leave the rejected ledger at 0
    and blame no replica."""
    router = ServingRouter(scheduler=ContinuousBatchScheduler(
        block_size=4))
    router.join_replica("r0", FakeEngine(slots=2, tokens_per_step=2))
    handle = router.manager.get("r0")
    req = router.submit(_prompt(1), 2)
    req.abort(ServingRequestState.CANCELLED)

    real_schedule = router.scheduler.schedule
    router.scheduler.schedule = (
        lambda gateway, replicas, now=None: [(handle, req)])
    try:
        router.step()
    finally:
        router.scheduler.schedule = real_schedule

    assert router.gateway.rejected == 0
    assert router.metrics.metrics()[
        "serving_requests_rejected_total"] == 0
    assert not handle.inflight
    assert req.state == ServingRequestState.CANCELLED


def test_drain_racing_delivery_is_not_a_failover():
    """A begin_drain landing between the placement decision and the
    out-of-lock delivery must keep the drain graceful: the SUBMIT was
    never sent, so the request just goes back to the queue and the
    replica stays DRAINING — failing it over would requeue its real
    in-flight work and retire it crash-style (no GOODBYE)."""
    from dlrover_tpu.serving.router.replica import ReplicaStatus

    router = ServingRouter(scheduler=ContinuousBatchScheduler(
        block_size=4))
    router.join_replica("r0", FakeEngine(slots=2, tokens_per_step=2))
    handle = router.manager.get("r0")
    req = router.submit(_prompt(1), 2)

    real_schedule = router.scheduler.schedule

    def schedule_then_drain(gateway, replicas, now=None):
        # the real decision runs first (with pre-drain membership),
        # then the drain lands — i.e. before the out-of-lock delivery
        placements = real_schedule(gateway, replicas, now=now)
        assert placements == [(handle, req)]
        handle.begin_drain()
        return placements

    router.scheduler.schedule = schedule_then_drain
    try:
        router.step()
    finally:
        router.scheduler.schedule = real_schedule

    # the replica retired GRACEFULLY: it was empty, so the same step's
    # phase-5 moved it DRAINING -> retired into router.drained (with
    # GOODBYE) — the bug escalated it into router.dead instead
    assert handle.status in (ReplicaStatus.DRAINING, ReplicaStatus.LEFT)
    assert not handle._failed
    assert any(d.name == "r0" for d in router.drained)
    assert not any(d.name == "r0" for d in router.dead)
    assert req.state == ServingRequestState.QUEUED
    assert router.metrics.metrics()[
        "serving_requests_requeued_total"] == 1
    assert router.gateway.depth() == 1


# -- ISSUE 8: capacity debt -> replacement-node autoscaling -----------------


class _DebtFeed:
    """Stands in for a WorkerSupervisor's quarantine feed: tests put
    debt records in, the autoscaler polls them out."""

    def __init__(self):
        self.records = []

    def capacity_debt(self, now=None):
        return list(self.records)


def test_quarantine_debt_issues_replacement_same_poll():
    """The tentpole contract: a quarantined worker becomes a
    replacement-node ScalePlan on the SAME autoscale poll — no waiting
    out the quarantine window, no waiting for load signals — and the
    debt retires exactly once when the replacement joins."""
    cluster, scaler, router, provisioner, auto = _autoscale_rig(
        queue_low=0.0)
    feed = _DebtFeed()
    auto.supervisor = feed
    t = time.monotonic()
    auto.on_step(t)  # baseline: no debt, no replacement plans
    assert not [p for p in auto.plans if p.launch_nodes]

    feed.records.append({
        "key": "quarantine:w4", "kind": "quarantine",
        "source": "w4", "until": t + 120.0,
    })
    auto.on_step(t + 0.05)  # the poll that learns of the quarantine
    launch = [p for p in auto.plans if p.launch_nodes]
    assert len(launch) == 1, \
        "the replacement plan must be issued the same poll"
    replacement = launch[0].launch_nodes[0].name
    assert auto.debts["quarantine:w4"]["replacement"] == replacement
    assert router.metrics.metrics()["serving_capacity_debt"] == 1.0
    kinds = [e["kind"] for e in router.recorder.events(64)]
    assert "capacity_debt_opened" in kinds

    provisioner.poll()  # the cluster delivers the node -> replica joins
    assert replacement in router.replica_names
    auto.on_step(t + 0.10)
    assert auto.capacity_debt_retired == 1
    assert router.metrics.metrics()["serving_capacity_debt"] == 0.0
    retired = [e for e in router.recorder.events(64)
               if e["kind"] == "capacity_debt_retired"]
    assert len(retired) == 1
    assert retired[0]["reason"] == "replacement_joined"

    # the quarantine persists: the SAME episode must not reopen a debt
    # or launch a second replacement (no double-provisioning)
    auto.on_step(t + 0.15)
    auto.on_step(t + 0.20)
    assert len([p for p in auto.plans if p.launch_nodes]) == 1
    assert auto.capacity_debt_retired == 1

    # quarantine served: the episode's bookkeeping clears, so a LATER
    # quarantine of the same worker opens a FRESH debt
    feed.records.clear()
    auto.on_step(t + 1.0)
    assert "quarantine:w4" not in auto.debts
    feed.records.append({
        "key": "quarantine:w4", "kind": "quarantine",
        "source": "w4", "until": t + 300.0,
    })
    auto.on_step(t + 1.1)
    assert len([p for p in auto.plans if p.launch_nodes]) == 2


def test_debt_source_clearing_first_retires_without_replacement():
    """A quarantine that ends (or a worker that exits cleanly) BEFORE
    the replacement joins retires the debt by itself — exactly once,
    with no second provisioning and no retire-twice when the surplus
    replacement node eventually joins anyway."""
    cluster, scaler, router, provisioner, auto = _autoscale_rig(
        queue_low=0.0)
    feed = _DebtFeed()
    auto.supervisor = feed
    t = time.monotonic()
    feed.records.append({
        "key": "quarantine:w1", "kind": "quarantine",
        "source": "w1", "until": t + 5.0,
    })
    auto.on_step(t + 0.05)
    assert len([p for p in auto.plans if p.launch_nodes]) == 1
    # the worker exits cleanly before its replacement materializes
    feed.records.clear()
    auto.on_step(t + 0.10)
    assert auto.capacity_debt_retired == 1
    retired = [e for e in router.recorder.events(64)
               if e["kind"] == "capacity_debt_retired"]
    assert [e["reason"] for e in retired] == ["source_cleared"]
    assert router.metrics.metrics()["serving_capacity_debt"] == 0.0
    # the surplus node still joins (launch plans are not recalled) but
    # retires NOTHING a second time; the idle policy drains it later
    provisioner.poll()
    auto.on_step(t + 0.15)
    assert auto.capacity_debt_retired == 1
    assert len([p for p in auto.plans if p.launch_nodes]) == 1


def test_replacement_death_reopens_debt_while_source_still_out():
    """A retired debt must not be the fleet's last word: if the joined
    replacement itself dies while the source is still quarantined, the
    episode reopens and a fresh replacement launches — otherwise the
    fleet serves short-handed for the rest of the quarantine window
    with the sweep insisting everything is healed.  A replacement the
    POLICY drained is exempt (that disappearance was a deliberate
    shrink, not a new loss)."""
    cluster, scaler, router, provisioner, auto = _autoscale_rig(
        queue_low=0.0)
    feed = _DebtFeed()
    auto.supervisor = feed
    t = time.monotonic()
    feed.records.append({
        "key": "quarantine:w9", "kind": "quarantine",
        "source": "w9", "until": t + 600.0,
    })
    auto.on_step(t + 0.05)
    first = auto.debts["quarantine:w9"]["replacement"]
    provisioner.poll()
    auto.on_step(t + 0.10)
    assert auto.capacity_debt_retired == 1

    # the replacement dies mid-quarantine: reopen + second launch
    router.fail_replica(first)
    router.step(now=t + 0.15)  # reap -> the handle leaves the manager
    assert first not in router.replica_names
    auto.on_step(t + 0.20)
    launches = [p for p in auto.plans if p.launch_nodes]
    assert len(launches) == 2, "the lost replacement must be backfilled"
    second = auto.debts["quarantine:w9"]["replacement"]
    assert second != first
    kinds = [e["kind"] for e in router.recorder.events(128)]
    assert "capacity_debt_reopened" in kinds

    # second replacement joins -> retires the reopened debt
    provisioner.poll()
    auto.on_step(t + 0.25)
    assert auto.capacity_debt_retired == 2

    # but a POLICY-drained replacement is not a loss: drain it and
    # sweep again — no third launch
    auto._policy_drained.add(second)
    router.begin_drain(second)
    router.step(now=t + 0.30)
    auto.on_step(t + 10.0)
    auto.on_step(t + 20.0)
    assert len([p for p in auto.plans if p.launch_nodes]) == 2, \
        "a deliberate shrink must not re-trigger the debt"


def test_probation_opens_replacement_debt():
    """The ReplicaManager side of the feed: a replica held out of
    placement by crash-loop probation is lost capacity too — the
    autoscaler backfills it and the debt self-retires when the
    cooldown elapses."""
    cluster, scaler, router, provisioner, auto = _autoscale_rig(
        queue_low=0.0)
    t = time.monotonic()
    victim = router.replica_names[0]
    router.fail_replica(victim)
    router.step(now=t + 1.0)         # reaped: short life -> flap 1
    router.join_replica(f"{victim}#r1", FakeEngine(slots=2),
                        now=t + 2.0)  # probation (cooldown 2s default)
    auto.on_step(t + 2.1)
    launch = [p for p in auto.plans if p.launch_nodes]
    assert len(launch) == 1, "probation must open a replacement debt"
    key = f"probation:{victim}"
    assert key in auto.debts
    assert auto.debts[key]["kind"] == "probation"
    # cooldown elapses before the replacement joins: source cleared
    auto.on_step(t + 10.0)
    assert auto.capacity_debt_retired == 1
    assert router.metrics.metrics()["serving_capacity_debt"] == 0.0


def test_flapping_base_opens_one_probation_debt_not_one_per_respawn():
    """A crash-looping replica's probation source flickers OUT during
    every death gap (the handle is reaped between respawns).  The debt
    entry must linger through the gap and be reused by the next flap —
    NOT deleted and reopened, which would launch one surplus
    replacement node per respawn cycle.  The episode only closes when
    the base demonstrably heals (a live off-probation replica), after
    which a genuinely new flap opens a fresh debt."""
    cluster, scaler, router, provisioner, auto = _autoscale_rig(
        queue_low=0.0)
    t = time.monotonic()
    victim = router.replica_names[0]
    router.fail_replica(victim)
    router.step(now=t + 1.0)                       # flap 1 recorded
    router.join_replica(f"{victim}#r1", FakeEngine(slots=2),
                        now=t + 2.0)               # probation ~2s
    auto.on_step(t + 2.1)
    assert len([p for p in auto.plans if p.launch_nodes]) == 1
    key = f"probation:{victim}"

    # death gap: #r1 dies mid-cooldown -> source vanishes
    router.fail_replica(f"{victim}#r1")
    router.step(now=t + 2.5)
    auto.on_step(t + 2.6)
    assert key in auto.debts, \
        "the entry must LINGER through the death gap"
    # flap 2 rejoins on (longer) probation: the entry is reused
    router.join_replica(f"{victim}#r2", FakeEngine(slots=2),
                        now=t + 3.0)
    auto.on_step(t + 3.1)
    auto.on_step(t + 3.2)
    assert len([p for p in auto.plans if p.launch_nodes]) == 1, \
        "a flap cycle must not provision a second replacement"

    # the base heals: #r2 outlives its 4s cooldown -> episode closes
    auto.on_step(t + 7.5)
    assert key not in auto.debts

    # ...and a LATER fresh flap is a new episode with a new debt
    # (#r2 dies at 4.8s of life: past its cooldown, but still inside
    # probation_lifetime so the death counts as a flap)
    router.fail_replica(f"{victim}#r2")
    router.step(now=t + 7.8)
    router.join_replica(f"{victim}#r3", FakeEngine(slots=2),
                        now=t + 8.0)
    auto.on_step(t + 8.1)
    assert len([p for p in auto.plans if p.launch_nodes]) == 2


def test_quarantine_adopts_probation_replacement_no_double_provision():
    """One worker, one backfill across feed kinds: a crash-looper first
    surfaces as probation:<base> (replacement launched + joined), then
    blows its respawn budget and surfaces as quarantine:<base> — a
    DIFFERENT key.  The quarantine debt must adopt the live probation
    replacement instead of launching a second node."""
    cluster, scaler, router, provisioner, auto = _autoscale_rig(
        queue_low=0.0)
    feed = _DebtFeed()
    auto.supervisor = feed
    t = time.monotonic()
    victim = router.replica_names[0]
    router.fail_replica(victim)
    router.step(now=t + 1.0)
    router.join_replica(f"{victim}#r1", FakeEngine(slots=2),
                        now=t + 2.0)               # probation source
    auto.on_step(t + 2.1)
    assert len([p for p in auto.plans if p.launch_nodes]) == 1
    provisioner.poll()                             # replacement joins
    auto.on_step(t + 2.2)
    assert auto.capacity_debt_retired == 1

    # the budget blows: worker dies for good, supervisor quarantines it
    router.fail_replica(f"{victim}#r1")
    router.step(now=t + 2.5)
    feed.records.append({
        "key": f"quarantine:{victim}", "kind": "quarantine",
        "source": f"{victim}#r1", "until": t + 120.0,
    })
    auto.on_step(t + 2.6)
    assert len([p for p in auto.plans if p.launch_nodes]) == 1, \
        "the quarantine must adopt the live replacement, not launch"
    assert f"quarantine:{victim}" in auto.debts
    assert f"probation:{victim}" not in auto.debts
    kinds = [e["kind"] for e in router.recorder.events(256)]
    assert "capacity_debt_rekeyed" in kinds

    # sentence served: the adopted episode closes like any quarantine
    feed.records.clear()
    auto.on_step(t + 3.0)
    assert f"quarantine:{victim}" not in auto.debts


def test_same_poll_quarantine_and_probation_is_one_debt():
    """Both feeds can surface the SAME base in one poll (the budget
    blows while the dead respawn still sits in the manager awaiting
    reaping: supervisor says quarantine:<base>, manager still says
    probation:<base>).  The sweep must collapse them to one debt —
    keyed quarantine, the authoritative record — and stay stable
    across subsequent polls (no rekey ping-pong, no second node)."""
    cluster, scaler, router, provisioner, auto = _autoscale_rig(
        queue_low=0.0)
    feed = _DebtFeed()
    auto.supervisor = feed
    t = time.monotonic()
    victim = router.replica_names[0]
    router.fail_replica(victim)
    router.step(now=t + 1.0)
    router.join_replica(f"{victim}#r1", FakeEngine(slots=2),
                        now=t + 2.0)               # probation source on
    feed.records.append({
        "key": f"quarantine:{victim}", "kind": "quarantine",
        "source": f"{victim}#r1", "until": t + 120.0,
    })
    auto.on_step(t + 2.1)                          # both feeds, one poll
    assert len([p for p in auto.plans if p.launch_nodes]) == 1
    assert list(auto.debts) == [f"quarantine:{victim}"]
    auto.on_step(t + 2.2)
    auto.on_step(t + 2.3)
    assert len([p for p in auto.plans if p.launch_nodes]) == 1, \
        "the shadowed probation source must never open a second debt"
    assert list(auto.debts) == [f"quarantine:{victim}"]


def test_short_probation_debt_is_deferred_not_launched():
    """ISSUE 11 satellite (provisioning-latency-aware debts): a
    probation whose ``until`` horizon is shorter than the node-join
    latency floor self-retires before ANY replacement could take
    traffic — launching for it pays a full launch+drain cycle for
    nothing.  The debt opens DEFERRED (bookkept, no node) and clears
    silently when the source heals first."""
    cluster, scaler, router, provisioner, auto = _autoscale_rig(
        queue_low=0.0)
    feed = _DebtFeed()
    auto.supervisor = feed
    auto.join_latency_floor = 10.0  # no node has ever joined in <10s
    t = time.monotonic()
    feed.records.append({
        "key": "probation:w1", "kind": "probation",
        "source": "w1", "until": t + 2.0,   # 2s horizon << 10s floor
    })
    auto.on_step(t + 0.05)
    assert not [p for p in auto.plans if p.launch_nodes], \
        "a 2s probation must not launch a node that takes 10s to join"
    assert auto.debts["probation:w1"]["deferred"]
    assert auto.capacity_debt_deferred_total == 1
    # deferred entries stay out of the launched-but-unjoined gauge
    assert router.metrics.metrics()["serving_capacity_debt"] == 0.0
    kinds = [e["kind"] for e in router.recorder.events(64)]
    assert "capacity_debt_deferred" in kinds
    # the probation self-retires: the entry clears with NOTHING
    # provisioned and nothing counted as retired
    feed.records.clear()
    auto.on_step(t + 2.5)
    assert "probation:w1" not in auto.debts
    assert auto.capacity_debt_retired == 0
    assert not [p for p in auto.plans if p.launch_nodes]
    kinds = [e["kind"] for e in router.recorder.events(64)]
    assert "capacity_debt_deferred_cleared" in kinds


def test_fast_flapping_base_defers_until_quarantine_promotes():
    """The ROADMAP regression: a fast-flapping base whose ~2s
    first-flap probations each self-retire must pay ZERO launch+drain
    cycles — until the episode escalates (quarantine), at which point
    the deferred debt PROMOTES to a real launch that retires exactly
    once on join."""
    cluster, scaler, router, provisioner, auto = _autoscale_rig(
        queue_low=0.0)
    feed = _DebtFeed()
    auto.supervisor = feed
    auto.join_latency_floor = 10.0
    t = time.monotonic()
    # five flap cycles: probation appears (2s horizon), flickers out,
    # reappears — historical behavior provisioned a node per cycle
    for i in range(5):
        feed.records[:] = [{
            "key": "probation:w7", "kind": "probation",
            "source": "w7", "until": t + i + 2.0,
        }]
        auto.on_step(t + i + 0.1)
        feed.records.clear()
        auto.on_step(t + i + 0.6)
    assert not [p for p in auto.plans if p.launch_nodes], \
        "a fast-flapping base must not provision per flap"
    # one more flap is still live when the budget blows: the deferred
    # entry follows its base into the quarantine key (rekey) and
    # PROMOTES to a real launch
    feed.records[:] = [{
        "key": "probation:w7", "kind": "probation",
        "source": "w7", "until": t + 7.5,
    }]
    auto.on_step(t + 5.8)
    assert auto.debts["probation:w7"]["deferred"]
    feed.records[:] = [{
        "key": "quarantine:w7", "kind": "quarantine",
        "source": "w7", "until": t + 300.0,
    }]
    auto.on_step(t + 6.0)
    launches = [p for p in auto.plans if p.launch_nodes]
    assert len(launches) == 1, "escalation must launch exactly once"
    kinds = [e["kind"] for e in router.recorder.events(256)]
    assert "capacity_debt_promoted" in kinds
    provisioner.poll()
    auto.on_step(t + 6.1)
    assert auto.capacity_debt_retired == 1


def test_observed_join_latency_raises_the_deferral_floor():
    """The floor is LEARNED: once a real replacement join has been
    observed to take ~8s, later sub-horizon probations defer with no
    configuration at all."""
    cluster, scaler, router, provisioner, auto = _autoscale_rig(
        queue_low=0.0)
    feed = _DebtFeed()
    auto.supervisor = feed
    t = time.monotonic()
    # first episode: a quarantine launches; the node takes 8s to join
    feed.records.append({
        "key": "quarantine:w2", "kind": "quarantine",
        "source": "w2", "until": t + 600.0,
    })
    auto.on_step(t + 0.0)
    assert len([p for p in auto.plans if p.launch_nodes]) == 1
    provisioner.poll()                   # join observed at t+8
    auto.on_step(t + 8.0)
    assert auto.capacity_debt_retired == 1
    assert auto._join_floor() >= 7.9
    feed.records.clear()
    auto.on_step(t + 8.5)
    # second episode: a 2s probation now defers automatically
    feed.records[:] = [{
        "key": "probation:w3", "kind": "probation",
        "source": "w3", "until": t + 11.0,   # 2.4s horizon < ~8s floor
    }]
    auto.on_step(t + 8.6)
    assert auto.debts["probation:w3"]["deferred"]
    assert len([p for p in auto.plans if p.launch_nodes]) == 1


def test_replacement_trace_carries_replacement_for():
    """Replacement decisions get their own always-sampled autoscale
    trace: root attrs name what it backfills (``replacement_for``) and
    the stitched milestones cover node_create -> hello_join ->
    first_placement, closing ok when the replacement takes traffic."""
    cluster, scaler, router, provisioner, auto = _autoscale_rig(
        queue_low=0.0)
    feed = _DebtFeed()
    auto.supervisor = feed
    t = time.monotonic()
    feed.records.append({
        "key": "quarantine:w9", "kind": "quarantine",
        "source": "w9", "until": t + 60.0,
    })
    auto.on_step(t + 0.05)
    replacement = auto.debts["quarantine:w9"]["replacement"]
    provisioner.poll()
    # enough work that BOTH replicas get placements (ties go to the
    # incumbent, so fill its slots too)
    reqs = [router.submit(_prompt(i), 8) for i in range(6)]
    for _ in range(80):
        t += 0.05
        router.step(now=t)
        provisioner.poll()
        if not router.has_work:
            break
    assert all(r.state == ServingRequestState.DONE for r in reqs)
    traces = router.tracer.traces_named("autoscale", limit=50)
    rep = [tr for tr in traces
           if tr["spans"][0]["attrs"].get("replacement_for") == "w9"]
    assert len(rep) == 1, traces
    tree = rep[0]
    assert tree["spans"][0]["attrs"]["debt_kind"] == "quarantine"
    assert tree["status"] == "ok"
    names = _span_names(tree)
    assert "capacity_debt" in names
    for stage in ("node_create", "hello_join", "first_placement"):
        spans = [s for s in _spans_named(tree, stage)
                 if s["attrs"].get("replica") == replacement]
        assert spans, (stage, names)


# -- ISSUE 8: per-priority brown-out ----------------------------------------


def test_brownout_policy_hysteresis_and_ladder():
    from dlrover_tpu.serving.router import BrownoutPolicy

    bo = BrownoutPolicy(enter_pressure=2.0, exit_pressure=0.5,
                        dwell_seconds=1.0)
    with pytest.raises(ValueError):
        BrownoutPolicy(enter_pressure=1.0, exit_pressure=1.0)
    t = 100.0
    assert bo.update(t, 40, 4.0) == 0, "escalation needs a dwell"
    assert bo.update(t + 0.5, 40, 4.0) == 0
    assert bo.update(t + 1.0, 40, 4.0) == 1
    assert bo.update(t + 1.5, 40, 4.0) == 1, "one stage per dwell"
    assert bo.update(t + 2.1, 40, 4.0) == 2
    assert bo.update(t + 3.2, 40, 4.0) == 3
    assert bo.update(t + 4.5, 40, 4.0) == 3, "stage 3 is the ceiling"
    # inside the hysteresis band: hold, and reset both dwell clocks
    assert bo.update(t + 5.0, 4, 4.0) == 3
    assert bo.update(t + 9.0, 4, 4.0) == 3
    # recovery walks DOWN one stage per dwell below the exit watermark
    assert bo.update(t + 9.5, 1, 4.0) == 3
    assert bo.update(t + 10.5, 1, 4.0) == 2
    assert bo.update(t + 11.6, 0, 4.0) == 1
    assert bo.update(t + 12.7, 0, 4.0) == 0
    # a dead fleet with demand is MAXIMAL pressure, not zero
    assert BrownoutPolicy.compute_pressure(5, 0.0) == float("inf")
    assert BrownoutPolicy.compute_pressure(0, 0.0) == 0.0
    # the transition log tells the whole ordered story
    assert [(a, b) for a, b, _, _ in bo.transitions] == [
        (0, 1), (1, 2), (2, 3), (3, 2), (2, 1), (1, 0)]


def test_brownout_shed_answers_carry_retry_after_hint():
    """ISSUE 11 satellite: a shed answer names WHERE the ladder stands
    (stage + name) and HOW LONG the best-case recovery takes (exit
    watermark + dwell walk-down), so clients back off instead of
    hammering a shedding gateway — the Retry-After contract an HTTP
    front end maps 1:1 onto the 503 header."""
    from dlrover_tpu.serving.router import (
        BrownoutPolicy,
        BrownoutShedError,
    )

    bo = BrownoutPolicy(enter_pressure=2.0, exit_pressure=0.5,
                        dwell_seconds=2.0)
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4), brownout=bo)
    router.join_replica("r0", FakeEngine(slots=1, tokens_per_step=1),
                        now=1000.0)
    for i in range(20):
        router.submit(_prompt(i), 16, priority=PRIORITY_NORMAL,
                      now=1000.0)
    t = 1000.0
    router.step(now=t)
    router.step(now=t + 2.1)          # dwell earned: stage 1
    assert bo.stage == 1
    with pytest.raises(BrownoutShedError) as ei:
        router.submit(_prompt(99), 8, priority=PRIORITY_BATCH,
                      now=t + 2.2)
    err = ei.value
    assert err.stage == 1 and err.stage_name == "shed_batch"
    # pressure is still above exit: full walk-down = stage * dwell
    assert err.retry_after_s == pytest.approx(2.0)
    assert "recovery" in str(err)
    # deeper stage -> longer hint; and time already spent below the
    # exit watermark is credited against the first step
    router.step(now=t + 4.2)
    assert bo.stage == 2
    with pytest.raises(BrownoutShedError) as ei:
        router.submit(_prompt(98), 8, priority=PRIORITY_BATCH,
                      now=t + 4.3)
    assert ei.value.retry_after_s == pytest.approx(4.0)
    assert bo.expected_recovery_s(t + 4.3) == pytest.approx(4.0)
    # simulate pressure already below exit for 1.5s of the 2s dwell
    bo.update(t + 5.0, 0, 10.0)
    assert bo.expected_recovery_s(t + 6.5) == pytest.approx(
        0.5 + 2.0)  # remainder of this dwell + one more stage
    # stage 0 needs no hint
    bo2 = BrownoutPolicy()
    assert bo2.expected_recovery_s(0.0) == 0.0


@pytest.mark.parametrize("step_engine", ["event", "sweep"])
def test_brownout_sheds_batch_then_normal_never_high(step_engine):
    """The ordered-degradation acceptance: stage 1 rejects new BATCH,
    stage 2 expiry-cancels queued + in-flight BATCH through the cancel
    machinery, stage 3 rejects NORMAL — HIGH admits and completes
    through the whole brown-out, and recovery walks the ladder back
    down.  Parameterized over both step engines (ISSUE 15): the shed
    ORDER is a books-balance contract, not an implementation detail."""
    from dlrover_tpu.serving.router import (
        BrownoutPolicy,
        BrownoutShedError,
    )

    bo = BrownoutPolicy(enter_pressure=2.0, exit_pressure=0.5,
                        dwell_seconds=1.0)
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4),
        brownout=bo,
        step_engine=step_engine,
    )
    eng = FakeEngine(slots=2, tokens_per_step=2)
    t = 1000.0
    router.join_replica("r0", eng, now=t)
    high = [router.submit(_prompt(i), 8, priority=PRIORITY_HIGH, now=t)
            for i in range(4)]
    normal = [router.submit(_prompt(i), 8, priority=PRIORITY_NORMAL,
                            now=t) for i in range(8)]
    batch = [router.submit(_prompt(i), 8, priority=PRIORITY_BATCH,
                           now=t) for i in range(8)]

    router.step(now=t)
    assert bo.stage == 0, "no escalation before the dwell"
    # one in-flight BATCH for stage 2 to reclaim: park it directly on
    # the replica (the strict-priority queue would never place it
    # while HIGH/NORMAL wait)
    handle = router.manager.get("r0")
    inflight_batch = batch[0]
    router.gateway.remove(inflight_batch)
    handle.submit(inflight_batch)

    router.step(now=t + 1.1)
    assert bo.stage == 1
    with pytest.raises(BrownoutShedError):
        router.submit(_prompt(90), 8, priority=PRIORITY_BATCH,
                      now=t + 1.2)
    late_normal = router.submit(
        _prompt(91), 8, priority=PRIORITY_NORMAL, now=t + 1.2)

    router.step(now=t + 2.2)
    assert bo.stage == 2
    # queued AND in-flight BATCH are gone: slots + queue space freed
    for b in batch:
        assert b.state == ServingRequestState.CANCELLED, b.rid
    assert inflight_batch.engine_rid not in handle.inflight
    assert not eng.active or all(
        rid != inflight_batch.engine_rid for rid in eng.active), \
        "the engine slot must be reclaimed"

    router.step(now=t + 3.3)
    assert bo.stage == 3
    with pytest.raises(BrownoutShedError):
        router.submit(_prompt(92), 8, priority=PRIORITY_NORMAL,
                      now=t + 3.4)
    late_high = router.submit(
        _prompt(93), 8, priority=PRIORITY_HIGH, now=t + 3.4)

    # drain: HIGH and NORMAL complete, pressure falls, stages recover
    for i in range(200):
        t += 0.3
        router.step(now=t)
        if not router.has_work and bo.stage == 0:
            break
    assert bo.stage == 0, bo.transitions
    for r in high + [late_high]:
        assert r.state == ServingRequestState.DONE, (r.rid, r.state)
    for r in normal + [late_normal]:
        assert r.state == ServingRequestState.DONE, (r.rid, r.state)
    # the ladder went up and came back down IN ORDER
    assert [(a, b) for a, b, _, _ in bo.transitions] == [
        (0, 1), (1, 2), (2, 3), (3, 2), (2, 1), (1, 0)]
    # per-band shed accounting: BATCH and NORMAL refused, HIGH never
    gw = router.gateway
    assert gw.shed_by_priority[PRIORITY_BATCH] == 1
    assert gw.shed_by_priority[PRIORITY_NORMAL] == 1
    assert gw.shed_by_priority[PRIORITY_HIGH] == 0
    # books balance: every admitted request is DONE or CANCELLED, and
    # the counters agree with the requests
    done = sum(1 for r in high + normal + batch
               + [late_normal, late_high]
               if r.state == ServingRequestState.DONE)
    cancelled = sum(1 for r in high + normal + batch
                    + [late_normal, late_high]
                    if r.state == ServingRequestState.CANCELLED)
    assert gw.submitted == done + cancelled
    m = router.metrics.metrics()
    assert m["serving_requests_completed_total"] == done
    assert m["serving_requests_cancelled_total"] == cancelled
    assert m["serving_requests_rejected_total"] == 2
    assert m["serving_brownout_stage"] == 0.0
    # every transition is in the flight recorder
    stage_events = [e for e in router.recorder.events(256)
                    if e["kind"] == "brownout_stage"]
    assert [(e["prev"], e["stage"]) for e in stage_events] == [
        (0, 1), (1, 2), (2, 3), (3, 2), (2, 1), (1, 0)]


def test_transition_spec_is_importable_truth():
    """The DL009 spec in common/constants.py is runtime-checkable: it
    covers every enum state exactly, and terminal means terminal."""
    from dlrover_tpu.common.constants import (
        SERVING_REQUEST_TERMINAL_STATES,
        SERVING_REQUEST_TRANSITIONS,
    )

    states = {
        v for k, v in vars(ServingRequestState).items()
        if not k.startswith("_") and isinstance(v, str)
    }
    assert set(SERVING_REQUEST_TRANSITIONS) == states
    assert set(SERVING_REQUEST_TERMINAL_STATES) < states
    for s in SERVING_REQUEST_TERMINAL_STATES:
        assert SERVING_REQUEST_TRANSITIONS[s] == ()
    for s, targets in SERVING_REQUEST_TRANSITIONS.items():
        assert set(targets) <= states
        if s not in SERVING_REQUEST_TERMINAL_STATES:
            assert targets, f"non-terminal {s} must go somewhere"


def test_unmet_demand_does_not_latch_on_borrowed_capacity():
    """The fleet borrow signal must RELEASE: borrowed hosts push
    up_count past max_replicas, and measuring raw demand against that
    inflated count would keep unmet_demand positive forever (the
    coordinator would never return the loan).  Demand is measured as
    if only the serving-native pool existed."""
    cluster, scaler, router, provisioner, auto = _autoscale_rig(
        max_replicas=2, queue_low=0.5)
    t = time.monotonic()
    # two "borrowed" replicas beyond the native cap
    router.join_replica("host-8", FakeEngine(slots=2), now=t)
    router.join_replica("host-9", FakeEngine(slots=2), now=t)
    reqs = [router.submit(_prompt(i), 8) for i in range(40)]
    # one pump round records the gauges the autoscaler samples (and
    # runs on_step itself: the rig attaches the autoscaler)
    router.step(now=t + 0.05)
    router.step(now=t + 0.10)
    assert auto.unmet_demand > 0, "spike must register as unmet"
    # the spike drains (borrowed capacity did its job)
    while router.has_work:
        t += 0.05
        router.step(now=t)
    for _ in range(6):
        t += 0.3
        router.step(now=t)
    assert auto.unmet_demand == 0, \
        "zero load with 4 up replicas must not read as unmet demand"
    for r in reqs:
        r.result(timeout=5)
