"""Graceful-degradation chaos suite (ISSUE 5): end-to-end request
cancellation, crash-loop quarantine, and the frame-level fault-
injection harness (serving/remote/faults.py).

The acceptance bar: under a seeded fault schedule (a torn connection, a
heartbeat stall, an abrupt worker death, a crash-looping worker) a
200-request stream completes with ZERO lost requests; every cancelled
or expired in-flight request's engine slot is reclaimed (asserted via
worker STATS and local-engine ``slots_free()``); a crash-looping
worker's respawn timestamps show strictly increasing gaps and end in
quarantine rather than a hot loop.  Subprocess scenarios carry
``@pytest.mark.slow``; the same machinery is covered fast in-thread.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

msgpack = pytest.importorskip(
    "msgpack", reason="remote fabric frames are msgpack")

from dlrover_tpu.common.constants import (  # noqa: E402
    ServingFabric,
    ServingRequestState,
)
from dlrover_tpu.serving.remote.faults import (  # noqa: E402
    FaultSchedule,
    FaultyFrameConnection,
)
from dlrover_tpu.serving.remote.proxy import RemoteReplicaHandle  # noqa: E402
from dlrover_tpu.serving.remote.supervisor import (  # noqa: E402
    WorkerRecord,
    WorkerSupervisor,
)
from dlrover_tpu.serving.remote.worker import (  # noqa: E402
    FakeEngine,
    WorkerServer,
)
from dlrover_tpu.serving.router import (  # noqa: E402
    ContinuousBatchScheduler,
    RequestGateway,
    ServingRouter,
)
from dlrover_tpu.serving.router.gateway import RequestTimedOut  # noqa: E402
from dlrover_tpu.serving.router.replica import (  # noqa: E402
    ReplicaManager,
    base_replica_name,
)
from dlrover_tpu.utils.tracing import FlightRecorder  # noqa: E402


def _prompt(i, n=8):
    return np.full(n, i % 251, np.int32)


def _drive(router, timeout=30.0, extra=None):
    deadline = time.monotonic() + timeout
    while router.has_work:
        assert time.monotonic() < deadline, (
            f"router still busy after {timeout}s "
            f"(depth={router.gateway.depth()})")
        router.step()
        if extra is not None:
            extra()
        time.sleep(0.002)


# -- fault schedule semantics ------------------------------------------------


def test_fault_schedule_after_count_and_stall_semantics():
    sched = FaultSchedule([
        {"op": "drop", "kind": "DONE", "after": 2, "count": 2},
        {"op": "stall", "kind": "STATS", "after": 3, "seconds": 60.0},
    ], seed=0)
    # DONE #1 passes, #2 and #3 drop, #4 passes again
    assert sched.actions_for("DONE") == []
    assert sched.actions_for("DONE")[0]["op"] == "drop"
    assert sched.actions_for("DONE")[0]["op"] == "drop"
    assert sched.actions_for("DONE") == []
    # STATS stall triggers on the 3rd and swallows everything after
    assert sched.actions_for("STATS") == []
    assert sched.actions_for("STATS") == []
    assert sched.actions_for("STATS")[0]["op"] == "stall"
    assert sched.actions_for("STATS")[0]["op"] == "stall"
    # other kinds unaffected by the STATS stall
    assert sched.actions_for("TOKEN") == []
    assert [e["op"] for e in sched.fired()].count("drop") == 2
    assert len(sched.fired("stall")) >= 2


def test_fault_schedule_from_env_and_seeded_jitter():
    payload = {"seed": 7, "faults": [
        {"op": "delay", "kind": "TOKEN", "seconds": 0.001,
         "jitter": 0.002},
    ]}
    env = {ServingFabric.FAULTS_ENV: json.dumps(payload)}
    a = FaultSchedule.from_env(env)
    b = FaultSchedule.from_env(env)
    assert a is not None and b is not None
    da = a.actions_for("TOKEN")[0]["seconds"]
    db = b.actions_for("TOKEN")[0]["seconds"]
    assert da == db, "same seed must replay the same perturbation"
    assert 0.001 <= da <= 0.003
    assert FaultSchedule.from_env({}) is None


def test_fault_schedule_rejects_unknown_op():
    with pytest.raises(ValueError):
        FaultSchedule([{"op": "explode"}])


# -- in-thread workers with injectable faults --------------------------------


class _ThreadedWorker:
    def __init__(self, fault_schedule=None, **engine_kw):
        self.engine = FakeEngine(**engine_kw)
        self.server = WorkerServer(
            self.engine, fault_schedule=fault_schedule)
        self.thread = threading.Thread(
            target=self.server.serve_forever, daemon=True)
        self.thread.start()

    def proxy(self, name, **kw):
        return RemoteReplicaHandle(self.server.addr, name=name, **kw)

    def stop(self):
        self.server.crash()


@pytest.fixture()
def workers():
    made = []

    def factory(fault_schedule=None, **kw):
        w = _ThreadedWorker(fault_schedule=fault_schedule, **kw)
        made.append(w)
        return w

    yield factory
    for w in made:
        w.stop()


def test_torn_connection_fails_over_zero_lost(workers):
    """A connection torn mid-length-prefix (the SIGKILL-mid-send wire
    signature) must read as a dead replica, fail over, and lose
    nothing."""
    sched = FaultSchedule(
        [{"op": "tear", "kind": "TOKEN", "after": 5}], seed=1)
    torn = workers(fault_schedule=sched, slots=4, tokens_per_step=2,
                   step_delay=0.002)
    ok = workers(slots=4, tokens_per_step=2)
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4))
    router.join_replica("torn", torn.proxy("torn"))
    router.join_replica("ok", ok.proxy("ok"))
    reqs = [router.submit(_prompt(i), 8) for i in range(20)]
    _drive(router)
    assert sched.fired("tear"), "the tear must actually have fired"
    lost = [r for r in reqs if r.state != ServingRequestState.DONE]
    assert not lost
    assert router.metrics.metrics()[
        "serving_requests_requeued_total"] >= 1
    assert router.replica_names == ["ok"]


def test_heartbeat_stall_reads_as_silent_and_fails_over(workers):
    """A worker whose socket stays open but whose frames stop (wedged
    event loop, SIGSTOP) trips the proxy's frame-staleness check."""
    sched = FaultSchedule(
        [{"op": "stall", "kind": "*", "after": 10, "seconds": 60.0}],
        seed=2)
    stalled = workers(fault_schedule=sched, slots=4, tokens_per_step=2,
                      step_delay=0.002)
    ok = workers(slots=4, tokens_per_step=2)
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4))
    router.join_replica(
        "stalled", stalled.proxy("stalled", frame_timeout=0.5))
    router.join_replica("ok", ok.proxy("ok"))
    reqs = [router.submit(_prompt(i), 8) for i in range(20)]
    _drive(router, timeout=30.0)
    assert sched.fired("stall")
    assert all(r.state == ServingRequestState.DONE for r in reqs)
    assert router.replica_names == ["ok"]


def test_duplicated_token_does_not_corrupt_result(workers):
    """A duplicated TOKEN frame (retransmit-style) may echo in the
    stream, but DONE's full output stays authoritative and the replica
    must NOT be failed over."""
    sched = FaultSchedule(
        [{"op": "dup", "kind": "TOKEN", "after": 1, "count": 3}],
        seed=3)
    w = workers(fault_schedule=sched, slots=2, tokens_per_step=2)
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4))
    router.join_replica("dup", w.proxy("dup"))
    req = router.submit(_prompt(1), 8)
    _drive(router)
    assert sched.fired("dup")
    assert req.state == ServingRequestState.DONE
    assert req.result(timeout=0).size == 8, \
        "DONE's authoritative output must win over duplicated frames"
    assert router.replica_names == ["dup"], \
        "a duplicated frame is noise, not a replica death"
    assert router.metrics.metrics()[
        "serving_requests_requeued_total"] == 0


def test_dropped_done_recovered_by_expiry_cancel(workers):
    """A DONE frame dropped on the floor would strand its request
    in-flight forever; with ``cancel_inflight_on_expiry`` the deadline
    aborts it, a CANCEL reclaims the (already-free) slot, and the
    router goes idle instead of pumping a ghost."""
    sched = FaultSchedule(
        [{"op": "drop", "kind": "DONE", "after": 1}], seed=4)
    w = workers(fault_schedule=sched, slots=2, tokens_per_step=2)
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4),
        cancel_inflight_on_expiry=True,
    )
    router.join_replica("droppy", w.proxy("droppy"))
    req = router.submit(_prompt(1), 8, timeout=1.0)
    _drive(router, timeout=20.0)
    assert sched.fired("drop")
    assert req.state == ServingRequestState.TIMED_OUT
    with pytest.raises(RequestTimedOut):
        req.result(timeout=0)
    assert not router.has_work, "the ghost request must be gone"
    # the worker finished the request long ago: its slots are free and
    # the trace closed with the timeout status
    assert w.engine.slots_free() == 2
    assert w.engine.used_blocks == 0
    m = router.metrics.metrics()
    assert m["serving_requests_timed_out_total"] == 1


# -- cancellation end-to-end -------------------------------------------------


def test_client_cancel_mid_generation_reclaims_remote_slot(workers):
    """THE cancellation path: a request cancelled mid-decode frees its
    remote engine slot and KV blocks, visible in the next STATS."""
    w = workers(slots=2, tokens_per_step=1, step_delay=0.01)
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4))
    router.join_replica("rw", w.proxy("rw"))
    req = router.submit(_prompt(1), 500)
    deadline = time.monotonic() + 10.0
    handle = router.manager.get("rw")
    while not handle.inflight and time.monotonic() < deadline:
        router.step()
        time.sleep(0.002)
    assert handle.inflight, "cancel must land mid-generation"
    assert w.engine.active, "the engine must actually be decoding"
    assert req.cancel() is True
    _drive(router, timeout=10.0)
    assert req.state == ServingRequestState.CANCELLED
    with pytest.raises(RequestTimedOut):
        req.result(timeout=0)
    # the CANCEL frame reached the engine: slot + blocks reclaimed
    deadline = time.monotonic() + 5.0
    while w.engine.active and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not w.engine.active
    assert w.engine.used_blocks == 0
    # ... and the freed capacity reached the router's ledger via the
    # post-cancel STATS
    deadline = time.monotonic() + 5.0
    while handle.slots_free() < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert handle.slots_free() == 2
    m = router.metrics.metrics()
    assert m["serving_requests_cancelled_total"] == 1
    assert m["serving_cancel_send_failures_total"] == 0
    # the span tree closed with the cancelled status
    tree = router.tracer.get_tree(req.trace.trace_id)
    assert tree["status"] == ServingRequestState.CANCELLED


def test_client_cancel_while_queued():
    """A cancel before placement drops the request from the queue —
    no replica ever sees it."""
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4))
    router.join_replica("e", FakeEngine(slots=1, tokens_per_step=1))
    blocker = router.submit(_prompt(0), 50)
    queued = router.submit(_prompt(1), 4)
    router.step()   # blocker takes the only slot; queued waits
    assert queued.state == ServingRequestState.QUEUED
    assert queued.cancel() is True
    router.step()
    assert queued.state == ServingRequestState.CANCELLED
    assert router.gateway.depth() == 0
    _drive(router, timeout=10.0)
    assert blocker.state == ServingRequestState.DONE
    assert router.metrics.metrics()[
        "serving_requests_cancelled_total"] == 1
    # cancel of an already-finished request is refused
    assert blocker.cancel() is False


def test_cancel_inflight_on_expiry_local_engine_reclaims_slot():
    """The policy knob against a LOCAL engine: expiry mid-generation
    frees the slot for the waiting request (slot reclamation is what
    continuous batching lives on)."""
    eng = FakeEngine(slots=1, tokens_per_step=1)
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4),
        cancel_inflight_on_expiry=True,
    )
    t0 = 100.0
    router.join_replica("local", eng, now=t0)
    hog = router.submit(_prompt(0), 1000, timeout=5.0, now=t0)
    waiter = router.submit(_prompt(1), 4, timeout=None, now=t0)
    router.step(now=t0 + 1.0)   # hog placed, decoding
    assert hog.state == ServingRequestState.RUNNING
    assert eng.slots_free() == 0
    router.step(now=t0 + 6.0)   # hog past deadline: abort + cancel
    assert hog.state == ServingRequestState.TIMED_OUT
    for _ in range(10):
        router.step(now=t0 + 7.0)
        if waiter.state == ServingRequestState.DONE:
            break
    assert waiter.state == ServingRequestState.DONE, \
        "the reclaimed slot must serve the waiting request"
    assert eng.used_blocks == 0
    assert router.metrics.metrics()[
        "serving_requests_timed_out_total"] == 1


def test_adapter_cancel_frees_paged_engine_blocks():
    """InferenceEngineAdapter.cancel against the REAL paged engine:
    the slot and its KV blocks return to the pool mid-generation."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.serving.engine import InferenceEngine
    from dlrover_tpu.serving.router import InferenceEngineAdapter

    cfg = LlamaConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    eng = InferenceEngine(cfg, variables, max_slots=2, chunk=4,
                          paged=True, block_size=16, seed=0)
    adapter = InferenceEngineAdapter(eng)
    free0 = adapter.blocks_free()
    rid = adapter.add_request(_prompt(1), 32)
    eng.step()          # admit + decode a little
    assert adapter.blocks_free() < free0
    assert adapter.cancel(rid) is True
    assert adapter.slots_free() == 2
    assert adapter.blocks_free() == free0, \
        "cancel must free the paged KV blocks"
    # cancelling a gone rid is a delivered no-op, and a queued (not
    # yet admitted) request is cancellable too
    assert adapter.cancel(rid) is True
    rid2 = adapter.add_request(_prompt(2), 8)
    assert adapter.cancel(rid2) is True
    assert not eng.has_work
    # the engine still serves after cancels
    rid3 = adapter.add_request(_prompt(3), 4)
    for _ in range(20):
        done = eng.step()
        if done:
            break
    assert done and done[0].rid == rid3


def test_router_cancel_mid_chunked_prefill_frees_blocks():
    """PR 5 reclamation extended to HALF-PREFILLED slots, through the
    full router cancel machinery: a long prompt admitted into a
    chunked-prefill paged engine is cancelled while its real_len
    cursor is mid-prompt — the router sweep aborts it, the engine
    frees the slot AND the lifetime block allocation, and the books
    balance for the traffic that follows."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.serving.engine import InferenceEngine
    from dlrover_tpu.serving.router import (
        ContinuousBatchScheduler,
        InferenceEngineAdapter,
        ServingRouter,
    )

    cfg = LlamaConfig.tiny(max_seq_len=96, dtype=jnp.float32)
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    eng = InferenceEngine(cfg, variables, max_slots=2, chunk=4,
                          paged=True, block_size=8, prefill_chunk=16,
                          seed=0)
    router = ServingRouter(
        gateway=RequestGateway(max_pending=8),
        scheduler=ContinuousBatchScheduler(block_size=8),
    )
    router.join_replica("chunked", InferenceEngineAdapter(eng))
    total = eng._blockmgr.num_blocks - 1  # minus the trash sink
    long_prompt = np.arange(64, dtype=np.int32) % cfg.vocab_size
    req = router.submit(long_prompt, 8)
    # step until the engine is provably MID-prefill (cursor interior)
    for _ in range(6):
        router.step()
        slot = next((s for s, r in enumerate(eng._slot_req)
                     if r is not None), None)
        if slot is not None and eng._prefilling[slot] \
                and 0 < int(eng._prefill_pos[slot]) < 64:
            break
    assert slot is not None and eng._prefilling[slot]
    assert req.cancel() is True
    router.step()  # the sweep acts on the withdrawal
    assert req.state == ServingRequestState.CANCELLED
    assert eng._slot_req[slot] is None
    assert not eng._prefilling[slot]
    assert eng._blockmgr.available_blocks == total, (
        "router cancel mid-prefill must free the lifetime blocks"
    )
    assert router.gateway.cancelled == 1
    # the slot serves fresh traffic afterwards, books still balanced
    req2 = router.submit(np.arange(12, dtype=np.int32), 4)
    router.run_until_idle()
    assert len(req2.output) == 4
    assert eng._blockmgr.available_blocks == total


def test_cancel_vs_failover_race_no_resurrection():
    """A failover racing a cancel must not resurrect the request:
    requeue_front of an already-terminal request is a no-op."""
    gw = RequestGateway()
    req = gw.submit(_prompt(1), 4)
    gw.remove(req)
    req.state = ServingRequestState.RUNNING      # placed on a replica
    req.cancel()
    # the router's sweep aborts it (as step() would)...
    req.abort(ServingRequestState.CANCELLED)
    gw.cancelled += 1
    # ...then the replica dies and failover tries to requeue it
    assert gw.requeue_front([req]) == []
    assert req.state == ServingRequestState.CANCELLED
    assert gw.depth() == 0, "a cancelled request must stay dead"
    assert req.requeues == 0, "no replay was burned on the corpse"
    with pytest.raises(RequestTimedOut):
        req.result(timeout=0)


def test_cancel_on_dead_replica_counts_send_failure(workers):
    """A cancel whose CANCEL frame cannot be delivered (worker gone
    between sweeps) is counted — a live fleet with rising cancel-send
    failures is a real signal, not noise."""
    w = workers(slots=2, tokens_per_step=1, step_delay=0.01)
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4))
    proxy = w.proxy("rw")
    router.join_replica("rw", proxy)
    req = router.submit(_prompt(1), 500)
    deadline = time.monotonic() + 10.0
    handle = router.manager.get("rw")
    while not handle.inflight and time.monotonic() < deadline:
        router.step()
        time.sleep(0.002)
    assert handle.inflight
    # tear the worker down and cancel before the router notices the
    # death: the sweep runs before the reap in the same step
    w.stop()
    deadline = time.monotonic() + 5.0
    while proxy.dead is None and time.monotonic() < deadline:
        time.sleep(0.01)
    req.cancel()
    router.step()
    assert req.state == ServingRequestState.CANCELLED
    assert router.metrics.metrics()[
        "serving_cancel_send_failures_total"] == 1
    assert proxy.cancel_send_failures == 1
    # failover of the dead replica must NOT resurrect the cancelled
    # request
    _drive(router, timeout=10.0)
    assert req.state == ServingRequestState.CANCELLED
    assert req.requeues == 0


# -- crash-loop quarantine (supervisor) --------------------------------------


class _StubProc:
    def __init__(self, pid):
        self.pid = pid
        self.returncode = None

    def poll(self):
        return self.returncode


class _StubProxy:
    def close(self, goodbye=True):
        pass


class _StubSupervisor(WorkerSupervisor):
    """spawn() without fork/exec: tests flip ``record.proc.returncode``
    to simulate crashes and drive ``poll(now=...)`` deterministically."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self._pid = 1000
        self.spawned = []

    def spawn(self, name=None, join=True, managed=True):
        with self._lock:
            if name is None:
                name = f"{self.name_prefix}-{self._next}"
                self._next += 1
        self._pid += 1
        record = WorkerRecord(
            name, _StubProc(self._pid), "127.0.0.1:0", _StubProxy(),
            managed)
        with self._lock:
            self.workers[name] = record
        self.spawned.append(name)
        return record


def _crash_current(sup):
    for record in sup.workers.values():
        record.proc.returncode = 9


def test_supervisor_backoff_schedule_and_quarantine():
    """A crash-looping worker is respawned on an exponential, jittered
    backoff — NEVER a hot loop — and lands in quarantine once it blows
    the sliding-window budget."""
    recorder = FlightRecorder()
    sup = _StubSupervisor(
        respawn=True, max_respawns=3, respawn_window=300.0,
        backoff_base=0.5, backoff_max=60.0, backoff_jitter=0.25,
        quarantine_seconds=50.0, seed=42, recorder=recorder)
    sup.spawn(name="crashy")
    t = 100.0
    while "crashy" not in {
        base_replica_name(n) for n in sup.quarantined
    } and t < 100.0 + 200.0:
        _crash_current(sup)
        sup.poll(now=t)
        t += 0.05
    quarantined = [r for n, r in sup.quarantined.items()
                   if base_replica_name(n) == "crashy"]
    assert quarantined, "the crash loop must end in quarantine"
    record = quarantined[0]
    # the planned schedule shows exponential growth...
    backoffs = [e["backoff_s"] for e in record.respawn_schedule]
    assert len(backoffs) == 3, "budget 3 = three metered respawns"
    assert all(b2 > b1 for b1, b2 in zip(backoffs, backoffs[1:]))
    assert backoffs[0] >= 0.5 and backoffs[-1] >= 2.0
    # ...and the ACTUAL respawn timestamps show strictly increasing
    # gaps (the anti-hot-loop acceptance)
    times = record.respawn_times
    assert len(times) == 3
    gaps = [b - a for a, b in zip(times, times[1:])]
    assert all(g2 > g1 for g1, g2 in zip(gaps, gaps[1:])), gaps
    # seeded: a second supervisor replays the identical schedule
    sup2 = _StubSupervisor(
        respawn=True, max_respawns=3, respawn_window=300.0,
        backoff_base=0.5, backoff_max=60.0, backoff_jitter=0.25,
        quarantine_seconds=50.0, seed=42)
    sup2.spawn(name="crashy")
    t = 100.0
    while not sup2.quarantined and t < 300.0:
        _crash_current(sup2)
        sup2.poll(now=t)
        t += 0.05
    rec2 = list(sup2.quarantined.values())[0]
    assert [e["backoff_s"] for e in rec2.respawn_schedule] == backoffs
    # flight recorder saw the whole story
    kinds = [e["kind"] for e in recorder.events(256)]
    assert "worker_respawn_scheduled" in kinds
    assert "worker_quarantined" in kinds
    assert sup.quarantined_total == 1


def test_supervisor_quarantine_exit_earns_fresh_window():
    """A served quarantine sentence resumes respawns with a clean
    crash window (the fleet is never silently permanently smaller) —
    and a worker that LIVES clears its flap history."""

    class _Router:
        def __init__(self):
            from dlrover_tpu.serving.router.metrics import RouterMetrics

            self.metrics = RouterMetrics()

    recorder = FlightRecorder()
    router = _Router()
    sup = _StubSupervisor(
        router=router, respawn=True, max_respawns=1,
        respawn_window=300.0, backoff_base=0.5, backoff_jitter=0.0,
        quarantine_seconds=10.0, seed=0, recorder=recorder)
    sup.spawn(name="flappy")
    t = 100.0
    while not sup.quarantined and t < 200.0:
        _crash_current(sup)
        sup.poll(now=t)
        t += 0.05
    assert sup.quarantined
    assert router.metrics.metrics()[
        "serving_worker_quarantined_total"] == 1.0
    until = list(sup.quarantined.values())[0].quarantine_until
    # sitting out the sentence...
    sup.poll(now=until - 1.0)
    assert sup.quarantined and not sup.workers
    # ...then release: respawned with an EMPTY crash window
    sup.poll(now=until + 0.1)
    assert not sup.quarantined
    assert sup.pending or sup.workers
    sup.poll(now=until + 0.2)
    assert len(sup.workers) == 1
    revived = list(sup.workers.values())[0]
    assert revived.crash_times == []
    kinds = [e["kind"] for e in recorder.events(256)]
    assert "worker_quarantine_exit" in kinds
    # this time it lives: a crash AFTER the window clears the history
    # and is metered from scratch (backoff back to base)
    revived.proc.returncode = 9
    sup.poll(now=until + 400.0)
    fresh_backoffs = [
        e["backoff_s"] for e in revived.respawn_schedule
        if e["exit_at"] >= until + 400.0
    ]
    assert fresh_backoffs == [0.5]


def test_supervisor_kill_unknown_name_raises_value_error():
    sup = _StubSupervisor(respawn=False)
    sup.spawn(name="alive")
    with pytest.raises(ValueError) as e:
        sup.kill("ghost")
    assert "ghost" in str(e.value) and "alive" in str(e.value)


def test_supervisor_voluntary_exit_not_metered():
    """rc==0 (GOODBYE-initiated) is a scale decision, not a crash: no
    respawn, no backoff, no quarantine accounting."""
    sup = _StubSupervisor(respawn=True, max_respawns=1)
    rec = sup.spawn(name="retired")
    rec.proc.returncode = 0
    sup.poll(now=100.0)
    assert not sup.workers and not sup.pending and not sup.quarantined


def test_supervisor_worker_state_metric_labels_on_metrics(tmp_path):
    """The per-worker state family (ISSUE 6 satellite): one
    ``serving_worker_state{worker=…,state=…} 1`` sample per supervised
    worker — running / backoff / quarantined — rendered as Prometheus
    text and served end-to-end through ``MetricsExporter``."""
    import re
    import urllib.request

    from dlrover_tpu.utils.profiler import MetricsExporter

    sup = _StubSupervisor(
        respawn=True, max_respawns=2, respawn_window=300.0,
        backoff_base=0.5, backoff_max=60.0, backoff_jitter=0.25,
        quarantine_seconds=50.0, seed=7)
    sup.spawn(name="steady")
    sup.spawn(name="crashy")
    t = 100.0
    while not sup.quarantined and t < 300.0:
        for n, r in list(sup.workers.items()):
            if base_replica_name(n) == "crashy":
                r.proc.returncode = 9
        sup.poll(now=t)
        t += 0.05
    assert sup.quarantined, "crashy must have blown the respawn budget"
    flappy = sup.spawn(name="flappy")
    flappy.proc.returncode = 9
    sup.poll(now=t)  # first crash: backoff pending, not quarantine

    text = sup.render_worker_state()
    assert "# TYPE serving_worker_state gauge" in text
    assert "# HELP serving_worker_state" in text
    samples = re.findall(
        r'serving_worker_state\{worker="([^"]+)",state="([^"]+)"\} 1',
        text)
    by_base = {base_replica_name(w): s for w, s in samples}
    assert by_base == {
        "steady": "running",
        "crashy": "quarantined",
        "flappy": "backoff",
    }, samples
    # exporter wiring: the labeled family reaches a real /metrics scrape
    exporter = MetricsExporter()
    exporter.add_text_source(sup.render_worker_state)
    exporter.start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{exporter.port}/metrics",
            timeout=5).read().decode()
        assert ('serving_worker_state{worker="steady",'
                'state="running"} 1') in body
    finally:
        exporter.stop()


# -- replica probation (router) ----------------------------------------------


def test_replica_probation_cooldown_grows_and_clears():
    mgr = ReplicaManager(probation_lifetime=5.0,
                         probation_cooldown=2.0, probation_max=60.0)
    from dlrover_tpu.serving.router.replica import ReplicaHandle

    t = 1000.0
    h0 = mgr.join(ReplicaHandle("w", FakeEngine()), now=t)
    assert h0.probation_until == 0.0, "a first join has no history"
    h0.fail()
    mgr.reap_dead(now=t + 1.0)          # died 1s after joining: flap 1
    mgr.dead_handles.clear()
    h1 = mgr.join(ReplicaHandle("w#r1", FakeEngine()), now=t + 2.0)
    assert h1.probation_until == pytest.approx(t + 4.0)   # +2.0s
    assert mgr.schedulable(now=t + 3.0) == []
    assert mgr.probation_count(now=t + 3.0) == 1
    assert mgr.schedulable(now=t + 4.5) == [h1]
    assert mgr.probation_count(now=t + 4.5) == 0
    h1.fail()
    mgr.reap_dead(now=t + 5.0)          # another short life: flap 2
    mgr.dead_handles.clear()
    h2 = mgr.join(ReplicaHandle("w#r2", FakeEngine()), now=t + 6.0)
    assert h2.probation_until == pytest.approx(t + 10.0)  # +4.0s
    # this generation survives past the flap threshold: history clears
    h2.fail()
    mgr.reap_dead(now=t + 30.0)
    mgr.dead_handles.clear()
    h3 = mgr.join(ReplicaHandle("w#r3", FakeEngine()), now=t + 31.0)
    assert h3.probation_until == 0.0, \
        "a replica that lived must clear its crash-loop history"


def test_probation_blocks_placement_until_cooldown():
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4),
        manager=ReplicaManager(probation_lifetime=5.0,
                               probation_cooldown=4.0),
    )
    t = 500.0
    router.join_replica("w", FakeEngine(), now=t)
    router.fail_replica("w")
    router.step(now=t + 1.0)            # reaped: short life, flap 1
    router.join_replica("w#r1", FakeEngine(), now=t + 2.0)
    req = router.submit(_prompt(1), 4, now=t + 2.0)
    router.step(now=t + 3.0)            # inside the 4s cooldown
    assert req.state == ServingRequestState.QUEUED, \
        "probation must keep the flapper out of placement"
    assert router.metrics.metrics()["serving_replica_probation"] == 1.0
    router.step(now=t + 6.5)            # cooldown over
    assert req.state == ServingRequestState.DONE
    assert router.metrics.metrics()["serving_replica_probation"] == 0.0
    kinds = [e["kind"] for e in router.recorder.events(64)]
    assert "replica_probation" in kinds


# -- recv-side frame faults (ISSUE 8) ----------------------------------------


def test_fault_schedule_side_field_and_new_ops_validate():
    # side defaults to send (back-compat) and validates
    sched = FaultSchedule([{"op": "drop"}])
    assert sched.specs[0]["side"] == "send"
    with pytest.raises(ValueError):
        FaultSchedule([{"op": "drop", "side": "middle"}])
    # recv-side specs never fire at the send hook and vice versa
    sched = FaultSchedule([
        {"op": "drop", "kind": "TOKEN", "side": "recv"},
        {"op": "dup", "kind": "TOKEN", "side": "send"},
    ])
    assert [a["op"] for a in sched.actions_for("TOKEN")] == ["dup"]
    assert [a["op"] for a in sched.actions_for("TOKEN", side="recv")] \
        == ["drop"]
    # the ledger records which hook fired
    assert {e["side"] for e in sched.injected} == {"send", "recv"}


def test_recv_reorder_token_after_done_is_dropped(workers):
    """A TOKEN frame overtaken by its own DONE (recv-side ``reorder``
    on the proxy's real reader thread) must be dropped by the
    staleness guard — the authoritative DONE output wins, and an
    out-of-order frame is noise, not a replica death."""
    sched = FaultSchedule([
        {"op": "reorder", "kind": "TOKEN", "side": "recv",
         "after": 2, "count": 2},
    ], seed=21)
    w = workers(slots=2, tokens_per_step=2)
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4))
    router.join_replica("ro", w.proxy("ro", fault_schedule=sched))
    reqs = [router.submit(_prompt(i), 8) for i in range(4)]
    _drive(router)
    assert sched.fired("reorder"), "the reorder must actually fire"
    for r in reqs:
        assert r.state == ServingRequestState.DONE
        assert r.result(timeout=0).size == 8, \
            "DONE's authoritative output must survive the reorder"
    assert router.replica_names == ["ro"]
    assert router.metrics.metrics()[
        "serving_requests_requeued_total"] == 0


def test_recv_duplicated_done_is_ignored(workers):
    """A DONE delivered twice to the reader (recv-side ``dup``) must
    complete the request exactly once: the second copy's rid is gone
    from the in-flight set and is silently dropped."""
    sched = FaultSchedule([
        {"op": "dup", "kind": "DONE", "side": "recv",
         "after": 1, "count": 2},
    ], seed=22)
    w = workers(slots=2, tokens_per_step=2)
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4))
    router.join_replica("dd", w.proxy("dd", fault_schedule=sched))
    reqs = [router.submit(_prompt(i), 8) for i in range(3)]
    _drive(router)
    assert sched.fired("dup")
    assert all(r.state == ServingRequestState.DONE for r in reqs)
    m = router.metrics.metrics()
    assert m["serving_requests_completed_total"] == 3, \
        "a duplicated DONE must not double-complete"
    assert router.replica_names == ["dd"]


def test_recv_stale_stats_cannot_regress_ledger(workers):
    """STATS arriving out of order (recv-side ``reorder``) must not
    regress the proxy's capacity ledger: the worker's monotonic
    ``generated_tokens`` counter is the staleness watermark, and an
    older snapshot is dropped by the REAL parsing path
    (``RemoteReplicaHandle._dispatch``)."""
    sched = FaultSchedule([
        {"op": "reorder", "kind": "STATS", "side": "recv",
         "after": 3, "count": 3},
    ], seed=23)
    w = workers(slots=4, tokens_per_step=2)
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4))
    proxy = w.proxy("st", fault_schedule=sched)
    router.join_replica("st", proxy)
    reqs = [router.submit(_prompt(i), 8) for i in range(8)]
    _drive(router)
    assert sched.fired("reorder")
    assert all(r.state == ServingRequestState.DONE for r in reqs)
    # the ledger converges to the true free capacity despite reorders
    deadline = time.monotonic() + 5.0
    while proxy.slots_free() < 4 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert proxy.slots_free() == 4
    # the guard itself, through the real parser: an older snapshot
    # (lower generated_tokens) must lose to a newer one
    proxy._dispatch({"kind": "STATS", "slots_free": 1,
                     "blocks_free": 8.0, "generated_tokens": 10**9})
    assert proxy.slots_free() == 1
    proxy._dispatch({"kind": "STATS", "slots_free": 4,
                     "blocks_free": 999.0, "generated_tokens": 5})
    assert proxy.slots_free() == 1, \
        "a stale STATS must not resurrect phantom capacity"
    assert proxy.stale_stats_dropped >= 1
    # an EQUAL watermark is a legitimate refresh (cancel frees slots
    # without generating tokens)
    proxy._dispatch({"kind": "STATS", "slots_free": 2,
                     "blocks_free": 16.0, "generated_tokens": 10**9})
    assert proxy.slots_free() == 2


def test_stats_seq_orders_equal_token_snapshots(workers):
    """The token watermark cannot order two snapshots taken without a
    decode step between them (before/after a SUBMIT both carry the
    same ``generated_tokens``), so workers stamp a per-send ``seq``:
    a reorder of equal-token STATS must keep the NEWER snapshot and a
    duplicate must not re-apply — through the real parsing path."""
    w = workers(slots=4, tokens_per_step=2)
    proxy = w.proxy("seq")
    # the LIVE stream already proves workers stamp seq: wait for one
    deadline = time.monotonic() + 5.0
    while proxy._stats_seq_seen == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert proxy._stats_seq_seen > 0, "workers must stamp STATS seq"
    # quiesce the worker so synthetic frames can't race real ones
    w.stop()
    base = proxy._stats_seq_seen
    drops = proxy.stale_stats_dropped
    # worker sends A (4 slots, base+1), accepts a SUBMIT, sends B
    # (3 slots, base+2) — same generated_tokens; recv reorders B, A
    proxy._dispatch({"kind": "STATS", "slots_free": 3,
                     "blocks_free": 8.0, "generated_tokens": 100,
                     "seq": base + 2})
    assert proxy._slots_free == 3
    proxy._dispatch({"kind": "STATS", "slots_free": 4,
                     "blocks_free": 9.0, "generated_tokens": 100,
                     "seq": base + 1})
    assert proxy._slots_free == 3, \
        "an equal-token reorder must not resurrect the consumed slot"
    assert proxy.stale_stats_dropped == drops + 1
    # a duplicated delivery of the applied snapshot is also stale
    proxy._dispatch({"kind": "STATS", "slots_free": 3,
                     "blocks_free": 8.0, "generated_tokens": 100,
                     "seq": base + 2})
    assert proxy.stale_stats_dropped == drops + 2
    # and a genuinely newer snapshot still lands
    proxy._dispatch({"kind": "STATS", "slots_free": 1,
                     "blocks_free": 4.0, "generated_tokens": 102,
                     "seq": base + 3})
    assert proxy._slots_free == 1
    # seq-less sender (fallback): token watermark still guards
    proxy._dispatch({"kind": "STATS", "slots_free": 9,
                     "blocks_free": 99.0, "generated_tokens": 5})
    assert proxy._slots_free == 1
    assert proxy.stale_stats_dropped == drops + 3


# -- control-plane fault tolerance (ISSUE 8) ---------------------------------


def _manual_clock():
    state = {"t": 0.0}
    sleeps = []

    def sleep(s):
        sleeps.append(s)
        state["t"] += s

    return state, sleeps, sleep


def test_retry_policy_deterministic_backoff_and_deadline():
    from dlrover_tpu.common.retry import RetryPolicy

    state, sleeps, sleep = _manual_clock()
    pol = RetryPolicy(
        max_attempts=10, backoff_base=0.5, backoff_multiplier=2.0,
        backoff_max=8.0, deadline=10.0, jitter=0.25, seed=42,
        sleep=sleep, clock=lambda: state["t"])
    calls = {"n": 0}

    def always_down():
        calls["n"] += 1
        raise ConnectionError("master down")

    with pytest.raises(ConnectionError):
        pol.call(always_down, what="probe")
    # the total DEADLINE bites before the attempt budget: every sleep
    # fit inside the budget, and the refused next delay would not have
    assert sum(sleeps) <= 10.0
    assert calls["n"] < 10, \
        "the deadline must stop retrying before the attempt budget"
    # exponential: each jittered delay sits in [base*2^n, base*2^n*1.25]
    for i, s in enumerate(sleeps):
        lo = min(8.0, 0.5 * (2 ** i))
        assert lo <= s <= lo * 1.25, (i, s)
    # deterministic under the seed: an identical policy replays the
    # exact schedule
    state2, sleeps2, sleep2 = _manual_clock()
    pol2 = RetryPolicy(
        max_attempts=10, backoff_base=0.5, backoff_multiplier=2.0,
        backoff_max=8.0, deadline=10.0, jitter=0.25, seed=42,
        sleep=sleep2, clock=lambda: state2["t"])
    with pytest.raises(ConnectionError):
        pol2.call(always_down, what="probe")
    assert sleeps2 == sleeps


def test_retry_policy_does_not_retry_non_transient():
    import grpc

    from dlrover_tpu.common.retry import (
        RetryPolicy,
        is_transient,
        retries_total,
    )

    # classification: transport errors are transient, served errors not
    class _Rpc(grpc.RpcError):
        def __init__(self, code):
            self._code = code

        def code(self):
            return self._code

    assert is_transient(_Rpc(grpc.StatusCode.UNAVAILABLE))
    assert is_transient(_Rpc(grpc.StatusCode.DEADLINE_EXCEEDED))
    assert not is_transient(_Rpc(grpc.StatusCode.INVALID_ARGUMENT))
    assert is_transient(ConnectionError("x"))
    assert is_transient(TimeoutError("x"))
    assert not is_transient(RuntimeError("master get failed"))
    assert not is_transient(ValueError("bad request"))

    pol = RetryPolicy(max_attempts=5, backoff_base=0.001, jitter=0.0,
                      deadline=5.0, sleep=lambda s: None)
    calls = {"n": 0}

    def served_refusal():
        calls["n"] += 1
        raise RuntimeError("master get failed")

    before = retries_total()
    with pytest.raises(RuntimeError):
        pol.call(served_refusal, what="refused")
    assert calls["n"] == 1, "a served refusal is an ANSWER, not a blip"
    assert retries_total() == before, \
        "non-transient failures are not retries"


def test_retry_counter_counts_retries_not_failures():
    """`serving_rpc_retries_total` sells itself as the control-plane
    flakiness signal: the final failure that GIVES UP is not followed
    by a retry, so it must not count — an exhausted call of N failures
    burned N-1 retries, and a success after one blip counts exactly 1."""
    from dlrover_tpu.common.retry import RetryPolicy, retries_total

    pol = RetryPolicy(max_attempts=4, backoff_base=0.001, jitter=0.0,
                      deadline=60.0, sleep=lambda s: None)
    calls = {"n": 0}

    def always_down():
        calls["n"] += 1
        raise ConnectionError("down")

    before = retries_total()
    with pytest.raises(ConnectionError):
        pol.call(always_down, what="probe")
    assert calls["n"] == 4
    assert retries_total() - before == 3, \
        "4 failures -> 3 retries (the give-up is not a retry)"

    def flaky_once(state={"n": 0}):
        state["n"] += 1
        if state["n"] == 1:
            raise ConnectionError("blip")
        return "ok"

    before = retries_total()
    assert pol.call(flaky_once, what="blip") == "ok"
    assert retries_total() - before == 1


def test_retry_policy_logs_once_per_state_change():
    import logging

    from dlrover_tpu.common.log import default_logger
    from dlrover_tpu.common.retry import RetryPolicy

    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record)

    pol = RetryPolicy(max_attempts=8, backoff_base=0.001, jitter=0.0,
                      deadline=5.0, sleep=lambda s: None)
    state = {"n": 0}

    def flaky():
        state["n"] += 1
        if state["n"] < 5:
            raise ConnectionError(f"blip {state['n']}")
        return "ok"

    handler = _Capture(level=logging.DEBUG)
    old_level = default_logger.level
    default_logger.addHandler(handler)
    default_logger.setLevel(logging.DEBUG)
    try:
        assert pol.call(flaky, what="flaky_rpc") == "ok"
    finally:
        default_logger.removeHandler(handler)
        default_logger.setLevel(old_level)
    warnings = [r for r in records
                if r.levelno == logging.WARNING
                and "flaky_rpc" in r.getMessage()]
    assert len(warnings) == 1, \
        "one warning per OUTAGE (4 failures used to mean 4 warnings)"
    recoveries = [r for r in records
                  if r.levelno == logging.INFO
                  and "recovered" in r.getMessage()]
    assert len(recoveries) == 1
    debugs = [r for r in records if r.levelno == logging.DEBUG
              and "still failing" in r.getMessage()]
    assert len(debugs) == 3, "retries 2..4 log at debug only"


def test_retry_rpc_decorator_typed_and_budgeted():
    from dlrover_tpu.agent.master_client import retry_rpc
    from dlrover_tpu.common.retry import RetryPolicy

    pol = RetryPolicy(max_attempts=5, backoff_base=0.001, jitter=0.0,
                      deadline=2.0, sleep=lambda s: None)

    class Client:
        def __init__(self):
            self.calls = 0
            self.hard = False

        @retry_rpc(policy=pol)
        def ping(self):
            self.calls += 1
            if self.hard:
                raise RuntimeError("served refusal")
            if self.calls <= 2:
                raise ConnectionError("down")
            return "pong"

    c = Client()
    assert c.ping() == "pong"
    assert c.calls == 3, "transient failures retried to success"
    hard = Client()
    hard.hard = True
    with pytest.raises(RuntimeError):
        hard.ping()
    assert hard.calls == 1, "non-transient errors must NOT retry"
    assert Client.ping.retry_policy is pol  # introspection seam
    # the default decorator derives its budget from the legacy knobs
    from dlrover_tpu.agent.master_client import MasterClient

    default_pol = MasterClient.get_task.retry_policy
    assert default_pol.deadline == pytest.approx(30.0)
    assert default_pol.max_attempts == 10


def test_faulty_rpc_stub_fault_mapping_and_ledger():
    from dlrover_tpu.common.retry import RetryPolicy, is_transient
    from dlrover_tpu.serving.remote.faults import FaultyRpcStub

    class _Transport:
        def __init__(self):
            self.calls = []
            self.closed = False

        def get(self, payload, timeout=0):
            self.calls.append(("get", payload))
            return b"g"

        def report(self, payload, timeout=0):
            self.calls.append(("report", payload))
            return b"r"

        def close(self):
            self.closed = True

    sched = FaultSchedule([
        {"op": "delay", "kind": "get", "after": 1, "seconds": 0.0},
        {"op": "drop", "kind": "get", "after": 2},
        {"op": "error", "kind": "report", "after": 1},
        {"op": "stall", "kind": "report", "after": 2, "seconds": 60.0},
    ], seed=3)
    inner = _Transport()
    stub = FaultyRpcStub(inner, sched)
    assert stub.get(b"1") == b"g"           # delayed but delivered
    with pytest.raises(ConnectionError) as drop_exc:
        stub.get(b"2")                      # dropped: never reached
    assert is_transient(drop_exc.value), \
        "a dropped RPC must look transient (retry is correct)"
    assert stub.get(b"3") == b"g"
    with pytest.raises(RuntimeError) as err_exc:
        stub.report(b"a")                   # served an error
    assert not is_transient(err_exc.value), \
        "an errored RPC must look non-transient (no retry)"
    with pytest.raises(TimeoutError):
        stub.report(b"b")                   # stall window opens
    with pytest.raises(TimeoutError):
        stub.report(b"c")                   # ...and persists
    ops = [(e["op"], e["kind"]) for e in sched.injected]
    for expected in [("delay", "get"), ("drop", "get"),
                     ("error", "report"), ("stall", "report")]:
        assert expected in ops, ops
    # inert schedules cannot masquerade: the firings ARE the ledger
    assert len(sched.injected) >= 5
    stub.close()
    assert inner.closed and stub.closed

    # the retry policy rides out the transient window end-to-end
    sched2 = FaultSchedule(
        [{"op": "drop", "kind": "get", "after": 1, "count": 2}], seed=0)
    stub2 = FaultyRpcStub(_Transport(), sched2)
    pol = RetryPolicy(max_attempts=5, backoff_base=0.0, jitter=0.0,
                      deadline=10.0, sleep=lambda s: None)
    assert pol.call(stub2.get, b"x", what="get") == b"g"
    assert len(sched2.fired("drop")) == 2


# -- the fast acceptance -----------------------------------------------------


@pytest.mark.parametrize("step_engine", ["event", "sweep"])
def test_chaos_acceptance_fast_matrix(workers, step_engine):
    """In-thread acceptance: a 200-request stream over 4 workers while
    a seeded fault schedule tears one connection, stalls another
    worker's frames, and a third dies abruptly — plus a handful of
    client cancels — completes with zero lost requests and reclaimed
    slots everywhere.  Parameterized over BOTH step-engine candidates
    (ISSUE 15): the zero-lost/books discipline must hold identically
    under the event-driven loop and the historical sweep."""
    tear = FaultSchedule(
        [{"op": "tear", "kind": "TOKEN", "after": 60}], seed=11)
    stall = FaultSchedule(
        [{"op": "stall", "kind": "*", "after": 90, "seconds": 120.0}],
        seed=12)
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4),
        cancel_inflight_on_expiry=True,
        step_engine=step_engine,
    )
    fleet = {
        "torn": workers(fault_schedule=tear, slots=4,
                        tokens_per_step=2, step_delay=0.002),
        "stalled": workers(fault_schedule=stall, slots=4,
                           tokens_per_step=2, step_delay=0.002),
        "doomed": workers(slots=4, tokens_per_step=2,
                          step_delay=0.002),
        "healthy": workers(slots=4, tokens_per_step=2,
                           step_delay=0.002),
    }
    for name, w in fleet.items():
        router.join_replica(
            name, w.proxy(name, frame_timeout=1.0))
    reqs = [router.submit(_prompt(i), 8) for i in range(200)]

    state = {"killed": False, "cancelled": []}

    def chaos():
        if not state["killed"]:
            doomed = router.manager.get("doomed")
            if doomed is not None and doomed.inflight:
                fleet["doomed"].stop()   # abrupt death, mid-stream
                state["killed"] = True
        if not state["cancelled"] and state["killed"]:
            for r in reqs:
                if len(state["cancelled"]) >= 5:
                    break
                if r.state in (ServingRequestState.QUEUED,
                               ServingRequestState.RUNNING):
                    if r.cancel():
                        state["cancelled"].append(r)

    _drive(router, timeout=60.0, extra=chaos)
    assert state["killed"], "the abrupt death must have happened"
    assert tear.fired("tear"), "the torn connection must have fired"
    assert stall.fired("stall"), "the stall must have fired"
    assert len(state["cancelled"]) == 5

    # ZERO lost requests: every request reached a terminal, accounted
    # state — cancelled ones answered their caller, the rest completed
    terminal = {ServingRequestState.DONE, ServingRequestState.CANCELLED}
    for r in reqs:
        assert r.state in terminal, (r.rid, r.state)
    m = router.metrics.metrics()
    done = sum(1 for r in reqs if r.state == ServingRequestState.DONE)
    cancelled = 200 - done
    assert m["serving_requests_completed_total"] == done
    assert m["serving_requests_cancelled_total"] == cancelled
    assert 0 < cancelled <= 5
    assert m["serving_requests_requeued_total"] >= 1, \
        "the deaths must have exercised failover"
    assert m["serving_requests_poisoned_total"] == 0
    # the fleet degraded to exactly the healthy worker
    assert router.replica_names == ["healthy"]
    # slot reclamation: the surviving engine holds NOTHING (cancelled
    # requests' slots included), asserted at the engine and via the
    # proxy's STATS-fed ledger
    deadline = time.monotonic() + 5.0
    handle = router.manager.get("healthy")
    while (fleet["healthy"].engine.active
           or handle.slots_free() < 4) \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not fleet["healthy"].engine.active
    assert fleet["healthy"].engine.used_blocks == 0
    assert handle.slots_free() == 4
    # cancelled in-flight requests closed their trace with the
    # cancelled status and a flight-recorder cancel event exists
    for r in state["cancelled"]:
        tree = router.tracer.get_tree(r.trace.trace_id)
        assert tree is not None
        assert tree["status"] == ServingRequestState.CANCELLED


def test_chaos_sampled_tracing_keeps_every_incident(workers):
    """ISSUE 6 acceptance: a chaos matrix at ``sample_rate=0.01``
    drops (almost) every healthy trace but still yields a COMPLETE
    span tree for every failed-over, expired and cancelled request —
    the incident override working under real failover machinery, with
    the ``sampled/dropped`` counter pair proving the knob bites."""

    def names_in(tree):
        out = []

        def walk(spans):
            for s in spans:
                out.append(s["name"])
                walk(s["children"])

        walk(tree["spans"])
        return out

    tear = FaultSchedule(
        [{"op": "tear", "kind": "TOKEN", "after": 40}], seed=31)
    torn = workers(fault_schedule=tear, slots=4, tokens_per_step=2,
                   step_delay=0.002)
    ok = workers(slots=4, tokens_per_step=2, step_delay=0.002)
    router = ServingRouter(
        gateway=RequestGateway(
            max_pending=256, trace_sample_rate=0.01),
        scheduler=ContinuousBatchScheduler(block_size=4),
    )
    router.join_replica("torn", torn.proxy("torn", frame_timeout=1.0))
    router.join_replica("ok", ok.proxy("ok", frame_timeout=1.0))
    reqs = [router.submit(_prompt(i), 8) for i in range(120)]
    expired = router.submit(_prompt(7), 8, timeout=0.0)
    cancelled = []
    for r in reqs:
        if len(cancelled) >= 3:
            break
        if r.state == ServingRequestState.QUEUED and r.cancel():
            cancelled.append(r)
    _drive(router, timeout=60.0)
    assert tear.fired("tear"), "the torn connection must have fired"

    # zero lost, and the fault actually exercised failover
    terminal = {ServingRequestState.DONE, ServingRequestState.CANCELLED}
    assert all(r.state in terminal for r in reqs)
    assert expired.state == ServingRequestState.TIMED_OUT
    requeued = [r for r in reqs if r.requeues > 0
                and r.state == ServingRequestState.DONE]
    assert requeued, "the tear must have failed requests over"

    tracer = router.tracer
    # every FAILED-OVER request kept its full tree: both attempts, and
    # the retry's worker-side spans (incident marking resumed
    # traceparent propagation despite the 1% rate)
    for r in requeued:
        tree = tracer.get_tree(r.trace.trace_id)
        assert tree is not None and tree["status"] == "ok"
        names = names_in(tree)
        assert names.count("attempt") >= 2, names
        assert "worker.request" in names, names
    # every cancelled/expired request kept its tree via its non-ok
    # terminal status
    for r, status in [(c, ServingRequestState.CANCELLED)
                      for c in cancelled] \
            + [(expired, ServingRequestState.TIMED_OUT)]:
        tree = tracer.get_tree(r.trace.trace_id)
        assert tree is not None and tree["status"] == status
        assert "queued" in names_in(tree)
    # the knob's proof pair: almost all healthy traces dropped, the
    # books balance (121 finished traces total), and both counters
    # surface as registered metrics
    m = tracer.metrics()
    assert m["serving_trace_dropped_total"] >= 80
    assert m["serving_trace_sampled_total"] \
        + m["serving_trace_dropped_total"] == len(reqs) + 1
    assert m["serving_trace_sampled_total"] >= len(requeued) + 4


def test_cancellation_and_fault_paths_lock_clean():
    """The DL003 acceptance line, executed: cancel frame sends and
    fault injection must add no blocking work under fabric locks."""
    from dlrover_tpu.dlint.checkers import CHECKERS, DlintConfig, Project
    from dlrover_tpu.dlint.core import ParsedModule

    paths = [
        "dlrover_tpu/serving/router/gateway.py",
        "dlrover_tpu/serving/router/router.py",
        "dlrover_tpu/serving/router/replica.py",
        "dlrover_tpu/serving/remote/proxy.py",
        "dlrover_tpu/serving/remote/worker.py",
        "dlrover_tpu/serving/remote/supervisor.py",
        "dlrover_tpu/serving/remote/faults.py",
    ]
    modules = []
    for p in paths:
        with open(p, encoding="utf-8") as f:
            modules.append(ParsedModule(p, p, f.read()))
    project = Project(modules, DlintConfig())
    by_path = {m.rel_path: m for m in modules}
    dl003 = [c for c in CHECKERS if c.CODE == "DL003"][0]
    violations = [
        v for v in dl003.check_project(project)
        if not by_path[v.path].suppressed(v.code, v.line)
    ]
    assert violations == [], [str(v) for v in violations]


# -- the self-healing acceptance (ISSUE 8) -----------------------------------


def test_self_healing_acceptance_fast():
    """THE ISSUE-8 acceptance, in-thread on a synthetic clock: 2 of 6
    workers crash-loop into quarantine while seeded RPC faults hit the
    Brain link and a demand spike hits the gateway.  Replacement
    replicas are provisioned within ONE autoscale poll of each
    quarantine (no waiting out the sentence), capacity debt retires
    exactly once per quarantine, the brown-out sheds BATCH before
    NORMAL and never HIGH (zero HIGH requests lost or poisoned), and
    the books balance."""
    from dlrover_tpu.brain.serving import ServingScalePolicy
    from dlrover_tpu.common.constants import NodeType
    from dlrover_tpu.common.node import Node
    from dlrover_tpu.scheduler.in_memory import (
        InMemoryCluster,
        InMemoryNodeWatcher,
        InMemoryScaler,
    )
    from dlrover_tpu.serving.remote.faults import FaultyRpcStub
    from dlrover_tpu.serving.router import (
        PRIORITY_BATCH,
        PRIORITY_HIGH,
        PRIORITY_NORMAL,
        BrownoutPolicy,
        BrownoutShedError,
        ReplicaProvisioner,
        RouterMetrics,
        ServingAutoScaler,
    )

    bo = BrownoutPolicy(enter_pressure=2.0, exit_pressure=0.5,
                        dwell_seconds=0.5)
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4),
        metrics=RouterMetrics(window_seconds=0.5),
        brownout=bo,
    )
    cluster = InMemoryCluster()
    scaler = InMemoryScaler(cluster)
    provisioner = ReplicaProvisioner(
        router, InMemoryNodeWatcher(cluster),
        engine_factory=lambda node: FakeEngine(
            slots=2, tokens_per_step=2))

    # seeded control-plane faults on the Brain link: two dropped
    # serving_plan queries, a stalled one, errored telemetry reports —
    # the autoscale loop must ride them out on the local policy
    rpc_sched = FaultSchedule([
        {"op": "drop", "kind": "get", "after": 1, "count": 2},
        {"op": "stall", "kind": "get", "after": 5, "seconds": 0.2},
        {"op": "error", "kind": "report", "after": 1, "count": 3},
    ], seed=9)

    class _Transport:
        closed = False

        def get(self, payload, timeout=0):
            return b"ok"

        def report(self, payload, timeout=0):
            return b"ok"

        def close(self):
            pass

    faulty_stub = FaultyRpcStub(_Transport(), rpc_sched)

    class _Brain:
        def serving_plan(self, **query):
            faulty_stub.get(b"serving_plan")
            return None  # defer to the local policy

        def record_serving(self, **report):
            faulty_stub.report(b"record_serving")

    sup = _StubSupervisor(
        router=router, respawn=True, max_respawns=2,
        respawn_window=300.0, backoff_base=0.2, backoff_max=2.0,
        backoff_jitter=0.25, quarantine_seconds=120.0, seed=13,
        recorder=router.recorder)
    auto = ServingAutoScaler(
        router, scaler,
        policy=ServingScalePolicy(min_replicas=1, max_replicas=8,
                                  queue_high=2.0, queue_low=0.0),
        brain=_Brain(), supervisor=sup,
        decide_interval=0.0, cooldown=0.5, min_samples=1)

    # a 6-replica fleet through the cluster, 2 of them backed by
    # supervised worker processes that are about to crash-loop
    for i in range(6):
        cluster.create_node(
            Node(NodeType.SERVING_REPLICA, i, rank_index=i))
    provisioner.poll()
    assert router.manager.up_count() == 6
    loopers = ("serving-replica-4", "serving-replica-5")
    for name in loopers:
        sup.spawn(name=name)

    t = time.monotonic()
    # the demand spike: long requests so the overload outlives the
    # quarantine episode and the brown-out ladder has time to climb
    high = [router.submit(_prompt(i), 32, priority=PRIORITY_HIGH,
                          now=t) for i in range(20)]
    normal = [router.submit(_prompt(i), 32, priority=PRIORITY_NORMAL,
                            now=t) for i in range(60)]
    batch = [router.submit(_prompt(i), 32, priority=PRIORITY_BATCH,
                           now=t) for i in range(80)]
    admitted = high + normal + batch
    # one placement round so the doomed replicas hold REAL in-flight
    # work, then they die mid-spike: failover requeues it while the
    # supervisor meters their crash loop
    router.step(now=t)
    assert all(router.manager.get(n).inflight for n in loopers)
    for name in loopers:
        router.fail_replica(name)

    shed_probe = {"batch": None, "normal": None, "high_after": None}
    max_stage = 0
    for _ in range(500):
        t += 0.05
        _crash_current(sup)       # every live looper crashes again
        sup.poll(now=t)
        router.step(now=t)
        provisioner.poll(timeout=0.001)
        max_stage = max(max_stage, bo.stage)
        if bo.stage >= 1 and shed_probe["batch"] is None:
            try:
                router.submit(_prompt(200), 4,
                              priority=PRIORITY_BATCH, now=t)
                shed_probe["batch"] = False
            except BrownoutShedError:
                shed_probe["batch"] = True
        if bo.stage >= 3 and shed_probe["normal"] is None:
            try:
                router.submit(_prompt(201), 4,
                              priority=PRIORITY_NORMAL, now=t)
                shed_probe["normal"] = False
            except BrownoutShedError:
                shed_probe["normal"] = True
            # HIGH admits at the DEEPEST brown-out stage
            probe_high = router.submit(
                _prompt(202), 4, priority=PRIORITY_HIGH, now=t)
            admitted.append(probe_high)
            high.append(probe_high)
            shed_probe["high_after"] = True
        if (len(sup.quarantined) == 2
                and auto.capacity_debt_retired >= 2
                and not router.has_work and bo.stage == 0):
            break

    # the chaos all actually happened
    assert len(sup.quarantined) == 2, \
        "both crash-loopers must end in quarantine"
    assert rpc_sched.fired("drop") and rpc_sched.fired("error"), \
        "the RPC faults must actually have fired"
    assert max_stage == 3, "the brown-out ladder must reach stage 3"
    assert bo.stage == 0, "recovery must walk the ladder back down"
    assert not router.has_work

    # replacement within ONE autoscale poll: each quarantine's debt
    # opens at the SAME recorder timestamp the quarantine fired
    events = router.recorder.events(1024)
    quarantines = {e["worker"]: e for e in events
                   if e["kind"] == "worker_quarantined"}
    debts_opened = {e["key"]: e for e in events
                    if e["kind"] == "capacity_debt_opened"}
    assert len(quarantines) == 2 and len(debts_opened) == 2
    for worker, q in quarantines.items():
        key = f"quarantine:{base_replica_name(worker)}"
        assert key in debts_opened, (key, list(debts_opened))
        assert debts_opened[key]["t"] == q["t"], \
            "the replacement plan must be issued the same poll"

    # capacity debt retired EXACTLY once per quarantine, by the
    # replacement joining (the sentence is 120s — never waited out)
    retired = [e for e in events if e["kind"] == "capacity_debt_retired"]
    assert len(retired) == 2
    assert auto.capacity_debt_retired == 2
    assert all(e["reason"] == "replacement_joined" for e in retired)
    assert router.metrics.metrics()["serving_capacity_debt"] == 0.0
    # ...and the replacements took real traffic
    for e in retired:
        handle = router.manager.get(e["replacement"])
        assert handle is not None, e["replacement"]
        assert handle.ever_placed, \
            f"replacement {e['replacement']} never served"

    # ISSUE 12: the replacements' origins are registered, and every
    # attempt that landed on a replacement links to the replacement's
    # always-sampled autoscale trace — "why was this request slow"
    # resolves to "because it rode the replica THIS decision created"
    origins = router.replica_origins
    for e in retired:
        assert base_replica_name(e["replacement"]) in origins, origins

    def _spans(tree):
        out = []

        def walk(spans):
            for s in spans:
                out.append(s)
                walk(s["children"])

        walk(tree["spans"])
        return out

    linked = 0
    for tree in router.tracer.finished(limit=512, name="request"):
        for span in _spans(tree):
            if span["name"] != "attempt":
                continue
            base = base_replica_name(
                str(span["attrs"].get("replica", "")))
            if base not in origins:
                continue
            links = span.get("links") or []
            assert links, (tree["trace_id"], span)
            assert links[0]["trace_id"] == \
                origins[base]["trace_id"]
            target = router.tracer.get_tree(links[0]["trace_id"])
            assert target is not None \
                and target["name"] == "autoscale"
            linked += 1
    assert linked > 0, "replacements served but no attempt linked"

    # shed ORDER: BATCH refused first, NORMAL only at stage 3, HIGH
    # admitted at every stage and NEVER lost or poisoned
    assert shed_probe["batch"] is True
    assert shed_probe["normal"] is True
    assert shed_probe["high_after"] is True
    gw = router.gateway
    assert gw.shed_by_priority[PRIORITY_HIGH] == 0
    assert gw.shed_by_priority[PRIORITY_BATCH] >= 1
    assert gw.shed_by_priority[PRIORITY_NORMAL] >= 1
    for r in high:
        assert r.state == ServingRequestState.DONE, (r.rid, r.state)
    # the first stage-2 sweep cancelled BATCH before touching NORMAL:
    # every brown-out cancellation is a BATCH request
    shed_events = [e for e in events
                   if e["kind"] == "brownout_shed_queued"]
    assert shed_events
    assert {e["priority"] for e in shed_events} == {PRIORITY_BATCH}

    # books balance: every admitted request is DONE or CANCELLED (no
    # deadlines armed -> no expiry), nothing poisoned, counters agree
    done = sum(1 for r in admitted
               if r.state == ServingRequestState.DONE)
    cancelled = sum(1 for r in admitted
                    if r.state == ServingRequestState.CANCELLED)
    assert done + cancelled == len(admitted), [
        (r.rid, r.state) for r in admitted
        if r.state not in (ServingRequestState.DONE,
                           ServingRequestState.CANCELLED)]
    m = router.metrics.metrics()
    assert m["serving_requests_completed_total"] == done
    assert m["serving_requests_cancelled_total"] == cancelled
    assert m["serving_requests_poisoned_total"] == 0
    assert m["serving_requests_timed_out_total"] == 0
    assert gw.submitted == done + cancelled
    assert m["serving_worker_quarantined_total"] == 2.0
    assert m["serving_requests_requeued_total"] >= 1, \
        "the replica deaths must have exercised failover"


def test_failover_span_links_resolve_to_replacement_trace():
    """ISSUE 12 acceptance: a replica dies with requests in flight,
    its capacity debt launches a replacement, and every failed-over
    request that lands on the replacement carries a span link
    resolving to the always-sampled autoscale trace that created it —
    visible in the /traces JSON tree and as flow events in the Chrome
    export."""
    from dlrover_tpu.brain.serving import ServingScalePolicy
    from dlrover_tpu.common.constants import NodeType
    from dlrover_tpu.common.node import Node
    from dlrover_tpu.scheduler.in_memory import (
        InMemoryCluster,
        InMemoryNodeWatcher,
        InMemoryScaler,
    )
    from dlrover_tpu.serving.router import (
        ReplicaProvisioner,
        RouterMetrics,
        ServingAutoScaler,
    )

    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4),
        metrics=RouterMetrics(window_seconds=0.5),
    )
    cluster = InMemoryCluster()
    scaler = InMemoryScaler(cluster)
    provisioner = ReplicaProvisioner(
        router, InMemoryNodeWatcher(cluster),
        engine_factory=lambda node: FakeEngine(
            slots=2, tokens_per_step=1, blocks=100000))
    sup = _StubSupervisor(
        router=router, respawn=True, max_respawns=1,
        respawn_window=300.0, backoff_base=0.2, backoff_max=1.0,
        backoff_jitter=0.25, quarantine_seconds=120.0, seed=5,
        recorder=router.recorder)
    # debt replacement only: huge decide/cooldown keep the load
    # policy out of the picture — the ORIGIN must be the replacement
    # trace, not a coincidental scale-up
    ServingAutoScaler(
        router, scaler,
        policy=ServingScalePolicy(min_replicas=1, max_replicas=8,
                                  queue_high=1e9, queue_low=0.0),
        supervisor=sup,
        decide_interval=1e9, cooldown=1e9, min_samples=1000)

    t = time.monotonic()
    # replica 0 joins first and fills up with LONG work, so the
    # failed-over requests can only land on the replacement later
    cluster.create_node(Node(NodeType.SERVING_REPLICA, 0,
                             rank_index=0))
    provisioner.poll()
    long_reqs = [router.submit(_prompt(i), 256, now=t)
                 for i in range(2)]
    router.step(now=t)
    assert all(r.replica == "serving-replica-0" for r in long_reqs)
    # replica 1 joins (supervised: it is about to crash-loop) and
    # takes the short requests that will be failed over
    cluster.create_node(Node(NodeType.SERVING_REPLICA, 1,
                             rank_index=1))
    provisioner.poll()
    sup.spawn(name="serving-replica-1")
    doomed = [router.submit(_prompt(10 + i), 8, now=t)
              for i in range(2)]
    router.step(now=t)
    assert all(r.replica == "serving-replica-1" for r in doomed)

    router.fail_replica("serving-replica-1")
    for _ in range(200):
        t += 0.1
        _crash_current(sup)
        sup.poll(now=t)
        router.step(now=t)
        provisioner.poll(timeout=0.001)
        if all(r.state == ServingRequestState.DONE for r in doomed):
            break
    assert all(r.state == ServingRequestState.DONE for r in doomed)
    assert all(r.requeues > 0 for r in doomed), \
        "the replica death must have failed the requests over"
    assert all(
        r.replica and r.replica.startswith(
            "serving-replica-replacement")
        for r in doomed), [r.replica for r in doomed]

    def spans_of(tree):
        out = []

        def walk(spans):
            for s in spans:
                out.append(s)
                walk(s["children"])

        walk(tree["spans"])
        return out

    tracer = router.tracer
    link_targets = set()
    for r in doomed:
        tree = tracer.get_tree(r.trace.trace_id)
        assert tree is not None
        attempts = [s for s in spans_of(tree) if s["name"] == "attempt"]
        # the dead attempt is closed as failover and kept in the tree
        assert any(a["status"] == "failover" for a in attempts)
        landed = [a for a in attempts
                  if str(a["attrs"].get("replica", "")).startswith(
                      "serving-replica-replacement")]
        assert landed, attempts
        links = landed[-1].get("links") or []
        assert links, "the attempt must link to its replica's origin"
        link = links[0]
        assert link["attrs"]["rel"] == "replica_origin"
        assert link["attrs"]["kind"] == "replacement"
        # the quarantined source may be a respawn (#rN suffix) — the
        # base name is the stable identity
        assert base_replica_name(
            link["attrs"]["replacement_for"]) == "serving-replica-1"
        # the link RESOLVES: its target is the always-sampled
        # replacement autoscale trace held by the same tracer
        target = tracer.get_tree(link["trace_id"])
        assert target is not None and target["name"] == "autoscale"
        assert base_replica_name(
            target["spans"][0]["attrs"]["replacement_for"]) == \
            "serving-replica-1"
        link_targets.add(link["trace_id"])

    # the Chrome export renders every link as a flow-event pair
    # (ph "s" at the decision, ph "f" at the attempt, same id)
    chrome = json.loads(tracer.export_chrome_trace())
    flows = [e for e in chrome["traceEvents"]
             if e.get("name") == "span_link"]
    starts = {e["id"] for e in flows if e["ph"] == "s"}
    finishes = {e["id"] for e in flows if e["ph"] == "f"}
    assert starts and starts == finishes
    assert any(e["args"].get("kind") == "replacement" for e in flows)


# -- subprocess acceptance (slow) --------------------------------------------


def _can_spawn() -> bool:
    try:
        subprocess.run(
            [sys.executable, "-c", "pass"], timeout=30, check=True,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        return True
    except Exception:
        return False


needs_spawn = pytest.mark.skipif(
    not _can_spawn(), reason="cannot spawn subprocesses here")


@pytest.mark.slow
@needs_spawn
@pytest.mark.parametrize("step_engine", ["event", "sweep"])
def test_chaos_acceptance_full_matrix_subprocess(step_engine):
    """THE acceptance: real worker processes under a seeded fault
    schedule — one torn connection, one heartbeat stall, one SIGKILL,
    one crash-looping worker — serve a 200-request stream with zero
    lost requests; cancelled requests reclaim their slots; the crash
    looper's respawn gaps strictly increase and end in quarantine.
    Parameterized over both step engines (ISSUE 15): the SIGKILL
    matrix must balance its books identically under each."""
    import signal as signal_mod

    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4),
        cancel_inflight_on_expiry=True,
        step_engine=step_engine,
    )
    base_args = ["--slots", "4", "--tokens-per-step", "2",
                 "--step-delay", "0.005"]

    def faulted_env(faults, seed):
        env = dict(os.environ)
        env[ServingFabric.FAULTS_ENV] = json.dumps(
            {"seed": seed, "faults": faults})
        return env

    sups = []
    try:
        healthy = WorkerSupervisor(
            router=router, engine="fake", worker_args=base_args,
            name_prefix="healthy", seed=1)
        sups.append(healthy)
        for _ in range(2):
            healthy.spawn()
        victim_sup = WorkerSupervisor(
            router=router, engine="fake", worker_args=base_args,
            name_prefix="victim", backoff_base=0.2, seed=2)
        sups.append(victim_sup)
        victim_sup.spawn()

        # torn + stalled workers: armed through the env seam
        os.environ[ServingFabric.FAULTS_ENV] = json.dumps(
            {"seed": 3, "faults": [
                {"op": "tear", "kind": "TOKEN", "after": 60}]})
        try:
            torn_sup = WorkerSupervisor(
                router=router, engine="fake", worker_args=base_args,
                name_prefix="torn", respawn=False)
            sups.append(torn_sup)
            torn_sup.spawn()
            os.environ[ServingFabric.FAULTS_ENV] = json.dumps(
                {"seed": 4, "faults": [
                    {"op": "stall", "kind": "*", "after": 90,
                     "seconds": 120.0}]})
            stalled_sup = WorkerSupervisor(
                router=router, engine="fake", worker_args=base_args,
                name_prefix="stalled", respawn=False)
            sups.append(stalled_sup)
            stalled_sup.spawn()
        finally:
            os.environ.pop(ServingFabric.FAULTS_ENV, None)

        # the crash looper: dies 0.3s after every start, forever
        crash_sup = WorkerSupervisor(
            router=router, engine="fake",
            worker_args=base_args + ["--crash-after", "0.3"],
            name_prefix="crashloop", max_respawns=3,
            respawn_window=300.0, backoff_base=1.0, backoff_max=30.0,
            backoff_jitter=0.25, quarantine_seconds=600.0, seed=5)
        sups.append(crash_sup)
        crash_sup.spawn()

        assert len(router.replica_names) == 6
        reqs = [router.submit(_prompt(i), 8) for i in range(200)]

        state = {"killed": False, "cancelled": []}

        def chaos():
            for sup in sups:
                sup.poll()
            if not state["killed"]:
                victims = [n for n in router.replica_names
                           if n.startswith("victim")]
                if victims:
                    v = router.manager.get(victims[0])
                    if v is not None and v.inflight:
                        victim_sup.kill(
                            victims[0], signal_mod.SIGKILL)
                        state["killed"] = True
            if state["killed"] and not state["cancelled"]:
                for r in reqs:
                    if len(state["cancelled"]) >= 5:
                        break
                    if r.state in (ServingRequestState.QUEUED,
                                   ServingRequestState.RUNNING):
                        if r.cancel():
                            state["cancelled"].append(r)

        deadline = time.monotonic() + 120.0
        while (router.has_work or not crash_sup.quarantined) \
                and time.monotonic() < deadline:
            router.step()
            chaos()
            time.sleep(0.002)
        assert state["killed"], "the SIGKILL must have landed"

        # zero lost requests
        terminal = {ServingRequestState.DONE,
                    ServingRequestState.CANCELLED}
        for r in reqs:
            assert r.state in terminal, (r.rid, r.state)
        m = router.metrics.metrics()
        done = sum(
            1 for r in reqs if r.state == ServingRequestState.DONE)
        assert m["serving_requests_completed_total"] == done
        assert m["serving_requests_cancelled_total"] == 200 - done
        assert m["serving_requests_requeued_total"] >= 1
        assert m["serving_requests_poisoned_total"] == 0

        # the crash looper: strictly increasing respawn gaps, then
        # quarantine — never a hot loop, never silent fleet loss
        assert crash_sup.quarantined, \
            "the crash loop must end in quarantine"
        record = list(crash_sup.quarantined.values())[0]
        times = record.respawn_times
        assert len(times) == 3
        gaps = [b - a for a, b in zip(times, times[1:])]
        assert all(g2 > g1 for g1, g2 in zip(gaps, gaps[1:])), gaps
        assert m["serving_worker_quarantined_total"] == 1.0

        # slot reclamation on every surviving replica, via STATS
        for name in router.replica_names:
            handle = router.manager.get(name)
            slot_deadline = time.monotonic() + 5.0
            while handle.slots_free() < 4 \
                    and time.monotonic() < slot_deadline:
                time.sleep(0.01)
            assert handle.slots_free() == 4, name
        # the flight recorder tells the whole story
        kinds = {e["kind"] for e in router.recorder.events(512)}
        assert "worker_quarantined" in kinds
        assert "worker_respawn_scheduled" in kinds
        assert "replica_dead" in kinds
    finally:
        for sup in sups:
            sup.shutdown()
