"""Step-engine suite (ISSUE 15): the router data-plane rebuild.

Covers the seam itself (event loop vs historical sweep), the incremental placement index's no-rescan guarantee (the
scheduling-decision-count regression pin the acceptance criteria
name), the event-driven cancel/expiry sweeps, batched frame drains,
the step-phase/step-lock histograms on /metrics, the full-pipeline
open-loop rig, and the satellites (cached worker trace headers, the
sampled traceparent fast path).

The equivalence test is the safety net under the whole refactor: the
same seeded workload — mixed priorities, cancels, an expiry, a replica
failure — must reach the SAME terminal state and output per submitted
request under the old sweep and the event loop.
"""

import dataclasses
import threading
import time

import numpy as np
import pytest

msgpack = pytest.importorskip(
    "msgpack", reason="remote fabric frames are msgpack")

from dlrover_tpu.common.constants import (  # noqa: E402
    ServingRequestState,
)
from dlrover_tpu.serving.remote.protocol import (  # noqa: E402
    FrameConnection,
    FrameKind,
)
from dlrover_tpu.serving.remote.worker import (  # noqa: E402
    FakeEngine,
    WorkerServer,
)
from dlrover_tpu.serving.router import (  # noqa: E402
    PRIORITY_BATCH,
    PRIORITY_HIGH,
    PRIORITY_NORMAL,
    ContinuousBatchScheduler,
    RequestGateway,
    RouterMetrics,
    ServingRouter,
)
from dlrover_tpu.serving.router.loadgen import (  # noqa: E402
    LoadgenConfig,
    run_router_rig,
)


def _prompt(i, n=8):
    return np.full(n, i % 251, np.int32)


def _router(step_engine, **kw):
    return ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4),
        step_engine=step_engine, **kw)


# -- the seam ----------------------------------------------------------------


def test_step_engine_validation():
    with pytest.raises(ValueError):
        ServingRouter(step_engine="warp")
    r = ServingRouter(step_engine="sweep")
    assert r.gateway.incremental is False
    assert r.scheduler.incremental is False
    r = ServingRouter()  # shipped default = the measured winner
    assert r.step_engine == "event"
    assert r.gateway.incremental is True
    assert r.scheduler.incremental is True


# -- placement fast path: the scheduling-decision-count pin ------------------


def test_placement_idle_cost_does_not_scale():
    """THE regression pin from the acceptance criteria: with R
    replicas all busy and Q queued requests nothing can place, the
    event engine's per-step placement cost must NOT scale with R x Q —
    after the round that blocks them, further steps do ZERO capacity
    evaluations until capacity actually grows.  The sweep twin shows
    the product the index kills."""
    R, Q = 32, 200
    evals = {}
    for engine in ("sweep", "event"):
        router = _router(engine)
        for i in range(R):
            router.join_replica(
                f"r{i}", FakeEngine(slots=1, tokens_per_step=1,
                                    max_len=4096))
        # pin every slot with a long job (well under max_len)
        pins = [router.submit(_prompt(i), 2000, timeout=None)
                for i in range(R)]
        for _ in range(2):
            router.step()
        assert all(p.state == ServingRequestState.RUNNING
                   for p in pins)
        blocked = [router.submit(_prompt(i), 8, timeout=None)
                   for i in range(Q)]
        router.step()  # the round that blocks them
        e0 = router.scheduler.capacity_evals
        for _ in range(10):
            router.step()
        evals[engine] = router.scheduler.capacity_evals - e0
        assert all(b.state == ServingRequestState.QUEUED
                   for b in blocked)
        if engine == "event":
            # the round short-circuit engaged for the idle steps
            assert router.scheduler.rounds_skipped >= 8
        else:
            assert router.scheduler.rounds_skipped == 0
    assert evals["event"] == 0, (
        f"idle entries must cost zero fit evaluations, got "
        f"{evals['event']}")
    # the sweep's cost is the (replicas x window) product, every step
    assert evals["sweep"] >= 10 * R * min(Q, 64) * 0.9


def test_capacity_growth_unblocks_requests():
    """The flip side of the pin: blocked requests MUST re-scan as soon
    as any replica's capacity grows — a stale blocked stamp that
    outlives freed capacity would strand the queue."""
    router = _router("event")
    eng = FakeEngine(slots=1, tokens_per_step=4, max_len=4096)
    router.join_replica("r0", eng)
    pin = router.submit(_prompt(0), 2000, timeout=None)
    router.step()
    assert pin.state == ServingRequestState.RUNNING
    blocked = [router.submit(_prompt(i), 8, timeout=None)
               for i in range(5)]
    for _ in range(5):
        router.step()
    assert all(b.state == ServingRequestState.QUEUED for b in blocked)
    # withdraw the pin -> slot frees -> capacity generation bumps ->
    # blocked requests place, one at a time, until all complete
    pin.cancel()
    deadline = time.monotonic() + 10.0
    while router.has_work and time.monotonic() < deadline:
        router.step()
    assert pin.state == ServingRequestState.CANCELLED
    for b in blocked:
        assert b.state == ServingRequestState.DONE, (b.rid, b.state)


def test_queue_removal_invalidates_idle_marker():
    """Review-found starvation regression: a window full of
    unplaceable requests blocks everything behind it; when they leave
    the queue WITHOUT a placement or an admission (deadline expiry
    here — cancellation and brown-out shed are the same class), the
    scheduler's idle short-circuit must invalidate, or the now-visible
    placeable requests behind the window starve forever while the
    fleet sits idle."""
    t = 3000.0
    router = _router("event")
    # one replica with a tiny KV budget: big requests can never fit
    eng = FakeEngine(slots=4, tokens_per_step=4, block_size=4,
                     blocks=20, max_len=4096)
    router.join_replica("r0", eng, now=t)
    # a full schedule window of unplaceable requests with a deadline
    big = [router.submit(_prompt(i, n=64), 512, timeout=1.0, now=t)
           for i in range(64)]
    # placeable requests stuck BEHIND the window
    small = [router.submit(_prompt(i), 4, timeout=None, now=t)
             for i in range(4)]
    router.step(now=t)
    assert all(b.state == ServingRequestState.QUEUED for b in big)
    assert all(s.state == ServingRequestState.QUEUED for s in small)
    # the big ones expire out of the queue; nothing else changes —
    # no admission, no capacity growth
    router.step(now=t + 1.5)
    assert all(b.state == ServingRequestState.TIMED_OUT for b in big)
    # the smalls must now enter the window and complete
    for _ in range(10):
        router.step(now=t + 2.0)
        if not router.has_work:
            break
    for s in small:
        assert s.state == ServingRequestState.DONE, (s.rid, s.state)


def test_affinity_reverse_index_consistency():
    """Affinity placement must survive the index rebuild: a replica
    that served a prefix wins its next request, and forgetting the
    replica cleans the reverse index."""
    sched = ContinuousBatchScheduler(block_size=4, prefix_tokens=8,
                                     incremental=True)
    gw = RequestGateway()
    gw.incremental = True
    engines = {name: FakeEngine(slots=4, tokens_per_step=8)
               for name in ("a", "b")}

    class H:
        def __init__(self, name, eng):
            self.name, self.eng = name, eng

        def slots_free(self):
            return self.eng.slots_free()

        def blocks_free(self):
            return self.eng.blocks_free()

    handles = [H(n, e) for n, e in engines.items()]
    prompt = np.arange(16, dtype=np.int32)
    r1 = gw.submit(prompt, 4)
    placed = sched.schedule(gw, handles)
    assert len(placed) == 1
    winner = placed[0][0].name
    key = sched.prefix_key(prompt)
    assert winner in sched._affinity_index[key]
    # same prefix again: the warm replica must win even if the other
    # is less loaded
    engines[winner].active[99] = {"remaining": 1, "output": [],
                                  "blocks": 0}
    r2 = gw.submit(prompt, 4)
    placed = sched.schedule(gw, handles)
    assert placed[0][0].name == winner
    sched.forget_replica(winner)
    assert key not in sched._affinity_index
    assert r1.state == r2.state  # both left the queue identically


# -- event-driven sweeps -----------------------------------------------------


@pytest.mark.parametrize("step_engine", ["event", "sweep"])
def test_cancel_queued_and_inflight_accounting(step_engine):
    """Queued and in-flight withdrawals answer their callers and
    balance the books identically under both engines."""
    router = _router(step_engine)
    eng = FakeEngine(slots=2, tokens_per_step=1, max_len=4096)
    router.join_replica("r0", eng)
    inflight = [router.submit(_prompt(i), 100) for i in range(2)]
    router.step()
    assert all(r.state == ServingRequestState.RUNNING
               for r in inflight)
    queued = [router.submit(_prompt(i), 8) for i in range(3)]
    assert inflight[0].cancel()
    assert queued[1].cancel()
    router.step()
    assert inflight[0].state == ServingRequestState.CANCELLED
    assert queued[1].state == ServingRequestState.CANCELLED
    assert router.gateway.cancelled == 2
    # the engine slot was reclaimed (CANCEL delivered locally)
    assert inflight[0].engine_rid not in eng.active
    # double-cancel of a terminal request is refused and changes
    # nothing
    assert not inflight[0].cancel()
    router.step()
    assert router.gateway.cancelled == 2


def test_double_cancel_counts_once_event_engine():
    """Review-found books regression: a client retrying cancel() (or
    racing threads) must not inflate the cancelled counter — cancel()
    is idempotent at the source and the event drain dedupes by
    identity as the belt."""
    router = _router("event")
    router.join_replica(
        "r0", FakeEngine(slots=1, tokens_per_step=1, max_len=4096))
    req = router.submit(_prompt(1), 8)
    assert req.cancel()
    assert req.cancel()  # retry: accepted, but one event only
    router.step()
    assert req.state == ServingRequestState.CANCELLED
    assert router.gateway.cancelled == 1
    assert router.gateway.submitted == 1


def test_duplicate_heap_entries_expire_once():
    """Review-found books regression: a failover requeue pushes a
    SECOND deadline-heap entry for the same request; when the deadline
    passes while it is QUEUED, expire() must count it once, not once
    per entry."""
    t = 2000.0
    gw = RequestGateway()
    req = gw.submit(_prompt(1), 4, timeout=5.0, now=t)
    gw.remove(req)
    req.state = ServingRequestState.RUNNING  # placed on a replica
    # the replica dies: requeue_front re-pushes a heap entry
    assert gw.requeue_front([req], now=t + 1.0) == []
    assert req.state == ServingRequestState.QUEUED
    expired = gw.expire(now=t + 6.0)
    assert expired == [req]
    assert gw.timed_out == 1
    assert req.state == ServingRequestState.TIMED_OUT


def test_deadline_heap_expiry_edges():
    """The event engine's heap must reproduce the sweep's strict
    ``now > deadline`` semantics: timeout=0 expires on the NEXT step
    (not at now == deadline), and a failover-requeued request whose
    deadline passed while RUNNING still expires promptly."""
    t = 1000.0
    router = _router("event")
    req = router.submit(_prompt(1), 4, timeout=0.0, now=t)
    router.step(now=t)   # now == deadline: strict >, stays queued
    assert req.state == ServingRequestState.QUEUED
    router.step(now=t + 0.001)
    assert req.state == ServingRequestState.TIMED_OUT

    # requeue-past-deadline: RUNNING through its deadline under the
    # let-it-finish policy, then the replica dies -> requeue -> the
    # replay must expire, not sit in the queue forever
    router = _router("event")
    eng = FakeEngine(slots=1, tokens_per_step=1, max_len=4096)
    router.join_replica("r0", eng, now=t)
    req = router.submit(_prompt(2), 1000, timeout=5.0, now=t)
    router.step(now=t)
    assert req.state == ServingRequestState.RUNNING
    router.step(now=t + 6.0)  # past deadline; policy lets it run
    assert req.state == ServingRequestState.RUNNING
    router.fail_replica("r0")
    router.step(now=t + 7.0)  # failover requeues...
    router.step(now=t + 7.1)  # ...and the re-armed heap expires it
    assert req.state == ServingRequestState.TIMED_OUT


def test_cancel_inflight_on_expiry_event_engine():
    """The expiry-cancel policy rides the deadline heap: a RUNNING
    request past its deadline aborts and frees its engine slot."""
    t = 1000.0
    router = _router("event", cancel_inflight_on_expiry=True)
    eng = FakeEngine(slots=1, tokens_per_step=1, max_len=4096)
    router.join_replica("r0", eng, now=t)
    req = router.submit(_prompt(1), 1000, timeout=2.0, now=t)
    router.step(now=t)
    assert req.state == ServingRequestState.RUNNING
    router.step(now=t + 2.5)
    assert req.state == ServingRequestState.TIMED_OUT
    assert req.engine_rid not in eng.active, "slot must be reclaimed"
    assert router.gateway.timed_out == 1


# -- equivalence: same seeded workload, same terminal states -----------------


def _replay_workload(router):
    """One seeded mixed workload: three priority bands, two cancels, a
    replica failure mid-run.  Returns the per-submission-index
    (state, output length) list — output VALUES differ legitimately
    across engines (FakeEngine tokens encode the engine-local rid, and
    placement distribution is allowed to differ); outcomes may not."""
    t = 5000.0
    engines = [FakeEngine(slots=2, tokens_per_step=2, max_len=4096)
               for _ in range(4)]
    for i, eng in enumerate(engines):
        router.join_replica(f"r{i}", eng, now=t)
    reqs = []
    bands = [PRIORITY_HIGH, PRIORITY_NORMAL, PRIORITY_NORMAL,
             PRIORITY_BATCH]
    for i in range(60):
        reqs.append(router.submit(
            _prompt(i), 8, priority=bands[i % 4],
            timeout=None if i % 7 else 300.0, now=t))
    for step in range(400):
        t += 0.05
        router.step(now=t)
        if step == 2:
            reqs[5].cancel()
            reqs[40].cancel()
        if step == 4:
            # kill one replica: its in-flight requests fail over
            router.fail_replica("r1")
        if not router.has_work:
            break
    return [(r.state, len(r.output)) for r in reqs]


@pytest.mark.parametrize("candidate", ["sweep"])
def test_step_engine_equivalence_terminal_states(candidate):
    """Same seeded workload -> same terminal state and output per
    submitted request under the event loop (the shipped default) and
    the reference.  Placement DISTRIBUTION may differ (the index breaks
    capacity ties by name); request OUTCOME may not."""
    baseline = _replay_workload(_router("event"))
    other = _replay_workload(_router(candidate))
    assert len(baseline) == len(other)
    for i, (a, b) in enumerate(zip(baseline, other)):
        assert a == b, f"submission {i}: event={a} {candidate}={b}"
    # the workload exercised what it claims to
    states = {s for s, _ in baseline}
    assert ServingRequestState.DONE in states
    assert ServingRequestState.CANCELLED in states


def test_failover_equivalence_zero_lost():
    """A replica failure mid-run balances the books under every
    engine: every request terminal, requeues observed, zero poisoned."""
    for engine in ("event", "sweep"):
        router = _router(engine)
        t = 7000.0
        for i in range(4):
            router.join_replica(
                f"r{i}", FakeEngine(slots=2, tokens_per_step=1,
                                    max_len=4096), now=t)
        reqs = [router.submit(_prompt(i), 12, now=t)
                for i in range(40)]
        for step in range(500):
            t += 0.05
            router.step(now=t)
            if step == 3:
                router.fail_replica("r0")
            if not router.has_work:
                break
        for r in reqs:
            assert r.state == ServingRequestState.DONE, (
                r.rid, r.state)
        m = router.metrics.metrics()
        assert m["serving_requests_requeued_total"] >= 1
        assert m["serving_requests_poisoned_total"] == 0


class _ThreadedWorker:
    def __init__(self, **engine_kw):
        self.engine = FakeEngine(**engine_kw)
        self.server = WorkerServer(self.engine)
        self.thread = threading.Thread(
            target=self.server.serve_forever, daemon=True)
        self.thread.start()

    def stop(self):
        self.server.crash()


# -- instrumentation on /metrics ---------------------------------------------


def test_step_phase_and_lock_histograms_render():
    """The measure-first half of the acceptance: step-lock hold time
    and per-phase step histograms are registered families rendered on
    the same surface as the latency histograms, with samples after one
    step."""
    from dlrover_tpu.serving.router.metrics import STEP_PHASES
    from dlrover_tpu.utils.metric_registry import (
        METRIC_HELP,
        METRIC_LABELS,
    )

    assert "serving_step_lock_hold_seconds" in METRIC_HELP
    assert "serving_step_phase_seconds" in METRIC_HELP
    assert METRIC_LABELS["serving_step_phase_seconds"] == ("phase",)

    router = _router("event")
    router.join_replica("r0", FakeEngine(slots=2, tokens_per_step=4))
    reqs = [router.submit(_prompt(i), 4) for i in range(4)]
    deadline = time.monotonic() + 10.0
    while router.has_work and time.monotonic() < deadline:
        router.step()
    assert all(r.state == ServingRequestState.DONE for r in reqs)
    text = router.metrics.render_histograms()
    assert "serving_step_lock_hold_seconds_bucket" in text
    # every phase renders as one labeled series of the SAME family,
    # with exactly one TYPE header for it
    for phasename in STEP_PHASES:
        assert f'serving_step_phase_seconds_bucket{{phase="{phasename}"' \
            in text, phasename
    assert text.count("# TYPE serving_step_phase_seconds ") == 1
    # the hot phases actually observed samples
    assert router.metrics.step_phase_hists["pump"].count > 0
    assert router.metrics.step_phase_hists["schedule"].count > 0
    assert router.metrics.step_lock_hist.count > 0
    # and the scheduler counters reached the scrape dict
    m = router.metrics.metrics()
    assert "serving_sched_capacity_evals_total" in m
    assert "serving_sched_rounds_skipped_total" in m


# -- batched frame drains ----------------------------------------------------


def test_recv_many_batches_and_defers_mid_batch_state():
    """recv_many returns the first frame plus everything buffered
    behind it; a clean EOF at a frame boundary ends the batch and the
    NEXT call reports it."""
    import socket

    a, b = socket.socketpair()
    tx = FrameConnection(a)
    rx = FrameConnection(b)
    for i in range(5):
        tx.send(FrameKind.TOKEN, rid=i, tokens=[i])
    time.sleep(0.05)  # let the bytes land in rx's kernel buffer
    frames = rx.recv_many(timeout=1.0)
    assert [f["rid"] for f in frames] == [0, 1, 2, 3, 4]
    tx.send(FrameKind.GOODBYE)
    a.close()
    frames = rx.recv_many(timeout=1.0)
    assert [f["kind"] for f in frames] == [FrameKind.GOODBYE]
    assert rx.recv_many(timeout=1.0) is None  # clean EOF
    rx.close()


def test_proxy_coalesces_token_storm_into_batches():
    """Under a token storm the proxy's reader crosses its lock once
    per BATCH: frames_received grows much faster than frame_batches,
    and the drained events still carry every token in order."""
    from dlrover_tpu.serving.remote.proxy import RemoteReplicaHandle

    w = _ThreadedWorker(slots=8, tokens_per_step=4)
    try:
        proxy = RemoteReplicaHandle(w.server.addr, name="storm")
        router = _router("event")
        router.join_replica("storm", proxy)
        reqs = [router.submit(_prompt(i), 64) for i in range(8)]
        deadline = time.monotonic() + 30.0
        while router.has_work and time.monotonic() < deadline:
            router.step()
            time.sleep(0.001)
        for r in reqs:
            assert r.state == ServingRequestState.DONE
            assert len(r.output) == 64
        assert proxy.frames_received > 50
        assert proxy.frame_batches < proxy.frames_received, (
            "batching never coalesced anything: "
            f"{proxy.frame_batches} batches for "
            f"{proxy.frames_received} frames")
        proxy.close()
    finally:
        w.stop()


# -- the full-pipeline rig ---------------------------------------------------


def test_router_rig_full_pipeline_books_balance():
    """The fast twin of the bench gate: a small open-loop schedule
    through the whole pipeline — zero lost, books balancing, e2e
    percentiles measured from the requests themselves."""
    router = _router("event", gateway=RequestGateway(
        max_pending=4096, default_timeout=10.0))
    for i in range(4):
        router.join_replica(
            f"r{i}", FakeEngine(slots=32, tokens_per_step=8,
                                blocks=500_000))
    rig = run_router_rig(
        router,
        LoadgenConfig(rate_qps=1500, duration_s=0.5, seed=3,
                      max_new_tokens=8))
    assert rig["router_admitted"] > 200
    assert rig["router_lost"] == 0
    assert rig["router_poisoned"] == 0
    assert rig["router_books_ok"]
    assert rig["router_completed"] == rig["router_admitted"]
    assert rig["router_qps"] > 0
    assert rig["router_e2e_p99_s"] > 0


def test_router_rig_mid_flight_cancels_keep_books():
    """cancel_every drives the withdrawal machinery at rate: books
    still balance with cancels in the mix."""
    router = _router("event", gateway=RequestGateway(
        max_pending=4096, default_timeout=10.0))
    for i in range(2):
        router.join_replica(
            f"r{i}", FakeEngine(slots=8, tokens_per_step=2,
                                blocks=500_000))
    rig = run_router_rig(
        router,
        LoadgenConfig(rate_qps=800, duration_s=0.5, seed=5,
                      max_new_tokens=16),
        cancel_every=10)
    assert rig["router_lost"] == 0
    assert rig["router_poisoned"] == 0
    assert rig["router_books_ok"]
    assert rig["router_cancel_attempts"] > 0
    assert rig["router_by_state"].get(
        ServingRequestState.CANCELLED, 0) > 0


# -- satellites --------------------------------------------------------------


def test_worker_trace_header_cached_per_request():
    """The TOKEN-frame trace echo is built once per request, not once
    per frame — and a sampled-out request ships no trace bytes."""
    server = WorkerServer(FakeEngine(slots=2))
    try:
        server._trace_by_erid[7] = {
            "trace": "00-" + "a" * 32 + "-" + "b" * 16 + "-01",
            "t0": 0.0, "t_first": None, "steps": 0, "engine_s": 0.0,
            "hdr": {"trace": "00-" + "a" * 32 + "-" + "b" * 16
                    + "-01"},
        }
        h1 = server._trace_header(7)
        h2 = server._trace_header(7)
        assert h1 is h2, "header must be the cached per-request dict"
        assert server._trace_header(99) == {}
    finally:
        server.crash()


def test_traceparent_sampled_fast_path(monkeypatch):
    """A sampled-IN trace builds its traceparent without consulting
    the tracer (no lock round trip per submit); a sampled-OUT one
    still honors the incident override through should_propagate."""
    from dlrover_tpu.utils.tracing import RequestTrace, Tracer

    tracer = Tracer(sample_rate=1.0)
    rt = RequestTrace(tracer, 1)
    assert rt.sampled is True
    calls = {"n": 0}
    real = tracer.should_propagate

    def counting(trace_id):
        calls["n"] += 1
        return real(trace_id)

    monkeypatch.setattr(tracer, "should_propagate", counting)
    assert rt.traceparent() is not None
    assert calls["n"] == 0, "sampled-in must skip the tracer lock"

    # sampled-out: propagation denied until the incident override
    tracer = Tracer(sample_rate=0.0)
    rt = RequestTrace(tracer, 2)
    assert rt.sampled is False
    assert rt.traceparent() is None
    tracer.mark_incident(rt.root.trace_id, "failover")
    assert rt.traceparent() is not None


def test_sampled_out_done_frames_skip_span_work():
    """End-to-end: at sample_rate=0.0 a remote completion carries no
    spans and grafts nothing — the frame path pays no tracing cost the
    knob was meant to shed; incidents still keep their trace."""
    from dlrover_tpu.serving.remote.proxy import RemoteReplicaHandle

    w = _ThreadedWorker(slots=4, tokens_per_step=4)
    try:
        router = ServingRouter(
            gateway=RequestGateway(trace_sample_rate=0.0),
            scheduler=ContinuousBatchScheduler(block_size=4))
        proxy = RemoteReplicaHandle(w.server.addr, name="w")
        router.join_replica("w", proxy)
        reqs = [router.submit(_prompt(i), 8) for i in range(4)]
        deadline = time.monotonic() + 20.0
        while router.has_work and time.monotonic() < deadline:
            router.step()
            time.sleep(0.002)
        for r in reqs:
            assert r.state == ServingRequestState.DONE
            assert r.trace.sampled is False
        assert router.tracer.orphan_spans_total == 0
        # nothing retained: the knob bit end to end
        assert router.tracer.dropped_total == 4
        proxy.close()
    finally:
        w.stop()


# -- the nightly soak --------------------------------------------------------


@pytest.mark.slow
def test_router_open_loop_soak_60s():
    """Nightly: 60s of full-router open-loop traffic — a bursty
    segment then a diurnal segment, heavy-tail prompts, mid-flight
    cancels every 50 admissions — books balance and nothing is lost
    or poisoned at the end of each segment."""
    for arrival, seed in (("bursty", 11), ("diurnal", 13)):
        router = ServingRouter(
            gateway=RequestGateway(
                max_pending=8192, default_timeout=10.0,
                trace_sample_rate=0.01),
            scheduler=ContinuousBatchScheduler(block_size=4),
            metrics=RouterMetrics(window_seconds=5.0),
        )
        for i in range(8):
            router.join_replica(
                f"r{i}", FakeEngine(slots=64, tokens_per_step=8,
                                    blocks=2_000_000))
        rig = run_router_rig(
            router,
            LoadgenConfig(
                rate_qps=4000, duration_s=30.0, seed=seed,
                arrival=arrival, prompt_mix="heavy_tail",
                max_new_tokens=8),
            cancel_every=50)
        assert rig["router_lost"] == 0, (arrival, rig)
        assert rig["router_poisoned"] == 0, (arrival, rig)
        assert rig["router_books_ok"], (arrival, rig)
        assert rig["router_cancel_attempts"] > 0
        assert rig["router_qps"] >= 1000, (arrival, rig)


# ----------------------------------------------------------------------
# The ENGINE's step (ISSUE 35, ISSUE 48): a step dispatches all its
# programs before it waits for any, and leaves its decode chunk unread for
# the next step.  Each slot's last token passes from program to program
# on the device; the host reads in dispatch order, behind the step's last
# dispatch, the chunk of the step before first.

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from dlrover_tpu.models.llama import LlamaConfig, LlamaModel  # noqa: E402
from dlrover_tpu.serving.engine import InferenceEngine  # noqa: E402
from dlrover_tpu.serving.router.replica import (  # noqa: E402
    InferenceEngineAdapter,
)

# (prompt length, max_new_tokens): across the buckets 8 / 16 / 32 / 48,
# two longer than ``prefill_chunk`` 16, one with a budget of one token;
# the first token of request 4 is made the end-of-sequence
_QUEUE = ((5, 9), (12, 7), (20, 10), (7, 1), (9, 8), (30, 6), (6, 11),
          (14, 5))
_EOS_FIRST = 4


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    variables = LlamaModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
               for n, _ in _QUEUE]
    return cfg, variables, prompts


def _engine(tiny_model, **kw):
    cfg, variables, _ = tiny_model
    args = dict(max_slots=3, chunk=4, temperature=0.0, max_len=48,
                prefill_buckets=(8, 16, 32, 48))
    args.update(kw)
    return InferenceEngine(cfg, variables, **args)


@pytest.fixture(scope="module")
def solo_tokens(tiny_model):
    """Every request of the queue alone, through ``generate`` on an idle
    engine of one slot with no end-of-sequence: greedy decoding's tokens,
    which no batching, layout, admission path or order of reads may
    change."""
    _, _, prompts = tiny_model
    eng = _engine(tiny_model, max_slots=1)
    solo = []
    for prompt, (_, new) in zip(prompts, _QUEUE):
        tokens, _ = eng.generate(prompt[None], new)
        solo.append(tokens[0, prompt.size:].tolist())
    return solo


def _until_eos(tokens, eos):
    return tokens[: tokens.index(eos) + 1] if eos in tokens else tokens


def _in_flight_is_one_chunk(eng):
    """Between steps: nothing, or the last step's decode chunk."""
    assert [u.name for u in eng._unread] in ([], ["decode_chunk"])
    assert eng._in_flight == len(eng._unread)


def _drive(eng, prompts, parents_order=False):
    """The whole queue through ``step``; returns the tokens by request,
    the order of finishes, and the finishes of each step as (where the
    finishing token came from, slot, request).  ``parents_order``: every
    step reads its own chunk before it returns, as the parent's did, and
    the next uploads the host's last tokens, which is where the parent's
    programs took them from."""
    phases = []
    finish = eng._finish

    def during(phase, fn):
        def inner(*args):
            phases.append(phase)
            try:
                return fn(*args)
            finally:
                phases.pop()
        return inner

    def noted_finish(s, req):
        steps[-1].append((phases[-1], s, req.rid))
        order.append(req.rid)
        return finish(s, req)

    eng._finish = noted_finish
    eng._deliver_firsts = during("first", eng._deliver_firsts)
    eng._deliver_chunk = during("chunk", eng._deliver_chunk)
    for prompt, (_, new) in zip(prompts, _QUEUE):
        eng.add_request(prompt, new)
    steps, order, returned = [], [], []
    while eng.has_work:
        assert len(steps) < 200
        steps.append([])
        returned += [r.rid for r in eng.step()]
        _in_flight_is_one_chunk(eng)
        if parents_order:
            eng._read_results()
            eng._last_dev = None
        else:
            # a step returns what it finished, whole
            assert returned == order
        if not eng._unread:
            # the device's vector is the host's, slot for slot
            assert np.array_equal(np.asarray(eng._last_tokens()),
                                  eng._tokens)
    assert all(r.done and len(r.output) <= r.max_new_tokens
               for r in eng._finished)
    return ({r.rid: list(r.output) for r in eng._finished}, order, steps)


@pytest.mark.parametrize("sampling", ["greedy", "seeded"])
@pytest.mark.parametrize("admission", ["bucketed", "chunked"])
@pytest.mark.parametrize("layout", ["paged", "dense"])
def test_a_mixed_queue_yields_the_parents_tokens(
        tiny_model, solo_tokens, layout, admission, sampling):
    """Prompts across the buckets, long ones, a budget of one token and a
    first token that is the end-of-sequence, more requests than slots,
    every step looking ahead: greedy, every request's tokens are its solo
    run's through ``generate`` (the parent's, token for token); sampled
    from a seed, they are those of the same engine driven in the parent's
    order (each step reads its own chunk before it returns, and the host's
    last tokens are uploaded again).  Requests finish in the order of the
    dispatches: the chunk of the step before by slot, then this step's
    first tokens."""
    _, _, prompts = tiny_model
    kw = dict(paged=layout == "paged", block_size=8,
              prefill_chunk=16 if admission == "chunked" else 0)
    if sampling == "greedy":
        eos = solo_tokens[_EOS_FIRST][0]
        want = {i: _until_eos(t, eos) for i, t in enumerate(solo_tokens)}
        eng = _engine(tiny_model, eos_token=eos, **kw)
        got, order, steps = _drive(eng, prompts)
        assert got == want
    else:
        kw.update(temperature=0.8, top_k=20, seed=5)
        free, _, _ = _drive(_engine(tiny_model, **kw), prompts)
        eos = free[_EOS_FIRST][0]
        eng = _engine(tiny_model, eos_token=eos, **kw)
        got, order, steps = _drive(eng, prompts)
        want, want_order, _ = _drive(
            _engine(tiny_model, eos_token=eos, **kw), prompts,
            parents_order=True)
        assert got == want and order == want_order
        assert got != {i: _until_eos(t, eos)
                       for i, t in enumerate(solo_tokens)}   # it sampled
    assert got[_EOS_FIRST] == [eos] and len(got[3]) == 1
    assert sorted(order) == list(range(len(_QUEUE)))
    assert eng.stats.lookahead_steps > 0
    for step in steps:
        chunk = [f for f in step if f[0] == "chunk"]
        firsts = [f for f in step if f[0] == "first"]
        assert step == chunk + firsts
        assert [s for _, s, _ in chunk] == sorted(s for _, s, _ in chunk)
    # a first token that ends its request is learnt at the step's reads;
    # its lane in that step's chunk is dropped when the chunk is read
    assert any(f[2] == _EOS_FIRST and f[0] == "first"
               for step in steps for f in step)
    assert eng.stats.wasted_lane_chunks >= 1


class _Read:
    """A program's output that says when the host reads it."""

    def __init__(self, array, events, name):
        self._array, self._events, self._name = array, events, name

    def __array__(self, *args, **kwargs):
        self._events.append(("read", self._name))
        return np.asarray(self._array, *args, **kwargs)


def _spied(eng, events):
    """Wrap the engine's programs: every dispatch is noted, and every
    output the host has a use for notes its read."""
    def spy(name, program, reads):
        def run(*args):
            events.append(("dispatch", name))
            out = list(program(*args))
            for i in reads:
                out[i] = _Read(out[i], events, name)
            return tuple(out)
        return run

    eng._insert_fn = spy("prefill", eng._insert_fn, (1,))
    eng._chunk_fn = spy("decode_chunk", eng._chunk_fn, (0,))
    if eng._prefill_chunk_fn is not None:
        eng._prefill_chunk_fn = spy("prefill_chunk", eng._prefill_chunk_fn,
                                    (1,))


def test_a_step_reads_nothing_between_its_dispatches(tiny_model):
    """Two admissions (two buckets: two prefill programs), a prompt
    chunk and a decode chunk in one step, behind the chunk the step
    before left in flight: four dispatches with no device-to-host read
    among them, then four reads in the order of the dispatches, the OLD
    chunk's first; the new chunk stays unread."""
    _, _, prompts = tiny_model
    eng = _engine(tiny_model, max_slots=4, paged=True, block_size=8,
                  prefill_chunk=16)
    events = []
    _spied(eng, events)
    eng.add_request(prompts[0], 12)
    eng.add_request(prompts[5], 6)          # 30 tokens: two chunks
    eng.step()
    # the first step's chunk is in flight: dispatched, not read
    assert events == [
        ("dispatch", "prefill"), ("dispatch", "prefill_chunk"),
        ("dispatch", "decode_chunk"), ("read", "prefill"),
        ("read", "prefill_chunk")]
    assert [u.name for u in eng._unread] == ["decode_chunk"]
    assert len(eng._slot_req[0].output) == 1
    del events[:]
    eng.add_request(prompts[6], 8)          # bucket 8
    eng.add_request(prompts[1], 8)          # bucket 16
    before = dataclasses.replace(eng.stats)
    with jax.transfer_guard_device_to_host("disallow_explicit"):
        eng._dispatch_admissions()
        eng._advance_prefill()
        eng._dispatch_decode(eng._decoding())
    assert events == [("dispatch", n) for n in (
        "prefill", "prefill", "prefill_chunk", "decode_chunk")]
    assert [len(r.output) for r in eng._slot_req] == [1, 0, 0, 0]
    eng._read_results(ahead=True)
    # chunk N is read after chunk N + 1's dispatch, and chunk N + 1 is not
    assert events[4:] == [("read", n) for n in (
        "decode_chunk", "prefill", "prefill", "prefill_chunk")]
    assert [u.name for u in eng._unread] == ["decode_chunk"]
    assert eng.stats.dispatches - before.dispatches == 4
    assert eng.stats.chained_dispatches - before.chained_dispatches == 4
    assert eng.stats.lookahead_steps - before.lookahead_steps == 1
    # a first token each, and for slot 0 the OLD chunk's four
    assert [len(r.output) for r in eng._slot_req] == [1 + 4, 1, 1, 1]
    # the whole of it again through ``step``: the same shape, and every
    # decode chunk is read exactly one step after its dispatch
    del events[:]
    eng.add_request(prompts[4], 3)
    unread_chunks = 1
    while eng.has_work:
        eng.step()
        kinds = [k for k, _ in events]
        assert kinds == sorted(kinds), events   # dispatches, then reads
        sent = events.count(("dispatch", "decode_chunk"))
        assert events.count(("read", "decode_chunk")) == unread_chunks
        if unread_chunks:
            assert events[kinds.index("read")] == ("read", "decode_chunk")
        unread_chunks = sent
        del events[:]
    assert unread_chunks == 0 and not eng._unread


def test_a_slot_and_its_blocks_are_refilled_under_the_unread_chunk(
        tiny_model, solo_tokens):
    """One slot, a pool that holds one request: the chunk that spends a
    request's budget frees its slot and its blocks at the DISPATCH, the
    next request is admitted into both while that chunk is unread, and
    both get their right tokens.  The finished one is returned by the
    step that reads it, with its whole output; ``has_work`` stays true
    until then."""
    _, _, prompts = tiny_model
    eng = _engine(tiny_model, max_slots=1, paged=True, block_size=8,
                  cache_blocks=5, prefix_sharing=False)
    first = eng.add_request(prompts[0], 9)      # 5 + 9: two blocks of 4
    returned = eng.step() + eng.step()
    # its last chunk is dispatched: the slot and the blocks are free, the
    # request has not been returned and lacks that chunk's tokens
    assert returned == [] and eng._slot_blocks[0] is None
    assert eng._blockmgr.available_blocks == 4
    (chunk,) = eng._unread
    (_, req, take, last), = chunk.rows
    assert req.rid == first and last and not req.done
    assert len(req.output) == 9 - take
    assert eng.has_work and not eng._queue
    second = eng.add_request(prompts[2], 10)    # 20 + 10: all the blocks
    returned = eng.step()
    assert eng._slot_req[0].rid == second
    assert set(eng._slot_blocks[0]) == {1, 2, 3, 4}
    assert [r.rid for r in returned] == [first] and returned[0].done
    assert returned[0].output == solo_tokens[0]
    done = eng.run()
    assert done[second].tolist() == solo_tokens[2]
    assert not eng.has_work and not eng._unread


def test_an_end_of_sequence_is_learnt_one_chunk_late(tiny_model,
                                                     solo_tokens):
    """Greedy, with the token a request emits INSIDE its second decode
    chunk made the end-of-sequence: the output is cut there, the request
    has a lane in the third chunk already, whose tokens are dropped (one
    ``wasted_lane_chunks``), and its blocks are released when the second
    chunk is read, not before."""
    _, _, prompts = tiny_model
    tokens = solo_tokens[2]                 # 10 tokens: 1 + 2 + 2 + 2 + ..
    eos = tokens[3]                         # ... the second chunk's first
    assert eos not in tokens[:3]
    eng = _engine(tiny_model, max_slots=2, chunk=2, paged=True,
                  block_size=8, eos_token=eos)
    events = []
    _spied(eng, events)
    release = eng._release_slot
    eng._release_slot = lambda s: (events.append(("release", s)),
                                   release(s))[1]
    eng.add_request(prompts[2], 10)
    returned = []
    while eng.has_work:
        returned += eng.step()
    assert [r.output for r in returned] == [tokens[:4]]
    assert eng.stats.wasted_lane_chunks == 1
    assert eng.stats.generated_tokens == 3      # (first tokens not counted)
    chunks = [e for e in events if e[1] != "prefill"]
    assert chunks == [
        ("dispatch", "decode_chunk"),           # 1: tokens 1-2
        ("dispatch", "decode_chunk"),           # 2: the end inside it
        ("read", "decode_chunk"),               # 1
        ("dispatch", "decode_chunk"),           # 3: the wasted lane
        ("read", "decode_chunk"),               # 2: the end is learnt
        ("release", 0),
        ("read", "decode_chunk"),               # 3: dropped
    ]
    assert eng._blockmgr.available_blocks == eng._blockmgr.num_blocks - 1


def test_lookahead_counters_are_counted_and_exported(tiny_model):
    """A scripted sequence of steps: a program is chained when an earlier
    one is unread, be it the step's own or the chunk the step before left;
    a step that returns with its chunk unread is a look-ahead step."""
    _, _, prompts = tiny_model
    eng = _engine(tiny_model, max_slots=4, paged=True, block_size=8)
    st = eng.stats
    assert (st.dispatches, st.chained_dispatches, st.lookahead_steps,
            st.wasted_lane_chunks) == (0, 0, 0, 0)
    assert st.chained_dispatch_share == 0.0
    eng.add_request(prompts[0], 20)
    eng.step()                  # a prefill, and the chunk behind it
    assert (st.dispatches, st.chained_dispatches) == (2, 1)
    eng.step()                  # the chunk, behind the unread one
    assert (st.dispatches, st.chained_dispatches) == (3, 2)
    assert st.lookahead_steps == 2
    eng.add_request(prompts[6], 2)      # bucket 8
    eng.add_request(prompts[4], 2)      # bucket 16
    eng.add_request(prompts[1], 2)      # bucket 16: one group with it
    eng.step()                  # two prefills and the chunk
    assert (st.dispatches, st.chained_dispatches) == (6, 5)
    assert st.prefill_calls == 3 and st.prefill_admissions == 4
    assert st.chained_dispatch_share == 5 / 6
    assert 0 < st.prefill_seconds and 0 < st.decode_seconds
    while eng.has_work:
        eng.step()
    # only the dispatch into an idle engine was not chained; the step
    # that found nothing to dispatch read what was left and looked no
    # further
    assert st.chained_dispatches == st.dispatches - 1
    assert st.lookahead_steps == st.decode_forwards // eng.chunk
    assert st.wasted_lane_chunks == 0
    metrics = RouterMetrics()
    sent = InferenceEngineAdapter(eng).engine_metrics()
    assert (sent["dispatches"], sent["chained_dispatches"]) == (
        float(st.dispatches), float(st.dispatches - 1))
    assert sent["lookahead_steps"] == float(st.lookahead_steps)
    assert sent["wasted_lane_chunks"] == 0.0
    metrics.observe_engine_metrics(
        [sent, {"dispatches": 2.0, "lookahead_steps": 1.0,
                "wasted_lane_chunks": 3.0}, {}])
    got = metrics.metrics()
    assert got["serving_engine_chained_dispatch_share"] \
        == (st.dispatches - 1) / (st.dispatches + 2.0)
    assert got["serving_engine_lookahead_steps_total"] \
        == st.lookahead_steps + 1.0
    assert got["serving_engine_wasted_lane_chunks_total"] == 3.0
    metrics.observe_engine_metrics([])
    assert metrics.metrics()["serving_engine_chained_dispatch_share"] == 0.0
    from dlrover_tpu.utils.metric_registry import METRIC_HELP

    for name in ("serving_engine_chained_dispatch_share",
                 "serving_moe_walks_per_layer",
                 "serving_engine_lookahead_steps_total",
                 "serving_engine_wasted_lane_chunks_total"):
        assert name in METRIC_HELP and name in got


@pytest.mark.parametrize("outside", ["cancel", "spec_step", "drain_fixed",
                                     "run", "generate"])
def test_what_is_in_flight_is_read_or_dropped_first_by(tiny_model,
                                                       solo_tokens, outside):
    """What works on the slots outside a step's chain of programs first
    deals with the chunk a step left in flight.  ``cancel`` drops the
    request's lane and waits for nothing (the device's vector stays: the
    chain is whole); a speculating step, ``_drain_fixed``, ``run`` and
    ``generate`` read it, and where they write the host's last tokens
    the next dispatch uploads those.  The tokens stay the solo runs'."""
    _, _, prompts = tiny_model
    kw = dict(paged=True, block_size=8)
    if outside == "spec_step":
        kw["speculative_k"] = 3
    eng = _engine(tiny_model, **kw)
    rids = [eng.add_request(prompts[i], _QUEUE[i][1]) for i in (0, 1, 2)]
    if outside == "spec_step":
        # chunk decode behind a verify leaves its chunk in flight ...
        eng._spec_state = "backoff"
        eng._spec_cooldown = 100
    eng.step()
    assert [u.name for u in eng._unread] == ["decode_chunk"]
    assert eng._last_dev is not None
    assert [len(r.output) for r in eng._slot_req] == [1, 1, 1]
    uploads = []
    upload = eng._last_tokens
    eng._last_tokens = lambda: (
        uploads.append(eng._last_dev is None), upload())[1]
    if outside == "cancel":
        assert eng.cancel(rids[1])
        assert eng._slot_req[1] is None and eng._slot_blocks[1] is None
        (chunk,) = eng._unread
        assert [row[0] for row in chunk.rows] == [0, 2]
        assert eng._last_dev is not None
        rids.append(eng.add_request(prompts[4], _QUEUE[4][1]))
        eng.step()
        assert not any(uploads)
    elif outside == "spec_step":
        # ... which the verify's step reads before it drafts
        eng._spec_state = "on"
        rids.append(eng.add_request(prompts[4], _QUEUE[4][1]))
        eng.step()
        assert eng.stats.spec_calls == 1
        assert eng._last_dev is None and not eng._unread
        assert all(len(r.output) >= 1 + 4 + 1 for r in eng._slot_req)
        eng._spec_state = "backoff"
        del uploads[:]
        eng.step()
        assert uploads[0] and not any(uploads[1:])
    elif outside == "drain_fixed":
        rids.append(eng.add_request(prompts[4], _QUEUE[4][1]))
        eng._drain_fixed()
        assert eng._last_dev is None and not eng._unread
        assert eng._in_flight == 0
        del uploads[:]
        eng.step()
        assert uploads[0] and not any(uploads[1:])
    else:
        rids.append(eng.add_request(prompts[4], _QUEUE[4][1]))
    if outside == "generate":
        tokens, _ = eng.generate(prompts[6][None], _QUEUE[6][1])
        assert tokens[0, prompts[6].size:].tolist() == solo_tokens[6]
        assert not eng.has_work and eng._last_dev is None
        return
    done = eng.run()
    assert not eng.has_work and not eng._unread and eng._in_flight == 0
    assert np.array_equal(np.asarray(eng._last_tokens()), eng._tokens)
    for i, rid in zip((0, 1, 2, 4), rids):
        if outside == "cancel" and i == 1:
            assert rid not in done
        else:
            assert done[rid].tolist() == solo_tokens[i]
