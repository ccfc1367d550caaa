"""End-to-end request tracing + flight recorder (utils/tracing.py).

The debugging surface ISSUE 4 adds on top of the aggregate metrics:
every serving request carries a span trace (admission -> placement ->
submit -> first token -> done, with worker-side spans grafted over the
frame protocol), ``/traces`` serves the ring, and the flight recorder
turns deadline expiries / poisonings / replica deaths into one
structured, self-explaining log record.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from dlrover_tpu.common.constants import ServingRequestState
from dlrover_tpu.serving.router import (
    ContinuousBatchScheduler,
    RequestGateway,
    ServingRouter,
)
from dlrover_tpu.utils.profiler import Histogram, MetricsExporter
from dlrover_tpu.utils.tracing import (
    FlightRecorder,
    Tracer,
    format_traceparent,
    new_span_id,
    new_trace_id,
    parse_traceparent,
    trace_sampled,
)


def _prompt(i, n=8):
    return np.full(n, i % 251, np.int32)


def _names(tree):
    """All span names in a trace tree, depth-first."""
    out = []

    def walk(spans):
        for s in spans:
            out.append(s["name"])
            walk(s["children"])

    walk(tree["spans"])
    return out


def _find(tree, name):
    found = []

    def walk(spans):
        for s in spans:
            if s["name"] == name:
                found.append(s)
            walk(s["children"])

    walk(tree["spans"])
    return found


# -- ids + traceparent -------------------------------------------------------


def test_traceparent_roundtrip():
    tid, sid = new_trace_id(), new_span_id()
    assert len(tid) == 32 and len(sid) == 16
    assert parse_traceparent(format_traceparent(tid, sid)) == (tid, sid)


@pytest.mark.parametrize("bad", [
    None, 17, "", "nonsense", "00-short-short-01",
    "00-" + "g" * 32 + "-" + "0" * 16 + "-01",   # non-hex
    "00-" + "0" * 32 + "-" + "0" * 8 + "-01",    # short span id
])
def test_traceparent_malformed_degrades_to_none(bad):
    assert parse_traceparent(bad) is None


def test_traceparent_roundtrip_over_frames():
    """The context string survives the msgpack frame protocol — what
    the SUBMIT header actually carries between router and worker."""
    import socket

    from dlrover_tpu.serving.remote.protocol import (
        FrameConnection,
        FrameKind,
    )

    tid, sid = new_trace_id(), new_span_id()
    a, b = socket.socketpair()
    left, right = FrameConnection(a), FrameConnection(b)
    left.send(FrameKind.SUBMIT, rid=1, prompt=[1, 2],
              max_new_tokens=4, trace=format_traceparent(tid, sid))
    frame = right.recv(timeout=2.0)
    assert parse_traceparent(frame["trace"]) == (tid, sid)
    left.close()
    right.close()


# -- tracer mechanics --------------------------------------------------------


def test_ring_evicts_oldest_finished_trace():
    tracer = Tracer(ring_capacity=3)
    roots = [tracer.start_trace("request", now=float(i), rid=i)
             for i in range(5)]
    for i, root in enumerate(roots):
        tracer.finish_trace(root, now=float(i) + 0.5)
    finished = tracer.finished()
    assert len(finished) == 3, "ring must stay bounded"
    kept = [t["spans"][0]["attrs"]["rid"] if t["spans"] else None
            for t in finished]
    assert [t["trace_id"] for t in finished] == [
        r.trace_id for r in roots[2:]], kept
    assert tracer.metrics()["serving_request_trace_finished_total"] == 5.0
    # the evicted trace is no longer findable
    assert tracer.get_tree(roots[0].trace_id) is None


def test_active_traces_are_bounded():
    tracer = Tracer(ring_capacity=8, max_active=4)
    roots = [tracer.start_trace("request", now=0.0) for _ in range(6)]
    assert tracer.metrics()["serving_request_trace_active"] == 4.0
    evicted = tracer.get_tree(roots[0].trace_id)
    assert evicted is not None and evicted["status"] == "evicted"


def test_graft_orphan_remote_spans_dropped_and_counted():
    tracer = Tracer()
    n = tracer.graft(new_trace_id(), new_span_id(), [
        {"name": "worker.request", "start": 1.0, "end": 2.0},
    ])
    assert n == 0
    assert tracer.metrics()[
        "serving_request_trace_orphan_spans_total"] == 1.0
    # malformed span dicts are also orphans, not errors
    root = tracer.start_trace("request", now=0.0)
    n = tracer.graft(root.trace_id, root.span_id,
                     [{"name": "x"}, {"name": "ok", "start": 0, "end": 1}])
    assert n == 1
    assert tracer.metrics()[
        "serving_request_trace_orphan_spans_total"] == 2.0


def test_graft_into_finished_trace_still_lands():
    """A DONE frame can race request completion: the trace is already
    in the ring, and the worker spans must still graft (the ring holds
    the object, not a copy)."""
    tracer = Tracer()
    root = tracer.start_trace("request", now=0.0)
    tracer.finish_trace(root, now=1.0)
    assert tracer.graft(root.trace_id, root.span_id, [
        {"name": "worker.request", "start": 0.2, "end": 0.8},
    ]) == 1
    assert "worker.request" in _names(tracer.get_tree(root.trace_id))


def test_flight_recorder_rings_are_bounded_and_dump_structured():
    rec = FlightRecorder(event_capacity=4, dump_capacity=2)
    for i in range(10):
        rec.record("evt", seq=i)
    assert [e["seq"] for e in rec.events()] == [6, 7, 8, 9]
    for i in range(3):
        rec.dump(f"reason-{i}", {"trace_id": "t", "spans": []})
    assert rec.dumps_total == 3
    assert len(rec.dumps) == 2
    d = rec.dumps[-1]
    assert d["reason"] == "reason-2"
    assert d["trace"]["trace_id"] == "t"
    assert [e["seq"] for e in d["recent_events"]] == [6, 7, 8, 9]
    json.dumps(d)  # the dump must be one JSON-serializable record


# -- request traces through the router ---------------------------------------


def _local_router(**gw_kw):
    from dlrover_tpu.serving.remote.worker import FakeEngine

    router = ServingRouter(
        gateway=RequestGateway(**gw_kw),
        scheduler=ContinuousBatchScheduler(block_size=4),
    )
    router.join_replica("local-0", FakeEngine(slots=4))
    return router


def test_request_trace_covers_every_hop_local():
    router = _local_router()
    req = router.submit(_prompt(1), 8)
    assert req.trace is not None
    router.run_until_idle()
    assert req.state == ServingRequestState.DONE
    tree = router.tracer.get_tree(req.trace.trace_id)
    assert tree["status"] == "ok"
    names = _names(tree)
    for expected in ("queued", "attempt", "submit", "first_token"):
        assert expected in names, names
    (attempt,) = _find(tree, "attempt")
    assert attempt["attrs"]["replica"] == "local-0"
    assert attempt["attrs"]["attempt"] == 1
    (submit,) = _find(tree, "submit")
    assert submit["status"] == "ok" and submit["duration_s"] is not None
    # every span closed, durations non-negative, nested under the root
    def check(spans):
        for s in spans:
            assert s["duration_s"] is not None and s["duration_s"] >= 0
            check(s["children"])
    check(tree["spans"])


def test_remote_request_trace_grafts_worker_spans():
    from dlrover_tpu.serving.remote.proxy import RemoteReplicaHandle
    from dlrover_tpu.serving.remote.worker import FakeEngine, WorkerServer

    server = WorkerServer(FakeEngine(slots=4, tokens_per_step=4))
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        router = ServingRouter(
            scheduler=ContinuousBatchScheduler(block_size=4))
        router.join_replica(
            "rw", RemoteReplicaHandle(server.addr, name="rw"))
        req = router.submit(_prompt(2), 8)
        deadline = time.monotonic() + 15.0
        while router.has_work and time.monotonic() < deadline:
            router.step()
            time.sleep(0.002)
        assert req.state == ServingRequestState.DONE
        tree = router.tracer.get_tree(req.trace.trace_id)
        names = _names(tree)
        for expected in ("queued", "attempt", "submit", "first_token",
                         "worker.request", "worker.decode"):
            assert expected in names, names
        # worker spans hang under the attempt, in ROUTER clock: the
        # worker.request span must sit inside the trace, not before it
        (wreq,) = _find(tree, "worker.request")
        assert wreq["offset_s"] >= 0
        (wdec,) = _find(tree, "worker.decode")
        assert wdec["attrs"]["steps"] >= 1
        assert wdec["attrs"]["engine_seconds"] >= 0
        router.begin_drain("rw")
        router.step()
    finally:
        server.crash()


def test_failover_trace_shows_both_attempts_and_flight_dump():
    """A replica death mid-flight leaves the dead attempt in the tree
    (status failover), the retry lands as attempt 2, and the flight
    recorder dumps the span tree at the moment of death."""
    from dlrover_tpu.serving.remote.worker import FakeEngine

    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4))
    router.join_replica("a", FakeEngine(slots=4, tokens_per_step=1))
    req = router.submit(_prompt(3), 8)
    router.step()  # placed on "a", partially generated
    assert req.state == ServingRequestState.RUNNING
    router.fail_replica("a")
    router.join_replica("b", FakeEngine(slots=4))
    router.run_until_idle()
    assert req.state == ServingRequestState.DONE
    assert req.requeues == 1
    tree = router.tracer.get_tree(req.trace.trace_id)
    attempts = _find(tree, "attempt")
    assert len(attempts) == 2
    by_n = {a["attrs"]["attempt"]: a for a in attempts}
    assert by_n[1]["attrs"]["replica"] == "a"
    assert by_n[1]["status"] == "failover"
    assert "failover_reason" in by_n[1]["attrs"]
    assert by_n[2]["attrs"]["replica"] == "b"
    assert by_n[2]["status"] == "ok"
    # two queue spans: the original wait and the requeue wait
    assert len(_find(tree, "queued")) == 2
    # the flight recorder dumped this request's tree on replica death
    dumps = [d for d in router.recorder.dumps
             if d["reason"] == "replica_death"]
    assert dumps
    assert dumps[0]["trace"]["trace_id"] == req.trace.trace_id
    kinds = [e["kind"] for e in dumps[0]["recent_events"]]
    assert "replica_join" in kinds
    assert "request_requeued" in kinds


def test_deadline_expiry_dumps_flight_record():
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4))
    # no replicas: the request can only wait, then expire
    req = router.submit(_prompt(4), 8, timeout=0.0, now=100.0)
    router.gateway.expire(now=101.0)
    assert req.state == ServingRequestState.TIMED_OUT
    tree = router.tracer.get_tree(req.trace.trace_id)
    assert tree["status"] == ServingRequestState.TIMED_OUT
    dumps = [d for d in router.recorder.dumps
             if d["reason"] == "deadline_expired"]
    assert dumps and dumps[0]["trace"]["trace_id"] == req.trace.trace_id
    assert router.tracer.metrics()[
        "serving_request_trace_flight_dumps_total"] >= 1.0


def test_poisoned_request_dumps_flight_record():
    gw = RequestGateway(max_requeues=0)
    req = gw.submit(_prompt(5), 4)
    gw.remove(req)
    poisoned = gw.requeue_front([req])
    assert poisoned == [req]
    assert req.state == ServingRequestState.POISONED
    dumps = [d for d in gw.tracer.recorder.dumps
             if d["reason"] == "poisoned"]
    assert dumps and dumps[0]["trace"]["trace_id"] == req.trace.trace_id
    assert gw.tracer.get_tree(req.trace.trace_id)["status"] == \
        ServingRequestState.POISONED


# -- /traces + metrics surfaces ----------------------------------------------


def test_traces_endpoints_serve_ring_and_slowest():
    router = _local_router()
    reqs = [router.submit(_prompt(i), 4 + 4 * i) for i in range(3)]
    router.run_until_idle()
    assert all(r.state == ServingRequestState.DONE for r in reqs)
    exporter = MetricsExporter()
    exporter.attach_tracer(router.tracer)
    exporter.start()
    try:
        base = f"http://127.0.0.1:{exporter.port}"
        body = json.loads(urllib.request.urlopen(
            f"{base}/traces", timeout=5).read().decode())
        assert len(body["traces"]) == 3
        ids = {t["trace_id"] for t in body["traces"]}
        assert ids == {r.trace.trace_id for r in reqs}
        for t in body["traces"]:
            assert t["status"] == "ok"
            assert "spans" in t and t["spans"]
        slow = json.loads(urllib.request.urlopen(
            f"{base}/traces/slowest", timeout=5).read().decode())
        durations = [t["duration_s"] for t in slow["traces"]]
        assert durations == sorted(durations, reverse=True)
        # tracer gauges ride the normal /metrics scrape
        metrics = urllib.request.urlopen(
            f"{base}/metrics", timeout=5).read().decode()
        assert "serving_request_trace_finished_total 3.0" in metrics
        assert "# HELP serving_request_trace_finished_total" in metrics
    finally:
        exporter.stop()


def test_traces_endpoint_404_without_tracer():
    exporter = MetricsExporter()
    exporter.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(
                f"http://127.0.0.1:{exporter.port}/traces", timeout=5)
        assert e.value.code == 404
    finally:
        exporter.stop()


# -- head sampling -----------------------------------------------------------


def test_trace_sampling_is_deterministic_and_rate_proportional():
    """The verdict is a pure function of (trace_id, rate): the router's
    retention decision and a worker's span-shipping decision agree with
    no coordination — and over many random ids the keep fraction tracks
    the rate."""
    ids = [new_trace_id() for _ in range(4000)]
    for tid in ids[:50]:
        assert trace_sampled(tid, 0.25) == trace_sampled(tid, 0.25)
        assert trace_sampled(tid, 1.0) is True
        assert trace_sampled(tid, 0.0) is False
        # monotone in the rate: sampled at 0.25 implies sampled at 0.5
        if trace_sampled(tid, 0.25):
            assert trace_sampled(tid, 0.5)
    kept = sum(trace_sampled(t, 0.25) for t in ids) / len(ids)
    assert 0.18 < kept < 0.32, kept
    # malformed ids sample IN: observability degrades toward keeping
    assert trace_sampled("not-hex", 0.001) is True


def test_worker_side_verdict_matches_router_side():
    """A router-built context asserts the sampled flag: it IS the
    router's keep verdict (the router omits the traceparent for
    sampled-out traces and keeps propagating for incidents), so the
    worker honors it unconditionally — re-deriving from the trace_id
    would veto exactly the incident traces the override preserves.
    Undecided (flags 00) contexts gate through the SAME deterministic
    predicate the router uses, so both sides agree coordination-free."""
    from dlrover_tpu.serving.remote.worker import FakeEngine, WorkerServer

    server = WorkerServer(FakeEngine(), trace_sample_rate=0.25)
    try:
        for _ in range(100):
            tid = new_trace_id()
            assert server._trace_wanted(
                format_traceparent(tid, new_span_id()))
            undecided = f"00-{tid}-{new_span_id()}-00"
            assert server._trace_wanted(undecided) \
                == trace_sampled(tid, 0.25)
    finally:
        server.crash()


def test_sampled_out_healthy_trace_dropped_and_counted():
    router = _local_router(trace_sample_rate=0.0)
    req = router.submit(_prompt(1), 8)
    assert req.trace is not None          # spans always stamped
    assert req.trace.traceparent() is None  # but never propagated
    router.run_until_idle()
    assert req.state == ServingRequestState.DONE
    m = router.tracer.metrics()
    assert m["serving_trace_dropped_total"] == 1.0
    assert m["serving_trace_sampled_total"] == 0.0
    assert router.tracer.finished() == []
    assert router.tracer.get_tree(req.trace.trace_id) is None


def test_incident_override_keeps_failover_trace_at_zero_rate():
    """Even at sample_rate 0, a failed-over request keeps its FULL
    trace (both attempts) — incidents must always be debuggable."""
    from dlrover_tpu.serving.remote.worker import FakeEngine

    router = ServingRouter(
        gateway=RequestGateway(trace_sample_rate=0.0),
        scheduler=ContinuousBatchScheduler(block_size=4))
    router.join_replica("a", FakeEngine(slots=4, tokens_per_step=1))
    req = router.submit(_prompt(3), 8)
    router.step()
    router.fail_replica("a")
    router.join_replica("b", FakeEngine(slots=4))
    router.step()  # reaps "a": the requeue marks the incident
    # the failover marked the trace as an incident: the retry's submit
    # resumes propagating context despite the zero rate
    assert req.trace.traceparent() is not None
    router.run_until_idle()
    assert req.state == ServingRequestState.DONE
    tree = router.tracer.get_tree(req.trace.trace_id)
    assert tree is not None and tree["status"] == "ok"
    assert len(_find(tree, "attempt")) == 2
    assert router.tracer.metrics()["serving_trace_sampled_total"] == 1.0


def test_expiry_and_cancel_kept_at_zero_rate():
    """Non-ok terminal statuses retain without any explicit marking."""
    router = ServingRouter(
        gateway=RequestGateway(trace_sample_rate=0.0),
        scheduler=ContinuousBatchScheduler(block_size=4))
    expired = router.submit(_prompt(4), 8, timeout=0.0, now=100.0)
    router.gateway.expire(now=101.0)
    cancelled = router.submit(_prompt(5), 8)
    assert cancelled.cancel()
    router.step()
    for req, status in ((expired, ServingRequestState.TIMED_OUT),
                        (cancelled, ServingRequestState.CANCELLED)):
        tree = router.tracer.get_tree(req.trace.trace_id)
        assert tree is not None and tree["status"] == status
    assert router.tracer.dropped_total == 0


# -- histograms + exemplars --------------------------------------------------


def test_histogram_cumulative_buckets_and_exemplar_escaping():
    h = Histogram("serving_ttft_hist_seconds",
                  buckets=(0.1, 1.0, 10.0))
    h.observe(0.05, trace_id="aa")
    h.observe(0.5, trace_id='evil"id\\with\nstuff')
    h.observe(0.7)          # no exemplar: bucket keeps the last one
    h.observe(99.0, trace_id="ff")  # overflow bucket
    text = h.render()
    lines = text.splitlines()
    assert "# TYPE serving_ttft_hist_seconds histogram" in lines[0]
    bucket_lines = [
        ln for ln in lines if "_bucket" in ln]
    counts = [int(ln.split("} ")[1].split(" #")[0])
              for ln in bucket_lines]
    assert counts == [1, 3, 3, 4]  # cumulative, +Inf last
    assert 'le="+Inf"' in bucket_lines[-1]
    # the escaped exemplar survives on its bucket's line
    assert 'trace_id="evil\\"id\\\\with\\nstuff"' in bucket_lines[1]
    assert "\n".join(lines).count("# {trace_id=") == 3
    assert "serving_ttft_hist_seconds_count 4" in text
    # sum parses back
    [sum_line] = [ln for ln in lines if "_sum" in ln]
    assert abs(float(sum_line.split()[-1]) - 100.25) < 1e-9


def test_histograms_on_metrics_scrape_resolve_to_traces():
    """The Grafana drill-down contract: /metrics serves the latency
    histograms with trace_id exemplars, and every exemplar's trace_id
    resolves through the tracer (and thus /traces)."""
    import re

    router = _local_router()
    reqs = [router.submit(_prompt(i), 8) for i in range(3)]
    router.run_until_idle()
    exporter = MetricsExporter()
    exporter.attach_router(router)
    exporter.start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{exporter.port}/metrics",
            timeout=5).read().decode()
        for family in ("serving_ttft_hist_seconds",
                       "serving_queue_wait_seconds",
                       "serving_e2e_latency_seconds"):
            assert f"# TYPE {family} histogram" in body, family
            assert f"{family}_count 3" in body, family
        # this engine streams nothing in process: each answer is one
        # delivery, and a gap needs two
        assert "# TYPE serving_token_gap_seconds histogram" in body
        assert "serving_token_gap_seconds_count 0" in body
        exemplar_ids = set(re.findall(r'# \{trace_id="([0-9a-f]{32})"\}',
                                      body))
        assert exemplar_ids
        assert exemplar_ids <= {r.trace.trace_id for r in reqs}
        for tid in exemplar_ids:
            assert router.tracer.get_tree(tid) is not None
    finally:
        exporter.stop()


# -- chrome-trace export ------------------------------------------------------


def _assert_trace_events_schema(events):
    assert events, "export must hold events"
    for e in events:
        for key in ("name", "ph", "ts", "dur", "pid", "tid"):
            assert key in e, e
        assert e["ph"] in ("X", "M")
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)


def test_chrome_export_schema_and_pid_mapping():
    router = _local_router()
    reqs = [router.submit(_prompt(i), 8) for i in range(2)]
    router.run_until_idle()
    doc = json.loads(router.tracer.export_chrome_trace())
    events = doc["traceEvents"]
    _assert_trace_events_schema(events)
    spans = [e for e in events if e["ph"] == "X"]
    # concurrent requests land on distinct tid rows; all spans carry
    # their trace_id in args for cross-referencing with /traces
    assert len({e["tid"] for e in spans}) == 2
    assert {e["args"]["trace_id"] for e in spans} == \
        {r.trace.trace_id for r in reqs}
    # single-trace export narrows to that request
    one = json.loads(router.tracer.export_chrome_trace(
        reqs[0].trace.trace_id))["traceEvents"]
    assert {e["args"]["trace_id"] for e in one
            if e["ph"] == "X"} == {reqs[0].trace.trace_id}
    # process-name metadata names the router process
    meta = [e for e in events if e["ph"] == "M"]
    assert any(e["args"]["name"] == "router" for e in meta)


def test_traces_chrome_endpoint_serves_and_404s():
    router = _local_router()
    req = router.submit(_prompt(1), 8)
    router.run_until_idle()
    exporter = MetricsExporter()
    exporter.attach_tracer(router.tracer)
    exporter.start()
    try:
        base = f"http://127.0.0.1:{exporter.port}"
        doc = json.loads(urllib.request.urlopen(
            f"{base}/traces/chrome?trace_id={req.trace.trace_id}",
            timeout=5).read().decode())
        _assert_trace_events_schema(doc["traceEvents"])
        # no trace_id: the whole ring exports
        doc = json.loads(urllib.request.urlopen(
            f"{base}/traces/chrome", timeout=5).read().decode())
        _assert_trace_events_schema(doc["traceEvents"])
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(
                f"{base}/traces/chrome?trace_id={'0' * 32}", timeout=5)
        assert e.value.code == 404
    finally:
        exporter.stop()


def test_traces_autoscale_endpoint_serves_named_traces():
    tracer = Tracer(sample_rate=0.0)  # control plane ignores the knob
    root = tracer.start_trace(
        "autoscale", now=1.0, always_sample=True,
        current=1, desired=2, direction="up")
    tracer.start_span(root, "scale_plan", now=1.0).finish(1.0)
    exporter = MetricsExporter()
    exporter.attach_tracer(tracer)
    exporter.start()
    try:
        body = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{exporter.port}/traces/autoscale",
            timeout=5).read().decode())
        # active (still-open) control-plane traces are visible
        assert len(body["traces"]) == 1
        assert body["traces"][0]["status"] == "active"
        tracer.finish_trace(root, now=2.0, status="ok")
        body = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{exporter.port}/traces/autoscale",
            timeout=5).read().decode())
        assert body["traces"][0]["status"] == "ok"
        assert "scale_plan" in _names(body["traces"][0])
    finally:
        exporter.stop()


def test_tracing_hot_path_is_lock_clean():
    """The DL003 acceptance line, executed: dlint over the tracing hot
    path (tracer + gateway/router/scheduler/replica) must stay clean —
    no blocking work under router/gateway locks."""
    from dlrover_tpu.dlint.checkers import CHECKERS, DlintConfig, Project
    from dlrover_tpu.dlint.core import ParsedModule

    paths = [
        "dlrover_tpu/utils/tracing.py",
        "dlrover_tpu/serving/router/gateway.py",
        "dlrover_tpu/serving/router/router.py",
        "dlrover_tpu/serving/router/scheduler.py",
        "dlrover_tpu/serving/router/replica.py",
    ]
    modules = []
    for p in paths:
        with open(p, encoding="utf-8") as f:
            modules.append(ParsedModule(p, p, f.read()))
    project = Project(modules, DlintConfig())
    dl003 = [c for c in CHECKERS if c.CODE == "DL003"][0]
    violations = list(dl003.check_project(project))
    assert violations == [], [str(v) for v in violations]
