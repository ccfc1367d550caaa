"""Observability + hang-detection tests (reference parity:
elastic_agent/monitor/resource.py:86-180, monitor/training.py:77-134,
master/stats/job_collector.py, atorch fault_tolerance/
hanging_detector.py:86, xpu_timer Prometheus export)."""

import json
import os
import sys
import threading
import time
import urllib.request

import pytest

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.monitor.hang import HangingDetector
from dlrover_tpu.agent.monitor.resource import (
    ResourceMonitor,
    sample_resource_stats,
)
from dlrover_tpu.agent.monitor.training import (
    TrainingMonitor,
    read_runtime_metrics,
    write_runtime_metrics,
)
from dlrover_tpu.master.stats.job_collector import (
    JobMetricCollector,
    LocalMetricReporter,
)
from dlrover_tpu.utils.profiler import (
    MetricsExporter,
    StepTimer,
    render_prometheus,
)


def test_sample_resource_stats():
    stats = sample_resource_stats(num_chips=4)
    assert stats.memory_mb > 0
    assert stats.tpu_chips == 4


def test_resource_monitor_reports_to_master(local_master, master_client):
    master, _ = local_master
    monitor = ResourceMonitor(master_client, interval=60)
    stats = monitor.report_once()
    assert stats.memory_mb > 0
    usage = master.job_metric_collector.node_usage
    assert "worker-0" in usage
    assert usage["worker-0"]["memory_mb"] == stats.memory_mb


def test_training_monitor_reports_global_step(
    local_master, master_client, tmp_path
):
    master, _ = local_master
    path = str(tmp_path / "metrics.json")
    write_runtime_metrics(7, elapsed_per_step=0.5, path=path)
    assert read_runtime_metrics(path)["step"] == 7

    monitor = TrainingMonitor(master_client, interval=60, path=path)
    before = monitor.last_progress_time
    time.sleep(0.01)
    assert monitor.check_once() == 7
    assert monitor.last_step == 7
    assert monitor.last_progress_time > before
    # the master saw the step (collector + speed monitor)
    assert master.job_metric_collector.steps[-1]["step"] == 7

    # no new step => no progress-time update
    stamp = monitor.last_progress_time
    monitor.check_once()
    assert monitor.last_progress_time == stamp


def test_hang_detector_fires_once():
    det = HangingDetector(
        progress_fn=lambda: 9999.0,
        timeout=10.0,
        grace_period=0.0,
        max_triggers=1,
    )
    assert det.check_once(now=100.0)
    assert not det.check_once(now=200.0)  # max_triggers reached
    det.reset()  # re-arms grace (0.0) and trigger budget
    assert det.check_once(now=time.time() + 300.0)


def test_hang_detector_respects_grace_and_progress():
    det = HangingDetector(
        progress_fn=lambda: 5.0,
        timeout=10.0,
        grace_period=1000.0,
    )
    det.arm()
    assert not det.check_once()  # inside grace
    det._armed_at = 0.0
    assert not det.check_once()  # progress below timeout


def test_training_monitor_reset_counts_resumed_step_as_progress(
    local_master, master_client, tmp_path
):
    """After a restart the trainer resumes BELOW the pre-crash step; the
    reset must drop the high-water mark so that still counts as progress."""
    path = str(tmp_path / "metrics.json")
    monitor = TrainingMonitor(master_client, interval=60, path=path)
    write_runtime_metrics(1000, path=path)
    assert monitor.check_once() == 1000
    monitor.reset_progress_clock()
    assert monitor.last_step == -1
    assert read_runtime_metrics(path) is None  # stale file dropped
    write_runtime_metrics(950, path=path)  # resumed from checkpoint
    before = monitor.last_progress_time
    time.sleep(0.01)
    assert monitor.check_once() == 950
    assert monitor.last_progress_time > before


def test_agent_restarts_on_hang(local_master, tmp_path):
    """E2e: a worker that never reports progress gets restarted, then the
    agent fails after max_restarts (reference relaunch-on-hang protocol)."""
    _, addr = local_master
    client = MasterClient(addr, node_id=0, node_type="worker")
    metrics_path = str(tmp_path / "rt_metrics.json")
    os.environ["DLROVER_RUNTIME_METRICS_PATH"] = metrics_path
    try:
        from dlrover_tpu.agent.elastic_agent import ElasticAgent, WorkerSpec

        spec = WorkerSpec(
            entrypoint=[sys.executable, "-c", "import time; time.sleep(60)"],
            monitor_interval=0.2,
            max_restarts=1,
            hang_timeout=0.5,
            hang_grace_period=0.0,
            monitors=True,
            flash_ckpt=False,
        )
        agent = ElasticAgent(client, 0, spec)
        rc = agent.run()
        assert rc == 1
        assert agent._group.restart_count == 1
    finally:
        os.environ.pop("DLROVER_RUNTIME_METRICS_PATH", None)
        client.close()


def test_job_metric_collector_speed_and_dump(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    col = JobMetricCollector(LocalMetricReporter(path))
    t0 = 1000.0
    for i in range(5):
        col.report_global_step(i * 10, t0 + i)
    assert col.training_speed() == pytest.approx(10.0)
    col.report_event("node_failed", "worker-1", "exit 9")
    col.collect_job_meta(job="test", nodes=2)
    m = col.get_job_metrics()
    assert m["global_step"] == 40
    assert m["speed_steps_per_sec"] == pytest.approx(10.0)
    assert m["recent_events"][0]["event_type"] == "node_failed"
    lines = [json.loads(x) for x in open(path)]
    kinds = {r["kind"] for r in lines}
    assert kinds == {"global_step", "event"}


def test_goodput_mark_restart_caps_bridging_interval():
    """ISSUE 9 satellite: a fast recovery hiding a kill inside one
    below-3x-median step interval must still be charged as downtime
    once the master saw the failure report (mark_restart); without the
    flag the same interval is credited fully."""
    col = JobMetricCollector(LocalMetricReporter(None))
    t = 1000.0
    for i in range(1, 9):  # steady 1s/step baseline
        col.report_global_step(i, t + i)
    base = col.goodput()["productive_s"]
    assert base == pytest.approx(7.0)
    # a kill + fast recovery: the next report arrives 2.5s later, one
    # step ahead (resume landed exactly on the crash step) — under the
    # 3x-median radar.  With the failure reported, only ~1 median step
    # of it is productive.
    col.mark_restart()
    col.report_global_step(9, t + 8 + 2.5)
    g = col.goodput()
    assert g["restarts_observed"] == 1
    assert g["productive_s"] == pytest.approx(base + 1.0)
    assert g["steady_wall_s"] - g["productive_s"] == pytest.approx(1.5)
    # the flag is consumed: the following clean interval credits fully
    col.report_global_step(10, t + 8 + 3.5)
    assert col.goodput()["productive_s"] == pytest.approx(base + 2.0)


def test_node_failure_report_marks_goodput_restart(local_master):
    """The servicer wires NodeFailure -> mark_restart + a ledger event."""
    master, addr = local_master
    from dlrover_tpu.agent.master_client import MasterClient

    client = MasterClient(addr, node_id=0, node_type="worker")
    try:
        client.report_failure("worker exit 9", level="error", node_rank=0)
        col = master.job_metric_collector
        assert col.restarts_observed == 1
        events = [e["event_type"] for e in col.get_job_metrics()[
            "recent_events"]]
        assert "node_failure" in events
    finally:
        client.close()


def test_step_timer_stats():
    t = StepTimer()
    for v in (0.1, 0.2, 0.3):
        t.observe(v)
    assert t.count == 3
    assert 0.09 < t.percentile(50) < 0.31
    m = t.metrics()
    assert m["dlrover_step_count"] == 3.0
    assert m["dlrover_step_seconds_total"] == pytest.approx(0.6)


def test_step_timer_metrics_snapshot_is_consistent_under_scrape():
    """metrics() takes ONE locked snapshot (the DL011 fix): a scrape
    racing observe() must never pair count from one step with total
    from the next.  With a constant 0.5s sample (exact in binary
    float), any torn snapshot breaks count * 0.5 == total."""
    t = StepTimer(reservoir=16)
    stop = threading.Event()

    def _observe():
        while not stop.is_set():
            t.observe(0.5)

    w = threading.Thread(target=_observe, daemon=True)
    w.start()
    try:
        for _ in range(300):
            m = t.metrics()
            assert m["dlrover_step_count"] * 0.5 == \
                m["dlrover_step_seconds_total"]
    finally:
        stop.set()
        w.join(timeout=5)


def test_metrics_exporter_serves_prometheus():
    timer = StepTimer()
    timer.observe(0.25)
    exporter = MetricsExporter(labels={"rank": "0"})
    exporter.add_source(timer.metrics)
    exporter.start()
    try:
        url = f"http://127.0.0.1:{exporter.port}/metrics"
        body = urllib.request.urlopen(url, timeout=5).read().decode()
        assert 'dlrover_step_count{rank="0"} 1.0' in body
        health = urllib.request.urlopen(
            f"http://127.0.0.1:{exporter.port}/healthz", timeout=5
        ).read()
        assert health == b"ok"
    finally:
        exporter.stop()


def test_render_prometheus_format():
    text = render_prometheus({"a_metric": 1.5}, {"node": "w0"})
    assert text == 'a_metric{node="w0"} 1.5\n'


def test_render_prometheus_escapes_label_values():
    """Exposition-format escaping: an unescaped quote/backslash/newline
    in a label value corrupts every sample after it on the scrape."""
    text = render_prometheus(
        {"a_metric": 1.0},
        {"path": 'C:\\tmp', "msg": 'say "hi"\nbye'},
    )
    assert text == (
        'a_metric{msg="say \\"hi\\"\\nbye",path="C:\\\\tmp"} 1.0\n'
    )
    assert "\n" not in text[:-1].replace("\\n", "")


def test_metrics_exporter_counts_and_logs_failing_sources():
    """A raising source must not vanish silently: it is counted into
    dlrover_metrics_source_errors_total and logged once per source
    (the 'dlrover_tpu' logger is non-propagating, so the once-per-
    source gate is asserted through the exporter's own bookkeeping)."""
    exporter = MetricsExporter()

    def bad_source():
        raise RuntimeError("boom")

    exporter.add_source(bad_source)
    exporter.add_source(lambda: {"dlrover_step_count": 1.0})
    exporter.start()
    try:
        url = f"http://127.0.0.1:{exporter.port}/metrics"
        body1 = urllib.request.urlopen(url, timeout=5).read().decode()
        body2 = urllib.request.urlopen(url, timeout=5).read().decode()
        # the healthy source still renders; the failure is visible
        assert "dlrover_step_count 1.0" in body1
        assert "dlrover_metrics_source_errors_total 1.0" in body1
        assert "dlrover_metrics_source_errors_total 2.0" in body2
        logged = [k for k in exporter._sources_logged if "bad_source" in k]
        assert len(exporter._sources_logged) == 1 and logged, \
            "log once per source, not per scrape"
    finally:
        exporter.stop()


def test_window_gauge_trims_exactly_at_boundary():
    """A sample exactly window_seconds old sits ON the cutoff and must
    be kept (strict <): off-by-one trims silently bias the mean the
    autoscaler keys off."""
    from dlrover_tpu.utils.profiler import WindowGauge

    g = WindowGauge(window_seconds=10.0)
    g.observe(1.0, now=100.0)
    g.observe(3.0, now=105.0)
    # now=110: the t=100 sample is exactly at the cutoff (110-10) -> kept
    assert g.mean(now=110.0) == pytest.approx(2.0)
    # one tick past the window: dropped
    assert g.mean(now=110.0 + 1e-6) == pytest.approx(3.0)
    # far past the window every sample ages out
    assert g.max(now=120.0) == 0.0


def test_window_gauge_empty_window_rates_and_stats_are_zero():
    from dlrover_tpu.utils.profiler import WindowGauge

    g = WindowGauge(window_seconds=5.0)
    assert g.rate() == 0.0
    assert g.mean() == 0.0
    assert g.max() == 0.0
    g.observe(10.0, now=50.0)
    assert g.rate(now=50.0) == pytest.approx(2.0)  # 10 over a 5s window
    # everything aged out: rate decays to exactly zero, not NaN
    assert g.rate(now=100.0) == 0.0
    assert g.mean(now=100.0) == 0.0


# -- topology sorter --------------------------------------------------------

def test_slice_topology_sorter_keeps_rank0_group_first():
    from dlrover_tpu.master.elastic_training.net_topology import (
        NodeTopologyMeta,
        SliceTopologySorter,
    )

    nodes = {
        0: NodeTopologyMeta(node_rank=0, slice_id=2, asw="asw-9"),
        1: NodeTopologyMeta(node_rank=1, slice_id=1, asw="asw-1"),
        2: NodeTopologyMeta(node_rank=2, slice_id=2, asw="asw-9"),
        3: NodeTopologyMeta(node_rank=3, slice_id=1, asw="asw-1"),
    }
    ordered = list(SliceTopologySorter().sort(nodes).values())
    # rank 0's (slice 2, asw-9) group leads despite higher slice id
    assert [n.node_rank for n in ordered] == [0, 2, 1, 3]
    # groups are contiguous
    assert [n.slice_id for n in ordered] == [2, 2, 1, 1]


# ---------------------------------------------------------------------------
# xprof auto-profiling (reference xpu_timer: transparent per-kernel /
# per-collective timing -> Prometheus, atorch/dev/xpu_timer/nvidia/hook.cc)
# ---------------------------------------------------------------------------


def test_profile_call_reduces_by_scope(monkeypatch, fresh_compiles):
    """A registered program's captured step comes back as self seconds
    by its own device scopes, and the parts add up to the device time."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.utils import profiler
    from dlrover_tpu.utils.xprof_metrics import profile_call, total_seconds

    monkeypatch.setattr(profiler, "_PROGRAMS", profiler.ProgramRegistry())

    @jax.jit
    def f(a, b):
        with profiler.device_scope("mlp"):
            return (a @ b).sum()

    x = jnp.ones((128, 128))
    # compile outside the trace, and not from the persistent cache
    # (``fresh_compiles``): an entry another tree wrote would carry that
    # tree's scopes
    f(x, x).block_until_ready()
    shapes = profiler.abstract((x, x))
    profiler.register_program(
        "f", lambda: profiler.program_texts(f, *shapes))
    result, bd = profile_call(lambda: f(x, x))
    assert float(result) != 0.0
    assert bd["device_seconds"] > 0
    assert bd["device_seconds"] == pytest.approx(
        total_seconds(bd["programs"]))
    assert bd["programs"]["f"]["scopes"]["mlp"] > 0


def test_profile_call_times_collectives():
    """A psum under shard_map must land in the collectives table —
    the per-collective timing xpu_timer provides."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from dlrover_tpu.utils.xprof_metrics import profile_call

    mesh = Mesh(jax.devices(), ("dp",))

    @jax.jit
    def step(x):
        f = jax.shard_map(lambda v: jax.lax.psum(v @ v, "dp"), mesh=mesh,
                          in_specs=P("dp"), out_specs=P())
        return f(x).sum()

    x = jnp.ones((8 * 32, 32))
    step(x).block_until_ready()
    _, bd = profile_call(lambda: step(x))
    assert bd["collectives"], bd["programs"]
    assert sum(bd["collectives"].values()) > 0
    assert sum(bd["collectives"].values()) <= bd["device_seconds"]


def test_a_nested_while_is_not_counted_twice():
    """The old gauges summed durations: a ``while`` counted its body
    again.  Self time: the loop keeps what its body does not cover, and
    the gauges' total is the time the device was busy."""
    from dlrover_tpu.utils.profiler import ProgramTable
    from dlrover_tpu.utils.xprof_metrics import (
        collective_seconds, join, self_times, total_seconds)

    ops = [["while.1", 0.0, 1000.0],            # the scan over layers
           ["while.2", 100.0, 800.0],           # a loop inside its body
           ["fusion.3", 100.0, 300.0], ["all-reduce.4", 400.0, 500.0],
           ["fusion.5", 1000.0, 200.0]]
    assert {n: s for n, _, s in self_times(ops)} == {
        "while.1": 200.0, "while.2": 0.0, "fusion.3": 300.0,
        "all-reduce.4": 500.0, "fusion.5": 200.0}
    devices = {"/device:TPU:0": {
        "ops": ops, "modules": [["jit__train_step(1)", 0.0, 1200.0]]}}
    table = ProgramTable("train_step", "jit__train_step", {
        "while.1": "loss_and_grad", "while.2": "loss_and_grad",
        "fusion.3": "mlp", "all-reduce.4": "mlp", "fusion.5": "optimizer"})
    programs = join(devices, {"train_step": table})
    assert total_seconds(programs) == pytest.approx(1200e-9)   # not 2800
    assert programs["train_step"]["scopes"] == pytest.approx(
        {"loss_and_grad": 200e-9, "mlp": 800e-9, "optimizer": 200e-9})
    assert collective_seconds(devices) == pytest.approx(
        {"all-reduce.4": 500e-9})


def test_auto_profiler_every_n_and_prometheus_text():
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.utils.xprof_metrics import AutoProfiler

    f = jax.jit(lambda a: (a * 2).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    prof = AutoProfiler(every_n=3, warmup_steps=1)
    for _ in range(4):  # steps 1(warmup) 2 3 4: capture on step 4
        prof.around_step(lambda: f(x))
    assert prof.profile_count == 1
    assert prof.breakdown is not None
    text = prof.prometheus_text()
    assert "dlrover_xprof_profiles_total 1.0" in text
    assert "dlrover_xprof_device_seconds" in text
    # nobody registered ``f``: its time is there, under no scope's name
    assert 'dlrover_xprof_scope_seconds{scope="(other programs)"}' in text
    assert "dlrover_xprof_op_seconds" not in text


def test_elastic_trainer_xprof_endpoint():
    """Zero-instrumentation wiring: a normal train loop with
    xprof_every_n_steps exposes device time by the step's own scopes on
    /metrics."""
    import urllib.request

    import jax
    import numpy as np

    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.trainer.elastic.trainer import ElasticTrainer

    cfg = LlamaConfig.tiny(max_seq_len=16)
    tr = ElasticTrainer(
        LlamaModel(cfg), global_batch_size=8, micro_batch_per_shard=1,
        seq_len=16, xprof_every_n_steps=2, metrics_port=0,
    )
    try:
        tr.prepare()
        tr.restore_or_init(jax.random.PRNGKey(0))
        batch = np.ones((8, 16), np.int32)
        for _ in range(5):
            tr.train_step(batch)
        assert tr.auto_profiler.profile_count >= 1
        url = f"http://127.0.0.1:{tr.metrics_exporter.port}/metrics"
        body = urllib.request.urlopen(url, timeout=5).read().decode()
        assert "dlrover_step_count" in body
        assert "dlrover_xprof_scope_seconds{scope=" in body
        assert "dlrover_xprof_device_seconds" in body
    finally:
        tr.close()


# -- goodput (reference README.md:54-57: useful-new-step time / wall) -------


def test_goodput_healthy_run_approaches_one():
    c = JobMetricCollector()
    c.mark_job_start(timestamp=100.0)
    # first step lands after 2s of compile (downtime), then 10 steps
    # at 1s each — goodput = 10 / 12
    for i in range(11):
        c.report_global_step(i + 1, 102.0 + i)
    g = c.goodput()
    assert g["wall_s"] == pytest.approx(12.0)
    assert g["productive_s"] == pytest.approx(10.0)
    assert g["goodput"] == pytest.approx(10.0 / 12.0)


def test_goodput_counts_fault_and_rollback_as_downtime():
    """A kill at step 8 that rolls back to a step-5 checkpoint: the gap,
    the recompile, AND the re-run of steps 6-8 all earn nothing — only
    never-before-completed steps are credited."""
    c = JobMetricCollector()
    c.mark_job_start(timestamp=0.0)
    for i in range(1, 9):  # steps 1..8, 1s each, first at t=1
        c.report_global_step(i, float(i))
    # fault: 10s of detection + restart + recompile; resume at step 6
    c.report_global_step(6, 18.0)   # rollback report: no credit
    c.report_global_step(7, 19.0)   # re-done: no credit
    c.report_global_step(8, 20.0)   # re-done: no credit
    c.report_global_step(9, 21.0)   # NEW step: credited
    c.report_global_step(10, 22.0)
    g = c.goodput()
    # productive: steps 2..8 (7s; step 1's interval is from job start,
    # prev=None so uncredited) + steps 9,10 (2s)
    assert g["productive_s"] == pytest.approx(9.0)
    assert g["wall_s"] == pytest.approx(22.0)
    assert g["downtime_s"] == pytest.approx(13.0)
    assert g["goodput"] == pytest.approx(9.0 / 22.0)


def test_goodput_credits_partial_interval_across_rollback_point():
    """A sparse report window straddling the rollback point credits only
    the fraction covering new steps."""
    c = JobMetricCollector()
    c.mark_job_start(timestamp=0.0)
    c.report_global_step(4, 4.0)
    c.report_global_step(8, 8.0)    # steps 5-8 credited (4s)
    c.report_global_step(6, 20.0)   # post-restart resume: no credit
    # one 4s window covering steps 7..10: 8 already credited, so only
    # steps 9,10 count -> half the interval
    c.report_global_step(10, 24.0)
    g = c.goodput()
    assert g["productive_s"] == pytest.approx(4.0 + 2.0)
    assert g["goodput"] == pytest.approx(6.0 / 24.0)


def test_goodput_in_job_metrics_and_detail_rpc(local_master, master_client):
    """The goodput breakdown rides get_job_metrics and the job-detail
    RPC so any client (and the e2e artifact) can read it."""
    master, _ = local_master
    col = master.job_metric_collector
    now = time.time()
    col.report_global_step(1, now - 3.0)
    col.report_global_step(5, now)
    metrics = master_client.query_job_detail().get("metrics", {})
    assert "goodput" in metrics
    assert metrics["goodput"]["productive_s"] == pytest.approx(3.0, abs=0.1)
    assert 0.0 < metrics["goodput"]["goodput"] <= 1.0


def test_goodput_caps_windows_hiding_a_restart():
    """A sparse sampling window that spans a crash+recovery but still
    shows net step progress must not credit the recovery gap: new steps
    are credited at the typical per-step rate instead."""
    c = JobMetricCollector()
    c.mark_job_start(timestamp=0.0)
    for i in range(1, 6):  # steps 1..5, 1s cadence
        c.report_global_step(i, float(i))
    # window 5 -> 6 took 14s: a crash + restart hid inside it
    c.report_global_step(6, 19.0)
    g = c.goodput()
    # steps 2..5 credited fully (4s); step 6 at the 1s median, not 14s
    assert g["productive_s"] == pytest.approx(5.0)
    assert g["downtime_s"] == pytest.approx(19.0 - 5.0)


# -- ISSUE 12: /traces query filtering + the master metrics endpoint ---------


def _traced_router():
    import numpy as np

    from dlrover_tpu.serving.remote.worker import FakeEngine
    from dlrover_tpu.serving.router import (
        ContinuousBatchScheduler,
        RouterMetrics,
        ServingRouter,
    )

    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4),
        metrics=RouterMetrics(window_seconds=0.5),
    )
    router.join_replica(
        "r0", FakeEngine(slots=8, tokens_per_step=8, blocks=100000))
    t = time.monotonic()
    for i in range(6):
        router.submit(np.full(8, i % 251, "int32"), 8, now=t)
    # one request that can only expire: deadline already passed
    router.submit(np.full(8, 3, "int32"), 8, timeout=-1.0, now=t)
    router.run_until_idle()
    return router


def test_traces_endpoint_query_filters():
    """/traces and /traces/slowest take ?name= / ?status= / ?limit= —
    mid-incident "the failover traces, newest 20" must be one query,
    not a 4096-entry dump."""
    router = _traced_router()
    exporter = MetricsExporter()
    exporter.attach_router(router)
    exporter.start()
    try:
        base = f"http://127.0.0.1:{exporter.port}"

        def get(path):
            return json.loads(urllib.request.urlopen(
                base + path, timeout=5).read())

        everything = get("/traces")["traces"]
        assert len(everything) == 7
        limited = get("/traces?limit=3")["traces"]
        assert len(limited) == 3
        ok_only = get("/traces?status=ok")["traces"]
        assert len(ok_only) == 6
        assert all(t["status"] == "ok" for t in ok_only)
        timed_out = get("/traces?status=TimedOut")["traces"]
        assert len(timed_out) == 1
        named = get("/traces?name=request&limit=500")["traces"]
        assert len(named) == 7
        assert get("/traces?name=autoscale")["traces"] == []
        slowest = get("/traces/slowest?limit=2&status=ok")["traces"]
        assert len(slowest) == 2
        assert all(t["status"] == "ok" for t in slowest)
        assert slowest[0]["duration_s"] >= slowest[1]["duration_s"]
        # a bad limit degrades to the default instead of erroring
        assert len(get("/traces?limit=bogus")["traces"]) == 7
    finally:
        exporter.stop()


def test_tracer_filters_direct():
    from dlrover_tpu.utils.tracing import Tracer

    tracer = Tracer()
    for i, (name, status) in enumerate(
            [("request", "ok"), ("request", "failover"),
             ("autoscale", "ok")]):
        root = tracer.start_trace(name, rid=i)
        tracer.finish_trace(root, status=status)
    assert len(tracer.finished(name="request")) == 2
    assert len(tracer.finished(status="failover")) == 1
    assert len(tracer.slowest(name="autoscale")) == 1
    assert tracer.finished(name="request", status="ok")[0][
        "status"] == "ok"


def test_master_metrics_endpoint_serves_goodput_ledger(capsys):
    """The ISSUE-12 satellite: the master serves /metrics (port-0 +
    stdout announce) exposing the goodput ledger + rendezvous
    counters with registry help text — scrapeable, not
    JSON-artifact-only."""
    from dlrover_tpu.common.constants import NodeEnv, RendezvousName
    from dlrover_tpu.master.dist_master import DistributedJobMaster
    from dlrover_tpu.scheduler.in_memory import (
        InMemoryCluster,
        InMemoryNodeWatcher,
        InMemoryScaler,
    )

    cluster = InMemoryCluster()
    master = DistributedJobMaster(
        0, scaler=InMemoryScaler(cluster),
        watcher=InMemoryNodeWatcher(cluster), node_num=1)
    col = master.job_metric_collector
    col.mark_job_start(timestamp=time.time() - 10.0)
    col.report_global_step(1, time.time() - 8.0)
    col.report_global_step(5, time.time() - 1.0)
    rdzv = master.rdzv_managers[RendezvousName.ELASTIC_TRAINING]
    rdzv.update_rdzv_params(min_nodes=1, max_nodes=1,
                            waiting_timeout=5, node_unit=1)
    rdzv.join_rendezvous(0, 0, 1)
    rdzv.get_comm_world(0)
    port = master.start_metrics_exporter(0)
    try:
        announced = capsys.readouterr().out
        assert f"{NodeEnv.MASTER_METRICS_ANNOUNCE_PREFIX}{port}" \
            in announced
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ).read().decode()
        assert "dlrover_master_goodput " in body
        assert "# HELP dlrover_master_goodput" in body
        assert "dlrover_master_rendezvous_rounds_total 1.0" in body
        assert "dlrover_master_world_size 1.0" in body
        assert "dlrover_master_restarts_observed_total 0.0" in body
        m = master.master_metrics()
        assert 0.0 < m["dlrover_master_goodput"] <= 1.0
        assert m["dlrover_master_downtime_seconds_total"] >= 0.0
    finally:
        master.stop_metrics_exporter()
