"""Elastic agent tests: in-process master + real RPC + real subprocess
workers (the reference's testing pattern, reference:
dlrover/python/tests/test_elastic_training_agent.py:51-206)."""

import os
import sys
import threading
import time

import pytest

from dlrover_tpu.agent.elastic_agent import (
    ElasticAgent,
    MasterRendezvousHandler,
    WorkerSpec,
)
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.common.rpc import find_free_port
from dlrover_tpu.master.local_master import LocalJobMaster


@pytest.fixture()
def master2():
    port = find_free_port()
    master = LocalJobMaster(port, node_num=2)
    master.prepare()
    yield master, f"127.0.0.1:{port}"
    master.stop()


def _client(addr, rank):
    return MasterClient(addr, node_id=rank, node_type="worker")


def test_single_node_worker_success(local_master):
    _, addr = local_master
    client = _client(addr, 0)
    spec = WorkerSpec(
        entrypoint=[sys.executable, "-c", "print('worker ok')"],
        monitor_interval=0.3,
    )
    agent = ElasticAgent(client, 0, spec)
    assert agent.run() == 0
    client.close()


def test_restart_on_worker_failure(local_master, tmp_path):
    _, addr = local_master
    client = _client(addr, 0)
    flag = tmp_path / "attempted"
    # fails on the first attempt, succeeds on the second
    script = (
        "import os, sys, pathlib\n"
        f"p = pathlib.Path({str(flag)!r})\n"
        "if p.exists():\n"
        "    sys.exit(0)\n"
        "p.write_text('1')\n"
        "sys.exit(3)\n"
    )
    spec = WorkerSpec(
        entrypoint=[sys.executable, "-c", script],
        monitor_interval=0.3,
        max_restarts=2,
    )
    agent = ElasticAgent(client, 0, spec)
    assert agent.run() == 0
    assert agent._group.restart_count == 1
    client.close()


def test_exhausted_restarts_fail(local_master):
    _, addr = local_master
    client = _client(addr, 0)
    spec = WorkerSpec(
        entrypoint=[sys.executable, "-c", "import sys; sys.exit(7)"],
        monitor_interval=0.2,
        max_restarts=1,
    )
    agent = ElasticAgent(client, 0, spec)
    assert agent.run() == 7
    client.close()


def test_two_node_rendezvous_and_env(master2, tmp_path):
    _, addr = master2
    out0, out1 = tmp_path / "w0", tmp_path / "w1"
    script = (
        "import os\n"
        "path = os.environ['OUT_PATH']\n"
        "open(path, 'w').write(\n"
        "    os.environ['DLROVER_NODE_NUM'] + ' ' +\n"
        "    os.environ['DLROVER_WORKER_RANK'] + ' ' +\n"
        "    os.environ['DLROVER_COORDINATOR_ADDR'])\n"
    )
    results = {}

    def run_agent(rank, out):
        client = _client(addr, rank)
        spec = WorkerSpec(
            entrypoint=[sys.executable, "-c", script],
            monitor_interval=0.3,
            env={"OUT_PATH": str(out)},
            # two agents in one process share a job uid: with the saver
            # factory on, both bind ONE queue socket (unlink, then bind),
            # and the loser of that race dies with EADDRINUSE
            flash_ckpt=False,
        )
        agent = ElasticAgent(client, rank, spec)
        results[rank] = agent.run()
        client.close()

    t0 = threading.Thread(target=run_agent, args=(0, out0))
    t1 = threading.Thread(target=run_agent, args=(1, out1))
    t0.start(); t1.start()
    t0.join(60); t1.join(60)
    assert results == {0: 0, 1: 0}
    n0, r0, c0 = out0.read_text().split()
    n1, r1, c1 = out1.read_text().split()
    assert (n0, n1) == ("2", "2")
    assert sorted([r0, r1]) == ["0", "1"]
    assert c0 == c1  # same coordinator on both hosts


def test_two_jobs_on_one_host_get_a_coordinator_each(tmp_path):
    """Two jobs of two nodes rendezvous on this host at the same time.
    Within a job every agent hands its workers the SAME
    ``DLROVER_COORDINATOR_ADDR``; across the jobs the ports DIFFER (one
    ``jax.distributed`` service a world: workers of two jobs that dial
    one service kill each other as "a different incarnation"); and a
    job's second round has a port that is not its first round's."""
    script = (
        "import os\n"
        "open(os.environ['OUT_PATH'], 'w').write(\n"
        "    os.environ['DLROVER_COORDINATOR_ADDR'])\n"
    )
    masters = [LocalJobMaster(0, node_num=2) for _ in range(2)]
    for m in masters:
        m.prepare()
    addrs = [f"127.0.0.1:{m.port}" for m in masters]
    results = {}

    def run_agent(job, rank):
        client = _client(addrs[job], rank)
        spec = WorkerSpec(
            entrypoint=[sys.executable, "-c", script],
            monitor_interval=0.3,
            env={"OUT_PATH": str(tmp_path / f"j{job}r{rank}")},
            flash_ckpt=False,  # as above: agents in one process
        )
        results[job, rank] = ElasticAgent(client, rank, spec).run()
        client.close()

    second = {}

    def rejoin(rank):
        client = _client(addrs[0], rank)
        second[rank] = MasterRendezvousHandler(
            client, rank, timeout=30
        ).next_rendezvous()
        client.close()

    def run_all(target, keys):
        threads = [threading.Thread(target=target, args=k) for k in keys]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)

    try:
        run_all(run_agent, [(j, r) for j in range(2) for r in range(2)])
        assert results == {(j, r): 0 for j in range(2) for r in range(2)}
        seen = [
            [(tmp_path / f"j{j}r{r}").read_text() for r in range(2)]
            for j in range(2)
        ]
        assert seen[0][0] == seen[0][1] and seen[1][0] == seen[1][1]
        assert seen[0][0] != seen[1][0]  # a service a job
        run_all(rejoin, [(0,), (1,)])
    finally:
        for m in masters:
            m.stop()
    assert second[0].round == second[1].round == 2
    assert second[0].coordinator == second[1].coordinator
    assert second[0].coordinator != seen[0][0]  # a fresh port a round


def test_two_node_network_check(master2):
    """Both hosts pass the grouped check (cross-host collective over a
    jax.distributed group world on CPU) and proceed to training."""
    _, addr = master2
    results = {}

    def run_agent(rank):
        client = _client(addr, rank)
        spec = WorkerSpec(
            entrypoint=[sys.executable, "-c", "print('ok')"],
            monitor_interval=0.3,
            network_check=True,
            flash_ckpt=False,   # as above
        )
        agent = ElasticAgent(client, rank, spec)
        results[rank] = agent.run()
        client.close()

    t0 = threading.Thread(target=run_agent, args=(0,))
    t1 = threading.Thread(target=run_agent, args=(1,))
    t0.start(); t1.start()
    t0.join(240); t1.join(240)
    assert results == {0: 0, 1: 0}


def test_membership_change_triggers_restart(master2, tmp_path):
    """Agent 0 runs alone (min_nodes=1); when agent 1 joins, agent 0 must
    restart its worker into the 2-node world (reference: training.py:708)."""
    _, addr = master2
    setup = _client(addr, 0)
    setup.report_rdzv_params(1, 2, waiting_timeout=1.0, node_unit=1)

    # solo rounds run "forever" (killed by the membership restart); the
    # 2-node round finishes quickly so both agents can succeed.
    script = (
        "import os, time\n"
        "n = os.environ['DLROVER_NODE_NUM']\n"
        "tag = os.environ['DLROVER_RDZV_ROUND']\n"
        "open(os.environ['OUT_DIR'] + '/round_' + tag, 'w').write(n)\n"
        "time.sleep(2 if n == '2' else 300)\n"
    )
    results = {}

    def run_agent(rank):
        client = _client(addr, rank)
        spec = WorkerSpec(
            entrypoint=[sys.executable, "-c", script],
            monitor_interval=0.3,
            env={"OUT_DIR": str(tmp_path)},
            flash_ckpt=False,   # as above
        )
        agent = ElasticAgent(client, rank, spec)
        results[rank] = agent.run()
        client.close()

    t0 = threading.Thread(target=run_agent, args=(0,))
    t0.start()
    # wait until agent 0's solo round has spawned a worker
    deadline = time.time() + 30
    while time.time() < deadline and not list(tmp_path.glob("round_*")):
        time.sleep(0.2)
    solo = {p.name: p.read_text() for p in tmp_path.glob("round_*")}
    assert solo, "agent 0 never spawned a solo worker"
    assert "1" in solo.values()

    t1 = threading.Thread(target=run_agent, args=(1,))
    t1.start()
    t0.join(90); t1.join(90)
    assert results == {0: 0, 1: 0}
    rounds = {p.name: p.read_text() for p in tmp_path.glob("round_*")}
    assert "2" in rounds.values(), f"no 2-node round observed: {rounds}"
    setup.close()


def test_exclude_straggler_leaves_job(local_master):
    """A host flagged straggler by the check rounds exits for replacement
    when exclusion is enabled (reference: dlrover-run --exclude-straggler)."""
    import sys

    from dlrover_tpu.agent.elastic_agent import ElasticAgent, WorkerSpec
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.common.constants import RendezvousName

    master, addr = local_master
    client = MasterClient(addr, node_id=0, node_type="worker")
    # seed the check rendezvous so the median rule flags rank 0: its
    # round took >2x the median of its peers
    mgr = master.rdzv_managers[RendezvousName.NETWORK_CHECK]
    mgr._rdzv_nodes = {0: 1, 1: 1, 2: 1}
    mgr._node_times = {0: 30.0, 1: 2.0, 2: 2.0}
    try:
        stragglers, _ = client.check_straggler()
        assert stragglers == [0]
        # full agent path: the real check round would overwrite the
        # seeded timings, so pin the straggler verdict at the client and
        # assert the agent leaves without ever spawning workers
        client.check_straggler = lambda: ([0], "")
        reported = []
        orig_report = client.report_failure
        client.report_failure = lambda *a, **k: (
            reported.append(k.get("level")), orig_report(*a, **k))[1]
        spec = WorkerSpec(
            entrypoint=[sys.executable, "-c", "print('nope')"],
            monitor_interval=0.2,
            network_check=True,
            exclude_straggler=True,
            flash_ckpt=False,
            monitors=False,
        )
        agent = ElasticAgent(client, 0, spec)
        rc = agent.run()
        assert rc == 1  # left the job for replacement
        # specifically via the straggler path, not a failed check:
        assert "straggler" in reported, reported
        assert agent._group.procs == []  # never spawned workers
    finally:
        client.close()


def test_two_node_check_with_mismatched_comm_perf_flags(master2):
    """One agent requests comm perf, its peer does not: the group-wide
    agreement vote must let BOTH pass the check instead of stranding the
    flag-enabled host in a blocking collective until timeout."""
    _, addr = master2
    results = {}

    def run_agent(rank, comm_perf):
        client = _client(addr, rank)
        spec = WorkerSpec(
            entrypoint=[sys.executable, "-c", "print('ok')"],
            monitor_interval=0.3,
            network_check=True,
            comm_perf_test=comm_perf,
            flash_ckpt=False,
            monitors=False,
        )
        agent = ElasticAgent(client, rank, spec)
        results[rank] = agent.run()
        client.close()

    t0 = threading.Thread(target=run_agent, args=(0, True))
    t1 = threading.Thread(target=run_agent, args=(1, False))
    t0.start(); t1.start()
    t0.join(240); t1.join(240)
    assert results == {0: 0, 1: 0}, results


def test_agent_metrics_exporter_serves_counters_over_http(local_master):
    """ISSUE 11 satellite: the agent's dlrover_agent_* self-healing
    counters (and, when a saver lives in the process, the agent-side
    dlrover_ckpt_* persistence counters) are scrapable over HTTP with
    the metric registry's help text — no more dict-only metrics."""
    import urllib.request

    _, addr = local_master
    client = _client(addr, 0)
    spec = WorkerSpec(
        entrypoint=[sys.executable, "-c", "print('ok')"],
        monitor_interval=0.3,
    )
    agent = ElasticAgent(client, 0, spec)
    port = agent.start_metrics_exporter(0)
    try:
        agent._count("dlrover_agent_restarts_total")
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ).read().decode()
        assert "dlrover_agent_restarts_total 1.0" in body
        assert "dlrover_agent_master_outages_total" in body
        assert "dlrover_agent_rendezvous_rejoins_total" in body
        # registry help text reaches the scraper
        assert "# HELP dlrover_agent_restarts_total" in body
        # health endpoint rides along
        ok = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=5
        ).read()
        assert ok == b"ok"
    finally:
        agent.stop_metrics_exporter()
        client.close()


def test_agent_side_saver_metrics_contract():
    """AsyncCheckpointSaver.metrics() speaks the metric-source
    contract (plain name -> float) with registry-declared names, so
    the agent exporter can merge it directly."""
    from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
    from dlrover_tpu.utils.metric_registry import METRIC_HELP

    import uuid as _uuid

    os.environ["DLROVER_JOB_UID"] = _uuid.uuid4().hex[:8]
    saver = AsyncCheckpointSaver("/tmp/_dlrover_saver_metrics_test")
    try:
        m = saver.metrics()
        assert m["dlrover_ckpt_persists_total"] == 0.0
        assert m["dlrover_ckpt_last_persisted_step"] == -1.0
        for name in m:
            assert name in METRIC_HELP, name
    finally:
        for h in saver._shm_handlers:
            h.close()
        for lk in saver._shm_locks:
            lk.close()
        saver._event_queue.close()
