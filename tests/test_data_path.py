"""Worker data path: sharding client, elastic sampler/dataloader, and the
end-to-end example (launcher + master sharding + flash-ckpt resume after a
mid-run worker kill) — reference test models:
dlrover/python/tests/test_sharding_client.py and
dlrover/trainer/tests/torch/elastic_sampler_test.py."""

import json
import os
import subprocess
import sys
import time
import uuid

import numpy as np
import pytest

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.sharding.client import (
    IndexShardingClient,
    ShardingClient,
)
from dlrover_tpu.trainer.elastic.dataloader import ElasticDataLoader
from dlrover_tpu.trainer.elastic.sampler import ElasticDistributedSampler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------- sampler
def test_sampler_deals_indices_across_replicas():
    s0 = ElasticDistributedSampler(10, num_replicas=2, rank=0, shuffle=False)
    s1 = ElasticDistributedSampler(10, num_replicas=2, rank=1, shuffle=False)
    assert list(s0) == [0, 2, 4, 6, 8]
    assert list(s1) == [1, 3, 5, 7, 9]


def test_sampler_state_resume_across_world_change():
    """Mid-epoch state resumes on a different replica count without
    repeating or losing samples (reference: sampler.py:118-140)."""
    s = ElasticDistributedSampler(12, num_replicas=2, rank=0, shuffle=False)
    s.record_batch_done(6)  # 3 global batches of 2 consumed
    state = s.state_dict()

    resumed = [
        ElasticDistributedSampler(12, num_replicas=3, rank=r, shuffle=False)
        for r in range(3)
    ]
    for r in resumed:
        r.load_state_dict(state)
    remaining = sorted(i for r in resumed for i in r)
    assert remaining == [6, 7, 8, 9, 10, 11]


def test_sampler_shuffle_is_deterministic_per_epoch():
    a = ElasticDistributedSampler(32, num_replicas=1, rank=0, seed=5)
    b = ElasticDistributedSampler(32, num_replicas=1, rank=0, seed=5)
    a.set_epoch(2), b.set_epoch(2)
    assert list(a) == list(b)
    b.set_epoch(3)
    assert list(a) != list(b)


def test_dataloader_with_sampler_batches():
    data = [{"x": np.array([i, i + 1])} for i in range(8)]
    sampler = ElasticDistributedSampler(8, 1, 0, shuffle=False)
    dl = ElasticDataLoader(data, batch_size=4, sampler=sampler)
    batches = list(dl)
    assert len(batches) == 2
    np.testing.assert_array_equal(batches[0]["x"][:, 0], [0, 1, 2, 3])


# -------------------------------------------------------- sharding client
def test_sharding_client_failed_ack_stays_retryable():
    """The master ack runs OUTSIDE the client lock now (dlint DL007:
    it's a gRPC round trip) — but the pop-then-report split must not
    lose the old report-then-clear retry semantics: a transient RPC
    failure re-installs the task at its budget boundary so the next
    report_* call retries the ack instead of silently dropping it."""
    from dlrover_tpu.common import comm

    class FlakyClient:
        def __init__(self):
            self.acked = []
            self.fail_next = 0

        def report_task_result(self, dataset_name, task_id):
            if self.fail_next > 0:
                self.fail_next -= 1
                raise ConnectionError("master restarting")
            self.acked.append(task_id)

    client = FlakyClient()
    sc = ShardingClient(client, "ds0", batch_size=2,
                        num_minibatches_per_shard=2)
    sc._current_task = comm.Task(task_id=7, shard=None)
    client.fail_next = 1
    with pytest.raises(ConnectionError):
        sc.report_batch_done(2)
    # the failed ack left the task current: the very next report
    # crosses the restored budget boundary and retries
    assert client.acked == []
    assert sc._current_task is not None
    sc.report_batch_done(1)
    assert client.acked == [7]
    assert sc._current_task is None
    # an explicit shard-done retry works the same way
    sc._current_task = comm.Task(task_id=8, shard=None)
    client.fail_next = 1
    with pytest.raises(ConnectionError):
        sc.report_shard_done()
    sc.report_shard_done()
    assert client.acked == [7, 8]


def test_index_sharding_client_midloop_ack_failure_is_retried():
    """IndexShardingClient acks popped FIFO heads OUTSIDE the lock
    (dlint DL007) — but the FIFO already advanced past them, so a
    mid-loop RPC failure must stash the failed and not-yet-reported ids
    and retry them at the head of the next call, not silently drop acks
    the master still waits on (it would re-serve those shards)."""
    from dlrover_tpu.common import comm

    class FlakyClient:
        def __init__(self):
            self.acked = []
            self.fail_on = set()

        def get_task(self, dataset_name):
            return comm.Task(task_id=-1, shard=None)  # exhausted at once

        def report_task_result(self, dataset_name, task_id):
            if task_id in self.fail_on:
                self.fail_on.discard(task_id)
                raise ConnectionError("master restarting")
            self.acked.append(task_id)

    client = FlakyClient()
    sc = IndexShardingClient(client, "ds3", batch_size=1,
                             num_minibatches_per_shard=1)
    try:
        # three fully-consumed single-sample tasks waiting for their ack
        for tid in (1, 2, 3):
            sc._task_fifo.put((tid, 1))
        client.fail_on = {2}
        with pytest.raises(ConnectionError):
            sc.report_batch_done(3)
        # 1 was acked before the failure; 2 AND 3 are stashed, not lost
        assert client.acked == [1]
        sc.report_batch_done(0)
        assert client.acked == [1, 2, 3]
    finally:
        sc.close()


def test_sharding_client_consumes_and_acks(local_master):
    master, addr = local_master
    client = MasterClient(addr, node_id=0, node_type="worker")
    sc = ShardingClient(
        client, "ds1", batch_size=2, dataset_size=8,
        num_minibatches_per_shard=1,
    )
    seen = []
    while True:
        shard = sc.fetch_shard(timeout=10)
        if shard is None:
            break
        seen.append((shard.start, shard.end))
        sc.report_shard_done()
    assert seen == [(0, 2), (2, 4), (4, 6), (6, 8)]
    assert master.task_manager.finished()
    client.close()


def test_index_sharding_client_recovers_after_failure(local_master):
    """Indices prefetched but unconsumed at death are re-dispatched after
    the failure report (the local-master recovery path)."""
    master, addr = local_master
    c0 = MasterClient(addr, node_id=0, node_type="worker")
    sc = IndexShardingClient(
        c0, "ds2", batch_size=2, dataset_size=12,
        num_minibatches_per_shard=1, prefetch_shards=1,
    )
    got = [sc.fetch_sample_index(timeout=10) for _ in range(4)]
    assert got == [0, 1, 2, 3]
    sc.report_batch_done(2)  # only the first shard's samples were trained
    # worker 0 "dies": in-flight (fetched, unacked) shards recovered.
    # First the prefetcher comes to rest, on what it shows and not on a
    # clock: it holds three unacked shards ((2,3) dequeued, (4,5) filling
    # the index queue, (6,7) waiting for room) and asks the master for
    # nothing more until a sample is taken.  A task it were handed AFTER
    # the failure report would be a dead worker's, and lost.
    deadline = time.monotonic() + 30.0
    while not (sc._task_fifo.qsize() == 3 and sc._index_queue.full()):
        assert time.monotonic() < deadline, "the prefetcher never rested"
        time.sleep(0.01)
    c0.report_failure("killed", level="node", node_rank=0)
    sc.close()

    c1 = MasterClient(addr, node_id=1, node_type="worker")
    sc1 = IndexShardingClient(
        c1, "ds2", batch_size=2, dataset_size=0,
        num_minibatches_per_shard=1,
    )
    rest = []
    while True:
        idx = sc1.fetch_sample_index(timeout=10)
        if idx is None:
            break
        rest.append(idx)
        sc1.report_batch_done(1)
    # everything not ACKED by worker 0 arrives again (2,3 were dequeued
    # but never trained on => re-dispatched): nothing is lost
    assert set(rest) == set(range(2, 12))
    assert master.task_manager.finished()
    sc1.close()
    c0.close()
    c1.close()


# ------------------------------------------------------------------- e2e
def test_example_crash_resume_e2e(tmp_path):
    """The full story: dlrover-tpu-run launches the example; the worker is
    killed mid-run; the agent restarts it; it resumes from the in-memory
    checkpoint and the master re-dispatches lost shards (VERDICT item 5)."""
    out = tmp_path / "result.json"
    ckpt = tmp_path / "ckpt"
    env = dict(os.environ)
    env.update(
        {
            "DLROVER_JOB_UID": uuid.uuid4().hex[:8],
            "DLROVER_CRASH_AT_STEP": "3",
        }
    )
    proc = subprocess.run(
        [
            sys.executable, "-m", "dlrover_tpu.agent.launcher",
            "--nnodes=1", "--monitor-interval", "0.3",
            sys.executable, os.path.join(REPO, "examples", "train_llama.py"),
            "--steps", "8", "--global-batch", "8", "--seq-len", "64",
            "--ckpt-dir", str(ckpt), "--out-file", str(out),
            "--save-storage-interval", "5",
        ],
        env=env,
        capture_output=True,
        timeout=560,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr.decode()[-3000:]
    result = json.loads(out.read_text())
    assert result["start_step"] == 3, result  # resumed from memory
    assert result["final_step"] == 8, result
    # async disk persistence produced committed checkpoints
    assert any(p.name.startswith("step-") for p in ckpt.iterdir())


def test_device_prefetch_orders_and_places():
    """device_prefetch (reference preloader parity) preserves order and
    commits batches to the requested sharding."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from dlrover_tpu.accel.parallel.mesh import MeshSpec
    from dlrover_tpu.trainer.data.preloader import device_prefetch

    mesh = MeshSpec.for_device_count(8).build_mesh()
    sharding = NamedSharding(mesh, PartitionSpec(("dp", "fsdp")))

    def batches():
        for i in range(6):
            yield {"x": np.full((8, 4), i, np.float32)}

    got = list(device_prefetch(batches(), sharding={"x": sharding}, size=2))
    assert [int(b["x"][0, 0]) for b in got] == list(range(6))
    assert got[0]["x"].sharding == sharding

    import pytest

    with pytest.raises(ValueError):
        next(device_prefetch(batches(), size=0))
