"""Remote coworker data service (reference coworker_data_service.py /
coworker_dataset.py): CPU-side preprocessing served over gRPC, pulled by
workers with prefetch, failover, and dynamic discovery."""

import numpy as np
import pytest

from dlrover_tpu.trainer.data.coworker_service import (
    CoworkerDataService,
    RemoteBatchIterator,
    discover_coworkers,
)


def _batches(n, base=0):
    for i in range(n):
        yield {"x": np.full((2, 3), base + i, np.float32),
               "i": np.array([base + i])}


def test_single_coworker_round_trip():
    svc = CoworkerDataService(_batches(5), get_timeout_s=2.0)
    svc.start()
    try:
        it = RemoteBatchIterator([f"127.0.0.1:{svc.port}"], prefetch=2)
        got = sorted(int(b["i"][0]) for b in it)
        assert got == [0, 1, 2, 3, 4]
        it.close()
    finally:
        svc.stop()


def test_two_coworkers_merge_streams():
    a = CoworkerDataService(_batches(3, base=0))
    b = CoworkerDataService(_batches(3, base=100))
    a.start(); b.start()
    try:
        it = RemoteBatchIterator(
            [f"127.0.0.1:{a.port}", f"127.0.0.1:{b.port}"]
        )
        got = sorted(int(x["i"][0]) for x in it)
        assert got == [0, 1, 2, 100, 101, 102]
        it.close()
    finally:
        a.stop(); b.stop()


def test_dead_coworker_excluded():
    """A dead address doesn't block the stream; live coworkers carry it."""
    live = CoworkerDataService(_batches(4))
    live.start()
    dead = CoworkerDataService(_batches(1))  # never started
    try:
        it = RemoteBatchIterator(
            [f"127.0.0.1:{dead.port}", f"127.0.0.1:{live.port}"],
            rpc_timeout_s=1.0, max_failures=2,
        )
        got = []
        # dead coworker never reports END; pull the live stream's items
        for _ in range(4):
            got.append(int(next(it)["i"][0]))
        assert sorted(got) == [0, 1, 2, 3]
        it.close()
    finally:
        live.stop()


def test_discovery_via_master_kv(local_master):
    from dlrover_tpu.agent.master_client import MasterClient

    _, addr = local_master
    client = MasterClient(addr, node_id=0, node_type="worker")
    svc = CoworkerDataService(_batches(2))
    svc.start()
    try:
        svc.register(client, "cw0")
        addrs = discover_coworkers(client, ["cw0", "missing"])
        assert len(addrs) == 1 and addrs[0].endswith(f":{svc.port}")
        # worker consumes via discovery-refresh only (no static addrs)
        it = RemoteBatchIterator(
            [], refresh_fn=lambda: [f"127.0.0.1:{svc.port}"],
            refresh_interval_s=0.1,
        )
        vals = sorted(int(b["i"][0]) for b in it)
        assert vals == [0, 1]
        it.close()
    finally:
        svc.stop()


def test_all_dead_terminates_without_refresh():
    """Every coworker excluded + no refresh_fn => clean StopIteration,
    not a hang."""
    dead = CoworkerDataService(_batches(1))  # never started
    it = RemoteBatchIterator(
        [f"127.0.0.1:{dead.port}"], rpc_timeout_s=0.5, max_failures=1,
    )
    with pytest.raises(StopIteration):
        next(it)
    it.close()


def test_producer_error_raises_not_clean_end():
    """A broken input pipeline surfaces as RuntimeError on the worker,
    not as a silently short epoch."""

    def bad_iter():
        yield {"x": np.zeros(2, np.float32)}
        raise IOError("bad shard")

    svc = CoworkerDataService(bad_iter(), get_timeout_s=1.0)
    svc.start()
    try:
        it = RemoteBatchIterator([f"127.0.0.1:{svc.port}"])
        next(it)  # the good batch
        with pytest.raises(RuntimeError, match="pipeline failed"):
            while True:
                next(it)
    finally:
        it.close()
        svc.stop()


def test_excluded_coworker_rejoins_after_refresh():
    """A restarted coworker at a previously-excluded address serves again
    once the refresh re-announces it."""
    from dlrover_tpu.common.rpc import find_free_port

    port = find_free_port()
    addr = f"127.0.0.1:{port}"
    # nothing listens yet: first contacts are refused at once and
    # exclude the address.  (A service bound but not started would HOLD
    # those calls to their deadline, and the one in flight when it
    # starts can take a batch whose reply comes too late: the batch is
    # lost, and the test counted one of two.)
    it = RemoteBatchIterator(
        [addr], rpc_timeout_s=5.0, max_failures=1,
        refresh_fn=lambda: [addr], refresh_interval_s=0.2,
    )
    import time as _t
    _t.sleep(1.0)  # let it fail + exclude
    svc = CoworkerDataService(_batches(2), port=port)
    svc.start()    # "restart" the coworker
    got = sorted(int(b["i"][0]) for b in it)
    assert got == [0, 1]
    it.close()
    svc.stop()
