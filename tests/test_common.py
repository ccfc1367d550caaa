"""Tests of the common layer: serialization, IPC, storage, node model."""

import os
import queue
import threading
import time

import pytest

from dlrover_tpu.common import comm
from dlrover_tpu.common.constants import NodeStatus
from dlrover_tpu.common.multi_process import (
    SharedDict,
    SharedLock,
    SharedMemory,
    SharedQueue,
)
from dlrover_tpu.common.node import Node, NodeResource
from dlrover_tpu.common.serialize import (
    deserialize_message,
    serialize_message,
)
from dlrover_tpu.common.storage import (
    KeepLatestStepStrategy,
    PosixDiskStorage,
)


class TestSerialize:
    def test_roundtrip_nested(self):
        task = comm.Task(
            task_id=3,
            task_type="training",
            shard=comm.Shard(name="ds", start=0, end=10,
                             record_indices=[1, 2, 3]),
        )
        data = serialize_message(task)
        out = deserialize_message(data)
        assert isinstance(out, comm.Task)
        assert out.shard.record_indices == [1, 2, 3]
        assert out.shard.name == "ds"

    def test_envelope(self):
        inner = comm.GlobalStep(step=7, timestamp=1.5)
        req = comm.BaseRequest(
            node_id=1, node_type="worker", data=serialize_message(inner)
        )
        out = deserialize_message(serialize_message(req))
        step = deserialize_message(out.data)
        assert step.step == 7

    def test_dict_with_int_keys(self):
        reply = comm.CommWorldReply(
            round=1, world={0: 8, 2: 8}, node_ips={0: "a", 2: "b"},
            node_ports={0: 40001, 2: 40002},
        )
        out = deserialize_message(serialize_message(reply))
        assert out.world == {0: 8, 2: 8}
        assert out.node_ports == {0: 40001, 2: 40002}

    def test_bytes_payload(self):
        kv = comm.KeyValuePair(key="k", value=b"\x00\x01\xff")
        out = deserialize_message(serialize_message(kv))
        assert out.value == b"\x00\x01\xff"


class TestIPC:
    def test_shared_queue(self):
        server = SharedQueue("tq", create=True)
        client = SharedQueue("tq", create=False)
        client.put({"a": 1})
        item = server.get(timeout=5)
        assert item == {"a": 1}
        assert client.empty()
        server.close()

    def test_shared_queue_timeout(self):
        server = SharedQueue("tq2", create=True)
        client = SharedQueue("tq2", create=False)
        with pytest.raises(queue.Empty):
            client.get(block=False)
        server.close()

    def test_shared_lock(self):
        server = SharedLock("tl", create=True)
        client = SharedLock("tl", create=False)
        assert client.acquire()
        assert not client.acquire(blocking=False)
        assert client.release()
        assert not server.locked()
        server.close()

    def test_shared_dict(self):
        server = SharedDict("td", create=True)
        client = SharedDict("td", create=False)
        client.set({"x": 1, "y": [1, 2]})
        assert server.get() == {"x": 1, "y": [1, 2]}
        client.set({"x": 2})
        assert server.get()["x"] == 2
        server.close()

    def test_shared_memory(self):
        name = f"dlrtest_{os.getpid()}"
        shm = SharedMemory(name=name, create=True, size=1024)
        shm.buf[:4] = b"abcd"
        shm2 = SharedMemory(name=name)
        assert bytes(shm2.buf[:4]) == b"abcd"
        shm2.close()
        shm.close()
        shm.unlink()


class TestStorage:
    def test_write_read(self, tmp_path):
        storage = PosixDiskStorage()
        p = str(tmp_path / "a" / "f.txt")
        storage.write("hello", p)
        assert storage.read(p) == "hello"
        storage.write(b"\x01", p + ".bin")
        assert storage.read(p + ".bin", "rb") == b"\x01"

    def test_keep_latest(self, tmp_path):
        ckpt_dir = str(tmp_path)
        for step in [10, 20, 30, 40]:
            os.makedirs(os.path.join(ckpt_dir, str(step)))
        strategy = KeepLatestStepStrategy(2, ckpt_dir)
        storage = PosixDiskStorage(strategy)
        storage.commit(40, True)
        remaining = sorted(os.listdir(ckpt_dir))
        assert remaining == ["30", "40"]


class TestNode:
    def test_resource_parse(self):
        res = NodeResource.resource_str_to_node_resource(
            "cpu=4,memory=1024,tpu=8"
        )
        assert res.cpu == 4 and res.memory == 1024 and res.tpu_chips == 8

    def test_relaunch_policy(self):
        node = Node("worker", 0, max_relaunch_count=2)
        assert node.should_relaunch()
        node.inc_relaunch_count()
        node.inc_relaunch_count()
        assert not node.should_relaunch()

    def test_status_updates(self):
        node = Node("worker", 0)
        node.update_status(NodeStatus.RUNNING)
        assert node.start_time is not None
        node.update_status(NodeStatus.SUCCEEDED)
        assert node.is_exited()


class TestRpcStubHygiene:
    def test_close_releases_channel_fds(self):
        """RpcStub.close() must close the underlying gRPC channel —
        marking _closed without releasing the channel leaks its sockets
        and poller fds on every stub close."""
        grpc = pytest.importorskip(
            "grpc", reason="control-plane RPC needs grpcio")
        if not os.path.isdir("/proc/self/fd"):
            pytest.skip("no /proc fd table on this platform")
        from dlrover_tpu.common.rpc import RpcStub, build_server

        server = build_server(lambda b, ctx: b, lambda b, ctx: b)
        port = server.add_insecure_port("127.0.0.1:0")
        server.start()

        def fds():
            return len(os.listdir("/proc/self/fd"))

        try:
            # warm gRPC's lazily-created global state (pollers, logs) so
            # the measurement below only sees per-stub resources
            warm = RpcStub(f"127.0.0.1:{port}")
            assert warm.get(b"ping") == b"ping"
            warm.close()
            time.sleep(0.2)
            base = fds()

            stubs = [RpcStub(f"127.0.0.1:{port}") for _ in range(5)]
            for stub in stubs:
                assert stub.get(b"x") == b"x"
            assert fds() > base, "live channels must hold fds"
            for stub in stubs:
                stub.close()
                stub.close()  # idempotent
                assert stub.closed
            # channel teardown is asynchronous inside grpc; poll briefly
            deadline = time.monotonic() + 5.0
            while fds() > base + 1 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert fds() <= base + 1, (
                f"fds leaked: {fds()} open vs baseline {base}")
        finally:
            server.stop(0)
