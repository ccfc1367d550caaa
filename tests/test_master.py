"""Tests of master components: sharding, rendezvous, kv-store, servicer
+ MasterClient against an in-process master (the reference's key test
pattern — reference: dlrover/python/tests/test_rdzv_manager.py etc.)."""

import time

import pytest

from dlrover_tpu.common.constants import RendezvousName
from dlrover_tpu.master.elastic_training.kv_store_service import (
    KVStoreService,
)
from dlrover_tpu.master.elastic_training.rdzv_manager import (
    ElasticTrainingRendezvousManager,
    NetworkCheckRendezvousManager,
)
from dlrover_tpu.master.shard.dataset_splitter import (
    StreamingDatasetSplitter,
    TableDatasetSplitter,
    TextDatasetSplitter,
    new_dataset_splitter,
)
from dlrover_tpu.master.shard.task_manager import TaskManager


class TestSplitters:
    def test_table_splitter(self):
        sp = TableDatasetSplitter("d", dataset_size=103, shard_size=10)
        assert sp.create_shards()
        shards = sp.get_shards()
        assert len(shards) == 11
        assert shards[-1].end == 103
        assert not sp.create_shards()  # single epoch

    def test_text_splitter_shuffle(self):
        sp = TextDatasetSplitter(
            "d", dataset_size=20, shard_size=6, shuffle=True
        )
        sp.create_shards()
        shards = sp.get_shards()
        all_indices = sorted(
            i for s in shards for i in s.record_indices
        )
        assert all_indices == list(range(20))

    def test_streaming_checkpoint(self):
        sp = StreamingDatasetSplitter(
            "d", dataset_size=100, shard_size=10, fetch_data_size=30
        )
        sp.create_shards()
        ckpt = sp.to_checkpoint()
        sp2 = StreamingDatasetSplitter.from_checkpoint(ckpt)
        assert sp2._offset == 30
        sp2.create_shards()
        assert sp2.get_shards()[0].start == 30


class TestTaskManager:
    def _make(self, size=40, batch=2, epochs=1):
        tm = TaskManager()
        tm.new_dataset(
            batch_size=batch,
            dataset_size=size,
            dataset_name="ds",
            num_epochs=epochs,
            num_minibatches_per_shard=2,
        )
        return tm

    def test_dispatch_and_complete(self):
        tm = self._make()
        seen = []
        while True:
            task = tm.get_dataset_task(0, "ds")
            if task.task_id < 0:
                break
            seen.append((task.shard.start, task.shard.end))
            tm.report_dataset_task("ds", task.task_id, True)
        assert seen[0] == (0, 4)
        assert tm.finished()

    def test_recover_failed_worker_tasks(self):
        tm = self._make()
        t1 = tm.get_dataset_task(0, "ds")
        t2 = tm.get_dataset_task(1, "ds")
        tm.recover_tasks(0)
        # worker 0's shard is back in todo; next get returns it first
        t3 = tm.get_dataset_task(2, "ds")
        assert (t3.shard.start, t3.shard.end) == (
            t1.shard.start, t1.shard.end,
        )
        assert t2.task_id in tm.get_dataset("ds").doing

    def test_dataset_checkpoint_roundtrip(self):
        tm = self._make()
        t1 = tm.get_dataset_task(0, "ds")
        tm.report_dataset_task("ds", t1.task_id, True)
        tm.get_dataset_task(0, "ds")  # leave one doing
        ckpt = tm.get_dataset_checkpoint("ds")
        tm2 = self._make()
        tm2.restore_dataset_from_checkpoint("ds", ckpt)
        ds = tm2.get_dataset("ds")
        # doing task went back to todo
        starts = {t.shard.start for t in ds.todo}
        assert t1.shard.start not in starts or len(ds.todo) > 0
        total = 0
        while True:
            task = tm2.get_dataset_task(0, "ds")
            if task.task_id < 0:
                break
            total += task.shard.end - task.shard.start
            tm2.report_dataset_task("ds", task.task_id, True)
        assert total == 40 - 4  # completed shard not replayed


class TestElasticRendezvous:
    def test_basic_round(self):
        mgr = ElasticTrainingRendezvousManager()
        mgr.update_rdzv_params(2, 2, waiting_timeout=0.2)
        mgr.join_rendezvous(0, 0, 8)
        r, g, world = mgr.get_comm_world(0)
        assert world == {}  # not complete yet
        mgr.join_rendezvous(1, 1, 8)
        r, g, world = mgr.get_comm_world(0)
        assert set(world.keys()) == {0, 1}
        assert mgr.rdzv_round == 1

    def test_min_nodes_timeout(self):
        mgr = ElasticTrainingRendezvousManager()
        mgr.update_rdzv_params(1, 4, waiting_timeout=0.2)
        mgr.join_rendezvous(0, 0, 8)
        # a lone first joiner must NOT instantly form a singleton world
        # (staggered startup would diverge into per-node worlds); it
        # completes after the last-call window
        _, _, world = mgr.get_comm_world(0)
        assert world == {}
        time.sleep(0.25)
        _, _, world = mgr.get_comm_world(0)
        assert set(world.keys()) == {0}

    def test_node_unit_rounding(self):
        mgr = ElasticTrainingRendezvousManager()
        mgr.update_rdzv_params(2, 8, waiting_timeout=0.1, node_unit=2)
        for i in range(3):
            mgr.join_rendezvous(i, i, 4)
        time.sleep(0.15)
        # 3 nodes but node_unit=2 -> only 2 admitted
        mgr._alive_nodes.update({10, 11, 12, 13, 14})  # alive > waiting
        _, _, world = mgr.get_comm_world(0)
        assert len(world) == 2
        # the leftover node alone cannot grow a unit-2 world: reporting it
        # as waiting would make agents restart for a rendezvous that cannot
        # enlarge the world (restart churn)
        assert mgr.num_nodes_waiting() == 0
        # ... but once a 4th node arrives the pair is admissible
        mgr.join_rendezvous(3, 3, 4)
        assert mgr.num_nodes_waiting() == 2

    def test_slice_aware_admission_drops_incomplete_slice(self):
        """Losing one member of a slice drops the WHOLE slice from the
        world (broken ICI domain); the other slice trains on — and the
        slice is re-admitted when a replacement member joins (reference
        rdzv_manager.py:291-343 node-loss-at-scale)."""
        mgr = ElasticTrainingRendezvousManager()
        mgr.update_rdzv_params(2, 4, waiting_timeout=0.1, node_unit=2)
        # slice 0 complete (ranks 0,1); slice 1 broken (only rank 2 —
        # rank 3's host died before joining)
        mgr.join_rendezvous(0, 0, 2, slice_id=0)
        mgr.join_rendezvous(1, 1, 2, slice_id=0)
        mgr.join_rendezvous(2, 2, 2, slice_id=1)
        time.sleep(0.15)
        _, _, world = mgr.get_comm_world(0)
        assert set(world.keys()) == {0, 1}, world  # only the whole slice
        assert world[0].slice_id == 0 and world[1].slice_id == 0
        # rank 2 was NOT admitted and must re-join the next round
        _, _, w2 = mgr.get_comm_world(2)
        assert 2 not in w2
        # replacement for the dead host arrives: slice 1 is complete
        # again and the world can grow back to both slices
        mgr.join_rendezvous(3, 3, 2, slice_id=1)
        assert mgr.num_nodes_waiting() == 2
        # members re-join (agent restart on growth) -> 4-node world
        mgr.join_rendezvous(0, 0, 2, slice_id=0)
        mgr.join_rendezvous(1, 1, 2, slice_id=0)
        mgr.join_rendezvous(2, 2, 2, slice_id=1)
        _, _, world = mgr.get_comm_world(0)
        assert set(world.keys()) == {0, 1, 2, 3}

    def test_zero_admit_keeps_waiting(self):
        # fewer waiting nodes than node_unit: must NOT complete with an
        # empty world or inflate the round counter
        mgr = ElasticTrainingRendezvousManager()
        mgr.update_rdzv_params(1, 8, waiting_timeout=0.05, node_unit=4)
        mgr.join_rendezvous(0, 0, 4)
        mgr.join_rendezvous(1, 1, 4)
        time.sleep(0.1)
        for _ in range(3):
            _, _, world = mgr.get_comm_world(0)
        assert world == {}
        assert mgr.rdzv_round == 0
        assert mgr.num_nodes_waiting() == 2
        # two more nodes arrive -> full unit admitted after last-call
        mgr.join_rendezvous(2, 2, 4)
        mgr.join_rendezvous(3, 3, 4)
        time.sleep(0.1)
        _, _, world = mgr.get_comm_world(0)
        assert len(world) == 4

    def test_membership_growth_waiting(self):
        mgr = ElasticTrainingRendezvousManager()
        mgr.update_rdzv_params(2, 4, waiting_timeout=0.1)
        mgr.join_rendezvous(0, 0, 8)
        mgr.join_rendezvous(1, 1, 8)
        time.sleep(0.15)  # below max_nodes: last-call window applies
        mgr.get_comm_world(0)
        assert mgr.num_nodes_waiting() == 0
        # a new node joins -> agents see waiting>0 and restart workers
        mgr.join_rendezvous(2, 2, 8)
        assert mgr.num_nodes_waiting() == 1


class TestNetworkCheck:
    def test_fault_localization(self):
        mgr = NetworkCheckRendezvousManager()
        mgr.update_rdzv_params(4, 4, waiting_timeout=0.1)
        for i in range(4):
            mgr.join_rendezvous(i, i, 8)
        _, g0, world0 = mgr.get_comm_world(0)
        assert len(world0) == 2
        # round 1: nodes 2,3 (pair [2,3]) report failure
        for i in range(4):
            mgr.report_network_check_result(i, i < 2, 1.0)
        faults, _ = mgr.check_fault_node()
        assert faults == [2, 3]
        # round 2: re-pair each suspect with a good node
        for i in range(4):
            mgr.join_rendezvous(i, i, 8)
        _, _, w2 = mgr.get_comm_world(2)
        assert any(r < 2 for r in w2)  # 2 now paired with a good node
        # only node 3 fails again -> node 3 is faulty
        mgr.report_network_check_result(2, True, 1.0)
        mgr.report_network_check_result(3, False, 1.0)
        mgr.report_network_check_result(0, True, 1.0)
        mgr.report_network_check_result(1, True, 1.0)
        faults, _ = mgr.check_fault_node()
        assert faults == [3]

    def test_straggler_median(self):
        mgr = NetworkCheckRendezvousManager()
        mgr.update_rdzv_params(4, 4, waiting_timeout=0.1)
        for i in range(4):
            mgr.join_rendezvous(i, i, 8)
        mgr.get_comm_world(0)
        times = [1.0, 1.1, 1.0, 5.0]
        for i, t in enumerate(times):
            mgr.report_network_check_result(i, True, t)
        stragglers, _ = mgr.check_straggler()
        assert stragglers == [3]


class TestKVStore:
    def test_set_get_add(self):
        kv = KVStoreService()
        kv.set("a", b"1")
        assert kv.get("a") == b"1"
        assert kv.add("cnt", 5) == 5
        assert kv.add("cnt", 2) == 7
        assert kv.get("missing") == b""

    def test_wait(self):
        kv = KVStoreService()
        import threading

        def setter():
            time.sleep(0.1)
            kv.set("k", b"v")

        threading.Thread(target=setter).start()
        assert kv.wait(["k"], timeout=2)
        assert not kv.wait(["nope"], timeout=0.2)


class TestServicerEndToEnd:
    def test_sharding_via_rpc(self, master_client):
        master_client.report_dataset_shard_params(
            batch_size=4,
            num_epochs=1,
            dataset_size=16,
            shuffle=False,
            num_minibatches_per_shard=1,
            dataset_name="mnist",
        )
        task = master_client.get_task("mnist")
        assert task.task_id >= 0
        assert (task.shard.start, task.shard.end) == (0, 4)
        master_client.report_task_result("mnist", task.task_id)
        while True:
            t = master_client.get_task("mnist")
            if t.task_id < 0:
                break
            master_client.report_task_result("mnist", t.task_id)
        assert master_client.dataset_finished()

    def test_rendezvous_via_rpc(self, master_client):
        rdzv_round = master_client.join_rendezvous(0, 8)
        assert rdzv_round == 0
        r, g, world, ips, ports = master_client.get_comm_world(
            RendezvousName.ELASTIC_TRAINING, 0
        )
        assert world == {0: 8}
        # the port this join offered comes back with the node's address
        assert set(ports) == set(ips) == {0} and ports[0] > 0

    def test_kv_via_rpc(self, master_client):
        master_client.kv_store_set("key1", b"hello")
        assert master_client.kv_store_get("key1") == b"hello"
        assert master_client.kv_store_add("ctr", 3) == 3
        master_client.kv_store_multi_set(["a", "b"], [b"1", b"2"])
        assert master_client.kv_store_multi_get(["a", "b"]) == [b"1", b"2"]
        assert master_client.kv_store_wait(["a"], timeout=2)

    def test_step_and_heartbeat_via_rpc(self, master_client, local_master):
        master, _ = local_master
        master_client.report_global_step(10)
        master_client.report_global_step(20)
        assert master.speed_monitor.completed_global_step == 20
        action = master_client.report_heart_beat()
        assert action == ""

    def test_finished_job_waits_for_the_agents_final_report(
            self, master_client, local_master, monkeypatch):
        """After the last dataset task the master stays until every
        agent that reported RUNNING has reported how it ended (a worker
        still wraps up; a master gone by then costs its agent a whole
        retry deadline per call) — plain clients, which report no node
        status, do not hold it, and a killed agent holds it for
        AGENT_WRAPUP_SECONDS at most."""
        import threading

        from dlrover_tpu.common.constants import NodeStatus
        from dlrover_tpu.master import local_master as lm

        master, _ = local_master
        assert master.agents_ended()          # nobody reported anything
        master_client.report_node_status(0, NodeStatus.RUNNING)
        assert not master.agents_ended()
        master_client.report_dataset_shard_params(
            batch_size=4, num_epochs=1, dataset_size=4, shuffle=False,
            num_minibatches_per_shard=1, dataset_name="d")
        master_client.report_task_result(
            "d", master_client.get_task("d").task_id)
        assert master.task_manager.finished()
        t = threading.Thread(target=master.run, daemon=True)
        t.start()
        t.join(3.0)
        assert t.is_alive(), "the master left a running agent behind"
        master_client.report_node_status(0, NodeStatus.SUCCEEDED)
        t.join(5.0)
        assert not t.is_alive()
        # an agent that never reports its end: bounded by the wrap-up
        master_client.report_node_status(0, NodeStatus.RUNNING)
        monkeypatch.setattr(lm, "AGENT_WRAPUP_SECONDS", 0.5)
        t = threading.Thread(target=master.run, daemon=True)
        t.start()
        t.join(8.0)
        assert not t.is_alive()

    def test_barrier_via_rpc(self, master_client):
        assert not master_client.barrier("ckpt")
        assert master_client.barrier("ckpt", notify=True)
        assert master_client.barrier("ckpt")

    def test_network_check_via_rpc(self, master_client):
        master_client.join_rendezvous(
            0, 8, rdzv_name=RendezvousName.NETWORK_CHECK
        )
        r, g, world, _, ports = master_client.get_comm_world(
            RendezvousName.NETWORK_CHECK, 0
        )
        assert ports[0] > 0  # the check's world gets its port the same way
        assert world == {0: 8}
        master_client.report_network_check_result(0, True, 0.5)
        ok, reason = master_client.network_check_success()
        assert ok
