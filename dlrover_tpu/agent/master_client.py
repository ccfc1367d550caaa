"""Typed client of the job master RPC service.

Counterpart of reference
dlrover/python/elastic_agent/master_client.py:28-443: every call wraps the
get/report envelope with retries; one singleton client per process.
"""

import os
import socket
import threading
import time
import uuid
from functools import wraps
from typing import Dict, List, Optional, Tuple

from dlrover_tpu.common import comm
from dlrover_tpu.common.constants import NodeEnv, RendezvousName, TaskType
from dlrover_tpu.common.retry import RetryPolicy
from dlrover_tpu.common.rpc import RpcStub, find_free_port
from dlrover_tpu.common.serialize import (
    deserialize_message,
    serialize_message,
)


def retry_rpc(retry: int = 10, interval: float = 3.0,
              policy: Optional[RetryPolicy] = None):
    """Wrap a master RPC in a :class:`~dlrover_tpu.common.retry.
    RetryPolicy`: typed (only transport-level errors retry — a served
    failure response raises immediately), exponential + jittered
    (never a fixed-interval knock on a restarting master), bounded by
    a total deadline of ``retry * interval`` seconds, and logged once
    per state change rather than once per attempt.  ``interval`` keeps
    its historical meaning as the budget unit: the backoff starts at a
    quarter of it and caps at twice it, so a blip recovers faster than
    before while a real outage backs off harder."""

    def decorator(func):
        pol = policy if policy is not None else RetryPolicy(
            max_attempts=retry,
            backoff_base=max(0.1, interval / 4.0),
            backoff_max=interval * 2.0,
            deadline=retry * interval,
        )

        @wraps(func)
        def wrapped(self, *args, **kwargs):
            return pol.call(func, self, *args,
                            what=func.__name__, **kwargs)

        wrapped.retry_policy = pol  # introspection/test seam
        return wrapped

    return decorator


class MasterClient:
    _instance: Optional["MasterClient"] = None
    _instance_lock = threading.Lock()

    def __init__(self, master_addr: str, node_id: int, node_type: str,
                 timeout: float = 30.0, fault_schedule=None):
        self._master_addr = master_addr
        self._node_id = node_id
        self._node_type = node_type
        # wait_for_ready: riding out a master restart is this client's
        # CONTRACT (retry_rpc's whole point) — an attempt issued into
        # the outage waits on the reconnecting channel instead of
        # burning the retry budget replaying a cached UNAVAILABLE
        self._stub = RpcStub(master_addr, timeout=timeout,
                             wait_for_ready=True)
        if fault_schedule is not None:
            # chaos seam (ISSUE 9): interpose the training control plane
            # the same way the serving fabric's Brain client is — every
            # get/report passes the seeded schedule, so rendezvous,
            # heartbeat and task RPCs face injected outages in tests
            from dlrover_tpu.serving.remote.faults import FaultyRpcStub

            self._stub = FaultyRpcStub(self._stub, fault_schedule)
        self._host_name = socket.gethostname()
        try:
            self._host_ip = socket.gethostbyname(self._host_name)
        except OSError:
            self._host_ip = "127.0.0.1"

    # ---------------------------------------------------------- envelope
    def _get(self, message, timeout: float = 0):
        req = comm.BaseRequest(
            node_id=self._node_id,
            node_type=self._node_type,
            data=serialize_message(message),
        )
        resp_bytes = self._stub.get(serialize_message(req), timeout=timeout)
        resp: comm.BaseResponse = deserialize_message(resp_bytes)
        if not resp.success:
            raise RuntimeError(resp.message or "master get failed")
        return deserialize_message(resp.data)

    def _report(self, message, timeout: float = 0):
        req = comm.BaseRequest(
            node_id=self._node_id,
            node_type=self._node_type,
            data=serialize_message(message),
        )
        resp_bytes = self._stub.report(
            serialize_message(req), timeout=timeout
        )
        resp: comm.BaseResponse = deserialize_message(resp_bytes)
        if not resp.success:
            raise RuntimeError(resp.message or "master report failed")
        return deserialize_message(resp.data)

    # -------------------------------------------------------------- tasks
    @retry_rpc()
    def get_task(self, dataset_name: str) -> comm.Task:
        return self._get(comm.TaskRequest(dataset_name=dataset_name))

    @retry_rpc()
    def report_task_result(
        self, dataset_name: str, task_id: int, err_message: str = ""
    ):
        return self._report(
            comm.TaskResult(
                dataset_name=dataset_name,
                task_id=task_id,
                err_message=err_message,
            )
        )

    @retry_rpc()
    def report_dataset_shard_params(
        self,
        batch_size: int,
        num_epochs: int,
        dataset_size: int,
        shuffle: bool,
        num_minibatches_per_shard: int,
        dataset_name: str,
        task_type: str = TaskType.TRAINING,
        storage_type: str = "table",
    ):
        return self._report(
            comm.DatasetShardParams(
                batch_size=batch_size,
                num_epochs=num_epochs,
                dataset_size=dataset_size,
                shuffle=shuffle,
                num_minibatches_per_shard=num_minibatches_per_shard,
                dataset_name=dataset_name,
                task_type=task_type,
                storage_type=storage_type,
            )
        )

    @retry_rpc()
    def get_shard_checkpoint(self, dataset_name: str) -> str:
        reply = self._get(
            comm.ShardCheckpointRequest(dataset_name=dataset_name)
        )
        return reply.content

    @retry_rpc()
    def report_shard_checkpoint(self, content: str):
        return self._report(comm.ShardCheckpoint(content=content))

    @retry_rpc()
    def dataset_finished(self) -> bool:
        reply = self._get(comm.TaskStatus())
        return reply.finished

    # --------------------------------------------------------- rendezvous
    @retry_rpc()
    def join_rendezvous(
        self,
        node_rank: int,
        local_world_size: int,
        rdzv_name: str = RendezvousName.ELASTIC_TRAINING,
        node_unit: int = 1,
        slice_id: int = 0,
    ) -> int:
        # A port free on this host NOW, for the round's jax.distributed
        # service should this node come out as its lowest rank: a new
        # one every join, so no two jobs on a host and no two rounds of
        # a job dial one service, and TIME_WAIT is moot.  A pre-pick,
        # where every server of this package binds port 0 itself and
        # announces: this binder is a worker process, which cannot report
        # a port back before its peers dial it.  A port lost in between
        # fails the workers' start, and the agent's restart joins again
        # with a new one.
        # dlint: disable=DL001 the binder is jax.distributed inside a worker process, which cannot announce before its peers dial
        node_port = find_free_port()
        reply = self._get(
            comm.JoinRendezvousRequest(
                node_id=self._node_id,
                node_rank=node_rank,
                local_world_size=local_world_size,
                rdzv_name=rdzv_name,
                node_unit=node_unit,
                slice_id=slice_id,
                node_ip=self._host_ip,
                node_port=node_port,
            )
        )
        return reply.round

    @retry_rpc()
    def get_comm_world(
        self, rdzv_name: str, node_rank: int
    ) -> Tuple[int, int, Dict[int, int], Dict[int, str], Dict[int, int]]:
        reply = self._get(
            comm.CommWorldRequest(
                node_id=self._node_id,
                node_rank=node_rank,
                rdzv_name=rdzv_name,
            )
        )
        return (reply.round, reply.group, reply.world, reply.node_ips,
                reply.node_ports)

    @retry_rpc()
    def rendezvous_joined(
        self, node_rank: int,
        rdzv_name: str = RendezvousName.ELASTIC_TRAINING,
    ) -> bool:
        """Whether this node is still registered (waiting or admitted)
        with the master's rendezvous — False after a master restart
        wiped its state, which tells the handler to re-join instead of
        polling an empty world to its timeout."""
        reply = self._get(
            comm.RendezvousJoinedRequest(
                node_rank=node_rank, rdzv_name=rdzv_name
            )
        )
        return reply.joined

    @retry_rpc()
    def num_nodes_waiting(
        self, rdzv_name: str = RendezvousName.ELASTIC_TRAINING
    ) -> int:
        reply = self._get(
            comm.WaitingNodeNumRequest(
                node_id=self._node_id, rdzv_name=rdzv_name
            )
        )
        return reply.waiting_num

    @retry_rpc()
    def report_rdzv_params(
        self,
        min_nodes: int,
        max_nodes: int,
        waiting_timeout: float = 30.0,
        node_unit: int = 1,
        join_timeout: float = 600.0,
    ):
        return self._report(
            comm.RendezvousParamsReport(
                min_nodes=min_nodes,
                max_nodes=max_nodes,
                waiting_timeout=waiting_timeout,
                node_unit=node_unit,
                join_timeout=join_timeout,
            )
        )

    @retry_rpc()
    def report_network_check_result(
        self, node_rank: int, normal: bool, elapsed_time: float
    ):
        return self._report(
            comm.NetworkCheckResult(
                node_rank=node_rank,
                normal=normal,
                elapsed_time=elapsed_time,
            )
        )

    @retry_rpc()
    def network_check_success(self) -> Tuple[bool, str]:
        reply = self._get(comm.NetworkStatusRequest())
        return reply.normal, reply.reason

    @retry_rpc()
    def check_fault_node(self) -> Tuple[List[int], str]:
        reply = self._get(comm.FaultNodeRequest())
        return reply.fault_nodes, reply.reason

    @retry_rpc()
    def check_straggler(self) -> Tuple[List[int], str]:
        reply = self._get(comm.StragglerRequest())
        return reply.straggler, reply.reason

    # ----------------------------------------------------------- kv store
    @retry_rpc()
    def kv_store_set(self, key: str, value: bytes):
        return self._report(comm.KeyValuePair(key=key, value=value))

    @retry_rpc()
    def kv_store_get(self, key: str) -> bytes:
        reply = self._get(comm.KVStoreGetRequest(key=key))
        return reply.value

    @retry_rpc()
    def kv_store_get_ex(self, key: str):
        """(value, found): a stored empty value vs an absent key."""
        reply = self._get(comm.KVStoreGetRequest(key=key))
        return reply.value, reply.found

    @retry_rpc()
    def kv_store_cas(self, key: str, expected: bytes, desired: bytes,
                     expect_absent: bool = False):
        """Server-side atomic compare-and-set; (value_after, swapped)."""
        reply = self._get(comm.KVStoreCasRequest(
            key=key, expected=expected, desired=desired,
            expect_absent=expect_absent,
        ))
        return reply.value, reply.swapped

    def kv_store_add(self, key: str, amount: int) -> int:
        # A unique op_id makes retransmitted adds idempotent server-side,
        # so the retry decorator cannot double-count the atomic increment.
        op_id = uuid.uuid4().hex

        @retry_rpc()
        def _do(self):
            reply = self._get(
                comm.KVStoreAddRequest(key=key, amount=amount, op_id=op_id)
            )
            return reply.value

        return _do(self)

    @retry_rpc()
    def kv_store_multi_get(self, keys: List[str]) -> List[bytes]:
        reply = self._get(comm.KVStoreMultiGetRequest(keys=keys))
        return [kv.value for kv in reply.kvs]

    @retry_rpc()
    def kv_store_multi_set(self, keys: List[str], values: List[bytes]):
        kvs = [
            comm.KeyValuePair(key=k, value=v) for k, v in zip(keys, values)
        ]
        return self._report(comm.KVStoreMultiSetRequest(kvs=kvs))

    def kv_store_wait(self, keys: List[str], timeout: float = 300.0) -> bool:
        """Poll the master in short slices (the server caps each wait at a
        few seconds so waiters never starve its RPC thread pool)."""
        deadline = time.time() + timeout
        while True:
            remaining = deadline - time.time()
            if remaining <= 0:
                return False
            reply = self._get(
                comm.KVStoreWaitRequest(
                    keys=keys, timeout=min(remaining, 5.0)
                ),
                timeout=30,
            )
            if reply.success:
                return True

    @retry_rpc()
    def kv_store_delete(self, key: str):
        return self._report(comm.KVStoreDeleteRequest(key=key))

    # ---------------------------------------------------------- reporting
    def report_global_step(
        self, step: int, timestamp: float = 0.0, elapsed: float = 0.0
    ):
        return self._report(
            comm.GlobalStep(
                step=step,
                timestamp=timestamp or time.time(),
                elapsed_time_per_step=elapsed,
            )
        )

    def report_planned_elasticity(
        self, action: str, reason: str = "", timestamp: float = 0.0
    ):
        """Tell the master's goodput ledger a coordinator-initiated
        membership change begins/ends (fleet borrow/return) — charged
        as planned elasticity, not downtime."""
        return self._report(
            comm.PlannedElasticityEvent(
                action=action, reason=reason,
                timestamp=timestamp or time.time(),
            )
        )

    def report_heart_beat(self, timestamp: float = 0.0) -> str:
        reply = self._report(
            comm.HeartBeat(
                node_id=self._node_id,
                timestamp=timestamp or time.time(),
            )
        )
        return reply.action if reply else ""

    def report_resource_stats(self, stats: comm.ResourceStats):
        return self._report(stats)

    @retry_rpc(retry=3, interval=1)
    def report_failure(
        self,
        error_data: str,
        level: str,
        node_rank: int = 0,
        restart_count: int = 0,
    ):
        return self._report(
            comm.NodeFailure(
                node_id=self._node_id,
                node_rank=node_rank,
                error_data=error_data,
                level=level,
                restart_count=restart_count,
            )
        )

    def report_node_status(self, node_rank: int, status: str):
        return self._report(
            comm.NodeStatusReport(
                node_id=self._node_id, node_rank=node_rank, status=status
            )
        )

    def report_node_event(self, event: comm.NodeEventReport):
        return self._report(event)

    def report_diagnosis_data(self, data: comm.DiagnosisReportData):
        return self._report(data)

    # ------------------------------------------------------------- config
    @retry_rpc()
    def get_paral_config(self) -> comm.ParallelConfig:
        return self._get(comm.ParallelConfigRequest(node_id=self._node_id))

    @retry_rpc()
    def get_elastic_run_config(self) -> Dict[str, str]:
        reply = self._get(comm.ElasticRunConfigRequest())
        return reply.configs

    @retry_rpc()
    def query_job_detail(self) -> dict:
        """Master-side job state incl. collected metrics — node status,
        global step, speed and the goodput breakdown (reference: the
        Brain/metrics query surface)."""
        import json as _json

        reply = self._get(comm.JobDetailRequest())
        return _json.loads(reply.content) if reply.content else {}

    # ------------------------------------------------------------ PS path
    @retry_rpc()
    def query_ps_nodes(self):
        reply = self._get(comm.PsNodesRequest())
        return reply.nodes, reply.new_ps_ready, reply.ps_failure

    @retry_rpc()
    def update_cluster_version(
        self, version_type: str, version: int, task_type: str, task_id: int
    ):
        return self._report(
            comm.UpdateClusterVersionRequest(
                task_type=task_type,
                task_id=task_id,
                version_type=version_type,
                version=version,
            )
        )

    @retry_rpc()
    def query_cluster_version(
        self, version_type: str, task_type: str, task_id: int
    ) -> int:
        reply = self._get(
            comm.ClusterVersionRequest(
                task_type=task_type,
                task_id=task_id,
                version_type=version_type,
            )
        )
        return reply.version

    # --------------------------------------------------------------- sync
    def join_sync(self, sync_name: str) -> bool:
        reply = self._report(
            comm.SyncJoinRequest(
                sync_name=sync_name,
                node_type=self._node_type,
                node_id=self._node_id,
            )
        )
        return reply.success

    def sync_finished(self, sync_name: str) -> bool:
        reply = self._get(comm.SyncJoinRequest(sync_name=sync_name))
        return reply.success

    def barrier(self, barrier_name: str, notify: bool = False) -> bool:
        if notify:
            reply = self._report(
                comm.SyncFinishRequest(sync_name=barrier_name)
            )
            return reply.success
        reply = self._get(comm.BarrierRequest(barrier_name=barrier_name))
        return reply.success

    @property
    def closed(self) -> bool:
        return self._stub.closed

    def close(self):
        self._stub.close()

    # ------------------------------------------------------------ factory
    @classmethod
    def singleton_instance(cls) -> "MasterClient":
        if cls._instance is None:
            with cls._instance_lock:
                if cls._instance is None:
                    addr = os.getenv(NodeEnv.MASTER_ADDR, "")
                    node_id = int(os.getenv(NodeEnv.NODE_ID, "0"))
                    node_type = os.getenv(NodeEnv.NODE_TYPE, "worker")
                    if not addr:
                        raise RuntimeError(
                            f"{NodeEnv.MASTER_ADDR} is not set"
                        )
                    cls._instance = cls(addr, node_id, node_type)
        return cls._instance

    @classmethod
    def reset_singleton(cls):
        with cls._instance_lock:
            cls._instance = None
