"""gRPC dispatch of the job master.

Counterpart of reference dlrover/python/master/servicer.py:71-330: a single
service with two unary RPCs — ``get`` (queries) and ``report``
(notifications) — dispatching on the decoded message type.
"""

import json
import time
from typing import Dict, Optional

from dlrover_tpu.common import comm
from dlrover_tpu.common.constants import (
    NodeType,
    RendezvousName,
    TaskType,
)
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.common.serialize import (
    deserialize_message,
    serialize_message,
)
from dlrover_tpu.master.elastic_training.elastic_ps import ElasticPsService
from dlrover_tpu.master.elastic_training.kv_store_service import (
    KVStoreService,
)
from dlrover_tpu.master.elastic_training.rdzv_manager import (
    ElasticTrainingRendezvousManager,
    NetworkCheckRendezvousManager,
)
from dlrover_tpu.master.elastic_training.sync_service import SyncService
from dlrover_tpu.master.shard.task_manager import TaskManager


class MasterServicer:
    """Handlers receive the raw envelope bytes and return reply bytes."""

    def __init__(
        self,
        task_manager: Optional[TaskManager] = None,
        job_manager=None,
        rdzv_managers=None,
        kv_store: Optional[KVStoreService] = None,
        sync_service: Optional[SyncService] = None,
        elastic_ps_service: Optional[ElasticPsService] = None,
        job_metric_collector=None,
        diagnosis_manager=None,
    ):
        self._task_manager = task_manager
        self._job_manager = job_manager
        self._rdzv_managers = rdzv_managers or {}
        self._kv_store = kv_store or KVStoreService()
        self._sync_service = sync_service or SyncService()
        self._elastic_ps_service = elastic_ps_service or ElasticPsService()
        self._job_metric_collector = job_metric_collector
        self._diagnosis_manager = diagnosis_manager
        self._start_training_time = 0.0
        self._start_autoscale = False
        # last status each agent reported for its node (node_id -> status)
        self.node_status: Dict[int, str] = {}

    # ------------------------------------------------------------- get
    def get(self, request_bytes: bytes, context=None) -> bytes:
        req: comm.BaseRequest = deserialize_message(request_bytes)
        message = deserialize_message(req.data)
        response = comm.BaseResponse(success=True)
        try:
            result = self._dispatch_get(req, message)
            if result is not None:
                response.data = serialize_message(result)
        except Exception as e:
            logger.exception("get(%s) failed", type(message).__name__)
            response.success = False
            response.message = str(e)
        return serialize_message(response)

    def _dispatch_get(self, req: comm.BaseRequest, message):
        if isinstance(message, comm.TaskRequest):
            return self._get_task(req.node_type, req.node_id, message)
        if isinstance(message, comm.ShardCheckpointRequest):
            content = self._task_manager.get_dataset_checkpoint(
                message.dataset_name
            )
            return comm.ShardCheckpoint(content=content)
        if isinstance(message, comm.JoinRendezvousRequest):
            return self._join_rendezvous(req, message)
        if isinstance(message, comm.CommWorldRequest):
            return self._get_comm_world(message)
        if isinstance(message, comm.WaitingNodeNumRequest):
            mgr = self._rdzv_managers.get(
                message.rdzv_name or RendezvousName.ELASTIC_TRAINING
            )
            return comm.RendezvousStateReply(
                waiting_num=mgr.num_nodes_waiting() if mgr else 0
            )
        if isinstance(message, comm.RendezvousJoinedRequest):
            mgr = self._rdzv_managers.get(
                message.rdzv_name or RendezvousName.ELASTIC_TRAINING
            )
            return comm.RendezvousJoinedReply(
                joined=bool(mgr and mgr.joined(message.node_rank))
            )
        if isinstance(message, comm.NetworkStatusRequest):
            mgr = self._rdzv_managers.get(RendezvousName.NETWORK_CHECK)
            normal, reason = (
                mgr.network_check_success() if mgr else (True, "")
            )
            return comm.NetworkStatusReply(normal=normal, reason=reason)
        if isinstance(message, comm.FaultNodeRequest):
            mgr = self._rdzv_managers.get(RendezvousName.NETWORK_CHECK)
            nodes, reason = mgr.check_fault_node() if mgr else ([], "")
            return comm.FaultNodeReply(fault_nodes=nodes, reason=reason)
        if isinstance(message, comm.StragglerRequest):
            mgr = self._rdzv_managers.get(RendezvousName.NETWORK_CHECK)
            nodes, reason = mgr.check_straggler() if mgr else ([], "")
            return comm.StragglerExistReply(straggler=nodes, reason=reason)
        if isinstance(message, comm.KVStoreGetRequest):
            value, found = self._kv_store.get_ex(message.key)
            return comm.KVStoreGetReply(value=value, found=found)
        if isinstance(message, comm.KVStoreCasRequest):
            value, swapped = self._kv_store.compare_set(
                message.key, message.expected, message.desired,
                expect_absent=message.expect_absent,
            )
            return comm.KVStoreCasReply(value=value, swapped=swapped)
        if isinstance(message, comm.KVStoreAddRequest):
            return comm.KVStoreAddReply(
                value=self._kv_store.add(
                    message.key, message.amount, op_id=message.op_id
                )
            )
        if isinstance(message, comm.KVStoreMultiGetRequest):
            values = self._kv_store.multi_get(message.keys)
            return comm.KVStoreMultiGetReply(
                kvs=[
                    comm.KeyValuePair(key=k, value=v)
                    for k, v in zip(message.keys, values)
                ]
            )
        if isinstance(message, comm.KVStoreWaitRequest):
            # Cap the server-side block so waiters cannot starve the RPC
            # thread pool; clients poll (MasterClient.kv_store_wait loops).
            ok = self._kv_store.wait(
                message.keys, min(message.timeout, 5.0)
            )
            return comm.SyncResult(success=ok)
        if isinstance(message, comm.BarrierRequest):
            ok = self._sync_service.barrier(message.barrier_name)
            return comm.SyncResult(success=ok)
        if isinstance(message, comm.ParallelConfigRequest):
            return self._get_paral_config(req.node_id)
        if isinstance(message, comm.ClusterVersionRequest):
            version = self._elastic_ps_service.get_node_version(
                message.task_type, message.task_id, message.version_type
            )
            return comm.ClusterVersionReply(version=version)
        if isinstance(message, comm.PsNodesRequest):
            return self._query_ps_nodes()
        if isinstance(message, comm.TaskStatus):
            finished = (
                self._task_manager.finished()
                if self._task_manager
                else False
            )
            return comm.TaskStatus(finished=finished)
        if isinstance(message, comm.JobDetailRequest):
            return self._get_job_detail()
        if isinstance(message, comm.ElasticRunConfigRequest):
            configs = (
                self._job_manager.get_elastic_run_configs()
                if self._job_manager
                else {}
            )
            return comm.ElasticRunConfig(configs=configs)
        if isinstance(message, comm.SyncJoinRequest):
            ok = self._sync_service.sync_finished(message.sync_name)
            return comm.SyncResult(success=ok)
        raise ValueError(f"Unknown get message {type(message).__name__}")

    def _get_task(self, node_type, node_id, message: comm.TaskRequest):
        if not self._start_training_time:
            self._start_training_time = time.time()
        task = self._task_manager.get_dataset_task(
            node_id, message.dataset_name
        )
        res = comm.Task(task_id=task.task_id, task_type=task.task_type)
        if task.task_id >= 0 and task.shard is not None:
            res.shard = comm.Shard(
                name=task.shard.name,
                start=task.shard.start,
                end=task.shard.end,
                record_indices=list(task.shard.record_indices or []),
            )
        self._task_manager.speed_monitor.add_running_worker(
            node_type or NodeType.WORKER, node_id
        )
        return res

    def _join_rendezvous(
        self, req: comm.BaseRequest, message: comm.JoinRendezvousRequest
    ):
        mgr = self._rdzv_managers.get(
            message.rdzv_name or RendezvousName.ELASTIC_TRAINING
        )
        if mgr is None:
            raise ValueError(f"no rdzv manager {message.rdzv_name}")
        round_ = mgr.join_rendezvous(
            message.node_id,
            message.node_rank,
            message.local_world_size,
            node_ip=message.node_ip,
            slice_id=message.slice_id,
            node_port=message.node_port,
        )
        if self._job_manager is not None:
            # network-check joins may update node liveness
            pass
        return comm.RendezvousRoundReply(round=round_)

    def _get_comm_world(self, message: comm.CommWorldRequest):
        mgr = self._rdzv_managers.get(
            message.rdzv_name or RendezvousName.ELASTIC_TRAINING
        )
        if mgr is None:
            raise ValueError(f"no rdzv manager {message.rdzv_name}")
        round_, group, world = mgr.get_comm_world(message.node_rank)
        reply = comm.CommWorldReply(round=round_, group=group)
        for rank, meta in world.items():
            reply.world[rank] = meta.process_num
            reply.node_ips[rank] = meta.node_ip
            reply.node_ports[rank] = meta.node_port
        return reply

    def _get_paral_config(self, node_id: int):
        if self._job_manager is None:
            return comm.ParallelConfig()
        config = self._job_manager.get_paral_config(node_id)
        return config or comm.ParallelConfig()

    def _query_ps_nodes(self):
        reply = comm.PsNodesReply()
        if self._job_manager is None:
            # standalone/local master: no PS lifecycle to wait on — an
            # empty-but-ready set lets the failover client proceed
            reply.new_ps_ready = True
            return reply
        nodes, ready, failure = self._job_manager.query_ps_nodes()
        reply.nodes = nodes
        reply.new_ps_ready = ready
        reply.ps_failure = failure
        return reply

    def _get_job_detail(self):
        detail = {}
        if self._job_manager is not None:
            detail = self._job_manager.get_job_detail()
        if self._job_metric_collector is not None:
            detail["metrics"] = self._job_metric_collector.get_job_metrics()
        return comm.JobDetailReply(content=json.dumps(detail))

    # ------------------------------------------------------------ report
    def report(self, request_bytes: bytes, context=None) -> bytes:
        req: comm.BaseRequest = deserialize_message(request_bytes)
        message = deserialize_message(req.data)
        response = comm.BaseResponse(success=True)
        try:
            result = self._dispatch_report(req, message)
            if result is not None:
                response.data = serialize_message(result)
        except Exception as e:
            logger.exception("report(%s) failed", type(message).__name__)
            response.success = False
            response.message = str(e)
        return serialize_message(response)

    def _dispatch_report(self, req: comm.BaseRequest, message):
        if isinstance(message, comm.DatasetShardParams):
            self._task_manager.new_dataset(
                batch_size=message.batch_size,
                dataset_size=message.dataset_size,
                dataset_name=message.dataset_name,
                task_type=message.task_type or TaskType.TRAINING,
                num_epochs=message.num_epochs,
                shuffle=message.shuffle,
                num_minibatches_per_shard=message.num_minibatches_per_shard,
                storage_type=message.storage_type,
            )
            return None
        if isinstance(message, comm.TaskResult):
            self._task_manager.report_dataset_task(
                message.dataset_name,
                message.task_id,
                not message.err_message,
            )
            return None
        if isinstance(message, comm.ShardCheckpoint):
            # restore a dataset from a checkpoint saved by the trainer
            d = json.loads(message.content) if message.content else {}
            name = d.get("dataset_name", "")
            if name:
                self._task_manager.restore_dataset_from_checkpoint(
                    name, message.content
                )
            return None
        if isinstance(message, comm.GlobalStep):
            ts = message.timestamp or time.time()
            self._task_manager.speed_monitor.sample_global_step(
                message.step, ts
            )
            # per-rank step-time skew feed: the envelope names the
            # reporting rank (req.node_id) and the message already
            # carries its per-step wall time — together they are the
            # dlrover_master_step_skew_seconds{rank=...} gauge family
            self._task_manager.speed_monitor.sample_worker_step(
                req.node_id, message.elapsed_time_per_step
            )
            if self._job_metric_collector is not None:
                self._job_metric_collector.report_global_step(
                    message.step, ts
                )
            return None
        if isinstance(message, comm.NetworkCheckResult):
            mgr = self._rdzv_managers.get(RendezvousName.NETWORK_CHECK)
            if mgr:
                mgr.report_network_check_result(
                    message.node_rank, message.normal, message.elapsed_time
                )
            return None
        if isinstance(message, comm.RendezvousParamsReport):
            for mgr in self._rdzv_managers.values():
                mgr.update_rdzv_params(
                    message.min_nodes,
                    message.max_nodes,
                    message.waiting_timeout,
                    message.node_unit,
                    message.join_timeout,
                )
            return None
        if isinstance(message, comm.KeyValuePair):
            self._kv_store.set(message.key, message.value)
            return None
        if isinstance(message, comm.KVStoreMultiSetRequest):
            self._kv_store.multi_set(
                [kv.key for kv in message.kvs],
                [kv.value for kv in message.kvs],
            )
            return None
        if isinstance(message, comm.KVStoreDeleteRequest):
            self._kv_store.delete(message.key)
            return None
        if isinstance(message, comm.NodeFailure):
            if self._job_metric_collector is not None:
                # the goodput ledger must see the kill even when the
                # recovery is fast enough to hide inside one step-report
                # interval (stats/job_collector.py::mark_restart)
                self._job_metric_collector.mark_restart()
                self._job_metric_collector.report_event(
                    "node_failure", instance=str(message.node_id),
                    msg=f"{message.level}: {message.error_data}",
                )
            if self._job_manager is not None:
                self._job_manager.handle_training_failure(
                    req.node_type or NodeType.WORKER,
                    message.node_id,
                    restart_count=message.restart_count,
                    error_data=message.error_data,
                    level=message.level,
                )
            if self._task_manager is not None:
                # An in-place process restart (node still alive) loses the
                # dead process's in-flight shards either way — recover them
                # now instead of waiting out the task timeout.
                self._task_manager.recover_tasks(message.node_id)
            return None
        if isinstance(message, comm.PlannedElasticityEvent):
            if self._job_metric_collector is not None:
                ts = message.timestamp or None
                if message.action == "begin":
                    self._job_metric_collector.begin_planned_elasticity(
                        reason=message.reason, timestamp=ts
                    )
                else:
                    self._job_metric_collector.end_planned_elasticity(
                        timestamp=ts
                    )
            return None
        if isinstance(message, comm.HeartBeat):
            action = ""
            if self._job_manager is not None:
                action = self._job_manager.collect_node_heart_beat(
                    req.node_type or NodeType.WORKER,
                    message.node_id,
                    message.timestamp,
                )
            return comm.HeartbeatResponse(action=action or "")
        if isinstance(message, comm.ResourceStats):
            if self._job_manager is not None:
                self._job_manager.update_node_resource_usage(
                    req.node_type or NodeType.WORKER,
                    req.node_id,
                    message,
                )
            if self._job_metric_collector is not None:
                self._job_metric_collector.report_resource_usage(
                    req.node_type or NodeType.WORKER, req.node_id, message
                )
            return None
        if isinstance(message, comm.NodeStatusReport):
            self.node_status[message.node_id] = message.status
            if self._job_manager is not None:
                self._job_manager.update_node_reported_status(
                    req.node_type or NodeType.WORKER,
                    message.node_id,
                    message.status,
                )
            return None
        if isinstance(message, comm.NodeMeta):
            if self._job_manager is not None:
                self._job_manager.update_node_service_addr(
                    message.node_type, message.node_id, message.addr
                )
            return None
        if isinstance(message, comm.SyncJoinRequest):
            ok = self._sync_service.join_sync(
                message.sync_name, req.node_type, req.node_id
            )
            return comm.SyncResult(success=ok)
        if isinstance(message, comm.SyncFinishRequest):
            ok = self._sync_service.notify_barrier(message.sync_name)
            return comm.SyncResult(success=ok)
        if isinstance(message, comm.UpdateClusterVersionRequest):
            self._elastic_ps_service.update_node_version(
                message.task_type,
                message.task_id,
                message.version_type,
                message.version,
            )
            return None
        if isinstance(message, comm.NodeEventReport):
            if self._job_manager is not None:
                self._job_manager.process_reported_node_event(message)
            return None
        if isinstance(message, comm.DiagnosisReportData):
            if self._diagnosis_manager is not None:
                self._diagnosis_manager.collect_diagnosis_data(message)
            return None
        raise ValueError(f"Unknown report message {type(message).__name__}")
