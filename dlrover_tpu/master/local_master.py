"""Single-node job master used by `dlrover-tpu-run` standalone mode.

Counterpart of reference dlrover/python/master/local_master.py:38-118: the
launcher spawns this master as a subprocess when no cluster master exists;
it serves rendezvous, data sharding and the KV store for agents on one
host (or a handful of hosts pointing at it).
"""

import threading
import time
from typing import Dict, Optional

from dlrover_tpu.common.constants import NodeStatus, RendezvousName
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.common.rpc import bind_server_port, build_server
from dlrover_tpu.master.elastic_training.elastic_ps import ElasticPsService
from dlrover_tpu.master.elastic_training.kv_store_service import (
    KVStoreService,
)
from dlrover_tpu.master.elastic_training.rdzv_manager import (
    ElasticTrainingRendezvousManager,
    NetworkCheckRendezvousManager,
)
from dlrover_tpu.master.elastic_training.sync_service import SyncService
from dlrover_tpu.master.monitor.speed_monitor import SpeedMonitor
from dlrover_tpu.master.servicer import MasterServicer
from dlrover_tpu.master.shard.task_manager import TaskManager
from dlrover_tpu.master.stats.job_collector import JobMetricCollector


# how long a finished job's master waits for an agent's final report
AGENT_WRAPUP_SECONDS = 120.0


class LocalJobMaster:
    def __init__(self, port: int, node_num: int = 1):
        self._port = port
        self._node_num = node_num
        self.speed_monitor = SpeedMonitor()
        self.job_metric_collector = JobMetricCollector()
        self.task_manager = TaskManager(0, self.speed_monitor)
        self.rdzv_managers = {
            RendezvousName.ELASTIC_TRAINING: (
                ElasticTrainingRendezvousManager()
            ),
            RendezvousName.NETWORK_CHECK: NetworkCheckRendezvousManager(),
        }
        self.kv_store = KVStoreService()
        self.sync_service = SyncService()
        self.elastic_ps_service = ElasticPsService()
        self.servicer = MasterServicer(
            task_manager=self.task_manager,
            job_manager=None,
            rdzv_managers=self.rdzv_managers,
            kv_store=self.kv_store,
            sync_service=self.sync_service,
            elastic_ps_service=self.elastic_ps_service,
            job_metric_collector=self.job_metric_collector,
        )
        self._server = build_server(self.servicer.get, self.servicer.report)
        self._stopped = threading.Event()

    @property
    def port(self) -> int:
        """The actually-bound port — authoritative only after
        :meth:`prepare` (``port=0`` in the constructor means "let the
        kernel pick"; the race-free idiom, see rpc.bind_server_port)."""
        return self._port

    def prepare(self) -> None:
        for mgr in self.rdzv_managers.values():
            mgr.update_rdzv_params(
                min_nodes=self._node_num,
                max_nodes=self._node_num,
                waiting_timeout=30,
                node_unit=1,
            )
        self.task_manager.start()
        self.job_metric_collector.mark_job_start()
        self._port = bind_server_port(self._server, self._port)
        self._server.start()
        logger.info("Local master serving on port %s", self._port)

    def agents_ended(self) -> bool:
        """Every agent that reported its node RUNNING has since reported
        how it ended (vacuously true for plain clients, which report no
        node status at all)."""
        return all(
            status in (NodeStatus.SUCCEEDED, NodeStatus.FAILED)
            for status in self.servicer.node_status.values())

    def run(self) -> int:
        """Block until the job finishes or stop.  The job has finished
        when all datasets are completed AND the agents have reported how
        they ended: after its last shard a worker still wraps up (at a
        real size the final blocking checkpoint save alone takes tens of
        seconds), and a master that has left by then costs its agent a
        whole RPC retry deadline on every remaining call.  An agent that
        never reports (it was killed) holds the master for
        ``AGENT_WRAPUP_SECONDS`` at most."""
        done_at = None
        try:
            while not self._stopped.is_set():
                if not self.task_manager.finished():
                    done_at = None
                else:
                    done_at = done_at or time.time()
                    if self.agents_ended() or (
                            time.time() - done_at > AGENT_WRAPUP_SECONDS):
                        logger.info(
                            "All dataset tasks completed; master exits")
                        break
                time.sleep(2)
        except KeyboardInterrupt:
            pass
        return 0

    def stop(self) -> None:
        self._stopped.set()
        self._server.stop(grace=None)
