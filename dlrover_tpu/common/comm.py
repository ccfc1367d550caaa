"""Typed control-plane messages carried by the master ``report``/``get`` RPCs.

Counterpart of the reference message catalog (reference:
dlrover/python/common/grpc.py:129-469), with explicit msgpack serialization
(see serialize.py) instead of pickle.
"""

from dataclasses import field
from typing import Dict, List, Optional

from dlrover_tpu.common.serialize import (  # noqa: F401
    comm_message,
    deserialize_message,
    serialize_message,
)


@comm_message
class BaseRequest:
    node_id: int = -1
    node_type: str = ""
    data: bytes = b""


@comm_message
class BaseResponse:
    success: bool = False
    data: bytes = b""
    message: str = ""


# ---------------------------------------------------------------- tasks


@comm_message
class Shard:
    name: str = ""
    start: int = 0
    end: int = 0
    record_indices: List[int] = field(default_factory=list)


@comm_message
class Task:
    task_id: int = -1
    task_type: str = ""
    shard: Optional[Shard] = None

    @property
    def valid(self) -> bool:
        return self.task_id >= 0


@comm_message
class TaskRequest:
    dataset_name: str = ""


@comm_message
class TaskResult:
    dataset_name: str = ""
    task_id: int = -1
    err_message: str = ""


@comm_message
class DatasetShardParams:
    batch_size: int = 0
    num_epochs: int = 1
    dataset_size: int = 0
    shuffle: bool = False
    num_minibatches_per_shard: int = 2
    dataset_name: str = ""
    task_type: str = ""
    storage_type: str = "table"  # "table" | "text" | "streaming"


@comm_message
class ShardCheckpointRequest:
    dataset_name: str = ""


@comm_message
class ShardCheckpoint:
    content: str = ""  # JSON dataset checkpoint


@comm_message
class DatasetMeta:
    dataset_name: str = ""


@comm_message
class TaskStatus:
    finished: bool = False
    completed_step: int = 0


# ---------------------------------------------------------- rendezvous


@comm_message
class JoinRendezvousRequest:
    node_id: int = 0
    node_rank: int = 0
    local_world_size: int = 1
    rdzv_name: str = ""
    node_unit: int = 1
    slice_id: int = 0
    node_ip: str = ""
    # a port free on the joining host at the moment of this join: where
    # the round's jax.distributed service listens if this node turns out
    # to be its lowest rank
    node_port: int = 0


@comm_message
class WaitingNodeNumRequest:
    node_id: int = 0
    rdzv_name: str = ""


@comm_message
class RendezvousStateReply:
    waiting_num: int = 0


@comm_message
class CommWorldRequest:
    node_id: int = 0
    node_rank: int = 0
    rdzv_name: str = ""


@comm_message
class CommWorldReply:
    round: int = 0
    group: int = 0
    # node_rank -> local_world_size of every node in the comm world.
    world: Dict[int, int] = field(default_factory=dict)
    # node_rank -> ip/hostname and the port offered with the join: the
    # lowest rank's pair is the round's jax.distributed coordinator.
    node_ips: Dict[int, str] = field(default_factory=dict)
    node_ports: Dict[int, int] = field(default_factory=dict)


@comm_message
class RendezvousRoundReply:
    round: int = 0


@comm_message
class RendezvousJoinedRequest:
    """Is this node still registered (waiting or admitted) with the
    rendezvous?  A restarted master answers False for every node — the
    agent-side handler re-joins instead of polling an empty world until
    its timeout (master-restart fault tolerance, ISSUE 9)."""

    node_rank: int = 0
    rdzv_name: str = ""


@comm_message
class RendezvousJoinedReply:
    joined: bool = False


@comm_message
class NetworkStatusRequest:
    pass


@comm_message
class NetworkStatusReply:
    normal: bool = True
    reason: str = ""


@comm_message
class FaultNodeRequest:
    pass


@comm_message
class StragglerRequest:
    pass


@comm_message
class KVStoreWaitRequest:
    keys: List[str] = field(default_factory=list)
    timeout: float = 300.0


@comm_message
class RendezvousParamsReport:
    """Launcher -> master: elastic bounds for the job's rendezvous."""

    min_nodes: int = 1
    max_nodes: int = 1
    waiting_timeout: float = 30.0
    node_unit: int = 1
    join_timeout: float = 600.0


@comm_message
class NetworkReadyRequest:
    node_id: int = 0
    node_rank: int = 0


@comm_message
class NetworkCheckResult:
    node_rank: int = 0
    normal: bool = True
    elapsed_time: float = 0.0


@comm_message
class StragglerExistReply:
    straggler: List[int] = field(default_factory=list)
    reason: str = ""


@comm_message
class FaultNodeReply:
    fault_nodes: List[int] = field(default_factory=list)
    reason: str = ""


# ------------------------------------------------------------- kv store


@comm_message
class KeyValuePair:
    key: str = ""
    value: bytes = b""


@comm_message
class KVStoreGetRequest:
    key: str = ""


@comm_message
class KVStoreAddRequest:
    key: str = ""
    amount: int = 0
    # Client-generated unique id: lets the server deduplicate retransmitted
    # adds so the atomic counter is exactly-once under RPC retries.
    op_id: str = ""


@comm_message
class KVStoreAddReply:
    value: int = 0


@comm_message
class KVStoreMultiGetRequest:
    keys: List[str] = field(default_factory=list)


@comm_message
class KVStoreMultiGetReply:
    kvs: List[KeyValuePair] = field(default_factory=list)


@comm_message
class KVStoreMultiSetRequest:
    kvs: List[KeyValuePair] = field(default_factory=list)


@comm_message
class KVStoreDeleteRequest:
    key: str = ""


@comm_message
class KVStoreGetReply:
    value: bytes = b""
    found: bool = False  # distinguishes a stored empty value from absence


@comm_message
class KVStoreCasRequest:
    """Server-side compare-and-set (atomic under the store lock)."""

    key: str = ""
    expected: bytes = b""
    desired: bytes = b""
    # empty `expected` means set-if-absent, NOT compare-to-empty-value
    expect_absent: bool = False


@comm_message
class KVStoreCasReply:
    value: bytes = b""  # value after the operation
    swapped: bool = False


# ------------------------------------------------------------ reporting


@comm_message
class GlobalStep:
    step: int = 0
    timestamp: float = 0.0
    elapsed_time_per_step: float = 0.0


@comm_message
class ResourceStats:
    cpu_percent: float = 0.0
    memory_mb: int = 0
    tpu_duty_cycle: float = 0.0
    tpu_hbm_used_mb: int = 0
    tpu_chips: int = 0


@comm_message
class NodeFailure:
    node_id: int = 0
    node_rank: int = 0
    error_data: str = ""
    level: str = ""
    restart_count: int = 0


@comm_message
class PlannedElasticityEvent:
    """Fleet-coordinator notification: a DELIBERATE membership change
    (borrow/return shrink+regrow) begins or ends — the goodput ledger
    charges the window as planned elasticity, not downtime."""

    action: str = ""       # "begin" | "end"
    reason: str = ""
    timestamp: float = 0.0


@comm_message
class NodeEventReport:
    event_type: str = ""
    instance: str = ""
    action: str = ""
    msg: str = ""
    labels: Dict[str, str] = field(default_factory=dict)


@comm_message
class HeartBeat:
    node_id: int = 0
    timestamp: float = 0.0


@comm_message
class HeartbeatResponse:
    action: str = ""  # "" | "stop" | "relaunch"


@comm_message
class NodeMeta:
    node_type: str = ""
    node_id: int = 0
    node_rank: int = 0
    addr: str = ""
    memory: int = 0
    cpu: float = 0.0
    tpu_chips: int = 0


@comm_message
class NodeStatusReport:
    node_id: int = 0
    node_rank: int = 0
    status: str = ""


# ----------------------------------------------------- parallel config


@comm_message
class DataLoaderConfig:
    dataloader_name: str = ""
    batch_size: int = 0
    num_workers: int = 0
    pin_memory: bool = False
    version: int = 0


@comm_message
class OptimizerConfig:
    optimizer_name: str = ""
    learning_rate: float = 0.0
    version: int = 0


@comm_message
class ParallelConfigRequest:
    node_id: int = 0


@comm_message
class ParallelConfig:
    dataloader: DataLoaderConfig = field(default_factory=DataLoaderConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    # Mesh re-plan pushed by the master (auto-parallel feedback loop).
    mesh_shape: Dict[str, int] = field(default_factory=dict)
    restart: bool = False


# -------------------------------------------------------- PS / TF path


@comm_message
class ClusterVersionRequest:
    task_type: str = ""
    task_id: int = 0
    version_type: str = ""  # GLOBAL | LOCAL | RESTORED


@comm_message
class ClusterVersionReply:
    version: int = 0


@comm_message
class UpdateClusterVersionRequest:
    task_type: str = ""
    task_id: int = 0
    version_type: str = ""
    version: int = 0


@comm_message
class PsNodesRequest:
    pass


@comm_message
class PsNodesReply:
    nodes: List[NodeMeta] = field(default_factory=list)
    new_ps_ready: bool = False
    ps_failure: bool = False


# ----------------------------------------------------------- sync / misc


@comm_message
class SyncJoinRequest:
    sync_name: str = ""
    node_type: str = ""
    node_id: int = 0


@comm_message
class SyncFinishRequest:
    sync_name: str = ""


@comm_message
class BarrierRequest:
    barrier_name: str = ""


@comm_message
class SyncResult:
    success: bool = False


@comm_message
class JobDetailRequest:
    pass


@comm_message
class JobDetailReply:
    content: str = ""  # JSON


@comm_message
class ElasticRunConfigRequest:
    pass


@comm_message
class ElasticRunConfig:
    configs: Dict[str, str] = field(default_factory=dict)


@comm_message
class DiagnosisReportData:
    data_cls: str = ""  # "metrics" | "log" | custom collector name
    data_content: str = ""
    node_id: int = 0
    node_type: str = ""
    node_rank: int = 0
    timestamp: float = 0.0


@comm_message
class CheckHardwareResult:
    healthy: bool = True
    detail: str = ""
