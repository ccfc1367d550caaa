"""Constants and the environment-variable contract of the control plane.

TPU-native counterpart of the reference's env/constant catalog
(reference: dlrover/python/common/constants.py). Values are re-designed for
TPU pod-slice deployments: workers are per-host processes driving all local
TPU chips via one JAX process, not per-GPU processes.
"""

import os
import tempfile


class NodeType:
    MASTER = "master"
    PS = "ps"
    WORKER = "worker"
    EVALUATOR = "evaluator"
    CHIEF = "chief"
    SERVING_REPLICA = "serving-replica"
    # TPU host agent inside one pod slice.
    TPU_HOST = "worker"


class NodeStatus:
    INITIAL = "Initial"
    PENDING = "Pending"
    RUNNING = "Running"
    SUCCEEDED = "Succeeded"
    FAILED = "Failed"
    DELETED = "Deleted"
    FINISHED = "Finished"
    BREAKDOWN = "Breakdown"
    UNKNOWN = "Unknown"


class NodeEventType:
    ADDED = "ADDED"
    MODIFIED = "MODIFIED"
    DELETED = "DELETED"


class ReplicaStatus:
    """Lifecycle of one serving replica in the router's replica manager
    (serving/router/replica.py) — the serving counterpart of NodeStatus."""

    JOINING = "Joining"    # announced, warming up (compiling/loading)
    UP = "Up"              # heartbeating, schedulable
    DRAINING = "Draining"  # no new placements; finishing in-flight work
    DEAD = "Dead"          # missed heartbeats / crashed; in-flight requeued
    LEFT = "Left"          # drained and removed


class ServingRequestState:
    """Lifecycle of one request through the serving gateway."""

    QUEUED = "Queued"        # admitted, waiting for a replica slot
    RUNNING = "Running"      # placed on a replica, generating
    DONE = "Done"            # output complete
    TIMED_OUT = "TimedOut"   # deadline expired before completion
    CANCELLED = "Cancelled"  # caller withdrew it
    REJECTED = "Rejected"    # refused at admission (queue bound)
    POISONED = "Poisoned"    # crashed every replica it landed on
    #                          (requeue cap exceeded; see ServingFabric)


# THE transition spec for ServingRequestState — the single source of
# truth both the runtime (gateway terminal-state guards) and static
# analysis (dlint DL009 state-transition checker) read.  It lives next
# to the enum ON PURPOSE: adding a state without a spec entry, or a
# spec entry naming a non-state, is itself a DL009 finding, so the two
# can never drift apart silently.
#
# Terminal states answer the caller (result()/stream() unblocked); a
# write that would LEAVE one re-opens a request whose answer already
# shipped — the resurrect bug class requeue_front's guard exists for.
SERVING_REQUEST_TERMINAL_STATES = (
    ServingRequestState.DONE,
    ServingRequestState.TIMED_OUT,
    ServingRequestState.CANCELLED,
    ServingRequestState.REJECTED,
    ServingRequestState.POISONED,
)

SERVING_REQUEST_TRANSITIONS = {
    # QUEUED -> QUEUED is the pre-placement failover requeue (a replica
    # died while the request sat scheduled-but-unsubmitted).
    ServingRequestState.QUEUED: (
        ServingRequestState.QUEUED,
        ServingRequestState.RUNNING,
        ServingRequestState.TIMED_OUT,
        ServingRequestState.CANCELLED,
        ServingRequestState.REJECTED,
        ServingRequestState.POISONED,
    ),
    # RUNNING -> QUEUED is the failover replay; REJECTED is absent on
    # purpose (rejection happens at placement, before RUNNING is set).
    ServingRequestState.RUNNING: (
        ServingRequestState.QUEUED,
        ServingRequestState.DONE,
        ServingRequestState.TIMED_OUT,
        ServingRequestState.CANCELLED,
        ServingRequestState.POISONED,
    ),
    # terminal states transition nowhere — DL009 checks the empty
    # entries against SERVING_REQUEST_TERMINAL_STATES
    ServingRequestState.DONE: (),
    ServingRequestState.TIMED_OUT: (),
    ServingRequestState.CANCELLED: (),
    ServingRequestState.REJECTED: (),
    ServingRequestState.POISONED: (),
}


class FleetOwner:
    """Ownership of one host in the shared train/serve fleet — the
    lease states of the :mod:`dlrover_tpu.fleet` coordinator's ledger.

    Every host has EXACTLY ONE owner at any instant.  The two
    ``MIGRATING_*`` states are the in-flight halves of a handoff: a
    host is never simultaneously a rendezvous member and a serving
    replica — the coordinator moves it through a migrating state, and
    a crash mid-migration is recovered by re-deriving the lease from
    ground truth (master rendezvous membership + worker supervisor),
    never by trusting a stale claim (epoch fencing)."""

    TRAINING = "Training"            # rendezvous member, training world
    MIGRATING_OUT = "MigratingOut"   # checkpointed + shrunk, serving
    #                                  worker not yet joined the router
    SERVING = "Serving"              # serving replica taking traffic
    MIGRATING_BACK = "MigratingBack"  # draining / rejoining rendezvous


# THE transition spec for FleetOwner — the DL009-style single source of
# truth next to the enum, same contract as
# SERVING_REQUEST_TRANSITIONS below: the runtime
# (fleet/lease.LeaseLedger.transition) and static analysis (dlint
# DL009's extra-spec drift pass) both read THIS declaration, so a new
# owner state without a declared lifecycle, or a spec naming a
# non-state, is a dlint finding before it is a production surprise.
#
# The machine is a cycle with two abort edges and no terminal states —
# a host is repurposed forever, never retired by the coordinator:
#   TRAINING -> MIGRATING_OUT -> SERVING -> MIGRATING_BACK -> TRAINING
# MIGRATING_OUT -> TRAINING is the borrow abort (checkpoint barrier
# failed, or the worker never booted within its attempt budget);
# MIGRATING_BACK -> SERVING is the return abort (pressure spiked again
# before the host left the router).
FLEET_HOST_TERMINAL_STATES = ()

FLEET_HOST_TRANSITIONS = {
    FleetOwner.TRAINING: (
        FleetOwner.MIGRATING_OUT,
    ),
    FleetOwner.MIGRATING_OUT: (
        FleetOwner.SERVING,
        FleetOwner.TRAINING,   # borrow aborted: give the host back
    ),
    FleetOwner.SERVING: (
        FleetOwner.MIGRATING_BACK,
    ),
    FleetOwner.MIGRATING_BACK: (
        FleetOwner.TRAINING,
        FleetOwner.SERVING,    # return aborted: keep serving
    ),
}


class ServingFabric:
    """Serving data-plane knobs (router + remote replica fabric)."""

    # Failover replays before a request is declared POISONED: a request
    # that takes down every replica it lands on must stop circulating
    # (each replay costs a replica failover, not just queue time).
    MAX_REQUEST_REQUEUES = 3
    # First stdout line of a worker process: its self-announced address
    # (the worker binds port 0 itself; nothing pre-picks ports).
    WORKER_ANNOUNCE_PREFIX = "DLROVER_WORKER_ADDR="
    # Worker -> router STATS cadence; STATS double as liveness.
    STATS_INTERVAL = 0.05
    # Proxy declares a connected-but-silent worker dead past this.
    FRAME_TIMEOUT = 5.0
    # Phi-accrual suspicion thresholds (serving/remote/phi.py): at
    # PHI_SUSPECT the replica is demoted in placement (gray zone, no
    # failover); at PHI_DEAD — only when a proxy's phi_kill_floor is
    # armed — silence is suspicious enough to fail over EARLY, before
    # FRAME_TIMEOUT (which stays the hard ceiling regardless).
    PHI_SUSPECT = 3.0
    PHI_DEAD = 8.0
    # Router address env var a deployed worker registers back to.
    ROUTER_ADDR_ENV = "DLROVER_ROUTER_ADDR"
    # JSON fault-injection schedule for the frame protocol
    # (serving/remote/faults.py) — chaos tests set this on spawned
    # workers to tear/stall/duplicate/drop frames deterministically.
    FAULTS_ENV = "DLROVER_SERVING_FAULTS"


class NodeExitReason:
    KILLED = "Deleted"
    OOM = "OOMKilled"
    FATAL_ERROR = "Error"
    HARDWARE_ERROR = "HardwareError"  # chip / ICI-link failure
    PREEMPTED = "Preempted"
    UNKNOWN_ERROR = "UnknownError"
    RELAUNCHED = "Relaunched"

    @classmethod
    def relaunchable(cls, reason: str) -> bool:
        return reason not in (cls.FATAL_ERROR,)


class JobStage:
    CREATED = "Created"
    PENDING = "Pending"
    RUNNING = "Running"
    SCALING = "Scaling"
    SUCCEEDED = "Succeeded"
    FAILED = "Failed"


class JobExitReason:
    SUCCEEDED = "Completed"
    CODE_ERROR = "CodeError"
    WORKER_OOM = "WorkerOOM"
    WORKER_ERROR = "WorkerError"
    PS_OOM_ERROR = "PSOOM"
    PS_ERROR = "PSError"
    EVALUATOR_OOM = "EvaluatorOOM"
    EVALUATOR_ERROR = "EvaluatorError"
    HANG_ERROR = "HangError"
    UNKNOWN_ERROR = "UnknownError"


class PlatformType:
    KUBERNETES = "k8s"
    RAY = "ray"
    LOCAL = "local"
    PYK8S = "pyk8s"


class DistributionStrategy:
    LOCAL = "Local"
    PS = "ParameterServerStrategy"
    ALLREDUCE = "AllreduceStrategy"  # SPMD over a jax Mesh
    CUSTOM = "CustomStrategy"


class NodeEnv:
    """Env-var contract between master, agent and workers."""

    MASTER_ADDR = "DLROVER_MASTER_ADDR"
    JOB_NAME = "DLROVER_JOB_NAME"
    JOB_UID = "DLROVER_JOB_UID"
    NODE_TYPE = "DLROVER_NODE_TYPE"
    NODE_ID = "DLROVER_NODE_ID"
    NODE_RANK = "DLROVER_NODE_RANK"
    NODE_NUM = "DLROVER_NODE_NUM"
    POD_NAME = "DLROVER_POD_NAME"
    MONITOR_ENABLED = "DLROVER_MONITOR_ENABLED"
    # Rank of this host within its TPU pod slice, and slice index.
    HOST_RANK_IN_SLICE = "DLROVER_HOST_RANK_IN_SLICE"
    SLICE_ID = "DLROVER_SLICE_ID"
    # JAX distributed coordinator (host 0 of the comm world).
    COORDINATOR_ADDR = "DLROVER_COORDINATOR_ADDR"
    # File the trainer writes runtime metrics into (read by the agent).
    RUNTIME_METRICS_PATH = "DLROVER_RUNTIME_METRICS_PATH"
    # File the agent writes mutable parallel config into (read by trainer).
    PARAL_CONFIG_PATH = "DLROVER_PARAL_CONFIG_PATH"
    AUTO_PARAL = "DLROVER_AUTO_PARAL"
    # First stdout line of a master process launched with --port 0: its
    # self-announced address (the master binds port 0 itself and reports
    # the kernel-assigned port — same race-free idiom as the serving
    # worker's WORKER_ANNOUNCE_PREFIX).
    MASTER_ANNOUNCE_PREFIX = "DLROVER_MASTER_ADDR="
    # Stdout announce of the elastic agent's metrics-exporter port
    # (--metrics-port 0 binds a kernel-assigned port; the agent
    # announces what it got — same idiom as the other announces).
    AGENT_METRICS_ANNOUNCE_PREFIX = "DLROVER_AGENT_METRICS_PORT="
    # Stdout announce of the master's metrics-exporter port (the
    # goodput ledger becomes scrapeable instead of JSON-artifact-only).
    MASTER_METRICS_ANNOUNCE_PREFIX = "DLROVER_MASTER_METRICS_PORT="
    # Stdout announce of the fleet telemetry collector's port, and the
    # env var processes read to find it (OTLP push endpoint base URL).
    TELEMETRY_ANNOUNCE_PREFIX = "DLROVER_TELEMETRY_PORT="
    TELEMETRY_ENDPOINT = "DLROVER_TELEMETRY_ENDPOINT"


def runtime_dir(*parts: str) -> str:
    """Where one host's processes keep the small files they share (IPC
    sockets, stack dumps, the runtime-metrics and paral-config files):
    under the temporary directory the environment names (``TMPDIR``),
    never a fixed ``/tmp`` path, so two checkouts that are given
    directories of their own cannot meet in one file."""
    return os.path.join(tempfile.gettempdir(), "dlrover_tpu", *parts)


def job_uid() -> str:
    return os.getenv(NodeEnv.JOB_UID, "local")


class ConfigPath:
    """The agent <-> trainer hot-reload files.  The defaults carry the
    job's id: two jobs on one host share neither file."""

    ENV_PARAL_CONFIG = NodeEnv.PARAL_CONFIG_PATH
    ENV_RUNTIME_METRICS = NodeEnv.RUNTIME_METRICS_PATH

    @staticmethod
    def paral_config() -> str:
        return os.getenv(
            ConfigPath.ENV_PARAL_CONFIG,
            runtime_dir(f"auto_paral_config_{job_uid()}.json"))

    @staticmethod
    def runtime_metrics() -> str:
        return os.getenv(
            ConfigPath.ENV_RUNTIME_METRICS,
            runtime_dir(f"runtime_metrics_{job_uid()}.json"))


class RendezvousName:
    ELASTIC_TRAINING = "elastic-training"
    NETWORK_CHECK = "network-check"


class NetworkFailureReason:
    NODE_FAILURE = "Node breakdown"
    WAITING_NODE = "Waiting node join"
    NO_INIT = "Not initialized"


class TrainingExceptionLevel:
    PROCESS_ERROR = "process"
    NODE_ERROR = "node"
    RDZV_ERROR = "rdzv"
    WARNING = "warning"
    ERROR = "error"


class RendezvousParams:
    MIN_NODES = "min_nodes"
    MAX_NODES = "max_nodes"


class GRPC:
    # Max message size for the control-plane RPC (checkpoint metas etc.).
    MAX_SEND_MESSAGE_LENGTH = 256 * 1024 * 1024
    MAX_RECEIVE_MESSAGE_LENGTH = 256 * 1024 * 1024


class CheckpointConstant:
    TRACKER_FILE = "latest_checkpointed_iteration.txt"
    MODEL_STATES_NAME = "model_states"
    TRAIN_STATE_NAME = "train_state"
    SAVE_TIMEOUT = 600


class SaverClassMeta:
    """Queue name over which trainers ask the agent to build a saver."""

    FACTORY_QUEUE = "dlrover_tpu_factory"


class JobConstant:
    RDZV_JOIN_TIMEOUT_DEFAULT = 600
    # Master monitors node heartbeats; no heartbeat in this window => dead.
    NODE_HEARTBEAT_TIMEOUT = 300
    MASTER_MONITOR_INTERVAL = 15
    TRAINING_AGENT_LOOP_INTERVAL = 5
    # Max times the master relaunches one node.
    MAX_NODE_RELAUNCH_COUNT = 5


class TaskType:
    TRAINING = "training"
    EVALUATION = "evaluation"
    PREDICTION = "prediction"
    WAIT = "wait"
    TRAIN_END_CALLBACK = "train_end_callback"


class TpuEnv:
    """TPU runtime discovery (libtpu / cloud metadata style)."""

    ACCELERATOR_TYPE = "TPU_ACCELERATOR_TYPE"
    WORKER_HOSTNAMES = "TPU_WORKER_HOSTNAMES"
    WORKER_ID = "TPU_WORKER_ID"
    CHIPS_PER_HOST_BOUNDS = "TPU_CHIPS_PER_HOST_BOUNDS"


class EventReportConstants:
    TYPE_INFO = "info"
    TYPE_WARN = "warn"
    TYPE_ERROR = "error"
    ACTION_STOP = "stop"
    ACTION_RELAUNCH = "relaunch"


DEFAULT_MASTER_PORT = 22225
