"""Elastic serving gateway: continuous-batching router over replicas.

The serving-side counterpart of the trainer's elasticity stack: the
single-replica engine (serving/engine.py) scales out behind a router
that admits, queues, places and — when a replica dies — REQUEUES
requests, and that feeds load signals into the Brain so replica counts
scale like worker counts do for training.

Layers (one module each):

- :mod:`gateway`   — admission control, bounded priority queues,
  per-request deadlines;
- :mod:`scheduler` — continuous-batching placement: micro-batches per
  replica under the KV-block budget, prefix-affine + least-loaded;
- :mod:`replica`   — replica handles + manager: heartbeats, failover
  (drain + requeue, zero lost requests), graceful join/leave;
- :mod:`autoscale` — queue/TTFT/throughput signals -> Brain plan ->
  ScalePlan through a cluster Scaler, plus the provisioner closing the
  loop from cluster node events back to router membership;
- :mod:`brownout`  — per-priority brown-out shedding: watermark +
  hysteresis ladder that sheds BATCH before NORMAL, never HIGH;
- :mod:`slo`       — per-priority objectives + multi-window error-
  budget burn rates; the SLO-pressure autoscale signal;
- :mod:`loadgen`   — seeded replayable open-loop traffic generator +
  the gateway rig and the FULL-pipeline router rig (admission ->
  placement -> streamed tokens -> DONE), both driven by tier-1 tests;
- :mod:`metrics`   — Prometheus gauges/counters for all of the above;
- :mod:`router`    — the orchestrating pump, behind the step-engine
  seam (``step_engine="event" | "sweep"``).

Tenancy (who is asking, as opposed to how urgent) lives one package up
in :mod:`dlrover_tpu.serving.tenancy` — policy + accounting with no
router imports; the gateway wires it into admission (token-bucket
quotas, :class:`TenantQuotaError`), within-band weighted fair
queueing, and proportional brown-out shedding.
"""

from dlrover_tpu.serving.router.brownout import (  # noqa: F401
    BrownoutPolicy,
)
from dlrover_tpu.serving.router.gateway import (  # noqa: F401
    PRIORITY_BATCH,
    PRIORITY_HIGH,
    PRIORITY_NORMAL,
    STREAM_RESTART,
    AdmissionError,
    BrownoutShedError,
    QueueFullError,
    RequestGateway,
    ServingRequest,
    TenantQuotaError,
)
from dlrover_tpu.serving.router.metrics import RouterMetrics  # noqa: F401
from dlrover_tpu.serving.router.replica import (  # noqa: F401
    InferenceEngineAdapter,
    ReplicaDeadError,
    ReplicaHandle,
    ReplicaManager,
)
from dlrover_tpu.serving.router.router import ServingRouter  # noqa: F401
from dlrover_tpu.serving.router.scheduler import (  # noqa: F401
    ContinuousBatchScheduler,
)
from dlrover_tpu.serving.router.autoscale import (  # noqa: F401
    ReplicaProvisioner,
    ServingAutoScaler,
)
from dlrover_tpu.serving.router.slo import (  # noqa: F401
    SloEngine,
    SloObjective,
)
from dlrover_tpu.serving.tenancy import (  # noqa: F401
    TenantRegistry,
    TenantSpec,
)
