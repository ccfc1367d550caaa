"""Elastic training agent: one per host, drives worker processes through
master-coordinated rendezvous, restarts and failure reporting.

Counterpart of the reference's ``ElasticTrainingAgent`` /
``MasterRendezvousHandler`` / ``launch_agent`` (reference:
dlrover/python/elastic_agent/torch/training.py:179,359-819) re-designed for
TPU hosts:

- A "worker" is one process per host driving all local TPU chips (the JAX
  model), not one process per accelerator; ``nproc_per_node`` exists for
  CPU tests and multi-slice hosts.
- Rendezvous yields host ranks; the agent exports as
  ``DLROVER_COORDINATOR_ADDR`` the lowest rank's address and the port
  that host offered with its join (free there at that moment, a new one
  every round: ``MasterClient.join_rendezvous``), both from the master's
  comm-world reply, so workers can call ``jax.distributed.initialize``
  (the trainer does this — TPU collectives then ride ICI/DCN via XLA;
  there is no NCCL process-group setup).
- Membership changes (scale-up detected via ``num_nodes_waiting``) and
  worker failures both funnel into the same restart path, capped by
  ``max_restarts`` (reference: training.py:594-728).
"""

from __future__ import annotations

import dataclasses
import os
import signal
import subprocess
import sys
import threading
import time
from enum import Enum
from typing import Dict, List, Optional, Sequence, Tuple

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.common import comm
from dlrover_tpu.common.constants import (
    NodeEnv,
    NodeStatus,
    RendezvousName,
)
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.common.retry import RetryPolicy, is_transient
from dlrover_tpu.utils.tracing import FlightRecorder


@dataclasses.dataclass
class WorkerSpec:
    """What to run on this host."""

    entrypoint: Sequence[str]  # argv of the training program
    nproc_per_node: int = 1
    max_restarts: int = 3
    monitor_interval: float = 5.0
    network_check: bool = False
    # measure ICI/DCN collective bandwidth during the check rounds
    # (reference: dlrover-run --comm-perf-test)
    comm_perf_test: bool = False
    # leave the job when the check rounds mark this host a straggler
    # (reference: dlrover-run --exclude-straggler): the scheduler then
    # replaces the slow host instead of letting it drag every step
    exclude_straggler: bool = False
    # poll the master's mutable ParallelConfig into the trainer's
    # hot-reload file (reference: --auto_tunning + ParalConfigTuner)
    auto_tunning: bool = False
    env: Optional[Dict[str, str]] = None
    # Host the flash-checkpoint saver factory so trainers can checkpoint
    # into agent-owned shared memory (reference: training.py:580).
    flash_ckpt: bool = True
    # Persist the shm checkpoint to storage at the failure breakpoint,
    # before restarting workers (reference: --save_at_breakpoint,
    # elastic_run.py:171 + training.py:662-672).  Default ON here — the
    # reference defaults off because its torch save can be slow; the
    # zero-copy shm persist is cheap enough to always take.
    save_at_breakpoint: bool = True
    # Observability: sample host/TPU usage + tail the trainer's runtime-
    # metrics file and report upstream (reference: elastic_agent/monitor/).
    monitors: bool = True
    # Hang detection: restart workers when the global step stalls this
    # long (reference: atorch fault_tolerance/hanging_detector.py:86).
    # 0 disables.  Grace period covers compile + first-step latency.
    hang_timeout: float = 0.0
    hang_grace_period: float = 600.0


class WorkerState(str, Enum):
    RUNNING = "RUNNING"
    SUCCEEDED = "SUCCEEDED"
    FAILED = "FAILED"


@dataclasses.dataclass
class RendezvousResult:
    round: int
    group: int
    world: Dict[int, int]  # node_rank -> nproc on that node
    node_ips: Dict[int, str]
    node_ports: Dict[int, int]  # what each node offered with its join

    @property
    def coordinator(self) -> str:
        """``host:port`` of this world's ``jax.distributed`` service: the
        LOWEST rank's address and the port it offered when it joined
        this round, so every member derives the same one and no two
        worlds (another job on the host, this job's next round) share
        it."""
        rank0 = min(self.world)
        ip = self.node_ips.get(rank0) or "127.0.0.1"
        return f"{ip}:{self.node_ports[rank0]}"


class OutageEdge:
    """healthy -> failing -> recovered edge detector.

    Every master-facing loop in this module logs/accounts ONCE per
    state change, not once per tick; this is the one shared state
    machine behind that contract (heartbeat, membership poll,
    rendezvous poll, rendezvous join retry)."""

    def __init__(self):
        self.since: Optional[float] = None

    @property
    def failing(self) -> bool:
        return self.since is not None

    def fail(self) -> bool:
        """Record a failure; True exactly once per outage (the edge)."""
        if self.since is None:
            self.since = time.monotonic()
            return True
        return False

    def recover(self) -> Optional[float]:
        """Record a success; elapsed outage seconds when this ends an
        outage, else None."""
        if self.since is None:
            return None
        elapsed = time.monotonic() - self.since
        self.since = None
        return elapsed


class MasterRendezvousHandler:
    """Joins the master's elastic rendezvous and polls for the comm world
    (reference: training.py:179-311).

    Fault tolerance (ISSUE 9): the poll loop rides out transient master
    outages (each RPC already retries under ``retry_rpc``'s
    ``RetryPolicy``; an outage outliving one call's budget is absorbed
    here until the handler timeout), and every ``rejoin_check_interval``
    it verifies the master still KNOWS this node — a restarted master
    answers no, and the handler re-joins instead of polling the fresh
    master's empty world until timeout.
    """

    def __init__(
        self,
        client: MasterClient,
        node_rank: int,
        rdzv_name: str = RendezvousName.ELASTIC_TRAINING,
        local_world_size: int = 1,
        timeout: float = 600.0,
        rejoin_check_interval: float = 5.0,
        recorder: Optional[FlightRecorder] = None,
    ):
        self._client = client
        self._node_rank = node_rank
        self._rdzv_name = rdzv_name
        self._local_world_size = local_world_size
        self._timeout = timeout
        self._rejoin_check_interval = rejoin_check_interval
        self.recorder = recorder or FlightRecorder()
        self.rejoins = 0  # lost registrations re-established (lifetime)
        # this host's TPU slice (DCN granule); the master groups
        # admission by it so only COMPLETE slices train
        self._slice_id = int(os.environ.get("DLROVER_SLICE_ID") or 0)

    def _join(self) -> None:
        self._client.join_rendezvous(
            node_rank=self._node_rank,
            local_world_size=self._local_world_size,
            rdzv_name=self._rdzv_name,
            slice_id=self._slice_id,
        )
        self.recorder.record(
            "rendezvous_join", rdzv=self._rdzv_name,
            node_rank=self._node_rank,
        )

    def next_rendezvous(self) -> RendezvousResult:
        start = time.time()
        deadline = start + self._timeout
        outage = OutageEdge()
        last_join_check = time.time()
        self._retryable(self._join, deadline)
        while True:
            try:
                rdzv = RendezvousResult(*self._client.get_comm_world(
                    self._rdzv_name, self._node_rank
                ))
                outage_s = outage.recover()
                if outage_s is not None:
                    logger.info(
                        "rendezvous poll recovered after %.1fs master "
                        "outage", outage_s,
                    )
                    self.recorder.record("master_reconnected",
                                         where="rendezvous")
            except Exception as e:
                # one state-change log per outage; each get_comm_world
                # already burned a full RetryPolicy budget before
                # raising, so the cadence here is minutes, not ticks
                if not is_transient(e):
                    raise
                if outage.fail():
                    logger.warning(
                        "rendezvous poll failed transiently (%s); "
                        "holding on until the %.0fs handler timeout",
                        e, self._timeout,
                    )
                    self.recorder.record("master_outage",
                                         where="rendezvous")
                if time.time() > deadline:
                    raise TimeoutError(
                        f"rendezvous {self._rdzv_name!r} timed out after "
                        f"{self._timeout}s (master unreachable)"
                    ) from e
                time.sleep(1.0)
                continue
            if rdzv.world:
                if self._node_rank not in rdzv.world:
                    # completed without us (e.g. we were rounded out by
                    # node_unit); re-join next round
                    raise RendezvousOutError(rdzv.round)
                self.recorder.record(
                    "rendezvous_complete", rdzv=self._rdzv_name,
                    round=rdzv.round, world=sorted(rdzv.world),
                )
                return rdzv
            now = time.time()
            if now - last_join_check >= self._rejoin_check_interval:
                last_join_check = now
                try:
                    joined = self._client.rendezvous_joined(
                        self._node_rank, self._rdzv_name
                    )
                except Exception:
                    joined = True  # can't tell; keep polling
                if not joined:
                    # a restarted master lost our registration: re-join
                    # (idempotent server-side) or this poll never ends
                    logger.warning(
                        "master no longer knows this node's rendezvous "
                        "join (restarted?); re-joining round",
                    )
                    self.rejoins += 1
                    self.recorder.record(
                        "rendezvous_rejoin", rdzv=self._rdzv_name,
                        node_rank=self._node_rank,
                    )
                    self._retryable(self._join, deadline)
            if now > deadline:
                raise TimeoutError(
                    f"rendezvous {self._rdzv_name!r} timed out after "
                    f"{self._timeout}s"
                )
            time.sleep(0.2)

    def _retryable(self, fn, deadline: float) -> None:
        """Run ``fn`` (already retry_rpc-wrapped) absorbing transient
        failures until the handler deadline — a join issued INTO a
        master restart must not abort the whole rendezvous."""
        outage = OutageEdge()
        while True:
            try:
                fn()
                return
            except Exception as e:
                if not is_transient(e) or time.time() > deadline:
                    raise
                if outage.fail():  # once per outage, not per round
                    logger.warning(
                        "rendezvous join failed transiently (%s); "
                        "retrying until the handler deadline", e,
                    )
                else:
                    logger.debug("rendezvous join still failing: %s", e)
                time.sleep(1.0)


class RendezvousOutError(RuntimeError):
    def __init__(self, rnd: int):
        super().__init__(f"excluded from rendezvous round {rnd}")
        self.round = rnd


class LocalWorkerGroup:
    """The worker processes of this host."""

    def __init__(self):
        self.procs: List[subprocess.Popen] = []
        self.restart_count = 0
        # the stack-dump dir the workers were actually SPAWNED with —
        # the collector must read the same one (spec.env overrides can
        # diverge from the agent's own environment)
        self.stack_dump_dir: Optional[str] = None

    def spawn(
        self,
        spec: WorkerSpec,
        rdzv: RendezvousResult,
        node_rank: int,
        base_env: Dict[str, str],
    ) -> None:
        ranks = sorted(rdzv.world)
        # global process ranks: prefix sum over node ranks
        prefix = 0
        starts: Dict[int, int] = {}
        for r in ranks:
            starts[r] = prefix
            prefix += rdzv.world[r]
        total_procs = prefix
        coordinator = rdzv.coordinator

        # Workers must be able to import the framework even when it is run
        # from a source checkout (script entrypoints don't inherit the
        # agent's sys.path the way `-m` module entrypoints do).
        pkg_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        for local_rank in range(spec.nproc_per_node):
            env = dict(base_env)
            env.update(spec.env or {})
            prev = env.get("PYTHONPATH", "")
            if pkg_root not in prev.split(os.pathsep):
                env["PYTHONPATH"] = (
                    pkg_root + (os.pathsep + prev if prev else "")
                )
            env[NodeEnv.NODE_RANK] = str(node_rank)
            env[NodeEnv.NODE_NUM] = str(len(ranks))
            env[NodeEnv.COORDINATOR_ADDR] = coordinator
            env["DLROVER_LOCAL_RANK"] = str(local_rank)
            env["DLROVER_LOCAL_WORLD_SIZE"] = str(spec.nproc_per_node)
            env["DLROVER_WORKER_RANK"] = str(starts[node_rank] + local_rank)
            env["DLROVER_WORKER_NUM"] = str(total_procs)
            env["DLROVER_RDZV_ROUND"] = str(rdzv.round)
            # stack forensics: workers register a SIGUSR1 traceback
            # dumper here; the agent signals + collects on hang
            from dlrover_tpu.agent.monitor.stack_dump import (
                ENV_DUMP_DIR,
                default_dump_dir,
            )

            env.setdefault(ENV_DUMP_DIR, default_dump_dir())
            self.stack_dump_dir = env[ENV_DUMP_DIR]
            proc = subprocess.Popen(  # noqa: S603
                list(spec.entrypoint), env=env
            )
            self.procs.append(proc)
        logger.info(
            "Spawned %s worker(s): world=%s coordinator=%s round=%s",
            spec.nproc_per_node, rdzv.world, coordinator, rdzv.round,
        )

    def state(self) -> Tuple[WorkerState, int]:
        """Aggregate state and the first non-zero exit code (if failed)."""
        any_running = False
        for p in self.procs:
            rc = p.poll()
            if rc is None:
                any_running = True
            elif rc != 0:
                return WorkerState.FAILED, rc
        if any_running:
            return WorkerState.RUNNING, 0
        return WorkerState.SUCCEEDED, 0

    def stop(self, timeout: float = 15.0) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.time() + timeout
        for p in self.procs:
            remaining = max(0.1, deadline - time.time())
            try:
                p.wait(remaining)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(5)
        self.procs = []


class ElasticAgent:
    """Per-host agent (reference ``ElasticTrainingAgent`` training.py:359)."""

    def __init__(
        self,
        client: MasterClient,
        node_rank: int,
        spec: WorkerSpec,
        heartbeat_policy: Optional[RetryPolicy] = None,
    ):
        self._client = client
        self._node_rank = node_rank
        self._spec = spec
        # flight recorder mirroring the serving fleet's vocabulary:
        # rendezvous_join/complete/rejoin, master_outage/reconnected,
        # worker_spawn/restart, breakpoint_save
        self.recorder = FlightRecorder()
        self._handler = MasterRendezvousHandler(
            client, node_rank, local_world_size=spec.nproc_per_node,
            recorder=self.recorder,
        )
        self._group = LocalWorkerGroup()
        self._stop_heartbeat = threading.Event()
        self._heartbeat_thread: Optional[threading.Thread] = None
        # a heartbeat tick rides out short master blips INSIDE one
        # policy.call (typed + jittered + deadline-budgeted, logging
        # once per state change); an outage outliving the policy's
        # deadline flips the agent into "master outage" state — ONE
        # escalation log, bare probe per tick, never touching the
        # worker group — until a probe lands and logs the recovery
        self._hb_policy = heartbeat_policy or RetryPolicy(
            max_attempts=6, backoff_base=0.5, backoff_max=4.0,
            deadline=30.0,
        )
        self._hb_outage = OutageEdge()
        self._poll_outage = OutageEdge()
        # dlrover_agent_* metric counters (names registered in
        # utils/metric_registry.py; mirrored vocabulary of the serving
        # fleet's self-healing counters)
        self._metrics_lock = threading.Lock()
        self._metrics: Dict[str, float] = {
            "dlrover_agent_heartbeat_failures_total": 0.0,
            "dlrover_agent_master_outages_total": 0.0,
            "dlrover_agent_master_reconnects_total": 0.0,
            "dlrover_agent_rendezvous_rounds_total": 0.0,
            "dlrover_agent_restarts_total": 0.0,
            "dlrover_agent_breakpoint_saves_total": 0.0,
        }
        self._saver_factory = None
        self._training_monitor = None
        self._resource_monitor = None
        self._hang_detector = None
        self.metrics_exporter = None
        self.otlp_exporter = None
        self.profiler = None  # contprof sampler, start_metrics_exporter

    def start_metrics_exporter(self, port: int = 0) -> int:
        """Serve the agent's self-healing counters over HTTP — the
        ``dlrover_agent_*`` dict (heartbeat outages, rendezvous
        rounds/rejoins, restarts, breakpoint saves) plus the agent-side
        checkpoint-persistence counters (``dlrover_ckpt_persists_*``
        from the :class:`AsyncCheckpointSaver` living in this process),
        rendered with the metric registry's help text on ``/metrics``.
        ``port=0`` binds a kernel-assigned port (the project's
        race-free port idiom) and the chosen port is announced on
        stdout as ``DLROVER_AGENT_METRICS_PORT=<port>``.  Returns the
        bound port."""
        from dlrover_tpu.utils.profiler import MetricsExporter

        exporter = MetricsExporter(port=port)
        exporter.add_source(self.metrics)

        def _saver_metrics():
            from dlrover_tpu.agent.ckpt_saver import (
                AsyncCheckpointSaver,
            )

            saver = AsyncCheckpointSaver.get_ckpt_saver()
            if saver is None:
                return {}
            return saver.metrics()

        exporter.add_source(_saver_metrics)
        # always-on sampling profiler (role "agent"): live flame at
        # /debug/prof(+/collapsed); flight-recorder dumps (rendezvous
        # rejoins, master outages, worker restarts) freeze a snapshot
        # ref so an incident's CPU state survives the live tables
        from dlrover_tpu.utils.contprof import ContinuousProfiler

        prof = ContinuousProfiler(role="agent")
        prof.start()
        self.profiler = prof
        exporter.attach_profiler(prof)
        self.recorder.attach_profiler(prof)
        exporter.start()
        self.metrics_exporter = exporter
        # OTLP push into the fleet collector when one is announced
        # (DLROVER_TELEMETRY_ENDPOINT); inert otherwise.  The agent's
        # counters then appear on /fleet/metrics next to the router's
        # and the master's — one pane across the planes.
        from dlrover_tpu.utils.otlp import OtlpExporter

        otlp = OtlpExporter.from_env(
            resource={"service.name": "agent",
                      "node.rank": str(self._node_rank)})
        otlp.add_metrics_source(self.metrics)
        otlp.add_metrics_source(_saver_metrics)
        otlp.add_profile_source(lambda: [prof.snapshot(top=64)])
        otlp.start()
        self.otlp_exporter = otlp
        exporter.add_source(otlp.metrics)
        # stdout announce, flushed: a supervisor piping us reads the
        # port the same way it reads the master/worker announces
        from dlrover_tpu.common.constants import NodeEnv

        print(f"{NodeEnv.AGENT_METRICS_ANNOUNCE_PREFIX}"
              f"{exporter.port}", flush=True)
        logger.info("agent metrics exporter on 127.0.0.1:%d",
                    exporter.port)
        return exporter.port

    def stop_metrics_exporter(self) -> None:
        if self.metrics_exporter is not None:
            self.metrics_exporter.stop()
            self.metrics_exporter = None
        otlp = getattr(self, "otlp_exporter", None)
        if otlp is not None:
            otlp.stop()
            self.otlp_exporter = None
        prof = getattr(self, "profiler", None)
        if prof is not None:
            prof.stop()
            self.profiler = None

    def _count(self, name: str, n: float = 1.0) -> None:
        with self._metrics_lock:
            self._metrics[name] = self._metrics.get(name, 0.0) + n

    def metrics(self) -> Dict[str, float]:
        """Agent-side counters + the rendezvous handler's rejoin count
        (metric source contract: plain name -> value floats)."""
        with self._metrics_lock:
            out = dict(self._metrics)
        out["dlrover_agent_rendezvous_rejoins_total"] = float(
            self._handler.rejoins)
        return out

    # -- flash checkpoint -------------------------------------------------
    def _start_ckpt_factory(self) -> None:
        """Serve saver-creation requests from trainers (reference:
        AsyncCheckpointSaver.start_async_saving_ckpt, training.py:580)."""
        from dlrover_tpu.agent.ckpt_saver import SaverFactory

        self._saver_factory = SaverFactory()
        self._saver_factory.start()

    def _save_shm_checkpoint(self, commit_async: bool = False,
                             commit_timeout: float = 30.0) -> None:
        """Persist any in-memory checkpoint before a restart/exit wipes the
        workers (reference: training.py:662-672).

        The shard writes always run synchronously HERE, before any worker
        respawn — the lock reclaim inside is only sound while no worker
        is alive.  ``commit_async=True`` (the restart path) moves just the
        cross-node done-file wait off-thread: when a PEER node died that
        wait cannot finish and must not delay this node's re-rendezvous.
        The terminal (max-restarts) path keeps the commit synchronous so
        a single-host job's last checkpoint is fully published before the
        process exits.
        """
        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

        saver = AsyncCheckpointSaver.get_ckpt_saver()
        if saver is None:
            return
        try:
            saver.save_shm_to_storage(
                commit_async=commit_async, commit_timeout=commit_timeout)
            self._count("dlrover_agent_breakpoint_saves_total")
            self.recorder.record("breakpoint_save",
                                 commit_async=commit_async)
        except Exception:
            logger.exception("persisting shm checkpoint failed")

    def _collect_hang_stacks(self) -> str:
        """On hang: SIGUSR1 the workers, ship their all-thread tracebacks
        through the diagnosis channel (data_cls="stack"), and return a
        one-line summary of the deepest frames for the failure reason.

        Reference counterpart: the py-spy-style stack collector feeding
        diagnosis (dlrover/python/elastic_agent/datacollector/
        cuda_log_collector.py:20)."""
        from dlrover_tpu.agent.monitor.stack_dump import (
            format_stack_report,
            summarize_stacks,
            trigger_stack_dumps,
        )

        pids = [p.pid for p in self._group.procs
                if p.poll() is None]
        if not pids:
            return ""
        try:
            dumps = trigger_stack_dumps(
                pids, dump_dir=self._group.stack_dump_dir)
        except Exception:
            logger.exception("stack-dump collection failed")
            return ""
        report = format_stack_report(dumps)
        try:
            self._client.report_diagnosis_data(comm.DiagnosisReportData(
                data_cls="stack",
                data_content=report,
                node_id=self._node_rank,
                timestamp=time.time(),
            ))
        except Exception as e:
            logger.warning("stack diagnosis report failed: %s", e)
        logger.error("hang stack dumps:\n%s", report)
        return summarize_stacks(dumps)

    # -- heartbeats ------------------------------------------------------
    def _heartbeat_loop(self, interval: float = 15.0) -> None:
        """One beat per tick, hardened (ISSUE 9): short blips are
        absorbed inside the tick by the ``RetryPolicy`` (which logs once
        per state change by contract); an outage outliving the policy's
        deadline logs ONE escalation and degrades to a silent bare probe
        per tick until the master answers again.  The worker group is
        NEVER touched from here — a master outage is a control-plane
        problem; killing healthy training over it would manufacture the
        exact downtime this agent exists to prevent."""
        while not self._stop_heartbeat.wait(interval):
            in_outage = self._hb_outage.failing
            try:
                if in_outage:
                    # bare probe: the policy's own retries/logs would
                    # re-announce the same outage once per tick
                    self._client.report_heart_beat(time.time())
                else:
                    self._hb_policy.call(
                        self._client.report_heart_beat, time.time(),
                        what="report_heart_beat",
                    )
            except ValueError as e:
                # grpc raises ValueError when invoked on a closed channel
                # (owner shut the client without stop_heartbeat) — beating
                # on is pure noise then.  Any OTHER ValueError (e.g. a
                # serialization bug) must NOT silently kill the thread:
                # the master would synthesize this node as dead.
                if self._stop_heartbeat.is_set() or getattr(
                    self._client, "closed", False
                ):
                    return
                logger.warning("heartbeat failed: %s", e)
            except Exception as e:
                # a shutdown that closed the channel mid-RPC is expected
                if self._stop_heartbeat.is_set():
                    continue
                self._count("dlrover_agent_heartbeat_failures_total")
                if self._hb_outage.fail():
                    self._count("dlrover_agent_master_outages_total")
                    self.recorder.record("master_outage",
                                         where="heartbeat")
                    logger.warning(
                        "heartbeat still failing after the retry "
                        "deadline (%s); entering master-outage state — "
                        "workers keep running, probing once per %.0fs "
                        "tick", e, interval,
                    )
                else:
                    logger.debug("heartbeat probe failed: %s", e)
            else:
                outage_s = self._hb_outage.recover()
                if outage_s is not None:
                    self._count("dlrover_agent_master_reconnects_total")
                    self.recorder.record("master_reconnected",
                                         where="heartbeat",
                                         outage_s=round(outage_s, 1))
                    logger.info(
                        "heartbeat recovered after %.1fs master outage",
                        outage_s,
                    )

    def start_heartbeat(self) -> None:
        self._heartbeat_thread = threading.Thread(
            target=self._heartbeat_loop, daemon=True, name="agent-heartbeat"
        )
        self._heartbeat_thread.start()

    def stop_heartbeat(self, timeout: float = 5.0) -> None:
        """Stop and join the heartbeat thread BEFORE the master channel
        closes, so no RPC races the close (advisor r2 weak #7)."""
        self._stop_heartbeat.set()
        if self._heartbeat_thread is not None:
            self._heartbeat_thread.join(timeout)
            self._heartbeat_thread = None

    # -- lifecycle -------------------------------------------------------
    def _initialize_workers(self) -> RendezvousResult:
        while True:
            try:
                rdzv = self._handler.next_rendezvous()
                break
            except RendezvousOutError:
                time.sleep(1.0)
        self._count("dlrover_agent_rendezvous_rounds_total")
        self._group.spawn(self._spec, rdzv, self._node_rank, dict(os.environ))
        self.recorder.record(
            "worker_spawn", round=rdzv.round,
            world=sorted(rdzv.world), procs=self._spec.nproc_per_node,
        )
        self._client.report_node_status(self._node_rank, NodeStatus.RUNNING)
        return rdzv

    def _restart_workers(self, reason: str,
                         persist_first: bool = False) -> RendezvousResult:
        logger.info("Restarting workers: %s", reason)
        self._count("dlrover_agent_restarts_total")
        self.recorder.record(
            "worker_restart", reason=reason,
            restart_count=self._group.restart_count + 1,
        )
        self._group.stop()
        if persist_first:
            # growth restart: peers are alive, commit synchronously so
            # the regrown world's restore-step consensus finds the
            # committed storage step (a replacement host has no shm).
            # Must run AFTER group.stop(): the shm lock reclaim inside
            # the save is only sound with no worker alive.  The wait is
            # BOUNDED SHORT: if the step being committed still carries a
            # dead peer's shard, its done-file never appears, and a long
            # stall here staggers this node's rendezvous join past the
            # admission window (measured: the multislice regrow flapped
            # between 2- and 4-worlds exactly this way).  The regrown
            # world's restore does not depend on this commit — survivor
            # shm covers it via the GSPMD resharding restore; storage is
            # the fallback tier only.
            self._save_shm_checkpoint(commit_async=False,
                                      commit_timeout=8.0)
        self._group.restart_count += 1
        rdzv = self._initialize_workers()
        # EVERY restart (failure, hang, rescale) re-enters restore +
        # compile; re-arm the progress clock and the hang grace period so
        # that latency is not mistaken for a fresh hang.
        if self._training_monitor is not None:
            self._training_monitor.reset_progress_clock()
        if self._hang_detector is not None:
            self._hang_detector.reset()
        return rdzv

    def _recover_failed_workers(
        self, reason: str, level: str, rc: int
    ) -> Optional[int]:
        """Shared failure/hang recovery: report upstream, persist the
        in-memory checkpoint, then restart (or give up past max_restarts).
        Returns an exit code to propagate, or None after a restart."""
        self._client.report_failure(
            reason,
            level=level,
            node_rank=self._node_rank,
            restart_count=self._group.restart_count,
        )
        # stop remaining workers FIRST so a crashed writer's shm lock is
        # safely reclaimable, then persist the in-memory checkpoint
        # (reference: training.py:662-672)
        self._group.stop()
        terminal = self._group.restart_count >= self._spec.max_restarts
        if self._spec.save_at_breakpoint:
            self._save_shm_checkpoint(commit_async=not terminal)
        if terminal:
            self._client.report_node_status(self._node_rank, NodeStatus.FAILED)
            logger.error(
                "Exhausted %s restarts (%s); failing",
                self._spec.max_restarts,
                reason,
            )
            return rc
        self._restart_workers(reason)
        return None

    def run(self) -> int:
        """Monitor loop (reference training.py:577-728). Returns exit code."""
        self.start_heartbeat()
        self._training_monitor = None
        self._resource_monitor = None
        hang_detector = None
        if self._spec.monitors:
            from dlrover_tpu.agent.monitor.resource import ResourceMonitor
            from dlrover_tpu.agent.monitor.training import TrainingMonitor

            self._training_monitor = TrainingMonitor(self._client)
            self._training_monitor.start()
            self._resource_monitor = ResourceMonitor(self._client)
            self._resource_monitor.start()
        self._paral_tuner = None
        if self._spec.auto_tunning:
            from dlrover_tpu.agent.config.paral_config_tuner import (
                ParalConfigTuner,
            )

            self._paral_tuner = ParalConfigTuner(self._client)
            self._paral_tuner.start()
        if self._spec.hang_timeout > 0:
            if self._training_monitor is None:
                logger.warning(
                    "hang_timeout=%s has no effect: hang detection needs "
                    "the training monitor (set monitors=True)",
                    self._spec.hang_timeout,
                )
            else:
                from dlrover_tpu.agent.monitor.hang import HangingDetector

                hang_detector = HangingDetector(
                    self._training_monitor.seconds_without_progress,
                    timeout=self._spec.hang_timeout,
                    grace_period=self._spec.hang_grace_period,
                )
                hang_detector.arm()
        self._hang_detector = hang_detector
        if self._spec.flash_ckpt:
            self._start_ckpt_factory()
        if self._spec.network_check:
            ok, reason = run_network_check(self._client, self._node_rank,
                                           self._spec)
            if not ok:
                logger.error("Network check failed: %s", reason)
                self._client.report_node_status(
                    self._node_rank, NodeStatus.FAILED
                )
                return 1
            if self._spec.exclude_straggler:
                try:
                    stragglers, _ = self._client.check_straggler()
                except Exception as e:
                    stragglers = []
                    logger.warning("straggler query failed: %s", e)
                if self._node_rank in stragglers:
                    logger.error(
                        "This host is a straggler (slower than the group "
                        "median threshold); leaving the job so the "
                        "scheduler replaces it"
                    )
                    self._client.report_failure(
                        "straggler excluded", level="straggler",
                        node_rank=self._node_rank, restart_count=0,
                    )
                    self._client.report_node_status(
                        self._node_rank, NodeStatus.FAILED
                    )
                    return 1
        self._initialize_workers()
        spec = self._spec
        try:
            while True:
                time.sleep(spec.monitor_interval)
                state, rc = self._group.state()
                if state == WorkerState.SUCCEEDED:
                    try:
                        self._client.report_node_status(
                            self._node_rank, NodeStatus.SUCCEEDED
                        )
                    except Exception:
                        # a local master that exits on dataset completion
                        # may already be gone — success stands regardless
                        logger.info("master gone before final status report")
                    logger.info("Workers finished successfully")
                    return 0
                if state == WorkerState.FAILED:
                    recovered = self._recover_failed_workers(
                        f"worker exit code {rc}", level="error", rc=rc or 1
                    )
                    if recovered is not None:
                        return recovered
                    continue
                if hang_detector is not None and hang_detector.check_once():
                    stalled = self._training_monitor.seconds_without_progress()
                    where = self._collect_hang_stacks()
                    recovered = self._recover_failed_workers(
                        f"training hang: no global-step progress for "
                        f"{stalled:.0f}s"
                        + (f"; stacks: {where}" if where else ""),
                        level="hang",
                        rc=1,
                    )
                    if recovered is not None:
                        return recovered
                    continue
                # healthy: check membership growth.  An unreachable master
                # must not kill healthy workers (it may be restarting, or —
                # local mode — already exited after the dataset finished).
                try:
                    waiting = self._client.num_nodes_waiting(
                        RendezvousName.ELASTIC_TRAINING
                    )
                except Exception as e:
                    # one warning per outage, not per monitor tick (the
                    # heartbeat thread owns the outage counters; this
                    # poll only keeps its own log state)
                    if self._poll_outage.fail():
                        logger.warning(
                            "membership poll failed (%s); workers keep "
                            "running, polling on", e,
                        )
                    else:
                        logger.debug("membership poll still failing: %s", e)
                    continue
                outage_s = self._poll_outage.recover()
                if outage_s is not None:
                    logger.info(
                        "membership poll recovered after %.1fs", outage_s,
                    )
                if waiting > 0:
                    self._restart_workers(
                        f"{waiting} node(s) waiting to join",
                        persist_first=True,
                    )
        finally:
            self.stop_heartbeat()
            if self._training_monitor is not None:
                self._training_monitor.stop()
            if self._resource_monitor is not None:
                self._resource_monitor.stop()
            if self._paral_tuner is not None:
                self._paral_tuner.stop()
            self._group.stop()
            self._save_shm_checkpoint()
            if self._saver_factory is not None:
                self._saver_factory.stop()


# ---------------------------------------------------------------------------
# network / node check
# ---------------------------------------------------------------------------


def run_network_check(
    client: MasterClient,
    node_rank: int,
    spec: WorkerSpec,
    rounds: int = 2,
    check_timeout: float = 300.0,
    result_timeout: float = 120.0,
) -> Tuple[bool, str]:
    """Two grouped check rounds; the master intersects failures to localize
    the faulty host (reference: NodeCheckElasticAgent training.py:861-1010
    and NetworkCheckRendezvousManager rdzv_manager.py:349-530).

    The check workload runs a matmul on every local chip and — when the
    rendezvous grouped us with peers — a cross-host collective over the
    group (jax.distributed world of the group members), so DCN faults
    between hosts are observable, not just local chip health.
    """
    from dlrover_tpu.common.constants import NetworkFailureReason

    handler = MasterRendezvousHandler(
        client,
        node_rank,
        rdzv_name=RendezvousName.NETWORK_CHECK,
        local_world_size=spec.nproc_per_node,
    )
    for _ in range(rounds):
        try:
            rdzv = handler.next_rendezvous()
        except (TimeoutError, RendezvousOutError) as e:
            return False, f"check rendezvous failed: {e}"
        group_ranks = sorted(rdzv.world)
        env = {
            **os.environ,
            "DLROVER_CHECK_GROUP": str(rdzv.group),
            "DLROVER_CHECK_RANK": str(group_ranks.index(node_rank)),
            "DLROVER_CHECK_WORLD": str(len(group_ranks)),
            "DLROVER_CHECK_COORDINATOR": rdzv.coordinator,
        }
        if spec.comm_perf_test:
            env["DLROVER_COMM_PERF"] = "1"
        start = time.time()
        try:
            proc = subprocess.run(  # noqa: S603
                [sys.executable, "-m", "dlrover_tpu.trainer.node_check.tpu"],
                env=env,
                capture_output=True,
                timeout=check_timeout,
            )
            ok = proc.returncode == 0
            stderr = proc.stderr
            if ok and spec.comm_perf_test:
                for line in proc.stdout.decode(errors="replace").splitlines():
                    if line.startswith("comm perf:"):
                        logger.info("node %s %s", node_rank, line)
        except subprocess.TimeoutExpired:
            # A hung runtime is exactly what the check exists to catch.
            ok, stderr = False, b"node check timed out"
        elapsed = time.time() - start
        client.report_network_check_result(node_rank, ok, elapsed)
        if not ok:
            logger.warning(
                "node check failed: %s", stderr[-500:].decode(errors="replace")
            )
    # Wait for peers' reports: success stays (False, WAITING_NODE) until
    # every group member has reported its round.
    deadline = time.time() + result_timeout
    while True:
        success, reason = client.network_check_success()
        if success or reason != NetworkFailureReason.WAITING_NODE:
            return success, reason
        if time.time() > deadline:
            return False, reason
        time.sleep(1.0)
