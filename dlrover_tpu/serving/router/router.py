"""The serving router: one pump tying gateway, scheduler and replicas
together, with failover and autoscale hooks.

Each :meth:`ServingRouter.step` round:

1. expire queued requests whose deadline passed (gateway);
2. reap dead replicas (failed engines / stale heartbeats) and requeue
   their in-flight requests at the front of the line — the zero-lost-
   requests failover;
3. place queued requests onto replicas (continuous-batching scheduler:
   KV-budget gated, prefix-affine, least-loaded) — a placement that
   fails mid-submit also fails the replica over, losing nothing;
4. pump every live replica's engine one step, completing requests and
   recording TTFT / token throughput;
5. retire drained replicas (graceful leave: the scale-down path);
6. refresh gauges and, if attached, let the autoscaler act.

The pump is deliberately synchronous and single-threaded: chaos tests
drive it step-by-step deterministically, and a deployment that wants a
background loop wraps :meth:`serve_forever` in a thread — concurrency
is a caller policy, not baked in.

Step engines (the data-plane raw-speed seam, ``step_engine=``):

- ``"event"`` (default, the measured winner — PERF.md "Router raw
  speed" records the A/B): expiry pops only DUE entries off the
  gateway's deadline heap, cancellation visits only requests whose
  caller actually withdrew them (``ServingRequest.cancel`` enqueues an
  event), TTFT recording drains per-replica first-token events, and
  placement runs the scheduler's incremental index — an idle step does
  O(replicas) work instead of O(replicas x queued + inflight);
- ``"sweep"``: the historical full-scan semantics, kept runnable so
  the choice stays auditable (bench A/B) and equivalence-testable
  (same seeded workload -> same terminal states, pinned in
  tests/test_step_engine.py).

Both engines observe the same step-phase histograms
(``serving_step_phase_seconds{phase=...}``) and step-lock hold-time
histogram (``serving_step_lock_hold_seconds``) — instrument first,
then attack what the histograms name.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from dlrover_tpu.common.constants import (
    SERVING_REQUEST_TERMINAL_STATES,
    ReplicaStatus,
    ServingRequestState,
)
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.serving.router.gateway import (
    PRIORITY_BATCH,
    PRIORITY_NORMAL,
    RequestGateway,
    ServingRequest,
)
from dlrover_tpu.serving.router.hedge import HedgePolicy
from dlrover_tpu.serving.router.metrics import RouterMetrics
from dlrover_tpu.serving.router.replica import (
    ReplicaDeadError,
    ReplicaHandle,
    ReplicaManager,
    StaleRequestError,
    base_replica_name,
)
from dlrover_tpu.serving.router.scheduler import ContinuousBatchScheduler
from dlrover_tpu.serving.tenancy.registry import TENANT_CLASSES
from dlrover_tpu.utils.profiler import PhaseSpans, event, span


def _tid(req: ServingRequest) -> Optional[str]:
    """The request's trace_id for histogram exemplars (None untraced)."""
    return None if req.trace is None else req.trace.trace_id


def _noop_phase(_phase) -> None:
    """Stand-in for ContinuousProfiler.set_phase when no profiler is
    attached — keeps the step loop's phase marks unconditional."""
    return None


@dataclasses.dataclass
class DrainedReplica:
    """Lightweight record of a retired replica (the handle — and its
    engine, i.e. model weights — must NOT be retained here: a
    long-running deployment cycling replicas would leak one engine per
    rotation)."""

    name: str
    node: object = None


class ServingRouter:
    """Admission -> placement -> generation -> completion, elastically."""

    # flight-recorder dumps emitted per reason per step; the rest of a
    # mass failure (a stall expiring a whole queue at once) is one
    # summary line instead of hundreds of multi-KB records
    MAX_DUMPS_PER_STEP = 8

    #: step-engine candidates behind the seam (see module docstring)
    STEP_ENGINES = ("event", "sweep")

    def __init__(
        self,
        gateway: Optional[RequestGateway] = None,
        scheduler: Optional[ContinuousBatchScheduler] = None,
        manager: Optional[ReplicaManager] = None,
        metrics: Optional[RouterMetrics] = None,
        cancel_inflight_on_expiry: bool = False,
        brownout=None,
        slo=None,
        step_engine: str = "event",
        tenant_spec_file: Optional[str] = None,
        hedge: Optional[HedgePolicy] = None,
    ):
        if step_engine not in self.STEP_ENGINES:
            raise ValueError(
                f"unknown step_engine {step_engine!r} "
                f"(one of {self.STEP_ENGINES})")
        self.step_engine = step_engine
        self._incremental = step_engine == "event"
        # policy knob: when True, a request whose deadline passes MID-
        # GENERATION is aborted and a CANCEL is sent to its replica so
        # the engine slot + KV blocks are reclaimed for live traffic;
        # when False (default, the historical behavior) work already
        # placed is allowed to finish — its cost is sunk and the late
        # answer may still be useful to a caller polling result()
        self.cancel_inflight_on_expiry = bool(cancel_inflight_on_expiry)
        self.gateway = gateway or RequestGateway()
        # per-priority brown-out controller (brownout.BrownoutPolicy):
        # when armed, the step loop drives its watermark and applies
        # the stage's shedding — BATCH admissions refused first, then
        # in-flight BATCH cancelled, then NORMAL refused; HIGH never.
        # None (default) keeps the historical all-bands-equal behavior.
        self.brownout = brownout
        if brownout is not None:
            self.gateway.brownout = brownout
        self.scheduler = scheduler or ContinuousBatchScheduler()
        self.manager = manager or ReplicaManager()
        self.metrics = metrics or RouterMetrics()
        # the step engine propagates into the gateway (deadline heap +
        # cancel events vs full scans) and the scheduler (incremental
        # placement index vs full rescan) — one knob, one behavior,
        # set BEFORE any submission can reach either
        self.gateway.incremental = self._incremental
        self.scheduler.incremental = self._incremental
        # per-priority SLO burn-rate engine (slo.SloEngine): fed by the
        # step loop's completion/expiry stream; its pressure signal is
        # sampled by the autoscaler next to the load windows.  None
        # (default) keeps the historical load-only behavior.
        self.slo = slo
        # gray-failure hedging ("The Tail at Scale"): when armed, the
        # step loop re-dispatches a stalled RUNNING request to a second
        # healthy replica — first DONE wins, the loser is CANCELled,
        # and the client stream stays byte-identical to an unhedged
        # run (stream_owner gate + the pump's terminal-state dedup).
        # None (default) keeps the historical single-attempt behavior:
        # the S1-S13 chaos rows and the step-engine equivalence suite
        # run byte-for-byte unchanged with hedging disarmed.
        self.hedge = hedge
        # rid -> live hedge record ({"req", "primary_name",
        # "primary_erid", "hedge_name", "hedge_erid"}); touched only
        # on the single-threaded step path (decisions under the step
        # lock, deliveries right after release — same discipline as
        # placements)
        self._hedges: Dict[int, dict] = {}
        self.hedge_dispatched = 0
        self.hedge_won = 0
        self.hedge_cancelled = 0
        self.hedge_budget_exhausted = 0
        self.hedge_promoted = 0
        # demoted-replica count from the latest suspicion sweep (the
        # serving_replica_suspect gauge's feed)
        self._suspect_count = 0
        self.autoscaler = None  # attached via ServingAutoScaler(router=...)
        # replica base name -> the control-plane trace that created it
        # ({"trace_id", "span_id", ...attrs}): written by the autoscale
        # trace stitcher and the fleet coordinator, read by the step
        # loop to stamp cross-plane span links on attempt spans ("this
        # placement landed on the replica THAT autoscale decision /
        # fleet borrow created")
        self.replica_origins: Dict[str, dict] = {}
        # the gateway owns the tracer (requests are traced from
        # admission); the router only needs it for fabric events and
        # failure dumps — expose it so exporters/supervisors reach one
        # surface
        self.tracer = self.gateway.tracer
        self.recorder = self.tracer.recorder
        # contprof.ContinuousProfiler via attach_profiler: the step
        # loop marks phases on it (self-time attribution next to the
        # wall-clock phase histograms) and flight dumps freeze a
        # snapshot ref.  None (default) costs one noop call per phase
        self.profiler = None
        # drained-replica records awaiting pickup (the autoscaler
        # finishes node removal); bounded so unclaimed records from
        # manual drains can never accumulate without limit
        self.drained: "deque[DrainedReplica]" = deque(maxlen=256)
        # same, for replicas that DIED (crash / stale heartbeat): their
        # cluster nodes are still alive and must be retired too, or the
        # scaler's node accounting drifts one node per crash
        self.dead: "deque[DrainedReplica]" = deque(maxlen=256)
        self._lock = threading.RLock()
        # tenant QoS spec persistence (tenancy satellite): a JSON file
        # of TenantSpec contracts loaded at construction and re-loaded
        # live on request — SIGHUP (arm_tenant_reload_signal) or an
        # admin endpoint both just call request_tenant_reload(); the
        # actual file read happens at the TOP of the next step, before
        # the step lock, so reload never does blocking I/O under it
        # (DL003) and never races admission mid-resolve
        self._tenant_spec_file: Optional[str] = tenant_spec_file
        self._tenant_reload_pending = False
        if tenant_spec_file is not None:
            self.reload_tenants()

    # ------------------------------------------------------- profiling
    def attach_profiler(self, prof) -> None:
        """Wire a :class:`~dlrover_tpu.utils.contprof.ContinuousProfiler`
        (role "router"): the step loop marks its phases on it so
        samples landing mid-step attribute to a phase (self-time — the
        wall-clock phase histograms cannot split running from
        waiting), and every flight-recorder dump freezes a snapshot
        ref (``profile_ref``) at incident time."""
        self.profiler = prof
        self.recorder.attach_profiler(prof)

    def profile_snapshots(self, top: int = 64) -> List[dict]:
        """Profiler snapshots this router can speak for: its own plus
        the latest tables its REMOTE replicas shipped over STATS (role
        "worker", tagged with the replica name as ``source``) — the
        list an OTLP ``add_profile_source`` pushes so ``/fleet/profile``
        merges ≥2 process roles through one exporter."""
        snaps: List[dict] = []
        prof = self.profiler
        if prof is not None:
            snaps.append(prof.snapshot(top=top))
        with self._lock:
            handles = list(self.manager.replicas.items())
        for name, handle in handles:
            fn = getattr(handle.engine, "profile_snapshot", None)
            if fn is None:
                continue
            try:
                snap = fn()
            except Exception:
                continue
            if isinstance(snap, dict):
                snap = dict(snap)
                snap.setdefault("source", name)
                snaps.append(snap)
        return snaps

    # ------------------------------------------------------ membership
    def join_replica(self, name: str, engine, node=None,
                     now: Optional[float] = None) -> ReplicaHandle:
        with self._lock:
            handle = self.manager.join(
                ReplicaHandle(name, engine, node=node), now=now)
        self.recorder.record("replica_join", replica=name, now=now)
        if handle.probation_until > handle.joined_at:
            # crash-loop damping kicked in: the join is visible in the
            # flight recorder WITH its cooldown, so a postmortem shows
            # why the fleet count and the placement count disagree
            self.recorder.record(
                "replica_probation", replica=name,
                until=handle.probation_until, now=now)
        return handle

    def begin_drain(self, name: str) -> Optional[ReplicaHandle]:
        """Graceful leave, phase 1: stop placing onto the replica; its
        in-flight requests finish.  Phase 2 (retirement) happens in
        :meth:`step` once it is empty."""
        with self._lock:
            handle = self.manager.begin_drain(name)
        if handle is not None:
            self.recorder.record("replica_drain", replica=name)
        return handle

    def fail_replica(self, name: str) -> None:
        """Chaos/ops hook: the replica dies NOW; next step fails it over."""
        with self._lock:
            handle = self.manager.get(name)
            if handle is not None:
                handle.fail()

    @property
    def replica_names(self) -> List[str]:
        return list(self.manager.replicas)

    # -------------------------------------------- tenant spec reload
    def request_tenant_reload(self) -> None:
        """Ask for a live tenant-spec reload; honored at the top of the
        next :meth:`step`.  Safe from a signal handler or an admin
        endpoint thread — it only flips a flag."""
        self._tenant_reload_pending = True

    def reload_tenants(self) -> tuple:
        """Reload tenant specs from the configured file NOW (in place:
        usage books survive, dropped tenants leave, quota buckets
        re-arm).  Returns ``(registered, removed)``."""
        if self._tenant_spec_file is None:
            return (0, 0)
        registered, removed = self.gateway.tenants.reload_file(
            self._tenant_spec_file)
        logger.info(
            "tenant specs reloaded from %s: %d registered, %d removed",
            self._tenant_spec_file, registered, removed)
        return registered, removed

    def arm_tenant_reload_signal(self) -> bool:
        """Install a SIGHUP handler that requests a live tenant-spec
        reload (deployment convenience; main thread only — returns
        False where signals are unavailable)."""
        try:
            import signal

            signal.signal(
                signal.SIGHUP,
                lambda *_: self.request_tenant_reload())
            return True
        except (ValueError, OSError, AttributeError):
            # not the main thread, or a platform without SIGHUP —
            # request_tenant_reload() stays callable directly
            return False

    # --------------------------------------------------------- client
    def submit(
        self,
        prompt_ids,
        max_new_tokens: int,
        priority: int = PRIORITY_NORMAL,
        timeout: Optional[float] = None,
        now: Optional[float] = None,
        tenant: Optional[str] = None,
    ) -> ServingRequest:
        try:
            with span("dlrover.router.submit"):
                req = self.gateway.submit(
                    prompt_ids, max_new_tokens, priority=priority,
                    timeout=timeout, now=now, tenant=tenant,
                )
        except Exception:
            self.metrics.rejected = self.gateway.rejected
            raise
        self.metrics.submitted = self.gateway.submitted
        req._on_gap = self.metrics.observe_token_gap
        return req

    # ----------------------------------------------------------- pump
    def step(self, now: Optional[float] = None) -> List[ServingRequest]:
        """One router round; returns the requests completed by it."""
        # in the profiler's trace: the round, and under it one span per
        # phase (``dlrover.router.phase.<name>``, the names of the
        # step-phase histogram); each replica's pump is a span of its
        # own under the pump phase
        phases = PhaseSpans("dlrover.router.phase.")
        with span("dlrover.router.step"):
            try:
                return self._step_round(now, phases)
            finally:
                phases.close()

    def _step_round(self, now: Optional[float],
                    phases: PhaseSpans) -> List[ServingRequest]:
        now = time.monotonic() if now is None else now
        perf = time.perf_counter
        phase = self.metrics.observe_step_phase
        # per-phase SELF-time attribution: mark the phase on the
        # profiler so its samples landing on this thread mid-step know
        # which phase they hit (noop call per phase when unattached)
        prof = self.profiler
        set_phase = prof.set_phase if prof is not None else _noop_phase

        def mark(name: Optional[str]) -> None:
            set_phase(name)
            phases.enter(name)

        # live tenant-spec reload, OUTSIDE the step lock (file I/O):
        # requested by SIGHUP or an admin endpoint, applied here so the
        # new contracts are in force for this round's admissions
        if self._tenant_reload_pending:
            self._tenant_reload_pending = False
            try:
                self.reload_tenants()
            except Exception as e:  # a bad file must not kill the pump
                logger.warning("tenant spec reload failed: %s", e)
        # flight-recorder dumps requested during this round: flushed
        # AFTER the step lock is released — serializing span trees and
        # logging must not extend the critical section that placement
        # and membership calls contend on
        dumps: List[tuple] = []
        # CANCEL deliveries requested during this round: (handle, erid)
        # pairs COLLECTED under the step lock, TRANSMITTED after its
        # release — for a remote replica delivery is a frame send, and
        # blocking socket I/O under the step lock is the stall class
        # dlint DL003 exists to forbid
        cancels: List[tuple] = []
        with self._lock:
            t_lock = t_prev = perf()
            mark("expire")
            # 1. deadline expiry (event engine: heap-pop only DUE
            # entries; sweep engine: scan every queued request)
            for req in self.gateway.expire(now, dump=False):
                if self.slo is not None:
                    # an expiry IS an SLO violation: the answer never
                    # arrived inside any target
                    self.slo.observe_violation(
                        req.priority, now,
                        tenant_class=self.gateway.tenant_class(
                            req.tenant))
                if req.trace is not None:
                    dumps.append(
                        ("deadline_expired", req.trace.trace_id))
            t = perf()
            phase("expire", t - t_prev)
            t_prev = t
            mark("cancel")

            # 1b. cancellation sweep: queued client withdrawals leave
            # the queue here; in-flight withdrawals — and, under the
            # cancel_inflight_on_expiry policy, in-flight requests past
            # their deadline — abort now and queue a CANCEL delivery so
            # the replica's slot and KV blocks return to live traffic
            for req in self.gateway.take_cancelled(now, dump=False):
                if req.trace is not None:
                    dumps.append(("cancelled", req.trace.trace_id))
            if self._incremental:
                self._inflight_sweep_events(now, cancels, dumps)
            else:
                self._inflight_sweep_scan(now, cancels, dumps)
            t = perf()
            phase("cancel", t - t_prev)
            t_prev = t
            mark("brownout")

            # 1c. brown-out watermark + per-priority shedding: DECIDE
            # the stage under the step lock (pure arithmetic over the
            # live ledgers), queue the band's CANCEL deliveries for
            # after release exactly like the expiry sweep above —
            # BATCH sheds first, then NORMAL; HIGH is never touched
            if self.brownout is not None:
                self._brownout_sweep(now, cancels, dumps)
            self.metrics.cancelled = self.gateway.cancelled
            self.metrics.timed_out = self.gateway.timed_out
            t = perf()
            phase("brownout", t - t_prev)
            t_prev = t
            mark("failover")

            # 2. health + failover: fold each replica's raw phi
            # verdict into its effective demotion flag (gray zone —
            # placement weight only, NO failover), then reap the
            # actually-dead and requeue their in-flight
            self._suspect_count = self.manager.update_suspects(now)
            self._reap(now, dumps=dumps)
            t = perf()
            phase("failover", t - t_prev)
            t_prev = t
            mark("schedule")

            # 3a. placement DECISIONS (micro-batch per replica per
            # round); schedulable(now) keeps probation replicas
            # (crash-loop cooldown) out of the candidate set.  The
            # autoscaler's trace stitch runs FIRST so a replica that
            # joined since the last poll has its origin registered
            # before its first attempt links to it.
            if self.autoscaler is not None:
                sync = getattr(self.autoscaler, "sync_traces", None)
                if sync is not None:
                    sync()
            placements = self.scheduler.schedule(
                self.gateway, self.manager.schedulable(now), now=now)
            # cross-plane span links: an attempt landing on a replica
            # the control plane created (autoscale scale-up, capacity-
            # debt replacement, fleet borrow) references that decision's
            # always-sampled trace — "why does this replica exist" one
            # hop from "why was this request slow".  List append under
            # the lock; no I/O (DL003).
            if self.replica_origins:
                for handle, req in placements:
                    self._link_attempt_origin(handle, req)
            t = perf()
            phase("schedule", t - t_prev)
            t_prev = t
            mark("hedge")

            # 3h. hedge DECISIONS (arithmetic over the live ledgers,
            # step lock held): RUNNING requests whose time-since-
            # progress exceeds the policy's adaptive delay get a
            # second attempt queued toward a healthy replica; the
            # deliveries ride the out-of-lock block below exactly
            # like placements (submit_hedge is a frame send)
            hedge_dispatches: List[tuple] = []
            if self.hedge is not None:
                self._plan_hedges(now, hedge_dispatches)
            t = perf()
            phase("hedge", t - t_prev)
            self.metrics.observe_step_lock(t - t_lock)
        # 3b. placement DELIVERY outside the step lock: for a remote
        # replica, submit is a SUBMIT frame send plus a synchronous ack
        # wait — socket I/O bounded only by submit_timeout, and holding
        # the step lock across it would freeze every membership call
        # and has_work reader for up to that long (dlint DL007 found
        # exactly this chain: step -> ReplicaHandle.submit ->
        # RemoteReplicaHandle.add_request -> FrameConnection.send).
        # The pump is single-threaded by design (module docstring), so
        # handle/request state is safe to touch here; concurrent
        # join/fail/drain calls only mutate OTHER entries.
        t_prev = perf()
        mark("deliver")
        for handle, req in placements:
            try:
                handle.submit(req)
                waited = max(0.0, now - req.enqueued_at)
                self.metrics.observe_queue_wait(
                    waited, trace_id=_tid(req))
                # both identifiers: the request is one key from this
                # queue to its last chunk in the profiler's trace
                event("dlrover.request.placed", rid=req.rid,
                      erid=req.engine_rid, replica=handle.name,
                      queue_wait_ms=waited * 1e3,
                      prompt_tokens=int(req.prompt.size),
                      requeues=req.requeues)
                if not handle.ever_placed:
                    # the autoscale trace's final milestone: the
                    # new replica is not just joined but SERVING
                    handle.ever_placed = True
                    self.recorder.record(
                        "replica_first_placement",
                        replica=handle.name, rid=req.rid, now=now)
            except StaleRequestError:
                # the request reached a terminal state (cancel/expiry)
                # between the placement decision and this delivery: it
                # was already answered and accounted by that path —
                # neither a rejection nor a replica fault, just skip
                logger.debug(
                    "request %s went %s before delivery to %s; dropped",
                    req.rid, req.state, handle.name,
                )
            except ReplicaDeadError:
                # submit's PRE-SEND schedulable check refused: the
                # replica stopped accepting work between the decision
                # and this delivery (a begin_drain — or a reap — slid
                # into the gap the out-of-lock delivery opened).  The
                # SUBMIT frame was never sent, so the request simply
                # goes back to the queue; calling handle.fail() here
                # would escalate a graceful drain into a crash-style
                # failover (in-flight requeued, no GOODBYE sent).  A
                # mid-send death raises ConnectionError from the proxy
                # and still takes the fail-over branch below.
                logger.info(
                    "replica %s became unschedulable before delivery "
                    "of request %s; requeueing", handle.name, req.rid)
                with self._lock:
                    self._requeue([req], dumps, now=now)
            except ValueError as e:
                # the ENGINE rejected the request as impossible
                # (exceeds max_len / pool capacity): a poison
                # request must abort, not fail healthy replicas
                # over one by one
                logger.warning(
                    "request %s rejected by replica %s: %s",
                    req.rid, handle.name, e,
                )
                req.abort(ServingRequestState.REJECTED)
                self.gateway.rejected += 1
                self.metrics.rejected = self.gateway.rejected
            except Exception:
                # the replica died between capacity probe and submit:
                # fail it over; THIS request goes back too
                logger.warning(
                    "placement on replica %s failed; failing it over",
                    handle.name,
                )
                handle.fail()
                with self._lock:
                    self._reap(now, extra=[req], dumps=dumps)
        # 3h-delivery: hedge dispatches, also outside the lock.  A
        # reap raced in by a placement failure above may have settled
        # a record already — those dispatches are skipped, not sent.
        for target, req, rec in hedge_dispatches:
            with self._lock:
                if self._hedges.get(req.rid) is not rec:
                    continue
            try:
                rec["hedge_erid"] = target.submit_hedge(req)
            except (StaleRequestError, ReplicaDeadError):
                # answered, or the target went unschedulable, between
                # decision and delivery: the request simply continues
                # single-attempt — a hedge is an optimization, never
                # an error path
                self._unwind_hedge(rec)
            except Exception:
                logger.warning(
                    "hedge dispatch of request %s on replica %s "
                    "failed; failing it over", req.rid, target.name)
                self._unwind_hedge(rec)
                target.fail()
                with self._lock:
                    self._reap(now, dumps=dumps)
            else:
                self.recorder.record(
                    "hedge_dispatched", rid=req.rid,
                    primary=rec["primary_name"], replica=target.name,
                    now=now)
        phase("deliver", perf() - t_prev)
        with self._lock:
            t_lock = t_prev = perf()
            mark("pump")
            # 4. pump engines
            completed: List[ServingRequest] = []
            for handle in self.manager.pumpable():
                try:
                    with span("dlrover.router.pump", replica=handle.name):
                        done = handle.pump(now)
                except ReplicaDeadError:
                    self._reap(now, dumps=dumps)
                    continue
                for req in done:
                    self._record_ttft(req, now)
                    self.metrics.observe_tokens(len(req.output), now)
                    # per-tenant generated-token book (usage endpoint;
                    # plain dict arithmetic, safe under the step lock)
                    self.gateway.tenants.note_tokens(
                        req.tenant, len(req.output))
                    self.metrics.completed += 1
                    if req.finished_at is not None:
                        e2e = req.finished_at - req.submitted_at
                        self.metrics.observe_e2e(
                            e2e, trace_id=_tid(req))
                        if self.slo is not None:
                            ttft = (
                                req.first_token_at - req.submitted_at
                                if req.first_token_at is not None
                                else None)
                            self.slo.observe(
                                req.priority, ttft, e2e, now,
                                tenant_class=self.gateway
                                .tenant_class(req.tenant))
                    if self.hedge is not None:
                        self._feed_hedge_policy(req)
                    if self._hedges:
                        rec = self._hedges.pop(req.rid, None)
                        if rec is not None:
                            # first DONE wins: this handle's attempt
                            # answered the caller; the loser is
                            # withdrawn and CANCELled below
                            self._resolve_hedge(
                                rec, handle, cancels, now)
                completed.extend(done)
            # TTFT for still-running requests whose FIRST token arrived
            # this round: pump stages them in handle.ttft_pending, so
            # this visits only the requests with news — the old sweep
            # touched every in-flight request on every replica, every
            # step (completion above covers the finished ones)
            for handle in self.manager.pumpable():
                if handle.ttft_pending:
                    for req in handle.ttft_pending:
                        self._record_ttft(req, now)
                    handle.ttft_pending.clear()
            t = perf()
            phase("pump", t - t_prev)
            t_prev = t
            mark("retire")

            # 5. retire drained replicas (graceful scale-down, phase 2)
            for handle in list(self.manager.replicas.values()):
                if handle.drained:
                    self.manager.remove(handle.name)
                    self.scheduler.forget_replica(handle.name)
                    self._close_engine(handle, goodbye=True)
                    self.recorder.record(
                        "replica_retired", replica=handle.name, now=now)
                    # a deliberately-retired name leaves the fleet for
                    # good: drop its origin so a later same-named
                    # joiner cannot inherit a stale (likely evicted)
                    # decision link — its OWN creation re-registers.
                    # Deaths keep theirs: a supervisor respawn rejoins
                    # under the same base and is still the original
                    # decision's offspring.
                    self.replica_origins.pop(
                        base_replica_name(handle.name), None)
                    self.drained.append(
                        DrainedReplica(handle.name, handle.node))
            t = perf()
            phase("retire", t - t_prev)
            t_prev = t
            mark("observe")

            # 6. gauges + autoscale
            inflight = sum(
                len(h.inflight) for h in self.manager.replicas.values())
            self.metrics.observe_gauges(
                queue_depth=self.gateway.depth(),
                inflight=inflight,
                replica_up=self.manager.up_count(),
                replica_draining=sum(
                    1 for h in self.manager.replicas.values()
                    if h.status == ReplicaStatus.DRAINING
                ),
                replica_probation=self.manager.probation_count(now),
                now=now,
            )
            # raw-speed engine aggregates (spec accept ratio, int8 KV
            # pool size, chunked-prefill seconds): plain attribute
            # reads — local adapters read host-side stats, remote
            # proxies return the dict cached off their last STATS
            # frame — so this stays lock-discipline-clean
            self.metrics.observe_engine_metrics([
                h.engine_metrics()
                for h in self.manager.replicas.values()
            ])
            # prefix-routing table feed: each replica advertises its
            # hottest committed prefix heads (rode the same STATS frame
            # as engine_metrics for remote replicas — plain attribute
            # reads here).  Advertisement REPLACES the replica's head
            # set, so a head evicted replica-side drops its route this
            # round — the table only ever claims residency it has
            # fresh evidence for.
            for name, h in self.manager.replicas.items():
                heads = h.prefix_heads()
                if heads or self.scheduler.prefix_table.heads_of(name):
                    self.scheduler.advertise_prefixes(name, heads)
            for key, val in self.scheduler.prefix_route_stats().items():
                setattr(self.metrics, key, float(val))
            # per-tenant-class QoS books: the registry aggregates its
            # per-tenant dicts onto the bounded class vocabulary here,
            # so raw tenant ids never leave the gateway (DL010).
            # Plain dict arithmetic — safe under the step lock.
            tenants = self.gateway.tenants
            self.metrics.observe_tenants(
                tenants.by_class(self.gateway.tenant_queue_depths()),
                tenants.by_class(tenants.shed),
                tenants.by_class(tenants.quota_rejected),
            )
            # SLO-burn WFQ boost: a tenant class burning its error
            # budget gets a temporary, bounded weight multiplier so
            # admission favors it until the burn recovers (pure
            # arithmetic over the SLO engine's windows — lock-clean)
            if self.slo is not None and not tenants.trivial:
                tenants.update_slo_boosts({
                    cls: self.slo.class_burn_rate(cls, now)
                    for cls in TENANT_CLASSES
                })
            # placement fast-path counters (regression surface for the
            # incremental index; plain attribute reads)
            self.metrics.sched_capacity_evals = float(
                getattr(self.scheduler, "capacity_evals", 0))
            self.metrics.sched_rounds_skipped = float(
                getattr(self.scheduler, "rounds_skipped", 0))
            # gray-failure plane: suspicion + hedging books (plain
            # attribute reads; phi_value is cached arithmetic on the
            # proxy's interarrival window, no I/O under the lock)
            self.metrics.replica_suspect = float(self._suspect_count)
            self.metrics.phi_max = max(
                (h.phi_value(now)
                 for h in self.manager.replicas.values()),
                default=0.0)
            self.metrics.suspect_demotions = float(
                self.manager.suspect_demotions)
            self.metrics.suspect_recoveries = float(
                self.manager.suspect_recoveries)
            self.metrics.suspect_flaps_damped = float(
                self.manager.suspect_flaps_damped)
            self.metrics.hedge_active = float(len(self._hedges))
            self.metrics.hedge_dispatched = float(self.hedge_dispatched)
            self.metrics.hedge_won = float(self.hedge_won)
            self.metrics.hedge_cancelled = float(self.hedge_cancelled)
            self.metrics.hedge_budget_exhausted = float(
                self.hedge_budget_exhausted)
            self.metrics.hedge_promoted = float(self.hedge_promoted)
            t = perf()
            phase("observe", t - t_prev)
            self.metrics.observe_step_lock(t - t_lock)
        # autoscale OUTSIDE the step lock: a Brain-backed policy's
        # serving_plan is a synchronous control-plane RPC (30s default
        # timeout), and executing a ScalePlan spawns nodes/processes —
        # neither belongs inside the critical section every membership
        # call contends on (dlint DL007: step -> on_step -> ... ->
        # BrainClient.serving_plan -> stub RPC).  on_step is only ever
        # called from here, so its own state needs no lock; the router
        # surfaces it reads (metrics, manager counts, gateway depth)
        # are each internally consistent.
        t_prev = perf()
        mark("autoscale")
        if self.autoscaler is not None:
            self.autoscaler.on_step(now)
        t = perf()
        phase("autoscale", t - t_prev)
        t_prev = t
        mark("flush")
        # deliver the round's CANCELs now that the lock is gone: remote
        # deliveries are frame sends (bounded by the connection's
        # send_timeout, but still I/O); local ones are slot/KV-block
        # frees, safe here because the pump is single-threaded by
        # design (concurrency is a caller policy, see module docstring)
        for handle, erid in cancels:
            if not handle.cancel_request(erid):
                self.metrics.cancel_send_failures += 1
        # bound the log burst: a stall can expire a whole queue in one
        # step, and one multi-KB FLIGHT-RECORDER record per request
        # would flood the log exactly mid-incident — the first few per
        # reason carry the signal, the rest are summarized
        flushed: Dict[str, int] = {}
        dropped: Dict[str, int] = {}
        for reason, trace_id in dumps:
            if flushed.get(reason, 0) >= self.MAX_DUMPS_PER_STEP:
                dropped[reason] = dropped.get(reason, 0) + 1
                continue
            flushed[reason] = flushed.get(reason, 0) + 1
            self.tracer.flight_dump(reason, trace_id, now=now)
        for reason, n in dropped.items():
            logger.warning(
                "flight recorder: %d more %s dumps suppressed this "
                "step (first %d emitted)", n, reason,
                self.MAX_DUMPS_PER_STEP)
        phase("flush", perf() - t_prev)
        mark(None)
        return completed

    # ------------------------------------------- in-flight sweeps (1b)
    def _inflight_abort(self, handle: ReplicaHandle, erid: int,
                        req: ServingRequest, cancelled: bool,
                        now: float, cancels: List[tuple],
                        dumps: List[tuple]) -> None:
        """Shared abort bookkeeping for an in-flight withdrawal/expiry
        (step lock held): state flip, accounting, recorder event, the
        CANCEL delivery queued for after lock release."""
        del handle.inflight[erid]
        # a hedged request goes down whole: its second attempt is
        # withdrawn too, or it would decode into a dropped stream and
        # its DONE would race the abort
        self._clear_hedge_attempts(req, cancels)
        if cancelled:
            state = ServingRequestState.CANCELLED
            self.gateway.cancelled += 1
            reason = "cancelled"
        else:
            state = ServingRequestState.TIMED_OUT
            self.gateway.timed_out += 1
            reason = "deadline_expired"
            if self.slo is not None:
                self.slo.observe_violation(
                    req.priority, now,
                    tenant_class=self.gateway.tenant_class(req.tenant))
        req.abort(state)
        self.recorder.record(
            "request_cancel_inflight", rid=req.rid,
            replica=handle.name, state=state, now=now)
        cancels.append((handle, erid))
        if req.trace is not None:
            dumps.append((reason, req.trace.trace_id))

    def _inflight_sweep_scan(self, now: float, cancels: List[tuple],
                             dumps: List[tuple]) -> None:
        """Sweep engine: visit EVERY in-flight request on every replica
        looking for withdrawals (and, under the policy, expiries) —
        the historical O(inflight)-per-step behavior."""
        for handle in self.manager.pumpable():
            for erid, req in list(handle.inflight.items()):
                expired = (
                    self.cancel_inflight_on_expiry
                    and req.deadline is not None
                    and now > req.deadline
                )
                if not (req.cancel_requested or expired):
                    continue
                self._inflight_abort(
                    handle, erid, req, req.cancel_requested,
                    now, cancels, dumps)

    def _inflight_sweep_events(self, now: float, cancels: List[tuple],
                               dumps: List[tuple]) -> None:
        """Event engine: visit ONLY requests with news — caller
        withdrawals staged by the gateway's cancel-event queue, and
        (under cancel_inflight_on_expiry) RUNNING requests whose
        deadline-heap entry came due.  A request that reached a
        terminal state (or failed over back to QUEUED) between the
        event and this sweep is simply skipped: the path that moved it
        already answered its caller."""
        work = [(req, True)
                for req in self.gateway.take_inflight_cancels()]
        # drain unconditionally (the stage list must not grow under the
        # let-it-finish policy); act only when the policy says so — a
        # request discarded here that later fails over re-arms the
        # deadline heap through requeue_front
        expired = self.gateway.take_expired_running()
        if self.cancel_inflight_on_expiry:
            work.extend((req, False) for req in expired)
        for req, cancelled in work:
            if req.state != ServingRequestState.RUNNING:
                continue
            if not cancelled and (req.deadline is None
                                  or now <= req.deadline):
                continue  # popped early by a prior step's clock skew
            handle = (self.manager.get(req.replica)
                      if req.replica else None)
            if handle is None:
                continue
            erid = req.engine_rid
            if erid is None or handle.inflight.get(erid) is not req:
                continue
            self._inflight_abort(
                handle, erid, req, cancelled, now, cancels, dumps)

    def _brownout_sweep(self, now: float, cancels: List[tuple],
                        dumps: List[tuple]) -> None:
        """One brown-out round (step lock held by the caller): update
        the watermark, record stage transitions, and at stage 2+
        expiry-cancel queued and in-flight BATCH through the cancel
        machinery — decisions here, deliveries after lock release via
        ``cancels`` (a remote CANCEL is a frame send; DL003/DL007)."""
        capacity = self._capacity(now)
        prev = self.brownout.stage
        stage = self.brownout.update(now, self.gateway.depth(), capacity)
        if stage != prev:
            pressure = self.brownout.pressure
            self.recorder.record(
                "brownout_stage", stage=stage, prev=prev,
                name=self.brownout.stage_name,
                pressure=(round(pressure, 3)
                          if pressure != float("inf") else "inf"),
                now=now)
            log = logger.warning if stage > prev else logger.info
            log(
                "brown-out stage %d -> %d (%s): pressure %.3g, "
                "queue depth %d, capacity %.0f slots",
                prev, stage, self.brownout.stage_name,
                self.brownout.pressure, self.gateway.depth(), capacity)
        self.metrics.brownout_stage = float(stage)
        if not self.brownout.cancels_batch:
            return
        self._brownout_cancel_batch(
            now, cancels, dumps,
            keep_total=(None if self.gateway.tenants.trivial
                        else int(capacity
                                 * self.brownout.exit_pressure)))

    def _capacity(self, now: float) -> float:
        capacity = 0.0
        for handle in self.manager.schedulable(now):
            try:
                capacity += handle.slots_free() + len(handle.inflight)
            except Exception:
                continue  # a dying replica's ledger is not capacity
        return capacity

    def _brownout_cancel_batch(self, now: float, cancels: List[tuple],
                               dumps: List[tuple],
                               keep_total: Optional[int] = None
                               ) -> None:
        # stage 2+: the BATCH band drains NOW — queued requests answer
        # their callers instead of aging out, in-flight ones return
        # their slots and paged KV blocks to the surviving bands.
        # Multi-tenant fleets shed down to ``keep_total`` instead,
        # proportionally from the tenants furthest over fair share —
        # the tenant that CAUSED the brown-out pays for it first.
        for req in self.gateway.shed_queued(
                PRIORITY_BATCH, now=now, dump=False,
                keep_total=keep_total):
            if self.slo is not None:
                # a brown-out shed IS an SLO violation for its band:
                # the user was failed by the fleet's own degradation
                # ladder, not by their request — the burn it causes
                # is the signal that pulls capacity back
                self.slo.observe_violation(
                    req.priority, now,
                    tenant_class=self.gateway.tenant_class(req.tenant))
            if req.trace is not None:
                dumps.append(("brownout_shed", req.trace.trace_id))
        for handle in self.manager.pumpable():
            for erid, req in list(handle.inflight.items()):
                if req.priority != PRIORITY_BATCH:
                    continue
                if handle.inflight.get(erid) is not req:
                    # already withdrawn this round (a hedge mate's
                    # clearing removed it from under the snapshot)
                    continue
                del handle.inflight[erid]
                cancels.append((handle, erid))
                if req.state in SERVING_REQUEST_TERMINAL_STATES:
                    # the other attempt of a hedged request was
                    # aborted first: accounted once already
                    continue
                self._clear_hedge_attempts(req, cancels)
                req.abort(ServingRequestState.CANCELLED)
                self.gateway.cancelled += 1
                if self.slo is not None:
                    self.slo.observe_violation(
                        req.priority, now,
                        tenant_class=self.gateway.tenant_class(
                            req.tenant))
                self.recorder.record(
                    "brownout_cancel_inflight", rid=req.rid,
                    replica=handle.name, now=now)
                if req.trace is not None:
                    dumps.append(("brownout_shed", req.trace.trace_id))

    # ---------------------------------------------------- hedging (3h)
    def _plan_hedges(self, now: float,
                     dispatches: List[tuple]) -> None:
        """Hedge DECISIONS (step lock held, arithmetic only): find the
        RUNNING primary attempts whose time-since-progress exceeds the
        policy's adaptive delay, pick a healthy (non-demoted) second
        replica with real capacity for each, and queue the dispatch
        for the out-of-lock delivery block.  BATCH-band requests are
        never hedged while a brown-out is shedding: hedging doubles a
        request's load, and the ladder exists because load already
        won."""
        policy = self.hedge
        primaries = []
        for handle in self.manager.pumpable():
            for erid, req in handle.inflight.items():
                # the hedge attempt of an already-hedged request also
                # lives in an inflight map — only PRIMARY attempts
                # (the request's own routing identity) are candidates
                if req.engine_rid == erid and req.replica == handle.name:
                    primaries.append((handle, erid, req))
        if not primaries:
            return
        delay = policy.hedge_delay()
        shedding = (self.brownout is not None
                    and self.brownout.stage > 0)
        stalled = []
        for handle, erid, req in primaries:
            if (req.rid in self._hedges
                    or req.state != ServingRequestState.RUNNING
                    or req.dispatched_at is None
                    # a non-None owner is a promoted hedge running
                    # DONE-flush-only: re-gating its stream to a new
                    # attempt would deliver a suffix with no prefix
                    or req.stream_owner is not None):
                continue
            if shedding and req.priority == PRIORITY_BATCH:
                continue
            last = (req.last_token_at if req.last_token_at is not None
                    else req.dispatched_at)
            if now - last > delay:
                stalled.append((now - last, handle, erid, req))
        # worst stall first: when the budget only covers some, it
        # covers the requests that need it most
        stalled.sort(key=lambda s: -s[0])
        for stall, handle, erid, req in stalled:
            if not policy.allows(
                    len(self._hedges), len(primaries),
                    dispatched_total=self.hedge_dispatched,
                    submitted_total=self.gateway.submitted):
                # a saturated budget is a fleet-health signal, not a
                # silent no-op — count every denial
                self.hedge_budget_exhausted += 1
                break
            target = self._hedge_target(req, now)
            if target is None:
                continue
            rec = {"req": req, "primary_name": handle.name,
                   "primary_erid": erid, "hedge_name": target.name,
                   "hedge_erid": None}
            # gate the client stream to the primary attempt BEFORE
            # the second copy can emit: two attempts, one stream
            req.stream_owner = (handle.name, erid)
            self._hedges[req.rid] = rec
            self.hedge_dispatched += 1
            dispatches.append((target, req, rec))

    def _hedge_target(self, req: ServingRequest,
                      now: float) -> Optional[ReplicaHandle]:
        """The healthiest second replica for a hedge: schedulable,
        NOT demoted (hedging onto a gray replica buys nothing), not
        the primary, with a free slot and the KV blocks the request
        actually needs — fit checked against REAL capacity, the same
        rules placement uses."""
        best = None
        best_key = None
        for h in self.manager.schedulable(now):
            if h.name == req.replica or h.demoted:
                continue
            try:
                slots = h.slots_free()
                if slots <= 0:
                    continue
                blocks = h.blocks_free()
                need = h.blocks_needed(
                    int(req.prompt.size), req.max_new_tokens)
                if need is not None and blocks < need:
                    continue
            except Exception:
                continue  # a dying replica's ledger is not capacity
            key = (slots, blocks)
            if best_key is None or key > best_key:
                best, best_key = h, key
        return best

    def _unwind_hedge(self, rec: dict) -> None:
        """A hedge dispatch failed to deliver: drop the record and
        reopen the stream gate — the request continues single-attempt
        (runs outside the step's critical section, so it re-takes the
        lock for the record table)."""
        req = rec["req"]
        with self._lock:
            if self._hedges.get(req.rid) is rec:
                del self._hedges[req.rid]
            if req.stream_owner == (rec["primary_name"],
                                    rec["primary_erid"]):
                req.stream_owner = None

    def _clear_hedge_attempts(self, req: ServingRequest,
                              cancels: List[tuple]) -> None:
        """An abort path (cancel / expiry / brown-out shed) is taking
        the request down: withdraw whichever of its attempts are still
        in an inflight map and queue their CANCELs (step lock held)."""
        rec = self._hedges.pop(req.rid, None)
        if rec is None:
            return
        for name, erid in ((rec["primary_name"], rec["primary_erid"]),
                           (rec["hedge_name"], rec["hedge_erid"])):
            if erid is None:
                continue
            h = self.manager.get(name)
            if h is not None and h.inflight.get(erid) is req:
                del h.inflight[erid]
                cancels.append((h, erid))

    def _resolve_hedge(self, rec: dict, winner: ReplicaHandle,
                       cancels: List[tuple], now: float) -> None:
        """First DONE wins (step lock held): count the winner, pull
        the losing attempt out of its handle's inflight map and queue
        its CANCEL.  The loser's own DONE, if the CANCEL loses the
        race, hits the pump's terminal-state dedup guard and is
        dropped — completed_total stays exactly one per request."""
        req = rec["req"]
        if winner.name == rec["hedge_name"]:
            self.hedge_won += 1
        for name, erid in ((rec["primary_name"], rec["primary_erid"]),
                           (rec["hedge_name"], rec["hedge_erid"])):
            if erid is None:
                continue
            h = self.manager.get(name)
            if h is None or h.inflight.get(erid) is not req:
                continue
            del h.inflight[erid]
            cancels.append((h, erid))
            self.hedge_cancelled += 1
        self.recorder.record(
            "hedge_resolved", rid=req.rid, winner=winner.name,
            hedged_to=rec["hedge_name"], now=now)

    def _settle_hedged_orphans(self, orphans: List[ServingRequest],
                               now: float) -> List[ServingRequest]:
        """Failover meets hedging (step lock held): a hedged request
        appears in the orphan drain once per attempt a dead replica
        held.  Hedge replica died -> drop the attempt, the primary
        continues untouched (no requeue).  Primary died with the
        hedge still live -> PROMOTE the hedge in place of requeueing:
        the request's routing identity moves to the hedge attempt,
        the client stream restarts, and only the authoritative DONE
        flush delivers tokens (the attempt raced silently, so its
        early tokens cannot be re-streamed incrementally).  Both
        died -> one ordinary failover requeue."""
        out: List[ServingRequest] = []
        seen: set = set()
        for req in orphans:
            rec = self._hedges.get(req.rid)
            if rec is None:
                out.append(req)
                continue
            if req.rid in seen:
                continue  # second appearance: both attempts died
            seen.add(req.rid)
            primary = self.manager.get(rec["primary_name"])
            primary_live = (
                primary is not None
                and primary.inflight.get(rec["primary_erid"]) is req)
            hedge = (self.manager.get(rec["hedge_name"])
                     if rec["hedge_erid"] is not None else None)
            hedge_live = (
                hedge is not None
                and hedge.inflight.get(rec["hedge_erid"]) is req)
            if primary_live and hedge_live:
                # defensive: neither attempt actually died (an extra
                # orphan aliased the rid) — leave the race running
                continue
            del self._hedges[req.rid]
            if primary_live:
                # the hedge side died; the primary still decodes —
                # reopen its stream gate and carry on
                if req.state == ServingRequestState.RUNNING:
                    req.stream_owner = None
                continue
            if hedge_live:
                # primary died: zero lost requests WITHOUT a replay —
                # the hedge attempt becomes the request
                req.replica = rec["hedge_name"]
                req.engine_rid = rec["hedge_erid"]
                req.restart_stream()
                # never-matching owner: incremental tokens stay
                # suppressed; the DONE flush (streamed position just
                # reset to 0) delivers the full output byte-correct
                req.stream_owner = ("", -1)
                req.dispatched_at = now
                self.hedge_promoted += 1
                self.recorder.record(
                    "hedge_promoted", rid=req.rid,
                    replica=rec["hedge_name"], now=now)
                logger.info(
                    "request %s: primary replica died, hedge attempt "
                    "on %s promoted (no requeue)",
                    req.rid, rec["hedge_name"])
                continue
            out.append(req)  # both attempts gone: standard failover
        return out

    def _feed_hedge_policy(self, req: ServingRequest) -> None:
        """Completion-time progress samples for the hedge delay's
        rolling p99: the winning attempt's TTFT and its mean
        inter-token pace (bounded: two observations per completion)."""
        policy = self.hedge
        if req.dispatched_at is None or req.finished_at is None:
            return
        if req.first_token_at is not None:
            policy.observe(
                max(0.0, req.first_token_at - req.dispatched_at))
        span = req.finished_at - req.dispatched_at
        if req.output and span >= 0:
            policy.observe(span / len(req.output))

    def _link_attempt_origin(self, handle: ReplicaHandle,
                             req: ServingRequest) -> None:
        """Stamp the W3C-shaped span link from this placement's
        ``attempt`` span to the control-plane trace that created the
        replica it landed on (autoscale decision, capacity-debt
        replacement, fleet borrow).  Failed-over requests are exactly
        the ones this pays for: their retry's attempt resolves to the
        replacement trace, so the postmortem reads 'replica died ->
        HERE is the decision that produced where the retry went'."""
        if req.trace is None or req.trace.attempt is None:
            return
        origin = self.replica_origins.get(
            base_replica_name(handle.name))
        if origin is None:
            return
        attrs = {k: v for k, v in origin.items()
                 if k not in ("trace_id", "span_id")}
        req.trace.attempt.add_link(
            origin["trace_id"], origin["span_id"],
            rel="replica_origin", **attrs)

    def _record_ttft(self, req: ServingRequest, now: float) -> None:
        if req.first_token_at is not None and not req.ttft_recorded:
            req.ttft_recorded = True
            self.metrics.observe_ttft(
                req.first_token_at - req.submitted_at, now,
                trace_id=_tid(req))

    def _reap(self, now: float,
              extra: Optional[List[ServingRequest]] = None,
              dumps: Optional[List[tuple]] = None) -> None:
        """Reap dead replicas, requeue their (+ ``extra``) in-flight
        requests, and run the post-mortem: drop affinity state (a
        same-named successor must not inherit routing toward a cache
        that died with the process) and surface the dead replicas'
        cluster nodes for retirement.  Flight-recorder dump requests
        are appended to ``dumps`` — the step lock is held here, and
        serializing span trees + logging belongs after its release."""
        orphans = (extra or []) + self.manager.reap_dead(now)
        if self._hedges:
            orphans = self._settle_hedged_orphans(orphans, now)
        self._requeue(orphans, dumps, now=now)
        for handle in self.manager.dead_handles:
            self.scheduler.forget_replica(handle.name)
            self._close_engine(handle, goodbye=False)
            self.recorder.record(
                "replica_dead", replica=handle.name, now=now)
            self.dead.append(DrainedReplica(handle.name, handle.node))
        self.manager.dead_handles.clear()
        # black-box readout for the failover: each orphaned request's
        # span tree (the dead-replica attempt is closed as "failover"
        # by the requeue above, so the dump shows exactly where the
        # request was when its replica died)
        if dumps is not None:
            for req in orphans:
                # poisoned orphans are queued for their own "poisoned"
                # dump by _requeue; dumping them twice would just burn
                # ring slots
                if req.trace is not None and \
                        req.state == ServingRequestState.QUEUED:
                    dumps.append(
                        ("replica_death", req.trace.trace_id))

    @staticmethod
    def _close_engine(handle: ReplicaHandle, goodbye: bool) -> None:
        """Release a retired replica's engine resources.  Remote engine
        proxies expose ``close()`` (connection torn down, reader thread
        joined); without this every scale-down or crash would leak the
        proxy's TCP connection and thread.  ``goodbye`` is sent only on
        DELIBERATE retirement (drain/scale-down) — a replica reaped as
        dead is only *presumed* dead, and telling a falsely-reaped-but-
        alive worker to exit would convert a transient liveness glitch
        into permanent fleet loss (its supervisor would read the clean
        rc-0 exit as a scale decision and never respawn it; a truly
        dead process respawns off its nonzero rc instead).  In-process
        engines expose no ``close`` and need none."""
        close = getattr(handle.engine, "close", None)
        if close is None:
            return
        try:
            import inspect

            try:
                takes_goodbye = "goodbye" in inspect.signature(
                    close).parameters
            except (TypeError, ValueError):
                takes_goodbye = False
            close(goodbye=goodbye) if takes_goodbye else close()
        except Exception as e:  # teardown must never fail the pump
            logger.warning(
                "closing engine of retired replica %s failed: %s",
                handle.name, e)

    def _requeue(self, requests: List[ServingRequest],
                 dumps: Optional[List[tuple]] = None,
                 now: Optional[float] = None) -> None:
        if not requests:
            return
        poisoned = self.gateway.requeue_front(
            requests, dump=dumps is None, now=now)
        self.metrics.requeued += len(requests) - len(poisoned)
        self.metrics.poisoned = self.gateway.poisoned
        if self.slo is not None:
            for req in poisoned:
                # the caller never gets an answer: an SLO violation.
                # (Engine REJECTED requests deliberately are NOT fed
                # here or at their abort site — an impossible request
                # is the caller's 4xx, not the fleet's failure.)
                self.slo.observe_violation(
                    req.priority,
                    time.monotonic() if now is None else now,
                    tenant_class=self.gateway.tenant_class(req.tenant))
        for req in poisoned:
            if dumps is not None and req.trace is not None:
                dumps.append(("poisoned", req.trace.trace_id))
            logger.error(
                "request %s poisoned: crashed a replica on each of its "
                "%d placements; failing it instead of requeueing",
                req.rid, req.requeues,
            )

    # ------------------------------------------------------ conveniences
    @property
    def has_work(self) -> bool:
        with self._lock:
            return self.gateway.depth() > 0 or any(
                h.inflight for h in self.manager.replicas.values())

    def run_until_idle(
        self, max_steps: int = 100000, now_fn=None
    ) -> int:
        """Pump until queue and replicas are empty; returns steps taken.
        Raises if work remains but no replica can make progress (so a
        stuck test fails loudly instead of spinning)."""
        now_fn = now_fn or time.monotonic
        steps = 0
        while self.has_work:
            if steps >= max_steps:
                raise RuntimeError(
                    f"router still busy after {max_steps} steps "
                    f"(depth={self.gateway.depth()})")
            if not self.manager.replicas and self.gateway.depth():
                raise RuntimeError("queued work but no replicas")
            self.step(now_fn())
            steps += 1
        return steps

    def serve_forever(
        self, poll_seconds: float = 0.001, stop_event=None
    ) -> None:  # pragma: no cover - deployment loop
        stop_event = stop_event or threading.Event()
        while not stop_event.is_set():
            self.step()
            if not self.has_work:
                stop_event.wait(poll_seconds)

    def results(self, requests: List[ServingRequest],
                timeout: Optional[float] = None) -> Dict[int, np.ndarray]:
        return {r.rid: r.result(timeout) for r in requests}
