"""Request gateway: admission control, bounded priority queues, deadlines.

The front door of the serving router.  Every request is admitted (or
refused) HERE, before any replica sees it — the queue bound is the
backpressure surface (a full queue answers "overloaded" in microseconds
instead of letting latency grow without bound), and the per-request
deadline turns an unserviceable backlog into fast, explicit timeouts
instead of silently stale answers.

Three strict priority bands (HIGH > NORMAL > BATCH); WITHIN each band
requests are weighted-fair-queued across tenants (tenancy.WfqBandQueue
— with a single tenant the order is exactly the historical FIFO);
failover requeues go to the FRONT of their band so a replica crash
never sends a half-served request to the back of the line.

Tenancy at the door: ``submit(tenant=...)`` resolves the id against
the gateway's :class:`~dlrover_tpu.serving.tenancy.TenantRegistry`
(unknown ids land on the configurable default tenant — identity can
never crash admission) and admits through the tenant's token bucket
(quota QPS) and queue bound; over-quota BATCH/NORMAL answer
:class:`TenantQuotaError` with a Retry-After hint, HIGH is never
quota-rejected — only fair-queued behind its own tags.
"""

from __future__ import annotations

import dataclasses
import heapq
import queue as queue_mod
import threading
import time
from collections import deque
from typing import Deque, Dict, Iterator, List, Optional

import numpy as np

from dlrover_tpu.common.constants import (
    SERVING_REQUEST_TERMINAL_STATES,
    ServingFabric,
    ServingRequestState,
)
from dlrover_tpu.serving.tenancy import (
    TenantRegistry,
    WfqBandQueue,
    plan_shed,
)
from dlrover_tpu.utils.profiler import event
from dlrover_tpu.utils.tracing import RequestTrace, Tracer

PRIORITY_HIGH = 0
PRIORITY_NORMAL = 1
PRIORITY_BATCH = 2
_PRIORITIES = (PRIORITY_HIGH, PRIORITY_NORMAL, PRIORITY_BATCH)


class AdmissionError(RuntimeError):
    """The gateway refused the request at the door.

    ONE Retry-After contract for every refusal class: every
    :class:`AdmissionError` carries ``retry_after_s`` (None when the
    gateway has no honest estimate — a validation refusal retries
    never, a capacity refusal retries on the caller's own backoff).
    An HTTP front end maps a non-None hint 1:1 onto a ``Retry-After``
    header on the 503/429."""

    def __init__(self, message: str,
                 retry_after_s: Optional[float] = None):
        super().__init__(message)
        self.retry_after_s = retry_after_s


class QueueFullError(AdmissionError):
    """Bounded queue at capacity — shed load upstream."""


class TenantQuotaError(AdmissionError):
    """The TENANT is over its own contract (quota QPS token bucket or
    max-queued bound) while the fleet itself may be fine — a 429, not
    a 503.  ``retry_after_s`` is the token bucket's time-to-next-token
    (coming back sooner cannot succeed).  HIGH-priority requests are
    never refused here: an over-quota tenant's HIGH traffic is only
    fair-queued behind its own WFQ tags."""

    def __init__(self, message: str, tenant: str = "",
                 retry_after_s: Optional[float] = None):
        super().__init__(message, retry_after_s=retry_after_s)
        self.tenant = tenant


class BrownoutShedError(AdmissionError):
    """The brown-out controller is shedding this priority band — the
    fleet is degrading in ORDER (BATCH first, then NORMAL, HIGH never)
    instead of letting the queue bound bounce all bands equally.
    Retry later, or resubmit at a higher priority if the work is.

    On top of the shared ``retry_after_s`` contract (here the policy's
    best-case exit-watermark + dwell recovery estimate,
    :meth:`~dlrover_tpu.serving.router.brownout.BrownoutPolicy.
    expected_recovery_s`) the answer carries ``stage`` /
    ``stage_name`` — where the ladder stands."""

    def __init__(self, message: str, stage: Optional[int] = None,
                 stage_name: str = "",
                 retry_after_s: Optional[float] = None):
        super().__init__(message, retry_after_s=retry_after_s)
        self.stage = stage
        self.stage_name = stage_name


class RequestTimedOut(RuntimeError):
    """Raised by :meth:`ServingRequest.result` for an expired request."""


class _StreamRestart:
    """Yielded by :meth:`ServingRequest.stream` when a replica failure
    requeued the request: everything yielded so far is void (the replay
    regenerates from scratch — at-least-once execution) and the stream
    restarts from token 0 of the new attempt."""

    def __repr__(self) -> str:  # pragma: no cover - repr only
        return "STREAM_RESTART"


STREAM_RESTART = _StreamRestart()


@dataclasses.dataclass
class ServingRequest:
    """One request's routing state (the router's view, distinct from the
    engine-internal ``serving.engine.Request`` it maps to on a replica)."""

    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    priority: int = PRIORITY_NORMAL
    # resolved tenant name (registry identity, never a raw unknown id
    # — the gateway resolves at admission, so per-tenant state stays
    # bounded by the registered set)
    tenant: str = "default"
    deadline: Optional[float] = None       # absolute monotonic time
    submitted_at: float = 0.0
    state: str = ServingRequestState.QUEUED
    output: List[int] = dataclasses.field(default_factory=list)
    replica: Optional[str] = None          # placed-on replica name
    engine_rid: Optional[int] = None       # rid inside that replica's engine
    requeues: int = 0                      # failover replays (at-least-once)
    # when THIS stay in the queue began: admission time, reset by every
    # failover requeue — queue-wait metrics measure the current
    # attempt's wait, not the dead predecessor's service time
    enqueued_at: float = 0.0
    # caller withdrew the request (ServingRequest.cancel); acted on by
    # the next router step — queued requests are dropped, in-flight
    # ones are aborted and a CANCEL is sent to the owning replica
    cancel_requested: bool = False
    # when the first token of the current attempt was seen: the TOKEN
    # frame's receive time for a remote replica, the engine's read of
    # the program that sampled it for an in-process one
    # (``last_delivery_at`` is the clock read at the hand-over here)
    first_token_at: Optional[float] = None
    ttft_recorded: bool = False            # metrics bookkeeping
    finished_at: Optional[float] = None
    # when the current attempt was handed to its replica (stamped by
    # ReplicaHandle.submit, cleared by failover requeue) and when the
    # newest token arrived — together they give time-since-progress,
    # the signal the hedging sweep compares against its adaptive delay
    dispatched_at: Optional[float] = None
    last_token_at: Optional[float] = None
    # deliveries of the current attempt: how many times tokens were
    # handed to this request, and ``time.monotonic()`` READ AT the
    # newest hand-over (never a caller's ``now``) — first-token time
    # and token gaps as a client sees them come from these two
    deliveries: int = 0
    last_delivery_at: Optional[float] = None
    # hedging stream gate: None = the single attempt streams normally;
    # a (replica_name, engine_rid) pair = ONLY that attempt's tokens
    # reach the client stream (the hedge attempt races silently and
    # can still win via DONE, which flushes the full suffix); a
    # never-matching sentinel = all incremental tokens suppressed
    # until DONE (a promoted hedge after the primary died — its early
    # tokens are already gone, so only the authoritative DONE flush
    # keeps the stream byte-correct)
    stream_owner: Optional[tuple] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    _done: threading.Event = dataclasses.field(
        default_factory=threading.Event, repr=False, compare=False
    )
    # token stream: events pushed as TOKEN frames arrive (or as the
    # local engine emits); consumed by stream().  Events are recorded
    # even with no consumer attached — a deliberate tradeoff: ONE
    # subscriber, attaching at any time (even post-completion), sees
    # the full history including restarts, at the cost of one extra
    # token copy bounded by the request's own output length and
    # lifetime.  The queue drains destructively: stream() is
    # single-consumer, a second iteration sees nothing (use result())
    _events: "queue_mod.Queue" = dataclasses.field(
        default_factory=queue_mod.Queue, repr=False, compare=False
    )
    _streamed: int = dataclasses.field(
        default=0, repr=False, compare=False
    )  # tokens pushed to the stream since the last (re)start
    # per-request span trace (utils/tracing.RequestTrace), stamped by
    # the gateway at admission; None when the gateway runs untraced
    trace: Optional[RequestTrace] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # cancel-event hook, stamped at admission: cancel() calls it so the
    # router's event-driven step engine visits ONLY withdrawn requests
    # instead of sweeping every queue + every in-flight map per step
    _on_cancel: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # capacity generation at which the scheduler last found NO replica
    # able to hold this request — the incremental placement index skips
    # it until some replica's capacity actually grows (scheduler.py)
    sched_blocked_gen: int = dataclasses.field(
        default=-1, repr=False, compare=False
    )
    # terminal-state hook, stamped at admission: finish()/abort() call
    # it exactly once (the terminal-state guard makes re-entry a
    # no-op) so the gateway's per-tenant in-flight accounting comes
    # down without the router having to report every completion path
    _on_terminal: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    # token-gap hook, stamped by the router that admitted the request:
    # every delivery but an attempt's first calls it with the seconds
    # since the delivery before and the trace id (the
    # serving_token_gap_seconds histogram and its exemplar)
    _on_gap: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    @property
    def total_len(self) -> int:
        return int(self.prompt.size) + int(self.max_new_tokens)

    @property
    def admitted_at(self) -> Optional[float]:
        """When a replica took the current attempt (its ``dispatched_at``,
        ``time.monotonic()`` as its engine accepted it); None while
        queued.  ``admitted_at - enqueued_at`` is the wait in the queue."""
        return self.dispatched_at

    # ------------------------------------------------------- streaming
    def push_tokens(self, tokens: List[int], now: float) -> None:
        """Tokens newly emitted for this request.  The FIRST push of an
        attempt stamps ``first_token_at`` — for remote replicas ``now``
        is the TOKEN frame's receive time, which is what makes reported
        TTFT the true first-token latency rather than a pump artifact."""
        if not tokens:
            return
        if self.first_token_at is None:
            self.mark_first_token(now)
        self.last_token_at = now
        self._delivered()
        self.output.extend(tokens)
        self._streamed += len(tokens)
        self._events.put(("tokens", list(tokens)))

    def mark_first_token(self, now: float) -> None:
        """The current attempt's first token was seen at ``now``."""
        self.first_token_at = now
        if self.trace is not None:
            self.trace.first_token(now)
        event("dlrover.request.first_delivery", rid=self.rid,
              ttft_ms=(now - self.submitted_at) * 1e3)

    def _delivered(self) -> None:
        """Tokens change hands: a local engine's hand-over and a remote
        worker's TOKEN frame both come through here, so the gap between
        two deliveries reads alike for both kinds of replica."""
        now = time.monotonic()
        if self._on_gap is not None and self.last_delivery_at is not None:
            self._on_gap(now - self.last_delivery_at,
                         None if self.trace is None
                         else self.trace.trace_id)
        self.deliveries += 1
        self.last_delivery_at = now

    def finish(self, output: List[int], now: float) -> None:
        if self.state in SERVING_REQUEST_TERMINAL_STATES:
            # an engine completing a request the router already
            # answered (cancelled/expired mid-generation with the
            # CANCEL frame lost, or failed over and finished elsewhere)
            # must not flip a terminal state back to DONE: result()
            # already raised and the stream already closed (DL009)
            return
        output = list(output)
        if len(output) > self._streamed:
            # engines without incremental emission (or a final flush
            # race) still complete the stream before it closes
            self._delivered()
            self._events.put(("tokens", output[self._streamed:]))
        if self.first_token_at is None:
            self.mark_first_token(now)
        self.output = output
        self.state = ServingRequestState.DONE
        # clamp: the router stamps a whole pump round with its entry
        # time, but a remote TOKEN frame received DURING the round
        # carries a later (true) timestamp — completion can never
        # precede the first token
        self.finished_at = max(now, self.first_token_at)
        if self.trace is not None:
            self.trace.finished(self.finished_at)
        self._events.put(("done", None))
        self._done.set()
        cb = self._on_terminal
        if cb is not None:
            cb(self)

    def abort(self, state: str) -> None:
        if self.state in SERVING_REQUEST_TERMINAL_STATES:
            # terminal means terminal: a second abort racing the first
            # (expiry vs cancel, failover vs expiry) must not rewrite
            # the answer the caller was already given (DL009's
            # transition spec in common/constants.py is the contract)
            return
        self.state = state
        if self.trace is not None:
            self.trace.aborted(state)
        self._events.put(("abort", state))
        self._done.set()
        cb = self._on_terminal
        if cb is not None:
            cb(self)

    def cancel(self) -> bool:
        """Withdraw this request (the client no longer wants the
        answer).  Returns True when the withdrawal was accepted —
        i.e. the request had not already reached a terminal state.
        Cancellation is asynchronous: the next router step drops the
        request from the queue (or aborts it in-flight and sends a
        CANCEL frame to the owning replica, reclaiming the engine
        slot), so ``result()`` raises :class:`RequestTimedOut` shortly
        after, not instantly."""
        if self._done.is_set():
            return False
        if self.cancel_requested:
            # already pending: one event is enough — a client retrying
            # cancel() must not inflate the cancelled counter when the
            # event drain processes both copies of a QUEUED request
            return True
        self.cancel_requested = True
        cb = self._on_cancel
        if cb is not None:
            # enqueue the withdrawal for the event-driven sweep (a
            # bare deque.append — atomic under the GIL, no lock, no
            # I/O: this runs on the CLIENT's thread)
            cb(self)
        return True

    def restart_stream(self) -> None:
        """Failover requeue: void partial output, signal consumers."""
        self.output = []
        self.first_token_at = None
        self.ttft_recorded = False
        self._streamed = 0
        # hedging state follows the attempt, not the request: the next
        # dispatch starts unhedged with a fresh progress clock
        self.dispatched_at = None
        self.last_token_at = None
        self.deliveries = 0
        self.last_delivery_at = None
        self.stream_owner = None
        self._events.put(("restart", None))

    def stream(self, timeout: Optional[float] = None) -> Iterator:
        """Iterate tokens as they are generated.  Yields ints; a
        replica failure mid-generation yields :data:`STREAM_RESTART`
        once, then the replay's tokens from the beginning.  Ends at
        completion; raises :class:`RequestTimedOut` if the request
        aborts and ``TimeoutError`` if ``timeout`` elapses between
        events."""
        while True:
            try:
                kind, payload = self._events.get(timeout=timeout)
            except queue_mod.Empty:
                raise TimeoutError(
                    f"request {self.rid}: no stream event within "
                    f"{timeout}s") from None
            if kind == "tokens":
                for tok in payload:
                    yield tok
            elif kind == "restart":
                yield STREAM_RESTART
            elif kind == "done":
                return
            else:  # abort
                raise RequestTimedOut(
                    f"request {self.rid} ended as {payload}")

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block until completion; the synchronous client surface."""
        if not self._done.wait(timeout):
            raise TimeoutError(f"request {self.rid} still pending")
        if self.state != ServingRequestState.DONE:
            raise RequestTimedOut(
                f"request {self.rid} ended as {self.state}")
        return np.asarray(self.output, np.int32)


class RequestGateway:
    """Bounded priority admission queue with deadline expiry."""

    def __init__(
        self,
        max_pending: int = 1024,
        max_prompt_len: Optional[int] = None,
        max_total_len: Optional[int] = None,
        default_timeout: Optional[float] = None,
        max_requeues: int = ServingFabric.MAX_REQUEST_REQUEUES,
        tracer: Optional[Tracer] = None,
        trace_sample_rate: float = 1.0,
        tenants: Optional[TenantRegistry] = None,
    ):
        self.max_pending = int(max_pending)
        self.max_prompt_len = max_prompt_len
        self.max_total_len = max_total_len
        self.default_timeout = default_timeout
        self.max_requeues = int(max_requeues)
        # tenant identity + QoS contracts; the default registry is the
        # trivial single-tenant fleet (everything resolves to one
        # unmetered weight-1.0 tenant — WFQ degenerates to exact FIFO
        # and nothing below behaves differently from pre-tenancy).
        # Gateways handed ONE registry meter their traffic together.
        self.tenants = tenants if tenants is not None else TenantRegistry()
        # tracing is on by default: stdlib-only dict/deque bookkeeping
        # whose memory is capped by the tracer's bounded rings, so
        # every deployment gets per-request traces without opting in.
        # ``trace_sample_rate`` < 1 keeps only that fraction of HEALTHY
        # traces (deterministic per trace_id) — the knob a
        # millions-of-users fleet turns down; incidents always survive
        self.tracer = tracer if tracer is not None else Tracer(
            sample_rate=trace_sample_rate)
        self._lock = threading.RLock()
        # tenant -> queued count ACROSS bands (per-tenant max_queued is
        # a tenant bound, not a per-band one); the band queues share
        # and maintain it on every insert/removal
        self._tenant_queued: Dict[str, int] = {}
        # tenant -> admitted-and-not-yet-terminal count; in-flight =
        # open - queued.  Incremented at admission, decremented by the
        # request's own terminal hook (_on_terminal), so every
        # completion path — DONE, expiry, cancel, shed, poison —
        # balances it without router cooperation.
        self._tenant_open: Dict[str, int] = {}
        self._queues: List[WfqBandQueue] = [
            WfqBandQueue(self._tenant_weight,
                         shared_counts=self._tenant_queued)
            for _ in _PRIORITIES
        ]
        self._next_rid = 0
        self.submitted = 0
        self.rejected = 0
        self.timed_out = 0
        self.poisoned = 0
        self.cancelled = 0
        # brown-out controller (serving/router/brownout.BrownoutPolicy),
        # attached by the router when per-priority shedding is armed;
        # None = every band admits normally.  Consulted read-only here —
        # the ROUTER updates its stage under the step lock.
        self.brownout = None
        # per-priority admissions refused by the brown-out (index =
        # priority band) — introspection for tests/dashboards; shed
        # requests also count into ``rejected`` (they were refused at
        # the door, the accounting identity must keep balancing)
        self.shed_by_priority = [0 for _ in _PRIORITIES]
        # ---- event-driven step-engine structures (ServingRouter
        # ---- step_engine="event"; the "sweep" engine keeps the
        # ---- historical full-scan paths and leaves these empty)
        # whether expire()/take_cancelled() use the deadline heap and
        # cancel-event queue below instead of scanning every queued
        # request per step; set by the router to match its step engine
        self.incremental = True
        # min-heap of (deadline, tiebreak, request) — every admitted
        # request with a deadline gets an entry (failover requeues
        # re-push, so a consumed entry can't orphan a replayed
        # request); consumed lazily when the deadline passes, so the
        # expiry sweep touches only requests that are actually due
        self._deadline_heap: List[tuple] = []
        self._heap_seq = 0
        # requests whose caller withdrew them (ServingRequest.cancel
        # fires _on_cancel), drained by take_cancelled — bare deque:
        # append is GIL-atomic from client threads
        self._cancel_events: Deque[ServingRequest] = deque()
        # RUNNING requests whose deadline passed, staged by expire()
        # for the router's in-flight sweep (consumed every step; under
        # the default let-it-finish policy the router discards them)
        self._expired_running: List[ServingRequest] = []
        # RUNNING requests whose caller withdrew them, staged by
        # take_cancelled for the router's in-flight sweep
        self._inflight_cancels: List[ServingRequest] = []
        # queue generation: bumped on EVERY queue-content change —
        # admissions, failover requeues, AND removals (placement,
        # expiry, cancellation, brown-out shed).  The scheduler's
        # short-circuit ("nothing new to place, nothing freed to place
        # it on") keys on it; removals must bump too, because dropping
        # a blocked request from the window's head lets requests
        # BEHIND it into the window — an idle marker that survived the
        # removal would starve them forever
        self.queue_gen = 0

    # ---------------------------------------------------------- tenants
    def _tenant_weight(self, tenant: str) -> float:
        # boosted_weight = configured WFQ weight x the tenant class's
        # temporary SLO-burn boost (1.0 in steady state) — the router's
        # observe phase drives the boost up while the class burns error
        # budget and decays it back once the burn recovers
        return self.tenants.boosted_weight(self.tenants.resolve(tenant))

    def _tenant_release(self, req: ServingRequest) -> None:
        """Terminal hook (exactly once per request): the tenant's open
        count comes down.  Runs on whatever thread drove the terminal
        transition, sometimes already holding this gateway's lock —
        which is why _lock is an RLock: re-entry is a no-op, and a
        bare completion path (a client thread cancelling, a proxy
        reader finishing a request) still serializes against
        admission/expiry instead of losing a decrement or a
        queue_gen bump to a concurrent += .  No I/O happens under it.
        When an in-flight-capped tenant still has queued work, the
        freed in-flight slot is a scheduling event the placement
        index cannot otherwise see — bump the queue generation so the
        idle short-circuit re-scans."""
        with self._lock:
            name = req.tenant
            n = self._tenant_open.get(name, 0) - 1
            if n > 0:
                self._tenant_open[name] = n
            else:
                self._tenant_open.pop(name, None)
            spec = self.tenants.resolve(name)
            if spec.max_inflight is not None and \
                    self._tenant_queued.get(name, 0) > 0:
                self.queue_gen += 1

    def tenant_queue_depths(self) -> Dict[str, int]:
        """Queued count per tenant across all bands (resolved names)."""
        with self._lock:
            return dict(self._tenant_queued)

    def tenant_inflight(self, tenant: str) -> int:
        """Admitted-but-not-queued (placed or being placed) count."""
        return max(0, self._tenant_open.get(tenant, 0)
                   - self._tenant_queued.get(tenant, 0))

    def tenant_can_place(self, req: ServingRequest) -> bool:
        """Scheduler gate: may this request be placed NOW without
        breaching its tenant's max_inflight?  Plain dict reads — the
        scheduler calls this per window entry."""
        spec = self.tenants.resolve(req.tenant)
        if spec.max_inflight is None:
            return True
        return self.tenant_inflight(spec.name) < spec.max_inflight

    def tenant_class(self, tenant: str) -> str:
        """The request's BOUNDED metric/SLO class (tenancy vocab)."""
        return self.tenants.resolve(tenant).tenant_class

    # ----------------------------------------------------------- admit
    def submit(
        self,
        prompt_ids,
        max_new_tokens: int,
        priority: int = PRIORITY_NORMAL,
        timeout: Optional[float] = None,
        now: Optional[float] = None,
        tenant: Optional[str] = None,
    ) -> ServingRequest:
        """Admit a request or raise :class:`AdmissionError`.  ``timeout``
        (seconds, default ``default_timeout``) becomes an absolute
        deadline: expiry while QUEUED aborts the request; a request
        already generating is allowed to finish by default (its work is
        paid for) unless the router runs with
        ``cancel_inflight_on_expiry=True``, which aborts it and sends
        CANCEL so the engine slot returns to live traffic."""
        if priority not in _PRIORITIES:
            raise ValueError(f"unknown priority {priority}")
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size < 1:
            raise AdmissionError("empty prompt")
        if self.max_prompt_len and prompt.size > self.max_prompt_len:
            raise AdmissionError(
                f"prompt length {prompt.size} exceeds gateway bound "
                f"{self.max_prompt_len}")
        total = prompt.size + int(max_new_tokens)
        if self.max_total_len and total > self.max_total_len:
            raise AdmissionError(
                f"prompt + max_new_tokens = {total} exceeds gateway "
                f"bound {self.max_total_len}")
        now = time.monotonic() if now is None else now
        timeout = self.default_timeout if timeout is None else timeout
        spec = self.tenants.resolve(tenant)
        with self._lock:
            # admission checks in refusal-severity order, and EXACTLY
            # ONE ``rejected`` count per refused submit whichever path
            # raises — a request that is simultaneously over quota AND
            # in a browned-out band must not double-count (the books
            # identity: offered == admitted + rejected)
            brownout = self.brownout
            if brownout is not None and brownout.sheds_priority(priority):
                # ordered degradation: this band is browned out while
                # higher bands still admit — a refusal here IS the
                # mechanism protecting HIGH, not a capacity accident
                self.rejected += 1
                self.shed_by_priority[priority] += 1
                self.tenants.count_shed(spec.name)
                retry_after = brownout.expected_recovery_s(now)
                raise BrownoutShedError(
                    f"priority {priority} shed at brown-out stage "
                    f"{brownout.stage} ({brownout.stage_name}); "
                    f"expected recovery in >= {retry_after:.1f}s",
                    stage=brownout.stage,
                    stage_name=brownout.stage_name,
                    retry_after_s=retry_after)
            if spec.max_queued is not None and \
                    self._tenant_queued.get(spec.name, 0) \
                    >= spec.max_queued:
                # the tenant's own buffer bound (all bands: a memory
                # bound, unlike the QPS bucket below) — checked BEFORE
                # the bucket so the refusal does not also burn a token
                self.rejected += 1
                self.tenants.count_quota_rejected(spec.name)
                raise TenantQuotaError(
                    f"tenant {spec.name!r} at max_queued "
                    f"({spec.max_queued})", tenant=spec.name,
                    retry_after_s=(1.0 / spec.quota_qps
                                   if spec.quota_qps else 0.0))
            if priority != PRIORITY_HIGH:
                # quota QPS: BATCH/NORMAL over the tenant's token
                # bucket are refused with the time-to-next-token hint;
                # HIGH is NEVER quota-refused — over-quota HIGH
                # traffic pays by fair-queueing behind its own tags
                ok, retry_after = self.tenants.try_admit(spec, now)
                if not ok:
                    self.rejected += 1
                    self.tenants.count_quota_rejected(spec.name)
                    raise TenantQuotaError(
                        f"tenant {spec.name!r} over quota "
                        f"({spec.quota_qps:g} QPS); next token in "
                        f"{retry_after:.3f}s", tenant=spec.name,
                        retry_after_s=retry_after)
            if self.depth() >= self.max_pending:
                self.rejected += 1
                raise QueueFullError(
                    f"gateway at capacity ({self.max_pending} pending)")
            req = ServingRequest(
                rid=self._next_rid,
                prompt=prompt,
                max_new_tokens=int(max_new_tokens),
                priority=priority,
                tenant=spec.name,
                # timeout=0 means "fail unless immediately serviceable",
                # not "no deadline" — only None disables expiry
                deadline=(now + timeout) if timeout is not None else None,
                submitted_at=now,
                enqueued_at=now,
            )
            self._next_rid += 1
            self.tenants.count_admitted(spec.name)
            self._tenant_open[spec.name] = \
                self._tenant_open.get(spec.name, 0) + 1
            req._on_terminal = self._tenant_release
            req.trace = RequestTrace(
                self.tracer, req.rid, now=now,
                priority=priority, prompt_len=int(prompt.size),
                max_new_tokens=int(max_new_tokens),
            )
            req._on_cancel = self._cancel_events.append
            if self.incremental and req.deadline is not None:
                self._heap_seq += 1
                heapq.heappush(
                    self._deadline_heap,
                    (req.deadline, self._heap_seq, req))
            self._queues[priority].append(req)
            self.submitted += 1
            self.queue_gen += 1
            return req

    def requeue_front(
        self, requests: List[ServingRequest],
        dump: bool = True,
        now: Optional[float] = None,
    ) -> List[ServingRequest]:
        """Failover path: a dead replica's in-flight requests re-enter at
        the FRONT of their band (they have waited longest).  Partial
        output is discarded — the replay regenerates from scratch
        (at-least-once, exactly-once output) — and any open token stream
        is restarted.

        Poison guard: a request that has already burned ``max_requeues``
        replays is statistically the thing KILLING replicas, not their
        victim — it is failed with ``POISONED`` instead of circulating
        forever.  Returns the poisoned requests (the router counts them
        into ``serving_requests_poisoned_total``).

        ``dump=False`` skips the poison flight-recorder dumps: a caller
        already holding its own lock (the router's step) defers them to
        after release and dumps from the returned list itself."""
        poisoned: List[ServingRequest] = []
        requeued: List[ServingRequest] = []
        now = time.monotonic() if now is None else now
        with self._lock:
            for req in reversed(requests):
                if req.state not in (ServingRequestState.QUEUED,
                                     ServingRequestState.RUNNING):
                    # a failover racing a cancel (or an expiry) must
                    # not resurrect a request that already reached a
                    # terminal state — its stream is closed and its
                    # caller has been answered
                    continue
                req.requeues += 1
                if req.requeues > self.max_requeues:
                    self.poisoned += 1
                    req.abort(ServingRequestState.POISONED)
                    poisoned.append(req)
                    continue
                dead_replica = req.replica
                if dead_replica is None and req.trace is not None \
                        and req.trace.attempt is not None:
                    # placement-failure requeues arrive before submit()
                    # stamped req.replica — the attempt span (stamped
                    # by the scheduler) still knows who died
                    dead_replica = req.trace.attempt.attrs.get("replica")
                req.state = ServingRequestState.QUEUED
                req.replica = None
                req.engine_rid = None
                # the replay's queue wait starts NOW — the dead
                # attempt's service time is the failover's cost, not
                # queueing, and must not pollute the queue-wait metrics
                req.enqueued_at = now
                req.restart_stream()
                if req.trace is not None:
                    # close the dead-replica attempt as "failover" (it
                    # stays in the tree next to the retry) and reopen a
                    # queue span for the replay
                    req.trace.failover(
                        f"replica {dead_replica} died", now=now)
                self._queues[req.priority].appendleft(req)
                if self.incremental and req.deadline is not None:
                    # the original heap entry may already have been
                    # consumed (deadline passed while RUNNING under the
                    # let-it-finish policy): a replay past its deadline
                    # must still expire promptly, so re-push
                    self._heap_seq += 1
                    heapq.heappush(
                        self._deadline_heap,
                        (req.deadline, self._heap_seq, req))
                self.queue_gen += 1
                requeued.append(req)
        # flight-recorder dumps happen OUTSIDE the queue lock: logging
        # and tree serialization must never extend the admission
        # critical section
        for req in requeued:
            self.tracer.recorder.record(
                "request_requeued", rid=req.rid, requeues=req.requeues)
        for req in poisoned:
            self.tracer.recorder.record("request_poisoned", rid=req.rid)
            if dump and req.trace is not None:
                self.tracer.flight_dump("poisoned", req.trace.trace_id)
        return poisoned

    # ------------------------------------------------------- schedule
    def schedule_scan(self, window: int) -> List[ServingRequest]:
        """The first ``window`` queued requests in strict priority order
        (a snapshot; the scheduler calls :meth:`remove` on placement).
        Bounded look-ahead keeps head-of-line blocking at bay without
        letting a huge backlog starve placement decisions."""
        with self._lock:
            out: List[ServingRequest] = []
            for q in self._queues:
                if len(out) >= window:
                    break
                out.extend(q.scan(window - len(out)))
            return out

    def remove(self, req: ServingRequest) -> bool:
        with self._lock:
            try:
                self._queues[req.priority].remove(req)
                self.queue_gen += 1
                return True
            except ValueError:
                return False

    # -------------------------------------------------------- expiry
    def expire(self, now: Optional[float] = None,
               dump: bool = True) -> List[ServingRequest]:
        """Abort queued requests whose deadline has passed.
        ``dump=False`` defers the flight-recorder dumps to the caller
        (the router holds its step lock here and dumps after release —
        serialization + logging must not extend ITS critical section
        either).

        Two implementations behind one contract: the event engine pops
        only DUE entries off the deadline heap (an idle step costs one
        heap peek), the sweep engine scans every queued request — the
        measured A/B in PERF.md is exactly this difference, at rig
        scale."""
        now = time.monotonic() if now is None else now
        expired: List[ServingRequest] = []
        with self._lock:
            if self.incremental:
                due: List[ServingRequest] = []
                # one request can hold SEVERAL heap entries (each
                # failover requeue pushes one); collecting it twice
                # here would abort/count it twice and break the books
                # identity — dedupe by identity at collection
                due_seen: set = set()
                heap = self._deadline_heap
                while heap and heap[0][0] < now:
                    _, _, req = heapq.heappop(heap)
                    if req.state == ServingRequestState.QUEUED:
                        if id(req) not in due_seen:
                            due_seen.add(id(req))
                            due.append(req)
                    elif req.state == ServingRequestState.RUNNING:
                        # the router's in-flight sweep decides (abort +
                        # CANCEL under cancel_inflight_on_expiry,
                        # discard under let-it-finish; a later failover
                        # requeue re-pushes a fresh entry)
                        self._expired_running.append(req)
                    # terminal states: the answer already exists
                if due:
                    # bulk removal from ONLY the touched bands —
                    # per-entry remove would be O(n^2) on a mass
                    # expiry (a stall expiring a whole queue at once)
                    due_ids = {id(r) for r in due}
                    for i in {r.priority for r in due}:
                        self._queues[i].discard_ids(due_ids)
                    self.queue_gen += 1
                    for req in due:
                        req.abort(ServingRequestState.TIMED_OUT)
                        expired.append(req)
                        self.timed_out += 1
            else:
                for q in self._queues:
                    due = [req for req in q
                           if req.deadline is not None
                           and now > req.deadline]
                    if due:
                        q.discard_ids({id(r) for r in due})
                        for req in due:
                            req.abort(ServingRequestState.TIMED_OUT)
                            expired.append(req)
                            self.timed_out += 1
                        self.queue_gen += 1
        # dump outside the queue lock — the black-box readout
        # serializes the span tree and logs, neither belongs in the
        # admission path
        for req in expired:
            self.tracer.recorder.record(
                "deadline_expired", rid=req.rid, now=now)
            if dump and req.trace is not None:
                self.tracer.flight_dump(
                    "deadline_expired", req.trace.trace_id, now=now)
        return expired

    def take_cancelled(self, now: Optional[float] = None,
                       dump: bool = True) -> List[ServingRequest]:
        """Drop queued requests whose caller withdrew them
        (:meth:`ServingRequest.cancel`), aborting each as ``CANCELLED``.
        Same deferral contract as :meth:`expire`: ``dump=False`` leaves
        the flight-recorder dumps to a lock-holding caller, and ``now``
        keeps recorder timestamps on the caller's (possibly synthetic)
        clock next to the round's other events.

        Event engine: drains the cancel-event queue (each withdrawal
        visited once; RUNNING ones staged for the router's in-flight
        sweep via :meth:`take_inflight_cancels`).  Sweep engine: full
        scan of every band, as before."""
        taken: List[ServingRequest] = []
        with self._lock:
            if self.incremental:
                queued: List[ServingRequest] = []
                # belt to cancel()'s idempotence suspender: duplicate
                # events for one request (however minted) must not
                # count it twice
                q_seen: set = set()
                while self._cancel_events:
                    req = self._cancel_events.popleft()
                    if req.state == ServingRequestState.QUEUED:
                        if id(req) not in q_seen:
                            q_seen.add(id(req))
                            queued.append(req)
                    elif req.state == ServingRequestState.RUNNING:
                        self._inflight_cancels.append(req)
                    # terminal: a failover/expiry already answered
                if queued:
                    q_ids = {id(r) for r in queued}
                    for i in {r.priority for r in queued}:
                        self._queues[i].discard_ids(q_ids)
                    self.queue_gen += 1
                    for req in queued:
                        req.abort(ServingRequestState.CANCELLED)
                        taken.append(req)
                        self.cancelled += 1
            else:
                # sweep engine: a cancel event was also queued (the
                # callback fires regardless); clear it so the deque
                # cannot grow without a consumer
                self._cancel_events.clear()
                for q in self._queues:
                    withdrawn = [req for req in q
                                 if req.cancel_requested]
                    if withdrawn:
                        q.discard_ids({id(r) for r in withdrawn})
                        for req in withdrawn:
                            req.abort(ServingRequestState.CANCELLED)
                            taken.append(req)
                            self.cancelled += 1
                        self.queue_gen += 1
        for req in taken:
            self.tracer.recorder.record(
                "request_cancelled", rid=req.rid, now=now)
            if dump and req.trace is not None:
                self.tracer.flight_dump(
                    "cancelled", req.trace.trace_id, now=now)
        return taken

    def take_inflight_cancels(self) -> List[ServingRequest]:
        """RUNNING withdrawals staged by the event engine's
        :meth:`take_cancelled` — the router aborts them and queues
        CANCEL deliveries, visiting ONLY these instead of every
        in-flight request on every replica each step."""
        with self._lock:
            taken, self._inflight_cancels = self._inflight_cancels, []
            return taken

    def take_expired_running(self) -> List[ServingRequest]:
        """RUNNING requests whose deadline passed, staged by the event
        engine's :meth:`expire` — consumed by the router every step
        (acted on under ``cancel_inflight_on_expiry``, discarded under
        the default let-it-finish policy, where a later failover
        requeue re-arms the deadline heap)."""
        with self._lock:
            taken, self._expired_running = self._expired_running, []
            return taken

    def shed_queued(self, priority: int,
                    now: Optional[float] = None,
                    dump: bool = True,
                    keep_total: Optional[int] = None
                    ) -> List[ServingRequest]:
        """Brown-out stage 2: expiry-cancel QUEUED requests of
        ``priority`` (the band being browned out), aborting each as
        ``CANCELLED`` through the same machinery a caller withdrawal
        uses — the caller's ``result()`` raises promptly instead of
        aging toward its deadline in a queue that will never drain.
        Same deferral contract as :meth:`expire`.

        With a multi-tenant registry and a ``keep_total`` survivor
        budget the sweep is PROPORTIONAL: :func:`plan_shed` takes from
        the tenants furthest over their fair share first, so the
        tenant that caused the brown-out pays for it.  A trivial
        registry (or ``keep_total=None``) keeps the legacy
        whole-band clear."""
        taken: List[ServingRequest] = []
        with self._lock:
            q = self._queues[priority]
            if q:
                if keep_total is None or self.tenants.trivial:
                    taken = q.clear_all()
                else:
                    taken = q.pop_shed(plan_shed(
                        q.counts_by_tenant(), self.tenants,
                        keep_total))
                for req in taken:
                    req.abort(ServingRequestState.CANCELLED)
                    self.cancelled += 1
                    self.tenants.count_shed(req.tenant)
                if taken:
                    self.queue_gen += 1
        for req in taken:
            self.tracer.recorder.record(
                "brownout_shed_queued", rid=req.rid,
                priority=priority, now=now)
            if dump and req.trace is not None:
                self.tracer.flight_dump(
                    "brownout_shed", req.trace.trace_id, now=now)
        return taken

    def depth(self, priority: Optional[int] = None) -> int:
        with self._lock:
            if priority is not None:
                return len(self._queues[priority])
            return sum(len(q) for q in self._queues)
