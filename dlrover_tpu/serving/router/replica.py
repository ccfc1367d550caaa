"""Replica handles + manager: heartbeats, failover, elastic membership.

The training stack's fault-tolerance contract, applied to inference:

- every successful pump of a replica's engine refreshes its
  **heartbeat**; a replica that stops heartbeating (crashed process,
  hung device) is declared DEAD exactly like a worker that misses its
  agent heartbeats;
- a DEAD replica's in-flight requests are **drained and requeued** at
  the front of the gateway — the failover guarantee is *zero lost
  requests* (at-least-once execution: a replay regenerates from
  scratch, partial output is discarded);
- **graceful join/leave** makes replica count an elastic knob: a
  joining replica starts taking placements on its first heartbeat, a
  leaving one DRAINS (no new placements, in-flight finishes) before it
  is removed — scale-down loses nothing either.

A replica's engine is anything speaking the small duck-typed protocol
documented on :class:`ReplicaHandle` — the in-process
:class:`~dlrover_tpu.serving.engine.InferenceEngine` (via
:class:`InferenceEngineAdapter`), a test fake, or an RPC proxy to a
remote model server.
"""

from __future__ import annotations

import re
import time
from typing import Dict, List, Optional

from dlrover_tpu.common.constants import (
    SERVING_REQUEST_TERMINAL_STATES,
    ReplicaStatus,
    ServingRequestState,
)
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.serving.router.gateway import ServingRequest


class ReplicaDeadError(RuntimeError):
    """The replica's engine is gone; the caller must fail it over."""


class StaleRequestError(ValueError):
    """A submit raced the request into a terminal state (cancel/expiry
    landed between the placement decision and delivery).  ValueError
    subclass so callers treating any submit refusal as a rejection
    stay correct, but distinct so the router can tell 'this request is
    already answered' from 'the engine rejected it'."""


def stream_deltas(
    outputs: Dict[int, List[int]],
    sent: Dict[int, int],
    prune: bool = True,
) -> List[tuple]:
    """THE streaming diff: new tokens per request id since the last
    call, updating ``sent`` positions in place.  One implementation for
    both sides of the fabric — the in-process adapter below and the
    remote worker's TOKEN-frame emitter (serving/remote/worker.py) —
    so flush/reset edge cases cannot drift apart.  ``prune=True`` drops
    positions for ids absent from ``outputs`` (finished/evicted);
    callers that flush a final suffix from their own completion path
    (the worker's DONE handler) pass ``prune=False`` and pop positions
    themselves."""
    events = []
    for rid, out in outputs.items():
        n = sent.get(rid, 0)
        if len(out) > n:
            events.append((rid, list(out[n:])))
            sent[rid] = len(out)
    if prune:
        for rid in list(sent):
            if rid not in outputs:
                del sent[rid]
    return events


class InferenceEngineAdapter:
    """Protocol adapter over :class:`serving.engine.InferenceEngine`."""

    def __init__(self, engine):
        self.engine = engine
        self._stream_pos: Dict[int, int] = {}  # rid -> tokens streamed

    @property
    def block_size(self) -> int:
        """KV block granularity for capacity reporting (0 = unpaged) —
        the remote worker publishes this in its HELLO frame so the
        router-side proxy can gate placements on blocks."""
        if not getattr(self.engine, "paged", False):
            return 0
        return int(getattr(self.engine, "block_size", 0))

    def add_request(self, prompt, max_new_tokens: int) -> int:
        return self.engine.add_request(prompt, max_new_tokens)

    def step(self) -> List:
        return self.engine.step()

    def inflight_outputs(self) -> Dict[int, List[int]]:
        """Live output snapshot per request that HOLDS A SLOT (finished
        ones are covered by ``step()``'s return, and so is one whose
        last decode chunk is dispatched and unread: the engine has
        taken it off its slot, and the step that reads the chunk
        returns it whole) — the streaming introspection surface the
        remote worker and the local pump both diff against."""
        return {
            req.rid: req.output
            for req in self.engine._slot_req if req is not None
        }

    def drain_token_events(self, now: float) -> List:
        """Tokens emitted since the last drain as ``(rid, tokens, t)``
        events, ``t`` the engine's own stamp of each request's newest
        tokens: the read of the program that sampled them
        (``Request.last_token_at``, on the router's clock), which lies
        INSIDE the step the pump's ``now`` began before (so ``now`` is
        not used here; a remote proxy's ``t`` is the TOKEN frame's
        receive time)."""
        read_at = {req.rid: req.last_token_at
                   for req in self.engine._slot_req if req is not None}
        return [
            (rid, toks, read_at[rid])
            for rid, toks in stream_deltas(
                self.inflight_outputs(), self._stream_pos)
        ]

    @property
    def has_work(self) -> bool:
        return self.engine.has_work

    def cancel(self, erid: int) -> bool:
        """Withdraw a request from the engine, freeing its decode slot
        and (paged engines) its KV blocks immediately — the local twin
        of the remote worker's CANCEL handler, so in-process and remote
        replicas reclaim capacity identically.  Covers all the places
        the request can be: the engine admission queue, a live slot
        (decoding OR mid-chunked-prefill — the engine reclaims a
        half-prefilled slot identically), or already finished (a
        no-op — the withdrawal still "delivered").  Always returns
        True: local delivery cannot fail."""
        self._stream_pos.pop(erid, None)
        return self.engine.cancel(erid)

    def engine_metrics(self) -> Dict[str, float]:
        """Raw-speed engine introspection for the router's metric
        sweep (unprefixed keys; RouterMetrics owns the ``serving_*``
        names).  Remote replicas report the same dict on their STATS
        frames, so local and remote fleets render identically."""
        eng, st = self.engine, self.engine.stats
        out = {
            "tokens_per_forward": st.tokens_per_forward,
            "kv_quant_blocks": float(
                getattr(eng, "kv_quant_blocks", 0)),
            "prefill_chunk_seconds": st.prefill_chunk_seconds,
            "prefill_calls": float(st.prefill_calls),
            "prefill_admissions": float(st.prefill_admissions),
            # programs sent to the device, and those sent while an
            # earlier one was unread (the sums: a fleet's share weighs
            # by work); steps that returned with their decode chunk in
            # flight, and chunk lanes whose request had ended before
            # the chunk was read
            "dispatches": float(st.dispatches),
            "chained_dispatches": float(st.chained_dispatches),
            "lookahead_steps": float(st.lookahead_steps),
            "wasted_lane_chunks": float(st.wasted_lane_chunks),
            # the requests' own clocks, summed (serving/engine.py)
            **{name: float(getattr(st, name))
               for name in st.REQUEST_CLOCK},
        }
        if getattr(eng, "paged", False):
            # resolved paged-attention impl (0=xla gather, 1=fused
            # pallas kernel) + the kernel path's cumulative decode
            # seconds — floats so the dict rides STATS frames as-is.
            # Only PAGED engines report: a dense replica has no paged
            # attention path at all, and counting it into the labeled
            # serving_attention_impl{impl="xla"} series would hide
            # the xla->pallas crossover the gauge exists to show
            impl = getattr(eng, "attention_impl", "xla")
            out["attention_impl_pallas"] = (
                1.0 if impl == "pallas" else 0.0)
            out["paged_kernel_step_seconds"] = (
                st.decode_seconds if impl == "pallas" else 0.0)
            # what that kernel's decode forwards copied against what
            # their slots could see (both 0 on the gather path)
            out["kv_rows_live"] = float(st.kv_rows_live)
            out["kv_rows_streamed"] = float(st.kv_rows_streamed)
        if getattr(eng, "paged", False) or getattr(eng, "rowless", False):
            # (a model with no layer that caches rows has no pool to page
            # and no paged attention, and counts what follows all the same)
            # a learned selection of keys and a share of the experts:
            # the sums, so that a fleet's ratio weighs by work (all 0
            # for a model with neither)
            for name in ("dsa_rows_live", "attn_rows_selected",
                         "moe_picks", "moe_picks_held",
                         "moe_buffer_walks", "moe_layer_forwards",
                         "prefill_query_tiles",
                         "prefill_query_tiles_live",
                         "window_rows_in_window", "window_rows_streamed"):
                out[name] = float(getattr(st, name, 0))
            # window layers' rings beside the pools of blocks (0 for a
            # model with no window layer)
            kinds = getattr(eng, "cache_nbytes_by_kind", None)
            if kinds is not None:
                out["window_cache_bytes"] = float(kinds["window"])
                out["cache_bytes"] = float(sum(kinds.values()))
            # prefix-cache ledger (all-float, so the dict still rides
            # STATS frames as-is); dense engines have no sharing
            prefix = getattr(eng, "prefix_stats", None)
            if prefix is not None:
                out.update(prefix())
        if st.spec_proposed:
            # only replicas actually speculating report a ratio — a
            # spec-disabled engine's structural 0.0 would dilute the
            # fleet's speculation-health mean toward zero
            out["spec_accept_ratio"] = st.spec_accept_ratio
        return out

    def prefix_heads(self) -> List[str]:
        """Hottest committed prefix-head digests ([] when unpaged) —
        the local twin of the remote worker's ``prefix_heads`` STATS
        payload, feeding the router's prefix-routing table."""
        fn = getattr(self.engine, "prefix_heads", None)
        return [] if fn is None else list(fn())

    def slots_free(self) -> int:
        eng = self.engine
        free = sum(1 for r in eng._slot_req if r is None)
        # requests the router already handed over but the engine has not
        # yet admitted still consume future slots
        return max(0, free - len(eng._queue))

    def blocks_free(self) -> float:
        eng = self.engine
        if not getattr(eng, "paged", False):
            return float("inf")
        # handed-over-but-unadmitted requests will consume blocks too —
        # without subtracting them the router over-places and a request
        # can sit in the engine queue past the pool's real capacity
        pending = sum(
            self.blocks_needed(r.prompt.size, r.max_new_tokens)
            for r in eng._queue
        )
        return float(eng._blockmgr.available_blocks) - pending

    def blocks_needed(self, prompt_len: int, max_new_tokens: int) -> float:
        """The engine's REAL admission requirement (engine.py _admit):
        bucket-padded prefill writes + generation + speculative slack —
        the router must gate placement on the same formula or a
        'placed' request can wait in the engine queue forever."""
        eng = self.engine
        if not getattr(eng, "paged", False):
            return 0.0
        from dlrover_tpu.serving.engine import _bucket

        total = max(
            prompt_len + max_new_tokens + max(0, eng.speculative_k),
            _bucket(prompt_len, eng.buckets),
        )
        return float(-(-total // eng.block_size))


class ReplicaHandle:
    """One serving replica as the router sees it.

    ``engine`` protocol (duck-typed):

    - ``add_request(prompt, max_new_tokens) -> int`` (engine-local rid)
    - ``step() -> list`` of finished engine requests (``.rid``,
      ``.output``)
    - ``has_work -> bool``
    - ``slots_free() -> int`` and ``blocks_free() -> float``
    - optional ``blocks_needed(prompt_len, max_new_tokens) -> float``
      (the engine's own admission formula; the scheduler uses its
      block-size default otherwise)
    - optional ``cancel(erid) -> bool`` — withdraw a request, freeing
      its slot/KV blocks.  ``False`` means the withdrawal could not be
      DELIVERED (a remote send failure — counted into
      ``serving_cancel_send_failures_total``); engines that deliver
      locally return True even for an already-finished erid.
    """

    def __init__(self, name: str, engine, node=None):
        self.name = name
        self.engine = engine
        self.node = node  # cluster Node this replica runs on, if any
        self.status = ReplicaStatus.JOINING
        self.last_heartbeat = 0.0
        self.joined_at = 0.0
        # probation (crash-loop damping): a replica whose predecessors
        # kept dying right after joining is held out of placement until
        # this monotonic time — set by ReplicaManager.join
        self.probation_until = 0.0
        # gray-zone state (phi-accrual suspicion, ReplicaManager.
        # update_suspects): ``suspected`` mirrors the engine's raw phi
        # verdict; ``demoted`` is the EFFECTIVE placement penalty —
        # raw suspicion OR the flap-damping hold that keeps a
        # recovering link demoted until ``demoted_until``, so a
        # flapping link yields one demote/restore cycle, not one per
        # flap.  Demotion is a placement ORDERING penalty only: the
        # replica stays schedulable and its in-flight work continues.
        self.suspected = False
        self.demoted = False
        self.demoted_until = 0.0
        self.inflight: Dict[int, ServingRequest] = {}
        self.generated_tokens = 0
        # requests whose FIRST token arrived in the latest pump —
        # staged here so the router records TTFT by visiting only
        # requests with news instead of sweeping every in-flight
        # request per step (drained + cleared by ServingRouter.step)
        self.ttft_pending: List[ServingRequest] = []
        self._failed = False
        # first-ever placement marker: the autoscale trace's last
        # milestone (plan -> spawn -> join -> FIRST PLACEMENT) keys
        # off the router recording the transition exactly once
        self.ever_placed = False
        # engines that can carry trace context downstream (the remote
        # proxy forwards it in the SUBMIT frame header) declare a
        # ``trace=`` kwarg; probed once so submit stays cheap
        try:
            import inspect

            params = inspect.signature(engine.add_request).parameters
            self._engine_takes_trace = "trace" in params
            # engines that can tag a submission with its hedge attempt
            # ordinal (the remote proxy's SUBMIT frame key)
            self._engine_takes_attempt = "attempt" in params
        except (TypeError, ValueError):
            self._engine_takes_trace = False
            self._engine_takes_attempt = False

    # -------------------------------------------------------- capacity
    def slots_free(self) -> int:
        return self.engine.slots_free()

    def blocks_free(self) -> float:
        return self.engine.blocks_free()

    def blocks_needed(self, prompt_len: int,
                      max_new_tokens: int) -> Optional[float]:
        """Engine-specific block estimate for a request, or None when
        the engine doesn't expose one (scheduler falls back to its
        block-size default)."""
        fn = getattr(self.engine, "blocks_needed", None)
        return None if fn is None else fn(prompt_len, max_new_tokens)

    def engine_metrics(self) -> Optional[Dict[str, float]]:
        """Raw-speed engine introspection (spec accept ratio, int8 KV
        pool size, chunked-prefill seconds) when the engine reports it
        — the router's metric sweep aggregates these across the fleet.
        None for engines without the surface (FakeEngine)."""
        fn = getattr(self.engine, "engine_metrics", None)
        if fn is None:
            return None
        em = fn()
        return em if em else None

    def prefix_heads(self) -> List[str]:
        """This replica's advertised hot prefix heads (hex digests),
        [] for engines without the surface — the router's observe
        phase feeds these into the scheduler's prefix-routing table
        every step (replacement semantics: a head that stops being
        advertised was evicted, and its routing entry drops)."""
        fn = getattr(self.engine, "prefix_heads", None)
        if fn is None:
            return []
        try:
            return list(fn())
        except Exception:
            return []

    def suspect(self, now: Optional[float] = None) -> bool:
        """The engine's raw phi-accrual verdict (remote proxies expose
        ``suspect()``; engines without the surface — local adapters,
        fakes — are never suspect)."""
        fn = getattr(self.engine, "suspect", None)
        if fn is None:
            return False
        try:
            return bool(fn(now))
        except Exception:
            return False

    def phi_value(self, now: Optional[float] = None) -> float:
        """Current phi suspicion from the engine (0.0 for engines
        without a detector) — the ``serving_phi_max`` gauge's feed."""
        fn = getattr(self.engine, "phi_value", None)
        if fn is None:
            return 0.0
        try:
            return float(fn(now))
        except Exception:
            return 0.0

    @property
    def schedulable(self) -> bool:
        return self.status == ReplicaStatus.UP and not self._failed

    @property
    def pumpable(self) -> bool:
        return self.status in (ReplicaStatus.UP, ReplicaStatus.DRAINING)

    @property
    def drained(self) -> bool:
        return (
            self.status == ReplicaStatus.DRAINING
            and not self.inflight
            and not self.engine.has_work
        )

    # -------------------------------------------------------- requests
    def submit(self, req: ServingRequest) -> None:
        if not self.schedulable:
            raise ReplicaDeadError(f"replica {self.name} not schedulable")
        if req.state != ServingRequestState.QUEUED:
            # a cancel/expiry can race placement now that submits run
            # outside the router's step lock; placing a request that
            # already reached a terminal state would resurrect it
            # (DL009: only QUEUED -> RUNNING is a declared transition)
            raise StaleRequestError(
                f"request {req.rid} is {req.state}, not queued")
        tr = req.trace
        if tr is not None:
            tr.submit_started()
        # a sampled-out trace propagates no context (traceparent() is
        # None): the worker then builds/ships no spans for it, so the
        # sample-rate knob cuts worker-side cost too — incident-marked
        # traces (failover retries) resume propagating
        tp = tr.traceparent() if tr is not None else None
        try:
            if tp is not None and self._engine_takes_trace:
                erid = self.engine.add_request(
                    req.prompt, req.max_new_tokens, trace=tp)
            else:
                erid = self.engine.add_request(
                    req.prompt, req.max_new_tokens)
        except Exception:
            if tr is not None:
                tr.submit_finished(status="error")
            raise
        if tr is not None:
            tr.submit_finished()
        req.replica = self.name
        req.engine_rid = erid
        req.state = ServingRequestState.RUNNING
        req.dispatched_at = time.monotonic()
        self.inflight[erid] = req

    def submit_hedge(self, req: ServingRequest) -> int:
        """Dispatch a HEDGE attempt of an already-RUNNING request to
        this replica: the engine decodes it like any other request and
        this handle tracks it in ``inflight``, but the request's
        routing identity (``replica``/``engine_rid``/``state``) stays
        with the primary — first DONE wins, and the router cancels
        whichever attempt loses.  Engines that accept an ``attempt``
        kwarg (the remote proxy) get the attempt ordinal, which rides
        the SUBMIT frame and comes back on DONE for auditability."""
        if not self.schedulable:
            raise ReplicaDeadError(f"replica {self.name} not schedulable")
        if req.state != ServingRequestState.RUNNING:
            # completed/aborted between the hedge decision and this
            # delivery: racing a second copy of an answered request
            # would waste a slot on a stream nobody reads
            raise StaleRequestError(
                f"request {req.rid} is {req.state}, not running")
        if self._engine_takes_attempt:
            erid = self.engine.add_request(
                req.prompt, req.max_new_tokens, attempt=1)
        else:
            erid = self.engine.add_request(
                req.prompt, req.max_new_tokens)
        self.inflight[erid] = req
        return erid

    def pump(self, now: Optional[float] = None) -> List[ServingRequest]:
        """One engine step; returns router requests finished by it.
        A successful pump IS the heartbeat (the engine demonstrably made
        progress); an engine exception marks the replica failed.  (For
        a remote engine, ``step()`` itself raises when the worker is
        dead or frame-silent, so the heartbeat only refreshes on real
        evidence of a live process.)"""
        now = time.monotonic() if now is None else now
        if self._failed:
            raise ReplicaDeadError(f"replica {self.name} is dead")
        try:
            finished = self.engine.step() if self.engine.has_work else []
        except Exception as e:
            self._failed = True
            raise ReplicaDeadError(
                f"replica {self.name} engine failed: {e}") from e
        self.last_heartbeat = now
        # streaming engines: forward newly-emitted tokens into each
        # request's stream; the event timestamp (the engine's read of
        # the tokens in process, the TOKEN frame's receive time for a
        # remote worker) stamps first_token_at — TTFT is measured from
        # true first-token emission
        drain = getattr(self.engine, "drain_token_events", None)
        if drain is not None:
            for erid, toks, t in drain(now):
                req = self.inflight.get(erid)
                if req is None:
                    continue
                owner = req.stream_owner
                if owner is not None and owner != (self.name, erid):
                    # hedged request, and this attempt does not own
                    # the client stream: it races silently (it can
                    # still WIN via DONE, whose flush delivers the
                    # full suffix) — forwarding its tokens too would
                    # interleave two streams into one output
                    continue
                first = req.first_token_at is None
                req.push_tokens(toks, t)
                if first and req.first_token_at is not None:
                    self.ttft_pending.append(req)
        done: List[ServingRequest] = []
        for ereq in finished:
            req = self.inflight.pop(ereq.rid, None)
            if req is None:
                continue  # e.g. admitted before a drain started
            if req.state in SERVING_REQUEST_TERMINAL_STATES:
                # the losing attempt of a hedge race (or a completion
                # racing a cancel): the request was already answered —
                # finish() would no-op on the state, but it must not
                # be double-counted into ``done`` (completed_total
                # stays exactly one per request, the S9/S10 dedup
                # contract extended to hedging)
                continue
            self.generated_tokens += len(ereq.output)
            # (a sampled-out request's worker shipped no spans: its
            # completion pays no span grafting, the cost the sampling
            # knob exists to shed)
            spans = getattr(ereq, "trace_spans", None)
            if req.trace is not None and spans:
                # remote workers ship their own spans (decode steps,
                # engine time) back on the DONE frame, already shifted
                # to this process's clock by the proxy — graft them
                # under the attempt that served this request BEFORE
                # finish() closes the trace into the ring
                req.trace.graft_worker_spans(spans)
            # finished when its last tokens were handed over: an engine
            # that stamps its requests says when (inside this step); one
            # that does not (FakeEngine, a remote proxy) ends at ``now``
            read_at = getattr(ereq, "last_token_at", None)
            req.finish(list(ereq.output),
                       now if read_at is None else read_at)
            done.append(req)
        if drain is None:
            # legacy engines surface no token stream: the first pump
            # after placement completes the prefill and emits the first
            # token (engine._admit runs inside step()), so it remains
            # the best available TTFT estimate
            for req in self.inflight.values():
                if req.first_token_at is None:
                    req.mark_first_token(now)
                    self.ttft_pending.append(req)
            for req in done:
                if req.first_token_at is None:
                    req.first_token_at = now
        return done

    def cancel_request(self, erid: int) -> bool:
        """Deliver a withdrawal to the engine.  Called by the router
        AFTER its step lock is released — for remote engines this is a
        CANCEL frame send, i.e. socket I/O that must never run inside
        the step critical section (dlint DL003's stall class).  Returns
        False only when delivery failed; engines without a ``cancel``
        simply keep decoding into a dropped stream (the request left
        ``inflight`` already, so its tokens go nowhere)."""
        cancel = getattr(self.engine, "cancel", None)
        if cancel is None:
            return True
        try:
            return cancel(erid) is not False
        except Exception as e:
            logger.debug(
                "cancel of engine rid %s on replica %s failed: %s",
                erid, self.name, e)
            return False

    # ------------------------------------------------------- lifecycle
    def mark_up(self, now: float) -> None:
        self.status = ReplicaStatus.UP
        self.last_heartbeat = now

    def begin_drain(self) -> None:
        if self.status == ReplicaStatus.UP:
            self.status = ReplicaStatus.DRAINING

    def fail(self) -> None:
        """Chaos/ops hook: kill this replica (its next pump raises)."""
        self._failed = True

    def take_inflight(self) -> List[ServingRequest]:
        reqs = list(self.inflight.values())
        self.inflight.clear()
        return reqs


def base_replica_name(name: str) -> str:
    """Strip supervisor respawn suffixes (``worker-0#r2`` ->
    ``worker-0``): probation history must follow the flapping POD, not
    reset with every respawn's fresh replica name."""
    return re.sub(r"(#r\d+)+$", "", name)


class ReplicaManager:
    """Membership + health: join/leave/drain, heartbeat reaping, and
    crash-loop probation.

    Probation: a replica that dies within ``probation_lifetime`` of
    joining is a *flap*.  When a same-named successor (respawn suffixes
    stripped) joins, it is admitted but held out of placement for an
    exponentially growing cooldown — a crash-looping pod must stop
    eating placements (each one costs the orphaned requests a failover
    replay) while still getting a probe request once per cooldown to
    prove recovery.  A replica that survives past the flap threshold
    clears its name's history."""

    def __init__(self, heartbeat_timeout: float = 10.0,
                 probation_lifetime: float = 5.0,
                 probation_cooldown: float = 2.0,
                 probation_max: float = 60.0,
                 suspect_hold: float = 1.0):
        self.heartbeat_timeout = float(heartbeat_timeout)
        self.probation_lifetime = float(probation_lifetime)
        self.probation_cooldown = float(probation_cooldown)
        self.probation_max = float(probation_max)
        # gray-zone flap damping: how long a recovering (phi dropped)
        # replica STAYS demoted, doubling per recovery like probation's
        # cooldown — a flapping link must cost one demote/restore
        # cycle, not an invalidation per flap period
        self.suspect_hold = float(suspect_hold)
        self.replicas: Dict[str, ReplicaHandle] = {}
        # handles reaped by reap_dead, awaiting router post-mortem
        # (affinity cleanup + cluster-node retirement); drained by
        # ServingRouter.step each round
        self.dead_handles: List[ReplicaHandle] = []
        # base replica name -> consecutive short-lived deaths
        self._flaps: Dict[str, int] = {}
        # base replica name -> raw suspect->healthy recoveries (the
        # suspicion twin of _flaps, same exponential damping)
        self._suspect_flaps: Dict[str, int] = {}
        self._last_check: Optional[float] = None
        # suspicion lifecycle counters, mirrored into serving_replica_
        # suspect_* metrics by the router's observe sweep
        self.suspect_demotions = 0
        self.suspect_recoveries = 0
        self.suspect_flaps_damped = 0

    # ------------------------------------------------------ membership
    def join(self, handle: ReplicaHandle,
             now: Optional[float] = None) -> ReplicaHandle:
        now = time.monotonic() if now is None else now
        if handle.name in self.replicas:
            raise ValueError(f"replica {handle.name} already joined")
        handle.mark_up(now)
        handle.joined_at = now
        flaps = self._flaps.get(base_replica_name(handle.name), 0)
        if flaps:
            cooldown = min(
                self.probation_max,
                self.probation_cooldown * (2 ** (flaps - 1)),
            )
            handle.probation_until = now + cooldown
            logger.warning(
                "serving replica %s joined on probation for %.1fs "
                "(%d consecutive short-lived predecessors)",
                handle.name, cooldown, flaps)
        self.replicas[handle.name] = handle
        logger.info("serving replica %s joined", handle.name)
        return handle

    def begin_drain(self, name: str) -> Optional[ReplicaHandle]:
        handle = self.replicas.get(name)
        if handle is not None:
            handle.begin_drain()
        return handle

    def remove(self, name: str) -> Optional[ReplicaHandle]:
        handle = self.replicas.pop(name, None)
        if handle is not None:
            handle.status = ReplicaStatus.LEFT
            # a DELIBERATE retirement (drain/scale-down) ends the
            # name's story: stale flap history must not probation an
            # unrelated later join of the same name (and the dict must
            # not grow one entry per retired name forever)
            self._flaps.pop(base_replica_name(name), None)
            self._suspect_flaps.pop(base_replica_name(name), None)
            logger.info("serving replica %s left", name)
        return handle

    # ---------------------------------------------------------- views
    def get(self, name: str) -> Optional[ReplicaHandle]:
        return self.replicas.get(name)

    def schedulable(self, now: Optional[float] = None
                    ) -> List[ReplicaHandle]:
        now = time.monotonic() if now is None else now
        return [
            h for h in self.replicas.values()
            if h.schedulable and h.probation_until <= now
        ]

    def pumpable(self) -> List[ReplicaHandle]:
        return [h for h in self.replicas.values() if h.pumpable]

    def up_count(self) -> int:
        return sum(1 for h in self.replicas.values() if h.schedulable)

    def probation_count(self, now: Optional[float] = None) -> int:
        """Replicas currently held out of placement by probation — the
        ``serving_replica_probation`` gauge.  Defined as the size of the
        capacity-debt feed so the gauge and the autoscaler can never
        disagree about what counts as probationary."""
        return len(self.capacity_debt(now))

    def capacity_debt(self, now: Optional[float] = None) -> List[dict]:
        """Capacity currently lost to crash-loop probation — the feed
        the autoscaler polls to backfill a cooling-down replica with a
        replacement node instead of serving short-handed through the
        cooldown.  One record per probationary replica, keyed on the
        base name (respawn generations share one debt); the record
        disappears when the cooldown elapses or the replica dies, so
        an unreplaced debt retires by itself."""
        now = time.monotonic() if now is None else now
        return [
            {
                "key": f"probation:{base_replica_name(h.name)}",
                "kind": "probation",
                "source": h.name,
                "until": h.probation_until,
            }
            for h in self.replicas.values()
            if h.schedulable and h.probation_until > now
        ]

    # --------------------------------------------------------- health
    def update_suspects(self, now: Optional[float] = None) -> int:
        """One suspicion sweep: poll every pumpable replica's raw phi
        verdict and fold it into the EFFECTIVE ``demoted`` flag the
        scheduler weights on.  Demotion follows suspicion immediately;
        RECOVERY is damped — the demotion holds for ``suspect_hold``
        (doubling per recovery of the same base name, capped at
        ``probation_max``), so a link flapping faster than the hold
        stays continuously demoted: bounded placement churn by
        construction.  Returns the count of currently demoted replicas
        (the ``serving_replica_suspect`` gauge)."""
        now = time.monotonic() if now is None else now
        demoted_count = 0
        for handle in self.replicas.values():
            if not handle.pumpable:
                continue
            raw = handle.suspect(now)
            if raw and not handle.suspected:
                if now >= handle.demoted_until:
                    logger.warning(
                        "serving replica %s suspect (phi=%.1f): "
                        "demoted in placement, in-flight continues",
                        handle.name, handle.phi_value(now))
                else:
                    # re-suspected inside the hold window: the flap the
                    # damping exists to absorb — no new transition
                    self.suspect_flaps_damped += 1
            elif handle.suspected and not raw:
                base = base_replica_name(handle.name)
                n = self._suspect_flaps.get(base, 0) + 1
                self._suspect_flaps[base] = n
                hold = min(self.probation_max,
                           self.suspect_hold * (2 ** (n - 1)))
                handle.demoted_until = max(
                    handle.demoted_until, now + hold)
                self.suspect_recoveries += 1
            handle.suspected = raw
            demoted = raw or now < handle.demoted_until
            if demoted and not handle.demoted:
                self.suspect_demotions += 1
            elif not demoted and handle.demoted:
                logger.info(
                    "serving replica %s recovered: full placement "
                    "weight restored (no failover)", handle.name)
            handle.demoted = demoted
            if demoted:
                demoted_count += 1
        return demoted_count

    def reap_dead(self, now: Optional[float] = None
                  ) -> List[ServingRequest]:
        """Declare failed / heartbeat-stale replicas DEAD and return
        their in-flight requests for requeueing (the failover drain)."""
        now = time.monotonic() if now is None else now
        # staleness is only meaningful while the OBSERVER was watching:
        # if the router itself slept past the timeout (idle lull, no
        # step() calls), every heartbeat looks ancient — amnesty them
        # instead of mass-reaping healthy replicas, and judge from the
        # next real pump cycle
        observer_slept = (
            self._last_check is not None
            and now - self._last_check > self.heartbeat_timeout
        )
        self._last_check = now
        if observer_slept:
            for handle in self.replicas.values():
                if handle.pumpable and not handle._failed:
                    handle.last_heartbeat = now
        orphans: List[ServingRequest] = []
        for name in list(self.replicas):
            handle = self.replicas[name]
            stale = (
                handle.pumpable
                and now - handle.last_heartbeat > self.heartbeat_timeout
            )
            if handle._failed or stale:
                handle.status = ReplicaStatus.DEAD
                taken = handle.take_inflight()
                orphans.extend(taken)
                del self.replicas[name]
                self.dead_handles.append(handle)
                base = base_replica_name(name)
                if now - handle.joined_at < self.probation_lifetime:
                    # died right after joining: one more flap — the
                    # successor's probation cooldown doubles
                    self._flaps[base] = self._flaps.get(base, 0) + 1
                else:
                    # it lived: the crash loop (if any) is over
                    self._flaps.pop(base, None)
                logger.warning(
                    "serving replica %s died (%s); requeueing %d "
                    "in-flight requests", name,
                    "engine failure" if handle._failed
                    else "missed heartbeats", len(taken),
                )
        return orphans
