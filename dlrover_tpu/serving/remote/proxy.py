"""Router-side proxy engine for a remote worker process.

:class:`RemoteReplicaHandle` satisfies the duck-typed engine contract
documented on :class:`~dlrover_tpu.serving.router.replica.ReplicaHandle`
(``add_request`` / ``step`` / ``has_work`` / ``slots_free`` /
``blocks_free`` / ``blocks_needed``) plus the streaming extra
``drain_token_events``, so the router joins it exactly like an
in-process engine — and every elasticity behavior (heartbeat reaping,
drain+requeue failover, graceful leave) applies UNCHANGED:

- a background reader thread consumes TOKEN / DONE / STATS frames;
  STATS double as the liveness signal and capacity refresh;
- a SIGKILLed worker tears the TCP stream; the reader marks the proxy
  dead and the next ``pump`` raises, which is precisely the engine-
  failure path ``ReplicaManager.reap_dead`` already handles;
- a HUNG worker (socket alive, no frames) trips the frame-staleness
  check in :meth:`step`, mapping to the same failover;
- TOKEN frames carry their RECEIVE timestamp into
  ``drain_token_events`` — TTFT is measured from true first-token
  arrival, not from the first post-placement pump.
"""

from __future__ import annotations

import threading
import time
from types import SimpleNamespace
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from dlrover_tpu.common.constants import ServingFabric
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.serving.remote.phi import PhiAccrualDetector
from dlrover_tpu.serving.remote.protocol import (
    FrameConnection,
    FrameKind,
    FrameProtocolError,
    connect,
)

# Exhaustiveness contract (dlint DL004): every FrameKind must be either
# referenced in this module or declared here with its reason.  HEARTBEAT
# is router->worker ping-on-demand; this proxy never pings — the
# worker's own STATS cadence is the liveness signal, and a silent worker
# trips frame_timeout in step() instead.
_UNHANDLED_FRAME_KINDS = (FrameKind.HEARTBEAT,)


class RemoteReplicaHandle:
    """Engine-protocol proxy over one worker's frame connection."""

    def __init__(
        self,
        addr: str,
        name: str = "",
        connect_timeout: float = 5.0,
        submit_timeout: float = 5.0,
        frame_timeout: float = ServingFabric.FRAME_TIMEOUT,
        fault_schedule=None,
        phi_suspect: float = ServingFabric.PHI_SUSPECT,
        phi_dead: float = ServingFabric.PHI_DEAD,
        phi_kill_floor: Optional[float] = None,
        phi_window: int = 128,
        phi_min_samples: int = 8,
    ):
        self.addr = addr
        self.name = name or addr
        self.submit_timeout = float(submit_timeout)
        self.frame_timeout = float(frame_timeout)
        # phi-accrual detection (serving/remote/phi.py): a suspicion
        # GRADIENT over frame interarrivals next to the frame_timeout
        # cliff.  phi >= phi_suspect demotes this replica in placement
        # (suspect property); phi >= phi_dead AND silence past
        # phi_kill_floor fails it over EARLY — with the floor unset
        # (the default) phi never kills, so frame_timeout remains the
        # sole and unchanged death sentence; it stays the hard ceiling
        # either way.
        self.phi_suspect = float(phi_suspect)
        self.phi_dead = float(phi_dead)
        self.phi_kill_floor = (
            None if phi_kill_floor is None else float(phi_kill_floor))
        self._phi = PhiAccrualDetector(
            window=phi_window, min_samples=phi_min_samples)
        if fault_schedule is not None:
            # chaos seam (serving/remote/faults.py): perturb this
            # proxy's router->worker frames (SUBMIT/CANCEL/GOODBYE)
            from dlrover_tpu.serving.remote.faults import maybe_faulty

            self._conn = maybe_faulty(
                connect(addr, connect_timeout), fault_schedule)
        else:
            self._conn = FrameConnection(connect(addr, connect_timeout))
        # RLock: _dispatch(GOODBYE) -> _mark_dead re-enters under the
        # reader's own hold
        self._lock = threading.RLock()
        self._dead: Optional[str] = None
        self._closing = False  # deliberate close() in progress
        self._inflight: Set[int] = set()  # rids placed, not yet DONE
        self._finished: List[SimpleNamespace] = []
        # (rid, tokens, receive-time) — drained by ReplicaHandle.pump
        self._token_events: List[Tuple[int, List[int], float]] = []
        self._submit_replies: Dict[int, dict] = {}
        self._submit_cv = threading.Condition(self._lock)
        self._next_rid = 0
        # CANCEL frames that failed to send (router aggregates these
        # into serving_cancel_send_failures_total); logged once per
        # replica at debug — see cancel()
        self.cancel_send_failures = 0
        self._cancel_fail_logged = False
        # batched-drain introspection: frames per lock crossing — under
        # a token storm batches >> 1, which is the reader-coalescing
        # win (tests assert frames_received / frame_batches grows)
        self.frames_received = 0
        self.frame_batches = 0
        try:
            hello = self._conn.recv(timeout=connect_timeout)
        except Exception:
            # a wedged worker (accepted, never HELLOed) must not leak
            # the socket — the supervisor's respawn retries would pile
            # up one fd per attempt
            self._conn.close()
            raise
        if hello is None or hello.get("kind") != FrameKind.HELLO:
            self._conn.close()
            raise ConnectionError(
                f"worker {addr} did not open with HELLO: {hello!r}")
        self._slots_free = int(hello.get("slots_free", 0))
        self._blocks_free = float(hello.get("blocks_free", 0.0))
        self.block_size = int(hello.get("block_size", 0))
        self.engine_kind = str(hello.get("engine", "?"))
        # STATS staleness watermark: the worker's generated_tokens
        # counter is monotonic within a connection, so a STATS carrying
        # a LOWER value than one already applied arrived out of order
        # (recv-side reorder, a retransmit artifact) — applying it
        # would regress the capacity ledger and over-place
        self._stats_tokens = -1
        self._stats_seq_seen = 0
        # the worker's OWN view of its in-flight count (STATS
        # "inflight"): surfaced when the worker goes silent so the
        # failover log distinguishes "died idle" from "died holding
        # N requests" without trusting this side's ledger, which a
        # lost DONE frame can leave overcounted
        self._worker_inflight = 0
        self.stale_stats_dropped = 0
        self._engine_metrics: Optional[Dict[str, float]] = None
        self._prefix_heads: List[str] = []
        self._profile: Optional[dict] = None
        self._last_frame = time.monotonic()
        self._reader = threading.Thread(
            target=self._read_loop, daemon=True,
            name=f"replica-reader-{self.name}")
        self._reader.start()

    # ----------------------------------------------------- reader side
    def _read_loop(self) -> None:
        while self.dead is None and not self._conn.closed:
            try:
                # batched drain: one select wakeup scoops EVERY frame
                # already buffered behind the first — under a token
                # storm (N slots streaming per engine step) the
                # dispatch below then crosses the proxy lock once per
                # BATCH instead of once per TOKEN frame, which is
                # exactly the contention the router's step lock used
                # to eat (recv_many keeps per-frame fault injection:
                # it reads frames through recv)
                frames = self._conn.recv_many(timeout=0.5)
            except TimeoutError:
                # no frame in 0.5s is NOT death by itself — staleness
                # is judged against frame_timeout in step(); keep going
                continue
            except Exception as e:
                self._mark_dead(f"stream torn: {e}")
                return
            if frames is None:
                self._mark_dead("worker closed the connection")
                return
            try:
                self._dispatch_batch(frames)
            except Exception as e:
                # a malformed frame (missing rid, bad field type) must
                # kill the proxy LOUDLY, not leave a zombie reader that
                # silently drops every subsequent frame
                self._mark_dead(f"malformed frame in batch: {e}")
                return

    def _dispatch(self, frame: dict) -> None:
        """Single-frame dispatch (tests drive this directly; the read
        loop goes through :meth:`_dispatch_batch`)."""
        self._dispatch_batch([frame])

    def _dispatch_batch(self, frames: List[dict]) -> None:
        now = time.monotonic()
        self.frames_received += len(frames)
        self.frame_batches += 1
        with self._lock:
            # feed the phi detector the interarrival gap BEFORE the
            # stamp moves: one gap per batch (frames drained together
            # arrived together — intra-batch gaps are ~0 and carry no
            # timing signal, observe() ignores them anyway)
            self._phi.observe(now - self._last_frame)
            self._last_frame = now
            for frame in frames:
                self._dispatch_locked(frame, now)
                if self._dead is not None:
                    # a GOODBYE mid-batch closed the proxy; anything
                    # behind it on the wire is from a peer that said
                    # farewell first
                    return

    def _dispatch_locked(self, frame: dict, now: float) -> None:
        kind = frame.get("kind")
        if kind == FrameKind.TOKEN:
            rid = int(frame["rid"])
            if rid in self._inflight:
                self._token_events.append(
                    (rid, list(frame["tokens"]), now))
        elif kind == FrameKind.DONE:
            rid = int(frame["rid"])
            if rid in self._inflight:
                self._inflight.discard(rid)
                # span shifting only when the worker actually shipped
                # spans (sampled-in traces): a sampled-out request's
                # DONE pays zero tracing work on this thread
                spans = (self._shift_spans(frame, now)
                         if frame.get("spans") else [])
                self._finished.append(SimpleNamespace(
                    rid=rid, output=list(frame["tokens"]),
                    trace_spans=spans,
                    # hedge attempt id echoed from SUBMIT (None from
                    # unhedged submits and older workers) — lets the
                    # router audit WHICH dispatch attempt won the race
                    attempt=frame.get("attempt")))
        elif kind == FrameKind.STATS:
            seq = frame.get("seq")
            seq = int(seq) if isinstance(seq, (int, float)) else None
            gen = frame.get("generated_tokens")
            gen = int(gen) if isinstance(gen, (int, float)) else None
            if seq is not None:
                # per-send ordinal (current workers): a strict
                # total order, so duplicates AND equal-token
                # reorders (two snapshots with no decode step
                # between them, e.g. around a SUBMIT) are droppable
                stale = seq <= self._stats_seq_seen
            else:
                # token watermark fallback (seq-less sender): a
                # snapshot older than one already applied must not
                # regress the ledger — freed capacity would be
                # forgotten or phantom capacity resurrected; equal
                # still refreshes (cancels free slots without
                # generating)
                stale = gen is not None and gen < self._stats_tokens
            if stale:
                self.stale_stats_dropped += 1
            else:
                if seq is not None:
                    self._stats_seq_seen = seq
                if gen is not None:
                    self._stats_tokens = gen
                self._slots_free = int(frame.get("slots_free", 0))
                self._blocks_free = float(
                    frame.get("blocks_free", 0.0))
                self._worker_inflight = int(
                    frame.get("inflight", 0))
                em = frame.get("engine_metrics")
                if isinstance(em, dict):
                    # raw-speed introspection (spec accept ratio,
                    # int8 KV pool, chunked-prefill seconds) from
                    # engines that report it; absent on FakeEngine
                    # workers and older senders
                    self._engine_metrics = {
                        str(k): float(v) for k, v in em.items()
                        if isinstance(v, (int, float))
                    }
                prof = frame.get("profile")
                if isinstance(prof, dict):
                    # continuous-profiler tables from a --profile
                    # worker: cumulative, so latest-wins replacement
                    # is the whole merge; absent on unprofiled workers
                    self._profile = prof
                heads = frame.get("prefix_heads")
                if isinstance(heads, list):
                    # hottest committed prefix heads (hex digests):
                    # replacement semantics — the latest advertised
                    # set IS the replica's current hot set, so the
                    # router's routing table drops what vanished
                    self._prefix_heads = [
                        str(h) for h in heads if isinstance(h, str)
                    ]
        elif kind in (FrameKind.SUBMITTED, FrameKind.ERROR):
            self._submit_replies[int(frame["rid"])] = frame
            self._submit_cv.notify_all()
        elif kind == FrameKind.GOODBYE:
            self._mark_dead("worker said goodbye", graceful=True)

    @staticmethod
    def _shift_spans(frame: dict, now: float) -> list:
        """Worker-side spans ride the DONE frame in the WORKER's
        monotonic clock, which means nothing in this process.  The
        frame also carries ``sent_at`` (worker clock at send); the
        receive time ``now`` is the same instant in OUR clock, so
        ``now - sent_at`` translates every span (error = one-way
        network latency, microseconds on the links this fabric runs).
        Returns spans ready for ``Tracer.graft``; anything malformed
        degrades to no spans, never to a dead replica."""
        spans = frame.get("spans")
        sent_at = frame.get("sent_at")
        if not spans or not isinstance(sent_at, (int, float)):
            return []
        shift = now - float(sent_at)
        out = []
        for raw in spans:
            try:
                out.append(dict(
                    raw,
                    start=float(raw["start"]) + shift,
                    end=float(raw["end"]) + shift,
                ))
            except (KeyError, TypeError, ValueError):
                continue
        return out

    def _mark_dead(self, reason: str, graceful: bool = False) -> None:
        with self._lock:
            first = self._dead is None
            if first:
                self._dead = reason
            self._submit_cv.notify_all()
        # only the call that actually killed the proxy warns — the
        # reader re-detecting a close()d socket, or the peer's EOF
        # answering OUR deliberate goodbye, is not news
        if not graceful and first and not self._closing:
            logger.warning(
                "remote replica %s dead: %s", self.name, reason)
        self._conn.close()

    # -------------------------------------------------- engine protocol
    def add_request(self, prompt, max_new_tokens: int,
                    trace: Optional[str] = None,
                    attempt: Optional[int] = None) -> int:
        """Synchronous SUBMIT round trip.  An engine-side rejection
        (ERROR frame) raises ``ValueError`` — the router's poison-
        request path; a torn/silent worker raises ``ConnectionError`` —
        the router's failover path.  ``trace`` (a W3C-style traceparent
        from the request's span trace) rides the SUBMIT header so the
        worker's own spans come back on DONE and graft into the
        request's tree.

        Tradeoff, documented: the ack wait runs under the router's step
        lock, so a wedged worker can stall placement for up to
        ``submit_timeout`` (once — the timeout fails the replica over).
        The synchronous ack is what gives remote engines rejection
        parity with local ones (ValueError at submit time); an async
        submit pipeline is a future rung if placement RTTs ever show up
        in the step budget (localhost RTT is ~µs today)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        total = int(prompt.size) + int(max_new_tokens)
        with self._lock:
            if self._dead is not None:
                raise ConnectionError(self._dead)
            rid = self._next_rid
            self._next_rid += 1
            # register BEFORE sending: a fast worker's first TOKEN (or
            # even DONE) frame can beat this thread back to the lock
            # after the SUBMITTED ack — an unregistered rid would drop
            # those frames and strand the request in-flight forever
            self._inflight.add(rid)
        try:
            try:
                extra = {} if trace is None else {"trace": trace}
                if attempt is not None:
                    # hedge attempt ordinal (0 = primary dispatch,
                    # 1+ = hedges); the worker echoes it on DONE so
                    # the winner of a hedge race is auditable
                    extra["attempt"] = int(attempt)
                self._conn.send(
                    FrameKind.SUBMIT, rid=rid,
                    prompt=prompt.tolist(),
                    max_new_tokens=int(max_new_tokens),
                    **extra,
                )
            except FrameProtocolError as e:
                # a request too large to FRAME is the request's defect,
                # not the replica's: surface it on the rejection path
                # (ValueError -> router REJECTED) or a healthy replica
                # would be failed over for every oversized submit
                raise ValueError(f"request unframeable: {e}") from e
            deadline = time.monotonic() + self.submit_timeout
            with self._lock:
                while rid not in self._submit_replies:
                    if self._dead is not None:
                        raise ConnectionError(self._dead)
                    remaining = deadline - time.monotonic()
                    timed_out = remaining <= 0 or \
                        not self._submit_cv.wait(remaining)
                    # re-check before raising: the ack can land exactly
                    # on the timeout boundary (wait returns False AFTER
                    # the reader stored the reply), and a spurious raise
                    # here would fail over a healthy replica
                    if timed_out and rid not in self._submit_replies:
                        raise ConnectionError(
                            f"worker {self.name}: no SUBMIT ack in "
                            f"{self.submit_timeout}s")
                reply = self._submit_replies.pop(rid)
                if reply["kind"] == FrameKind.ERROR:
                    raise ValueError(str(reply.get("error", "rejected")))
                # optimistic ledger: the next STATS frame overwrites
                self._slots_free = max(0, self._slots_free - 1)
                if self.block_size:
                    self._blocks_free -= -(-total // self.block_size)
        except Exception:
            with self._lock:
                self._inflight.discard(rid)
            raise
        return rid

    def step(self) -> List[SimpleNamespace]:
        """Return requests finished since the last pump.  Raises when
        the worker is dead OR silent past ``frame_timeout`` — a
        successful return is a genuine liveness proof, which is what
        makes ``ReplicaHandle.pump``'s heartbeat semantics hold for a
        process the router cannot observe directly."""
        now = time.monotonic()
        with self._lock:
            if self._dead is not None:
                raise ConnectionError(self._dead)
            silence = now - self._last_frame
            if silence > self.frame_timeout:
                raise ConnectionError(
                    f"worker {self.name} silent for "
                    f"{silence:.1f}s (> frame_timeout "
                    f"{self.frame_timeout}s); last STATS reported "
                    f"{self._worker_inflight} inflight")
            if (self.phi_kill_floor is not None
                    and silence >= self.phi_kill_floor):
                phi = self._phi.phi(silence)
                if phi >= self.phi_dead:
                    raise ConnectionError(
                        f"worker {self.name} phi={phi:.1f} (>= "
                        f"phi_dead {self.phi_dead}) after "
                        f"{silence:.2f}s silence; last STATS reported "
                        f"{self._worker_inflight} inflight")
            finished, self._finished = self._finished, []
            return finished

    @property
    def has_work(self) -> bool:
        # a dead/stale proxy must claim work so ReplicaHandle.pump
        # actually calls step() and hits the failover path — an idle
        # corpse would otherwise keep "heartbeating" forever
        with self._lock:
            if self._dead is not None or self._finished:
                return True
            silence = time.monotonic() - self._last_frame
            if silence > self.frame_timeout:
                return True
            if (self.phi_kill_floor is not None
                    and silence >= self.phi_kill_floor
                    and self._phi.phi(silence) >= self.phi_dead):
                return True
            return bool(self._inflight)

    # --------------------------------------------- suspicion gradient
    def phi_value(self, now: Optional[float] = None) -> float:
        """Current phi-accrual suspicion for this replica (0.0 until
        the detector has its minimum interarrival history)."""
        now = time.monotonic() if now is None else now
        with self._lock:
            if self._dead is not None:
                # already past suspicion: the failover path owns a dead
                # proxy, and the phi gauges must stay finite
                return 0.0
            return self._phi.phi(now - self._last_frame)

    def suspect(self, now: Optional[float] = None) -> bool:
        """True when suspicion crosses ``phi_suspect`` but the replica
        is not (yet) dead — the gray zone: demote in placement, keep
        serving in-flight work, no failover."""
        now = time.monotonic() if now is None else now
        with self._lock:
            if self._dead is not None:
                return False
            return self._phi.phi(now - self._last_frame) \
                >= self.phi_suspect

    def slots_free(self) -> int:
        with self._lock:
            return 0 if self._dead is not None else self._slots_free

    def blocks_free(self) -> float:
        with self._lock:
            return 0.0 if self._dead is not None else self._blocks_free

    def engine_metrics(self) -> Optional[Dict[str, float]]:
        """Latest engine introspection dict from STATS, or None when
        the worker's engine doesn't report one (FakeEngine).  A dead
        replica reports None like slots_free/blocks_free report zero:
        the fleet gauges must not keep aggregating a corpse's cached
        numbers while its handle awaits the reap."""
        with self._lock:
            if self._dead is not None:
                return None
            em = self._engine_metrics
            return dict(em) if em else None

    def prefix_heads(self) -> List[str]:
        """Latest advertised hot prefix heads from STATS ([] while
        none arrived, or once the replica is dead — a corpse must not
        keep feeding the routing table)."""
        with self._lock:
            if self._dead is not None:
                return []
            return list(self._prefix_heads)

    def profile_snapshot(self) -> Optional[dict]:
        """Latest continuous-profiler snapshot the worker shipped over
        STATS (None while none arrived — unprofiled worker — or once
        the replica is dead: a corpse's flame must not keep merging
        into the fleet view as if it were live)."""
        with self._lock:
            if self._dead is not None:
                return None
            return self._profile

    def blocks_needed(self, prompt_len: int,
                      max_new_tokens: int) -> Optional[float]:
        if not self.block_size:
            return None  # scheduler falls back to its own default
        return float(
            -(-(int(prompt_len) + int(max_new_tokens)) // self.block_size))

    # ------------------------------------------------- streaming extras
    def drain_token_events(
        self, now: Optional[float] = None
    ) -> List[Tuple[int, List[int], float]]:
        """TOKEN frames received since the last drain, each stamped with
        its true arrival time (``now`` is ignored: receipt already
        happened — this is the TTFT-semantics change)."""
        with self._lock:
            events, self._token_events = self._token_events, []
            return events

    def cancel(self, rid: int) -> bool:
        """Withdraw a placed request: drop its frames from here on and
        send CANCEL so the worker frees the slot + KV blocks.  Returns
        False when the frame could not be delivered — a dead worker
        cancelled everything anyway, but the caller counts it into
        ``serving_cancel_send_failures_total`` because a LIVE worker
        that missed a cancel keeps decoding a dropped request."""
        with self._lock:
            self._inflight.discard(rid)
        try:
            self._conn.send(FrameKind.CANCEL, rid=rid)
        except (ConnectionError, OSError, TimeoutError) as e:
            self.cancel_send_failures += 1
            if not self._cancel_fail_logged:
                # once per replica: every queued cancel fails the same
                # way once the connection is gone — one line carries
                # the signal, a line per request is log spam mid-death
                self._cancel_fail_logged = True
                logger.debug(
                    "CANCEL send to replica %s failed "
                    "(counted, logged once): %s", self.name, e)
            return False
        return True

    # -------------------------------------------------------- lifecycle
    @property
    def dead(self) -> Optional[str]:
        # locked so the None -> reason transition in _mark_dead is
        # never half-observed next to the state it guards (_inflight,
        # _submit_replies are only consistent with _dead under _lock)
        with self._lock:
            return self._dead

    def close(self, goodbye: bool = True) -> None:
        self._closing = True
        if goodbye and self.dead is None:
            try:
                self._conn.send(FrameKind.GOODBYE)
                # half-close and let the reader drain to EOF: a full
                # close with unread STATS in our buffer would RST the
                # stream and can destroy the in-flight GOODBYE — the
                # worker would never learn it should exit
                self._conn.half_close()
                self._reader.join(timeout=2.0)
            except (ConnectionError, OSError, TimeoutError):
                pass
        self._mark_dead("closed by router", graceful=True)
        self._reader.join(timeout=2.0)
