"""Serving worker process: ``python -m dlrover_tpu.serving.remote.worker``.

One worker = one replica process.  It binds port 0 ITSELF (the listener
reports the kernel-assigned port through the stdout announce line and
the HELLO frame — a pre-picked ``find_free_port`` would race another
process between bind-and-close and re-bind), hosts an engine speaking
the router's duck-typed engine protocol, and pushes TOKEN frames the
moment tokens exist instead of waiting for request completion.  The
engine is either the in-repo test :class:`FakeEngine` (deterministic,
numpy-only — what chaos tests SIGKILL) or a real
:class:`~dlrover_tpu.serving.engine.InferenceEngine` behind
:class:`~dlrover_tpu.serving.router.replica.InferenceEngineAdapter`
(imported lazily: the fake path must work on a jax-less image).

Startup contract (read by ``supervisor.py`` and the k8s/ray stubs):
the first matching stdout line is ``DLROVER_WORKER_ADDR=<host>:<port>``.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import signal
import socket
import sys
import threading
import time
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

from dlrover_tpu.common.constants import ServingFabric
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.serving.prefixcache import head_key
from dlrover_tpu.serving.remote.protocol import FrameConnection, FrameKind
from dlrover_tpu.utils.tracing import parse_traceparent, trace_sampled


class FakeEngine:  # dlint: disable=DL011 stands in for the remote worker PROCESS: driven only by that process's single-threaded frame loop, router-side chains reach it through duck fan-out, never at runtime
    """Deterministic engine for fabric tests and jax-less images: each
    ``step()`` appends ``tokens_per_step`` tokens (value = rid % 997) to
    every active request.  Speaks the full router engine protocol plus
    the streaming extras (``inflight_outputs``, ``cancel``)."""

    def __init__(self, slots: int = 4, blocks: int = 10_000,
                 block_size: int = 4, tokens_per_step: int = 4,
                 max_len: int = 4096, step_delay: float = 0.0,
                 content_tokens: bool = False):
        self.max_slots = int(slots)
        self.block_size = int(block_size)
        self.total_blocks = int(blocks)
        self.used_blocks = 0
        self.tokens_per_step = int(tokens_per_step)
        self.max_len = int(max_len)
        # per-step sleep: lets chaos tests catch a worker MID-stream
        self.step_delay = float(step_delay)
        # content-derived tokens: token_i = (prompt hash + i) % 997
        # instead of rid % 997.  rid-keyed tokens differ across
        # replicas (each proxy numbers its own submits), so hedging's
        # byte-identical-stream gate needs tokens that are a function
        # of the REQUEST, like a greedy LLM's — opt-in so every
        # existing rid-based assertion stays untouched
        self.content_tokens = bool(content_tokens)
        self._next = 0
        self.active: Dict[int, dict] = {}
        self.generated_tokens = 0
        # prompt-head hit counts: the fake's stand-in for the real
        # engine's committed-prefix hot-head ranking, so fabric/router
        # tests exercise prefix-routing advertisements without jax
        self._head_hits: Dict[str, int] = {}

    def add_request(self, prompt, max_new_tokens: int) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        total = prompt.size + int(max_new_tokens)
        if total > self.max_len:
            raise ValueError(
                f"prompt {prompt.size} + max_new {max_new_tokens} "
                f"exceeds engine max_len {self.max_len}")
        head = head_key(prompt, self.block_size)
        if head is not None:
            self._head_hits[head] = self._head_hits.get(head, 0) + 1
        rid = self._next
        self._next += 1
        need = -(-total // self.block_size)
        self.used_blocks += need
        base = rid
        if self.content_tokens:
            base = (int(prompt.astype(np.int64).sum()) * 31
                    + int(prompt.size))
        self.active[rid] = {
            "remaining": int(max_new_tokens), "output": [],
            "blocks": need, "base": base,
        }
        return rid

    def step(self) -> List:
        if self.step_delay:
            time.sleep(self.step_delay)
        finished = []
        for rid in list(self.active):
            st = self.active[rid]
            take = min(self.tokens_per_step, st["remaining"])
            if self.content_tokens:
                pos = len(st["output"])
                st["output"].extend(
                    (st["base"] + pos + i) % 997 for i in range(take))
            else:
                st["output"].extend([st["base"] % 997] * take)
            st["remaining"] -= take
            self.generated_tokens += take
            if st["remaining"] <= 0:
                self.used_blocks -= st["blocks"]
                finished.append(
                    SimpleNamespace(rid=rid, output=st["output"]))
                del self.active[rid]
        return finished

    @property
    def has_work(self) -> bool:
        return bool(self.active)

    def slots_free(self) -> int:
        return max(0, self.max_slots - len(self.active))

    def blocks_free(self) -> float:
        return float(self.total_blocks - self.used_blocks)

    def blocks_needed(self, prompt_len: int, max_new_tokens: int) -> float:
        return float(-(-(prompt_len + max_new_tokens) // self.block_size))

    def prefix_heads(self, n: int = 8) -> List[str]:
        """Hottest prompt-head digests seen by this fake (hex) — the
        advertisement the router's prefix-routing table is fed from,
        same surface as the real engine's committed-prefix ranking."""
        live = sorted(((hits, hx) for hx, hits in
                       self._head_hits.items()), reverse=True)
        return [hx for _, hx in live[:n]]

    # streaming extras -------------------------------------------------
    def inflight_outputs(self) -> Dict[int, List[int]]:
        """Live output snapshot per running request — the worker diffs
        these against what it already streamed as TOKEN frames."""
        return {rid: st["output"] for rid, st in self.active.items()}

    def cancel(self, rid: int) -> bool:
        """Free the request's slot + blocks.  Always True: local
        delivery cannot fail, and an already-finished rid is a
        successfully-delivered no-op (the router-side contract on
        ``ReplicaHandle`` — False would be miscounted as a CANCEL
        send failure when a cancel races completion)."""
        st = self.active.pop(rid, None)
        if st is not None:
            self.used_blocks -= st["blocks"]
        return True


class WorkerServer:
    """Frame server around one engine.  Accepts one router connection
    at a time (the router owns its replicas 1:1) and re-listens after a
    disconnect so a restarted router can re-adopt a warm worker."""

    def __init__(self, engine, host: str = "127.0.0.1", port: int = 0,
                 stats_interval: float = ServingFabric.STATS_INTERVAL,
                 engine_kind: str = "fake", fault_schedule=None,
                 trace_sample_rate: float = 1.0, profiler=None,
                 profile_ship_interval: float = 2.0):
        self.engine = engine
        # contprof.ContinuousProfiler (role "worker"): its folded-stack
        # table rides STATS as an additive "profile" key, throttled to
        # profile_ship_interval so liveness-cadence STATS stay small
        self.profiler = profiler
        self.profile_ship_interval = float(profile_ship_interval)
        self._last_profile_ship = 0.0
        self.stats_interval = float(stats_interval)
        self.engine_kind = engine_kind
        # head-sampling agreement with the router: a received context
        # that asserts the sampled flag IS the router's keep verdict
        # (it omits the traceparent for sampled-out requests and keeps
        # propagating for incidents) and is always honored; this rate
        # only gates contexts that DON'T assert sampling, via the same
        # deterministic trace_sampled() predicate the router uses, so
        # both sides agree with no coordination frame
        self.trace_sample_rate = float(trace_sample_rate)
        # chaos seam (serving/remote/faults.py): a FaultSchedule here
        # perturbs every outgoing frame — torn streams, stalled STATS,
        # duplicated TOKENs — so degradation paths are TESTED, not
        # hoped for.  None (the default) costs nothing.
        self.fault_schedule = fault_schedule
        # bind-port-0-yourself: the ONLY race-free way to pick a port
        self._listener = socket.create_server(
            (host, int(port)), reuse_port=False)
        self._listener.settimeout(0.2)
        self.host = host
        self.port = self._listener.getsockname()[1]
        self.addr = f"{host}:{self.port}"
        self.stop_event = threading.Event()
        self._conn: Optional[FrameConnection] = None
        # SUBMIT rid (router-side) <-> engine rid maps
        self._erid_by_rid: Dict[int, int] = {}
        self._rid_by_erid: Dict[int, int] = {}
        self._streamed: Dict[int, int] = {}  # erid -> tokens streamed
        # erid -> hedge attempt ordinal from SUBMIT (absent for
        # unhedged submits): echoed on DONE so the router can audit
        # which dispatch attempt won a hedge race
        self._attempt_by_erid: Dict[int, int] = {}
        # erid -> trace bookkeeping for SUBMITs that carried a
        # traceparent header: worker-side spans (request lifetime,
        # decode steps, engine time) go back on the DONE frame in THIS
        # process's monotonic clock plus a sent_at anchor the proxy
        # uses to translate them into router time
        self._trace_by_erid: Dict[int, dict] = {}
        # last consistent STATS numbers; the heartbeat thread falls
        # back to these when a live read races an engine mutation.
        # Shared by the heartbeat thread and the serve loop: outside
        # __init__ it is ONLY read or swapped under _stats_seq_lock
        self._last_stats_payload: Dict[str, object] = dict(
            slots_free=0, blocks_free=0.0, inflight=0,
            generated_tokens=0,
        )
        # per-send STATS ordinal: generated_tokens alone cannot order
        # two snapshots taken without a decode step between them (e.g.
        # before/after a SUBMIT), so a recv-side reorder could resurrect
        # a consumed slot.  The lock pins seq order to WIRE order —
        # an atomic draw alone would let the heartbeat thread and the
        # serve loop interleave draw and send, handing the higher seq
        # to the older snapshot
        self._stats_seq = itertools.count(1)
        self._stats_seq_lock = threading.Lock()

    # ------------------------------------------------------- lifecycle
    def announce(self, stream=None) -> None:
        stream = stream or sys.stdout
        print(f"{ServingFabric.WORKER_ANNOUNCE_PREFIX}{self.addr}",
              file=stream, flush=True)

    def crash(self) -> None:
        """Test hook: die abruptly mid-stream (socket torn, no GOODBYE) —
        the in-process stand-in for SIGKILL."""
        self.stop_event.set()
        conn = self._conn
        if conn is not None:
            conn.close()
        self._listener.close()

    def serve_forever(self) -> None:
        try:
            while not self.stop_event.is_set():
                try:
                    sock, _ = self._listener.accept()
                except socket.timeout:
                    continue
                except OSError:
                    break
                sock.setsockopt(
                    socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                from dlrover_tpu.serving.remote.faults import maybe_faulty

                self._conn = maybe_faulty(sock, self.fault_schedule)
                try:
                    self._serve_connection(self._conn)
                except (ConnectionError, TimeoutError, OSError) as e:
                    logger.warning("router connection dropped: %s", e)
                finally:
                    self._conn.close()
                    self._conn = None
        finally:
            self._listener.close()

    # ------------------------------------------------------ connection
    def _serve_connection(self, conn: FrameConnection) -> None:
        eng = self.engine
        self._erid_by_rid.clear()
        self._rid_by_erid.clear()
        self._streamed.clear()
        self._trace_by_erid.clear()
        self._attempt_by_erid.clear()
        conn.send(
            FrameKind.HELLO,
            addr=self.addr,
            slots_free=eng.slots_free(),
            blocks_free=self._finite_blocks(),
            block_size=getattr(eng, "block_size", 0),
            engine=self.engine_kind,
        )
        # liveness off-thread: a long engine.step() (first-call jit
        # compile on a real engine runs tens of seconds) must not
        # starve STATS, or the proxy's frame_timeout would read a
        # healthy-but-compiling worker as dead and fail it over —
        # FrameConnection sends are lock-serialized, so this is safe
        # alongside the pump's TOKEN/DONE sends
        hb_stop = threading.Event()

        def _heartbeat():
            while not hb_stop.wait(self.stats_interval):
                try:
                    self._send_stats(conn)
                except (ConnectionError, OSError):
                    return
                except Exception:
                    # capacity accessors race the serve thread's engine
                    # mutations (e.g. a deque mutating mid-iteration in
                    # blocks_free) — a torn READ must not kill the
                    # liveness beat; resend the last consistent numbers
                    try:
                        self._send_stats(conn, cached=True)
                    except (ConnectionError, OSError):
                        return

        hb = threading.Thread(target=_heartbeat, daemon=True,
                              name="worker-heartbeat")
        hb.start()
        try:
            while not self.stop_event.is_set():
                # drain every pending control frame before pumping:
                # SUBMIT latency must not queue behind a decode step
                busy = eng.has_work
                frame = self._recv_one(conn, 0.0 if busy else 0.02)
                while frame is not None:
                    if not self._handle(conn, frame):
                        return
                    frame = self._recv_one(conn, 0.0)
                if eng.has_work:
                    self._pump(conn)
        finally:
            hb_stop.set()
            hb.join(timeout=1.0)

    def _recv_one(self, conn: FrameConnection,
                  timeout: float) -> Optional[dict]:
        try:
            frame = conn.recv(timeout=timeout)
        except TimeoutError:
            return None
        if frame is None:
            raise ConnectionError("router closed the connection")
        return frame

    def _handle(self, conn: FrameConnection, frame: dict) -> bool:
        kind = frame.get("kind")
        if kind == FrameKind.SUBMIT:
            rid = int(frame["rid"])
            try:
                erid = self.engine.add_request(
                    np.asarray(frame["prompt"], np.int32),
                    int(frame["max_new_tokens"]),
                )
            except ValueError as e:
                # an impossible request is the ENGINE's verdict, not a
                # worker failure: report it, stay alive
                conn.send(FrameKind.ERROR, rid=rid, error=str(e))
                return True
            self._erid_by_rid[rid] = erid
            self._rid_by_erid[erid] = rid
            attempt = frame.get("attempt")
            if isinstance(attempt, int):
                self._attempt_by_erid[erid] = attempt
            tp = frame.get("trace")
            if isinstance(tp, str) and tp \
                    and self._trace_wanted(tp):
                self._trace_by_erid[erid] = {
                    "trace": tp, "t0": time.monotonic(),
                    "t_first": None, "steps": 0, "engine_s": 0.0,
                    # the header every TOKEN/DONE frame for this
                    # request echoes — built ONCE here: the verdict
                    # and the parse are per-request, so the per-frame
                    # hot path below pays a dict lookup, not a parse
                    # or a fresh dict per frame
                    "hdr": {"trace": tp},
                }
            conn.send(FrameKind.SUBMITTED, rid=rid)
        elif kind == FrameKind.CANCEL:
            rid = int(frame["rid"])
            erid = self._erid_by_rid.pop(rid, None)
            if erid is not None:
                self._rid_by_erid.pop(erid, None)
                self._streamed.pop(erid, None)
                self._trace_by_erid.pop(erid, None)
                self._attempt_by_erid.pop(erid, None)
                cancel = getattr(self.engine, "cancel", None)
                if cancel is not None:
                    cancel(erid)
                # freed capacity must be visible to the router's
                # placement ledger NOW, not a stats-interval later —
                # a cancel exists to reclaim the slot for live traffic
                self._send_stats(conn)
        elif kind == FrameKind.HEARTBEAT:
            self._send_stats(conn)
        elif kind == FrameKind.GOODBYE:
            logger.info("router said goodbye; worker %s exits", self.addr)
            self.stop_event.set()
            return False
        return True

    # ------------------------------------------------------------ pump
    def _pump(self, conn: FrameConnection) -> None:
        from dlrover_tpu.serving.router.replica import stream_deltas

        t0 = time.monotonic()
        finished = self.engine.step()
        step_s = time.monotonic() - t0
        # attribute the step to every traced request that was aboard
        # (whole-batch attribution: a batched decode step serves all of
        # them at once — per-request engine_seconds overlap by design)
        for erid, rec in self._trace_by_erid.items():
            if erid in self._rid_by_erid:
                rec["steps"] += 1
                rec["engine_s"] += step_s
                if rec["t_first"] is None:
                    rec["t_first"] = time.monotonic()
        # stream the deltas FIRST — TTFT is measured at the receiver.
        # prune=False: _streamed keeps the positions of just-finished
        # requests so the DONE path below flushes only their SUFFIX
        outputs = getattr(self.engine, "inflight_outputs", None)
        if outputs is not None:
            for erid, toks in stream_deltas(
                    outputs(), self._streamed, prune=False):
                rid = self._rid_by_erid.get(erid)
                if rid is not None:
                    conn.send(FrameKind.TOKEN, rid=rid,
                              tokens=[int(t) for t in toks],
                              **self._trace_header(erid))
        for ereq in finished:
            rid = self._rid_by_erid.pop(ereq.rid, None)
            sent = self._streamed.pop(ereq.rid, 0)
            trace_kw = self._trace_header(ereq.rid)
            rec = self._trace_by_erid.pop(ereq.rid, None)
            attempt = self._attempt_by_erid.pop(ereq.rid, None)
            if rid is None:
                continue  # cancelled while decoding
            self._erid_by_rid.pop(rid, None)
            out = [int(t) for t in ereq.output]
            if len(out) > sent:
                conn.send(FrameKind.TOKEN, rid=rid, tokens=out[sent:],
                          **trace_kw)
            # DONE carries the full output: authoritative completion —
            # plus this worker's spans and a sent_at clock anchor so
            # the router can graft them into the request's trace (and
            # the SUBMIT's hedge attempt ordinal echoed back, when one
            # rode in)
            attempt_kw = {} if attempt is None else {"attempt": attempt}
            conn.send(FrameKind.DONE, rid=rid, tokens=out, **trace_kw,
                      **self._trace_spans(rec), **attempt_kw)
        if finished:
            self._send_stats(conn)

    def _trace_wanted(self, traceparent: str) -> bool:
        """Worker-side verdict for a SUBMIT's trace context.  A context
        asserting the sampled flag (``…-01``) carries the ROUTER's keep
        decision — it only propagates traces it retains, and the
        incident override (a failover retry's worker spans must come
        back even at 1% sampling) rides that decision, so it is honored
        as-is, never re-derived and vetoed here.  Undecided contexts
        (flags ``00``, e.g. a foreign sender delegating the decision)
        fall back to the same deterministic predicate the router uses,
        keyed on the trace_id, so both sides agree without
        coordination.  Unparseable context samples in (degrade toward
        keeping data)."""
        if self.trace_sample_rate >= 1.0:
            return True
        parsed = parse_traceparent(traceparent)
        if parsed is None:
            return True
        if traceparent.rsplit("-", 1)[-1] == "01":
            return True
        return trace_sampled(parsed[0], self.trace_sample_rate)

    _NO_TRACE_HEADER: dict = {}

    def _trace_header(self, erid: int) -> dict:
        """Per-frame trace echo, cached per request at SUBMIT time —
        a sampled-out request (no record) pays one dict miss per
        frame and ships zero trace bytes; a traced one reuses the
        SAME header dict for its whole lifetime (callers ``**`` it
        into the frame payload, never mutate it)."""
        rec = self._trace_by_erid.get(erid)
        return self._NO_TRACE_HEADER if rec is None else rec["hdr"]

    def _trace_spans(self, rec: Optional[dict]) -> dict:
        if rec is None:
            return {}
        now = time.monotonic()
        return {
            "sent_at": now,
            "spans": [
                {"name": "worker.request", "start": rec["t0"],
                 "end": now, "attrs": {"engine": self.engine_kind}},
                {"name": "worker.decode", "parent": "worker.request",
                 "start": rec["t_first"] or rec["t0"], "end": now,
                 "attrs": {"steps": rec["steps"],
                           "engine_seconds": round(rec["engine_s"], 6)}},
            ],
        }

    def _finite_blocks(self) -> float:
        free = self.engine.blocks_free()
        # msgpack floats carry inf fine, but cap it so downstream
        # arithmetic (ledger subtraction) stays well-behaved
        return min(float(free), 1e18)

    def _send_stats(self, conn: FrameConnection,
                    cached: bool = False) -> None:
        payload = None
        if not cached:
            eng = self.engine
            # built into a LOCAL first: the heartbeat thread and the
            # serve loop both run this, and the shared cached copy is
            # only ever touched under _stats_seq_lock below
            payload = dict(
                slots_free=eng.slots_free(),
                blocks_free=self._finite_blocks(),
                inflight=len(self._rid_by_erid),
                generated_tokens=int(
                    getattr(eng, "generated_tokens", 0)),
            )
            # raw-speed engine introspection (spec accept ratio, int8
            # KV pool size, chunked-prefill seconds) rides STATS so the
            # router renders remote fleets like local ones; receivers
            # ignore unknown keys, so old proxies stay compatible
            em = getattr(eng, "engine_metrics", None)
            if em is not None:
                payload["engine_metrics"] = {
                    k: float(v) for k, v in em().items()
                }
            # hottest committed prefix heads (hex digests) ride STATS
            # as their own additive key — they are identities, not
            # numbers, so they cannot live in engine_metrics' float
            # namespace; receivers ignore unknown keys (DL004 holds)
            heads = getattr(eng, "prefix_heads", None)
            if heads is not None:
                payload["prefix_heads"] = [
                    str(h) for h in heads()
                ]
            # continuous-profiler tables ride STATS as their own
            # additive key, throttled well below the liveness cadence
            # (tables are cumulative, so a skipped ship loses nothing);
            # the trimmed top-N snapshot keeps the frame small.  The
            # throttle check is benign under the heartbeat/serve-loop
            # race: the worst interleaving ships one extra snapshot
            prof = self.profiler
            if prof is not None:
                now = time.monotonic()
                if now - self._last_profile_ship >= \
                        self.profile_ship_interval:
                    self._last_profile_ship = now
                    payload["profile"] = prof.snapshot(top=32)
        # seq is assigned at SEND time (never stored in the cached
        # payload): a cached liveness resend carries stale numbers
        # under a fresh ordinal, same last-send-wins semantics as
        # before, but now reorderable by the receiver.  Draw, payload
        # swap and send share the lock so seq order == wire order ==
        # snapshot order (the send itself is bounded by the
        # connection's send_timeout); before the swap moved in here, a
        # heartbeat and the serve loop could interleave draw and send
        # and hand the higher seq to the OLDER snapshot
        with self._stats_seq_lock:
            if payload is not None:
                self._last_stats_payload = payload
            # dlint: disable=DL007 serializing the send IS this lock's contract — seq order must equal wire order, and the send is bounded by the connection's send_timeout
            conn.send(FrameKind.STATS, seq=next(self._stats_seq),
                      **self._last_stats_payload)


def _build_llama_engine(args) -> object:
    """Real-engine path (lazy imports: jax must not gate ``--engine
    fake``).  Weights are randomly initialized — the checkpoint-loading
    rung is recorded in ROADMAP, not faked here.

    The engine compiles what it will serve (``InferenceEngine.warmup``)
    BEFORE this returns, so the worker announces its address only once
    no request can wait on a compile: the fabric's liveness windows are
    sized for steady-state steps, and a full-width program compiles for
    longer than any of them.  ``--report-file`` receives what the build
    saw (device, depth, pool size, the attention pick and its evidence,
    kernel-vs-gather parity on this backend)."""
    t0 = time.time()
    from dlrover_tpu.utils.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.ops.pallas import interpret_off_chip
    from dlrover_tpu.ops.pallas.paged_attention import kernel_parity
    from dlrover_tpu.serving.engine import InferenceEngine
    from dlrover_tpu.serving.router.replica import InferenceEngineAdapter

    # one dtype for compute AND parameters: initializing in float32 and
    # re-laying to bf16 would double the footprint during the build
    dtype = jnp.dtype(args.dtype)
    cfg = LlamaConfig.from_preset(
        args.model, args.layers, max_seq_len=args.max_len,
        dtype=dtype, param_dtype=dtype)
    model = LlamaModel(cfg)
    variables = jax.jit(model.init)(
        jax.random.PRNGKey(args.seed), jnp.zeros((1, 8), jnp.int32))
    engine = InferenceEngine(
        cfg, variables, max_slots=args.slots, chunk=4, paged=True,
        block_size=args.block_size, seed=args.seed,
        cache_blocks=args.blocks,
        kv_dtype=args.kv_dtype if args.kv_dtype != "bf16" else None,
        prefill_chunk=args.prefill_chunk,
        speculative_k=args.speculative_k,
        attention_impl=args.attention_impl,
    )
    del variables
    t_built = time.time()
    programs = engine.warmup()
    if args.report_file:
        dev = jax.devices()[0]
        report = {
            "platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": jax.device_count(),
            "jax": jax.__version__, "compile_cache_dir": cache_dir,
            "model": args.model, "layers": cfg.num_layers,
            "dtype": args.dtype, "params": cfg.num_params,
            "slots": args.slots, "max_len": args.max_len,
            "block_size": args.block_size,
            "kv_dtype": args.kv_dtype,
            "kv_blocks": engine._blockmgr.num_blocks,
            "attention_impl_requested": args.attention_impl,
            "attention_impl": engine.attention_impl,
            "attention_impl_why": engine.attention_impl_why,
            "attention_impl_us": engine.attention_impl_us,
            "warmup_programs": programs,
            "build_seconds": t_built - t0,
            "warmup_seconds": time.time() - t_built,
            "kernel_parity": kernel_parity(
                slots=engine.max_slots, max_blocks=engine._max_blocks,
                block_size=engine.block_size, num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim_,
                dtype=cfg.dtype, kv_dtype=engine.kv_dtype,
                interpret=interpret_off_chip(), seed=args.seed),
        }
        report["peak_bytes_in_use"] = (dev.memory_stats() or {}).get(
            "peak_bytes_in_use")
        with open(args.report_file, "w") as f:
            json.dump(report, f)
    return InferenceEngineAdapter(engine)


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="dlrover_tpu.serving.remote.worker",
        description="One serving replica process (frame protocol).",
    )
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0,
                   help="0 (default): bind a kernel-assigned port and "
                        "announce it — never pre-pick a port")
    p.add_argument("--engine", choices=("fake", "llama"), default="fake")
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--tokens-per-step", type=int, default=4)
    p.add_argument("--block-size", type=int, default=4)
    p.add_argument("--blocks", type=int, default=None,
                   help="KV pool size in native-dtype blocks (fake "
                        "engine: 10000 when unset; llama engine: "
                        "every slot at full length when unset)")
    p.add_argument("--model", default="tiny",
                   help="llama engine: LlamaConfig preset "
                        "(models.llama.PRESETS)")
    p.add_argument("--layers", type=int, default=0,
                   help="llama engine: cut the preset's depth to what "
                        "the device holds beside its KV pool (0 = the "
                        "preset's own); widths are never cut")
    p.add_argument("--dtype", choices=("float32", "bfloat16"),
                   default="float32",
                   help="llama engine: compute and parameter dtype")
    p.add_argument("--report-file", default="",
                   help="llama engine: write what the build saw "
                        "(device, depth, pool, attention pick, kernel "
                        "parity) to this JSON file before announcing")
    p.add_argument("--max-len", type=int, default=4096)
    p.add_argument("--kv-dtype", choices=("bf16", "int8"),
                   default="bf16",
                   help="llama engine KV pool storage: int8 = "
                        "per-(token, head)-scale quantized pools "
                        "(~2x the block budget at the same HBM)")
    p.add_argument("--attention-impl",
                   choices=("auto", "xla", "pallas"), default="auto",
                   help="llama engine paged decode attention: "
                        "pallas = fused kernel reading (quantized) "
                        "pools in place, xla = fused gather, auto = "
                        "one-shot measured pick at engine build "
                        "(never selects the slower impl).  Forcing "
                        "pallas on a NON-TPU backend runs the kernel "
                        "in interpret mode — a parity/debug harness "
                        "whose multi-second steps can starve the "
                        "fabric's SUBMIT-ack liveness window; auto "
                        "refuses it off-TPU for exactly that reason")
    p.add_argument("--prefill-chunk", type=int, default=0,
                   help="llama engine: prefill long prompts this many "
                        "tokens per step, interleaved with decode "
                        "(bounds the batch's inter-token gap to one "
                        "chunk; 0 = whole-bucket prefill)")
    p.add_argument("--speculative-k", type=int, default=0,
                   help="llama engine: prompt-lookup speculative "
                        "decode, committing up to K tokens per "
                        "verify dispatch (0 disables)")
    p.add_argument("--step-delay", type=float, default=0.0)
    p.add_argument("--content-tokens", action="store_true",
                   help="fake engine: derive tokens from the prompt "
                        "content instead of the engine-local rid, so "
                        "two replicas produce identical streams for "
                        "the same request (the hedging byte-equality "
                        "gates need this)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--stats-interval", type=float,
                   default=ServingFabric.STATS_INTERVAL)
    p.add_argument("--trace-sample-rate", type=float, default=1.0,
                   help="head-sampling rate for trace contexts that "
                        "do NOT assert the sampled flag (a flagged "
                        "context carries the router's keep verdict, "
                        "incident overrides included, and is always "
                        "honored); the verdict is deterministic per "
                        "trace_id, so both sides agree without "
                        "coordination")
    p.add_argument("--profile", action="store_true",
                   help="run the always-on sampling profiler "
                        "(utils/contprof): folded-stack tables ride "
                        "STATS frames to the router, which forwards "
                        "them into the fleet /fleet/profile merge")
    p.add_argument("--profile-hz", type=float, default=19.0,
                   help="profiler sampling rate (seeded-jittered; the "
                        "default 19 Hz avoids phase-locking periodic "
                        "work)")
    p.add_argument("--crash-after", type=float, default=0.0,
                   help="chaos: hard-exit (rc 9) this many seconds "
                        "after startup — the crash-loop worker the "
                        "supervisor's quarantine exists for")
    args = p.parse_args(argv)

    if args.engine == "llama":
        try:
            engine = _build_llama_engine(args)
        except Exception:
            # the supervisor discards a worker's stderr: leave the
            # reason where whoever asked for a report will look
            if args.report_file:
                import traceback

                with open(args.report_file, "w") as f:
                    json.dump({"error": traceback.format_exc()}, f)
            raise
    else:
        engine = FakeEngine(
            slots=args.slots, blocks=args.blocks or 10_000,
            block_size=args.block_size,
            tokens_per_step=args.tokens_per_step,
            max_len=args.max_len, step_delay=args.step_delay,
            content_tokens=args.content_tokens,
        )
    from dlrover_tpu.serving.remote.faults import FaultSchedule

    profiler = None
    if args.profile:
        from dlrover_tpu.utils.contprof import ContinuousProfiler

        profiler = ContinuousProfiler(
            role="worker", hz=args.profile_hz, seed=args.seed)
        profiler.start()
    server = WorkerServer(
        engine, host=args.host, port=args.port,
        stats_interval=args.stats_interval, engine_kind=args.engine,
        fault_schedule=FaultSchedule.from_env(),
        trace_sample_rate=args.trace_sample_rate,
        profiler=profiler,
    )
    if args.crash_after > 0:
        # a real abrupt death (no GOODBYE, no atexit, nonzero rc): the
        # supervisor must read it as a crash and meter its respawns
        crash = threading.Timer(
            args.crash_after, lambda: os._exit(9))
        crash.daemon = True
        crash.start()

    terminated = threading.Event()

    def _term(signum, _frame):  # pragma: no cover - signal path
        terminated.set()
        server.stop_event.set()

    signal.signal(signal.SIGTERM, _term)
    server.announce()
    logger.info("serving worker up at %s (engine=%s)",
                server.addr, args.engine)
    server.serve_forever()
    # rc 0 is reserved for a GOODBYE-initiated exit (the router
    # DECIDED to retire this worker; the supervisor must not respawn).
    # An external SIGTERM is not a scale decision — exit 143 so the
    # supervisor restores the fleet.
    return 143 if terminated.is_set() else 0


if __name__ == "__main__":  # pragma: no cover - subprocess entry
    sys.exit(main())
