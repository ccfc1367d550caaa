"""Per-request span tracing for the serving fabric (stdlib-only).

The metrics surface (profiler.py, router/metrics.py) answers "how is
the fleet doing *on aggregate*"; this module answers the question those
gauges cannot: for THIS request, *where did the time go* — queue wait,
placement, the SUBMIT round trip, worker-side decode, first token,
retry after a replica death?  The design is a small Dapper/W3C-style
tracer:

- :class:`Span` — one timed operation with ``trace_id`` / ``span_id`` /
  ``parent_id`` links, monotonic timestamps and free-form attrs;
- :class:`Tracer` — creates spans, holds active traces, and keeps a
  **bounded ring** of finished traces (old traces fall off; a tracer
  can run forever without growing);
- traceparent helpers — ``00-<32 hex>-<16 hex>-01`` context strings the
  remote frame protocol carries in SUBMIT/TOKEN/DONE headers, so
  worker-side spans come back and are **grafted** into the request's
  trace (:meth:`Tracer.graft` shifts nothing itself — the proxy
  translates worker clocks to router clocks before grafting, see
  serving/remote/proxy.py);
- :class:`RequestTrace` — the serving request's span vocabulary
  (``request`` root, ``queued``, per-placement ``attempt`` with
  ``submit`` / ``first_token`` children) so gateway/scheduler/replica
  code stays one guarded line per hop;
- :class:`FlightRecorder` — a bounded ring of fabric events (replica
  join/death, requeue, poison, expiry) plus structured **dumps**: on a
  deadline expiry, a poisoning, or a replica death the request's whole
  span tree and the last N fabric events are emitted as ONE log record,
  so a chaos postmortem does not require replaying the run.

Everything here is dict/deque bookkeeping under short private locks —
no I/O, no blocking calls — so stamping spans from under the router or
gateway lock adds no stall surface (dlint DL003 stays clean).

Timestamps are ``time.monotonic()`` (span math must survive clock
steps); each trace also records one wall-clock anchor at creation so
exports can place the trace in absolute time.

Fleet-scale additions (the observability plane):

- **sampling** — ``Tracer(sample_rate=…)`` decides retention with
  :func:`trace_sampled`, a *deterministic* head-sampling predicate
  keyed on the trace_id itself, so a worker process configured with
  the same rate reaches the SAME verdict as the router without any
  coordination; spans are always stamped (cheap dict ops, bounded by
  ``max_active``) — the rate only gates what survives into the ring
  and whether the traceparent propagates to workers;
- **incident override** — a failover (:meth:`Tracer.mark_incident`)
  or any non-``ok`` terminal status (expiry, cancellation, poisoning)
  forces retention, so every incident keeps its full span tree even
  at 1% sampling;
- **Chrome export** — :meth:`Tracer.export_chrome_trace` emits
  Chrome trace-event JSON, pid mapped to router/replica and tid to
  the trace: request trees across processes in one perfetto view.
  (Where a process spends its time — step loop, checkpoint writer,
  engine dispatches — is in the profiler's own trace, beside the
  device's operations: ``utils/profiler.span``.)
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from collections import OrderedDict, deque
from typing import Deque, Dict, List, Optional, Tuple

from dlrover_tpu.common.log import default_logger as logger

TRACEPARENT_VERSION = "00"


def new_trace_id() -> str:
    """128-bit random trace id, W3C-trace-context shaped (32 hex)."""
    return os.urandom(16).hex()


def new_span_id() -> str:
    """64-bit random span id (16 hex)."""
    return os.urandom(8).hex()


def trace_sampled(trace_id: str, sample_rate: float) -> bool:
    """Deterministic head-sampling verdict for ``trace_id``.

    Keyed on the id's leading 32 bits (uniform for our random ids), so
    EVERY process that knows the rate computes the same answer — the
    router's retention decision and a worker's span-shipping decision
    agree without a coordination frame.  Malformed ids sample in:
    observability must degrade toward keeping data, not dropping it.
    """
    if sample_rate >= 1.0:
        return True
    if sample_rate <= 0.0:
        return False
    try:
        bucket = int(trace_id[:8], 16)
    except (TypeError, ValueError):
        return True
    return bucket < sample_rate * float(0x100000000)


def format_traceparent(trace_id: str, span_id: str) -> str:
    """``00-<trace>-<span>-01`` (always sampled: the ring is the cap)."""
    return f"{TRACEPARENT_VERSION}-{trace_id}-{span_id}-01"


def parse_traceparent(value) -> Optional[Tuple[str, str]]:
    """``(trace_id, span_id)`` from a traceparent string, or ``None``
    for anything malformed — a bad header degrades to "untraced", never
    to an error on the data plane."""
    if not isinstance(value, str):
        return None
    parts = value.split("-")
    if len(parts) != 4:
        return None
    _, trace_id, span_id, _ = parts
    if len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16), int(span_id, 16)
    except ValueError:
        return None
    return trace_id, span_id


@dataclasses.dataclass
class Span:
    """One timed operation inside a trace."""

    trace_id: str
    span_id: str
    parent_id: Optional[str]
    name: str
    start: float                     # monotonic
    end: Optional[float] = None
    status: str = "ok"
    attrs: Dict[str, object] = dataclasses.field(default_factory=dict)
    # cross-trace references (W3C-shaped: the OTLP span-link concept):
    # each entry is {"trace_id", "span_id", "attrs"} pointing at a span
    # in ANOTHER trace — how a failed-over request's attempt names the
    # autoscale/replacement trace that created the replica it landed
    # on, and how a fleet_migration trace names its demand evidence
    links: List[Dict[str, object]] = dataclasses.field(
        default_factory=list)

    @property
    def duration(self) -> Optional[float]:
        return None if self.end is None else self.end - self.start

    def add_link(self, trace_id: str, span_id: str,
                 **attrs) -> "Span":
        """Reference a span in another trace (parenthood crosses a
        causality boundary the tree cannot express: the linked trace
        happened on the control plane, this span on the data plane)."""
        self.links.append({
            "trace_id": trace_id, "span_id": span_id,
            "attrs": dict(attrs),
        })
        return self

    def finish(self, now: Optional[float] = None,
               status: Optional[str] = None) -> "Span":
        if self.end is None:
            self.end = time.monotonic() if now is None else now
            if status is not None:
                self.status = status
        return self

    def to_dict(self, t0: float = 0.0) -> Dict[str, object]:
        out = {
            "name": self.name,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "offset_s": round(self.start - t0, 6),
            "duration_s": (
                None if self.end is None
                else round(self.end - self.start, 6)
            ),
            "status": self.status,
            "attrs": dict(self.attrs),
        }
        if self.links:
            out["links"] = [dict(ln) for ln in self.links]
        return out


class Trace:
    """All spans of one trace (internal record; export via ``tree``)."""

    def __init__(self, root: Span, wall_anchor: Optional[float] = None,
                 sampled: bool = True):
        self.root = root
        self.spans: List[Span] = [root]
        # wall-clock anchor for exports; spans themselves are monotonic
        self.wall_anchor = time.time() if wall_anchor is None \
            else wall_anchor
        self.status = "active"
        # head-sampling verdict (trace_sampled at creation); gates ring
        # retention and traceparent propagation, never span stamping
        self.sampled = sampled
        # incident override: a failover/expiry/cancellation marks the
        # trace so it is retained (and propagated) regardless of the
        # sampling verdict — incidents must keep their full trace
        self.incident = False

    @property
    def trace_id(self) -> str:
        return self.root.trace_id

    @property
    def duration(self) -> float:
        end = self.root.end
        if end is None:
            end = max(
                (s.end for s in self.spans if s.end is not None),
                default=self.root.start,
            )
        return end - self.root.start

    def tree(self) -> Dict[str, object]:
        """The nested span tree (JSON-ready)."""
        t0 = self.root.start
        by_id: Dict[str, Dict[str, object]] = {}
        for s in self.spans:
            d = s.to_dict(t0)
            d["children"] = []
            by_id[s.span_id] = d
        roots: List[Dict[str, object]] = []
        for s in self.spans:
            d = by_id[s.span_id]
            parent = by_id.get(s.parent_id or "")
            if parent is not None and parent is not d:
                parent["children"].append(d)
            else:
                roots.append(d)
        return {
            "trace_id": self.trace_id,
            "name": self.root.name,
            "status": self.status,
            "start_unix": round(self.wall_anchor, 6),
            "duration_s": round(self.duration, 6),
            "spans": roots,
        }


class FlightRecorder:
    """Bounded fabric-event ring + structured failure dumps.

    ``record()`` appends one event (cheap, lock-only).  ``dump()`` is
    the black-box readout: it snapshots the last events next to the
    failing request's span tree and emits them as ONE structured log
    record (single line, JSON payload) — the self-explaining postmortem
    for a deadline expiry, a poisoning, or a replica death.  Dumps are
    also kept in a bounded ring so tests and the ``/traces`` surface
    can read them without scraping logs.
    """

    def __init__(self, event_capacity: int = 256,
                 dump_capacity: int = 32):
        self._lock = threading.Lock()
        self._events: Deque[Dict[str, object]] = deque(
            maxlen=int(event_capacity))
        self.dumps: Deque[Dict[str, object]] = deque(
            maxlen=int(dump_capacity))
        self.dumps_total = 0
        self._seq = 0  # monotone event counter (cursor for consumers)
        # contprof.ContinuousProfiler via attach_profiler: every dump
        # then carries a "profile_ref" freezing the flame state at the
        # moment of the incident (resolve at /debug/prof?ref=...)
        self._profiler = None

    def attach_profiler(self, prof) -> None:
        """Stamp a frozen profile snapshot ref onto every future dump —
        the answer to "where was the CPU when this expired" survives
        even after the live profiler tables move on."""
        self._profiler = prof

    def record(self, kind: str, now: Optional[float] = None,
               **fields) -> None:
        event = {"kind": kind,
                 "t": time.monotonic() if now is None else now}
        event.update(fields)
        with self._lock:
            event["seq"] = self._seq
            self._seq += 1
            self._events.append(event)

    def events(self, limit: int = 64) -> List[Dict[str, object]]:
        with self._lock:
            return list(self._events)[-int(limit):]

    @property
    def last_seq(self) -> int:
        """Sequence number of the newest event (-1 when empty) — the
        starting cursor for :meth:`events_since` consumers."""
        with self._lock:
            return self._seq - 1

    def events_since(self, seq: int) -> List[Dict[str, object]]:
        """Events with ``seq`` strictly greater than the cursor — how
        the autoscale-trace stitcher consumes the fabric vocabulary
        (worker spawn, replica join, first placement) incrementally.
        A consumer that lags past the ring's capacity simply misses the
        overwritten events; the ring stays bounded either way."""
        with self._lock:
            return [e for e in self._events if e["seq"] > seq]

    def dump(self, reason: str, trace_tree: Optional[Dict[str, object]],
             now: Optional[float] = None,
             last_events: int = 64) -> Dict[str, object]:
        record = {
            "reason": reason,
            "t": time.monotonic() if now is None else now,
            "trace": trace_tree,
            "recent_events": self.events(last_events),
        }
        prof = self._profiler
        if prof is not None:
            try:
                record["profile_ref"] = prof.capture_ref(reason)
            except Exception:  # a dump must never fail on the stamp
                pass
        with self._lock:
            self.dumps.append(record)
            self.dumps_total += 1
        try:
            payload = json.dumps(record, default=str)
        except (TypeError, ValueError):  # unserializable attr snuck in
            payload = repr(record)
        logger.error("FLIGHT-RECORDER %s trace=%s %s",
                     reason,
                     (trace_tree or {}).get("trace_id", "?"),
                     payload)
        return record


class Tracer:
    """Span factory + bounded in-memory store of finished traces."""

    def __init__(self, ring_capacity: int = 512, max_active: int = 4096,
                 recorder: Optional[FlightRecorder] = None,
                 sample_rate: float = 1.0):
        self._lock = threading.Lock()
        self._active: "OrderedDict[str, Trace]" = OrderedDict()
        self._ring: Deque[Trace] = deque(maxlen=int(ring_capacity))
        self.max_active = int(max_active)
        self.recorder = recorder or FlightRecorder()
        # head-sampling knob: the fraction of HEALTHY traces retained
        # into the ring (and propagated to workers).  1.0 = everything
        # (the historical behavior); incidents always survive.
        self.sample_rate = float(sample_rate)
        self.finished_total = 0
        self.orphan_spans_total = 0
        self.sampled_total = 0   # finished traces retained
        self.dropped_total = 0   # finished healthy traces sampled out
        # OTLP push pipeline (utils/otlp.OtlpExporter), attached via
        # attach_otlp: every RETAINED finished trace is offered to it
        # (bounded non-blocking enqueue) right after the ring append
        self._otlp = None

    def attach_otlp(self, exporter) -> None:
        """Ship every retained finished trace through ``exporter``
        (``ship_trace(trace)`` — the bounded drop-never-block offer).
        Sampled-out traces are not shipped: the sampling knob stays a
        real cost knob across the push pipeline too."""
        self._otlp = exporter

    # ----------------------------------------------------------- spans
    def start_trace(self, name: str, now: Optional[float] = None,
                    always_sample: bool = False, **attrs) -> Span:
        """Open a trace.  ``always_sample=True`` exempts it from head
        sampling — control-plane traces (one per autoscale decision)
        are rare and always worth keeping."""
        now = time.monotonic() if now is None else now
        root = Span(
            trace_id=new_trace_id(), span_id=new_span_id(),
            parent_id=None, name=name, start=now, attrs=dict(attrs),
        )
        trace = Trace(root, sampled=(
            always_sample
            or trace_sampled(root.trace_id, self.sample_rate)))
        with self._lock:
            self._active[root.trace_id] = trace
            # bound active traces: a submitted-but-never-pumped request
            # must not leak memory forever — oldest evicts to the ring
            while len(self._active) > self.max_active:
                _, stale = self._active.popitem(last=False)
                stale.status = "evicted"
                self._ring.append(stale)
        return root

    def start_span(self, parent: Span, name: str,
                   now: Optional[float] = None, **attrs) -> Span:
        now = time.monotonic() if now is None else now
        span = Span(
            trace_id=parent.trace_id, span_id=new_span_id(),
            parent_id=parent.span_id, name=name, start=now,
            attrs=dict(attrs),
        )
        with self._lock:
            trace = self._active.get(parent.trace_id)
            if trace is not None:
                trace.spans.append(span)
        return span

    def finish_trace(self, root: Span, now: Optional[float] = None,
                     status: str = "ok") -> None:
        root.finish(now, status=status)
        ship = None
        with self._lock:
            trace = self._active.pop(root.trace_id, None)
            if trace is None:
                return
            trace.status = status
            # retention: sampled-in traces, plus EVERY incident — a
            # non-ok terminal status or an explicit mark_incident (a
            # failover that later completed ok) — survive the knob
            if trace.sampled or trace.incident or status != "ok":
                self._ring.append(trace)
                self.finished_total += 1
                self.sampled_total += 1
                ship = trace
            else:
                self.dropped_total += 1
        # the OTLP offer happens OUTSIDE this tracer's lock (it takes
        # the exporter's own short queue lock; no nesting, no I/O)
        if ship is not None and self._otlp is not None:
            self._otlp.ship_trace(ship)

    def mark_incident(self, trace_id: str, reason: str = "") -> None:
        """Incident override: this trace must be retained (and its
        traceparent keep propagating) regardless of the sampling
        verdict.  Called on failover — expiries/cancellations/poison
        already retain via their non-``ok`` terminal status."""
        with self._lock:
            trace = self._find_locked(trace_id)
            if trace is not None:
                trace.incident = True
                if reason:
                    trace.root.attrs.setdefault("incident", reason)

    def should_propagate(self, trace_id: str) -> bool:
        """Whether the traceparent should ride frames to a worker for
        this trace: sampled-in or incident-marked.  Unknown traces
        propagate (never drop context on a bookkeeping miss)."""
        with self._lock:
            trace = self._find_locked(trace_id)
            if trace is None:
                return True
            return trace.sampled or trace.incident

    def sampling_verdict(self, trace_id: str) -> bool:
        """The head-sampling verdict stamped at trace creation —
        immutable for the trace's lifetime (the incident override adds
        retention on TOP of it, it never flips it off).  Callers cache
        it: a sampled-IN trace can then build traceparents without
        ever re-taking this lock, which matters on the submit hot path
        (:meth:`RequestTrace.traceparent`).  Unknown traces read as
        sampled (degrade toward keeping data)."""
        with self._lock:
            trace = self._find_locked(trace_id)
            return True if trace is None else trace.sampled

    # ----------------------------------------------------------- graft
    def graft(self, trace_id: str, parent_span_id: str,
              spans: List[Dict[str, object]]) -> int:
        """Attach remote-side spans (already translated to THIS
        process's monotonic clock by the caller) under
        ``parent_span_id``.  Span dicts: ``name``/``start``/``end``,
        optional ``attrs`` and ``parent`` (the *name* of an earlier
        span in the same batch, for nesting).  Spans for an unknown
        trace — a DONE that raced past completion, a late frame after
        failover — are counted as orphans and dropped, never an error:
        observability must not add failure modes."""
        if not spans:
            return 0
        with self._lock:
            trace = self._find_locked(trace_id)
            if trace is None:
                self.orphan_spans_total += len(spans)
                return 0
            by_name: Dict[str, str] = {}
            grafted = 0
            for raw in spans:
                try:
                    name = str(raw["name"])
                    start = float(raw["start"])
                    end = float(raw["end"])
                except (KeyError, TypeError, ValueError):
                    self.orphan_spans_total += 1
                    continue
                parent = by_name.get(str(raw.get("parent", "")),
                                     parent_span_id)
                span = Span(
                    trace_id=trace_id, span_id=new_span_id(),
                    parent_id=parent, name=name, start=start, end=end,
                    attrs=dict(raw.get("attrs") or {}),
                )
                trace.spans.append(span)
                by_name[name] = span.span_id
                grafted += 1
            return grafted

    def _find_locked(self, trace_id: str) -> Optional[Trace]:
        trace = self._active.get(trace_id)
        if trace is not None:
            return trace
        for t in self._ring:  # bounded by ring_capacity
            if t.trace_id == trace_id:
                return t
        return None

    # ---------------------------------------------------------- export
    def get_tree(self, trace_id: str) -> Optional[Dict[str, object]]:
        with self._lock:
            trace = self._find_locked(trace_id)
            return None if trace is None else trace.tree()

    @staticmethod
    def _matches(trace: "Trace", name: Optional[str],
                 status: Optional[str]) -> bool:
        if name is not None and trace.root.name != name:
            return False
        if status is not None and trace.status != status:
            return False
        return True

    def finished(self, limit: int = 50, name: Optional[str] = None,
                 status: Optional[str] = None
                 ) -> List[Dict[str, object]]:
        """Most recent finished traces, newest last.  ``name`` filters
        on the root span, ``status`` on the terminal status — mid-
        incident the question is "the failover traces, now", and
        dumping a 4096-entry ring is not an answer."""
        with self._lock:
            traces = [t for t in self._ring
                      if self._matches(t, name, status)][-int(limit):]
        return [t.tree() for t in traces]

    def slowest(self, limit: int = 10, name: Optional[str] = None,
                status: Optional[str] = None
                ) -> List[Dict[str, object]]:
        """Finished traces ranked by duration, slowest first — the
        ``/traces/slowest`` debugging view: which requests blew their
        budget, and inside which span.  Same filters as
        :meth:`finished`."""
        with self._lock:
            traces = sorted(
                (t for t in self._ring
                 if self._matches(t, name, status)),
                key=lambda t: -t.duration)[:int(limit)]
        return [t.tree() for t in traces]

    def traces_named(self, name: str,
                     limit: int = 20) -> List[Dict[str, object]]:
        """Traces whose ROOT span is ``name`` — active ones included,
        newest last.  The ``/traces/autoscale`` view: control-plane
        traces are long-lived (plan -> spawn -> join -> first
        placement spans arrive over seconds), so the view must show
        them mid-flight, not only after they close."""
        with self._lock:
            finished = [t for t in self._ring if t.root.name == name]
            active = [t for t in self._active.values()
                      if t.root.name == name]
            picked = (finished + active)[-int(limit):]
            return [t.tree() for t in picked]

    # ---------------------------------------------- chrome-trace export
    def export_chrome_trace(self, trace_id: Optional[str] = None,
                            path: Optional[str] = None) -> str:
        """Chrome trace-event JSON: complete events with
        ``name``/``ph``/``ts``/``dur``/``pid``/``tid``, µs timestamps
        on the monotonic clock, loadable in perfetto.  ``pid`` maps to
        the process the span ran in (router vs each replica — worker
        spans are already clock-translated to router time at graft),
        ``tid`` to the trace, so concurrent requests land on separate
        rows.  ``trace_id=None`` exports every held trace."""
        with self._lock:
            if trace_id is not None:
                trace = self._find_locked(trace_id)
                traces = [] if trace is None else [trace]
            else:
                traces = list(self._ring) + list(self._active.values())
            events: List[Dict[str, object]] = []
            pids: Dict[str, int] = {"router": 1}
            # span_id -> (ts_us, pid, tid) of every exported span, and
            # the spans carrying links: resolved into flow events after
            # the main pass so a link renders as an arrow between the
            # linking span and its (cross-trace) target in perfetto
            located: Dict[str, Tuple[float, int, int]] = {}
            linkers: List[Tuple[Span, float, int, int]] = []
            for tid_n, trace in enumerate(traces):
                parent_of = {s.span_id: s.parent_id for s in trace.spans}
                replica_of = {
                    s.span_id: s.attrs.get("replica")
                    for s in trace.spans
                }
                fallback_end = trace.root.start + trace.duration
                for s in trace.spans:
                    proc = "router"
                    if s.name.startswith("worker."):
                        # nearest ancestor that names a replica (the
                        # attempt span) owns the worker-side spans
                        sid: Optional[str] = s.span_id
                        while sid is not None:
                            rep = replica_of.get(sid)
                            if rep:
                                proc = f"replica {rep}"
                                break
                            sid = parent_of.get(sid)
                    pid = pids.setdefault(proc, len(pids) + 1)
                    end = s.end if s.end is not None else fallback_end
                    ts = round(s.start * 1e6, 3)
                    events.append({
                        "name": s.name, "ph": "X",
                        "ts": ts,
                        "dur": round(max(0.0, end - s.start) * 1e6, 3),
                        "pid": pid, "tid": tid_n,
                        "args": dict(
                            s.attrs, trace_id=trace.trace_id,
                            status=s.status),
                    })
                    located[s.span_id] = (ts, pid, tid_n)
                    if s.links:
                        linkers.append((s, ts, pid, tid_n))
        # span links as flow events: an "s" (start) at the LINKED span
        # — the autoscale/replacement decision — flowing into an "f"
        # (finish) at the linking span, so perfetto draws the arrow
        # from cause to consequence.  Links whose target is not in
        # this export (evicted, other process) are skipped: a flow
        # event without both ends renders as clutter, not signal.
        for s, ts, pid, tid_n in linkers:
            for ln in s.links:
                src = located.get(str(ln.get("span_id", "")))
                if src is None:
                    continue
                flow_id = str(ln["span_id"]) + s.span_id
                src_ts, src_pid, src_tid = src
                events.append({
                    "name": "span_link", "cat": "link", "ph": "s",
                    "id": flow_id, "ts": src_ts,
                    "pid": src_pid, "tid": src_tid,
                    "args": dict(ln.get("attrs") or {}),
                })
                events.append({
                    "name": "span_link", "cat": "link", "ph": "f",
                    "bp": "e", "id": flow_id, "ts": ts,
                    "pid": pid, "tid": tid_n,
                    "args": dict(ln.get("attrs") or {}),
                })
        for proc, pid in sorted(pids.items(), key=lambda kv: kv[1]):
            events.append({
                "name": "process_name", "ph": "M", "ts": 0.0,
                "dur": 0.0, "pid": pid, "tid": 0,
                "args": {"name": proc},
            })
        text = json.dumps({"traceEvents": events}, default=str)
        if path:
            with open(path, "w") as f:
                f.write(text)
        return text

    def flight_dump(self, reason: str, trace_id: str,
                    now: Optional[float] = None) -> Dict[str, object]:
        return self.recorder.dump(
            reason, self.get_tree(trace_id), now=now)

    def metrics(self) -> Dict[str, float]:
        """Prometheus source (``MetricsExporter.add_source``)."""
        with self._lock:
            ring = len(self._ring)
            active = len(self._active)
            slowest = max(
                (t.duration for t in self._ring), default=0.0)
            # counters snapshot under the same lock finish_trace and
            # graft bump them under — a scrape must not see
            # finished_total from before a retention decision and
            # dropped_total from after it
            finished = self.finished_total
            orphans = self.orphan_spans_total
            sampled = self.sampled_total
            dropped = self.dropped_total
        return {
            "serving_request_trace_finished_total": float(finished),
            "serving_request_trace_active": float(active),
            "serving_request_trace_ring_size": float(ring),
            "serving_request_trace_slowest_seconds": float(slowest),
            "serving_request_trace_orphan_spans_total": float(orphans),
            "serving_request_trace_flight_dumps_total": float(
                self.recorder.dumps_total),
            # the sampling knob's proof pair: dropped > 0 says the
            # rate is biting; sampled counts what survived (incident
            # overrides included)
            "serving_trace_sampled_total": float(sampled),
            "serving_trace_dropped_total": float(dropped),
        }


class RequestTrace:
    """One serving request's span vocabulary, so fabric code stays a
    guarded one-liner per hop:

    - ``request`` (root) — admission to completion;
    - ``queued`` — gateway wait (one per attempt: a failover requeue
      opens a fresh one);
    - ``attempt`` — one placement on one replica (attrs: replica,
      attempt number; a dead replica leaves it closed as ``failover``
      and the retry opens the next one — postmortems see BOTH);
    - ``submit`` — the engine admission / remote SUBMIT round trip;
    - ``first_token`` — zero-length marker at true first-token time;
    - worker-side spans grafted under the attempt they served.
    """

    def __init__(self, tracer: Tracer, rid: int,
                 now: Optional[float] = None, **attrs):
        self.tracer = tracer
        self.root = tracer.start_trace(
            "request", now=now, rid=rid, **attrs)
        self.queued: Optional[Span] = tracer.start_span(
            self.root, "queued", now=now)
        self.attempt: Optional[Span] = None
        self.submit: Optional[Span] = None
        self.attempts = 0
        # the sampling verdict is fixed at creation (incident only
        # ADDS retention), so cache it once: sampled-in traces — the
        # common case at rate 1.0 — then skip the tracer-lock round
        # trip on every traceparent() the submit path makes, and
        # sampled-out ones skip worker-span graft work entirely
        self.sampled = tracer.sampling_verdict(self.root.trace_id)

    @property
    def trace_id(self) -> str:
        return self.root.trace_id

    # -------------------------------------------------------- lifecycle
    def placed(self, replica: str, now: Optional[float] = None,
               **attrs) -> None:
        if self.queued is not None:
            self.queued.finish(now)
            self.queued = None
        self.attempts += 1
        self.attempt = self.tracer.start_span(
            self.root, "attempt", now=now,
            replica=replica, attempt=self.attempts, **attrs)

    def submit_started(self, now: Optional[float] = None) -> None:
        self.submit = self.tracer.start_span(
            self.attempt or self.root, "submit", now=now)

    def submit_finished(self, now: Optional[float] = None,
                        status: str = "ok") -> None:
        if self.submit is not None:
            self.submit.finish(now, status=status)
            self.submit = None

    def first_token(self, now: Optional[float] = None) -> None:
        span = self.tracer.start_span(
            self.attempt or self.root, "first_token", now=now)
        span.finish(now)

    def traceparent(self) -> Optional[str]:
        """Context string the remote SUBMIT frame carries: worker-side
        spans parent under the CURRENT attempt, so a retry's worker
        time lands under the retry, not the dead first attempt.
        ``None`` for a sampled-out trace — the worker then builds and
        ships no spans for it, which is what makes the sample-rate
        knob a real cost knob end to end (an incident-marked trace
        resumes propagating: the failover retry's worker spans come
        back even at 1% sampling)."""
        if not self.sampled and \
                not self.tracer.should_propagate(self.root.trace_id):
            # only sampled-OUT traces pay the tracer-lock round trip,
            # and only to check the incident override
            return None
        parent = self.attempt or self.root
        return format_traceparent(self.root.trace_id, parent.span_id)

    def graft_worker_spans(
            self, spans: Optional[List[Dict[str, object]]]) -> int:
        if not spans:
            return 0
        parent = self.attempt or self.root
        return self.tracer.graft(
            self.root.trace_id, parent.span_id, spans)

    def failover(self, reason: str,
                 now: Optional[float] = None) -> None:
        """The replica serving this attempt died: close the attempt as
        ``failover`` (it stays in the tree — the postmortem shows the
        dead-replica attempt AND the retry) and reopen a queue span.
        A failover is an INCIDENT: even if the retry completes ok, the
        trace must survive sampling — mark it before anything else."""
        self.tracer.mark_incident(self.root.trace_id, reason)
        if self.submit is not None:
            self.submit.finish(now, status="failover")
            self.submit = None
        if self.attempt is not None:
            self.attempt.attrs["failover_reason"] = reason
            self.attempt.finish(now, status="failover")
            self.attempt = None
        if self.queued is not None:
            # requeued while still waiting (never placed): close the
            # open queue span rather than leaking a dangling one
            self.queued.finish(now, status="failover")
        self.queued = self.tracer.start_span(
            self.root, "queued", now=now, requeue=True)

    def finished(self, now: Optional[float] = None) -> None:
        self._close_open(now, "ok")
        self.tracer.finish_trace(self.root, now=now, status="ok")

    def aborted(self, status: str,
                now: Optional[float] = None) -> None:
        self._close_open(now, status)
        self.tracer.finish_trace(self.root, now=now, status=status)

    def _close_open(self, now: Optional[float], status: str) -> None:
        if self.submit is not None:
            self.submit.finish(now, status=status)
            self.submit = None
        if self.attempt is not None:
            self.attempt.finish(now, status=status)
            self.attempt = None
        if self.queued is not None:
            self.queued.finish(now, status=status)
            self.queued = None
