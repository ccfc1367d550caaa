"""Profiling + metrics export: the TPU counterpart of xpu_timer.

Parity target: reference atorch/dev/xpu_timer/ — an LD_PRELOAD C++
library hooking cudaLaunchKernel/NCCL/cuBLAS, timing kernels with CUDA
events and serving Prometheus metrics per rank
(atorch/dev/xpu_timer/README.md:1-40, xpu_timer/nvidia/hook.cc).

On TPU the XLA runtime already owns kernel timing — the idiomatic
equivalents are:

- :class:`StepTimer` — wall-clock step timing with EMA + reservoir
  percentiles (device time is visible through it because JAX dispatch
  blocks on donated-buffer reuse each step);
- :func:`trace` — ``jax.profiler`` trace capture (the XProf/``xplane``
  trace is the TPU analogue of the CUDA-event kernel timeline; view with
  TensorBoard);
- :func:`span` — the program's own host spans (trainer step, checkpoint
  stage and commit, engine step, router step) written into that same
  trace, on the device's clock, whoever opened the profiler;
  :func:`event` — one that says a fact and times nothing (a served
  request's ``dlrover.request.*``, joined by ``rid`` / ``erid``);
- :func:`device_scope` — the device-side sibling of :func:`span`: a
  ``jax.named_scope`` the process remembers, so that
  :func:`program_scopes` can say which scope each instruction of a
  compiled hot-path program (:func:`register_program`) belongs to —
  what ``utils/xprof_metrics.scope_seconds`` joins a trace with;
- :class:`MetricsExporter` — a Prometheus text endpoint per process
  (``/metrics``), like xpu_timer's per-rank ``:38888+rank`` exporter.

No LD_PRELOAD is needed: libtpu/XLA expose their timeline through the
profiler plugin, so the framework only adds the serving layer.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import http.server
import json
import random
import re
import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Set, Tuple

from dlrover_tpu.common.log import default_logger as logger


class StepTimer:
    """Per-step wall-time stats: count, EMA, and reservoir percentiles."""

    def __init__(self, ema_alpha: float = 0.05, reservoir: int = 256):
        self._alpha = ema_alpha
        self._reservoir_size = reservoir
        self._lock = threading.Lock()
        self.count = 0
        self.ema_seconds = 0.0
        self.last_seconds = 0.0
        self.total_seconds = 0.0
        self._samples: List[float] = []
        self._t0: Optional[float] = None

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self) -> float:
        if self._t0 is None:
            return 0.0
        dt = time.perf_counter() - self._t0
        self._t0 = None
        self.observe(dt)
        return dt

    @contextlib.contextmanager
    def step(self):
        self.start()
        try:
            yield
        finally:
            self.stop()

    def observe(self, seconds: float) -> None:
        with self._lock:
            self.count += 1
            self.last_seconds = seconds
            self.total_seconds += seconds
            if self.count == 1:
                self.ema_seconds = seconds
            else:
                self.ema_seconds += self._alpha * (seconds - self.ema_seconds)
            if len(self._samples) < self._reservoir_size:
                self._samples.append(seconds)
            else:  # reservoir sampling keeps percentiles unbiased
                j = random.randint(0, self.count - 1)
                if j < self._reservoir_size:
                    self._samples[j] = seconds

    def percentile(self, q: float) -> float:
        with self._lock:
            return self._percentile_locked(q)

    def _percentile_locked(self, q: float) -> float:
        if not self._samples:
            return 0.0
        ordered = sorted(self._samples)
        idx = min(len(ordered) - 1, int(q / 100.0 * len(ordered)))
        return ordered[idx]

    def metrics(self, prefix: str = "dlrover_step") -> Dict[str, float]:
        # one locked snapshot: a scrape racing observe() must never
        # see count from one step and total/ema from the next
        with self._lock:
            return {
                f"{prefix}_count": float(self.count),
                f"{prefix}_seconds_ema": self.ema_seconds,
                f"{prefix}_seconds_last": self.last_seconds,
                f"{prefix}_seconds_p50": self._percentile_locked(50),
                f"{prefix}_seconds_p99": self._percentile_locked(99),
                f"{prefix}_seconds_total": self.total_seconds,
            }


class WindowGauge:
    """Sliding-time-window aggregate: mean / max / rate over the last
    ``window_seconds`` of observations.  The serving router reports
    queue depth and token throughput through these — a scrape must see
    recent load, not the lifetime average (autoscaling keys off it)."""

    def __init__(self, window_seconds: float = 60.0):
        self.window = float(window_seconds)
        self._lock = threading.Lock()
        self._samples: List[tuple] = []  # (timestamp, value)

    def observe(self, value: float, now: Optional[float] = None) -> None:
        now = time.monotonic() if now is None else now
        with self._lock:
            self._samples.append((now, float(value)))
            self._trim(now)

    def _trim(self, now: float) -> None:
        cutoff = now - self.window
        i = 0
        while i < len(self._samples) and self._samples[i][0] < cutoff:
            i += 1
        if i:
            del self._samples[:i]

    def _values(self, now: Optional[float]) -> List[float]:
        now = time.monotonic() if now is None else now
        with self._lock:
            self._trim(now)
            return [v for _, v in self._samples]

    def mean(self, now: Optional[float] = None) -> float:
        vals = self._values(now)
        return sum(vals) / len(vals) if vals else 0.0

    def max(self, now: Optional[float] = None) -> float:
        vals = self._values(now)
        return max(vals) if vals else 0.0

    def rate(self, now: Optional[float] = None) -> float:
        """Sum of observed values per second over the window (e.g. feed
        token counts in, read tokens/sec out)."""
        vals = self._values(now)
        return sum(vals) / self.window if vals else 0.0


def log_buckets(lo: float = 0.001, hi: float = 64.0,
                factor: float = 2.0) -> tuple:
    """Log-spaced histogram bucket bounds: ``lo, lo*factor, …`` until
    ``hi`` is covered.  Fixed at construction — latency distributions
    span decades, and a fixed log ladder keeps every process's buckets
    identical (aggregatable across the fleet)."""
    out = [float(lo)]
    while out[-1] < hi:
        out.append(out[-1] * factor)
    return tuple(round(b, 12) for b in out)


class Histogram:
    """Fixed-bucket latency histogram with OpenMetrics exemplars.

    The WindowGauge answers "what is the p99 NOW"; this answers "what
    does the distribution look like, and WHICH request put a sample in
    the tail" — each bucket remembers the most recent observation's
    ``trace_id`` as an OpenMetrics exemplar
    (``… # {trace_id="…"} value timestamp``), so a Grafana-style
    drill-down jumps from a bucket straight to ``/traces/<id>``.
    Lock-guarded; ``observe`` is O(#buckets) with no allocation — safe
    from the router's hot path."""

    def __init__(self, name: str, help_text: str = "",
                 buckets: Optional[tuple] = None,
                 labels: Optional[Dict[str, str]] = None):
        self.name = name
        self.help_text = help_text
        # constant label set rendered on every sample line (next to
        # ``le``) — how one family fans out into a BOUNDED set of
        # series (e.g. serving_step_phase_seconds{phase="pump"}); the
        # label keys must be declared in metric_registry.METRIC_LABELS
        # (dlint DL010) and the values must come from closed
        # vocabularies, never per-request identifiers
        self.labels = dict(labels) if labels else None
        self.buckets = tuple(sorted(buckets or log_buckets()))
        self._lock = threading.Lock()
        # one slot per bucket + overflow; counts are NON-cumulative
        # here (cumulated at render time, per the exposition format)
        self._counts = [0] * (len(self.buckets) + 1)
        # per-bucket exemplar: (trace_id, value, wall_ts) — newest wins
        self._exemplars: List[Optional[tuple]] = [None] * (
            len(self.buckets) + 1)
        self._sum = 0.0
        self._count = 0

    def observe(self, value: float, trace_id: Optional[str] = None,
                now: Optional[float] = None) -> None:
        value = float(value)
        idx = len(self.buckets)
        for i, bound in enumerate(self.buckets):
            if value <= bound:
                idx = i
                break
        with self._lock:
            self._counts[idx] += 1
            self._sum += value
            self._count += 1
            if trace_id:
                self._exemplars[idx] = (
                    str(trace_id), value,
                    time.time() if now is None else now)

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    def snapshot(self) -> Dict[str, object]:
        """Consistent copy of the whole distribution (counts are the
        per-bucket NON-cumulative values; the last slot is overflow) —
        what the OTLP exporter converts into a histogram dataPoint
        with trace exemplars."""
        with self._lock:
            return {
                "name": self.name,
                "labels": dict(self.labels) if self.labels else {},
                "buckets": list(self.buckets),
                "counts": list(self._counts),
                "exemplars": list(self._exemplars),
                "sum": self._sum,
                "count": self._count,
            }

    @staticmethod
    def _fmt(x: float) -> str:
        return f"{x:.12g}"

    def render(self) -> str:
        """OpenMetrics text: ``# TYPE … histogram``, cumulative
        ``_bucket`` series with exemplars on the buckets that hold
        one, then ``_count`` / ``_sum``."""
        with self._lock:
            counts = list(self._counts)
            exemplars = list(self._exemplars)
            total, total_sum = self._count, self._sum
        lines = [f"# TYPE {self.name} histogram"]
        if self.help_text:
            lines.append(f"# HELP {self.name} {self.help_text}")
        extra = ""
        if self.labels:
            extra = ",".join(
                f'{k}="{escape_label_value(str(v))}"'
                for k, v in sorted(self.labels.items())) + ","
        plain = "{" + extra.rstrip(",") + "}" if extra else ""
        cum = 0
        bounds = [self._fmt(b) for b in self.buckets] + ["+Inf"]
        for i, le in enumerate(bounds):
            cum += counts[i]
            line = f'{self.name}_bucket{{{extra}le="{le}"}} {cum}'
            ex = exemplars[i]
            if ex is not None:
                tid, value, ts = ex
                line += (
                    f' # {{trace_id="{escape_label_value(tid)}"}} '
                    f"{self._fmt(value)} {ts:.3f}"
                )
            lines.append(line)
        lines.append(f"{self.name}_count{plain} {total}")
        lines.append(f"{self.name}_sum{plain} {self._fmt(total_sum)}")
        return "\n".join(lines) + "\n"


@contextlib.contextmanager
def trace(log_dir: str, host_tracer_level: int = 2):
    """Capture an XLA/XProf trace for the enclosed region (TensorBoard-
    viewable) — the TPU analogue of xpu_timer's kernel timeline.  The
    program's :func:`span`s land in it; Python-level function tracing
    is off (it slows the host it is meant to time)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = host_tracer_level
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def span(name: str, **attrs):
    """A host span in the profiler's OWN trace: a
    ``jax.profiler.TraceAnnotation``, so it lands in the ``.xplane.pb``
    of whichever session is open (:func:`trace`, the trainer's
    ``AutoProfiler``, a benchmark's) on the clock of the device's
    ``XLA Ops`` line, with ``attrs`` as the event's stats.  With no
    session open it records nothing and costs about a microsecond, so
    the program's spans (``dlrover.<layer>.<what>``) are unconditional."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, **attrs)


def event(name: str, **attrs) -> None:
    """A fact in the profiler's trace: a :func:`span` opened and closed
    at once, ``attrs`` what it says (a request was placed, admitted,
    given its first token).  The profiler cuts a string at a comma:
    join lists with spaces."""
    with span(name, **attrs):
        pass


def spanned(name: str):
    """Decorator: the whole call of a function as one :func:`span`."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def step_span(name: str, step: int):
    """:func:`span` for one training step: a ``StepTraceAnnotation``,
    which xprof's step views group device time by (stat ``step_num``)."""
    from jax.profiler import StepTraceAnnotation

    return StepTraceAnnotation(name, step_num=step)


class PhaseSpans:
    """Consecutive :func:`span`s of one function's phases, one open at a
    time: ``enter(phase)`` closes the open one and opens
    ``<prefix><phase>``; ``enter(None)`` / ``close()`` close the last.
    For a long function that marks its phases at boundaries instead of
    nesting them in ``with`` blocks (the router's step)."""

    def __init__(self, prefix: str):
        self._prefix = prefix
        self._open = None

    def enter(self, phase: Optional[str]) -> None:
        self.close()
        if phase is not None:
            self._open = span(self._prefix + phase)
            self._open.__enter__()

    def close(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


# ---------------------------------------------------------------------
# Device scopes: which part of the program an instruction belongs to.
#
# ``jax.named_scope`` puts a name on the ``op_name`` path of every
# operation traced under it, and the compiler keeps that path as the
# instruction's metadata.  A profiler trace names an event by its
# instruction (``fusion.379``) and ``jax.profiler.ProfileData`` shows no
# metadata, so the scope of an event comes from the compiled program's
# text, which only the process that compiled it can produce.  Nothing
# here runs on a step or a request: a scope is entered while a program
# is TRACED, and a program is registered once, when it is first compiled.

_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_CALLED = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_PATH_ELEMENT = re.compile(r"[^/()]+")
_LOCATION = re.compile(r'loc\("((?:[^"\\]|\\.)*)"')
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")


def innermost_scope(op_name: str, declared: Iterable[str]) -> Optional[str]:
    """The last element of an ``op_name`` path that is a declared scope.
    ``transpose(jvp(..))``, ``jit(..)``, ``checkpoint``,
    ``rematted_computation``, ``while/body`` and flax's module names are
    elements to pass over: ``jit(step)/loss_and_grad/transpose(jvp(Model))
    /layers/attn/attn_proj/q_proj/dot_general`` is ``attn_proj``."""
    on_path = _scopes_on(op_name, declared)
    return on_path[-1] if on_path else None


def _scopes_on(path: str, declared: Iterable[str]) -> List[str]:
    """The declared scopes among a path's elements, outermost first."""
    return [e for e in _PATH_ELEMENT.findall(path) if e in declared]


@dataclasses.dataclass(frozen=True)
class ProgramTable:
    """One compiled program: ``scope_of`` maps each instruction name of
    its optimized HLO to the innermost declared scope on the
    instruction's ``op_name`` path (None: unscoped).  ``missing`` are
    scopes the program's trace-time code entered that the text does not
    hold: an executable from a compile cache that ANOTHER tree filled
    carries that tree's metadata (jax's cache key leaves it out), and
    then no reading may be made from this table (``complete``)."""

    label: str
    module: str
    scope_of: Dict[str, Optional[str]]
    missing: Tuple[str, ...] = ()

    @property
    def complete(self) -> bool:
        return not self.missing


def parse_program(label: str, compiled_text: str, declared: Iterable[str],
                  traced_text: str = "") -> ProgramTable:
    """The table of one program from its optimized HLO text.

    An instruction takes the scope of its OWN ``metadata={op_name=..}``
    (a fusion's is the one the compiler gave the fusion).  One without
    an ``op_name`` was made by the compiler, and is placed by structure,
    never by a guess: a fusion or call takes the scope its called
    computation's instructions agree on; an instruction that only moves
    data (memory-space assignment's ``copy-done`` / ``slice-done`` and
    the bitcasts behind them) takes the scope its consumers agree on,
    being their wait.  Where they disagree it stays unscoped.
    ``traced_text`` is the lowered module with locations
    (``Lowered.as_text(debug_info=True)``): the scopes this tree's code
    entered when the program was traced."""
    declared = frozenset(declared)
    module = _MODULE.match(compiled_text)
    scope_of: Dict[str, Optional[str]] = {}
    unnamed: List[str] = []                  # instructions with no op_name
    called: Dict[str, str] = {}              # instruction -> computation
    members: Dict[str, List[str]] = {}       # computation -> its named ones
    users: Dict[str, List[str]] = {}         # instruction -> consumers
    present: Set[str] = set()
    computation = None
    for line in compiled_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m or computation is None:
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        if op:
            on_path = _scopes_on(op.group(1), declared)
            scope_of[name] = on_path[-1] if on_path else None
            present.update(on_path)
            members.setdefault(computation, []).append(name)
        else:
            scope_of[name] = None
            unnamed.append(name)
        callee = _CALLED.search(rest)
        if callee:
            called[name] = callee.group(1)
        body = rest.split(", metadata=", 1)[0]
        for operand in _OPERAND.findall(body.split("(", 1)[-1]):
            users.setdefault(operand, []).append(name)

    def agreed(names: Iterable[str]) -> Optional[str]:
        scopes = {scope_of.get(n) for n in names}
        return scopes.pop() if len(scopes) == 1 else None

    for name in unnamed:
        inside = members.get(called.get(name))
        if inside:
            scope_of[name] = agreed(inside)
    # consumers' scope, through chains of unnamed instructions: a few
    # passes reach a fixed point (copy-start -> copy-done -> bitcast)
    for _ in range(4):
        for name in unnamed:
            if scope_of[name] is None and name in users:
                scope_of[name] = agreed(users[name])

    # a location is an operation's name-stack path (``jit(f)/scope/mul``)
    # or a frame of the traceback behind it, named by its FUNCTION
    # (``loc("prefill"(..))``): only a path says a scope was entered
    traced = set()
    for loc in _LOCATION.findall(traced_text):
        if "/" in loc:
            traced.update(_scopes_on(loc, declared))
    return ProgramTable(label=label, module=module.group(1) if module else "",
                        scope_of=scope_of,
                        missing=tuple(sorted(traced - present)))


def abstract(tree: Any) -> Any:
    """``tree`` with a ``jax.ShapeDtypeStruct`` (shape, dtype, weak type
    and, of an array COMMITTED to its devices, its sharding) in place of
    every array: what a program is lowered over again without its
    buffers, to the executable it already ran with.  An uncommitted
    array (``jnp.zeros``, a jit's result from such) gets no sharding:
    one would pin the argument and make another program of it."""
    import jax

    def one(x):
        sharding = x.sharding if getattr(x, "committed", False) else None
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding,
            weak_type=bool(getattr(x, "weak_type", False)))

    return jax.tree_util.tree_map(one, tree)


def program_texts(jitted: Any, *args: Any) -> Tuple[str, str]:
    """``(lowered text with locations, optimized HLO text)`` of a jitted
    function over (abstract) arguments.  The compile is the one the
    program already ran with: a hit in the process's own or in the
    persistent compile cache."""
    lowered = jitted.lower(*args)
    return lowered.as_text(debug_info=True), lowered.compile().as_text()


class ProgramRegistry:
    """The declared device scopes of a process and its registered
    hot-path programs.  ``register`` keeps a THUNK that yields
    :func:`program_texts`' pair over abstract arguments: one dictionary
    entry a compiled program until somebody asks for :meth:`tables`."""

    def __init__(self):
        self._lock = threading.Lock()
        self.scopes: Set[str] = set()
        self._thunks: Dict[str, Callable[[], Tuple[str, str]]] = {}
        self._tables: Dict[str, Optional[ProgramTable]] = {}

    def register(self, label: str,
                 thunk: Callable[[], Tuple[str, str]]) -> None:
        with self._lock:
            self._thunks[label] = thunk
            self._tables.pop(label, None)

    def labels(self) -> List[str]:
        with self._lock:
            return sorted(self._thunks)

    def tables(self) -> Dict[str, ProgramTable]:
        """label -> :class:`ProgramTable`, each thunk evaluated once.  A
        program whose text cannot be made any more is left out (logged)."""
        with self._lock:
            wanted = list(self._thunks)
            todo = {l: self._thunks[l] for l in wanted
                    if l not in self._tables}
        for label, thunk in todo.items():
            try:
                traced, compiled = thunk()
                table = parse_program(label, compiled, self.scopes, traced)
            except Exception:
                logger.exception("no compiled text for program %s", label)
                table = None
            with self._lock:
                self._tables.setdefault(label, table)
        with self._lock:
            return {l: self._tables[l] for l in wanted
                    if self._tables.get(l) is not None}


_PROGRAMS = ProgramRegistry()


def device_scope(name: str):
    """The device-side sibling of :func:`span`: ``jax.named_scope(name)``,
    and ``name`` joins the process's declared scopes, which is what lets
    :func:`program_scopes` tell a scope from the other elements of an
    ``op_name`` path.  Entered while a program is traced, never on a
    step.  An unnamed ``pallas_call`` takes the name of the innermost
    scope around it and the benchmark finds kernels by that name: wrap
    what is around a kernel call, never the call."""
    import jax

    _PROGRAMS.scopes.add(name)
    return jax.named_scope(name)


def device_scoped(name: str):
    """Decorator: the whole call of a function under one
    :func:`device_scope` (the device-side sibling of :func:`spanned`)."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with device_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def register_program(label: str,
                     thunk: Callable[[], Tuple[str, str]]) -> None:
    """Make a compiled hot-path program known to :func:`program_scopes`
    (call it once, when the program is first compiled; see
    :class:`ProgramRegistry`)."""
    _PROGRAMS.register(label, thunk)


def program_scopes() -> Dict[str, ProgramTable]:
    """The tables of the process's registered programs, their texts
    compiled on demand, once."""
    return _PROGRAMS.tables()


def name_os_thread(name: str) -> None:
    """Give the calling thread ``name`` at the OS too (Linux
    ``PR_SET_NAME``, 15 bytes): the profiler labels a thread's line in
    the trace by its OS name, and Python sets none (every line reads
    ``python``).  Call it first thing on the thread."""
    import ctypes

    try:
        ctypes.CDLL(None).prctl(15, name.encode()[:15], 0, 0, 0)
    except (OSError, AttributeError):
        pass  # no prctl on this platform: the line keeps its default


def escape_label_value(value: str) -> str:
    """Escape a label value per the Prometheus text exposition format:
    backslash, double-quote and newline must be escaped or a value
    containing them corrupts every sample after it on the scrape."""
    return (
        str(value)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def render_prometheus(
    metrics: Dict[str, float],
    labels: Optional[Dict[str, str]] = None,
    help_map: Optional[Dict[str, str]] = None,
) -> str:
    """Prometheus text exposition format.  Names present in ``help_map``
    (usually :data:`~dlrover_tpu.utils.metric_registry.METRIC_HELP`) get
    a ``# HELP`` comment so the registry's documentation reaches every
    scraper."""
    label_str = ""
    if labels:
        inner = ",".join(
            f'{k}="{escape_label_value(v)}"'
            for k, v in sorted(labels.items())
        )
        label_str = "{" + inner + "}"
    lines = []
    for name in sorted(metrics):
        if help_map and name in help_map:
            lines.append(f"# HELP {name} {help_map[name]}")
        lines.append(f"{name}{label_str} {metrics[name]}")
    return "\n".join(lines) + "\n"


class MetricsExporter:
    """Serves ``/metrics`` (Prometheus text) + ``/healthz`` on a local
    port (per-process, like xpu_timer's per-rank exporter ports).  With
    a tracer attached (:meth:`attach_tracer`) it also serves the
    request-trace debugging views: ``/traces`` (recent finished span
    trees + flight-recorder dumps, JSON) and ``/traces/slowest``
    (ranked by duration — where the tail latency lives)."""

    def __init__(self, port: int = 0, labels: Optional[Dict[str, str]] = None):
        self._labels = labels or {}
        self._sources = []  # callables returning Dict[str, float]
        self._text_sources = []  # callables returning Prometheus text
        self._tracer = None  # utils/tracing.Tracer, via attach_tracer
        self._tenants = None  # tenancy.TenantRegistry, attach_tenants
        self._profiler = None  # contprof.ContinuousProfiler
        # a failing source must be VISIBLE: silently dropping it makes
        # a dashboard go quietly stale (satellite of ISSUE 4) — each
        # failure counts into dlrover_metrics_source_errors_total and
        # logs once per source (not once per scrape: a broken source on
        # a 15s scrape cadence must not flood the log).  Guarded by a
        # lock: ThreadingHTTPServer serves concurrent scrapes, and an
        # unguarded += here would under-count (and double-log) when two
        # scrapers race
        self._error_lock = threading.Lock()
        self._source_errors = 0
        self._sources_logged = set()
        exporter = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 — http.server API
                if self.path.startswith("/healthz"):
                    body = b"ok"
                    ctype = "text/plain"
                elif self.path.startswith("/metrics"):
                    body = exporter._render_metrics().encode()
                    ctype = "text/plain; version=0.0.4"
                elif self.path.startswith("/traces"):
                    payload = exporter._render_traces(self.path)
                    if payload is None:
                        self.send_response(404)
                        self.end_headers()
                        return
                    body = payload.encode()
                    ctype = "application/json"
                elif self.path.startswith("/tenants"):
                    payload = exporter._render_tenants(self.path)
                    if payload is None:
                        self.send_response(404)
                        self.end_headers()
                        return
                    body = payload.encode()
                    ctype = "application/json"
                elif self.path.startswith("/debug/prof"):
                    rendered = exporter._render_prof(self.path)
                    if rendered is None:
                        self.send_response(404)
                        self.end_headers()
                        return
                    payload, ctype = rendered
                    body = payload.encode()
                else:
                    self.send_response(404)
                    self.end_headers()
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):  # silence per-request logging
                pass

        self._server = http.server.ThreadingHTTPServer(("127.0.0.1", port), Handler)
        self.port = self._server.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def add_source(self, fn) -> None:
        """``fn() -> Dict[str, float]`` merged into /metrics at scrape time."""
        self._sources.append(fn)

    def add_text_source(self, fn) -> None:
        """``fn() -> str`` of ready-made Prometheus text appended at
        scrape time (e.g. ``RouterMetrics.render_histograms``)."""
        self._text_sources.append(fn)

    def attach_tracer(self, tracer) -> None:
        """Wire a :class:`~dlrover_tpu.utils.tracing.Tracer`: enables
        ``/traces`` + ``/traces/slowest`` + ``/traces/autoscale`` +
        ``/traces/chrome`` and merges the tracer's
        ``serving_request_trace_*`` gauges into ``/metrics``."""
        self._tracer = tracer
        self.add_source(tracer.metrics)

    def attach_router(self, router) -> None:
        """One-call wiring for a ServingRouter: gauges + OpenMetrics
        latency histograms (with trace-exemplar drill-down) on
        ``/metrics``, span traces on ``/traces*``, and — when the
        router carries an SLO engine — the per-band
        ``serving_slo_*`` families."""
        self.add_source(router.metrics.metrics)
        self.add_text_source(router.metrics.render_histograms)
        self.add_text_source(router.metrics.render_labeled)
        slo = getattr(router, "slo", None)
        if slo is not None:
            self.add_text_source(slo.render)
        self.attach_tracer(router.tracer)
        tenants = getattr(getattr(router, "gateway", None),
                          "tenants", None)
        if tenants is not None:
            self.attach_tenants(tenants)

    def attach_profiler(self, prof) -> None:
        """Wire a :class:`~dlrover_tpu.utils.contprof.ContinuousProfiler`:
        its scalar gauges (and phase self-time samples, when phases are
        marked) join ``/metrics``, and the live flame state is served
        at ``/debug/prof`` (JSON snapshot; ``?ref=prof-N`` resolves an
        incident capture) and ``/debug/prof/collapsed`` (flamegraph.pl
        collapsed-stack text)."""
        self._profiler = prof
        self.add_source(prof.metrics)
        self.add_text_source(prof.render_phases)

    def _render_prof(self, path: str):
        if self._profiler is None:
            return None
        import urllib.parse

        split = urllib.parse.urlsplit(path)
        if split.path.startswith("/debug/prof/collapsed"):
            return self._profiler.collapsed(), "text/plain"
        if split.path not in ("/debug/prof", "/debug/prof/"):
            return None
        query = urllib.parse.parse_qs(split.query)
        ref = (query.get("ref") or [None])[0]
        if ref is not None:
            snap = self._profiler.resolve_ref(ref)
            if snap is None:
                return None  # unknown/evicted incident ref -> 404
        else:
            snap = self._profiler.snapshot()
        return json.dumps(snap, sort_keys=True), "application/json"

    def attach_tenants(self, registry) -> None:
        """Wire a tenancy ``TenantRegistry``: enables the
        ``/tenants/usage`` JSON view (per-RAW-tenant-id admission /
        refusal / shed / generated-token books).  Raw ids belong here —
        an on-demand JSON document bounded by the registered set — and
        never on Prometheus label values (DL010)."""
        self._tenants = registry

    def _render_tenants(self, path: str) -> Optional[str]:
        if self._tenants is None:
            return None
        import urllib.parse

        sub = urllib.parse.urlsplit(path).path
        if sub not in ("/tenants", "/tenants/", "/tenants/usage"):
            return None
        return json.dumps(
            {"tenants": self._tenants.usage_snapshot()},
            indent=2, sort_keys=True)

    # ---------------------------------------------------------- render
    def _note_source_error(self, src) -> None:
        key = getattr(src, "__qualname__", None) or repr(src)
        with self._error_lock:
            self._source_errors += 1
            first = key not in self._sources_logged
            self._sources_logged.add(key)
        if first:
            logger.warning(
                "metrics source %s failed; its series are missing from "
                "/metrics (logged once; see "
                "dlrover_metrics_source_errors_total)", key,
                exc_info=True)

    def _render_metrics(self) -> str:
        from dlrover_tpu.utils.metric_registry import METRIC_HELP

        merged: Dict[str, float] = {}
        for src in self._sources:
            try:
                merged.update(src())
            except Exception:
                self._note_source_error(src)
        merged["dlrover_metrics_source_errors_total"] = float(
            self._source_errors)
        body = render_prometheus(
            merged, self._labels, help_map=METRIC_HELP)
        for src in self._text_sources:
            try:
                body += src()
            except Exception:
                self._note_source_error(src)
        return body

    def _render_traces(self, path: str) -> Optional[str]:
        if self._tracer is None:
            return None
        import urllib.parse

        split = urllib.parse.urlsplit(path)
        query = urllib.parse.parse_qs(split.query)

        def q(key):
            return (query.get(key) or [None])[0]

        def q_limit(default: int) -> int:
            # clamp: ?limit= is an operator convenience mid-incident,
            # not a lever for unbounded serialization work
            try:
                return max(1, min(int(q("limit") or default), 500))
            except ValueError:
                return default

        if split.path.startswith("/traces/slowest"):
            return json.dumps({
                "traces": self._tracer.slowest(
                    q_limit(10), name=q("name"), status=q("status")),
            }, default=str)
        if split.path.startswith("/traces/autoscale"):
            # control-plane traces: one per scale decision, active ones
            # included (plan -> spawn -> join spans arrive over seconds)
            return json.dumps({
                "traces": self._tracer.traces_named(
                    "autoscale", limit=q_limit(20)),
            }, default=str)
        if split.path.startswith("/traces/chrome"):
            # perfetto-ready trace-event JSON; ?trace_id= narrows to
            # one request (404 when it is unknown/evicted)
            trace_id = q("trace_id")
            if trace_id is not None \
                    and self._tracer.get_tree(trace_id) is None:
                return None
            return self._tracer.export_chrome_trace(trace_id)
        # /traces with ?name= / ?status= / ?limit= — at a 4096-entry
        # active set the unfiltered dump is unusable mid-incident;
        # "the failover traces, newest 20" is the real question
        return json.dumps({
            "traces": self._tracer.finished(
                q_limit(50), name=q("name"), status=q("status")),
            "flight_dumps": list(self._tracer.recorder.dumps),
        }, default=str)

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True, name="metrics-exporter"
        )
        self._thread.start()

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None
