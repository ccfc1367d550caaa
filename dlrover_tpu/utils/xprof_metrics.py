"""Device time by the program's own scopes, from an XLA trace.

Parity target: xpu_timer (reference atorch/dev/xpu_timer/nvidia/hook.cc
+ README.md:1-40) — an LD_PRELOAD shim that times every CUDA kernel and
NCCL collective and serves the numbers as Prometheus gauges with no user
instrumentation.  The TPU equivalent needs no interposer: XLA's profiler
records every executed instruction with device timestamps.  What this
module adds is the reduction an operator and the benchmark both read:
device SELF time by the scope (``utils/profiler.device_scope``) each
instruction belongs to, program by program.

Two steps, so the arithmetic is tested on plain lists:

- :func:`extract` reads one ``.xplane.pb`` with
  ``jax.profiler.ProfileData`` (nothing but JAX) into, per device, the
  events of its ``XLA Ops`` line and the intervals of its ``XLA Modules``
  line;
- :func:`join` takes each event's self time, assigns it to the program
  that was running, and looks its instruction up in that program's table
  (``utils/profiler.program_scopes``).  :func:`scope_seconds` is the two.

What a v5e capture holds (read by hand with ``perfbench/trace_reduce
.describe`` from captures of ``serve-batch-closed`` and
``train-moe-dropless``, jax 0.9.0; PERF.md section 6, PR 36): one plane a
chip, ``/device:TPU:<n>``.  Its ``XLA Ops`` line has one event an executed
HLO instruction, named by the instruction; control flow (``while``,
``conditional``, ``call``) ENCLOSES its body's events on the same line, so
an instruction's time is its duration less that of the events nested in
it.  Its ``XLA Modules`` line has one event an EXECUTION of a compiled
program, named ``<HLO module name>(<id>)`` — ``jit__train_step(1234..)``,
``jit_chunk_fn(..)`` — spanning that execution's instructions; the id is
the executable's, the same for every execution of one program and
different between two programs that share a module name (the prefill
buckets are all ``jit_insert_fn``).  The id is nowhere in a program's
text, so programs that share a module name are told apart by the
instruction names their executions show (see :func:`join`).  The CPU
backend writes no device plane: its executed instructions are on the
``tf_XLA*`` host lines with nothing saying which program ran, and the
join then goes by instruction name over all registered programs.

:class:`AutoProfiler` owns the operator's every-N-steps capture
(``ElasticTrainer(xprof_every_n_steps=)``): one step runs under a trace
and the reduction becomes Prometheus gauges,
``dlrover_xprof_scope_seconds{scope=..}`` and the collective gauges of
the xpu_timer parity, all self time.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import tempfile
import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.utils.profiler import (
    ProgramTable,
    escape_label_value,
    program_scopes,
)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OTHER = "(other programs)"
SEVERAL = "(several programs)"
CPU_PLANE = "/device:CPU-rehearsal:0"

_HLO_NAME = re.compile(r"^%?([\w.\-]+)\s*=")
# XLA collective instructions as they are named in a device trace
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast|send|recv|psum|ppermute",
    re.IGNORECASE,
)

Event = List[Any]          # [name, start ns, duration ns]


def _instruction(raw: str) -> str:
    """``%fusion.12 = f32[..] fusion(...)`` -> ``fusion.12``."""
    m = _HLO_NAME.match(raw)
    return m.group(1) if m else raw.split("(")[0].strip()[:160]


def newest_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def extract(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """``{plane: {"ops": [Event], "modules": [Event]}}`` of one capture.
    With no TPU plane in it (the CPU backend), the ``tf_XLA*`` host
    lines' instructions stand in as one device with no module line."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    cpu: List[Event] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            rec = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    rec["ops"] = [[_instruction(e.name), float(e.start_ns),
                                   float(e.duration_ns)] for e in line.events]
                elif line.name == MODULES_LINE:
                    rec["modules"] = [[e.name, float(e.start_ns),
                                       float(e.duration_ns)]
                                      for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if line.name.startswith("tf_XLA"):
                    cpu.extend(
                        [_instruction(e.name), float(e.start_ns),
                         float(e.duration_ns)] for e in line.events
                        if e.duration_ns > 0
                        and not e.name.startswith(("end:", "Thread")))
    if not devices and cpu:
        devices[CPU_PLANE] = {"ops": cpu, "modules": []}
    return devices


def _clip(events: List[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append([name, s, e - s])
    return out


def self_times(events: List[Event]) -> List[Event]:
    """``[name, start, SELF ns]`` an event: its duration less that of the
    events nested in it on the line (a ``while`` less its body), so the
    sum over a line is the time the line was busy, counted once."""
    out: List[Event] = []
    stack: List[list] = []            # [index into out, end]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack:
            out[stack[-1][0]][2] -= dur
        stack.append([len(out), start + dur])
        out.append([name, start, dur])
    for ev in out:
        ev[2] = max(0.0, ev[2])
    return out


def _merged_label(tables: List[ProgramTable]) -> str:
    """One label for programs that could not be told apart: what their
    labels share up to a ``.`` and ``*`` (``prefill.*``), else the
    module's name (on the CPU, where instruction names are all there is
    to go by, programs of different modules may share one: ``SEVERAL``)."""
    if len(tables) == 1:
        return tables[0].label
    parts = [t.label.split(".") for t in tables]
    common = []
    for column in zip(*parts):
        if len(set(column)) != 1:
            break
        common.append(column[0])
    if common:
        return ".".join(common + ["*"])
    modules = {t.module for t in tables}
    return modules.pop() if len(modules) == 1 else SEVERAL


class _Program:
    """The registered programs one executable of the trace may be."""

    def __init__(self, tables: List[ProgramTable]):
        self.tables = tables
        self.label = _merged_label(tables)
        self.complete = all(t.complete for t in tables)

    def scope_of(self, instruction: str) -> Optional[str]:
        """The scope every candidate gives the instruction, else None:
        never a guess between programs that disagree."""
        scopes = {t.scope_of.get(instruction) for t in self.tables
                  if instruction in t.scope_of}
        return scopes.pop() if len(scopes) == 1 else None


def _resolve(candidates: List[ProgramTable], seen: Iterable[str]) -> _Program:
    """Which of the programs that share a module name an executable is:
    those whose text holds every instruction its executions showed."""
    seen = set(seen)
    holding = [t for t in candidates if seen <= t.scope_of.keys()]
    return _Program(holding or candidates)


def join(devices: Dict[str, Dict[str, List[Event]]],
         tables: Dict[str, ProgramTable],
         window: Optional[Tuple[float, float]] = None) -> Dict[str, dict]:
    """``{program label: {"executions": n, "scopes": {scope: self
    seconds}, "unscoped": seconds, "unscoped_ops": {instruction:
    seconds}, "complete": bool}}`` plus ``"(other programs)"`` for the
    modules nobody registered (a checkpoint's snapshot, transfers), mean
    over devices, within ``window`` (ns on the trace's clock).

    The sum over everything equals the time the devices were busy.  A
    program whose table is not ``complete`` (its text is another tree's:
    ``ProgramTable``) keeps its seconds, all ``unscoped``, and says so:
    a reader makes no reading from it.  Programs that share a module name
    are told apart by executable (the id in the module event's name) and
    the instructions it showed; where more than one registered program
    fits, an instruction counts under a scope only if all that fit agree
    on it.  With no module line (the CPU backend) the same holds over
    ALL registered programs."""
    by_module: Dict[str, List[ProgramTable]] = {}
    for table in tables.values():
        by_module.setdefault(table.module, []).append(table)
    out: Dict[str, dict] = {}
    n_dev = max(1, len(devices))

    def record(program: Optional[_Program]) -> dict:
        return out.setdefault(program.label if program else OTHER, {
            "executions": 0.0, "scopes": {}, "unscoped": 0.0,
            "unscoped_ops": {},
            "complete": program.complete if program else True})

    def add(program: Optional[_Program], name: str, self_ns: float) -> None:
        rec, sec = record(program), self_ns / 1e9 / n_dev
        scope = program.scope_of(name) \
            if program and program.complete else None
        if scope is None:
            rec["unscoped"] += sec
            rec["unscoped_ops"][name] = \
                rec["unscoped_ops"].get(name, 0.0) + sec
        else:
            rec["scopes"][scope] = rec["scopes"].get(scope, 0.0) + sec

    for plane in sorted(devices):
        ops, modules = devices[plane]["ops"], devices[plane]["modules"]
        if window is not None:
            ops, modules = _clip(ops, *window), _clip(modules, *window)
        events = self_times(ops)
        if not modules:
            # no module line (the CPU backend): by instruction name over
            # every registered program that has one of that name
            holders: Dict[str, Optional[_Program]] = {}
            for name, _, self_ns in events:
                if name not in holders:
                    having = [t for t in tables.values()
                              if name in t.scope_of]
                    holders[name] = _Program(having) if having else None
                add(holders[name], name, self_ns)
            continue
        modules = sorted(modules, key=lambda m: m[1])
        starts = [m[1] for m in modules]
        placed, shown = [], {}
        for name, start, self_ns in events:
            i = bisect.bisect_right(starts, start) - 1
            exe = modules[i][0] if i >= 0 and \
                start < modules[i][1] + modules[i][2] else None
            shown.setdefault(exe, set()).add(name)
            placed.append((exe, name, self_ns))
        programs: Dict[Optional[str], Optional[_Program]] = {}
        for exe in {m[0] for m in modules} | set(shown):
            # ``<module name>(<executable id>)``
            candidates = by_module.get(exe.rsplit("(", 1)[0]) \
                if exe else None
            programs[exe] = _resolve(candidates, shown.get(exe, ())) \
                if candidates else None
        for exe, _, _ in modules:
            record(programs[exe])["executions"] += 1.0 / n_dev
        for exe, name, self_ns in placed:
            add(programs[exe], name, self_ns)
    return out


def total_seconds(programs: Dict[str, dict]) -> float:
    """Everything :func:`join` placed: the devices' busy seconds."""
    return sum(sum(p["scopes"].values()) + p["unscoped"]
               for p in programs.values())


def scope_seconds(xplane_path: str,
                  window: Optional[Tuple[float, float]] = None,
                  tables: Optional[Dict[str, ProgramTable]] = None
                  ) -> Dict[str, dict]:
    """:func:`join` of one capture with the process's registered programs
    (``utils/profiler.program_scopes``: their texts are compiled here, on
    demand — cache hits — unless ``tables`` hands them in)."""
    return join(extract(xplane_path),
                program_scopes() if tables is None else tables, window)


def collective_seconds(devices: Dict[str, Dict[str, List[Event]]]
                       ) -> Dict[str, float]:
    """Self seconds by collective instruction, mean over devices (an async
    pair's ``-start`` and ``-done`` are two instructions: the gap between
    them is overlap, not collective time)."""
    out: Dict[str, float] = {}
    n_dev = max(1, len(devices))
    for rec in devices.values():
        for name, _, self_ns in self_times(rec["ops"]):
            if _COLLECTIVE.search(name):
                out[name] = out.get(name, 0.0) + self_ns / 1e9 / n_dev
    return out


def profile_call(fn: Callable[[], Any], log_dir: Optional[str] = None
                 ) -> Tuple[Any, Optional[Dict[str, Any]]]:
    """Run ``fn`` under a jax.profiler trace; return ``(result,
    breakdown)``: ``{"programs": join's result, "collectives":
    collective_seconds', "device_seconds": the busy seconds}``.

    Failures strictly AFTER ``fn`` executed (reading the trace) yield
    ``(result, None)`` — the caller must NOT re-run ``fn``: with donated
    arguments (the train step donates the state) a second call would
    reuse already-donated buffers and crash.  Only a failure to start
    the trace propagates before ``fn`` runs.
    """
    import jax

    tmp = log_dir or tempfile.mkdtemp(prefix="dlrover_xprof_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            result = fn()
            jax.block_until_ready(result)
        finally:
            try:
                jax.profiler.stop_trace()
            except Exception:
                logger.exception("stopping xprof trace failed")
        try:
            devices = extract(newest_xplane(tmp))
            programs = join(devices, program_scopes())
            breakdown = {"programs": programs,
                         "collectives": collective_seconds(devices),
                         "device_seconds": total_seconds(programs)}
        except Exception:
            logger.exception("xprof trace could not be read; step result "
                             "kept, breakdown skipped")
            breakdown = None
        return result, breakdown
    finally:
        if log_dir is None:
            shutil.rmtree(tmp, ignore_errors=True)


def _sanitize(name: str) -> str:
    return re.sub(r"[^a-zA-Z0-9_.:-]", "_", name)[:120]


class AutoProfiler:
    """Every-N-steps transparent device timing -> Prometheus text lines.

    ``around_step(fn)`` replaces a direct train-step call: on most steps
    it just calls through; every ``every_n``-th step it captures an XLA
    trace of that single step and refreshes the gauge set.  Register
    :meth:`prometheus_text` with ``MetricsExporter.add_text_source``.
    """

    def __init__(self, every_n: int = 100, warmup_steps: int = 2):
        self.every_n = max(1, int(every_n))
        self._warmup = warmup_steps  # never trace compile steps
        self._step = 0
        self._lock = threading.Lock()
        self._breakdown: Optional[Dict[str, Any]] = None
        self._last_profile_time = 0.0
        self.profile_count = 0

    def around_step(self, fn: Callable[[], Any]) -> Any:
        self._step += 1
        due = (
            self._step > self._warmup
            and (self._step - self._warmup) % self.every_n == 0
        )
        if not due:
            return fn()
        try:
            result, breakdown = profile_call(fn)
        except Exception:
            # profile_call only raises BEFORE fn ran (trace start
            # failure) — re-running is safe then, and only then
            logger.exception("xprof trace could not start; step runs "
                             "untraced")
            return fn()
        if breakdown is not None:
            with self._lock:
                self._breakdown = breakdown
                self._last_profile_time = time.time()
                self.profile_count += 1
        return result

    @property
    def breakdown(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            return self._breakdown

    def prometheus_text(self) -> str:
        """Labeled gauges in Prometheus text format (xpu_timer's metric
        surface, README.md:1-40), all device SELF time of the captured
        step: ``dlrover_xprof_scope_seconds{scope=..}`` by the program's
        device scope (``(unscoped)``: instructions of a registered program
        under no scope; ``(other programs)``: everything else that ran)."""
        with self._lock:
            bd = self._breakdown
            ts = self._last_profile_time
            n = self.profile_count
        if bd is None:
            return ""
        lines = [
            f"dlrover_xprof_profiles_total {float(n)}",
            f"dlrover_xprof_last_capture_timestamp {ts}",
            f"dlrover_xprof_device_seconds {bd['device_seconds']}",
            "dlrover_xprof_collective_seconds_total "
            f"{sum(bd['collectives'].values())}",
        ]
        for name, sec in sorted(bd["collectives"].items()):
            lines.append(
                f'dlrover_xprof_collective_seconds{{op="{_sanitize(name)}"}} '
                f"{sec}")
        by_scope: Dict[str, float] = {}
        for label, rec in bd["programs"].items():
            for scope, sec in rec["scopes"].items():
                by_scope[scope] = by_scope.get(scope, 0.0) + sec
            key = OTHER if label == OTHER else "(unscoped)"
            by_scope[key] = by_scope.get(key, 0.0) + rec["unscoped"]
        for scope, sec in sorted(by_scope.items()):
            lines.append(
                "dlrover_xprof_scope_seconds"
                f'{{scope="{escape_label_value(scope)}"}} {sec}')
        return "\n".join(lines) + "\n"
