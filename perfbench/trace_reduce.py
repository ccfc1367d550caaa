"""From a profiler trace to numbers: device busy and idle time, time per
operation, and what the host was doing in each idle gap.

Two steps, so the arithmetic can be tested on a small recorded file:

- :func:`extract` reads the ``.xplane.pb`` a ``jax.profiler`` capture
  wrote (``jax.profiler.ProfileData``, nothing but JAX) into plain lists:
  per device plane the events of its ``XLA Ops`` line, and the host
  events this benchmark wrote itself (``jax.profiler.TraceAnnotation``
  with names starting ``bench.``);
- :func:`reduce_events` does the arithmetic on those lists.

What a v5e trace looks like (read by hand from a capture of the
``train-flashsave`` cell, jax 0.9.0; PERF.md section 6, PR 23): one plane
per chip named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event
per executed HLO instruction, named by the instruction (``fusion.12``,
``custom-call.3``, ``all-gather.7``, ``while.1``).  Control-flow
instructions (``while``, ``conditional``, ``call``) ENCLOSE the events of
their bodies on the same line, so a plain sum of durations counts the
body twice: an operation's time here is its *self* time, its duration
less that of the events nested in it.  Host annotations are on the
``/host:CPU`` plane, one line per thread, on the same clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
_HLO_NAME = re.compile(r"^%?([\w.\-]+)\s*=")

# async collectives appear as a -start/-done pair; the pair's device time
# is the two events' own durations (the gap between them is overlap)
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)", re.IGNORECASE)
CUSTOM_CALL = re.compile(r"^custom-call", re.IGNORECASE)
# a gap shorter than this is the device stepping from one operation to
# the next, not the host holding it back
SHORT_GAP_NS = 5_000.0


def op_name(raw: str) -> str:
    """``%fusion.12 = f32[..] fusion(...)`` -> ``fusion.12``."""
    m = _HLO_NAME.match(raw)
    return m.group(1) if m else raw.split("(")[0].strip()[:160]


def newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def extract(path: str, cpu_rehearsal: bool = False) -> dict:
    """Plain lists out of one ``.xplane.pb``.  ``cpu_rehearsal`` reads the
    CPU backend's op lines instead of a device plane, so the same code
    path runs in a rehearsal; its numbers are never device metrics."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, List[list]] = {}
    host: List[list] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [op_name(e.name), float(e.start_ns),
                         float(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if cpu_rehearsal and line.name.startswith("tf_XLA"):
                    evs = devices.setdefault("/device:CPU-rehearsal:0", [])
                    evs.extend(
                        [op_name(e.name), float(e.start_ns),
                         float(e.duration_ns)] for e in line.events
                        if e.duration_ns > 0
                        and not e.name.startswith(("end:", "Thread")))
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
    return {"devices": devices, "host": host}


def describe(path: str, top: int = 40) -> str:
    """What is in a trace, for reading by hand: planes, lines, and the
    commonest event names with their stats' keys."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            seen: Dict[str, list] = {}
            for e in events:
                rec = seen.setdefault(op_name(e.name)[:100], [0, 0.0, None])
                rec[0] += 1
                rec[1] += e.duration_ns
                if rec[2] is None:
                    try:
                        rec[2] = {k: str(v)[:120] for k, v in e.stats}
                    except Exception:
                        rec[2] = {}
            for name, (n, ns, stats) in sorted(
                    seen.items(), key=lambda kv: -kv[1][1])[:top]:
                out.append(f"    {ns / 1e6:10.3f} ms x{n:<6d} {name} {stats}")
    return "\n".join(out)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _self_times(events: List[list]) -> Dict[str, List[float]]:
    """name -> [self seconds, count].  Events nested in another on the
    same line (a ``while`` around its body) take their time out of it."""
    out: Dict[str, List[float]] = {}
    stack: List[list] = []   # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            rec = out.setdefault(name, [0.0, 0])
            rec[0] += max(0.0, self_ns) / 1e9
            rec[1] += 1

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def _innermost_segments(spans: List[list]) -> List[Tuple[float, float, str]]:
    """Nested host spans flattened to disjoint ``(start, end, name)``
    pieces, each named by the innermost span open there."""
    out: List[Tuple[float, float, str]] = []
    stack: List[list] = []   # [name, end]; cursor is where naming resumes
    cursor = 0.0

    def emit(upto: float) -> None:
        nonlocal cursor
        if stack and upto > cursor:
            out.append((cursor, upto, stack[-1][0]))
        cursor = max(cursor, upto)

    for name, start, dur in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            emit(stack[-1][1])
            stack.pop()
        emit(start)
        cursor = max(cursor, start)
        stack.append([name, start + dur])
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def _add(table: Dict[str, float], key: str, value: float) -> None:
    table[key] = table.get(key, 0.0) + value


def _clip(events: List[list], lo: float, hi: float) -> List[list]:
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append([name, s, e - s])
    return out


def window_of(ev: dict, marker: str = "bench.window") -> Tuple[float, float]:
    """The traced window: the benchmark's own ``bench.window`` span where
    it wrote one, else first to last device event."""
    spans = [h for h in ev["host"] if h[0] == marker]
    if spans:
        _, s, d = max(spans, key=lambda h: h[2])
        return s, s + d
    starts = [e[1] for evs in ev["devices"].values() for e in evs]
    ends = [e[1] + e[2] for evs in ev["devices"].values() for e in evs]
    if not starts:
        raise ValueError("the trace holds no device event")
    return min(starts), max(ends)


def reduce_events(ev: dict, window: Optional[Tuple[float, float]] = None
                  ) -> dict:
    """Busy and idle seconds, self time per operation, and the idle gaps
    named by what the host was doing, over ``window`` (ns, trace clock)."""
    lo, hi = window or window_of(ev)
    window_s = (hi - lo) / 1e9
    per_device = []
    ops: Dict[str, List[float]] = {}
    gaps_by_span: Dict[str, float] = {}
    host = _clip([h for h in ev["host"] if h[0] != "bench.window"], lo, hi)
    segments = _innermost_segments(host)
    seg_starts = [seg[0] for seg in segments]
    for plane in sorted(ev["devices"]):
        events = _clip(ev["devices"][plane], lo, hi)
        busy = _union([(s, s + d) for _, s, d in events])
        busy_s = sum(e - s for s, e in busy) / 1e9
        per_device.append({"plane": plane, "busy_s": busy_s,
                           "events": len(events)})
        for name, (sec, n) in _self_times(events).items():
            rec = ops.setdefault(name, [0.0, 0])
            rec[0] += sec
            rec[1] += n
        # idle gaps of THIS device, shared out among the host spans
        # (innermost at each instant) that were open during them
        edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge - gs <= 0:
                continue
            if ge - gs < SHORT_GAP_NS:
                _add(gaps_by_span, "device:between-ops", (ge - gs) / 1e9)
                continue
            covered = 0.0
            i = max(0, bisect.bisect_right(seg_starts, gs) - 1)
            while i < len(segments) and segments[i][0] < ge:
                s, e, name = segments[i]
                c = min(ge, e) - max(gs, s)
                if c > 0:
                    _add(gaps_by_span, name, c / 1e9)
                    covered += c
                i += 1
            if ge - gs - covered > 0:
                _add(gaps_by_span, "host:unattributed",
                     (ge - gs - covered) / 1e9)
    n_dev = max(1, len(per_device))
    host_spans: Dict[str, List[float]] = {}
    for name, _, d in host:
        rec = host_spans.setdefault(name, [0.0, 0])
        rec[0] += d / 1e9
        rec[1] += 1
    return {
        "window_s": window_s,
        "busy_s": sum(d["busy_s"] for d in per_device) / n_dev,
        "devices": per_device,
        # seconds per operation, mean over the devices traced
        "ops": {k: [v[0] / n_dev, v[1]] for k, v in ops.items()},
        "idle_gaps": {k: v / n_dev for k, v in gaps_by_span.items()},
        "host_spans": host_spans,
    }


def op_seconds(trace: dict, pattern: "re.Pattern") -> float:
    """Self seconds (mean over devices) of the operations ``pattern``
    matches at the start of the name."""
    return sum(v[0] for k, v in trace["ops"].items() if pattern.match(k))


def breakdown(trace: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the operations that took most device
    time and the longest idle gaps by what the host was doing."""
    ops = sorted(trace["ops"].items(), key=lambda kv: -kv[1][0])[:top]
    gaps = sorted(trace["idle_gaps"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v[0]] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def idle_share(trace: Optional[dict]) -> Optional[float]:
    """Percent of the traced window with no operation on the device."""
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
