"""The program's own host spans out of a profiler trace, and the device's
idle time under them.

``dlrover_tpu`` writes its spans with ``jax.profiler.TraceAnnotation``
(``utils/profiler.span``; names ``dlrover.<layer>.<what>``), so they are in
the ``.xplane.pb`` of whoever opened the profiler, on the clock of the
device's ``XLA Ops`` line, one line per host thread, with their
attributes as the event's stats.  ``trace_reduce`` reads the benchmark's
own ``bench.*`` spans; this module reads the program's, per thread:

- :func:`load` parses one ``.xplane.pb`` once per process: the
  ``dlrover.*`` events of every host thread clipped to ``bench.window``,
  and the device's idle intervals (the complement of the busy intervals
  as ``trace_reduce`` unions them), per device;
- :func:`totals` gives seconds, count and SELF seconds (duration less the
  same-thread spans nested in it) per span name;
- :func:`idle_under` gives the device-idle seconds that fall under spans of
  one name, on whichever thread they are;
- run as a module on an ``.xplane.pb`` it prints where the device's idle
  time falls, by the innermost program span open on each thread, and the
  longest gaps with what every thread was in: the table PERF.md quotes.

A program without such spans (the parent of the PR that added them) gives
empty tables, and every reader built on them reports nothing.
"""

from __future__ import annotations

import functools
import statistics
import sys
from typing import Dict, List, Optional, Tuple

from perfbench import trace_reduce as tr

PREFIX = "dlrover."
NO_SPAN = "(no program span)"

Span = Tuple[str, float, float, dict]      # name, start ns, duration ns, attrs


def host_threads(path: str) -> Dict[str, List[Span]]:
    """The ``dlrover.*`` events of every host thread line of a capture,
    as written (unclipped)."""
    import jax

    threads: Dict[str, List[Span]] = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            spans = [(e.name, float(e.start_ns), float(e.duration_ns),
                      {k: v for k, v in e.stats}) for e in line.events
                     if e.name.startswith(PREFIX)]
            if spans:
                # a thread's OS name need not be unique ("python")
                threads[f"{line.name}#{i}"] = spans
    return threads


@functools.lru_cache(maxsize=None)      # a capture is parsed once a process
def load(path: str, cpu_rehearsal: bool = False) -> dict:
    """``{"window": (lo, hi), "threads": {line: [Span, ...]}, "idle":
    [[(start, end), ...] per device]}``, times in ns on the trace's clock."""
    return from_events(tr.extract(path, cpu_rehearsal=cpu_rehearsal),
                       host_threads(path))


def from_events(ev: dict, threads: Dict[str, List[Span]]) -> dict:
    """The same from plain lists (``trace_reduce.extract``'s and the
    program spans per thread): what the tests feed."""
    lo, hi = tr.window_of(ev)
    idle = []
    for plane in sorted(ev["devices"]):
        busy = tr._union([(s, s + d) for _, s, d
                          in tr._clip(ev["devices"][plane], lo, hi)])
        edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
        idle.append([(a, b) for a, b in zip(edges[0::2], edges[1::2])
                     if b > a])
    clipped = {}
    for line, spans in threads.items():
        kept = [(n, max(s, lo), min(s + d, hi) - max(s, lo), a)
                for n, s, d, a in spans if min(s + d, hi) > max(s, lo)]
        if kept:
            clipped[line] = sorted(kept, key=lambda x: (x[1], -x[2]))
    return {"window": (lo, hi), "threads": clipped, "idle": idle}


def of_run(run: dict) -> Optional[dict]:
    """The parsed trace of a benchmark run (None if it traced nothing)."""
    trace = run.get("trace")
    if not trace or not trace.get("xplane"):
        return None
    rehearsal = any(d["plane"].startswith("/device:CPU-rehearsal")
                    for d in trace["devices"])
    return load(trace["xplane"], cpu_rehearsal=rehearsal)


def window_s(parsed: dict) -> float:
    lo, hi = parsed["window"]
    return (hi - lo) / 1e9


def named(parsed: dict, name: str) -> List[Tuple[str, float, float, dict]]:
    """``(thread, start, duration, attrs)`` of every span of that name."""
    return [(line, s, d, a) for line, spans in parsed["threads"].items()
            for n, s, d, a in spans if n == name]


def totals(parsed: dict) -> Dict[str, Dict[str, float]]:
    """name -> ``{"seconds", "count", "self_seconds"}`` over all threads."""
    out: Dict[str, Dict[str, float]] = {}

    def close(stack, upto):
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out[name]["self_seconds"] += max(0.0, self_ns) / 1e9

    for spans in parsed["threads"].values():
        stack: List[list] = []          # [name, end, self ns]
        for name, start, dur, _ in spans:
            close(stack, start)
            rec = out.setdefault(
                name, {"seconds": 0.0, "count": 0, "self_seconds": 0.0})
            rec["seconds"] += dur / 1e9
            rec["count"] += 1
            if stack:
                stack[-1][2] -= dur
            stack.append([name, start + dur, dur])
        close(stack, float("inf"))
    return out


def _overlap(intervals: List[Tuple[float, float]],
             gaps: List[Tuple[float, float]]) -> float:
    """ns of ``gaps`` covered by the union of ``intervals``."""
    total, j = 0.0, 0
    for s, e in tr._union(intervals):
        while j < len(gaps) and gaps[j][1] <= s:
            j += 1
        k = j
        while k < len(gaps) and gaps[k][0] < e:
            total += min(e, gaps[k][1]) - max(s, gaps[k][0])
            k += 1
    return total


def idle_under(parsed: dict, *names: str) -> float:
    """Device-idle seconds (mean over devices) while a span of one of
    those names was open on any thread."""
    open_ = [(s, s + d) for name in names
             for _, s, d, _ in named(parsed, name)]
    if not open_ or not parsed["idle"]:
        return 0.0
    return sum(_overlap(open_, gaps) for gaps in parsed["idle"]) \
        / len(parsed["idle"]) / 1e9


def median_ms(parsed: dict, name: str, keep=None) -> Optional[float]:
    """Median duration of the spans of that name ``keep`` admits."""
    durs = [d for line, s, d, a in named(parsed, name)
            if keep is None or keep(line, s, d, a)]
    return statistics.median(durs) / 1e6 if durs else None


def idle_by_innermost(parsed: dict) -> Dict[str, Dict[str, float]]:
    """thread -> innermost program span -> device-idle seconds under it
    (mean over devices); time with no span open is ``NO_SPAN``."""
    n_dev = max(1, len(parsed["idle"]))
    idle_s = sum(b - a for gaps in parsed["idle"] for a, b in gaps) \
        / n_dev / 1e9
    out: Dict[str, Dict[str, float]] = {}
    for line, spans in parsed["threads"].items():
        table: Dict[str, float] = {}
        segments = tr._innermost_segments([[n, s, d] for n, s, d, _ in spans])
        by_name: Dict[str, list] = {}
        for s, e, n in segments:
            by_name.setdefault(n, []).append((s, e))
        for n, ivs in by_name.items():
            sec = sum(_overlap(ivs, gaps) for gaps in parsed["idle"]) \
                / n_dev / 1e9
            if sec > 0:
                table[n] = sec
        table[NO_SPAN] = max(0.0, idle_s - sum(table.values()))
        out[line] = table
    return out


def innermost_at(parsed: dict, t: float) -> Dict[str, str]:
    """thread -> the innermost program span open at time ``t``."""
    out = {}
    for line, spans in parsed["threads"].items():
        inside = [(d, n) for n, s, d, _ in spans if s <= t < s + d]
        out[line] = min(inside)[1] if inside else NO_SPAN
    return out


def report(parsed: dict, top: int = 5) -> str:
    lines = [f"window {window_s(parsed):.3f} s, "
             f"{len(parsed['idle'])} device(s), idle "
             + ", ".join(f"{sum(b - a for a, b in g) / 1e9:.3f} s"
                         for g in parsed["idle"])]
    lines.append("spans: seconds / count / self seconds")
    for name, r in sorted(totals(parsed).items()):
        lines.append(f"  {name:34s} {r['seconds']:10.4f} {r['count']:7d} "
                     f"{r['self_seconds']:10.4f}")
    lines.append("device-idle seconds by innermost program span, per thread")
    for line, table in idle_by_innermost(parsed).items():
        lines.append(f"  thread {line}")
        for name, sec in sorted(table.items(), key=lambda kv: -kv[1]):
            lines.append(f"    {sec:10.4f}  {name}")
    gaps = sorted(((b - a, a) for g in parsed["idle"][:1] for a, b in g),
                  reverse=True)[:top]
    lines.append(f"longest {len(gaps)} gaps of the first device: "
                 "ms, at s into the window, innermost span per thread")
    lo = parsed["window"][0]
    for dur, start in gaps:
        where = innermost_at(parsed, start + dur / 2.0)
        lines.append(f"  {dur / 1e6:10.3f} at {(start - lo) / 1e9:8.3f}  "
                     + "; ".join(f"{k}: {v}" for k, v in where.items()))
    return "\n".join(lines)


if __name__ == "__main__":
    print(report(load(sys.argv[1], cpu_rehearsal="--cpu" in sys.argv[2:])))
