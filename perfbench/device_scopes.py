"""Device time by the program's own scopes, for the per-layer readers.

The program says which scope each instruction of its compiled hot-path
programs belongs to (``dlrover_tpu/utils/profiler.device_scope``,
``program_scopes``) and reduces a capture by it
(``dlrover_tpu/utils/xprof_metrics.scope_seconds``: self time, program by
program).  This module hands that reduction to the readers under
``perfbench/layer_metrics/``:

- :func:`of_run` reduces a traced run's capture once a process, over the
  window ``program_spans`` reads (``bench.window``), and returns nothing
  where the program has no such reduction (the parent of the PR that
  added it), where the run traced nothing, or where the reduction's total
  is not the device's busy time (``trace_reduce``'s ``busy_s``) to 0.5 %;
- :func:`ms_per_step` and :func:`ms_per_execution` are what a reader
  calls: the self time under some scopes of one program over the traced
  steps or over that program's executions.  A program whose text is not
  this tree's (``ProgramTable.complete``) or that did not run in the
  window gives nothing;
- run as a module INSIDE a process that has the programs registered it
  prints the table PERF.md section 5 quotes (program x scope, ms an
  execution, share of busy time): ``report(of_run(run))``; from a shell,
  ``python -m perfbench.device_scopes <xplane.pb> <label>=<hlo text>...``
  takes the programs' optimized HLO texts as files and every scope named
  ``--scopes a,b,c``.
"""

from __future__ import annotations

import sys
import time
import traceback
from typing import Dict, Iterable, Optional

UNSCOPED = "(unscoped)"
TOLERANCE = 0.005        # the reduction's total against busy_s

_reduced: Dict[str, Optional[dict]] = {}     # xplane path -> reduction


def of_run(run: dict) -> Optional[dict]:
    """``{"programs": scope_seconds' result, "busy_s", "rehearsal"}`` of a
    benchmark run, or None (see the module's docstring)."""
    from perfbench import program_spans as ps

    trace = run.get("trace")
    parsed = ps.of_run(run)
    if parsed is None:
        return None
    path = trace["xplane"]
    if path not in _reduced:
        try:
            _reduced[path] = _reduce(path, parsed["window"], trace)
        except Exception:
            # a reader that cannot read reports nothing: the run's other
            # metrics and its result line do not depend on this one
            traceback.print_exc()
            _reduced[path] = None
    return _reduced[path]


def _reduce(path: str, window, trace: dict) -> Optional[dict]:
    try:
        from dlrover_tpu.utils.profiler import program_scopes
        from dlrover_tpu.utils.xprof_metrics import (
            scope_seconds, total_seconds)
    except ImportError:
        return None              # a program without the reduction
    from dlrover_tpu.utils.compile_cache import cache_counts

    # what a traced run pays after its window: the registered programs'
    # texts (lowered again over shapes; the compiles must be cache hits)
    # and the reduction itself
    t0, before = time.perf_counter(), cache_counts()
    tables = program_scopes()
    t1 = time.perf_counter()
    programs = scope_seconds(path, window, tables)
    cost = {"program_scopes_s": t1 - t0,
            "reduction_s": time.perf_counter() - t1,
            "programs": len(tables), "cache_before": before,
            "cache_after": cache_counts()}
    rehearsal = any(d["plane"].startswith("/device:CPU-rehearsal")
                    for d in trace["devices"])
    total, busy = total_seconds(programs), trace["busy_s"]
    # on the CPU the op lines of several threads overlap: no device, no
    # invariant (and never a number: a rehearsal reports names only)
    if not rehearsal and abs(total - busy) > TOLERANCE * busy:
        return None
    return {"programs": programs, "busy_s": busy if not rehearsal else total,
            "rehearsal": rehearsal, "cost": cost}


def _matching(reduced: dict, program: str) -> list:
    """The records of ``program`` and of ``program.<variant>``."""
    return [rec for label, rec in reduced["programs"].items()
            if label == program or label.startswith(program + ".")]


def scope_ms(run: dict, program: str, scopes: Iterable[str]
             ) -> Optional[tuple]:
    """``(milliseconds under the scopes, executions)`` of one program in
    the traced window, mean over devices."""
    reduced = of_run(run)
    if reduced is None:
        return None
    records = _matching(reduced, program)
    if not records or not all(r["complete"] for r in records):
        return None
    scopes = set(scopes)
    seconds = sum(sec for r in records for scope, sec in r["scopes"].items()
                  if scope in scopes)
    if seconds <= 0:
        return None
    return seconds * 1e3, sum(r["executions"] for r in records)


def ms_per_step(run: dict, scopes: Iterable[str]) -> Optional[float]:
    """Of the train step: milliseconds a traced step (``bench.train_step``
    spans, as the kernel readers count them)."""
    trace = run.get("trace") or {}
    steps = trace.get("host_spans", {}).get("bench.train_step", [0, 0])[1]
    found = scope_ms(run, "train_step", scopes)
    if not found or not steps:
        return None
    return found[0] / steps


def ms_per_execution(run: dict, program: str, scopes: Iterable[str],
                     span: str, per_execution: int = 1) -> Optional[float]:
    """Of a serving program: milliseconds an execution (``per_execution``
    > 1: a forward of a chunk of that many).  Executions are the
    program's module events in the window; a capture without them (the
    CPU backend) counts the engine's own waits, ``span`` with attributes
    (``serving/engine.py _read_results``)."""
    from perfbench import program_spans as ps

    found = scope_ms(run, program, scopes)
    if not found:
        return None
    ms, executions = found
    if not executions:
        executions = sum(1 for _, _, _, attrs
                         in ps.named(ps.of_run(run), span) if attrs)
    if not executions:
        return None
    return ms / executions / per_execution


def unscoped_share(run: dict) -> Optional[float]:
    """Percent of the device's busy time under no scope of a registered
    program, or in programs nobody registered."""
    reduced = of_run(run)
    if reduced is None or reduced["busy_s"] <= 0:
        return None
    if not all(r["complete"] for r in reduced["programs"].values()):
        return None
    unscoped = sum(r["unscoped"] for r in reduced["programs"].values())
    return 100.0 * unscoped / reduced["busy_s"]


def report(reduced: dict, steps: int = 0, top: int = 12) -> str:
    """Program x scope: ms in the window, ms an execution (a step where
    ``steps`` is given), share of busy time; then the unscoped
    instructions that took most."""
    busy = reduced["busy_s"]
    lines = [f"busy {busy * 1e3:.2f} ms"
             + (" (CPU rehearsal: no device time)"
                if reduced["rehearsal"] else "")]

    def total(rec):
        return sum(rec["scopes"].values()) + rec["unscoped"]

    for label, rec in sorted(reduced["programs"].items(),
                             key=lambda kv: -total(kv[1])):
        n = steps or rec["executions"] or 1
        lines.append(
            f"{label}: {rec['executions']:.1f} executions, "
            f"{total(rec) * 1e3:.2f} ms, {100 * total(rec) / busy:.2f} %"
            + ("" if rec["complete"] else "  TEXT NOT THIS TREE'S"))
        rows = sorted(rec["scopes"].items(), key=lambda kv: -kv[1])
        if rec["unscoped"]:
            rows.append((UNSCOPED, rec["unscoped"]))
        for scope, sec in rows:
            lines.append(f"    {scope:18s} {sec * 1e3:10.3f} ms "
                         f"{sec * 1e3 / n:10.3f} ms/exec "
                         f"{100 * sec / busy:6.2f} %")
        for name, sec in sorted(rec["unscoped_ops"].items(),
                                key=lambda kv: -kv[1])[:top]:
            if sec > 0.0005 * busy:
                lines.append(f"        unscoped {name:34s} "
                             f"{sec * 1e3:9.3f} ms {100 * sec / busy:6.2f} %")
    return "\n".join(lines)


def main(argv) -> int:
    """``<xplane.pb> [--scopes a,b,..] <label>=<optimized HLO text file>..``"""
    from dlrover_tpu.utils.profiler import parse_program
    from dlrover_tpu.utils.xprof_metrics import (
        extract, join, total_seconds)
    from perfbench import trace_reduce as tr

    path, scopes, files, tables = argv[0], set(), {}, {}
    args = list(argv[1:])
    while args:
        arg = args.pop(0)
        if arg == "--scopes":
            scopes = set(args.pop(0).split(","))
        else:
            label, _, file = arg.partition("=")
            files[label] = file
    for label, file in files.items():
        with open(file) as f:
            tables[label] = parse_program(label, f.read(), scopes)
    events = tr.extract(path)
    window = tr.window_of(events)
    programs = join(extract(path), tables, window)
    print(report({"programs": programs, "rehearsal": False,
                  "busy_s": total_seconds(programs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
