#!/usr/bin/env python3
"""Run one cell several times, one process a run, and say how the runs
spread: what a builder uses to set a bound and to see a cold first run.

    python3 perfbench/tools/sets.py --workload <cell> --seeds 11,12,13 \
        [--seconds S] [--trace 0|1] [--label NAME] [--describe]

Every run's result line (and the ``perfbench detail`` line before it) is
appended to ``chiprun_out/perfbench/<label>.jsonl``.  The spread printed
per metric is the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.  This
process never imports JAX: a parent that touched it would hold the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def spread(values):
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", default=None)
    p.add_argument("--trace", default="0")
    p.add_argument("--label", default=None)
    p.add_argument("--describe", action="store_true",
                   help="after a traced run, write what the trace holds "
                        "(planes, lines, commonest events) beside the log")
    args = p.parse_args(argv)
    assert "jax" not in sys.modules
    label = args.label or f"{args.workload}.trace{args.trace}"
    out_dir = os.path.join(ROOT, "chiprun_out", "perfbench")
    os.makedirs(out_dir, exist_ok=True)
    log = os.path.join(out_dir, label + ".jsonl")
    values = {}
    failures = 0
    for seed in [s for s in args.seeds.split(",") if s]:
        cmd = [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
               "--workload", args.workload, "--seed", seed,
               "--trace", args.trace]
        if args.seconds is not None:
            cmd += ["--seconds", args.seconds]
        t0 = time.time()
        # the child writes straight into files under chiprun_out/, so a run
        # that hangs or is cut still leaves what it said
        base = os.path.join(out_dir, f"{label}.seed{seed}")
        with open(base + ".out", "w") as so, open(base + ".err", "w") as se:
            proc = subprocess.run(cmd, cwd=ROOT, stdout=so, stderr=se)
        wall = time.time() - t0
        with open(base + ".out") as f:
            stdout = f.read()
        with open(base + ".err") as f:
            stderr = f.read()
        lines = [ln for ln in stdout.splitlines() if ln.strip()]
        detail = next((ln[len("perfbench detail "):] for ln in lines
                       if ln.startswith("perfbench detail ")), "null")
        record = {"seed": seed, "rc": proc.returncode, "wall_s": wall,
                  "detail": json.loads(detail)}
        try:
            record["result"] = json.loads(lines[-1]) if lines else None
        except ValueError:
            record["result"] = None
        if proc.returncode != 0 or not record["result"]:
            failures += 1
            record["stderr_tail"] = stderr[-3000:]
            record["stdout_tail"] = stdout[-1500:]
        with open(log, "a") as f:
            f.write(json.dumps(record) + "\n")
        res = record["result"] or {}
        print(f"seed {seed} rc {proc.returncode} wall {wall:.1f}s "
              f"correct {res.get('correct')} "
              + " ".join(f"{k}={v['value']:.6g}"
                         for k, v in res.get("metrics", {}).items()),
              flush=True)
        if record.get("stderr_tail"):
            print(record["stderr_tail"], flush=True)
        for k, v in res.get("metrics", {}).items():
            values.setdefault(k, []).append(v["value"])
        if args.describe and args.trace == "1":
            code = ("import sys, json; sys.path.insert(0, %r); "
                    "from perfbench import trace_reduce as t; "
                    "p = t.newest_xplane(%r); "
                    "json.dump(t.extract(p), open(%r, 'w')); "
                    "print(t.describe(p))" % (
                        ROOT, os.path.join(ROOT, ".perfbench_work",
                                           args.workload, "trace"),
                        os.path.join(out_dir, label + ".events.json")))
            d = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                               stdout=subprocess.PIPE, text=True,
                               env=dict(os.environ, JAX_PLATFORMS="cpu"))
            with open(os.path.join(out_dir, label + ".trace.txt"), "w") as f:
                f.write(d.stdout)
    for k, vs in values.items():
        sp = spread(vs)
        print(f"{k}: n={len(vs)} median={statistics.median(vs):.6g} "
              f"min={min(vs):.6g} max={max(vs):.6g} "
              f"iqr/median={'n/a' if sp is None else f'{sp:.4%}'}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
