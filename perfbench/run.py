#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its one result line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run: load, warm up (set-up), measure for
``--seconds``, check the outputs against ``perfbench/reference.py``, print
as the LAST line of standard output

    {"correct": .., "attempted": .., "failed": .., "metrics": {..}, "device": {..}}

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (and ``breakdown``).  Without a TPU holding the chips
the cell asks for the run fails and prints no result: no CPU number is
ever written under a device metric's name.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by the name in ``BENCHMARK.json``:
``perfbench/configs/<config>.json`` (the entry's ``file``),
``perfbench/traffic/<traffic>.json`` (its ``driver`` names
``perfbench/drivers/<driver>.py``) and
``perfbench/layer_metrics/<metric>.py`` (or, for ``<metric>.<suffix>``
with no file of its own, ``<metric>.py``: one quantity that moves
different end-to-end metrics in different cells has one reader, and its
``moves`` is the entry's).  A new cell needs new files and entries, and
no edit here.

``--rehearse`` runs the same code at the tiny sizes of the files'
``rehearse`` blocks on whatever backend is there (the CPU), to find
wrong paths and arguments at no chip time.  It prints which metrics it
could compute, never their values, and is not a benchmark run.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path.pop(0)          # perfbench's files are not top-level modules
sys.path.insert(0, ROOT)


def load_module(kind: str, name: str):
    """``perfbench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"perfbench: no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(metric: str):
    """The reader of a per-layer metric: its own file, else the file of
    the name without its last ``.<suffix>``."""
    stem = metric.rsplit(".", 1)[0]
    own = os.path.join(HERE, "layer_metrics", metric + ".py")
    return load_module("layer_metrics",
                       metric if os.path.exists(own) else stem)


def metrics_of(bench: dict, group: str, cell: str) -> list:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def main(argv=None) -> int:
    # a run that outlives this says where it stands, on standard error,
    # and goes on: a hang on the chip is otherwise invisible
    faulthandler.dump_traceback_later(300, repeat=True, file=sys.stderr)
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)

    from perfbench.harness import (
        WORK, CompileCounter, Context, Profiler, device_report, load_json,
        merged)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"perfbench: no cell {args.workload!r} in "
                         f"BENCHMARK.json (cells: {sorted(cells)})")
    cell = cells[args.workload]
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, config_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    if args.rehearse:
        config = merged(config, config.get("rehearse"))
        traffic = merged(traffic, traffic.get("rehearse"))
    seconds = float(args.seconds if args.seconds is not None
                    else bench["run_seconds"])

    import jax

    from dlrover_tpu.utils.compile_cache import cache_counts, \
        ensure_compile_cache

    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        print(f"perfbench: JAX found {devices[0].platform!r}, not a TPU; "
              "a benchmark run needs the chip (--rehearse is the CPU path)",
              file=sys.stderr)
        return 3
    if len(devices) < cell["chips"]:
        print(f"perfbench: cell {cell['name']} needs {cell['chips']} "
              f"chip(s), JAX found {len(devices)}", file=sys.stderr)
        return 3
    devices = devices[:cell["chips"]]
    ensure_compile_cache()     # <checkout>/.jax_cache unless the env names one
    ctx = Context(
        cell=cell, config=config, traffic=traffic, seed=args.seed,
        seconds=seconds, trace=bool(args.trace), rehearse=args.rehearse,
        t_start=T_START, devices=devices,
        profiler=Profiler(os.path.join(WORK, cell["name"], "trace"),
                          cpu_rehearsal=args.rehearse),
        compiles=CompileCounter())

    driver = load_module("drivers", traffic["driver"])
    ctx.say(f"{cell['name']}: driver {traffic['driver']} starts")
    run = driver.run(ctx)
    ctx.say("driver done")
    run["cache_counts"] = cache_counts()
    run["device_kind"] = devices[0].device_kind
    run["chips"] = len(devices)
    if run.get("compiles_in_window"):
        run["correct"] = False      # a program was not warm: not steady state
        run["checks"]["compiles_in_window"] = run["compiles_in_window"]

    if args.trace:
        wanted = metrics_of(bench, "per_layer", cell["name"])
        values = {}
        for m in wanted:
            reader = load_reader(m["name"])
            v = reader.read(run)
            if v is not None:
                values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        wanted = metrics_of(bench, "end_to_end", cell["name"])
        values = {m["name"]: {"value": float(run["end_to_end"][m["name"]]),
                              "unit": m["unit"]} for m in wanted}

    detail = {k: run.get(k) for k in ("checks", "setup", "counters",
                                      "window_s", "profiler_s",
                                      "cache_counts")}
    print("perfbench detail " + json.dumps(detail, default=str), flush=True)
    if args.rehearse:
        print(json.dumps({
            "rehearsal": True, "correct": bool(run["correct"]),
            "attempted": run["attempted"], "failed": run["failed"],
            "reported": sorted(values),
            "missing": sorted(m["name"] for m in wanted
                              if m["name"] not in values),
            "device": {"platform": devices[0].platform}}))
        return 0 if run["correct"] else 1
    line = {"correct": bool(run["correct"]), "attempted": run["attempted"],
            "failed": run["failed"], "metrics": values,
            "device": device_report(devices, run.get("trace")
                                    if args.trace else None)}
    if args.trace and run.get("trace"):
        from perfbench.trace_reduce import breakdown

        line["breakdown"] = breakdown(run["trace"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
