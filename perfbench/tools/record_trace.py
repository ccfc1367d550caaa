#!/usr/bin/env python3
"""Cut a short piece out of an extracted trace (the ``.events.json`` that
``sets.py --describe`` writes on the chip) and keep it, with what
``reduce_events`` makes of it, as a test recording:

    python3 perfbench/tools/record_trace.py <events.json> <out.events.json> \
        [--offset-s 0.0] [--seconds 0.3]

The piece starts ``offset`` seconds into the ``bench.window`` span.  The
numbers under ``expect`` are what the reduction gave when the recording was
made; ``perfbench/tests/test_trace_reduce.py`` holds later versions of the
reduction to them and to the invariants that do not depend on a version
(self times add up to busy time, gaps to idle time).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import trace_reduce as tr  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("events")
    p.add_argument("out")
    p.add_argument("--offset-s", type=float, default=0.0)
    p.add_argument("--seconds", type=float, default=0.3)
    p.add_argument("--note", default="")
    args = p.parse_args(argv)
    with open(args.events) as f:
        ev = json.load(f)
    lo = tr.window_of(ev)[0] + args.offset_s * 1e9
    hi = lo + args.seconds * 1e9
    cut = {
        "devices": {k: [[n, s - lo, d] for n, s, d in tr._clip(v, lo, hi)]
                    for k, v in ev["devices"].items()},
        "host": [["bench.window", 0.0, hi - lo]] + [
            [n, s - lo, d] for n, s, d in tr._clip(
                [h for h in ev["host"] if h[0] != "bench.window"], lo, hi)],
    }
    r = tr.reduce_events(cut)
    top = sorted(r["ops"].items(), key=lambda kv: -kv[1][0])[:8]
    rec = {"note": args.note, "events": cut, "expect": {
        "window_s": r["window_s"], "busy_s": r["busy_s"],
        "ops": {k: v[0] for k, v in top},
        "custom_call_s": tr.op_seconds(r, tr.CUSTOM_CALL)}}
    with open(args.out, "w") as f:
        json.dump(rec, f, separators=(",", ":"))
    print(f"{args.out}: {sum(len(v) for v in cut['devices'].values())} device "
          f"events, {os.path.getsize(args.out)} bytes, busy "
          f"{r['busy_s']:.6f} of {r['window_s']:.6f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
