#!/usr/bin/env python3
"""Cut a short piece out of a capture (an ``.xplane.pb`` of a traced
benchmark run) and keep what ``perfbench/program_spans.py`` reads of it as
a test recording:

    python3 perfbench/tools/record_spans.py <xplane.pb> <out.spans.json> \
        [--offset-s 0.0] [--seconds 0.5]

The piece starts ``offset`` seconds into the ``bench.window`` span.  Kept:
the device's outermost ``XLA Ops`` events (enough for busy and idle time:
events nested in a ``while`` are dropped), the ``bench.*`` spans, and the
program's ``dlrover.*`` spans per thread with their attributes, unclipped
where they reach over the piece's edges.  The numbers under ``expect`` are
what the module gave when the recording was made;
``perfbench/tests/test_program_spans.py`` holds later versions to them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import program_spans as ps  # noqa: E402
from perfbench import trace_reduce as tr  # noqa: E402


def _outermost(events):
    out, end = [], float("-inf")
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        if s >= end:
            out.append([name, s, d])
            end = s + d
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("xplane")
    p.add_argument("out")
    p.add_argument("--offset-s", type=float, default=0.0)
    p.add_argument("--seconds", type=float, default=0.5)
    p.add_argument("--note", default="")
    args = p.parse_args(argv)
    ev = tr.extract(args.xplane)
    lo = tr.window_of(ev)[0] + args.offset_s * 1e9
    hi = lo + args.seconds * 1e9
    cut = {
        "devices": {k: _outermost([[n, s - lo, d] for n, s, d
                                   in tr._clip(v, lo, hi)])
                    for k, v in ev["devices"].items()},
        "host": [["bench.window", 0.0, hi - lo]] + [
            [n, s - lo, d] for n, s, d in tr._clip(
                [h for h in ev["host"] if h[0] != "bench.window"], lo, hi)],
    }
    threads = {}
    for line, spans in ps.host_threads(args.xplane).items():
        kept = [[n, s - lo, d, a] for n, s, d, a in spans
                if s < hi and s + d > lo]
        if kept:
            threads[line] = kept
    parsed = ps.from_events(cut, {k: [tuple(x) for x in v]
                                  for k, v in threads.items()})
    rec = {"note": args.note, "events": cut, "threads": threads, "expect": {
        "window_s": ps.window_s(parsed),
        "idle_s": sum(b - a for a, b in parsed["idle"][0]) / 1e9,
        "totals": ps.totals(parsed),
        "idle_under": {n: ps.idle_under(parsed, n) for n in ps.totals(parsed)},
        "idle_by_innermost": ps.idle_by_innermost(parsed)}}
    with open(args.out, "w") as f:
        json.dump(rec, f, separators=(",", ":"))
    print(f"{args.out}: {sum(len(v) for v in cut['devices'].values())} device "
          f"events, {sum(len(v) for v in threads.values())} program spans, "
          f"{os.path.getsize(args.out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
