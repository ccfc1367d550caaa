"""Device time of the collective operations (all-gather, reduce-scatter,
all-reduce and their async halves) as a share of the traced window, mean
over the chips.  Total collective time, overlapped or not: the exposed part
needs spans the program does not write yet (PERF.md section 7)."""

LAYER = "strategy"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"


def read(run):
    from perfbench.trace_reduce import COLLECTIVE, op_seconds

    trace = run.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * op_seconds(trace, COLLECTIVE) / trace["window_s"]
