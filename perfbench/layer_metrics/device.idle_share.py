"""Share of the traced window in which no operation ran on the device
(1 - union of device-op intervals / window), mean over the chips used.

One body for every ``device.idle_share.<suffix>``: the suffix only says
which end-to-end metric the entry in BENCHMARK.json ``moves``."""

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"


def read(run):
    from perfbench.trace_reduce import idle_share

    return idle_share(run.get("trace"))
