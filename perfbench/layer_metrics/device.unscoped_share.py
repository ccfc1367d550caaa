"""Percent of the device's busy time in the traced window that the program's
own scopes do not name: instructions of a registered program under no scope
(``unscoped``) and everything that ran outside the registered programs
(``(other programs)``: uploads, a checkpoint's snapshot).  The instrument's
own coverage (``perfbench/device_scopes.py``).

One body for every ``device.unscoped_share.<suffix>``: the suffix only says
which end-to-end metric the entry in BENCHMARK.json ``moves``."""

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"


def read(run):
    from perfbench.device_scopes import unscoped_share

    return unscoped_share(run)
