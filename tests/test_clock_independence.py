"""A harness on a synthetic clock must not read the machine's.

``time.monotonic()`` is the seconds since boot, so a harness that steps
``t = 1000.0`` by hand and leaves ``now=`` out of one call puts that
call 1 000 s away from the rest — before or after, as the uptime has
it.  Each scenario below is an existing test, run through its own
harness with ``time.monotonic`` started at 0 s (a machine just booted:
a bare join's heartbeat lies in the synthetic clock's past, and the
reaper takes the replica) and at 1 000 000 s (up for days: the
heartbeat lies in its future, and nothing is ever reaped).  The result
must be the same."""

import time

import pytest

import test_fleet_chaos as fleet_chaos
import test_serving_chaos as serving_chaos
import test_slo as slo
from test_fleet_chaos import _isolate  # noqa: F401  (shm hygiene, autouse)


def _fleet_cycle(tmp_path):
    f = fleet_chaos._Fleet(tmp_path)
    try:
        fleet_chaos.test_borrow_and_return_full_cycle_zero_lost(f)
    finally:
        f.close()


def _slo_burn(tmp_path):
    slo.test_burn_rate_drives_scale_up_where_queue_depth_would_not()


def _cancel_on_expiry(tmp_path):
    serving_chaos. \
        test_cancel_inflight_on_expiry_local_engine_reclaims_slot()


@pytest.mark.parametrize("origin", [0.0, 1_000_000.0])
@pytest.mark.parametrize(
    "scenario", [_fleet_cycle, _slo_burn, _cancel_on_expiry])
def test_result_does_not_depend_on_uptime(scenario, origin, tmp_path,
                                          monkeypatch):
    real = time.monotonic
    start = real()
    monkeypatch.setattr(time, "monotonic",
                        lambda: real() - start + origin)
    scenario(tmp_path)
