"""The generator replays byte-identically, and seeds change order and not
work."""

import itertools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import loadgen  # noqa: E402
from perfbench.harness import load_json  # noqa: E402

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")


def _closed():
    return load_json(os.path.join(TRAFFIC, "batch-closed-16.json"))


def _first(traffic, seed, count):
    return list(itertools.islice(loadgen.schedule(traffic, seed), count))


def test_schedule_replays_byte_identically():
    t = _closed()
    big = 2**31 + 12345          # the driver's seeds pass 32 signed bits
    a = loadgen.schedule_bytes(t, big)
    b = loadgen.schedule_bytes(t, big)
    assert a == b and len(a) > 1000
    assert a != loadgen.schedule_bytes(t, big + 1)


def test_seed_changes_order_not_work():
    t = _closed()
    n = int(t["cycle"])
    runs = [_first(t, seed, 2 * n) for seed in (1, 2, 2**31 + 7)]
    for part in (slice(0, n), slice(n, 2 * n)):     # cycle by cycle
        work = [sorted((d.prompt_len, d.output_len) for d in r[part])
                for r in runs]
        assert work[0] == work[1] == work[2] == sorted(loadgen.cycle_draws(t))
    assert [d.prompt_len for d in runs[0]] != [d.prompt_len for d in runs[1]]
    assert [d.index for d in runs[2]] == list(range(2 * n))


def test_lengths_stay_inside_the_file_bounds():
    t = _closed()
    pairs = loadgen.cycle_draws(t)
    assert len(pairs) == t["cycle"]
    assert all(t["prompt_len"]["min"] <= p <= t["prompt_len"]["max"]
               and t["output_len"]["min"] <= o <= t["output_len"]["max"]
               for p, o in pairs)
    # the reference check wants one long prompt in every cycle
    assert any(p >= t["check_long_prompt"] for p, _ in pairs)


def test_prompt_content_is_a_function_of_the_draw():
    d = next(loadgen.schedule(_closed(), 9))
    a, b = loadgen.prompt_tokens(d, 32768), loadgen.prompt_tokens(d, 32768)
    assert (a == b).all() and a.size == d.prompt_len
    assert 0 <= a.min() and a.max() < 32768


def test_the_books_follow_deliveries_and_endings():
    """The serve driver's record of a request: tokens delivered so far,
    and out of ``live`` once the router says the request has ended."""
    from perfbench.drivers.serve import _Live, _stamp

    class Req:
        output, state = [], "Running"

    req = Req()
    rec = _Live(next(loadgen.schedule(_closed(), 3)), req)
    live, finished = {1: rec}, []
    _stamp(live, finished)
    assert rec.seen == 0 and live and not finished
    req.output = [7, 8, 9]
    _stamp(live, finished)
    assert rec.seen == 3 and live and not finished
    req.state = "Done"
    _stamp(live, finished)
    assert finished == [rec] and not live
