"""The program's spans out of a trace (``perfbench/program_spans.py``):
self time, idle time under a span, the clip to ``bench.window``, on
hand-made events whose answer is known and on a small recording from the
chip (``data/*.spans.json``, written by ``tools/record_spans.py`` on a TPU
v5 lite capture of ``train-flashsave``); and every reader built on them
reporting nothing where there is nothing to read."""

import glob
import importlib.util
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import program_spans as ps  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
READERS = ["ckpt.d2h_s_per_save", "ckpt.shm_copy_s_per_save",
           "ckpt.idle_under_commit_ms_per_save", "trainer.dispatch_ms",
           "engine.prefill_share.closed", "engine.host_self_share.closed",
           "router.self_ms_per_step.closed"]


def _parsed():
    # device busy 100-400 and 700-900 of a 1000 ns window; a step span with
    # a dispatch and a sync inside on the main thread, a commit on the
    # writer that began before the window and ends after it
    ev = {"devices": {"/device:TPU:0": [["while.1", 100.0, 300.0],
                                        ["fusion.2", 150.0, 50.0],
                                        ["fusion.9", 700.0, 200.0]]},
          "host": [["bench.window", 0.0, 1000.0]]}
    threads = {
        "main#0": [("dlrover.trainer.step", 50.0, 600.0, {"step_num": 7}),
                   ("dlrover.trainer.dispatch", 60.0, 100.0, {}),
                   ("dlrover.x.sync", 400.0, 200.0, {}),
                   ("dlrover.trainer.step", 900.0, 500.0, {"step_num": 8})],
        "ckpt-writer-0#1": [("dlrover.ckpt.commit", -500.0, 2000.0,
                             {"step": 6}),
                            ("dlrover.ckpt.d2h_wait", 300.0, 400.0, {})],
        "other#2": [("dlrover.late", 5000.0, 10.0, {})],
    }
    return ps.from_events(ev, threads)


def test_spans_are_clipped_to_the_window_and_self_time_excludes_children():
    p = _parsed()
    assert ps.window_s(p) == pytest.approx(1000e-9)
    assert set(p["threads"]) == {"main#0", "ckpt-writer-0#1"}   # none late
    t = ps.totals(p)
    assert t["dlrover.ckpt.commit"] == {
        "seconds": pytest.approx(1000e-9), "count": 1,
        "self_seconds": pytest.approx(600e-9)}
    # the second step is cut at the window's end: 100 of its 500 ns
    assert t["dlrover.trainer.step"]["seconds"] == pytest.approx(700e-9)
    assert t["dlrover.trainer.step"]["count"] == 2
    assert t["dlrover.trainer.step"]["self_seconds"] == pytest.approx(
        (600 - 100 - 200 + 100) * 1e-9)
    assert t["dlrover.trainer.dispatch"]["seconds"] \
        + t["dlrover.x.sync"]["seconds"] == pytest.approx(300e-9)
    assert [a["step_num"] for _, _, _, a in
            ps.named(p, "dlrover.trainer.step")] == [7, 8]
    assert ps.median_ms(p, "dlrover.trainer.step") == pytest.approx(
        350e-6)
    assert ps.median_ms(p, "dlrover.not_there") is None


def test_idle_time_falls_under_the_spans_open_on_any_thread():
    p = _parsed()
    # idle: 0-100, 400-700, 900-1000
    assert p["idle"] == [[(0.0, 100.0), (400.0, 700.0), (900.0, 1000.0)]]
    assert ps.idle_under(p, "dlrover.ckpt.commit") == pytest.approx(500e-9)
    assert ps.idle_under(p, "dlrover.ckpt.d2h_wait") == pytest.approx(300e-9)
    assert ps.idle_under(p, "dlrover.trainer.step") == pytest.approx(
        (50 + 250 + 100) * 1e-9)
    assert ps.idle_under(p, "dlrover.trainer.dispatch") == pytest.approx(
        40e-9)
    assert ps.idle_under(p, "dlrover.not_there") == 0.0
    by = ps.idle_by_innermost(p)
    assert by["main#0"] == {
        "dlrover.trainer.step": pytest.approx((10 + 50 + 100) * 1e-9),
        "dlrover.trainer.dispatch": pytest.approx(40e-9),
        "dlrover.x.sync": pytest.approx(200e-9),
        ps.NO_SPAN: pytest.approx((50 + 50) * 1e-9)}
    assert by["ckpt-writer-0#1"] == {
        "dlrover.ckpt.commit": pytest.approx(200e-9),
        "dlrover.ckpt.d2h_wait": pytest.approx(300e-9),
        ps.NO_SPAN: pytest.approx(0.0)}
    for table in by.values():     # every thread accounts for all of it
        assert sum(table.values()) == pytest.approx(500e-9)
    assert ps.innermost_at(p, 500.0) == {
        "main#0": "dlrover.x.sync", "ckpt-writer-0#1": "dlrover.ckpt.d2h_wait"}
    assert ps.innermost_at(p, 20.0)["main#0"] == ps.NO_SPAN
    assert "dlrover.ckpt.d2h_wait" in ps.report(p)


def test_idle_is_the_mean_over_devices():
    ev = {"devices": {"/device:TPU:0": [["a", 0.0, 1000.0]],
                      "/device:TPU:1": [["a", 0.0, 600.0]]},
          "host": [["bench.window", 0.0, 1000.0]]}
    p = ps.from_events(ev, {"main#0": [("dlrover.s", 500.0, 500.0, {})]})
    assert ps.idle_under(p, "dlrover.s") == pytest.approx(200e-9)
    assert ps.idle_by_innermost(p)["main#0"] == {
        "dlrover.s": pytest.approx(200e-9), ps.NO_SPAN: pytest.approx(0.0)}


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(DATA, "*.spans.json"))) or [None])
def test_recorded_chip_trace_gives_its_recorded_numbers(path):
    if path is None:
        pytest.skip("no recorded spans in perfbench/tests/data")
    with open(path) as f:
        rec = json.load(f)
    p = ps.from_events(rec["events"], {k: [tuple(x) for x in v]
                                       for k, v in rec["threads"].items()})
    want = rec["expect"]
    assert ps.window_s(p) == pytest.approx(want["window_s"], rel=1e-9)
    idle_s = sum(b - a for a, b in p["idle"][0]) / 1e9
    assert idle_s == pytest.approx(want["idle_s"], rel=1e-9)
    totals = ps.totals(p)
    assert set(totals) == set(want["totals"])
    for name, rec_t in want["totals"].items():
        assert totals[name] == pytest.approx(rec_t, rel=1e-9, abs=1e-12)
        assert ps.idle_under(p, name) == pytest.approx(
            want["idle_under"][name], rel=1e-9, abs=1e-12)
        assert 0 <= totals[name]["self_seconds"] <= totals[name]["seconds"]
        assert ps.idle_under(p, name) <= min(idle_s, totals[name]["seconds"])
    by = ps.idle_by_innermost(p)
    for line, table in want["idle_by_innermost"].items():
        assert by[line] == pytest.approx(table, rel=1e-9, abs=1e-12)
        assert sum(by[line].values()) == pytest.approx(idle_s, rel=1e-9)
    # spans reaching over the piece's edges were clipped to it
    lo, hi = p["window"]
    assert all(lo <= s and s + d <= hi + 1e-6
               for spans in p["threads"].values() for _, s, d, _ in spans)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(os.path.dirname(HERE), "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(parsed, counters=None, monkeypatch=None):
    """A driver's result whose trace parses to ``parsed``."""
    monkeypatch.setattr(ps, "load", lambda path, cpu_rehearsal=False: parsed)
    return {"counters": counters or {},
            "trace": {"xplane": "recorded.xplane.pb",
                      "devices": [{"plane": "/device:TPU:0"}]}}


@pytest.mark.parametrize("name", READERS)
def test_reader_reports_nothing_without_spans_or_counters(name, monkeypatch):
    """An untraced run, and the trace and counters of a program that has
    no such span or counter (the parent of the PR that added them)."""
    reader = _reader(name)
    assert reader.read({"counters": {}, "trace": None}) is None
    bare = ps.from_events(
        {"devices": {"/device:TPU:0": [["fusion.1", 0.0, 10.0]]},
         "host": [["bench.window", 0.0, 100.0]]}, {})
    old_counters = {"ckpt.saves_committed_total": 2.0,
                    "ckpt.commit_seconds_total": 20.0}
    assert reader.read(_run(bare, old_counters, monkeypatch)) is None


def test_readers_on_the_hand_made_trace(monkeypatch):
    run = _run(_parsed(), {"ckpt.saves_committed_total": 2.0,
                           "ckpt.d2h_seconds_total": 17.0,
                           "ckpt.shm_copy_seconds_total": 3.0}, monkeypatch)
    assert _reader("ckpt.d2h_s_per_save").read(run) == 8.5
    assert _reader("ckpt.shm_copy_s_per_save").read(run) == 1.5
    assert _reader("ckpt.idle_under_commit_ms_per_save").read(run) \
        == pytest.approx(500e-6)
    assert _reader("trainer.dispatch_ms").read(run) == pytest.approx(100e-6)


def test_dispatch_median_leaves_out_the_steps_with_a_save_due(monkeypatch):
    ev = {"devices": {"/device:TPU:0": [["a", 0.0, 4000.0]]},
          "host": [["bench.window", 0.0, 4000.0]]}
    spans = []
    for i, dispatch in enumerate([10.0, 12.0, 900.0, 14.0]):
        t = 1000.0 * i
        spans += [("dlrover.trainer.step", t, 950.0, {"step_num": i}),
                  ("dlrover.trainer.dispatch", t + 5, dispatch, {}),
                  ("dlrover.trainer.maybe_save", t + 960, 20.0,
                   {"due": int(i == 2), "tier": "MEMORY"})]
    run = _run(ps.from_events(ev, {"main#0": spans}), monkeypatch=monkeypatch)
    assert _reader("trainer.dispatch_ms").read(run) == pytest.approx(12e-6)


def test_serving_readers_on_a_hand_made_trace(monkeypatch):
    ev = {"devices": {"/device:TPU:0": [["a", 0.0, 1000.0]]},
          "host": [["bench.window", 0.0, 1000.0]]}
    spans = []
    for t in (0.0, 500.0):
        spans += [("dlrover.router.step", t, 400.0, {}),
                  ("dlrover.router.phase.pump", t + 20, 370.0, {}),
                  ("dlrover.router.pump", t + 25, 360.0, {"replica": "r"}),
                  ("dlrover.engine.step", t + 30, 350.0, {}),
                  ("dlrover.engine.admit", t + 30, 110.0, {}),
                  ("dlrover.engine.prefill", t + 35, 100.0, {"bucket": 64}),
                  ("dlrover.engine.prefill_chunk", t + 140, 50.0, {}),
                  ("dlrover.engine.decode_chunk", t + 200, 150.0, {}),
                  ("dlrover.engine.deliver", t + 350, 20.0, {})]
    run = _run(ps.from_events(ev, {"main#0": spans}), monkeypatch=monkeypatch)
    assert _reader("engine.prefill_share.closed").read(run) \
        == pytest.approx(100.0 * 300 / 1000)
    assert _reader("engine.host_self_share.closed").read(run) \
        == pytest.approx(100.0 * (700 - 600) / 1000)
    assert _reader("router.self_ms_per_step.closed").read(run) \
        == pytest.approx((800 - 700) / 2 * 1e-6)
