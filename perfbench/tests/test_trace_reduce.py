"""The reduction from trace events to busy/idle seconds, self time per
operation and named idle gaps: on hand-made events whose answer is known,
and on a small recording from the chip (``data/*.events.json``, written by
``trace_reduce.extract`` on a TPU v5 lite capture of a benchmark cell)."""

import glob
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import trace_reduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _ev():
    # one device: a while op around two body ops, then a late fusion;
    # host: a step span holding a sync span, then a sleep
    return {
        "devices": {"/device:TPU:0": [
            ["while.1", 100.0, 800.0], ["fusion.1", 150.0, 100.0],
            ["custom-call.2", 300.0, 200.0], ["all-gather.3", 600.0, 100.0],
            ["fusion.9", 20000.0, 1000.0]]},
        "host": [["bench.window", 0.0, 30000.0],
                 ["bench.train_step", 50.0, 10000.0],
                 ["bench.sync", 5000.0, 4000.0],
                 ["bench.idle_wait", 10050.0, 9000.0]],
    }


def test_busy_is_the_union_and_ops_are_self_time():
    r = tr.reduce_events(_ev())
    assert r["window_s"] == pytest.approx(30000e-9)
    assert r["busy_s"] == pytest.approx(1800e-9)        # 800 + 1000, not 2200
    assert r["ops"]["while.1"][0] == pytest.approx(400e-9)   # 800 - 400 nested
    assert r["ops"]["custom-call.2"] == [pytest.approx(200e-9), 1]
    assert sum(v[0] for v in r["ops"].values()) == pytest.approx(r["busy_s"])
    assert tr.idle_share(r) == pytest.approx(100 * (1 - 1800 / 30000))
    assert tr.op_seconds(r, tr.COLLECTIVE) == pytest.approx(100e-9)
    assert tr.op_seconds(r, tr.CUSTOM_CALL) == pytest.approx(200e-9)


def test_idle_gaps_are_shared_out_among_innermost_host_spans():
    r = tr.reduce_events(_ev())
    gaps = r["idle_gaps"]
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    # 900..20000 idle: step alone 900-5000 and 9000-10050, sync 5000-9000,
    # idle_wait 10050-19050, nothing 19050-20000 and 21000-30000
    assert gaps["bench.sync"] == pytest.approx(4000e-9)
    assert gaps["bench.idle_wait"] == pytest.approx(9000e-9)
    assert gaps["bench.train_step"] == pytest.approx((4100 + 1050) * 1e-9)
    assert gaps["host:unattributed"] == pytest.approx((950 + 9000) * 1e-9)
    # the 100 ns before the first operation is under SHORT_GAP_NS: the
    # device stepping between operations, nobody's fault
    assert gaps["device:between-ops"] == pytest.approx(100e-9)
    b = tr.breakdown(r)
    assert b["device_ops"][0] == ["fusion.9", pytest.approx(1000e-9)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_window_clips_events_and_means_over_devices():
    ev = _ev()
    ev["devices"]["/device:TPU:1"] = [["fusion.1", 0.0, 500.0]]
    r = tr.reduce_events(ev, window=(0.0, 1000.0))
    assert [d["busy_s"] for d in r["devices"]] == [
        pytest.approx(800e-9), pytest.approx(500e-9)]
    assert r["busy_s"] == pytest.approx(650e-9)
    assert r["ops"]["fusion.1"][0] == pytest.approx((100 + 500) / 2 * 1e-9)


def test_op_name_cuts_hlo_text():
    assert tr.op_name("%fusion.12 = f32[8]{0} fusion(%p0), kind=kLoop") \
        == "fusion.12"
    assert tr.op_name("custom-call.3") == "custom-call.3"


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(DATA, "*.events.json"))) or [None])
def test_recorded_chip_trace_reduces_to_its_recorded_numbers(path):
    if path is None:
        pytest.skip("no recorded trace in perfbench/tests/data")
    with open(path) as f:
        rec = json.load(f)
    r = tr.reduce_events(rec["events"])
    want = rec["expect"]
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    # self times add up to the busy time of the device (no double count)
    assert sum(v[0] for v in r["ops"].values()) == pytest.approx(
        r["busy_s"], rel=1e-6)
    assert sum(r["idle_gaps"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    for name, sec in want["ops"].items():
        assert r["ops"][name][0] == pytest.approx(sec, rel=1e-9)
    assert tr.op_seconds(r, tr.CUSTOM_CALL) == pytest.approx(
        want["custom_call_s"], rel=1e-9)


def test_flash_roofline_reader_counts_calls_before_it_reports():
    import importlib.util

    path = os.path.join(os.path.dirname(DATA), "..", "layer_metrics",
                        "kernel.flash_attn_roofline.py")
    spec = importlib.util.spec_from_file_location("flash_reader", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    shapes = {"seq": 4096, "rows": 4, "heads": 32, "head_dim": 128,
              "layers": 2, "remat": True}

    def run(names, chips=4, steps=3):
        # every kernel call lasts 2 ms on every chip
        ops = {n: [2e-3 * 2 * steps, 2 * steps * chips] for n in names}
        return {"trace": {"ops": ops, "host_spans": {
            "bench.train_step": [1.0, steps]}}, "shapes": shapes,
            "chips": chips, "device_kind": "TPU v5 lite"}

    mesh = ["shard_map.476", "shard_map.477", "shard_map.478",
            "shard_map.479"]
    share = reader.read(run(mesh))
    # 9 matmul units of 4096^2 * 32 * 128 FLOPs a layer, 8 ms of kernel
    assert share == pytest.approx(
        100 * 9 * 4096**2 * 32 * 128 / 197e12 / 8e-3)
    assert reader.read(run(["attn.38", "attn.39", "attn.40", "attn.41"],
                           chips=1) | {"shapes": dict(shapes, rows=1)}) \
        == pytest.approx(share)
    assert reader.read(run(mesh[:3])) is None      # a kernel went missing
    assert reader.read(run(["fusion.1"] * 1)) is None
