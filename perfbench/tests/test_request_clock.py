"""The six readers of a request's own clock (``router.queue_wait_ms``,
``engine.prefill_wall_ms``, ``router.first_token_ms``,
``engine.token_gap_ms``, ``engine.token_gap_p90_ms``,
``engine.prompt_cached_share``) on a hand-made trace whose answers are
known, on the trace of a program that writes no such event, and through
``--rehearse`` in the six serving cells.  ISSUE 52 named a seventh,
``engine.slot_wait_ms``: ``per_layer`` may hold 128 entries and held 122,
so the slot wait (0.1-3.3 ms in every cell) stays an attribute of
``dlrover.request.admitted`` and a sum in ``EngineStats``, with no entry."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import program_spans as ps  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
READERS = ["router.queue_wait_ms", "engine.prefill_wall_ms",
           "router.first_token_ms", "engine.token_gap_ms",
           "engine.token_gap_p90_ms", "engine.prompt_cached_share"]
SERVING_CELLS = ["serve-batch-closed", "serve-docqa-sparse",
                 "serve-longctx-decode", "serve-reasoning-linear",
                 "serve-mixed-window", "serve-rag-ssm"]
WINDOW = {"devices": {"/device:TPU:0": [["a", 0.0, 1e6]]},
          "host": [["bench.window", 0.0, 1e6]]}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(os.path.dirname(HERE), "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(spans, monkeypatch):
    parsed = ps.from_events(WINDOW, {"main#0": spans})
    monkeypatch.setattr(ps, "load", lambda path, cpu_rehearsal=False: parsed)
    return {"counters": {}, "trace": {
        "xplane": "made.xplane.pb", "devices": [{"plane": "/device:TPU:0"}]}}


def _events(n):
    """``n`` requests' events, and deliveries of 1 .. 120 gaps."""
    spans = []
    for i in range(n):
        t = 1000.0 * i
        spans += [
            ("dlrover.request.placed", t, 1.0,
             {"rid": i, "erid": i, "replica": "r",
              "queue_wait_ms": 10.0 * (i + 1), "prompt_tokens": 1000,
              "requeues": 0}),
            ("dlrover.request.admitted", t + 10, 1.0,
             {"erid": i, "slot": i, "slot_wait_ms": 1.0 + i,
              "prompt_tokens": 1000, "cached_tokens": 200 * i,
              "chunks": 2}),
            ("dlrover.request.first_token", t + 20, 1.0,
             {"erid": i, "prefill_ms": 100.0 * (i + 1),
              "since_queued_ms": 101.0 * (i + 1), "steps": 2}),
            ("dlrover.request.first_delivery", t + 30, 1.0,
             {"rid": i, "ttft_ms": 111.0 * (i + 1) + 1}),
        ]
    return spans


def _deliver(t, gaps, mean_ms, program="decode_chunk"):
    return ("dlrover.engine.deliver", t, 1.0,
            {"program": program, "lanes": max(1, gaps), "tokens": 8,
             "gaps": gaps, "gap_ms_sum": mean_ms * gaps,
             "gap_ms_max": mean_ms})


def test_medians_and_the_share_on_a_hand_made_trace(monkeypatch):
    run = _run(_events(5), monkeypatch)
    assert _reader("router.queue_wait_ms").read(run) == 30.0
    assert _reader("engine.prefill_wall_ms").read(run) == 300.0
    assert _reader("router.first_token_ms").read(run) == 334.0
    assert _reader("engine.prompt_cached_share").read(run) \
        == pytest.approx(100.0 * (0 + 200 + 400 + 600 + 800) / 5000)


def test_gaps_weigh_a_delivery_by_the_gaps_it_holds(monkeypatch):
    # 60 gaps of 100 ms, 30 of 200 ms, 10 of 900 ms; first tokens hold none
    spans = [_deliver(0.0, 0, 0.0, "prefill_chunk"),
             _deliver(100.0, 32, 100.0), _deliver(200.0, 28, 100.0),
             _deliver(300.0, 30, 200.0), _deliver(400.0, 10, 900.0)]
    run = _run(spans, monkeypatch)
    assert _reader("engine.token_gap_ms").read(run) == 100.0
    # nine tenths of the gaps lie under the 200 ms deliveries' end
    assert _reader("engine.token_gap_p90_ms").read(run) == 200.0
    run = _run(spans + [_deliver(500.0, 1, 5000.0)], monkeypatch)
    assert _reader("engine.token_gap_p90_ms").read(run) == 900.0
    # a ninth decile of under 100 gaps is a handful of events
    run = _run(spans[:-1], monkeypatch)
    assert _reader("engine.token_gap_ms").read(run) == 100.0
    assert _reader("engine.token_gap_p90_ms").read(run) is None
    # ... but in a rehearsal, which proves the path and prints no value
    run["trace"]["devices"] = [{"plane": "/device:CPU-rehearsal:0"}]
    assert _reader("engine.token_gap_p90_ms").read(run) == 200.0


def test_the_six_are_entries_of_the_six_serving_cells_and_the_list_fits():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    # the driver refuses a file of more than 128 per-layer metrics
    assert len(per_layer) <= 128
    ours = {m["name"]: m for m in per_layer if m["name"] in READERS}
    assert sorted(ours) == sorted(READERS)
    for m in ours.values():
        assert m["workloads"] == SERVING_CELLS
        assert (m["source"], m["moves"]) == ("program_span",
                                             "serve_tokens_per_s")


@pytest.mark.parametrize("name", READERS)
def test_reader_reports_nothing_under_three_events_or_without_them(
        name, monkeypatch):
    reader = _reader(name)
    assert reader.read({"counters": {}, "trace": None}) is None
    # two requests, two deliveries with a gap
    few = _events(2) + [_deliver(5000.0, 4, 100.0),
                        _deliver(6000.0, 4, 100.0)]
    assert reader.read(_run(few, monkeypatch)) is None
    # the parent of PR 52: no ``dlrover.request.*``, and ``.deliver``
    # spans that say nothing
    parent = [("dlrover.engine.deliver", 1000.0 * i, 20.0, {})
              for i in range(8)]
    assert reader.read(_run(parent, monkeypatch)) is None
    assert reader.read(_run([], monkeypatch)) is None


@pytest.mark.parametrize("cell", SERVING_CELLS)
def test_serving_cell_rehearses_the_six(cell):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", cell, "--seed", str(2**31 + 52), "--seconds", "2",
         "--trace", "1", "--rehearse"], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is True
    assert set(READERS) <= set(last["reported"]), last["missing"]
