"""BENCHMARK.json and the files it names agree: every cell, traffic mix,
configuration and per-layer metric is a file of its own, found by name."""

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _reader(name):
    path = os.path.join(BENCH_DIR, "layer_metrics", name + ".py")
    if not os.path.exists(path):    # <metric>.<suffix> read by <metric>.py
        path = os.path.join(BENCH_DIR, "layer_metrics",
                            name.rsplit(".", 1)[0] + ".py")
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_names_units_and_keys():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in b[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in b["end_to_end"] + b["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    assert all(0.01 <= m["bound"] <= 0.1 for m in b["end_to_end"])
    four = sum(1 for w in b["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(b["workloads"]) // 4)


def test_every_cell_resolves_to_files():
    b = _bench()
    configs = {c["name"]: c for c in b["configs"]}
    for w in b["workloads"]:
        c = configs[w["config"]]
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"]
        assert set(c["reduced"]) == set(conf["reduced"])
        assert conf["deployment"]["chips"] == w["chips"]
        with open(os.path.join(BENCH_DIR, "traffic",
                               w["traffic"] + ".json")) as f:
            t = json.load(f)
        assert os.path.exists(os.path.join(
            BENCH_DIR, "drivers", t["driver"] + ".py"))
        assert len(w["why"]) <= 200 and t["who"]
    assert {c["name"] for c in b["configs"]} == {
        w["config"] for w in b["workloads"]}


def test_widths_are_the_published_ones():
    published = dict(hidden_size=4096, intermediate_size=14336,
                     num_attention_heads=32, num_key_value_heads=8,
                     head_dim=128, vocab_size=32768, rope_theta=1e6,
                     rms_norm_eps=1e-5)
    for c in _bench()["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert {k: conf[k] for k in published} == published, c["name"]
        assert c["reduced"] == ["num_hidden_layers"]


def test_per_layer_metrics_have_readers_that_agree():
    b = _bench()
    e2e = {m["name"]: m for m in b["end_to_end"]}
    cells = [w["name"] for w in b["workloads"]]
    for m in b["per_layer"]:
        mod = _reader(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE) == (
            m["layer"], m["unit"], m["better"], m["source"])
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)
        # a reader that finds nothing to read returns nothing
        assert mod.read({"setup": {}, "counters": {}, "trace": None,
                         "samples": {"step_s": [], "step_had_save": [],
                                     "context": []},
                         "shapes": {"max_slots": 1}}) is None
    for cell in cells:
        assert any(cell in m.get("workloads", cells) for m in b["per_layer"])
        assert sum(cell in m.get("workloads", cells)
                   for m in b["end_to_end"]) >= 2


def test_every_reader_file_is_used():
    names = {m["name"] for m in _bench()["per_layer"]}
    stems = names | {n.rsplit(".", 1)[0] for n in names}
    files = {f[:-3] for f in os.listdir(os.path.join(BENCH_DIR,
                                                     "layer_metrics"))
             if f.endswith(".py")}
    assert files <= stems, files - stems
