"""Every cell of BENCHMARK.json runs end to end at a tiny width on the
CPU through ``--rehearse``, reports every metric the cell lists and never
prints a value under a device metric's name."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def _run(cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if cell["chips"] > 1:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={cell['chips']}")
    cmd = [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
           "--workload", cell["name"], "--seed", str(2**31 + 5),
           "--seconds", "2", "--trace", str(trace), "--rehearse"]
    return subprocess.run(
        cmd, cwd=ROOT, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, timeout=600)


def _check(proc, bench, cell, trace):
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is True
    assert "metrics" not in last            # names only, never a value
    group = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in bench[group]
            if cell["name"] in m.get("workloads", [cell["name"]])}
    return last, want


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_rehearses(cell, trace):
    last, want = _check(_run(cell, trace), BENCH, cell, trace)
    assert set(last["reported"]) | set(last["missing"]) == want
    # the Pallas kernels do not run at the rehearsal's sizes on the CPU,
    # so only their readers may find nothing to read
    assert all(m.startswith("kernel.") for m in last["missing"])


def test_a_run_without_the_chip_fails_and_prints_no_result():
    cell = BENCH["workloads"][0]
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", cell["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
