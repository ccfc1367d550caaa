"""The schedule of ``docqa-closed-48`` (``perfbench/drivers/serve_sparse.py``):
one fixed cycle of (document, tail, output) draws, a seed permutes their
order and decides content, and a seed replays byte for byte."""

import collections
import itertools
import json
import os

from perfbench.drivers import serve_sparse

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(os.path.dirname(HERE), "traffic",
                       "docqa-closed-48.json")) as _f:
    TRAFFIC = json.load(_f)


def test_the_cycle_replays_byte_identically_for_a_seed():
    a = serve_sparse.schedule_bytes(TRAFFIC, 2**31 + 11, count=130)
    b = serve_sparse.schedule_bytes(TRAFFIC, 2**31 + 11, count=130)
    c = serve_sparse.schedule_bytes(TRAFFIC, 2**31 + 12, count=130)
    assert a == b and a != c


def test_a_seed_permutes_the_cycle_and_never_redraws_a_length():
    cycle = sorted(serve_sparse.cycle_draws(TRAFFIC))
    assert len(cycle) == TRAFFIC["cycle"] == 64
    for seed in (1, 2**31 + 5):
        draws = list(itertools.islice(
            serve_sparse.schedule(TRAFFIC, seed), 128))
        for half in (draws[:64], draws[64:]):
            assert sorted((d.document, d.tail_len, d.output_len)
                          for d in half) == cycle
    # the seed permutes WITHIN groups of 8; a group is where the base seed
    # put it, so the first n draws are the same work to within a group
    assert TRAFFIC["seed_permutes_within"] == 8
    a, b = (list(itertools.islice(serve_sparse.schedule(TRAFFIC, s), 64))
            for s in (3, 2**31 + 9))
    assert [d[1:4] for d in a] != [d[1:4] for d in b]
    for g in range(0, 64, 8):
        assert sorted(d[1:4] for d in a[g:g + 8]) \
            == sorted(d[1:4] for d in b[g:g + 8])
    docs = TRAFFIC["documents"]
    assert docs == [16384, 18432, 20480, 24576, 28672, 30720]
    assert TRAFFIC["clients"] == 48
    for d, tail, out in cycle:
        assert 256 <= tail <= 2048 and 32 <= out <= 256
        assert docs[d] + tail + out <= 33024
    # Zipf(1) over six: the first rank takes about 1 / 2.45 of the draws
    counts = collections.Counter(d for d, _, _ in cycle).most_common()
    assert len(counts) == 6 and 18 <= counts[0][1] <= 34


def test_every_planted_fault_comes_out_not_correct_in_the_rehearsal():
    """``PERFBENCH_CONTROLS=1``: the cell's own comparison of what the
    engine's timed programs handed back, against the reference with each
    fault of ``perfbench/controls_glm5.py`` planted, through ``run.py``."""
    import subprocess
    import sys

    from perfbench import controls_glm5

    root = os.path.dirname(os.path.dirname(HERE))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "perfbench", "run.py"),
         "--workload", "serve-docqa-sparse", "--seed", str(2**31 + 351),
         "--seconds", "4", "--trace", "0", "--rehearse"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu",
                           PERFBENCH_CONTROLS="1"),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    detail = next(line for line in proc.stdout.splitlines()
                  if line.startswith("perfbench detail "))
    checks = json.loads(detail[len("perfbench detail "):])["checks"]
    assert all(checks[v] for v in controls_glm5.VERDICTS)
    assert checks["witnessed_run_queries"] and checks[
        "witnessed_decode_queries"]
    read = checks["controls"]
    assert set(read) == set(controls_glm5.FAULTS) | set(
        controls_glm5.WITNESSES)
    for name in controls_glm5.FAULTS:
        assert read[name]["correct"] is False, (name, read[name])
    for name in controls_glm5.WITNESSES:
        assert read[name]["correct"] is True, (name, read[name])
        assert "against_f32" in read[name]
