"""Seeded, replayable request schedules: the benchmark's own generator.

The length arithmetic is copied from
``dlrover_tpu/serving/router/loadgen.py`` (Pareto lengths clipped at a
maximum), so the yardstick does not move when the program does; a seeded
output-length draw is added, which the program's generator lacks.  The
schedules are those of closed loops: a request has no due time.  (The
program's arrival arithmetic was copied too, measured with an open-loop
cell in PR 23 and taken out with it: PERF.md section 7, row a.)

What the seed may change.  A traffic file fixes, through its own
``base_seed``, one *cycle* of ``cycle`` (prompt, output) length pairs.
``--seed`` only decides the ORDER of the pairs inside every cycle (a fresh
permutation per cycle) and the token content.  It never redraws a length:
every seed offers the same multiset of work, so runs with different seeds
differ by order alone, and the length distribution's tail is the file's
one sample of it (``cycle`` draws), not a new sample per seed.
"""

from __future__ import annotations

import dataclasses
import random
from typing import Iterator, List, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Draw:
    """One request of the schedule."""

    index: int            # position in the schedule
    prompt_len: int
    output_len: int
    content_seed: int     # the prompt's tokens are a function of this


def _pareto_len(rng: random.Random, lo: int, hi: int, alpha: float) -> int:
    # Pareto body at ``lo``, tail clipped at ``hi`` (the program's
    # loadgen._prompt_len arithmetic)
    return int(min(hi, lo * rng.paretovariate(alpha)))


def _length(rng: random.Random, spec: dict) -> int:
    kind = spec.get("dist", "pareto")
    if kind == "fixed":
        return int(spec["value"])
    if kind == "pareto":
        return _pareto_len(rng, int(spec["min"]), int(spec["max"]),
                           float(spec["alpha"]))
    raise ValueError(f"unknown length distribution {kind!r}")


def cycle_draws(traffic: dict) -> List[Tuple[int, int]]:
    """The fixed multiset of one cycle: (prompt, output) length pairs.  A
    function of the traffic file alone — never of ``--seed``."""
    rng = random.Random(int(traffic["base_seed"]))
    return [(_length(rng, traffic["prompt_len"]),
             _length(rng, traffic["output_len"]))
            for _ in range(int(traffic["cycle"]))]


def schedule(traffic: dict, seed: int) -> Iterator[Draw]:
    """The endless schedule for ``seed``: cycles of the fixed multiset,
    each in an order drawn from ``seed``."""
    pairs = cycle_draws(traffic)
    order = random.Random(int(seed) * 1000003 + 17)
    index = 0
    while True:
        p_order = list(range(len(pairs)))
        order.shuffle(p_order)
        for j in p_order:
            yield Draw(index, pairs[j][0], pairs[j][1],
                       content_seed=(int(seed) * 7919 + index) % (2**31 - 1))
            index += 1


def prompt_tokens(draw: Draw, vocab: int) -> np.ndarray:
    """Independent content: uniform token ids from the draw's own seed."""
    rng = np.random.RandomState(draw.content_seed)
    return rng.randint(0, vocab, size=draw.prompt_len).astype(np.int32)


def schedule_bytes(traffic: dict, seed: int, count: int = 256,
                   vocab: int = 32768) -> bytes:
    """The first ``count`` draws and their content as bytes: what 'replays
    byte-identically' is checked on."""
    import itertools

    out = []
    for d in itertools.islice(schedule(traffic, seed), count):
        out.append(repr(dataclasses.astuple(d)).encode())
        out.append(prompt_tokens(d, vocab).tobytes())
    return b"".join(out)
