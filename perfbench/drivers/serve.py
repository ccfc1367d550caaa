"""The serving loop: requests through ``ServingRouter`` into one
``InferenceEngine``, in a closed loop of the traffic file's ``clients``:
each client sends its next request when its last one has answered.

The system under test is the program's router, scheduler, replica handle
and engine in this process — the objects the remote worker and the fabric
wrap.  The schedule, the clock and the books are the benchmark's own.
(An open loop, with arrivals at a fixed rate timed from their due times,
was built and measured in PR 23 and left out with its cell: PERF.md
section 7, row a.)
"""

from __future__ import annotations

import random
import time
from typing import Dict, List

import numpy as np

from perfbench import loadgen, reference
from perfbench.harness import Context, llama_config
from perfbench.weights import SeededParams, fold_seed

# An emitted token's reference logit must be within this of the
# reference's largest logit at that position.  Greedy decoding emits the
# system's own argmax; with seeded random weights the reference's two
# largest logits are often closer than bf16 rounding (logits of unit
# scale, 32768 of them), so the tokens themselves may differ while the
# logits agree.  The system computes in bf16 (weights, activations, KV
# pool, MXU passes of the paged kernel): single logits move by a few
# 1e-2.  Measured on the chip at the published widths (my chip runs, PR
# 23): worst deficits 0.018 and 0.030 over 82-95 positions; the bound is
# four times the larger.  A dropped layer, a wrong RoPE base, a mask off by one or int8
# weights without their scales put the emitted token's logit whole units
# below the maximum.
LOGIT_ATOL = 0.12


class _Live:
    """The benchmark's own record of one request."""

    __slots__ = ("draw", "req", "seen")

    def __init__(self, draw, req):
        self.draw, self.req = draw, req
        self.seen = 0                   # tokens delivered so far


def _build(ctx: Context):
    import jax

    from dlrover_tpu.serving.engine import InferenceEngine
    from dlrover_tpu.serving.router import (
        ContinuousBatchScheduler,
        ServingRouter,
    )
    from dlrover_tpu.serving.router.replica import InferenceEngineAdapter

    eng = ctx.config["deployment"]["engine"]
    cfg = llama_config(ctx.config, max_seq_len=int(eng["max_len"]),
                       scan_layers=False)
    params = SeededParams(cfg, ctx.seed)
    kv = eng.get("kv_dtype", "bf16")
    engine = InferenceEngine(
        cfg, {"params": params},
        max_slots=int(eng["max_slots"]), chunk=int(eng["chunk"]),
        temperature=float(eng["temperature"]), eos_token=eng["eos_token"],
        max_len=int(eng["max_len"]),
        prefill_buckets=tuple(eng["prefill_buckets"]),
        speculative_k=eng.get("speculative_k", 0),
        paged=bool(eng["paged"]), block_size=int(eng["block_size"]),
        kv_dtype=None if kv == "bf16" else kv,
        prefill_chunk=int(eng["prefill_chunk"]),
        attention_impl=eng["attention_impl"],
        seed=fold_seed(ctx.seed),
        prefix_sharing=bool(eng.get("prefix_sharing", True)))
    jax.block_until_ready(engine.params)
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=int(eng["block_size"])))
    return cfg, params, engine, router, InferenceEngineAdapter(engine)


def _stamp(live: Dict[int, _Live], finished: List[_Live]) -> None:
    """After a router step: who got tokens, who finished."""
    for rid in list(live):
        rec = live[rid]
        rec.seen = len(rec.req.output)
        if rec.req.state not in ("Queued", "Running"):
            finished.append(rec)
            del live[rid]


def _counters(engine) -> dict:
    s = engine.stats
    return {"engine.decode_seconds": s.decode_seconds,
            "engine.decode_forwards": float(s.decode_forwards),
            "engine.prefill_seconds": s.prefill_seconds,
            "engine.prefill_calls": float(s.prefill_calls),
            "engine.prefill_chunks": float(s.prefill_chunks),
            "engine.generated_tokens": float(s.generated_tokens)}


def _reference_check(ctx, cfg, params, records: List[_Live]) -> dict:
    """Teacher-forced float32 reference over a seeded sample of finished
    requests: every emitted token's reference logit against the
    reference's maximum at that position."""
    import jax
    import jax.numpy as jnp

    t = ctx.traffic
    done = [r for r in records if r.req.state == "Done" and r.req.output]
    if not done:
        return {"checked_requests": 0, "logits_match_reference": False}
    rng = random.Random(fold_seed(ctx.seed))
    long_ones = [r for r in done
                 if r.draw.prompt_len >= int(t["check_long_prompt"])]
    sample = [max(long_ones, key=lambda r: r.draw.prompt_len)] \
        if long_ones else []
    rest = [r for r in done if r not in sample]
    rng.shuffle(rest)
    sample += rest[:max(0, int(t["check_sample"]) - len(sample))]
    # a few fixed shapes, so that the reference's programs are in the
    # compile cache after a cell's first runs whatever lengths a seed
    # draws: sequences are right-padded (causal: the padding cannot reach
    # back), and the head reads a fixed window of HEAD_ROWS positions
    eng = ctx.config["deployment"]["engine"]
    rows = int(t.get("check_head_rows", 256))
    pads = sorted(set(int(b) for b in t.get(
        "check_pad_to", [512, 1024, int(eng["max_len"])])))
    seqs, padded = [], []
    for r in sample:
        seq = np.concatenate(
            [r.req.prompt, np.asarray(r.req.output, np.int32)])
        need = r.draw.prompt_len - 1 + max(rows, len(r.req.output))
        size = next(b for b in pads + [need] if b >= need)
        seqs.append(seq)
        padded.append(np.pad(seq, (0, size - seq.size)))
    xs = reference.hidden_states(
        padded, params.layer, params.top(), cfg.num_layers,
        cfg.rope_theta, cfg.rms_norm_eps)
    worst, positions = 0.0, 0
    for r, seq, x in zip(sample, seqs, xs):
        p, n = r.draw.prompt_len, len(r.req.output)
        # position p-1+j predicts emitted token j
        window = jax.lax.dynamic_slice_in_dim(x, p - 1, max(rows, n))
        logits = reference.head_logits(
            window, params.top(), cfg.rms_norm_eps)[:n]
        emitted = jnp.asarray(seq[p:p + n])
        deficit = logits.max(axis=-1) - jnp.take_along_axis(
            logits, emitted[:, None], axis=-1)[:, 0]
        worst = max(worst, float(deficit.max()))
        positions += n
    return {"checked_requests": len(sample), "checked_positions": positions,
            "checked_longest_prompt": max(r.draw.prompt_len for r in sample),
            "worst_logit_deficit": worst,
            "logits_match_reference": worst <= LOGIT_ATOL}


def run(ctx: Context) -> dict:
    import jax.numpy as jnp

    from dlrover_tpu.utils.compile_cache import cache_counts

    clock = time.perf_counter
    t, eng = ctx.traffic, ctx.config["deployment"]["engine"]
    if t.get("loop", "closed") != "closed":
        raise ValueError(f"the serve driver runs closed loops only, not "
                         f"loop={t['loop']!r}")

    # ---------------------------------------------------------- set-up
    t0 = clock()
    cfg, params, engine, router, adapter = _build(ctx)
    t_weights = clock()
    ctx.say("weights made; engine.warmup()")
    programs = engine.warmup()
    # a replica joins once it can serve, as the remote worker announces its
    # address only after warm-up: the router counts a replica that has not
    # been pumped for 10 s as dead (found on the chip: warm-up takes
    # minutes there, and a replica joined before it was reaped at once)
    router.join_replica("replica-0", adapter)
    ctx.say(f"{programs} programs warm; warm requests")
    # the host path once per kind of admission (short bucket, full chunk
    # bucket, chunked long prompt), so that nothing in the window is a
    # first time: small eager programs compile here too
    warm_rng = np.random.RandomState(1)
    buckets = sorted(eng["prefill_buckets"])
    slots = int(eng["max_slots"])

    def warm(lengths, new_tokens):
        reqs = [router.submit(warm_rng.randint(0, cfg.vocab_size, n)
                              .astype(np.int32), new_tokens)
                for n in lengths]
        deadline = clock() + 120.0
        while router.has_work and clock() < deadline:
            router.step()
        if not all(r.state == "Done" for r in reqs):
            raise RuntimeError(
                f"warm requests ended {[r.state for r in reqs]}")

    warm([buckets[0] // 2, int(eng["prefill_chunk"]),
          min(int(eng["prefill_chunk"]) + 8, buckets[-1])],
         int(eng["chunk"]) + 2)
    for g in range(2, slots + 1):
        # admission groups of every size: the engine turns its slot list
        # into a device array with one tiny program per list length, which
        # ``InferenceEngine.warmup`` does not run (PERF.md section 7)
        warm([buckets[0] // 2] * g, 2)
        jnp.asarray(list(range(g)), jnp.int32)
    t_warm = clock()
    ctx.say("set-up done; window")
    setup = {"weights_s": t_weights - t0, "warmup_s": t_warm - t_weights,
             "import_s": t0 - ctx.t_start, "warmup_programs": programs,
             "cache_misses": cache_counts()["misses"],
             "cache_hits": cache_counts()["hits"]}

    # ---------------------------------------------------------- window
    draws = loadgen.schedule(t, ctx.seed)
    live: Dict[int, _Live] = {}
    finished: List[_Live] = []
    context_samples: List[tuple] = []   # (time, live context tokens, running)
    refused = 0
    # the traced sub-window is the END of the window: closing the profiler
    # takes seconds, which mid-window would stand in the counters' way
    trace_at = max(0.0, ctx.seconds - float(t["trace_seconds"]))
    traced = False
    before = _counters(engine)
    clients = int(t["clients"])
    t_w0 = clock()
    setup_s = t_w0 - ctx.t_start

    def submit(draw):
        nonlocal refused
        try:
            req = router.submit(loadgen.prompt_tokens(draw, cfg.vocab_size),
                                draw.output_len)
        except Exception:
            refused += 1
            return
        live[req.rid] = _Live(draw, req)

    while True:
        elapsed = clock() - t_w0
        if elapsed >= ctx.seconds:
            break
        if ctx.trace and not traced and elapsed >= trace_at:
            ctx.profiler.start()
            traced = True
        with ctx.span("submit"):
            # a refusal leaves its client without a request until the
            # next turn of the loop: one attempt per free client per turn
            for _ in range(clients - len(live)):
                submit(next(draws))
        if router.has_work:
            if ctx.trace:
                running = [r for r in live.values() if r.seen]
                context_samples.append(
                    (clock(),
                     sum(r.draw.prompt_len + r.seen for r in running),
                     len(running)))
            with ctx.span("router_step"):
                router.step()
            _stamp(live, finished)
        else:
            with ctx.span("idle_wait"):
                time.sleep(0.002)
    t_w1 = clock()
    after = _counters(engine)
    trace = ctx.profiler.result()
    window_s = t_w1 - t_w0
    in_window = list(finished)
    in_flight_at_end = len(live)

    ctx.say("window done; drain")
    # ------------------------------------------- drain, outside the window
    deadline = clock() + float(t.get("drain_timeout_s", 60))
    while router.has_work and clock() < deadline:
        router.step()
        _stamp(live, finished)
    everyone = finished + list(live.values())

    # ---------------------------------------------------------- metrics
    done_in_window = [r for r in in_window if r.req.state == "Done"]
    failed = [r for r in everyone if r.req.state != "Done"]
    attempted = len(everyone) + refused
    end_to_end = {
        "setup_s": setup_s,
        "serve_tokens_per_s":
            sum(len(r.req.output) for r in done_in_window) / window_s,
    }

    # ----------------------------------------------------------- checks
    checks = {
        "all_requests_done": not failed and refused == 0,
        "books_balance": attempted == len(done_in_window) + len(
            [r for r in in_window if r.req.state != "Done"])
        + in_flight_at_end + refused,
        "output_lengths_as_drawn": all(
            len(r.req.output) == r.draw.output_len for r in everyone
            if r.req.state == "Done"),
        "requests_done_in_window": len(done_in_window),
        "in_flight_at_window_end": in_flight_at_end,
    }
    ctx.say("drained; reference check")
    checks.update(_reference_check(ctx, cfg, params, finished))
    ok = all(v for v in checks.values() if isinstance(v, bool))
    return {
        "end_to_end": end_to_end,
        "setup": setup,
        "window_s": window_s,
        "compiles_in_window": ctx.compiles.inside(t_w0, t_w1),
        "counters": {k: after[k] - before[k] for k in after},
        "samples": {"context": context_samples},
        "shapes": {"max_slots": int(eng["max_slots"]),
                   "chunk": int(eng["chunk"]), "layers": cfg.num_layers,
                   "kv_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim_,
                   "kv_bytes_per_element": 2 if eng.get("kv_dtype", "bf16")
                   == "bf16" else 1},
        "trace": trace,
        "correct": ok,
        "checks": checks,
        "attempted": attempted,
        "failed": len(failed) + refused,
    }
