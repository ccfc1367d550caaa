"""Short questions answered at length: requests through ``ServingRouter``
into one ``InferenceEngine`` serving one chip's share of a model whose
layers are linear attention (KDA: a recurrent state a slot) beside latent
attention (a paged cache of rows), ``kimi-linear-48b-serve``, in a closed
loop of the traffic file's ``clients``.  Every prompt is unique and
nothing is shared: no document, no prefix cache.

The schedule is the benchmark's own: ONE cycle of ``cycle`` (prompt,
output) length pairs fixed by the file's ``base_seed``
(``perfbench/loadgen.py``'s arithmetic); ``--seed`` permutes the pairs
within the groups the file names and decides all token content (ids
uniform over the vocabulary slice), never a length.

``serve_tokens_per_s`` is every output token DELIVERED inside the window
over the window's seconds, those of requests still running at its end
included, as ``drivers/serve_latent.py`` counts and for its reason: a
request lasts a third of the window or more and most clients are in
flight when it ends.

Before a request is admitted the latent pools AND the recurrent states
are filled with ``POISON`` (set-up; the engine's programs are not
touched): a row behind a slot's length, or a state that the first chunk
of a prompt failed to zero, would otherwise be quiet.

``correct`` is five comparisons, every limit below with its reason.  What
(a), (b) and (d) compare is what the engine's TIMED programs did inside
the window: the engine is asked to ``watch`` (``InferenceEngine.watch``)
the first ``check_sample`` requests admitted in the window whose prompt
and output are within ``check_prompt_max`` / ``check_output_max``, one at
a time, and its own prefill-chunk and decode-chunk programs hand back,
with every dispatch that advances the watched slot, the slot's LOGITS over
the vocabulary slice and the slot's KDA STATE of the first and the last
KDA layer behind that forward.  No program is compiled for the check.  The
reference (``perfbench/reference_kimi_linear.py``: float32, the delta
rule as a recurrence, un-absorbed attention, no cache) runs each watched
request's prompt + output as one sequence: one full forward.

(a) the logits of every decode forward of the watched requests (and of
    the prompt chunk that gave their first token) against the reference's
    at the same position, teacher-forced: the root mean square of the
    difference over the slice, a position: the 90th percentile over
    positions within ``LOGIT_RMS_P90``, the worst within
    ``LOGIT_RMS_WORST``.
(b) the delivered tokens: each one's logit in the reference against the
    reference's largest (greedy decoding emits the program's own argmax),
    the 90th percentile within ``TOKEN_DEFICIT_P90``, the worst within
    ``TOKEN_DEFICIT_WORST``.
(c) the books: every request done, lengths as drawn, nothing compiled in
    the window, every admission's state reset, the watched requests'
    tokens in the engine the tokens the router delivered.
(d) the watched slot's recurrent state behind its LAST forward (a whole
    prompt's chunks and every decode step behind it), of the first KDA
    layer (whose input is the embedding: nothing discrete ahead of it)
    within ``STATE_REL_FIRST`` and of the last (behind two attention
    layers and ten routed MLPs) within ``STATE_REL_LAST``: the Frobenius
    norm of the difference over the reference's, all heads.

(e) the first KDA layer's log-decay of every watched forward against the
    reference's function of the SAME float32 sums the program computed
    it from (``witness["kda_decay"]``: ``f`` and ``g``), within
    ``DECAY_REL``.  Against the reference's own decay at that position
    the program's differs by its bf16 matmul operands, 0.2-0.4 %, which
    is also what a decay COMPUTED in bfloat16 differs by (its state moves
    0.13 % where the program's lies 0.36 % from the reference's: not to
    be told apart there); on its own input nothing but the precision of
    the decay's own arithmetic is left.

``perfbench/controls_kimi_linear.py`` plants one fault at a time in the
reference and reads the same comparison (``PERFBENCH_CONTROLS=1``); each
has to come out as not correct.
"""

from __future__ import annotations

import faulthandler
import os
import random
import sys
import time
from typing import Dict, Iterator, List, Optional

import numpy as np

from perfbench import loadgen, reference_kimi_linear
from perfbench.drivers.serve_sparse import _Live, _stamp
from perfbench.harness import Context
from perfbench.weights import fold_seed
from perfbench.weights_kimi_linear import SeededKimiLinearParams

# Every limit is in the cell's traffic file (``limits``: a value and its
# reason each, with the two readings on the chip it lies between: my chip
# runs, PR 43; PERF.md section 6 has them all): the largest the engine's
# timed programs give over the seeds, and what they give against a
# reference with one fault planted (``perfbench/controls_kimi_linear.py``;
# a program is as far from a wrong reference as a wrong program from the
# right one).
LIMITS = ("LOGIT_RMS_P90", "LOGIT_RMS_WORST", "TOKEN_DEFICIT_P90",
          "TOKEN_DEFICIT_WORST", "STATE_REL_FIRST", "STATE_REL_LAST",
          "DECAY_REL")


def limits_of(traffic: dict) -> Dict[str, float]:
    """``{name: value}`` of a traffic file's ``limits``, all of them."""
    return {name: float(traffic["limits"][name]["value"])
            for name in LIMITS}


#: what the pools and the states hold until a program writes them
POISON = 64.0


def model_config(config: dict, max_seq_len: int):
    """The configuration file as the program's ``LlamaConfig``."""
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    dep = config["deployment"]
    d = reference_kimi_linear.dims_of(config)    # refuses what it does not
    n = config["num_hidden_layers"]              # compute
    cfg = LlamaConfig.kimi_linear_48b(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=n,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        max_seq_len=max_seq_len,
        rms_norm_eps=float(config["rms_norm_eps"]),
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        kda_heads=d["kda_heads"], kda_head_dim=d["kda_dim"],
        kda_conv=d["taps"], kda_rank=config["assumed_sizes"]["kda_rank"],
        num_experts=config["num_experts_published"],
        moe_experts_held=tuple(config["experts_held"]),
        moe_top_k=config["num_experts_per_token"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_shared_width=config["moe_intermediate_size"]
        * config["num_shared_experts"],
        moe_routed_scale=float(config["routed_scaling_factor"]),
        moe_first_dense=config["first_k_dense_replace"],
        dtype=jnp.dtype(dep["compute_dtype"]),
        param_dtype=jnp.dtype(dep["param_dtype"]),
    )
    kinds = tuple(i + 1 for i, s in enumerate(cfg.layer_specs)
                  if s.mixer == "kda")
    if kinds != d["kda_layers"]:
        raise ValueError(f"the preset's KDA layers {kinds} are not the "
                         f"configuration file's {d['kda_layers']}")
    return cfg


def schedule(traffic: dict, seed: int) -> Iterator[loadgen.Draw]:
    """Cycles of the file's fixed (prompt, output) pairs
    (``loadgen.cycle_draws``), each in an order drawn from ``seed``, which
    permutes the pairs WITHIN consecutive groups of the file's
    ``seed_permutes_within`` and leaves the groups where the base seed put
    them (``drivers/serve_sparse.py schedule``'s arithmetic)."""
    pairs = loadgen.cycle_draws(traffic)
    group = int(traffic.get("seed_permutes_within", len(pairs)))
    order = random.Random(int(seed) * 1000003 + 17)
    index = 0
    while True:
        perm = []
        for g in range(0, len(pairs), group):
            part = list(range(g, min(g + group, len(pairs))))
            order.shuffle(part)
            perm += part
        for j in perm:
            yield loadgen.Draw(
                index, pairs[j][0], pairs[j][1],
                content_seed=(int(seed) * 7919 + index) % (2**31 - 1))
            index += 1


def _build(ctx: Context):
    import jax

    from dlrover_tpu.serving.engine import InferenceEngine
    from dlrover_tpu.serving.router import (
        ContinuousBatchScheduler,
        ServingRouter,
    )
    from dlrover_tpu.serving.router.replica import InferenceEngineAdapter

    eng = ctx.config["deployment"]["engine"]
    max_len = int(eng["max_len"])
    cfg = model_config(ctx.config, max_seq_len=max_len)
    params = SeededKimiLinearParams(cfg, ctx.seed)
    engine = InferenceEngine(
        cfg, {"params": params},
        max_slots=int(eng["max_slots"]), chunk=int(eng["chunk"]),
        temperature=float(eng["temperature"]), eos_token=eng["eos_token"],
        max_len=max_len, prefill_buckets=(max_len,),
        speculative_k=eng.get("speculative_k", 0),
        paged=bool(eng["paged"]), block_size=int(eng["block_size"]),
        cache_blocks=int(eng["cache_blocks"]),
        prefill_chunk=int(eng["prefill_chunk"]),
        attention_impl=eng["attention_impl"],
        seed=fold_seed(ctx.seed),
        prefix_sharing=bool(eng["prefix_sharing"]))
    jax.block_until_ready(engine.params)
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=int(eng["block_size"])))
    return cfg, params, engine, router, InferenceEngineAdapter(engine)


def _poison(engine) -> None:
    """Every row of the latent pools and every slot's recurrent state LOUD
    until a program writes it (module docstring), an array at a time."""
    import jax
    import jax.numpy as jnp

    for name in ("latent_pool", "kda_state", "kda_conv"):
        held = engine._cache[name]
        for i in range(len(held)):
            held[i] = jnp.full_like(held[i], POISON)
    jax.block_until_ready(engine._cache)


def _counters(engine) -> dict:
    s = engine.stats
    out = {"engine.decode_seconds": s.decode_seconds,
           "engine.prefill_seconds": s.prefill_seconds}
    for name in ("decode_forwards", "prefill_calls", "prefill_chunks",
                 "generated_tokens", "kv_rows_live", "kv_rows_streamed",
                 "moe_picks", "moe_picks_held", "state_bytes_live",
                 "state_bytes_streamed", "state_resets_total",
                 "kda_chunk_rows_real", "kda_chunk_rows_padded",
                 "prefill_admissions"):
        out["engine." + name] = float(getattr(s, name))
    return out


def _to_host(log: List[dict], chunk: int) -> None:
    """The engine's witness log, as each router step leaves it: what the
    programs handed back moves to the host, and only what is compared is
    kept.  Of a request's prompt chunks the last one's logits (the first
    token's); of a decode chunk its logits a forward, and the states (4 MB
    a forward on the device) of the ONE forward that fed the request's
    last fed token, which only the request's last chunk holds."""
    keep = []
    for e in log:
        seen, r = e["seen"], e["request"]
        if isinstance(seen.get("logits"), np.ndarray):
            keep.append(e)
            continue
        if e["kind"] == "run":
            if e["start"] + chunk < r.prompt.size:
                continue
            e["seen"] = {"logits": np.asarray(seen["logits"]),
                         "kda_decay": np.asarray(seen["kda_decay"])[None]}
        else:
            out = {"logits": np.asarray(seen["logits"]),
                   "kda_decay": np.asarray(seen["kda_decay"])}
            # a forward at position p feeds token p; the last token a
            # request delivers is fed to nothing
            last = r.prompt.size + len(r.output) - 2 - e["start"]
            if r.done and 0 <= last < out["logits"].shape[0]:
                # (indexed on the host: nothing compiles in the window)
                out["kda_state"] = np.asarray(seen["kda_state"])[last]
            e["seen"] = out
        keep.append(e)
    log[:] = keep


class Witnessed:
    """What the engine's timed programs handed back for the watched
    requests that finished, a request: ``tokens`` (prompt + output),
    ``logits_at`` the positions whose logits were handed back and
    ``logits`` [n, V], ``state`` [2, H, d, d] (first and last KDA layer
    behind the forward that fed token ``len(tokens) - 2``; None if the
    log holds none)."""

    def __init__(self, log: List[dict], chunk: int):
        self.requests = []
        for e in log:
            r = e["request"]
            if r.done and not any(r is x["request"] for x in self.requests):
                self.requests.append({"request": r, "at": [], "logits": [],
                                      "decay": [], "state": None})
        for e in log:
            mine = next((x for x in self.requests
                         if x["request"] is e["request"]), None)
            if mine is None:
                continue
            r, seen = e["request"], e["seen"]
            got = np.asarray(seen["logits"], np.float32)
            if e["kind"] == "run":
                if e["start"] + chunk >= r.prompt.size:
                    mine["at"].append(np.array([r.prompt.size - 1]))
                    mine["logits"].append(got[None])
                    mine["decay"].append(seen["kda_decay"])
            else:
                at = e["start"] + np.arange(got.shape[0])
                fed = at <= r.prompt.size + len(r.output) - 2
                mine["at"].append(at[fed])
                mine["logits"].append(got[fed])
                mine["decay"].append(seen["kda_decay"][fed])
                if "kda_state" in seen:
                    mine["state"] = np.asarray(seen["kda_state"], np.float32)
        for x in self.requests:
            r = x["request"]
            x["tokens"] = np.concatenate(
                [r.prompt, np.asarray(r.output, np.int32)])
            x["at"] = np.concatenate(x["at"]) if x["at"] \
                else np.zeros(0, np.int64)
            x["logits"] = np.concatenate(x["logits"]) if x["logits"] \
                else None
            x["decay"] = np.concatenate(x["decay"]).astype(np.float32) \
                if x["decay"] else None
        self.watched = len(self.requests)


def reference_check(cfg, params, config: dict, seen: Witnessed,
                    limits: Dict[str, float],
                    keep: Optional[dict] = None) -> dict:
    """(a), (b), (d) and (e) under ``limits`` (:func:`limits_of`): one
    pass of the reference over each watched request's prompt + output
    less its last token (which is fed to nothing).  ``keep`` (the
    controls') is given the reference's own ``logits`` at the checked
    positions and its ``states``."""
    import jax.numpy as jnp

    dims = reference_kimi_linear.dims_of(config)
    out = {"watched_requests": seen.watched}
    verdicts = ("logits_match_reference", "tokens_match_reference",
                "state_matches_reference", "decay_matches_reference")
    if not seen.watched or any(x["logits"] is None or x["state"] is None
                               for x in seen.requests):
        return dict(out, **{v: False for v in verdicts})
    kda_layers = [i - 1 for i in dims["kda_layers"]]
    rms, deficits, worst_abs = [], [], 0.0
    rel_first, rel_last, rel_decay = [], [], []
    a_log = params.layer(kda_layers[0])["kda"]["A_log"]
    for x in seen.requests:
        # (e) the first KDA layer's log-decay as the programs computed it,
        # against the reference's function of the SAME float32 sums
        f, g = jnp.asarray(x["decay"][:, 0]), x["decay"][:, 1]
        want = np.asarray(reference_kimi_linear.decay_of(
            f, a_log.astype(jnp.float32)))
        rel_decay.append(float(np.max(
            np.linalg.norm((g - want).reshape(len(g), -1), axis=-1)
            / np.linalg.norm(want.reshape(len(g), -1), axis=-1))))
        kept = {}
        hidden = reference_kimi_linear.hidden_states(
            x["tokens"][:-1], params.layer, params.top(), cfg.num_layers,
            dims, kept)
        states = kept["kda_states"]
        for mine, layer, rels in ((x["state"][0], kda_layers[0], rel_first),
                                  (x["state"][1], kda_layers[-1], rel_last)):
            want = np.asarray(states[layer])
            rels.append(float(np.linalg.norm(mine - want)
                              / max(np.linalg.norm(want), 1e-30)))
        if keep is not None:
            keep.setdefault("states", []).append(
                [np.asarray(states[kda_layers[0]]),
                 np.asarray(states[kda_layers[-1]])])
        # in blocks of positions (the slice's 20 480 logits a position);
        # the token behind a checked position is the one the program
        # emitted there
        for s0 in range(0, x["at"].size, 256):
            at = x["at"][s0:s0 + 256]
            want = np.asarray(reference_kimi_linear.head_logits(
                hidden[jnp.asarray(at)], params.top(), cfg.rms_norm_eps))
            if keep is not None:
                keep.setdefault("logits", []).append(want)
            diff = x["logits"][s0:s0 + 256] - want
            rms.append(np.sqrt(np.mean(diff * diff, axis=-1)))
            worst_abs = max(worst_abs, float(np.abs(diff).max()))
            deficits.append(want.max(axis=-1) - want[
                np.arange(at.size), x["tokens"][at + 1]])
        del hidden, kept, states
    rms, deficits = np.concatenate(rms), np.concatenate(deficits)
    out.update({
        "checked_requests": len(seen.requests),
        "checked_positions": int(rms.size),
        "checked_longest": max(x["tokens"].size for x in seen.requests),
        "logit_rms_p90": float(np.percentile(rms, 90)),
        "logit_rms_worst": float(rms.max()),
        "logit_abs_worst": worst_abs,
        "token_deficit_p90": float(np.percentile(deficits, 90)),
        "token_deficit_worst": float(deficits.max()),
        "state_rel_first": max(rel_first),
        "state_rel_last": max(rel_last),
        "decay_rel": max(rel_decay),
        "logits_match_reference": bool(
            np.percentile(rms, 90) <= limits["LOGIT_RMS_P90"]
            and rms.max() <= limits["LOGIT_RMS_WORST"]),
        "tokens_match_reference": bool(
            np.percentile(deficits, 90) <= limits["TOKEN_DEFICIT_P90"]
            and deficits.max() <= limits["TOKEN_DEFICIT_WORST"]),
        "state_matches_reference": bool(
            max(rel_first) <= limits["STATE_REL_FIRST"]
            and max(rel_last) <= limits["STATE_REL_LAST"]),
        "decay_matches_reference": bool(
            max(rel_decay) <= limits["DECAY_REL"])})
    return out


def run(ctx: Context) -> dict:
    import jax.numpy as jnp

    from dlrover_tpu.utils.compile_cache import cache_counts

    clock = time.perf_counter
    t, eng = ctx.traffic, ctx.config["deployment"]["engine"]
    if t.get("loop", "closed") != "closed":
        raise ValueError("the serve_linear driver runs closed loops only")

    # ---------------------------------------------------------- set-up
    t0 = clock()
    cfg, params, engine, router, adapter = _build(ctx)
    t_weights = clock()
    ctx.say("weights made; engine.warmup()")
    programs = engine.warmup()
    _poison(engine)
    router.join_replica("replica-0", adapter)
    ctx.say(f"{programs} programs warm; warm-up requests")
    chunk = int(eng["prefill_chunk"])
    warm_rng = np.random.RandomState(1)
    # a prompt of one chunk and one of two and a bit, decoded for a chunk
    # and more: every program on live slots, the table pushes, the reads
    reqs = [router.submit(warm_rng.randint(0, cfg.vocab_size, n)
                          .astype(np.int32), int(eng["chunk"]) + 2)
            for n in (chunk // 2, 2 * chunk + 3)]
    deadline = clock() + 600.0
    while router.has_work and clock() < deadline:
        router.step()
    if not all(r.state == "Done" for r in reqs):
        raise RuntimeError(f"set-up requests ended {[r.state for r in reqs]}")
    jnp.asarray([0], jnp.int32)
    t_warm = clock()
    ctx.say("set-up done; window")
    setup = {"weights_s": t_weights - t0, "warmup_s": t_warm - t_weights,
             "import_s": t0 - ctx.t_start, "warmup_programs": programs,
             "cache_misses": cache_counts()["misses"],
             "cache_hits": cache_counts()["hits"]}

    # ---------------------------------------------------------- window
    watched: List[int] = []

    def wanted(req) -> bool:
        if len(watched) >= int(t["check_sample"]) \
                or req.max_new_tokens > int(t["check_output_max"]) \
                or req.prompt.size > int(t["check_prompt_max"]):
            return False
        watched.append(req.rid)
        return True

    engine.watch(wanted)
    draws = schedule(t, ctx.seed)
    live: Dict[int, _Live] = {}
    finished: List[_Live] = []
    context_samples: List[tuple] = []   # (time, live context tokens, running)
    refused = 0
    trace_at = max(0.0, ctx.seconds - float(t["trace_seconds"]))
    traced = False
    before = _counters(engine)
    clients = int(t["clients"])
    t_w0 = clock()
    setup_s = t_w0 - ctx.t_start

    def submit(draw):
        nonlocal refused
        prompt = loadgen.prompt_tokens(draw, cfg.vocab_size)
        try:
            req = router.submit(prompt, draw.output_len)
        except Exception:
            refused += 1
            return
        live[req.rid] = _Live(draw, req, prompt.size)

    while True:
        elapsed = clock() - t_w0
        if elapsed >= ctx.seconds:
            break
        if ctx.trace and not traced and elapsed >= trace_at:
            ctx.profiler.start()
            traced = True
        with ctx.span("submit"):
            for _ in range(clients - len(live)):
                submit(next(draws))
        if router.has_work:
            if ctx.trace:
                running = [r for r in live.values() if r.seen]
                context_samples.append(
                    (clock(), sum(r.prompt_len + r.seen for r in running),
                     len(running)))
            with ctx.span("router_step"):
                router.step()
            _stamp(live, finished)
            with ctx.span("witness_to_host"):
                _to_host(engine.witness_log, chunk)
        else:
            with ctx.span("idle_wait"):
                time.sleep(0.002)
    t_w1 = clock()
    after = _counters(engine)
    trace = ctx.profiler.result()
    window_s = t_w1 - t_w0
    in_window = list(finished)
    in_flight_at_end = len(live)
    delivered_in_window = sum(len(r.req.output) for r in in_window) + sum(
        r.seen for r in live.values())

    ctx.say("window done; drain")
    deadline = clock() + float(t.get("drain_timeout_s", 60))
    while router.has_work and clock() < deadline:
        router.step()
        _stamp(live, finished)
        _to_host(engine.witness_log, chunk)
    everyone = finished + list(live.values())
    drained = _counters(engine)

    # ---------------------------------------------------------- metrics
    done_in_window = [r for r in in_window if r.req.state == "Done"]
    failed = [r for r in everyone if r.req.state != "Done"]
    attempted = len(everyone) + refused
    end_to_end = {
        "setup_s": setup_s,
        "serve_tokens_per_s": delivered_in_window / window_s,
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
        # every admission started its slot's state from zeros, once
        "every_admission_reset_its_state":
            drained["engine.state_resets_total"]
            - before["engine.state_resets_total"]
            == drained["engine.prefill_admissions"]
            - before["engine.prefill_admissions"],
        "requests_done_in_window": len(done_in_window),
        "in_flight_at_window_end": in_flight_at_end,
        "tokens_of_requests_done_in_window":
            sum(len(r.req.output) for r in done_in_window),
    }
    state = engine._cache["kda_state"][0]
    shapes = {"max_slots": int(eng["max_slots"]),
              "chunk": int(eng["chunk"]), "layers": cfg.num_layers,
              "prefill_chunk": chunk,
              "latent_layers": len(engine._cache["latent_pool"]),
              "latent_row_bytes": int(
                  engine._cache["latent_pool"][0].shape[-1]
                  * jnp.dtype(cfg.dtype).itemsize),
              "kda_layers": len(engine._cache["kda_state"]),
              "kda_heads": int(state.shape[1]),
              "kda_head_dim": int(state.shape[2]),
              "cache_nbytes": engine.cache_nbytes}
    delivered = {(r.prompt_len, tuple(r.req.output)) for r in everyone}
    mine = {(e["request"].prompt.size, tuple(e["request"].output))
            for e in engine.witness_log}
    checks["watched_as_delivered"] = mine <= delivered
    seen = Witnessed(engine.witness_log, chunk)
    # the reference needs the room the engine's weights and pools hold
    engine.witness_log.clear()
    del adapter, router, state
    engine.params = engine._cache = None
    # (``drivers/serve_latent.py``: the watchdog's 300 s start again here)
    faulthandler.dump_traceback_later(300, repeat=True, file=sys.stderr)
    ctx.say(f"reference check: {seen.watched} watched requests of "
            f"{[x['tokens'].size for x in seen.requests]} tokens")
    limits = limits_of(t)
    checks.update(reference_check(cfg, params, ctx.config, seen, limits))
    if os.environ.get("PERFBENCH_CONTROLS"):
        # the builder's controls (perfbench/controls_kimi_linear.py): the
        # same comparison against a reference with one fault planted, each
        # of which has to come out as not correct.  Readings only.
        from perfbench import controls_kimi_linear

        # five more passes of the reference outlive the watchdog's 300 s,
        # and its dump has ENDED a run (drivers/serve_latent.py): off
        faulthandler.cancel_dump_traceback_later()
        checks["controls"] = controls_kimi_linear.readings(
            ctx, lambda keep=None: reference_check(
                cfg, params, ctx.config, seen, limits, keep))
    if trace and os.environ.get("PERFBENCH_SCOPES"):
        # the builder's table (PERF.md section 5): program x scope, and
        # the unscoped instructions that took most
        from perfbench import device_scopes

        reduced = device_scopes.of_run({"trace": trace})
        if reduced is not None:
            print(device_scopes.report(reduced), file=sys.stderr)
    ok = all(v for v in checks.values() if isinstance(v, bool))
    return {
        "end_to_end": end_to_end,
        "setup": setup,
        "window_s": window_s,
        "compiles_in_window": ctx.compiles.inside(t_w0, t_w1),
        "counters": {k: after[k] - before[k] for k in after},
        "samples": {"context": context_samples},
        "shapes": shapes,
        "trace": trace,
        "correct": ok,
        "checks": checks,
        "attempted": attempted,
        "failed": len(failed) + refused,
    }
