"""Retrieved passages and a question, answered in a few hundred tokens:
requests through ``ServingRouter`` into one ``InferenceEngine`` serving one
chip's share of a model whose layers are Mamba-2 state-space scans (a
float32 recurrent state a slot) beside grouped-query attention (a paged
cache of K/V rows), with sparse experts behind both:
``granite-4.0-h-small-serve``, in a closed loop of the traffic file's
``clients``.  Every prompt is unique and nothing is shared: no document, no
prefix cache.

The schedule is the benchmark's own (``drivers/serve_linear.py schedule``):
ONE cycle of ``cycle`` (prompt, output) length pairs fixed by the file's
``base_seed``; ``--seed`` permutes the pairs within the groups the file
names and decides all token content (ids uniform over the vocabulary
slice), never a length.

``serve_tokens_per_s`` is every output token DELIVERED inside the window
over the window's seconds, those of requests still running at its end
included, as ``drivers/serve_linear.py`` counts and for its reason.

Before a request is admitted the K/V pools AND the recurrent states are
filled with ``POISON`` (set-up; the engine's programs are not touched): a
row behind a slot's length, or a state that the first chunk of a prompt
failed to zero, would otherwise be quiet.

``correct`` is five comparisons, every limit in the traffic file with its
reason.  What (a), (b), (d) and (e) compare is what the engine's TIMED
programs did inside the window: the engine is asked to ``watch``
(``InferenceEngine.watch``) the first ``check_sample`` requests admitted in
the window whose prompt and output are within ``check_prompt_max`` /
``check_output_max``, one at a time, and its own prefill-chunk and
decode-chunk programs hand back, with every dispatch that advances the
watched slot, the slot's LOGITS over the vocabulary slice and the slot's
STATE and CONVOLUTION ROWS of the first and the last Mamba-2 layer behind
that forward.  No program is compiled for the check.  The reference
(``perfbench/reference_granite.py``: float32, the scan as a recurrence,
full softmax attention, every held expert dense, no cache) runs each
watched request's prompt + output as one sequence: one full forward.

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
    prompt's chunks through the chunk kernel and every decode step through
    the decode kernel), of the first Mamba-2 layer (whose input is the
    embedding: nothing discrete ahead of it) within ``STATE_REL_FIRST`` and
    of the last (behind the attention layer and nine routed MLPs) within
    ``STATE_REL_LAST``: the Frobenius norm of the difference over the
    reference's, all heads.
(e) the same slot's convolution rows (its last three ``xBC`` inputs) of
    those two layers, within ``CONV_REL_FIRST`` / ``CONV_REL_LAST``.

``perfbench/controls_granite.py`` plants one fault at a time in the
reference and reads the same comparison (``PERFBENCH_CONTROLS=1``); each
has to come out as not correct.
"""

from __future__ import annotations

import faulthandler
import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from perfbench import loadgen, reference_granite
from perfbench.drivers.serve_linear import POISON, schedule
from perfbench.drivers.serve_sparse import _Live, _stamp
from perfbench.harness import Context
from perfbench.weights import fold_seed
from perfbench.weights_granite import SeededGraniteParams

# Every limit is in the cell's traffic file (``limits``: a value and its
# reason each, with the two readings on the chip it lies between: my chip
# runs, PR 50): the largest the engine's timed programs give over the
# seeds, and what they give against a reference with one fault planted
# (``perfbench/controls_granite.py``).
LIMITS = ("LOGIT_RMS_P90", "LOGIT_RMS_WORST", "TOKEN_DEFICIT_P90",
          "TOKEN_DEFICIT_WORST", "STATE_REL_FIRST", "STATE_REL_LAST",
          "CONV_REL_FIRST", "CONV_REL_LAST")


def limits_of(traffic: dict) -> Dict[str, float]:
    """``{name: value}`` of a traffic file's ``limits``, all of them."""
    return {name: float(traffic["limits"][name]["value"])
            for name in LIMITS}


def model_config(config: dict, max_seq_len: int):
    """The configuration file as the program's ``LlamaConfig``."""
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    dep = config["deployment"]
    d = reference_granite.dims_of(config)        # refuses what it does not
    n = config["num_hidden_layers"]              # compute
    cfg = LlamaConfig.granite_4_h_small(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=n,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=d["head_dim"],
        max_seq_len=max_seq_len,
        rms_norm_eps=float(config["rms_norm_eps"]),
        ssm_heads=d["ssm_heads"], ssm_head_dim=d["ssm_dim"],
        ssm_state=d["ssm_state"], ssm_conv=d["taps"],
        embedding_mult=d["embedding_mult"],
        residual_mult=d["residual_mult"],
        attn_scale=d["attn_scale"],
        logit_scale=1.0 / d["logits_scaling"],
        num_experts=config["num_experts_published"],
        moe_experts_held=tuple(config["experts_held"]),
        moe_top_k=config["num_experts_per_tok"],
        moe_shared_width=config["shared_intermediate_size"],
        dtype=jnp.dtype(dep["compute_dtype"]),
        param_dtype=jnp.dtype(dep["param_dtype"]),
    )
    kinds = tuple(i for i, s in enumerate(cfg.layer_specs)
                  if s.mixer == "ssm")
    if kinds != d["ssm_layers"]:
        raise ValueError(f"the preset's Mamba-2 layers {kinds} are not the "
                         f"configuration file's {d['ssm_layers']}")
    return cfg


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
    params = SeededGraniteParams(cfg, ctx.seed)
    # no bucketed prefill exists for this model (every prompt goes in
    # chunks), so a bucket is only what admission and the router's ledger
    # round a prompt up to: a page, and a request is charged its own
    # length (one bucket of ``max_len`` would charge every request 41
    # blocks and seat 74 of the 128 slots: my chip run, PR 50)
    page = int(eng["block_size"])
    engine = InferenceEngine(
        cfg, {"params": params},
        max_slots=int(eng["max_slots"]), chunk=int(eng["chunk"]),
        temperature=float(eng["temperature"]), eos_token=eng["eos_token"],
        max_len=max_len,
        prefill_buckets=tuple(range(page, max_len + page, page)),
        speculative_k=eng.get("speculative_k", 0),
        paged=bool(eng["paged"]), block_size=page,
        cache_blocks=int(eng["cache_blocks"]),
        prefill_chunk=int(eng["prefill_chunk"]),
        attention_impl=eng["attention_impl"],
        seed=fold_seed(ctx.seed),
        prefix_sharing=bool(eng["prefix_sharing"]))
    jax.block_until_ready(engine.params)
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=page))
    return cfg, params, engine, router, InferenceEngineAdapter(engine)


def _poison(engine) -> None:
    """Every row of the K/V pools and every slot's recurrent state LOUD
    until a program writes it (module docstring), an array at a time."""
    import jax
    import jax.numpy as jnp

    for name in ("k_pool", "v_pool", "ssm_state", "ssm_conv"):
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
                 "ssm_chunk_rows_real", "ssm_chunk_rows_padded",
                 "prefill_admissions"):
        out["engine." + name] = float(getattr(s, name))
    return out


def _to_host(log: List[dict], chunk: int) -> None:
    """The engine's witness log, as each router step leaves it: what the
    programs handed back moves to the host, and only what is compared is
    kept.  Of a request's prompt chunks the last one's logits (the first
    token's); of a decode chunk its logits a forward, and the states and
    convolution rows (8 MB a forward on the device) of the ONE forward
    that fed the request's last fed token, which only the request's last
    chunk holds."""
    keep = []
    for e in log:
        seen, r = e["seen"], e["request"]
        if isinstance(seen.get("logits"), np.ndarray):
            keep.append(e)
            continue
        if e["kind"] == "run":
            if e["start"] + chunk < r.prompt.size:
                continue
            e["seen"] = {"logits": np.asarray(seen["logits"])}
        else:
            out = {"logits": np.asarray(seen["logits"])}
            # a forward at position p feeds token p; the last token a
            # request delivers is fed to nothing
            last = r.prompt.size + len(r.output) - 2 - e["start"]
            if r.done and 0 <= last < out["logits"].shape[0]:
                # (indexed on the host: nothing compiles in the window)
                out["ssm_state"] = np.asarray(seen["ssm_state"])[last]
                out["ssm_conv"] = np.asarray(
                    seen["ssm_conv"]).astype(np.float32)[last]
            e["seen"] = out
        keep.append(e)
    log[:] = keep


class Witnessed:
    """What the engine's timed programs handed back for the watched
    requests that finished, a request: ``tokens`` (prompt + output),
    ``logits_at`` the positions whose logits were handed back and
    ``logits`` [n, V], ``state`` [2, H, P, N] and ``conv`` [2, taps - 1, H P
    + 2 N] (first and last Mamba-2 layer behind the forward that fed token
    ``len(tokens) - 2``; None if the log holds none)."""

    def __init__(self, log: List[dict], chunk: int):
        self.requests = []
        for e in log:
            r = e["request"]
            if r.done and not any(r is x["request"] for x in self.requests):
                self.requests.append({"request": r, "at": [], "logits": [],
                                      "state": None, "conv": None})
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
            else:
                at = e["start"] + np.arange(got.shape[0])
                fed = at <= r.prompt.size + len(r.output) - 2
                mine["at"].append(at[fed])
                mine["logits"].append(got[fed])
                if "ssm_state" in seen:
                    mine["state"] = np.asarray(seen["ssm_state"], np.float32)
                    mine["conv"] = np.asarray(seen["ssm_conv"], np.float32)
        for x in self.requests:
            r = x["request"]
            x["tokens"] = np.concatenate(
                [r.prompt, np.asarray(r.output, np.int32)])
            x["at"] = np.concatenate(x["at"]) if x["at"] \
                else np.zeros(0, np.int64)
            x["logits"] = np.concatenate(x["logits"]) if x["logits"] \
                else None
        self.watched = len(self.requests)


def _rel(mine, want) -> float:
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(mine - want)
                 / max(np.linalg.norm(want), 1e-30))


def reference_check(cfg, params, config: dict, seen: Witnessed,
                    limits: Dict[str, float],
                    keep: Optional[dict] = None) -> dict:
    """(a), (b), (d) and (e) under ``limits`` (:func:`limits_of`): one
    pass of the reference over each watched request's prompt + output
    less its last token (which is fed to nothing).  ``keep`` (the
    controls') is given the reference's own ``logits`` at the checked
    positions and its ``states``."""
    import jax.numpy as jnp

    dims = reference_granite.dims_of(config)
    out = {"watched_requests": seen.watched}
    verdicts = ("logits_match_reference", "tokens_match_reference",
                "state_matches_reference", "conv_matches_reference")
    if not seen.watched or any(x["logits"] is None or x["state"] is None
                               for x in seen.requests):
        return dict(out, **{v: False for v in verdicts})
    first, last = dims["ssm_layers"][0], dims["ssm_layers"][-1]
    rms, deficits, worst_abs = [], [], 0.0
    rels = {"state_rel_first": [], "state_rel_last": [],
            "conv_rel_first": [], "conv_rel_last": []}
    for x in seen.requests:
        kept = {}
        hidden = reference_granite.hidden_states(
            x["tokens"][:-1], params.layer, params.top(), cfg.num_layers,
            dims, kept)
        states, convs = kept["ssm_states"], kept["ssm_convs"]
        rels["state_rel_first"].append(_rel(x["state"][0], states[first]))
        rels["state_rel_last"].append(_rel(x["state"][1], states[last]))
        rels["conv_rel_first"].append(_rel(x["conv"][0], convs[first]))
        rels["conv_rel_last"].append(_rel(x["conv"][1], convs[last]))
        if keep is not None:
            keep.setdefault("states", []).append(
                [np.asarray(states[first]), np.asarray(states[last])])
        # in blocks of positions (the slice's 25 088 logits a position);
        # the token behind a checked position is the one the program
        # emitted there
        for s0 in range(0, x["at"].size, 256):
            at = x["at"][s0:s0 + 256]
            want = np.asarray(reference_granite.head_logits(
                hidden[jnp.asarray(at)], params.top(), dims["eps"],
                dims["logits_scaling"]))
            if keep is not None:
                keep.setdefault("logits", []).append(want)
            diff = x["logits"][s0:s0 + 256] - want
            rms.append(np.sqrt(np.mean(diff * diff, axis=-1)))
            worst_abs = max(worst_abs, float(np.abs(diff).max()))
            deficits.append(want.max(axis=-1) - want[
                np.arange(at.size), x["tokens"][at + 1]])
        del hidden, kept, states, convs
    rms, deficits = np.concatenate(rms), np.concatenate(deficits)
    rels = {k: max(v) for k, v in rels.items()}
    out.update({
        "checked_requests": len(seen.requests),
        "checked_positions": int(rms.size),
        "checked_longest": max(x["tokens"].size for x in seen.requests),
        "logit_rms_p90": float(np.percentile(rms, 90)),
        "logit_rms_worst": float(rms.max()),
        "logit_abs_worst": worst_abs,
        "token_deficit_p90": float(np.percentile(deficits, 90)),
        "token_deficit_worst": float(deficits.max()),
        **rels,
        "logits_match_reference": bool(
            np.percentile(rms, 90) <= limits["LOGIT_RMS_P90"]
            and rms.max() <= limits["LOGIT_RMS_WORST"]),
        "tokens_match_reference": bool(
            np.percentile(deficits, 90) <= limits["TOKEN_DEFICIT_P90"]
            and deficits.max() <= limits["TOKEN_DEFICIT_WORST"]),
        "state_matches_reference": bool(
            rels["state_rel_first"] <= limits["STATE_REL_FIRST"]
            and rels["state_rel_last"] <= limits["STATE_REL_LAST"]),
        "conv_matches_reference": bool(
            rels["conv_rel_first"] <= limits["CONV_REL_FIRST"]
            and rels["conv_rel_last"] <= limits["CONV_REL_LAST"])})
    return out


def run(ctx: Context) -> dict:
    import jax.numpy as jnp

    from dlrover_tpu.utils.compile_cache import cache_counts

    clock = time.perf_counter
    t, eng = ctx.traffic, ctx.config["deployment"]["engine"]
    if t.get("loop", "closed") != "closed":
        raise ValueError("the serve_ssm driver runs closed loops only")

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
    state = engine._cache["ssm_state"][0]
    pool = engine._cache["k_pool"][0]
    shapes = {"max_slots": int(eng["max_slots"]),
              "chunk": int(eng["chunk"]), "layers": cfg.num_layers,
              "prefill_chunk": chunk,
              "attn_layers": len(engine._cache["k_pool"]),
              "kv_heads": int(pool.shape[2]), "head_dim": int(pool.shape[3]),
              "kv_bytes_per_element": int(jnp.dtype(pool.dtype).itemsize),
              "ssm_layers": len(engine._cache["ssm_state"]),
              "ssm_heads": int(state.shape[1]),
              "ssm_head_dim": int(state.shape[2]),
              "ssm_state": int(state.shape[3]),
              "cache_nbytes": engine.cache_nbytes}
    delivered = {(r.prompt_len, tuple(r.req.output)) for r in everyone}
    mine = {(e["request"].prompt.size, tuple(e["request"].output))
            for e in engine.witness_log}
    checks["watched_as_delivered"] = mine <= delivered
    seen = Witnessed(engine.witness_log, chunk)
    # the reference needs the room the engine's weights and pools hold
    engine.witness_log.clear()
    del adapter, router, state, pool
    engine.params = engine._cache = None
    # (``drivers/serve_linear.py``: the watchdog's 300 s start again here)
    faulthandler.dump_traceback_later(300, repeat=True, file=sys.stderr)
    ctx.say(f"reference check: {seen.watched} watched requests of "
            f"{[x['tokens'].size for x in seen.requests]} tokens")
    limits = limits_of(t)
    checks.update(reference_check(cfg, params, ctx.config, seen, limits))
    if os.environ.get("PERFBENCH_CONTROLS"):
        # the builder's controls (perfbench/controls_granite.py): the same
        # comparison against a reference with one fault planted, each of
        # which has to come out as not correct.  Readings only.
        from perfbench import controls_granite

        # five more passes of the reference outlive the watchdog's 300 s,
        # and its dump has ENDED a run (drivers/serve_latent.py): off
        faulthandler.cancel_dump_traceback_later()
        checks["controls"] = controls_granite.readings(
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
