"""Documents continued by a base model: requests through ``ServingRouter``
into one ``InferenceEngine`` serving a depth cut of a dense model whose
EVERY layer is power retention (a float32 state and a sum of keys a key
head and slot, no K/V rows, no pool, no block table):
``brumby-14b-serve``, in a closed loop of the traffic file's ``clients``.
Every prompt is unique and nothing is shared.  The router's placement
ledger has nothing to charge: admission is by slots.

The schedule is the benchmark's own (``drivers/serve_linear.py schedule``):
ONE cycle of ``cycle`` (prompt, output) length pairs fixed by the file's
``base_seed``; ``--seed`` permutes the pairs within the groups the file
names and decides all token content (ids uniform over the whole
vocabulary), never a length.

``serve_tokens_per_s`` is every output token DELIVERED inside the window
over the window's seconds, those of requests still running at its end
included, as ``drivers/serve_linear.py`` counts and for its reason.

Before a request is admitted the states and the sums of keys are filled
with ``POISON`` (set-up; the engine's programs are not touched): a state
that the first chunk of a prompt failed to zero would otherwise be quiet.

``correct`` is six comparisons, every limit in the traffic file with its
reason.  What (a), (b), (d), (e) and (f) compare is what the engine's TIMED
programs did inside the window: the engine is asked to ``watch``
(``InferenceEngine.watch``) the first ``check_sample`` requests admitted in
the window whose prompt and output are within ``check_prompt_max`` /
``check_output_max``, one at a time, and its own prefill-chunk and
decode-chunk programs hand back, with every dispatch that advances the
watched slot, the slot's LOGITS over the vocabulary, its STATE and SUM OF
KEYS of the first and the last layer behind that forward, and the first
layer's gate beside the float32 sums it came from.  No program is compiled
for the check.  The reference (``perfbench/reference_brumby.py``: float32,
the retention in its ATTENTION form, no state, no cache) runs each watched
request's prompt + output as one sequence: one full forward.

(a) the logits of every decode forward of the watched requests (and of
    the prompt chunk that gave their first token) against the reference's
    at the same position, teacher-forced: the root mean square of the
    difference over the vocabulary, a position: the 90th percentile over
    positions within ``LOGIT_RMS_P90``, the worst within
    ``LOGIT_RMS_WORST``.
(b) the delivered tokens: each one's logit in the reference against the
    reference's largest (greedy decoding emits the program's own argmax),
    the 90th percentile within ``TOKEN_DEFICIT_P90``, the worst within
    ``TOKEN_DEFICIT_WORST``.
(c) the books: every request done, lengths as drawn, nothing compiled in
    the window, every admission's state reset, the watched requests'
    tokens in the engine the tokens the router delivered, NO pool in the
    engine's cache, and the rows the kept layout pads its pairs with
    still zero.
(d) the watched slot's state behind its LAST forward (a whole prompt's
    chunks through the chunk kernel and every decode step through the
    decode kernel), UNFOLDED from the kept layout (each unordered pair
    once, ``sqrt 2`` on a pair of different dimensions) into the full
    symmetric square the reference computes from the definition
    (:func:`unfold`), of the first layer (whose input is the embedding)
    within ``STATE_REL_FIRST`` and of the last within ``STATE_REL_LAST``:
    the Frobenius norm of the difference over the reference's, all heads.
(e) the same slot's sum of keys, within ``KEYSUM_REL_FIRST`` /
    ``KEYSUM_REL_LAST``.
(f) the first layer's ``log g`` of every watched forward against the
    reference's ``logsigmoid`` of the SAME float32 sums the program
    computed it from (both handed back), within ``GATE_REL``: on its own
    input nothing but the precision of the gate's arithmetic is left.

``perfbench/controls_brumby.py`` plants one fault at a time in the
reference and reads the same comparison (``PERFBENCH_CONTROLS=1``); each
has to come out as not correct.
"""

from __future__ import annotations

import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from perfbench import loadgen, reference_brumby
from perfbench.drivers.serve_linear import POISON, schedule
from perfbench.drivers.serve_sparse import _Live, _stamp
from perfbench.harness import Context
from perfbench.weights import fold_seed
from perfbench.weights_brumby import SeededBrumbyParams

# Every limit is in the cell's traffic file (``limits``: a value and its
# reason each, with the two readings on the chip it lies between: my chip
# runs, PR 62): the largest the engine's timed programs give over the
# seeds, and what they give against a reference with one fault planted
# (``perfbench/controls_brumby.py``).
LIMITS = ("LOGIT_RMS_P90", "LOGIT_RMS_WORST", "TOKEN_DEFICIT_P90",
          "TOKEN_DEFICIT_WORST", "STATE_REL_FIRST", "STATE_REL_LAST",
          "KEYSUM_REL_FIRST", "KEYSUM_REL_LAST", "GATE_REL")


def limits_of(traffic: dict) -> Dict[str, float]:
    """``{name: value}`` of a traffic file's ``limits``, all of them."""
    return {name: float(traffic["limits"][name]["value"])
            for name in LIMITS}


def model_config(config: dict, max_seq_len: int):
    """The configuration file as the program's ``LlamaConfig``."""
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    dep = config["deployment"]
    d = reference_brumby.dims_of(config)         # refuses what it does not
    if d["degree"] != 2:                         # compute
        raise ValueError("the served retention is of degree 2")
    return LlamaConfig.brumby_14b(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        max_seq_len=max_seq_len,
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(dep["compute_dtype"]),
        param_dtype=jnp.dtype(dep["param_dtype"]),
    )


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
    params = SeededBrumbyParams(cfg, ctx.seed)
    # no layer caches rows: no ``paged``, ``cache_blocks`` or ``block_size``
    # (the engine has no pool for them to size), and no prefill buckets (no
    # bucketed prefill exists for this model: every prompt goes in chunks)
    engine = InferenceEngine(
        cfg, {"params": params},
        max_slots=int(eng["max_slots"]), chunk=int(eng["chunk"]),
        temperature=float(eng["temperature"]), eos_token=eng["eos_token"],
        max_len=max_len,
        speculative_k=eng.get("speculative_k", 0),
        prefill_chunk=int(eng["prefill_chunk"]),
        attention_impl=eng["attention_impl"],
        seed=fold_seed(ctx.seed),
        prefix_sharing=bool(eng["prefix_sharing"]))
    jax.block_until_ready(engine.params)
    router = ServingRouter(scheduler=ContinuousBatchScheduler())
    return cfg, params, engine, router, InferenceEngineAdapter(engine)


STATES = ("retention_state", "retention_keysum")


def _poison(engine) -> None:
    """Every slot's state and sum of keys LOUD until a program writes it
    (module docstring), an array at a time."""
    import jax
    import jax.numpy as jnp

    for name in STATES:
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
                 "state_bytes_live", "state_bytes_streamed",
                 "state_resets_total", "retention_chunk_rows_real",
                 "retention_chunk_rows_padded", "prefill_admissions"):
        out["engine." + name] = float(getattr(s, name))
    return out


def _picker():
    """``x[i]`` on the device, as a program of its own: a decode chunk
    hands back the two layers' states of EVERY forward (68 MB each), and
    only one forward's is compared.  Warmed in set-up on the witness's own
    shapes, so that nothing compiles in the window."""
    import jax

    return jax.jit(lambda x, i: jax.lax.dynamic_index_in_dim(
        x, i, keepdims=False))


def _warm_picker(pick, engine, chunk: int) -> None:
    import jax
    import jax.numpy as jnp

    for name in STATES:
        one = engine._cache[name][0]
        held = jnp.zeros((chunk, 2) + one.shape[1:], one.dtype)
        jax.block_until_ready(pick(held, jnp.asarray(0, jnp.int32)))
        del held


def _to_host(log: List[dict], chunk: int, pick) -> None:
    """The engine's witness log, as each router step leaves it: what the
    programs handed back moves to the host, and only what is compared is
    kept.  Of a request's prompt chunks the last one's logits (the first
    token's) and gate; of a decode chunk its logits and gates a forward,
    and the states and sums of keys of the ONE forward that fed the
    request's last fed token, which only the request's last chunk holds."""
    import jax.numpy as jnp

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
                         "gate": np.asarray(seen["retention_decay"])[None]}
        else:
            out = {"logits": np.asarray(seen["logits"]),
                   "gate": np.asarray(seen["retention_decay"])}
            # a forward at position p feeds token p; the last token a
            # request delivers is fed to nothing
            last = r.prompt.size + len(r.output) - 2 - e["start"]
            if r.done and 0 <= last < out["logits"].shape[0]:
                at = jnp.asarray(last, jnp.int32)
                for name in STATES:
                    out[name] = np.asarray(pick(seen[name], at))
            e["seen"] = out
        keep.append(e)
    log[:] = keep


class Witnessed:
    """What the engine's timed programs handed back for the watched
    requests that finished, a request: ``tokens`` (prompt + output),
    ``logits_at`` the positions whose logits were handed back and
    ``logits`` [n, V], ``gate`` [n, 2, Hk] (``f`` and ``log g`` of the
    first layer at those positions), ``state`` [2, Hk, tiles, d, d] and
    ``keysum`` [2, Hk, tiles, d] (first and last layer behind the forward
    that fed token ``len(tokens) - 2``; None if the log holds none)."""

    def __init__(self, log: List[dict], chunk: int):
        self.requests = []
        for e in log:
            r = e["request"]
            if r.done and not any(r is x["request"] for x in self.requests):
                self.requests.append({"request": r, "at": [], "logits": [],
                                      "gate": [], "state": None,
                                      "keysum": None})
        for e in log:
            mine = next((x for x in self.requests
                         if x["request"] is e["request"]), None)
            if mine is None:
                continue
            r, seen = e["request"], e["seen"]
            got = np.asarray(seen["logits"], np.float32)
            gate = np.asarray(seen["gate"], np.float32)
            if e["kind"] == "run":
                if e["start"] + chunk >= r.prompt.size:
                    mine["at"].append(np.array([r.prompt.size - 1]))
                    mine["logits"].append(got[None])
                    mine["gate"].append(gate)
            else:
                at = e["start"] + np.arange(got.shape[0])
                fed = at <= r.prompt.size + len(r.output) - 2
                mine["at"].append(at[fed])
                mine["logits"].append(got[fed])
                mine["gate"].append(gate[fed])
                if STATES[0] in seen:
                    mine["state"] = np.asarray(seen[STATES[0]], np.float32)
                    mine["keysum"] = np.asarray(seen[STATES[1]], np.float32)
        for x in self.requests:
            r = x["request"]
            x["tokens"] = np.concatenate(
                [r.prompt, np.asarray(r.output, np.int32)])
            x["at"] = np.concatenate(x["at"]) if x["at"] \
                else np.zeros(0, np.int64)
            x["logits"] = np.concatenate(x["logits"]) if x["logits"] \
                else None
            x["gate"] = np.concatenate(x["gate"]) if x["gate"] else None
        self.watched = len(self.requests)


def unfold(kept: np.ndarray):
    """The program's kept layout, ``[..., tiles, d]`` (lane ``c`` of tile
    ``s`` the pair ``{c, c - s mod d}``: the squares in tile 0, ``sqrt 2``
    on every other pair, the upper half of the last tile padding), as the
    full symmetric square ``[..., d, d]`` with every pair at both its
    places and unweighted, and whether the padding is still zero.  Its own
    arithmetic: nothing of ``dlrover_tpu``."""
    d = kept.shape[-1]
    assert kept.shape[-2] == d // 2 + 1, kept.shape
    full = np.zeros(kept.shape[:-2] + (d, d), np.float32)
    for s in range(d // 2 + 1):
        c = np.arange(d // 2 if s == d // 2 else d)
        other = (c - s) % d
        value = kept[..., s, c] / (1.0 if s == 0 else np.sqrt(2.0))
        full[..., c, other] = value
        full[..., other, c] = value
    return full, not np.any(kept[..., d // 2, d // 2:])


def _rel(mine, want) -> float:
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(mine - want)
                 / max(np.linalg.norm(want), 1e-30))


def reference_check(cfg, params, config: dict, seen: Witnessed,
                    limits: Dict[str, float],
                    keep: Optional[dict] = None) -> dict:
    """(a), (b), (d), (e) and (f) under ``limits`` (:func:`limits_of`):
    one pass of the reference over each watched request's prompt + output
    less its last token (which is fed to nothing).  ``keep`` (the
    controls') is given the reference's own ``logits`` at the checked
    positions."""
    import jax.numpy as jnp

    dims = reference_brumby.dims_of(config)
    out = {"watched_requests": seen.watched}
    verdicts = ("logits_match_reference", "tokens_match_reference",
                "state_matches_reference", "keysum_matches_reference",
                "gate_matches_reference")
    if not seen.watched or any(x["logits"] is None or x["state"] is None
                               for x in seen.requests):
        return dict(out, **{v: False for v in verdicts})
    first, last = 0, cfg.num_layers - 1
    rms, deficits, worst_abs, gate_rel = [], [], 0.0, []
    rels = {"state_rel_first": [], "state_rel_last": [],
            "keysum_rel_first": [], "keysum_rel_last": []}
    padding = True
    for x in seen.requests:
        # (f) the first layer's gate as the programs computed it, against
        # the reference's arithmetic on the program's own sums
        f, lg = jnp.asarray(x["gate"][:, 0]), x["gate"][:, 1]
        want = np.asarray(reference_brumby.log_gate(f))
        gate_rel.append(float(np.max(
            np.linalg.norm(lg - want, axis=-1)
            / np.maximum(np.linalg.norm(want, axis=-1), 1e-30))))
        kept = {}
        hidden = reference_brumby.hidden_states(
            x["tokens"][:-1], params.layer, params.top(), cfg.num_layers,
            dims, kept, state_layers=(first, last))
        for which, layer in (("first", first), ("last", last)):
            i = 0 if which == "first" else 1
            # [Hk, tiles, d (v), d] -> [Hk, d (v), d, d]
            mine, clean = unfold(np.moveaxis(x["state"][i], 2, 1))
            rels["state_rel_" + which].append(
                _rel(mine, kept["states"][layer]))
            padding = padding and clean
            mine, clean = unfold(x["keysum"][i])
            rels["keysum_rel_" + which].append(
                _rel(mine, kept["keysums"][layer]))
            padding = padding and clean
        # in blocks of positions (151 936 logits a position); the token
        # behind a checked position is the one the program emitted there
        for s0 in range(0, x["at"].size, 128):
            at = x["at"][s0:s0 + 128]
            want = np.asarray(reference_brumby.head_logits(
                hidden[jnp.asarray(at)], params.top(), dims["eps"]))
            if keep is not None:
                keep.setdefault("logits", []).append(want)
            diff = x["logits"][s0:s0 + 128] - want
            rms.append(np.sqrt(np.mean(diff * diff, axis=-1)))
            worst_abs = max(worst_abs, float(np.abs(diff).max()))
            deficits.append(want.max(axis=-1) - want[
                np.arange(at.size), x["tokens"][at + 1]])
        del hidden, kept
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
        "gate_rel": max(gate_rel),
        "padding_rows_zero": bool(padding),
        "logits_match_reference": bool(
            np.percentile(rms, 90) <= limits["LOGIT_RMS_P90"]
            and rms.max() <= limits["LOGIT_RMS_WORST"]),
        "tokens_match_reference": bool(
            np.percentile(deficits, 90) <= limits["TOKEN_DEFICIT_P90"]
            and deficits.max() <= limits["TOKEN_DEFICIT_WORST"]),
        "state_matches_reference": bool(
            rels["state_rel_first"] <= limits["STATE_REL_FIRST"]
            and rels["state_rel_last"] <= limits["STATE_REL_LAST"]),
        "keysum_matches_reference": bool(
            rels["keysum_rel_first"] <= limits["KEYSUM_REL_FIRST"]
            and rels["keysum_rel_last"] <= limits["KEYSUM_REL_LAST"]),
        "gate_matches_reference": bool(
            max(gate_rel) <= limits["GATE_REL"])})
    return out


def run(ctx: Context) -> dict:
    import jax.numpy as jnp

    from dlrover_tpu.utils.compile_cache import cache_counts

    clock = time.perf_counter
    t, eng = ctx.traffic, ctx.config["deployment"]["engine"]
    if t.get("loop", "closed") != "closed":
        raise ValueError("the serve_retention driver runs closed loops only")

    # ---------------------------------------------------------- set-up
    t0 = clock()
    cfg, params, engine, router, adapter = _build(ctx)
    t_weights = clock()
    ctx.say("weights made; engine.warmup()")
    programs = engine.warmup()
    pick = _picker()
    _warm_picker(pick, engine, int(eng["chunk"]))
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
                _to_host(engine.witness_log, chunk, pick)
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
        _to_host(engine.witness_log, chunk, pick)
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
    state = engine._cache[STATES[0]][0]
    # no layer caches rows: nothing but the slots' states is kept
    checks["no_pool_in_the_cache"] = not engine.paged and not any(
        name in engine._cache for name in (
            "k_pool", "v_pool", "latent_pool", "index_pool", "table"))
    shapes = {"max_slots": int(eng["max_slots"]),
              "chunk": int(eng["chunk"]), "layers": cfg.num_layers,
              "prefill_chunk": chunk,
              "retention_layers": len(engine._cache[STATES[0]]),
              "heads": cfg.num_heads, "kv_heads": int(state.shape[1]),
              "head_dim": int(state.shape[-1]),
              "kept_rows": int(state.shape[2] * state.shape[-1]),
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
    ctx.say(f"reference check: {seen.watched} watched requests of "
            f"{[x['tokens'].size for x in seen.requests]} tokens")
    limits = limits_of(t)
    checks.update(reference_check(cfg, params, ctx.config, seen, limits))
    if os.environ.get("PERFBENCH_CONTROLS"):
        # the builder's controls (perfbench/controls_brumby.py): the same
        # comparison against a reference with one fault planted, each of
        # which has to come out as not correct.  Readings only.
        from perfbench import controls_brumby

        checks["controls"] = controls_brumby.readings(
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
