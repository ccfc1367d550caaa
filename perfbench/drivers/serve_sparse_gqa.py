"""Questions against long cached documents, asked of a GROUPED-QUERY model
with a learned selection of keys inside its layers and sparse experts
(``keye-vl2-30b-a3b-serve``): ``drivers/serve_sparse.py``'s closed loop,
schedule and comparison, letter for letter where the two models are alike,
through ``ServingRouter`` into one ``InferenceEngine`` serving one chip's
share.

What is ``serve_sparse``'s own is imported from it: the schedule (ONE
cycle of (document, tail, output) draws fixed by the traffic file's
``base_seed``, ``--seed`` permuting them within groups and deciding all
token content), the documents and tails, the books of a request, the
packed batch of the reference (``Witnessed``) and the statistics of a
selection.  ``serve_tokens_per_s`` is every output token DELIVERED inside
the window over the window's seconds, as there.

``correct`` is that driver's four comparisons against
``perfbench/reference_keye.py`` (float32, the three position streams of
M-RoPE), of what the engine's TIMED programs did for the watched requests
(``InferenceEngine.watch``: the witness of a grouped-query layer under a
selection is a latent layer's, the rows each query attended to, a layer):

(a) every emitted token of the watched requests: its logit in the
    reference, teacher-forced over document + tail + output, within
    ``LOGIT_ATOL`` of the reference's largest, and 9 in 10 within
    ``LOGIT_P90``.
(b) the selection, of the prefill-chunk program and of the decode
    program each on its own: in layer 0, whose input both sides share, a
    mean overlap of ``SELECTION_OVERLAP_FIRST`` and every stray row within
    ``SELECTION_MARGIN_FIRST`` standard deviations of the reference's
    threshold; in every layer ``SELECTION_OVERLAP_DEEPER``, no row the
    query cannot see, and AS MANY rows as the reference chose for
    ``SELECTION_COUNT_EQUAL`` of the queries (a selection of 2 047 or of
    every row overlaps a selection of 2 048 wholly: the count is what
    tells them apart; a tie at the threshold is one query in thousands).
(c) the books, as ``serve_sparse`` keeps them.
(d) the first sparse MLP as the two programs ran it, against the
    reference's on the same input: with no shared expert the routed sum of
    one chip's experts is the whole output, an eighth of the picks.

``perfbench/controls_keye.py`` plants one fault at a time in the reference
and reads the same comparison (``PERFBENCH_CONTROLS=1``); each has to come
out as not correct.
"""

from __future__ import annotations

import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from perfbench import reference_keye
from perfbench.drivers.serve_sparse import (PAD_TO, Witnessed, _first_chunks_only,
                                            _Live, _stamp, document_tokens,
                                            schedule, selection_stats,
                                            tail_tokens)
from perfbench.harness import Context
from perfbench.weights import fold_seed
from perfbench.weights_keye import SeededKeyeParams

# Every limit lies between two readings on the chip (my chip runs, PR 58:
# thirteen runs of the program, seeds 2147485801-04, -11 to -18 and -21;
# PERF.md section 6 has them all): what the engine's timed programs give
# over the seeds, and what they give against a reference with one fault
# planted (``perfbench/controls_keye.py``, seeds 2147485803 / -04; a
# program is as far from a wrong reference as a wrong program from the
# right one).
#
# (a) An emitted token's reference logit against the reference's largest at
# that position (``serve_sparse``'s reasons: greedy decoding emits the
# system's own argmax, and attention over a selection moves with the rows
# bf16 swaps at the threshold).  The program, thirteen runs: worst 0.0-
# 0.323 (the largest of 330-533 positions a run: a limit of twice that
# would stand where the milder faults read, so it stands where only a
# wrong layer reaches); the 90th percentile 0.0 in seven of them and 0.012-
# 0.079 in the others (nine emitted tokens in ten ARE the reference's
# argmax, so the percentile stands now on a zero and now on the first of
# the tail: a limit of 0.06, set from the first four runs, failed the
# ninth at 0.079); the reference with bf16 as served 0.15-0.17 / 0.0-0.002.
# Faults (worst / p90): every query head on the next KV head 4.9-5.5 / 4.0-
# 4.9, index key not rotated 1.4-2.1 / 0.85-1.5 (both limits see these
# two); selection off 0.72 / 0.38-0.67 (the percentile sees it), router
# weights not renormalised 0.44-0.87 / 0.13-0.70.  NOT seen here, and
# caught by (b) or (d): QK-norm off 0.40-0.42 / 0.0-0.27, fp8 weights 0.23-
# 0.28 / 0.0-0.08, 2 047 keys, bf16 index arithmetic.
LOGIT_ATOL = 1.0
LOGIT_P90 = 0.3
# (b) The rows a timed program chose against the reference's ``S_t``, the
# prefill chunk's and the decode forward's each on its own.  Layer 0 (both
# sides see the same input): overlap 0.9957-0.9963, the furthest stray row
# 0.017-0.031 standard deviations of a query's scores from the reference's
# threshold; fp8 weights 0.952-0.960 / 0.22-0.30, index key not rotated
# 0.18-0.19 / 6.1-6.5, selection off 0.066 / 7.2-8.0 (bf16 index arithmetic
# 0.991 / 0.025-0.037: the count is what sees it).  Deeper layers inherit
# bf16's noise in their input, layer 2 most (0.595-0.72 there, 0.76-0.98
# elsewhere; the reference with bf16 as served reads 0.59-0.73 against
# float32 itself): held to a share no wrong layer reaches (QK-norm off
# 0.26-0.35, router weights not renormalised 0.21-0.36, next KV head 0.05-
# 0.06, index key not rotated 0.02-0.05, selection off 0.066; fp8 weights
# 0.35-0.46).  The COUNT of chosen rows equals the reference's for 0.9929-
# 1.0 of a layer's queries over seventeen runs (a tie at the threshold is
# chosen whole, on either side: 0-5 of a chunk program's 1 353-1 936
# queries a layer, 0.9970 at the least, and in five layers of 136 ONE of
# the decode program's 139-215, which is the 0.9929); 2 047 keys or no selection 0.0, bf16 index arithmetic
# 0.017-0.024 (8-bit scores tie by the dozen), index key not rotated 0.46-
# 0.50.
SELECTION_OVERLAP_FIRST = 0.985
SELECTION_MARGIN_FIRST = 0.08
SELECTION_OVERLAP_DEEPER = 0.45
SELECTION_COUNT_EQUAL = 0.98
# (d) The first sparse MLP on its own input, a token's relative error
# (my chip runs, PR 58, seeds 2147485905 / -06 with every control; the
# earlier thirteen runs read the same medians).  A token whose picks are
# the reference's is off by bfloat16's noise: median 0.0064-0.0066 in both
# programs, the largest of a program's tokens 0.0087-0.0136; fp8 weights
# 0.047 (largest 0.057), router weights not renormalised 2.5-2.7, a
# token's eighth pick dropped and the seven renormalised 0.077-0.085.  A
# token beyond ``SPARSE_TOKEN_REL`` (twice that largest, 0.0136; the
# lightest misrouted token read was at 0.098, and of the tokens a swapped
# expert answers for a few with a small weight on it stay under it) is
# MISROUTED: 0-14 of a program's 56-1 316 tokens are, each where the
# reference's 8th and 9th scores stand within 0.0014-0.0119 of each other
# (``misroute_gap_max`` over the thirteen runs since; 0.0104 the largest of
# the thirteen before).  Every flip among them is the PROGRAM's side, by
# the float64 product on the host (``flipped_from_float64``: reference 0,
# program 11 of 11 in three runs), and the cause is the compiler's, not a
# router's: under ``XLA_FLAGS=--xla_allow_excess_precision=false`` the
# same seed reads 0 misrouted of 948 tokens where it read 9, and a median
# of 0.0049.  Inside the engine's programs the normed rows' round trip
# through bfloat16 is elided, so router and experts multiply rows of MORE
# precision than the bfloat16 ones the witness hands back (compiled alone
# the router is exact to 1e-6: PERF.md section 6).  The reference's router
# in bfloat16, 0.0062-0.0172, is NOT told apart from that
# (``controls_keye.UNSEEN``).  A
# router that picks otherwise misroutes at ANY gap: the eighth pick
# dropped 195-306 tokens of a chunk's, the largest gap 0.16-0.37; two held
# experts answering for each other 173-285, 0.13-0.35 (the median token
# is right there, and (a) and (b) pass both: only this gap sees them).
SPARSE_ROUTED_REL = 0.02
SPARSE_TOKEN_REL = 0.03
SPARSE_MISROUTE_GAP = 0.05


def model_config(config: dict, max_seq_len: int):
    """The configuration file as the program's ``LlamaConfig``."""
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    dep, sa = config["deployment"], config["sa_config"]
    if config["decoder_sparse_step"] != 1 or config["mlp_only_layers"] \
            or config["attention_bias"] or config["tie_word_embeddings"] \
            or config["use_sliding_window"] \
            or sa["indexer_num_kv_heads"] != 1 \
            or config["rope_scaling"]["rope_type"] != "default" \
            or sum(config["rope_scaling"]["mrope_section"]) * 2 \
            != config["head_dim"]:
        raise ValueError("not what perfbench/reference_keye.py computes")
    return LlamaConfig.keye_vl2_30b_a3b(
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
        index_n_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"],
        num_experts=config["num_local_experts"],
        moe_experts_held=tuple(config["experts_held"]),
        moe_top_k=config["num_experts_per_tok"],
        moe_norm_topk_prob=bool(config["norm_topk_prob"]),
        moe_intermediate_size=config["moe_intermediate_size"],
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
    max_len, step = int(eng["max_len"]), int(eng["prefill_bucket_step"])
    cfg = model_config(ctx.config, max_seq_len=max_len)
    params = SeededKeyeParams(cfg, ctx.seed)
    buckets = sorted(set(range(int(eng["prefill_chunk"]) + step, max_len,
                               step)) | {max_len})
    engine = InferenceEngine(
        cfg, {"params": params},
        max_slots=int(eng["max_slots"]), chunk=int(eng["chunk"]),
        temperature=float(eng["temperature"]), eos_token=eng["eos_token"],
        max_len=max_len, prefill_buckets=tuple(buckets),
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


def _counters(engine) -> dict:
    s = engine.stats
    out = {"engine.decode_seconds": s.decode_seconds,
           "engine.decode_forwards": float(s.decode_forwards),
           "engine.prefill_seconds": s.prefill_seconds,
           "engine.prefill_calls": float(s.prefill_calls),
           "engine.prefill_chunks": float(s.prefill_chunks),
           "engine.generated_tokens": float(s.generated_tokens)}
    for name in ("dsa_rows_live", "index_rows_scanned",
                 "attn_rows_selected", "kv_rows_live", "kv_rows_streamed",
                 "moe_picks", "moe_picks_held"):
        out["engine." + name] = float(getattr(s, name))
    out["engine.prefix_shared_tokens"] = engine.prefix_stats()[
        "prefix_shared_tokens"]
    return out


def sparse_layer_error(cfg, h, got, get_layer, dims) -> dict:
    """(d): the first sparse MLP as the engine's timed programs ran it
    (``h``: its normed input, ``got``: its output, a token a row) against
    the reference's MLP on that SAME input.  A token's error is relative,
    ``|got - want| / |want|``.  ``misrouted`` counts the tokens it puts
    beyond ``SPARSE_TOKEN_REL``: a pick on another held expert, or one side
    giving nothing and the other something (``flipped``, the part of them
    that is); ``misroute_gap_max`` is the largest ``1 - p_9th / p_8th``
    among them (the reference's scores: how far from a tie the reference
    stood where the two sides picked otherwise).  ``routed_rel`` is the
    median over the OTHER tokens that picked a held expert by the
    reference's routing, and ``routed_rel_max`` their largest (the room
    under ``SPARSE_TOKEN_REL``).  ``flipped_from_float64`` says WHICH side
    a flip is: the tokens each side gives something and the float64
    product of the same rows and router, on the host, nothing (or the
    other way round)."""
    import jax
    import jax.numpy as jnp

    m = get_layer(0)["mlp"]
    h = jnp.asarray(h, jnp.float32)
    got = jnp.asarray(got, jnp.float32)
    want = reference_keye.mlp(h, m, dims)
    d = reference_keye._Dims(dims)
    weights = reference_keye._route(h, m["router"]["kernel"], d)
    routed = jnp.sum(weights[:, dims["first"]:dims["first"] + dims["held"]],
                     axis=-1) > 0
    gave = jnp.linalg.norm(got, axis=-1) > 0
    rel = jnp.linalg.norm(got - want, axis=-1) / jnp.maximum(
        jnp.linalg.norm(want, axis=-1), 1e-30)
    misrouted = (routed | gave) & (rel > SPARSE_TOKEN_REL)
    with jax.default_matmul_precision(reference_keye.PRECISION):
        top = jax.lax.top_k(reference_keye.router_scores(
            h, m["router"]["kernel"]), dims["top_k"] + 1)[0]
    gap = 1.0 - top[:, -1] / top[:, -2]
    held = routed & ~misrouted
    n = int(jnp.sum(held))
    exact = np.asarray(h, np.float64) @ np.asarray(
        m["router"]["kernel"], np.float64)
    picks = np.argsort(-exact, axis=-1)[:, :dims["top_k"]] - dims["first"]
    routed64 = ((picks >= 0) & (picks < dims["held"])).any(axis=-1)
    return {"routed_rel": float(jnp.nanmedian(jnp.where(
                held, rel, jnp.nan))) if n else None,
            "routed_rel_max": float(jnp.max(jnp.where(held, rel, 0.0))),
            "routed_tokens": n,
            "misrouted": int(jnp.sum(misrouted)),
            "flipped": int(jnp.sum(routed != gave)),
            "flipped_from_float64": {
                "reference": int(np.sum(np.asarray(routed) != routed64)),
                "program": int(np.sum(np.asarray(gave) != routed64))},
            "misroute_gap_max": float(jnp.max(jnp.where(misrouted, gap,
                                                        0.0)))}


def selection_holds(stats: List[dict]) -> bool:
    """(b)'s verdict on one kind of program's per-layer statistics."""
    return bool(stats) and (
        stats[0]["overlap"] >= SELECTION_OVERLAP_FIRST
        and stats[0]["margin_max"] <= SELECTION_MARGIN_FIRST
        and all(s["overlap"] >= SELECTION_OVERLAP_DEEPER
                and s["unseen"] == 0
                and s["count_equal"] >= SELECTION_COUNT_EQUAL
                for s in stats))


def reference_check(cfg, params, config: dict, seen: Witnessed,
                    keep: Optional[dict] = None) -> dict:
    """(a), (b) and (d): one pass of the reference over the packed batch,
    a layer at a time, each layer's selection held against the programs'
    before the next is made.  ``keep`` (the controls') is given the
    reference's own ``chosen`` (a kind: a layer's [n, T] bool) and the
    ``logits`` behind every emitted token."""
    import jax.numpy as jnp

    dims = reference_keye.dims_of(config)
    out = {"watched_requests": seen.watched}
    if not seen.watched:
        return dict(out, logits_match_reference=False,
                    selection_matches_reference=False,
                    sparse_layer_matches_reference=False)
    x = reference_keye.embed(jnp.asarray(seen.tokens), params.top())
    want = (seen.first, seen.watched_end - seen.first)
    stats = {"run": [], "decode": []}
    for i in range(cfg.num_layers):
        x, picked = reference_keye.layer_forward(
            x, params.layer(i), dims, want, seen.positions, seen.segments)
        for kind in stats:
            if not seen.queries[kind].size:
                continue
            rows = jnp.asarray(seen.queries[kind] - seen.first)
            theirs = picked[1][rows]
            mine = seen.chosen[kind][i]
            one = selection_stats(mine[None], [(picked[0][rows], theirs)])[0]
            one["count_equal"] = float(np.mean(
                mine[:, :theirs.shape[1]].sum(-1)
                == np.asarray(theirs.sum(-1))))
            stats[kind].append(one)
            if keep is not None:
                keep.setdefault("chosen", {}).setdefault(kind, []).append(
                    np.asarray(theirs))
        del picked
    for kind in stats:
        out[f"selection_{kind}"] = stats[kind]
        out[f"witnessed_{kind}_queries"] = int(seen.queries[kind].size)
        if seen.sparse[kind] is not None:
            out[f"sparse_{kind}"] = sparse_layer_error(
                cfg, *seen.sparse[kind], params.layer, dims)
    out["selection_matches_reference"] = all(
        seen.queries[k].size and selection_holds(stats[k]) for k in stats)
    out["sparse_layer_matches_reference"] = all(
        f"sparse_{k}" in out
        and out[f"sparse_{k}"]["misroute_gap_max"] <= SPARSE_MISROUTE_GAP
        # (no token of these picked a held expert, by the reference's own
        # routing of the same input: nothing routed to hold)
        and (not out[f"sparse_{k}"]["routed_tokens"]
             or out[f"sparse_{k}"]["routed_rel"] <= SPARSE_ROUTED_REL)
        for k in stats)
    deficits = []
    for at, emitted in seen.emitted:
        logits = reference_keye.head_logits(
            x[jnp.asarray(at)], params.top(), cfg.rms_norm_eps)
        deficits.append(np.asarray(
            logits.max(axis=-1) - jnp.take_along_axis(
                logits, jnp.asarray(emitted)[:, None], axis=-1)[:, 0]))
        if keep is not None:
            keep.setdefault("logits", []).append(np.asarray(logits))
    deficits = np.concatenate(deficits)
    out.update({
        "checked_requests": len(seen.requests),
        "checked_positions": int(deficits.size),
        "checked_longest_prompt": max(r.prompt.size for r in seen.requests),
        "worst_logit_deficit": float(deficits.max()),
        "p90_logit_deficit": float(np.percentile(deficits, 90)),
        "logits_match_reference":
            bool(deficits.max() <= LOGIT_ATOL
                 and np.percentile(deficits, 90) <= LOGIT_P90)})
    return out


def run(ctx: Context) -> dict:
    import jax.numpy as jnp

    from dlrover_tpu.utils.compile_cache import cache_counts

    clock = time.perf_counter
    t, eng = ctx.traffic, ctx.config["deployment"]["engine"]
    if t.get("loop", "closed") != "closed":
        raise ValueError("the serve_sparse_gqa driver runs closed loops only")

    # ---------------------------------------------------------- set-up
    t0 = clock()
    cfg, params, engine, router, adapter = _build(ctx)
    t_weights = clock()
    ctx.say("weights made; engine.warmup()")
    programs = engine.warmup()
    router.join_replica("replica-0", adapter)
    ctx.say(f"{programs} programs warm; documents")

    def drain(reqs, seconds):
        deadline = clock() + seconds
        while router.has_work and clock() < deadline:
            router.step()
        if not all(r.state == "Done" for r in reqs):
            raise RuntimeError(
                f"set-up requests ended {[r.state for r in reqs]}")

    docs = [document_tokens(t, ctx.seed, d, cfg.vocab_size)
            for d in range(len(t["documents"]))]
    # every document prefilled once (its blocks stay in the prefix cache
    # when its request ends), then one question on each: the admission
    # that finds a cached head, the warm start, decode at depth
    drain([router.submit(doc, 1) for doc in docs], 900.0)
    warm_rng = np.random.RandomState(1)
    chunk = int(eng["prefill_chunk"])
    drain([router.submit(np.concatenate(
        [doc, warm_rng.randint(0, cfg.vocab_size, chunk // 2 + 3 * i)
         .astype(np.int32)]), int(eng["chunk"]) + 2)
        for i, doc in enumerate(docs)], 900.0)
    jnp.asarray([0], jnp.int32)
    t_warm = clock()
    ctx.say("set-up done; window")
    setup = {"weights_s": t_weights - t0, "warmup_s": t_warm - t_weights,
             "import_s": t0 - ctx.t_start, "warmup_programs": programs,
             "cache_misses": cache_counts()["misses"],
             "cache_hits": cache_counts()["hits"]}

    # ---------------------------------------------------------- window
    # the requests whose timed programs are held against the reference:
    # the first ``check_sample`` admitted in the window on the LONGEST
    # document with a tail of at most ``check_tail_max`` (one at a time:
    # ``InferenceEngine.watch``)
    longest = int(np.argmax(t["documents"]))
    watched: List[int] = []

    def wanted(req) -> bool:
        head = docs[longest]
        if len(watched) >= int(t["check_sample"]) \
                or not head.size < req.prompt.size <= head.size + int(
                    t["check_tail_max"]) \
                or not np.array_equal(req.prompt[:head.size], head):
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
        prompt = np.concatenate([docs[draw.document],
                                 tail_tokens(draw, cfg.vocab_size)])
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
            _first_chunks_only(engine.witness_log)
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
        _first_chunks_only(engine.witness_log)
    everyone = finished + list(live.values())
    drained = _counters(engine)

    # ---------------------------------------------------------- metrics
    done_in_window = [r for r in in_window if r.req.state == "Done"]
    failed = [r for r in everyone if r.req.state != "Done"]
    attempted = len(everyone) + refused
    end_to_end = {
        "setup_s": setup_s,
        # every output token DELIVERED inside the window, those of the
        # requests still running at its end too (serve_sparse's docstring)
        "serve_tokens_per_s": delivered_in_window / window_s,
    }

    # ----------------------------------------------------------- checks
    admitted_docs = sum(int(t["documents"][r.draw.document])
                        for r in everyone if r.req.output)
    checks = {
        "all_requests_done": not failed and refused == 0,
        "books_balance": attempted == len(done_in_window) + len(
            [r for r in in_window if r.req.state != "Done"])
        + in_flight_at_end + refused,
        "output_lengths_as_drawn": all(
            len(r.req.output) == r.draw.output_len for r in everyone
            if r.req.state == "Done"),
        # every admission found its whole document in the prefix cache:
        # the shared tokens booked are the documents' lengths, summed
        "documents_stayed_cached":
            drained["engine.prefix_shared_tokens"]
            - before["engine.prefix_shared_tokens"] == admitted_docs,
        "requests_done_in_window": len(done_in_window),
        "in_flight_at_window_end": in_flight_at_end,
        "tokens_of_requests_done_in_window":
            sum(len(r.req.output) for r in done_in_window),
    }
    shapes = {"max_slots": int(eng["max_slots"]),
              "chunk": int(eng["chunk"]), "layers": cfg.num_layers,
              # the USEFUL values of an index key (its pool's rows are
              # padded to whole lanes)
              "index_dim": cfg.index_head_dim,
              "index_bytes_per_element": jnp.dtype(cfg.dtype).itemsize}
    # what the router delivered is what is checked: a watched request's
    # tokens in the engine are the tokens of the benchmark's own record
    delivered = {(r.prompt_len, tuple(r.req.output)) for r in everyone}
    mine = {(e["request"].prompt.size, tuple(e["request"].output))
            for e in engine.witness_log}
    checks["watched_as_delivered"] = mine <= delivered
    # (a) also takes the other finished requests on that document, in the
    # order they ended, while the packed batch stays within the file's
    # ``check_tokens_max`` of tails + outputs
    others, room = [], int(t["check_tokens_max"]) - sum(
        n + len(o) - docs[longest].size for n, o in mine)
    for r in finished:
        own = r.draw.tail_len + len(r.req.output)
        if r.draw.document == longest and r.req.state == "Done" \
                and r.draw.tail_len <= int(t["check_tail_max"]) \
                and (r.prompt_len, tuple(r.req.output)) not in mine \
                and own <= room:
            others.append((r.req.prompt, r.req.output))
            room -= own
    seen = Witnessed(docs[longest], engine.witness_log, cfg.num_layers,
                     1 if ctx.rehearse else PAD_TO, others)
    # the reference needs the room the engine's weights and pools hold
    engine.witness_log.clear()
    del adapter, router
    engine.params = engine._cache = None
    ctx.say(f"reference check: {seen.watched} watched requests and "
            f"{len(others)} others, {seen.tokens.size} positions")
    controls = bool(os.environ.get("PERFBENCH_CONTROLS"))
    kept = {} if controls else None
    checks.update(reference_check(cfg, params, ctx.config, seen, kept))
    if controls:
        # the builder's controls (perfbench/controls_keye.py): the same
        # comparison against a reference with one fault planted, each of
        # which has to come out as not correct.  Readings only.
        from perfbench import controls_keye

        checks["controls"] = controls_keye.readings(
            ctx, kept, lambda keep=None: reference_check(
                cfg, params, ctx.config, seen, keep))
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
