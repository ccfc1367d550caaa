"""Long documents asked about again and again beside short chat turns, in
one queue: requests through ``ServingRouter`` into one ``InferenceEngine``
serving one chip's share of a model whose latent-attention layers are of
two kinds, FULL (an indexer's learned selection over a paged cache) and
WINDOW (the last 513 keys, a ring a slot), ``dots3-note-serve``, in a
closed loop of the traffic file's ``clients``.

The schedule is the benchmark's own: ONE cycle of ``cycle`` draws fixed by
the file's ``base_seed``, LONG and SHORT interleaved (even draws LONG: a
document by Zipf over the file's ``documents``, a tail and an output by
``long``; odd draws SHORT: a unique prompt and an output by ``short``, no
document); ``--seed`` permutes the draws within the groups the file names
and decides all token content (ids uniform over the vocabulary slice),
never a length.  The documents are prefilled once in set-up and held by
the prefix cache: the full layers' blocks shared copy-on-write, the window
layers' last ``window - 1`` rows kept beside them, so a LONG request
starts WARM behind its document (``window_warm_starts``; a cold start in
the window fails ``no_cold_start_in_window``).

``serve_tokens_per_s`` is every output token DELIVERED inside the window
over the window's seconds, those of requests still running at its end
included, as the three other latent cells count.

Before a request is admitted the latent pools, the index pools, the rings
and the kept prefix ends are filled with ``POISON`` (set-up; the engine's
programs are not touched): a row behind a slot's length or outside a
query's window, a ring's row of an earlier occupant, would otherwise be
quiet.

``correct`` is what the engine's TIMED programs did inside the window,
handed back by themselves (``InferenceEngine.watch``: the first LONG and
the first SHORT request admitted in the window, one at a time; no program
is compiled for the check), against ``perfbench/reference_dots3.py``
(float32, un-absorbed attention, no cache, each request's prompt + output
as ONE sequence at the published widths and the timed lengths):

(a) the slot's logits at every decode forward and at the chunk that gave
    the first token against the reference's at the same position,
    teacher-forced: the root mean square of the difference over the slice,
    a position: ``LOGIT_RMS_P90`` and ``LOGIT_RMS_WORST``.
(b) the delivered tokens: each one's logit in the reference against the
    reference's largest: ``TOKEN_DEFICIT_P90`` / ``TOKEN_DEFICIT_WORST``.
(c) the books: every request done, lengths as drawn, nothing compiled in
    the window, every LONG admission warm, the documents still cached, the
    window layers' resident rows within the ring, the watched requests'
    tokens in the engine the tokens the router delivered.
(d) of the LONG request, the rows the FIRST full layer's selection kept at
    every decode forward (layer 0: its input is the embedding, nothing
    discrete ahead of it) against the reference's ``S_t``: the smallest
    share of the reference's rows that the program chose too,
    ``SELECTION_OVERLAP``.
(e) the FIRST window layer's attention output at two positions of each
    watched request, the prompt's last (the chunk that gave the first
    token) and the last fed forward (a ring that has wrapped behind every
    decode step): the norm of the difference over the reference's, the
    larger.  Of the LONG request against the reference's whole forward
    there: behind a document the window of the prompt's last position is
    the warm start's kept rows, and the ring has wrapped sixteen times or
    more; layer 2 inherits what bf16 moves in two full layers and a
    routed MLP, so this holds the cache manager's new path to what is
    gross, ``WINDOW_WARM_REL``.  Of the SHORT request, which began at
    position 0, the programs hand back that layer's normed INPUT at every
    position too, and the reference computes the one layer from it: on an
    exact input nothing but the layer's own arithmetic and the precision
    of its ring's rows is in the difference, ``WINDOW_OUT_REL``.
(f) the FIRST full layer's attention output at those of the same
    positions that stand below ``index_topk``, where a query attends to
    every key behind it (a SHORT request's prompt ends there): layer 0's
    input is the embedding, so nothing but the layer's own arithmetic and
    the precision of its cached rows is in it, ``FULL_OUT_REL``.  Where
    the selection is at work the rows it swaps at its threshold under bf16
    (0.7 % of them: (d)) move a mean over 2 048 random rows by 6 %, more
    than a cache in float8 does: read (``full_out_rel_selected``), not
    judged.

Every limit is in the cell's traffic file (``limits``: a value and its
reason each, with the two readings on the chip it lies between).
``perfbench/controls_dots3.py`` plants one fault at a time in the
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

from perfbench import loadgen
from perfbench.drivers.serve_sparse import (Draw, _Live, _stamp,
                                            document_tokens, tail_tokens)
from perfbench.harness import Context
from perfbench.weights import fold_seed

LIMITS = ("LOGIT_RMS_P90", "LOGIT_RMS_WORST", "TOKEN_DEFICIT_P90",
          "TOKEN_DEFICIT_WORST", "SELECTION_OVERLAP", "FULL_OUT_REL",
          "WINDOW_OUT_REL", "WINDOW_WARM_REL")

#: what the pools and the rings hold until a program writes them
POISON = 64.0
#: the witness's layer outputs: the first full and the first window layer's
OUTPUTS = ("full_out", "window_out")


def limits_of(traffic: dict) -> Dict[str, float]:
    """``{name: value}`` of a traffic file's ``limits``, all of them."""
    return {name: float(traffic["limits"][name]["value"])
            for name in LIMITS}


def model_config(config: dict, max_seq_len: int):
    """The configuration file as the program's ``LlamaConfig``: the preset
    (all 46 layers' descriptions) cut to the file's first layers, its
    share of the experts and of the vocabulary; a tiny rehearsal's sizes
    replace the preset's layer by layer."""
    import dataclasses

    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    if not hasattr(LlamaConfig, "dots3_note"):
        raise SystemExit(
            "perfbench: this program has no LlamaConfig.dots3_note: it "
            "cannot serve dots3-note-serve (window layers of latent "
            "attention beside full ones)")
    from perfbench import reference_dots3

    dep = config["deployment"]
    d = reference_dots3.dims_of(config)       # refuses what it does not
    n = config["num_hidden_layers"]           # compute
    full, swa = d["kinds"]["full_attention"], d["kinds"]["sliding_attention"]
    if full["v"] != swa["v"]:
        raise ValueError("one value head size for both kinds of layer")
    published = LlamaConfig.dots3_note()
    layers = tuple(
        dataclasses.replace(
            s, num_heads=swa["heads"], window=swa["window"],
            kv_lora_rank=swa["latent"], qk_nope_head_dim=swa["nope"])
        if s.window else dataclasses.replace(s, num_heads=full["heads"])
        for s in published.layer_specs[:n])
    kinds = tuple("sliding_attention" if s.window else "full_attention"
                  for s in layers)
    if kinds != d["layer_types"]:
        raise ValueError(f"the preset's layers {kinds} are not the "
                         f"configuration file's {d['layer_types']}")
    return LlamaConfig.dots3_note(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=n, layers=layers,
        num_heads=full["heads"], num_kv_heads=full["heads"],
        max_seq_len=max_seq_len,
        rms_norm_eps=float(config["rms_norm_eps"]),
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=full["latent"], qk_nope_head_dim=full["nope"],
        qk_rope_head_dim=full["rope"], v_head_dim=full["v"],
        index_n_heads=config["index_n_heads"],
        index_head_dim=config["index_head_dim"],
        index_topk=config["index_topk"],
        mla_lora_rescale=bool(config["apply_mla_qkv_lora_rescale"]),
        num_experts=config["n_routed_experts_published"],
        moe_experts_held=tuple(config["experts_held"]),
        moe_top_k=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_shared_width=config["moe_intermediate_size"]
        * config["n_shared_experts"],
        moe_routed_scale=float(config["routed_scaling_factor"]),
        dtype=jnp.dtype(dep["compute_dtype"]),
        param_dtype=jnp.dtype(dep["param_dtype"]),
    )


def cycle_draws(traffic: dict) -> List[tuple]:
    """The fixed multiset of one cycle: (document, prompt or tail, output),
    the document -1 for a SHORT draw; LONG on the even draws, SHORT on the
    odd.  A function of the traffic file alone."""
    rng = random.Random(int(traffic["base_seed"]))
    n = len(traffic["documents"])
    ranks = list(range(n))
    rng.shuffle(ranks)                  # rank r is document ranks[r]
    s = float(traffic["document_choice"]["exponent"])
    weights = [1.0 / (r + 1) ** s for r in range(n)]
    out = []
    for i in range(int(traffic["cycle"])):
        if i % 2 == 0:
            kind = traffic["long"]
            out.append((rng.choices(ranks, weights=weights)[0],
                        loadgen._length(rng, kind["tail_len"]),
                        loadgen._length(rng, kind["output_len"])))
        else:
            kind = traffic["short"]
            out.append((-1, loadgen._length(rng, kind["prompt_len"]),
                        loadgen._length(rng, kind["output_len"])))
    return out


def schedule(traffic: dict, seed: int) -> Iterator[Draw]:
    """Cycles of the fixed multiset, each in an order drawn from ``seed``,
    which permutes the draws WITHIN consecutive groups of the file's
    ``seed_permutes_within`` (``drivers/serve_sparse.py schedule``'s
    arithmetic)."""
    draws = cycle_draws(traffic)
    group = int(traffic.get("seed_permutes_within", len(draws)))
    order = random.Random(int(seed) * 1000003 + 17)
    index = 0
    while True:
        perm = []
        for g in range(0, len(draws), group):
            part = list(range(g, min(g + group, len(draws))))
            order.shuffle(part)
            perm += part
        for j in perm:
            d, p, o = draws[j]
            yield Draw(index, d, p, o,
                       content_seed=(int(seed) * 7919 + index) % (2**31 - 1))
            index += 1


def schedule_bytes(traffic: dict, seed: int, count: int = 128,
                   vocab: int = 19008) -> bytes:
    """The first ``count`` draws and their content as bytes: what 'replays
    byte-identically' is checked on."""
    import itertools

    out = [document_tokens(traffic, seed, d, vocab).tobytes()
           for d in range(len(traffic["documents"]))]
    for d in itertools.islice(schedule(traffic, seed), count):
        out.append(repr(tuple(d)).encode())
        out.append(tail_tokens(d, vocab).tobytes())
    return b"".join(out)


def _build(ctx: Context):
    import jax

    from dlrover_tpu.serving.engine import InferenceEngine
    from dlrover_tpu.serving.router import (
        ContinuousBatchScheduler,
        ServingRouter,
    )
    from dlrover_tpu.serving.router.replica import InferenceEngineAdapter
    from perfbench.weights_dots3 import SeededDots3Params

    eng = ctx.config["deployment"]["engine"]
    max_len, step = int(eng["max_len"]), int(eng["prefill_bucket_step"])
    cfg = model_config(ctx.config, max_seq_len=max_len)
    params = SeededDots3Params(cfg, ctx.seed)
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


def _poison(engine) -> None:
    """Every row of the pools, the rings and the kept prefix ends LOUD
    until a program writes it (module docstring), an array at a time."""
    import jax
    import jax.numpy as jnp

    for name in ("latent_pool", "index_pool", "window_ring", "window_keep"):
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
                 "dsa_rows_live", "index_rows_scanned", "attn_rows_selected",
                 "moe_picks", "moe_picks_held", "window_rows_in_window",
                 "window_rows_streamed", "window_warm_starts",
                 "window_cold_fallbacks", "prefill_admissions"):
        out["engine." + name] = float(getattr(s, name))
    out["engine.prefix_shared_tokens"] = engine.prefix_stats()[
        "prefix_shared_tokens"]
    return out


def _to_host(log: List[dict], chunk: int) -> None:
    """The engine's witness log, as each router step leaves it: what the
    programs handed back moves to the host, and only what is compared is
    kept.  Of a request's prompt chunks the first window layer's input at
    their real rows, and of the last one its logits (the first token's)
    and the two layers' outputs at the prompt's last position, nothing
    else (a chunk's selection is 6 MB on the device); of a decode chunk
    its logits, the FIRST full layer's chosen rows and the first full and
    the first window layer's output and that layer's input, a forward."""
    for e in log:
        seen, r = e["seen"], e["request"]
        if isinstance(seen["window_in"], np.ndarray):
            continue
        if e["kind"] == "run":
            at = min(chunk, r.prompt.size - e["start"]) - 1
            # (indexed on the host: nothing compiles in the window)
            e["seen"] = {
                "window_in": np.asarray(seen["window_in"],
                                        np.float32)[:at + 1]}
            if e["start"] + chunk >= r.prompt.size:
                e["seen"].update(logits=np.asarray(seen["logits"]), **{
                    name: np.asarray(seen[name], np.float32)[at]
                    for name in OUTPUTS})
        else:
            e["seen"] = {
                "logits": np.asarray(seen["logits"]),
                "rows": np.asarray(seen["rows"])[:, 0], **{
                    name: np.asarray(seen[name], np.float32)[:, 0]
                    for name in OUTPUTS + ("window_in",)}}


class Witnessed:
    """What the engine's timed programs handed back for the watched
    requests that finished, a request: ``tokens`` (prompt + output), ``at``
    the positions whose logits were handed back and ``logits`` [n, V];
    ``rows_at`` / ``rows`` [m, S] the decode forwards' positions and the
    first full layer's chosen rows (-1 behind the last); ``outputs``
    {name: {position: [E]}}, the first full and the first window layer's
    output (``OUTPUTS``) at the prompt's last position and at the last
    FED forward (position ``len(tokens) - 2``), as far as the log holds
    them; ``window_in`` [len(tokens) - 1, E], the first window layer's
    normed input at every fed position, of a request whose prefill began
    at position 0 (None behind a cached prefix: nobody watched that)."""

    def __init__(self, log: List[dict], chunk: int):
        self.requests = []
        for e in log:
            r = e["request"]
            if r.done and not any(r is x["request"] for x in self.requests):
                self.requests.append({
                    "request": r, "at": [], "logits": [], "rows_at": [],
                    "rows": [], "window_in": [],
                    "outputs": {n: {} for n in OUTPUTS}})
        for e in log:
            mine = next((x for x in self.requests
                         if x["request"] is e["request"]), None)
            if mine is None:
                continue
            r, seen = e["request"], e["seen"]
            if e["kind"] == "run":
                mine["window_in"].append((e["start"], seen["window_in"]))
                if e["start"] + chunk >= r.prompt.size:
                    mine["at"].append(np.array([r.prompt.size - 1]))
                    mine["logits"].append(
                        np.asarray(seen["logits"], np.float32)[None])
                    for name in OUTPUTS:
                        mine["outputs"][name][r.prompt.size - 1] = seen[name]
                continue
            got = np.asarray(seen["logits"], np.float32)
            at = e["start"] + np.arange(got.shape[0])
            last = r.prompt.size + len(r.output) - 2
            fed = at <= last
            if fed.any():
                mine["window_in"].append(
                    (e["start"], seen["window_in"][fed]))
            mine["at"].append(at[fed])
            mine["logits"].append(got[fed])
            mine["rows_at"].append(at[fed])
            mine["rows"].append(seen["rows"][fed])
            if fed.any() and at[fed][-1] == last:
                for name in OUTPUTS:
                    mine["outputs"][name][last] = seen[name][fed][-1]
        for x in self.requests:
            r = x["request"]
            x["tokens"] = np.concatenate(
                [r.prompt, np.asarray(r.output, np.int32)])
            for name in ("at", "logits", "rows_at", "rows"):
                x[name] = np.concatenate(x[name]) if x[name] else None
            # (the log is in the order of the positions)
            starts = [start for start, _ in x["window_in"]]
            rows = [rows for _, rows in x["window_in"]]
            ends = list(np.cumsum([len(r) for r in rows]))
            x["window_in"] = np.concatenate(rows) \
                if starts == [0] + ends[:-1] \
                and ends[-1] == x["tokens"].size - 1 else None
        self.watched = len(self.requests)


def reference_check(cfg, params, config: dict, seen: Witnessed,
                    limits: Dict[str, float], documents: List[int],
                    fault: Optional[str] = None) -> dict:
    """(a), (b), (d) and (e) under ``limits`` (:func:`limits_of`): one
    pass of the reference over each watched request's prompt + output
    less its last token (which is fed to nothing).  ``fault``
    (``perfbench/controls_dots3.py``) plants one in the reference;
    ``documents`` are the documents' lengths (a request behind one is
    LONG: its selection is compared, and its warm start began at the
    document's end)."""
    import jax.numpy as jnp

    from perfbench import reference_dots3

    out = {"watched_requests": seen.watched}
    verdicts = ("logits_match_reference", "tokens_match_reference",
                "selection_matches_reference",
                "full_output_matches_reference",
                "window_output_matches_reference",
                "warm_window_matches_reference")
    if not seen.watched or any(
            x["logits"] is None or any(len(at) != 2 for at in
                                       x["outputs"].values())
            for x in seen.requests):
        return dict(out, **{v: False for v in verdicts})
    from dlrover_tpu.serving.paged import ring_geometry

    eng = config["deployment"]["engine"]
    ring = ring_geometry(int(config["sliding_window_size"]),
                         int(eng["prefill_chunk"]), int(eng["block_size"]))
    rms, deficits, worst_abs = [], [], 0.0
    overlaps, chose = [], []      # (rows the program chose, the reference)
    out_rel = {name: [] for name in OUTPUTS + ("full_out_selected",
                                               "window_warm")}

    def rel(mine, want):
        return float(np.linalg.norm(mine - want)
                     / max(np.linalg.norm(want), 1e-30))

    for x in seen.requests:
        prompt = x["request"].prompt.size
        behind = max([d for d in documents if d < prompt], default=0)
        dims = dict(reference_dots3.dims_of(config), fault=fault,
                    ring_rows=ring.rows, ring_block=ring.block_size,
                    missing=(max(0, behind - (
                        int(config["sliding_window_size"]) - 1)), behind)
                    if behind else None)
        first = int(x["rows_at"][0]) if behind else 0
        kept = {}
        hidden = reference_dots3.hidden_states(
            x["tokens"][:-1], params.layer, params.top(), cfg.num_layers,
            dims, kept,
            (first, int(x["rows_at"][-1]) - first + 1) if behind else None)
        for at, mine in x["outputs"]["full_out"].items():
            # (a full layer's output where its selection is at work is
            # read apart: module docstring, (f))
            out_rel["full_out" if at < int(config["index_topk"])
                    else "full_out_selected"].append(
                rel(mine, np.asarray(kept["full_out"][at])))
        if x["window_in"] is None:
            for at, mine in x["outputs"]["window_out"].items():
                out_rel["window_warm"].append(
                    rel(mine, np.asarray(kept["window_out"][at])))
        else:
            # the first window layer alone, on the input the programs had
            layer = dims["layer_types"].index("sliding_attention")
            rows = x["window_in"]
            alone = np.asarray(reference_dots3.attention(
                jnp.pad(jnp.asarray(rows), (
                    (0, -rows.shape[0] % reference_dots3.Q_BLOCK), (0, 0))),
                params.layer(layer), dims, layer))
            for at, mine in x["outputs"]["window_out"].items():
                out_rel["window_out"].append(rel(mine, alone[at]))
        if behind and kept.get("selection") is not None:
            chosen = np.asarray(kept["selection"][1])
            for at, rows in zip(x["rows_at"], x["rows"]):
                theirs = np.flatnonzero(chosen[at - first])
                overlaps.append(np.isin(theirs, rows[rows >= 0]).mean())
                chose.append((int((rows >= 0).sum()), int(theirs.size)))
        elif behind:
            overlaps.append(0.0)    # the reference chose nothing to hold to
        # in blocks of positions (the slice's 19 008 logits a position);
        # the token behind a checked position is the one the program
        # emitted there
        for s0 in range(0, x["at"].size, 256):
            at = x["at"][s0:s0 + 256]
            want = np.asarray(reference_dots3.head_logits(
                hidden[jnp.asarray(at)], params.top(), cfg.rms_norm_eps))
            diff = x["logits"][s0:s0 + 256] - want
            rms.append(np.sqrt(np.mean(diff * diff, axis=-1)))
            worst_abs = max(worst_abs, float(np.abs(diff).max()))
            deficits.append(want.max(axis=-1) - want[
                np.arange(at.size), x["tokens"][at + 1]])
        del hidden, kept
    rms, deficits = np.concatenate(rms), np.concatenate(deficits)
    overlap = float(min(overlaps)) if overlaps else 1.0
    out.update({
        "checked_requests": len(seen.requests),
        "checked_positions": int(rms.size),
        "checked_lengths": [int(x["tokens"].size) for x in seen.requests],
        "checked_selections": len(overlaps),
        "logit_rms_p90": float(np.percentile(rms, 90)),
        "logit_rms_worst": float(rms.max()),
        "logit_abs_worst": worst_abs,
        "token_deficit_p90": float(np.percentile(deficits, 90)),
        "token_deficit_worst": float(deficits.max()),
        "selection_overlap_min": overlap,
        "selection_overlap_mean": float(np.mean(overlaps))
        if overlaps else 1.0,
        # (the witness hands back at most index_topk + 128 of them)
        "selection_rows_program": [min(c[0] for c in chose),
                                   max(c[0] for c in chose)]
        if chose else None,
        "selection_rows_reference": [min(c[1] for c in chose),
                                     max(c[1] for c in chose)]
        if chose else None,
        "full_out_rel": max(out_rel["full_out"], default=float("inf")),
        "full_out_rel_selected": max(out_rel["full_out_selected"],
                                     default=0.0),
        "window_out_rel": max(out_rel["window_out"], default=float("inf")),
        "window_warm_rel": max(out_rel["window_warm"],
                               default=float("inf")),
        "logits_match_reference": bool(
            np.percentile(rms, 90) <= limits["LOGIT_RMS_P90"]
            and rms.max() <= limits["LOGIT_RMS_WORST"]),
        "tokens_match_reference": bool(
            np.percentile(deficits, 90) <= limits["TOKEN_DEFICIT_P90"]
            and deficits.max() <= limits["TOKEN_DEFICIT_WORST"]),
        "selection_matches_reference": bool(
            overlap >= limits["SELECTION_OVERLAP"]),
        "full_output_matches_reference": bool(
            max(out_rel["full_out"], default=float("inf"))
            <= limits["FULL_OUT_REL"]),
        "window_output_matches_reference": bool(
            max(out_rel["window_out"], default=float("inf"))
            <= limits["WINDOW_OUT_REL"]),
        "warm_window_matches_reference": bool(
            max(out_rel["window_warm"], default=float("inf"))
            <= limits["WINDOW_WARM_REL"])})
    if fault is None and overlap < limits["SELECTION_OVERLAP"]:
        # which forwards, for whoever reads the run that failed
        print("perfbench: selection overlap a forward "
              + " ".join(f"{o:.2f}" for o in overlaps), file=sys.stderr)
    return out


def run(ctx: Context) -> dict:
    import jax.numpy as jnp

    from dlrover_tpu.utils.compile_cache import cache_counts

    clock = time.perf_counter
    t, eng = ctx.traffic, ctx.config["deployment"]["engine"]
    if t.get("loop", "closed") != "closed":
        raise ValueError("the serve_window driver runs closed loops only")

    # ---------------------------------------------------------- set-up
    t0 = clock()
    cfg, params, engine, router, adapter = _build(ctx)
    t_weights = clock()
    ctx.say("weights made; engine.warmup()")
    programs = engine.warmup()
    _poison(engine)
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
    # and its last window rows in the store when its request ends), then
    # one question on each (the admission that finds a cached head, the
    # warm start, decode at depth: a used prefix end is never pushed out
    # by an unused one) beside two SHORT prompts, one of several chunks
    drain([router.submit(doc, 1) for doc in docs], 900.0)
    warm_rng = np.random.RandomState(1)
    chunk = int(eng["prefill_chunk"])
    drain([router.submit(np.concatenate(
        [doc, warm_rng.randint(0, cfg.vocab_size, chunk // 2 + 3 * i)
         .astype(np.int32)]), int(eng["chunk"]) + 2)
        for i, doc in enumerate(docs)]
        + [router.submit(warm_rng.randint(0, cfg.vocab_size, n)
                         .astype(np.int32), int(eng["chunk"]) + 2)
           for n in (chunk // 2, 2 * chunk + 3)], 600.0)
    if engine.stats.window_warm_starts != len(docs):
        raise RuntimeError(
            f"set-up: {engine.stats.window_warm_starts} warm starts behind "
            f"{len(docs)} documents")
    jnp.asarray([0], jnp.int32)
    t_warm = clock()
    ctx.say("set-up done; window")
    setup = {"weights_s": t_weights - t0, "warmup_s": t_warm - t_weights,
             "import_s": t0 - ctx.t_start, "warmup_programs": programs,
             "cache_misses": cache_counts()["misses"],
             "cache_hits": cache_counts()["hits"]}

    # ---------------------------------------------------------- window
    # the requests whose timed programs are held against the reference:
    # the first LONG and the first SHORT admitted in the window (one at a
    # time: ``InferenceEngine.watch``)
    lengths = sorted(int(n) for n in t["documents"])
    wanted_of = {"long": int(t["check_long"]), "short": int(t["check_short"])}
    watched: List[int] = []

    def wanted(req) -> bool:
        kind = "long" if req.prompt.size > lengths[0] else "short"
        if not wanted_of[kind]:
            return False
        wanted_of[kind] -= 1
        watched.append(req.rid)
        return True

    engine.watch(wanted)
    draws = schedule(t, ctx.seed)
    live: Dict[int, _Live] = {}
    finished: List[_Live] = []
    context_samples: List[tuple] = []   # (time, live context tokens, running)
    window_samples: List[tuple] = []    # (time, rows inside windows, running)
    refused = 0
    trace_at = max(0.0, ctx.seconds - float(t["trace_seconds"]))
    traced = False
    before = _counters(engine)
    engine.stats.window_rows_resident_max = 0
    clients = int(t["clients"])
    window = int(ctx.config["sliding_window_size"])
    t_w0 = clock()
    setup_s = t_w0 - ctx.t_start

    def submit(draw):
        nonlocal refused
        prompt = tail_tokens(draw, cfg.vocab_size)
        if draw.document >= 0:
            prompt = np.concatenate([docs[draw.document], prompt])
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
                now = clock()
                context_samples.append(
                    (now, sum(r.prompt_len + r.seen for r in running),
                     len(running)))
                window_samples.append(
                    (now, sum(min(r.prompt_len + r.seen, window)
                              for r in running), len(running)))
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
        # every output token DELIVERED inside the window, those of the
        # requests still running at its end too (module docstring)
        "serve_tokens_per_s": delivered_in_window / window_s,
    }

    # ----------------------------------------------------------- checks
    admitted = [r for r in everyone if r.req.output]
    admitted_docs = sum(int(t["documents"][r.draw.document])
                        for r in admitted if r.draw.document >= 0)
    moved = {k: drained[k] - before[k] for k in drained}
    kinds = engine.cache_nbytes_by_kind
    ring_rows = engine._blockmgr.windows.geometry.rows
    checks = {
        "all_requests_done": not failed and refused == 0,
        "books_balance": attempted == len(done_in_window) + len(
            [r for r in in_window if r.req.state != "Done"])
        + in_flight_at_end + refused,
        "output_lengths_as_drawn": all(
            len(r.req.output) == r.draw.output_len for r in everyone
            if r.req.state == "Done"),
        # every admission behind a document found its blocks in the prefix
        # cache and its window rows in the store
        "documents_stayed_cached":
            moved["engine.prefix_shared_tokens"] == admitted_docs,
        "every_long_admission_warm":
            moved["engine.window_warm_starts"] == len(
                [r for r in admitted if r.draw.document >= 0]),
        "no_cold_start_in_window":
            moved["engine.window_cold_fallbacks"] == 0,
        "window_rows_within_the_ring":
            0 < engine.stats.window_rows_resident_max <= ring_rows,
        "window_rows_resident_max": engine.stats.window_rows_resident_max,
        "cache_nbytes_by_kind": kinds,
        "requests_done_in_window": len(done_in_window),
        "in_flight_at_window_end": in_flight_at_end,
        "tokens_of_requests_done_in_window":
            sum(len(r.req.output) for r in done_in_window),
    }
    full = next(s for s in cfg.layer_specs if not s.window)
    shapes = {"max_slots": int(eng["max_slots"]),
              "chunk": int(eng["chunk"]), "layers": cfg.num_layers,
              "prefill_chunk": chunk,
              "index_dim": cfg.index_head_dim,
              "index_bytes_per_element": jnp.dtype(cfg.dtype).itemsize,
              "latent_layers": len(engine._cache["latent_pool"]),
              "latent_row_bytes": int(
                  engine._cache["latent_pool"][0].shape[-1]
                  * jnp.dtype(cfg.dtype).itemsize),
              "latent_heads": full.num_heads,
              "window": window,
              "window_layers": len(engine._cache["window_ring"]),
              "window_row_bytes": int(
                  engine._cache["window_ring"][0].shape[-1]
                  * jnp.dtype(cfg.dtype).itemsize),
              "cache_nbytes": engine.cache_nbytes,
              "window_cache_nbytes": kinds["window"]}
    delivered = {(r.prompt_len, tuple(r.req.output)) for r in everyone}
    mine = {(e["request"].prompt.size, tuple(e["request"].output))
            for e in engine.witness_log}
    checks["watched_as_delivered"] = mine <= delivered
    seen = Witnessed(engine.witness_log, chunk)
    checks["watched_a_long_and_a_short"] = sorted(
        x["request"].prompt.size > lengths[0] for x in seen.requests) == [
            False] * int(t["check_short"]) + [True] * int(t["check_long"])
    # the reference needs the room the engine's weights and pools hold
    engine.witness_log.clear()
    del adapter, router
    engine.params = engine._cache = None
    # (``drivers/serve_latent.py``: the watchdog's 300 s start again here)
    faulthandler.dump_traceback_later(300, repeat=True, file=sys.stderr)
    ctx.say(f"reference check: {seen.watched} watched requests of "
            f"{[x['tokens'].size for x in seen.requests]} tokens")
    limits = limits_of(t)
    checks.update(reference_check(cfg, params, ctx.config, seen, limits,
                                  lengths))
    if os.environ.get("PERFBENCH_CONTROLS"):
        # the builder's controls (perfbench/controls_dots3.py): the same
        # comparison against a reference with one fault planted, each of
        # which has to come out as not correct.  Readings only.
        from perfbench import controls_dots3

        # nine more passes of the reference outlive the watchdog's 300 s,
        # and its dump has ENDED a run (drivers/serve_latent.py): off
        faulthandler.cancel_dump_traceback_later()
        checks["controls"] = controls_dots3.readings(
            ctx, lambda fault: reference_check(
                cfg, params, ctx.config, seen, limits, lengths, fault))
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
        "samples": {"context": context_samples, "window": window_samples},
        "shapes": shapes,
        "trace": trace,
        "correct": ok,
        "checks": checks,
        "attempted": attempted,
        "failed": len(failed) + refused,
    }
