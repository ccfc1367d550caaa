"""Long answers against long cached documents: requests through
``ServingRouter`` into one ``InferenceEngine`` serving one chip's share of
a latent-attention model that attends to its WHOLE context at decode
(``sarvam-105b-serve``), in a closed loop of the traffic file's
``clients``.  The schedule, the documents and the tails are
``drivers/serve_sparse.py``'s (imported, not edited): ONE cycle of
``cycle`` (document, tail, output) draws fixed by the file's ``base_seed``;
``--seed`` permutes the draws within the groups the file names and decides
all token content, never a length.

``serve_tokens_per_s`` is every output token DELIVERED inside the window
over the window's seconds, those of requests still running at its end
included, as ``serve_sparse`` counts and for its reason: a request lasts a
third of the window or more and most of the 48 are in flight when it ends.

Before a row is written the latent pools are filled with
``controls_sarvam.POISON`` (set-up; the engine's programs are not
touched): a row the program may not attend (behind a slot's length in its
last page, of a page nobody wrote) would otherwise be one quiet row among
24 000 and could be attended unseen.

``correct`` is four comparisons, every limit below with its reason.  What
(a), (b) and (d) compare is what the engine's TIMED programs did inside
the window: the engine is asked to ``watch`` (``InferenceEngine.watch``)
the first ``check_sample`` requests admitted on the longest document, one
at a time, and its own prefill-chunk and decode-chunk programs hand back,
with every dispatch that advances the watched slot, the slot's LOGITS over
the vocabulary slice and the first sparse MLP's input and output.  No
program is compiled for the check.  The reference
(``perfbench/reference_sarvam.py``, float32, un-absorbed, no cache) takes
the document and the watched requests' tails + outputs as ONE packed
batch, the document's 30 k positions once: one full forward.

(a) the logits of every decode forward of the watched requests (and of
    the prompt chunk that gave their first token) against the reference's
    at the same position, teacher-forced: the root mean square of the
    difference over the slice, a position (the reference's logits are
    N(0, 1) by the head's initialisation, so this is a relative error):
    the 90th percentile over positions within ``LOGIT_RMS_P90``, the
    worst within ``LOGIT_RMS_WORST``.  Held to what is GROSS: behind the
    first routing bf16 changes picks, and the logits wander (the limits'
    comment has the arithmetic and the readings).
(b) the delivered tokens: each one's logit in the reference against the
    reference's largest (greedy decoding emits the program's own argmax),
    the 90th percentile within ``TOKEN_DEFICIT_P90``, the worst within
    ``TOKEN_DEFICIT_WORST``.
(c) the books: every request done, lengths as drawn, nothing compiled in
    the window, every request's document found in the prefix cache, the
    watched requests' tokens in the engine the tokens the router
    delivered.
(d) the first sparse layer as those two programs ran it (512-row chunks;
    32-row decode forwards): its normed INPUT against the reference's own
    at the same positions (``SPARSE_IN_REL``: everything before the first
    routing, two attention blocks at depth and the dense MLP; the limit
    that a lower PRECISION breaks), and its OUTPUT against the
    reference's MLP on that same input (``SPARSE_MLP_REL``; the routed sum
    alone ``SPARSE_ROUTED_REL``: one chip's experts are a quarter of the
    picks, and a wrong routed sum moves the logits little).

``perfbench/controls_sarvam.py`` plants one fault at a time in the
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

from perfbench import reference_sarvam
from perfbench.drivers.serve_sparse import (
    _Live, _stamp, document_tokens, schedule, tail_tokens)
from perfbench.harness import Context
from perfbench.weights import fold_seed
from perfbench.weights_sarvam import SeededSarvamParams

# Every limit lies between two readings on the chip (my chip runs, PR 41;
# PERF.md section 6 has them all): the largest the engine's timed programs
# give over 27 runs of as many seeds (598-907 checked positions each), and
# what they give against a reference with one fault planted
# (``perfbench/controls_sarvam.py``, seeds 2147484101 and 2147484104; a
# program is as far from a wrong reference as a wrong program from the
# right one).
#
# What the logits can and cannot hold.  With seeded random weights the
# router's 8th and 9th scores of 128 lie close, so bf16 changes a pick now
# and then, and a pick that falls on or off a HELD expert adds or removes
# a whole expert's output (x 2.5 / 8): past the first sparse layer bf16
# hidden states wander from float32 ones.  WITNESSED, not inferred: the
# REFERENCE ITSELF with bf16 where the program has it
# (``controls_sarvam.py bf16_as_served``) lies 0.204 (90th percentile of
# positions) / 0.567 (worst) from the float32 reference in the logits' RMS
# over the slice, where the program reads 0.199-0.254 / 0.49-0.67; ahead of
# the first routing the two agree to 0.74 % and the program to 0.92 % ((d)
# below), and every expert layer on ITS OWN input to 0.64 %.  The logits
# therefore hold what is gross: plain RoPE 1.08-1.09 / 1.20-1.25 (p90 /
# worst), no m^2 1.03 / 1.21-1.25, no shared expert 1.16 / 1.19-1.20, a
# dead row attended 1.43 / 1.45.  NOT seen here: the softmax in bf16
# (0.23-0.24 / 0.54-0.62): (d)'s input is for that.
LOGIT_RMS_P90 = 0.5
LOGIT_RMS_WORST = 0.9
# (b) An emitted token's reference logit against the reference's largest:
# the program's 0.016-0.076 (p90) / 0.92-2.49 (worst: an extreme of ~900
# near-ties a run); plain RoPE 3.42-3.56 / 4.96-5.78, no m^2 3.25-3.31 /
# 4.69-5.41, no shared expert 3.99-4.06 / 5.64-5.95, a dead row 5.59-5.67
# / 7.35-7.79 (the softmax in bf16 0.046-0.076 / 1.40-1.52: not seen here
# either).
TOKEN_DEFICIT_P90 = 0.5
TOKEN_DEFICIT_WORST = 3.5
# (d) The first sparse layer's normed INPUT against the reference's own at
# the same positions, relative error a token (median): everything ahead
# of the first routing (the embedding, two attention blocks at a depth of
# 30 k, the dense MLP), so nothing discrete is in it and it is STEADY:
# 0.00912-0.00932 in both programs over the 27 runs, 136-837 tokens each.
# The softmax in bf16 (scores, exponentials and sums; the nearest
# precision below the float32 stated) reads 0.01139-0.01161 (chunk) /
# 0.01143-0.01147 (decode): the one limit that precision breaks.  Plain
# RoPE 0.58-0.59, no m^2 0.52, a dead row 1.41.
SPARSE_IN_REL = 0.0104
# Its OUTPUT against the reference's MLP on that SAME input: the whole
# output 0.0064-0.0065 median relative error a token in both programs, the
# routed sum alone 0.0152-0.0163 (136-837 tokens picked a held expert);
# no shared expert 2.26-2.28 / 2.21-2.25.  (``glm5-serve`` read fp8
# weights at 0.047 / 0.15 under the same two limits, PR 34.)
SPARSE_MLP_REL = 0.02
SPARSE_ROUTED_REL = 0.05

#: the reference's packed batch is padded to whole multiples of this
PAD_TO = 512


def model_config(config: dict, max_seq_len: int):
    """The configuration file as the program's ``LlamaConfig``."""
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig, RopeSpec

    dep = config["deployment"]
    reference_sarvam.dims_of(config)       # refuses what it does not compute
    y = config["rope_scaling"]
    m_all = reference_sarvam.yarn_mscale(y["factor"], y["mscale_all_dim"])
    return LlamaConfig.sarvam_105b(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_attention_heads"],
        max_seq_len=max_seq_len,
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        qk_norm=bool(config["use_qk_norm"]),
        rope_scaling=RopeSpec(
            theta=float(config["rope_theta"]),
            yarn_factor=float(y["factor"]),
            yarn_original_max_len=int(
                y["original_max_position_embeddings"]),
            yarn_beta_fast=float(y["beta_fast"]),
            yarn_beta_slow=float(y["beta_slow"]),
            attention_factor=reference_sarvam.yarn_mscale(
                y["factor"], y["mscale"]) / m_all),
        attn_scale_mult=m_all * m_all,
        num_experts=config["num_experts_published"],
        moe_experts_held=tuple(config["experts_held"]),
        moe_top_k=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_shared_width=config["moe_intermediate_size"]
        * config["num_shared_experts"],
        moe_routed_scale=float(config["routed_scaling_factor"]),
        moe_first_dense=config["first_k_dense_replace"],
        dtype=jnp.dtype(dep["compute_dtype"]),
        param_dtype=jnp.dtype(dep["param_dtype"]),
    )


def _build(ctx: Context):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.serving.engine import InferenceEngine
    from dlrover_tpu.serving.router import (
        ContinuousBatchScheduler,
        ServingRouter,
    )
    from dlrover_tpu.serving.router.replica import InferenceEngineAdapter
    from perfbench.controls_sarvam import POISON

    eng = ctx.config["deployment"]["engine"]
    max_len, step = int(eng["max_len"]), int(eng["prefill_bucket_step"])
    cfg = model_config(ctx.config, max_seq_len=max_len)
    params = SeededSarvamParams(cfg, ctx.seed)
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
    # every row of the latent pools LOUD until a program writes it (module
    # docstring), a layer at a time: the zeros go as the poison comes
    pools = engine._cache["latent_pool"]
    for i in range(len(pools)):
        pools[i] = jnp.full_like(pools[i], POISON)
    jax.block_until_ready((engine.params, pools))
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=int(eng["block_size"])))
    return cfg, params, engine, router, InferenceEngineAdapter(engine)


def _counters(engine) -> dict:
    s = engine.stats
    out = {"engine.decode_seconds": s.decode_seconds,
           "engine.prefill_seconds": s.prefill_seconds}
    for name in ("decode_forwards", "prefill_calls", "prefill_chunks",
                 "generated_tokens", "kv_rows_live", "kv_rows_streamed",
                 "moe_picks", "moe_picks_held"):
        out["engine." + name] = float(getattr(s, name))
    out["engine.prefix_shared_tokens"] = engine.prefix_stats()[
        "prefix_shared_tokens"]
    return out


def _to_host(log: List[dict], chunk: int) -> None:
    """The engine's witness log, as each router step leaves it: what the
    programs handed back moves to the host (a decode chunk's logits are
    2 MB on the device, a request's a quarter of a gigabyte), and of a
    request's prompt chunks behind its first only the last one's logits
    stay (the first token's)."""
    first = {}
    for e in log:
        if e["kind"] == "run":
            first.setdefault(id(e["request"]), e)
    for e in log:
        if isinstance(e["seen"].get("logits"), np.ndarray):
            continue
        seen = {k: np.asarray(v) for k, v in e["seen"].items()}
        if e["kind"] == "run" and first[id(e["request"])] is not e:
            seen = {"logits": seen["logits"]}
        e["seen"] = seen
    log[:] = [e for e in log if e["kind"] != "run"
              or first[id(e["request"])] is e
              or e["start"] + chunk >= e["request"].prompt.size]


class Witnessed:
    """What the engine's timed programs handed back for the watched
    requests (``InferenceEngine.watch``; ``engine.witness_log`` as the
    window and the drain left it), laid out as ONE packed batch for the
    reference: the document once, then each request's tail + output as a
    segment of its own.

    ``tokens`` / ``positions`` / ``segments``: the batch, right-padded to
    whole multiples of ``pad_to`` by a segment nobody checks.
    ``logits_at`` / ``logits``: the batch indices of the queries whose
    logits the programs handed back, and those logits [n, V].  A kind of
    program (``"run"``: the first prefill chunk of a tail, ``"decode"``:
    every decode forward that fed on a delivered token): ``queries[kind]``
    the batch indices of its queries, ``sparse[kind]`` the first sparse
    MLP's (input, output) [n, E].  The token a checked position's logits
    chose is the batch's next."""

    def __init__(self, doc: np.ndarray, log: List[dict], pad_to: int,
                 chunk: int):
        d = doc.size
        requests = []
        for e in log:
            r = e["request"]
            if r.done and not any(r is x for x in requests):
                requests.append(r)
        self.watched = len(requests)
        self.requests = requests
        toks, pos, seg, offset = [doc], [np.arange(d)], [np.zeros(d)], {}
        at = d
        for i, r in enumerate(requests):
            own = np.concatenate([r.prompt[d:],
                                  np.asarray(r.output, np.int32)])
            offset[id(r)] = at
            toks.append(own)
            pos.append(d + np.arange(own.size))
            seg.append(np.full(own.size, i + 1))
            at += own.size
        pad = -at % pad_to
        toks.append(np.zeros(pad, np.int32))
        pos.append(d + np.arange(pad))
        seg.append(np.full(pad, len(requests) + 1))
        self.tokens = np.concatenate(toks).astype(np.int32)
        self.positions = np.concatenate(pos).astype(np.int32)
        self.segments = np.concatenate(seg).astype(np.int32)
        self.queries = {"run": [], "decode": []}
        self.sparse = {"run": [], "decode": []}
        logits_at, logits = [], []
        for e in log:
            r, seen, kind = e["request"], e["seen"], e["kind"]
            if id(r) not in offset:
                continue
            got = np.asarray(seen["logits"], np.float32)
            if kind == "run":
                n = seen["sparse_in"].shape[0] if "sparse_in" in seen else 0
                at = e["start"] + np.arange(n)
                keep = (at >= d) & (at < r.prompt.size)
                if e["start"] + chunk >= r.prompt.size:
                    # the prompt's last chunk: its logits chose the first
                    # output token
                    logits_at.append(
                        np.array([offset[id(r)] + r.prompt.size - 1 - d]))
                    logits.append(got[None])
            else:
                at = e["start"] + np.arange(got.shape[0])
                # a forward at position p feeds on token p: the last
                # delivered token is fed to nothing that counts
                keep = at <= r.prompt.size + len(r.output) - 2
                logits_at.append(offset[id(r)] + at[keep] - d)
                logits.append(got[keep])
            if "sparse_in" in seen and keep.size:
                self.queries[kind].append(offset[id(r)] + at[keep] - d)
                self.sparse[kind].append(tuple(
                    np.asarray(seen[k], np.float32).reshape(
                        keep.size, -1)[keep]
                    for k in ("sparse_in", "sparse_out")))
        self.logits_at = np.concatenate(logits_at) if logits_at \
            else np.zeros(0, np.int64)
        self.logits = np.concatenate(logits) if logits else None
        for kind in ("run", "decode"):
            n = len(self.queries[kind])
            self.queries[kind] = np.concatenate(self.queries[kind]) \
                if n else np.zeros(0, np.int64)
            self.sparse[kind] = tuple(
                np.concatenate(x) for x in zip(*self.sparse[kind])) \
                if self.sparse[kind] else None


def sparse_layer_error(h, got, m, dims) -> dict:
    """(d)'s second half: the first sparse MLP as the engine's timed
    programs ran it (``h``: its normed input, ``got``: its output, a token
    a row) against the reference's MLP on that SAME input.  Relative error
    a token of the whole output (median) and of the ROUTED sum alone (the
    output less the reference's shared expert; median over the tokens
    that picked a held expert)."""
    import jax.numpy as jnp

    h = jnp.asarray(h, jnp.float32)
    got = jnp.asarray(got, jnp.float32)
    want = reference_sarvam.mlp(h, m, dims)
    shared = reference_sarvam.shared_expert(h, m)
    weights = reference_sarvam._route(
        h, m["router"]["kernel"], m["select_bias"],
        reference_sarvam._Dims(dims))
    routed = jnp.sum(weights[:, dims["first"]:dims["first"] + dims["held"]],
                     axis=-1) > 0

    def rel(a, b):
        return jnp.linalg.norm(a - b, axis=-1) / jnp.maximum(
            jnp.linalg.norm(b, axis=-1), 1e-30)

    n = int(jnp.sum(routed))
    return {"mlp_rel": float(jnp.median(rel(got, want))),
            "routed_rel": float(jnp.nanmedian(jnp.where(
                routed, rel(got - shared, want - shared), jnp.nan)))
            if n else None,
            "routed_tokens": n}


def reference_check(cfg, params, config: dict, seen: Witnessed,
                    keep: Optional[dict] = None) -> dict:
    """(a), (b) and (d): one pass of the reference over the packed
    batch.  ``keep`` (the controls') is given the reference's own
    ``logits`` at the checked positions and the first sparse layer's input
    there (``sparse_in``, a kind of program)."""
    import jax.numpy as jnp

    dims = reference_sarvam.dims_of(config)
    out = {"watched_requests": seen.watched}
    verdicts = ("logits_match_reference", "tokens_match_reference",
                "sparse_layer_matches_reference")
    if not seen.watched or seen.logits is None:
        return dict(out, **{v: False for v in verdicts})
    first_sparse = next(i for i, s in enumerate(cfg.layer_specs)
                        if s.mlp == "sparse")
    x = reference_sarvam.embed(jnp.asarray(seen.tokens), params.top())
    for i in range(cfg.num_layers):
        mlp = {} if i == first_sparse else None
        lp = params.layer(i)
        x = reference_sarvam.layer_forward(
            x, lp, dims, seen.positions, seen.segments, mlp)
        if mlp is None:
            continue
        for kind in ("run", "decode"):
            if seen.sparse[kind] is None:
                continue
            h, got = seen.sparse[kind]
            want_h = np.asarray(mlp["mlp_in"][jnp.asarray(
                seen.queries[kind])])
            if keep is not None:
                keep.setdefault("sparse_in", {})[kind] = want_h
            out[f"sparse_in_rel_{kind}"] = float(np.median(
                np.linalg.norm(h - want_h, axis=-1)
                / np.maximum(np.linalg.norm(want_h, axis=-1), 1e-30)))
            out[f"sparse_{kind}"] = sparse_layer_error(
                h, got, lp["mlp"], dims)
        del mlp, lp
    out["sparse_layer_matches_reference"] = all(
        f"sparse_{k}" in out
        and out[f"sparse_in_rel_{k}"] <= SPARSE_IN_REL
        and out[f"sparse_{k}"]["mlp_rel"] <= SPARSE_MLP_REL
        # (no token of these picked a held expert, by the reference's own
        # routing of the same input: nothing routed to hold)
        and (not out[f"sparse_{k}"]["routed_tokens"]
             or out[f"sparse_{k}"]["routed_rel"] <= SPARSE_ROUTED_REL)
        for k in ("run", "decode"))
    # (a) and (b), in blocks of positions (the slice's 65 536 logits a
    # position): the batch's token behind a checked position is the one
    # the program emitted there
    rms, deficits, worst_abs = [], [], 0.0
    for s0 in range(0, seen.logits_at.size, 256):
        at = seen.logits_at[s0:s0 + 256]
        want = np.asarray(reference_sarvam.head_logits(
            x[jnp.asarray(at)], params.top(), cfg.rms_norm_eps))
        if keep is not None:
            keep.setdefault("logits", []).append(want)
        diff = seen.logits[s0:s0 + 256] - want
        rms.append(np.sqrt(np.mean(diff * diff, axis=-1)))
        worst_abs = max(worst_abs, float(np.abs(diff).max()))
        deficits.append(want.max(axis=-1) - want[
            np.arange(at.size), seen.tokens[at + 1]])
    rms, deficits = np.concatenate(rms), np.concatenate(deficits)
    out.update({
        "checked_requests": len(seen.requests),
        "checked_positions": int(rms.size),
        "checked_longest_prompt": max(r.prompt.size for r in seen.requests),
        "logit_rms_p90": float(np.percentile(rms, 90)),
        "logit_rms_worst": float(rms.max()),
        "logit_abs_worst": worst_abs,
        "token_deficit_p90": float(np.percentile(deficits, 90)),
        "token_deficit_worst": float(deficits.max()),
        "logits_match_reference": bool(
            np.percentile(rms, 90) <= LOGIT_RMS_P90
            and rms.max() <= LOGIT_RMS_WORST),
        "tokens_match_reference": bool(
            np.percentile(deficits, 90) <= TOKEN_DEFICIT_P90
            and deficits.max() <= TOKEN_DEFICIT_WORST)})
    return out


def run(ctx: Context) -> dict:
    import jax.numpy as jnp

    from dlrover_tpu.utils.compile_cache import cache_counts

    clock = time.perf_counter
    t, eng = ctx.traffic, ctx.config["deployment"]["engine"]
    if t.get("loop", "closed") != "closed":
        raise ValueError("the serve_latent driver runs closed loops only")

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
    drain([router.submit(doc, 1) for doc in docs], 600.0)
    warm_rng = np.random.RandomState(1)
    chunk = int(eng["prefill_chunk"])
    drain([router.submit(np.concatenate(
        [doc, warm_rng.randint(0, cfg.vocab_size, chunk // 2 + 3 * i)
         .astype(np.int32)]), int(eng["chunk"]) + 2)
        for i, doc in enumerate(docs)], 600.0)
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
    # document with a tail of at most ``check_tail_max`` and an output of
    # at most ``check_output_max`` (one at a time:
    # ``InferenceEngine.watch``); the packed batch of the reference is the
    # document and their tails + outputs
    longest = int(np.argmax(t["documents"]))
    watched: List[int] = []

    def wanted(req) -> bool:
        head = docs[longest]
        if len(watched) >= int(t["check_sample"]) \
                or req.max_new_tokens > int(t["check_output_max"]) \
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
              "latent_row_bytes": int(
                  engine._cache["latent_pool"][0].shape[-1]
                  * jnp.dtype(cfg.dtype).itemsize)}
    # what the router delivered is what is checked: a watched request's
    # tokens in the engine are the tokens of the benchmark's own record
    delivered = {(r.prompt_len, tuple(r.req.output)) for r in everyone}
    mine = {(e["request"].prompt.size, tuple(e["request"].output))
            for e in engine.witness_log}
    checks["watched_as_delivered"] = mine <= delivered
    seen = Witnessed(docs[longest], engine.witness_log,
                     1 if ctx.rehearse else PAD_TO, chunk)
    # the reference needs the room the engine's weights and pools hold
    engine.witness_log.clear()
    del adapter, router
    engine.params = engine._cache = None
    # ``perfbench/run.py`` arms ``faulthandler`` to dump every thread's
    # frames at 300 s of a run, and a dump taken while this thread runs
    # Python (the reference's eager dispatch) has ENDED a run: exit 139,
    # the dump cut mid-line (my chip run, PR 41; PERF.md section 7 (g)).
    # A run on an empty compile cache gets here at ~150 s and needs ~105 s
    # more: its 300 s start again here, so a hang still says where it
    # stands and a slow machine's cold run does not die of the watchdog.
    faulthandler.dump_traceback_later(300, repeat=True, file=sys.stderr)
    ctx.say(f"reference check: {seen.watched} watched requests, "
            f"{seen.tokens.size} positions, "
            f"{seen.logits_at.size} logit rows")
    checks.update(reference_check(cfg, params, ctx.config, seen))
    if os.environ.get("PERFBENCH_CONTROLS"):
        # the builder's controls (perfbench/controls_sarvam.py): the same
        # comparison against a reference with one fault planted, each of
        # which has to come out as not correct.  Readings only.
        from perfbench import controls_sarvam

        checks["controls"] = controls_sarvam.readings(
            ctx, lambda keep=None: reference_check(
                cfg, params, ctx.config, seen, keep))
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
