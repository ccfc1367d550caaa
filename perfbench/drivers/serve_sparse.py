"""Questions against long cached documents: requests through
``ServingRouter`` into one ``InferenceEngine`` serving one chip's share of
a latent-attention model with a learned selection of keys and sparse
experts (``glm5-serve``), in a closed loop of the traffic file's
``clients``, as ``drivers/serve.py`` runs the dense decoder.

A request's prompt is one of the traffic file's ``documents`` (prefilled
once during set-up and held by the engine's prefix cache) and a unique
tail.  The schedule is the benchmark's own: ONE cycle of ``cycle``
(document, tail, output) draws fixed by the file's ``base_seed`` (lengths
by ``perfbench/loadgen.py``'s arithmetic, documents Zipf over ranks the
base seed permutes); ``--seed`` permutes the draws' order in every cycle
(within the groups the traffic file names) and decides all token content,
never a length.

``serve_tokens_per_s`` here is every output token DELIVERED inside the
window over the window's seconds, the tokens of requests still running at
its end included: a request lasts a quarter of the window, 41-47 of the 48
are in flight when it ends, and counting only the requests that also
FINISHED inside it (``drivers/serve.py``'s count, right where a window
completes hundreds of short requests) read 180.4-193.0 over six seeds
whose delivered tokens read 219.7-222.1 (my chip runs, PR 34; the count of
finished requests' tokens stays in ``checks``).

``correct`` is four comparisons, every limit below with its reason.  What
(a), (b) and (d) compare is what the engine's TIMED programs did inside
the window: the engine is asked to ``watch`` (``InferenceEngine.watch``)
the first ``check_sample`` requests admitted on the longest document, one
at a time, and its own prefill-chunk and decode-chunk programs hand back,
with every dispatch that advances the watched slot, the rows each query
attended to and the first sparse MLP's input and output.  No program is
compiled for the check.  The reference (``perfbench/reference_glm5.py``,
float32) takes the document and the watched requests' tails + outputs as
ONE packed batch, the document's 30 k positions once.

(a) every emitted token of the watched requests: its logit in the
    reference, teacher-forced over document + tail + output (so the
    shared prefix is checked with it), within ``LOGIT_ATOL`` of the
    reference's largest, and 9 in 10 within ``LOGIT_P90``.
(b) the selection itself, of the prefill-chunk program (the first chunk
    of each watched tail) and of the decode program (every forward of
    every watched request), each on its own: a layer's chosen rows
    against the reference's ``S_t``: in layer 0, whose input both sides
    share, a mean overlap of ``SELECTION_OVERLAP_FIRST`` and every row
    only one side chose within ``SELECTION_MARGIN_FIRST`` (standard
    deviations of that query's scores) of the reference's threshold; in
    every layer ``SELECTION_OVERLAP_DEEPER`` and no row the query cannot
    see.
(c) the books: every request done, lengths as drawn, nothing compiled in
    the window, every request's document found in the prefix cache, and
    the watched requests' tokens in the engine the tokens the router
    delivered.
(d) the first sparse MLP as those two programs ran it (512-row chunks;
    32-row decode forwards), against the reference's MLP on the same
    input: the routed sum of one chip's experts is a sixteenth of the
    picks, and (a) does not see it.

``perfbench/controls_glm5.py`` plants one fault at a time in the
reference and reads the same comparison (``PERFBENCH_CONTROLS=1``); each
has to come out as not correct, and ``tests/test_sparse_serving.py``
plants them on the CPU, in the reference and in one program at a time.
"""

from __future__ import annotations

import collections
import os
import random
import types
import time
from typing import Dict, Iterator, List, Optional

import numpy as np

from perfbench import loadgen, reference_glm5
from perfbench.harness import Context
from perfbench.weights import fold_seed
from perfbench.weights_glm5 import SeededGlm5Params

# Every limit lies between two readings on the chip (my chip runs, PR 34;
# PERF.md section 6 has them all): what the engine's timed programs give
# over the seeds, and what they give against a reference with one fault
# planted (``perfbench/controls_glm5.py``, two seeds; a program is as far
# from a wrong reference as a wrong program from the right one).
#
# (a) An emitted token's reference logit against the reference's largest
# at that position.  Greedy decoding emits the system's own argmax, and
# with seeded random weights this model's logits move far more under bf16
# than the dense decoder's (``drivers/serve.py``: 0.03): attention over a
# selection is close to a MEAN over the chosen rows, and bf16 swaps the
# rows near the threshold (a tenth to a quarter of them in the deeper
# layers).  That this is bf16 and not the program is witnessed: the
# REFERENCE with bf16 where the program has it (``bf16_as_served``) emits
# tokens whose float32 deficits read 0.24 / 1.01 and chooses rows that
# overlap the float32 reference's 0.996, 0.90, 0.85, 0.81, 0.78 by layer;
# the program reads 0.26-0.29 / 0.98-1.13 (158 and 515 positions; 0.14-0.38
# / 0.35-1.31 over the 22 earlier runs of ~70) and 0.996, 0.89, 0.84, 0.80,
# 0.77.  Faults: fp8 weights 0.83-1.08 / 1.6-2.1, fp8 index keys 0.69-0.87
# / 1.4-1.7, no ReLU 3.2-3.5 / 5.1-5.5, not causal 1.4-2.1 / 4.6-5.1, no
# shared expert 3.5 / 4.9.  Two limits: the worst, which a dropped layer or
# a wrong mask breaks, and the 90th percentile, which a lower precision
# breaks.  NOT seen here: 7 of 8 picks (0.26-0.29) and no x 2.5 (0.37-0.43):
# (d) is for those.
LOGIT_ATOL = 2.5
LOGIT_P90 = 0.5
# (b) The rows a timed program chose against the reference's ``S_t``, the
# prefill chunk's and the decode forward's each on its own.  In layer 0
# both sides see the same input: overlap 0.9955-0.9956 in both programs,
# the furthest stray row 0.027-0.028 (chunk) and 0.018-0.019 (decode)
# standard deviations of a query's scores from the reference's threshold;
# fp8 index keys 0.975 / 0.12-0.17, fp8 weights 0.953-0.971 / 0.16-0.29,
# no ReLU 0.58, no RoPE 0.33, no head weights 0.12, not causal 0.94-0.95 /
# 3.3-3.9.  Deeper layers inherit bf16's noise in their input (0.89, 0.84,
# 0.80, 0.76-0.77 by layer, both programs): held to a share no wrong
# indexer reaches (fp8 keys 0.56, fp8 weights 0.42-0.52, the others 0.07-
# 0.28).
SELECTION_OVERLAP_FIRST = 0.985
SELECTION_MARGIN_FIRST = 0.08
SELECTION_OVERLAP_DEEPER = 0.65
# ``selection_stats`` counts stray rows beyond this many standard
# deviations (reported, not judged)
SELECTION_MARGIN = 0.5
# (d) The first sparse MLP on its own input (``sparse_layer_error``), the
# 512-row chunk's and the 32-row decode forward's each on its own: the
# whole output 0.0064 median relative error a token in both, the routed
# sum alone 0.021 (521-815 chunk tokens and 67-73 decode tokens picked a
# held expert); with fp8 weights 0.047 / 0.15, 7 of 8 picks - / 0.12, no
# x 2.5 - / 1.50, no shared expert both far off (a wrong routed sum leaves
# the median token, which picks no held expert, as it was).  What the
# logits cannot see here: one chip's experts are a sixteenth of the picks.
SPARSE_MLP_REL = 0.02
SPARSE_ROUTED_REL = 0.05

#: the reference's packed batch is padded to whole multiples of this
PAD_TO = 1024

#: one request of the schedule; ``document`` indexes the traffic file's
#: ``documents``
Draw = collections.namedtuple(
    "Draw", "index document tail_len output_len content_seed")


def cycle_draws(traffic: dict) -> List[tuple]:
    """The fixed multiset of one cycle: (document, tail, output).  A
    function of the traffic file alone."""
    pairs = loadgen.cycle_draws(traffic)
    rng = random.Random(int(traffic["base_seed"]) + 1)
    n = len(traffic["documents"])
    ranks = list(range(n))
    rng.shuffle(ranks)                  # rank r is document ranks[r]
    s = float(traffic["document_choice"]["exponent"])
    weights = [1.0 / (r + 1) ** s for r in range(n)]
    docs = rng.choices(ranks, weights=weights, k=len(pairs))
    return [(d, p, o) for d, (p, o) in zip(docs, pairs)]


def schedule(traffic: dict, seed: int) -> Iterator[Draw]:
    """Cycles of the fixed multiset, each in an order drawn from ``seed``
    (``loadgen.schedule``'s arithmetic), which permutes the draws WITHIN
    consecutive groups of the file's ``seed_permutes_within`` and leaves
    the groups where the base seed put them: whatever the seed, the first
    n draws of a cycle are the same multiset to within a group."""
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


def document_tokens(traffic: dict, seed: int, document: int,
                    vocab: int) -> np.ndarray:
    rng = np.random.RandomState(
        (fold_seed(seed) + 104729 * (document + 1)) % (2**31 - 1))
    return rng.randint(0, vocab, size=int(traffic["documents"][document])
                       ).astype(np.int32)


def tail_tokens(draw: Draw, vocab: int) -> np.ndarray:
    rng = np.random.RandomState(draw.content_seed)
    return rng.randint(0, vocab, size=draw.tail_len).astype(np.int32)


def schedule_bytes(traffic: dict, seed: int, count: int = 128,
                   vocab: int = 19360) -> bytes:
    """The first ``count`` draws and their content as bytes: what 'replays
    byte-identically' is checked on."""
    import itertools

    out = [document_tokens(traffic, seed, d, vocab).tobytes()
           for d in range(len(traffic["documents"]))]
    for d in itertools.islice(schedule(traffic, seed), count):
        out.append(repr(tuple(d)).encode())
        out.append(tail_tokens(d, vocab).tobytes())
    return b"".join(out)


def model_config(config: dict, max_seq_len: int):
    """The configuration file as the program's ``LlamaConfig``."""
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LlamaConfig

    dep = config["deployment"]
    if config["n_group"] != 1 or config["scoring_func"] != "sigmoid" \
            or config["topk_method"] != "noaux_tc" \
            or config["n_shared_experts"] != 1 \
            or not config["rope_interleave"] \
            or not config["indexer_rope_interleave"] \
            or config["qk_head_dim"] != (config["qk_nope_head_dim"]
                                         + config["qk_rope_head_dim"]):
        raise ValueError("not what perfbench/reference_glm5.py computes")
    return LlamaConfig.glm5(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        max_seq_len=max_seq_len,
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        index_n_heads=config["index_n_heads"],
        index_head_dim=config["index_head_dim"],
        index_topk=config["index_topk"],
        num_experts=config["n_routed_experts_published"],
        moe_experts_held=tuple(config["experts_held"]),
        moe_top_k=config["num_experts_per_tok"],
        moe_norm_topk_prob=bool(config["norm_topk_prob"]),
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_shared_width=config["moe_intermediate_size"],
        moe_routed_scale=float(config["routed_scaling_factor"]),
        moe_first_dense=config["first_k_dense_replace"],
        dtype=jnp.dtype(dep["compute_dtype"]),
        param_dtype=jnp.dtype(dep["param_dtype"]),
    )


class _Live:
    """The benchmark's own record of one request."""

    __slots__ = ("draw", "req", "seen", "prompt_len")

    def __init__(self, draw, req, prompt_len):
        self.draw, self.req, self.prompt_len = draw, req, prompt_len
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
    max_len, step = int(eng["max_len"]), int(eng["prefill_bucket_step"])
    cfg = model_config(ctx.config, max_seq_len=max_len)
    params = SeededGlm5Params(cfg, ctx.seed)
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


def _stamp(live: Dict[int, _Live], finished: List[_Live]) -> None:
    for rid in list(live):
        rec = live[rid]
        rec.seen = len(rec.req.output)
        if rec.req.state not in ("Queued", "Running"):
            finished.append(rec)
            del live[rid]


def _first_chunks_only(log: List[dict]) -> None:
    """Drop from the engine's witness log the prefill chunks behind a
    request's first (23 MB on the device each; (b) and (d) read the
    first chunk of a tail and every decode forward)."""
    first = {}
    for e in log:
        if e["kind"] == "run":
            first.setdefault(id(e["request"]), e)
    log[:] = [e for e in log
              if e["kind"] != "run" or first[id(e["request"])] is e]


def _counters(engine) -> dict:
    s = engine.stats
    out = {"engine.decode_seconds": s.decode_seconds,
           "engine.decode_forwards": float(s.decode_forwards),
           "engine.prefill_seconds": s.prefill_seconds,
           "engine.prefill_calls": float(s.prefill_calls),
           "engine.prefill_chunks": float(s.prefill_chunks),
           "engine.generated_tokens": float(s.generated_tokens)}
    for name in ("dsa_rows_live", "index_rows_scanned",
                 "attn_rows_selected", "moe_picks", "moe_picks_held"):
        out["engine." + name] = float(getattr(s, name))
    out["engine.prefix_shared_tokens"] = engine.prefix_stats()[
        "prefix_shared_tokens"]
    return out


def selection_stats(system, picked) -> List[dict]:
    """``system`` [layers, count, rows] bool against the reference's
    ``picked`` (a layer: scores [count, T], chosen [count, T]), a layer:
    ``overlap`` (mean share of a query's reference rows the system chose
    too), and of the rows only one side chose, how far the reference's
    score lies from the reference's threshold in units of the standard
    deviation of that query's live scores: the largest (``margin_max``)
    and the share of them beyond ``SELECTION_MARGIN`` (``beyond``).
    ``unseen``: rows chosen that the query cannot see at all."""
    import jax.numpy as jnp

    out = []
    for layer, (scores, chosen) in enumerate(picked):
        sys_l = jnp.asarray(system[layer][:, :scores.shape[1]])
        live = scores > -jnp.inf
        n = jnp.sum(live, axis=-1)
        kth = jnp.min(jnp.where(chosen, scores, jnp.inf), axis=-1)
        mean = jnp.sum(jnp.where(live, scores, 0.0), axis=-1) / n
        std = jnp.sqrt(jnp.sum(jnp.where(
            live, (scores - mean[:, None]) ** 2, 0.0), axis=-1) / n)
        differ = (sys_l != chosen) & live
        off = jnp.where(differ, jnp.abs(scores - kth[:, None])
                        / jnp.maximum(std, 1e-30)[:, None], 0.0)
        out.append({
            "overlap": float(jnp.mean(
                jnp.sum(sys_l & chosen, axis=-1)
                / jnp.maximum(jnp.sum(chosen, axis=-1), 1))),
            "margin_max": float(jnp.max(off)),
            "beyond": float(jnp.sum(off > SELECTION_MARGIN)
                            / jnp.maximum(jnp.sum(differ), 1)),
            "unseen": int(jnp.sum(sys_l & ~live))})
    return out


def sparse_layer_error(cfg, h, got, get_layer, dims) -> dict:
    """(d): the first sparse MLP as the engine's timed programs ran it
    (``h``: its normed input, ``got``: its output, a token a row) against
    the reference's MLP on that SAME input: no noise from the layers
    before it.  Relative error a token of the whole output (median) and
    of the ROUTED sum alone (the output less the reference's shared
    expert; median over the tokens that picked a held expert)."""
    import jax.numpy as jnp

    layer = next(i for i, s in enumerate(cfg.layer_specs)
                 if s.mlp == "sparse")
    m = get_layer(layer)["mlp"]
    h = jnp.asarray(h, jnp.float32)
    got = jnp.asarray(got, jnp.float32)
    want = reference_glm5.mlp(h, m, dims)
    shared = reference_glm5._swiglu(
        h, m["shared_gate"]["kernel"], m["shared_up"]["kernel"],
        m["shared_down"]["kernel"])
    weights = reference_glm5._route(
        h, m["router"]["kernel"], m["select_bias"],
        reference_glm5._Dims(dims))
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


class Witnessed:
    """What the engine's timed programs handed back for the watched
    requests (``InferenceEngine.watch``; ``engine.witness_log`` as the
    window and the drain left it), laid out as ONE packed batch for the
    reference: the document once, then each request's tail + output as a
    segment of its own (``reference_glm5``'s ``positions`` / ``segments``).

    ``tokens`` / ``positions`` / ``segments``: the batch, right-padded to
    whole query blocks by a segment nobody checks.  A kind of program
    (``"run"``: the first prefill chunk of a tail, ``"decode"``: every
    decode forward that fed on a delivered token): ``queries[kind]`` the
    batch indices of its queries, ``chosen[kind]`` [layers, n, T] bool the
    keys each attended to (batch columns), ``sparse[kind]`` the first
    sparse MLP's (input, output) [n, E].  ``emitted``: a request, (the
    batch indices whose logits chose its output tokens, those tokens).
    ``others``: finished requests on the same document that were not
    watched, as (prompt, output): segments behind the watched ones, for
    (a) alone."""

    def __init__(self, doc: np.ndarray, log: List[dict], layers: int,
                 pad_to: int, others=()):
        d = doc.size
        requests = []
        for e in log:
            r = e["request"]
            if r.done and not any(r is x for x in requests):
                requests.append(r)
        self.watched = len(requests)
        requests += [types.SimpleNamespace(prompt=p, output=o)
                     for p, o in others]
        self.requests = requests
        # the batch's tokens [first, watched_end) are the watched
        # requests': the only ones whose selection the reference keeps
        self.first = d
        self.watched_end = d + sum(
            r.prompt.size - d + len(r.output)
            for r in requests[:self.watched])
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
        total = self.tokens.size
        self.emitted = [
            (offset[id(r)] + r.prompt.size - d - 1 + np.arange(
                len(r.output)), np.asarray(r.output, np.int32))
            for r in requests]

        def columns(r, mask):
            """[..., engine positions] -> [..., batch columns]"""
            own = r.prompt.size - d + len(r.output)
            out = np.zeros(mask.shape[:-1] + (total,), bool)
            out[..., :d] = mask[..., :d]
            out[..., offset[id(r)]:offset[id(r)] + own] = \
                mask[..., d:d + own]
            return out

        self.queries = {"run": [], "decode": []}
        self.chosen = {"run": [], "decode": []}
        self.sparse = {"run": [], "decode": []}
        first_chunk = {}
        for e in log:
            if e["kind"] == "run" and id(e["request"]) in offset:
                key = id(e["request"])
                if key not in first_chunk \
                        or e["start"] < first_chunk[key]["start"]:
                    first_chunk[key] = e
        for e in log:
            r, seen, kind = e["request"], e["seen"], e["kind"]
            if id(r) not in offset:
                continue
            if kind == "run":
                if first_chunk[id(r)] is not e:
                    continue
                bits = np.asarray(seen["chosen_bits"])
                mask = np.unpackbits(bits, axis=-1).astype(bool)
                at = e["start"] + np.arange(mask.shape[1])
                keep = (at >= d) & (at < r.prompt.size)
            else:
                got = np.asarray(seen["rows"])      # [forwards, layers, S]
                mask = np.zeros(got.shape[:2] + (total + 1,), bool)
                np.put_along_axis(
                    mask, np.where((got < 0) | (got >= total), total, got),
                    True, axis=-1)
                mask = np.moveaxis(mask[..., :total], 0, 1)
                at = e["start"] + np.arange(got.shape[0])
                # a forward at position p feeds on token p: the last
                # delivered token is fed to nothing that counts
                keep = at <= r.prompt.size + len(r.output) - 2
            self.queries[kind].append(offset[id(r)] + at[keep] - d)
            self.chosen[kind].append(columns(r, mask[:, keep]))
            if "sparse_in" in seen:
                self.sparse[kind].append(tuple(
                    np.asarray(seen[k], np.float32).reshape(
                        keep.size, -1)[keep]
                    for k in ("sparse_in", "sparse_out")))
        for kind in ("run", "decode"):
            n = len(self.queries[kind])
            self.queries[kind] = np.concatenate(self.queries[kind]) \
                if n else np.zeros(0, np.int64)
            self.chosen[kind] = np.concatenate(self.chosen[kind], axis=1) \
                if n else np.zeros((layers, 0, total), bool)
            self.sparse[kind] = tuple(
                np.concatenate(x) for x in zip(*self.sparse[kind])) \
                if self.sparse[kind] else None


def selection_holds(stats: List[dict]) -> bool:
    """(b)'s verdict on one kind of program's per-layer statistics."""
    return bool(stats) and (
        stats[0]["overlap"] >= SELECTION_OVERLAP_FIRST
        and stats[0]["margin_max"] <= SELECTION_MARGIN_FIRST
        and all(s["overlap"] >= SELECTION_OVERLAP_DEEPER
                and s["unseen"] == 0 for s in stats))


def reference_check(cfg, params, config: dict, seen: Witnessed,
                    keep: Optional[dict] = None) -> dict:
    """(a), (b) and (d): one pass of the reference over the packed batch,
    a layer at a time, each layer's selection held against the programs'
    before the next is made.  ``keep`` (the controls') is given the
    reference's own ``chosen`` (a kind: a layer's [n, T] bool) and the
    ``logits`` behind every emitted token."""
    import jax.numpy as jnp

    dims = reference_glm5.dims_of(config)
    out = {"watched_requests": seen.watched}
    if not seen.watched:
        return dict(out, logits_match_reference=False,
                    selection_matches_reference=False,
                    sparse_layer_matches_reference=False)
    x = reference_glm5.embed(jnp.asarray(seen.tokens), params.top())
    want = (seen.first, seen.watched_end - seen.first)
    stats = {"run": [], "decode": []}
    for i in range(cfg.num_layers):
        x, picked = reference_glm5.layer_forward(
            x, params.layer(i), dims, want, seen.positions, seen.segments)
        for kind in stats:
            if not seen.queries[kind].size:
                continue
            rows = jnp.asarray(seen.queries[kind] - seen.first)
            stats[kind] += selection_stats(
                seen.chosen[kind][i][None],
                [(picked[0][rows], picked[1][rows])])
            if keep is not None:
                keep.setdefault("chosen", {}).setdefault(kind, []).append(
                    np.asarray(picked[1][rows]))
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
        and out[f"sparse_{k}"]["mlp_rel"] <= SPARSE_MLP_REL
        # (no token of these picked a held expert, by the reference's own
        # routing of the same input: nothing routed to hold)
        and (not out[f"sparse_{k}"]["routed_tokens"]
             or out[f"sparse_{k}"]["routed_rel"] <= SPARSE_ROUTED_REL)
        for k in stats)
    deficits = []
    for at, emitted in seen.emitted:
        logits = reference_glm5.head_logits(
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
        raise ValueError("the serve_sparse driver runs closed loops only")

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
    # document with a tail of at most ``check_tail_max`` (one at a time:
    # ``InferenceEngine.watch``); the packed batch of the reference is the
    # document and their tails
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
    # (whole multiples of PAD_TO positions: few shapes for the reference's
    # programs over a run's seeds, so the compile cache has them)
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
        # the builder's controls (perfbench/controls_glm5.py): the same
        # comparison against a reference with one fault planted, each of
        # which has to come out as not correct.  Readings only.
        from perfbench import controls_glm5

        checks["controls"] = controls_glm5.readings(
            ctx, kept, lambda keep=None: reference_check(
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
