"""The training loop of a sparse (mixture-of-experts) configuration:
``ElasticTrainer`` steps on seeded Zipfian tokens, no saves.

The system under test is the program's own ``ElasticTrainer`` with
``LlamaModel`` in its OLMoE shape (QK-norm, dropless top-k experts); the
loop, the clock, the data and the checks are here.  What a training loop
shares with ``drivers/train.py`` is imported from it.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
import time

import numpy as np

from perfbench import reference_olmoe
from perfbench.drivers.train import _layer_of
from perfbench.harness import Context, llama_config
from perfbench.weights import fold_seed

# First-step loss, system (bf16 matmuls with f32 accumulation, flash kernel,
# router in f32 on bf16 activations) against the float32 reference on the
# same parameters and batch, total and cross-entropy part.  Measured on the
# chip at the published widths, depth 3, losses of 11.2-11.7 (my chip runs,
# PR 26): 3.3e-5 to 6.0e-4 over 19 seeds, median 2.6e-4 (the cross-entropy
# part within 2e-5 of the total's).  The same step
# with its matmuls in fp8 is off by 5.2e-3, with a token's 8th pick dropped
# by 2.5e-3, without QK-norm by 1.9e-3 (one seed each, forward only).  At
# random weights the loss is this insensitive because the layers add little
# to the embedding the head reads; renormalised top-k weights (3.0e-4) are
# INSIDE the noise here and are held by the CPU tests at 1e-5.  So the
# bound cannot be ten times the largest difference (6e-3 would pass fp8):
# it is 3.3 times it, and 2.6 times under the fp8 step.
LOSS_ATOL = 2e-3
# Picks (of T x 8 = 32768 a layer) that may land on another expert than
# the reference's: the system's router reads bf16 activations, so a token
# whose 8th and 9th probabilities are closer than bf16's rounding swaps
# them.  Measured (19 runs, PR 26): 27 to 131 a layer, 0.40 % at most;
# the bound is 1 %.  Every layer's total is exact: T x 8, nothing dropped.
PICKS_MAY_DIFFER = 0.01


def zipf_batches(seed: int, base_seed: int, exponent: float, vocab: int,
                 rows: int, seq: int, n: int):
    """``n`` batches [rows, seq] of token ids with p(rank r) ~ 1 / r^exponent
    over the whole vocabulary; ``base_seed`` (the traffic file's) says
    which id has which rank, ``seed`` (the run's) draws the content."""
    ids_by_rank = np.random.RandomState(base_seed).permutation(vocab)
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    cdf = np.cumsum(p / p.sum())
    rng = np.random.RandomState(fold_seed(seed))
    out = []
    for _ in range(n):
        ranks = np.searchsorted(cdf, rng.random_sample((rows, seq)))
        out.append(ids_by_rank[np.minimum(ranks, vocab - 1)]
                   .astype(np.int32))
    return out


def moe_config(config: dict, max_seq_len: int, scan_layers: bool):
    """The program's ``LlamaConfig`` of a sparse configuration file."""
    return dataclasses.replace(
        llama_config(config, max_seq_len=max_seq_len,
                     scan_layers=scan_layers),
        num_experts=int(config["num_experts"]),
        moe_top_k=int(config["num_experts_per_tok"]),
        moe_norm_topk_prob=bool(config["norm_topk_prob"]),
        moe_aux_loss_coef=float(config["router_aux_loss_coef"]),
        moe_z_loss_coef=float(config["router_z_loss_coef"]),
        qk_norm=True)


def run(ctx: Context) -> dict:
    import jax

    from dlrover_tpu.models.llama import LlamaModel
    from dlrover_tpu.trainer.elastic.trainer import ElasticTrainer
    from dlrover_tpu.utils.compile_cache import cache_counts

    clock = time.perf_counter
    dep, traffic = ctx.config["deployment"], ctx.traffic
    seq = int(dep["seq_len"])
    rows = ctx.chips * int(dep.get("sequences_per_chip_per_step", 1))
    # a program that lacks the sparse layer's fields fails here, at once
    cfg = moe_config(ctx.config, max_seq_len=seq,
                     scan_layers=bool(dep.get("scan_layers", True)))

    # ---------------------------------------------------------- set-up
    t0 = clock()
    model = LlamaModel(cfg)
    trainer = ElasticTrainer(
        model, global_batch_size=rows, micro_batch_per_shard=1,
        seq_len=seq, checkpoint_dir=None, save_memory_interval=0,
        save_storage_interval=0)
    key = jax.random.PRNGKey(fold_seed(ctx.seed))
    try:
        trainer.prepare(devices=ctx.devices)
        if trainer.restore_or_init(key) != 0:
            raise RuntimeError("a fresh run restored a step")
        jax.block_until_ready(trainer.state)
        t_weights = clock()
        ctx.say("state made; warm-up steps")
        batches = zipf_batches(
            ctx.seed, int(traffic["base_seed"]),
            float(traffic["zipf_exponent"]), cfg.vocab_size, rows, seq,
            int(traffic.get("distinct_batches", 8)))
        stepped = []
        for i in range(int(traffic.get("warmup_steps", 4))):
            m = trainer.train_step(batches[i % len(batches)])
            jax.block_until_ready(m)
            stepped.append(m)
        first = {k: float(v) for k, v in stepped[0].items()}
        t_warm = clock()
        ctx.say("set-up done; window")
        setup = {"weights_s": t_weights - t0, "warmup_s": t_warm - t_weights,
                 "import_s": t0 - ctx.t_start,
                 "cache_misses": cache_counts()["misses"],
                 "cache_hits": cache_counts()["hits"]}

        # ------------------------------------------------------ window
        trace_steps = int(traffic.get("trace_steps", 8))
        trace_from = 4
        step_s = []
        n = 0
        t_w0 = clock()
        setup_s = t_w0 - ctx.t_start
        while clock() - t_w0 < ctx.seconds:
            if ctx.trace and n == trace_from:
                ctx.profiler.start()
            batch = batches[(n + 2) % len(batches)]
            t_s = clock()
            with ctx.span("train_step"):
                m = trainer.train_step(batch)
                jax.block_until_ready(m)
            step_s.append(clock() - t_s)
            stepped.append(m)
            n += 1
            if ctx.profiler.active and n >= trace_from + trace_steps:
                ctx.profiler.stop()
        t_w1 = clock()
        window_s = t_w1 - t_w0
        ctx.say(f"window done: {n} steps, median "
                f"{statistics.median(step_s) * 1e3:.1f} ms, min "
                f"{min(step_s) * 1e3:.1f}, max {max(step_s) * 1e3:.1f}; "
                "checks")
        trace = ctx.profiler.result()

        # ----------------------------------------------------- after it
        checks = {}
        stepped = [{k: float(v) for k, v in m.items()} for m in stepped]
        checks["losses_finite"] = all(
            math.isfinite(m["loss"]) for m in stepped)
        window = stepped[-n:] if n else []
        counters = {"moe.load_max_median": statistics.median(
            m["moe_load_max"] for m in window)} if window else {}
        trainer.state = None
        # the first step's loss against the reference, on the parameters
        # the run started from (the same key makes them again), and the
        # system's own routing of that batch, forward only
        params = trainer.result.init_fn(key).params
        _, sown = jax.jit(lambda p, ids: model.apply(
            {"params": p}, ids, return_hidden=True,
            mutable=["moe_losses"]))(params, batches[0])
        counts = np.concatenate([
            np.asarray(leaf).reshape(-1, cfg.num_experts) for path, leaf in
            jax.tree_util.tree_flatten_with_path(sown["moe_losses"])[0]
            if getattr(path[-1], "key", None) == "expert_counts"])
        stacked = params["layers"]["layer"]
        top = {k: params[k] for k in
               ("embed_tokens", "final_norm", "lm_head")}
        ref = reference_olmoe.lm_loss(
            batches[0], lambda i: _layer_of(stacked, i), top,
            cfg.num_layers, cfg.rope_theta, cfg.rms_norm_eps,
            cfg.moe_top_k, cfg.moe_norm_topk_prob, cfg.moe_aux_loss_coef,
            cfg.moe_z_loss_coef)
        del params, stacked, top
        ref_counts = np.asarray(ref["counts"])
        first_ce = (first["loss"]
                    - cfg.moe_aux_loss_coef * first["moe_balance_loss"]
                    - cfg.moe_z_loss_coef * first["moe_z_loss"])
        picks = rows * seq * cfg.moe_top_k
        # a pick that went elsewhere is one too many there, one too few here
        moved = np.abs(counts - ref_counts).sum(axis=-1) / 2.0
        checks.update({
            "first_loss": first["loss"],
            "reference_loss": float(ref["total"]),
            "loss_abs_diff": abs(first["loss"] - float(ref["total"])),
            "first_ce": first_ce,
            "reference_ce": float(ref["ce"]),
            "ce_abs_diff": abs(first_ce - float(ref["ce"])),
            "balance": [first["moe_balance_loss"], float(ref["balance"])],
            "z": [first["moe_z_loss"], float(ref["z"])],
            "picks_per_layer": counts.sum(axis=-1).tolist(),
            "picks_moved_per_layer": moved.tolist(),
            "load_max_first_step": first["moe_load_max"],
        })
        checks["loss_matches_reference"] = (
            checks["loss_abs_diff"] <= LOSS_ATOL
            and checks["ce_abs_diff"] <= LOSS_ATOL)
        checks["every_pick_routed"] = bool(
            (counts.sum(axis=-1) == picks).all()
            and (ref_counts.sum(axis=-1) == picks).all())
        checks["counts_match_reference"] = bool(
            (moved <= PICKS_MAY_DIFFER * picks).all())
    finally:
        trainer.close()

    ok = all(v for k, v in checks.items() if isinstance(v, bool))
    return {
        "end_to_end": {
            "setup_s": setup_s,
            "train_tokens_per_s": n * rows * seq / window_s / ctx.chips,
        },
        "setup": setup,
        "window_s": window_s,
        "profiler_s": ctx.profiler.overhead_s,
        "compiles_in_window": ctx.compiles.inside(t_w0, t_w1),
        "counters": counters,
        "samples": {
            "step_s": step_s,
            "step_had_save": [False] * len(step_s),
            "save_call_s": [],
            "tokens_per_step": rows * seq,
        },
        "shapes": {"seq": seq, "rows": rows, "heads": cfg.num_heads,
                   "head_dim": cfg.head_dim_, "layers": cfg.num_layers,
                   "remat": bool(cfg.remat), "hidden": cfg.hidden_size,
                   "expert_width": cfg.intermediate_size,
                   "top_k": cfg.moe_top_k},
        "trace": trace,
        "correct": ok,
        "checks": checks,
        "attempted": n,
        "failed": 0 if checks["losses_finite"] else 1,
    }
