"""The training loop of a model whose layers are not all alike and whose
sparse layers hold a SHARE of their experts (Laguna-XS.2): window and
full attention layers with different head counts in one scan over
periods, a shared expert beside sigmoid-routed experts of which this chip
holds some, a slice of the vocabulary.  ``ElasticTrainer`` steps on seeded
Zipfian tokens, no saves.

The system under test is the program's own ``ElasticTrainer`` with
``LlamaModel``; the loop, the clock, the data and the checks are here.
The configuration file's published keys become the program's
``LlamaConfig`` here (``harness.llama_config`` knows one kind of layer);
what a training loop shares with ``drivers/train.py`` and
``drivers/train_moe.py`` is imported from them.

What ``correct`` compares, on the chip at the timed sizes, of what the
timed path produced, against ``perfbench/reference_laguna.py`` (float32,
``highest``) on the same parameters and batch:

- the FIRST step's loss (the trainer's own step, bf16 matmuls, flash
  kernels, grouped matmuls, backward not involved);
- the BACKWARD: the gradient of the loss the step differentiates, leaf by
  leaf (a layer's matrix, an expert stack) against the reference's
  gradient, by its worst leaf; and the first step's own ``grad_norm``
  against that gradient's norm, which ties the comparison to the step that
  was timed (the step hands out no gradient, so the leaves come from the
  same loss function, model and kernels jitted once more);
- every sparse layer's ``expert_counts`` over all 256 experts, from the
  system's forward of that batch, and ``moe_picks_held`` of the first step
  against the held entries of those counts;
- the final normed hidden state of that same forward at 64 seeded
  positions (the median relative error over them): a loss at random
  weights barely sees a layer (PR 26), a hidden state does.

What this comparison cannot see is named at ``HIDDEN_REL`` and
``GRAD_LEAF_REL`` and held by the CPU tests
(``tests/test_laguna_reference.py``) at 1e-5, gradients included.  The
optimizer's update is NOT compared: at the traffic file's learning rate an
update is below half a bf16 ulp of most weights (``learning_rate_why``).
"""

from __future__ import annotations

import math
import statistics
import time

import numpy as np

from perfbench import reference_laguna
from perfbench.drivers.train import _layer_of
from perfbench.drivers.train_moe import zipf_batches
from perfbench.harness import Context
from perfbench.weights import fold_seed

# First-step loss, system (bf16 matmuls with f32 accumulation, flash
# kernels, grouped matmuls, router in f32 on bf16 activations) against the
# float32 reference.  Measured on the chip at the published widths, depth 9,
# 2 x 8192 tokens, losses of 9.78-10.07 (my chip runs, PR 31; PERF.md
# section 6 gives the same ranges): |system - reference| 2.4e-5 to 1.30e-3
# over 35 seeds (27 runs of the cell, 8 of the calibration scripts).
# The same forward with ONE thing wrong (seed 2147483811): fp8 matmuls
# 8.8e-3, a window of 1024 3.3e-3, no head gate 2.3e-2, the routed sum not
# scaled by 2.5 2.1e-3, a token's 8th pick lost 1.5e-3.  So the loss's limit
# stands 2.3 x over the largest reading.  It does NOT hold fp8 on every
# seed (seed 2147487001 reads 2.8e-3 with fp8), nor a lost pick or a
# missing 2.5: those are the hidden state's and the gradient's, below.
LOSS_ATOL = 3e-3
# Final normed hidden state at 64 seeded positions: the MEDIAN over them
# of ``|system - reference|_2 / |reference|_2``.  Measured: 0.0135 to 0.025
# over the same 35 seeds; with one thing wrong (same seed as above): a
# lost pick 0.179, a window of 1024 0.206, no 2.5 0.329, fp8 0.347, no head
# gate 1.00; plain RoPE, no YaRN ramp, no attention factor in the full layers
# or no shared expert 0.67-0.95 (seed 2147483801, experts at the stack's
# fan-in).  The limit stands 2.4 x over the largest reading and 3 x under
# the smallest fault.  The median and not the largest: a token whose 8th
# and 9th router scores lie closer than bf16 rounds (0.4-0.66 % of a
# layer's picks, ``PICKS_MAY_DIFFER``) visits another expert than the
# reference's, and its own position is then off by 0.2-0.5; 64 positions
# hold about five such tokens, and the largest error reads 0.16-0.62 on a
# correct system.  What this cannot see: one position in a few (the CPU
# tests hold every position at 1e-5).
HIDDEN_REL = 0.06
# Picks (of T x 8 = 131072 a layer, over all 256 experts) that may land on
# another expert than the reference's: the system's router reads bf16
# activations, so a token whose 8th and 9th scores are closer than bf16's
# rounding swaps them.  The bound is train_moe.py's (1 %); every layer's
# total is exact.
PICKS_MAY_DIFFER = 0.01
HIDDEN_POSITIONS = 64
# The gradient of the first batch's loss, a leaf at a time (a layer's
# matrix or norm, a layer's stack of held experts, the embedding, the head:
# 125 leaves): ``|system - reference|_2 / |reference|_2``, the WORST leaf of
# each of two classes.  The leaves the routing does not feed: 0.074 to 0.115
# over 14 seeds (my chip runs, PR 31; the worst is a layer's q_proj eleven
# times of the thirteen that name it); with one thing wrong (seed
# 2147487001, which reads 0.091 right): the BACKWARD kernels alone at twice
# the window 3.21 (a window layer's v_proj; 2.6 its q_proj), fp8 matmuls
# 0.376, a lost pick 0.191.  The limit stands 1.7 x over the largest
# reading and 1.9 x under fp8; a backward kernel that is wrong is 16 x
# over it.
GRAD_LEAF_REL = 0.2
# ... and the leaves the ROUTING feeds (a router, the three stacks of held
# experts): the 0.4-0.66 % of picks that land on another expert than the
# reference's are rows of another expert's gradient, and a router's
# gradient is small (norm 0.003-0.01 of ~10).  0.287 to 0.471 over 15
# seeds, the worst always a router; one thing wrong (same seed, 0.332
# right): fp8 0.822, a lost pick 0.638, the backward kernels' window 1.29.
# 1.4 x over the largest reading, 1.26 x under fp8: this class is there for
# the grouped matmuls' backward and the held rows' selects, whose faults
# are of order 1; fp8 is the other class's to see.
GRAD_ROUTED_REL = 0.65
# The first step's ``grad_norm`` (the timed program; summed and handed out
# in bf16) against the norm of the gradient compared above (the same loss,
# jitted alone, norm in float64): two programs of one computation.
# 7e-5 to 0.0113 over 13 seeds; the backward kernels at twice the window
# read 1.29 (a norm of 23.6 for 10.3).
GRAD_NORM_REL = 0.03
ROUTED_LEAVES = ("['mlp']['router']", "['mlp']['w_gate']", "['mlp']['w_up']",
                 "['mlp']['w_down']")


def hybrid_config(config: dict, max_seq_len: int):
    """The program's ``LlamaConfig`` of the configuration file's published
    keys.  A program without per-layer descriptions fails here, at the
    import, before anything is put on the device."""
    import jax.numpy as jnp

    from dlrover_tpu.models.llama import LayerSpec, LlamaConfig, RopeSpec

    dep = config["deployment"]
    n = int(config["num_hidden_layers"])

    def rope_spec(kind):
        r = config["rope_parameters"][kind]
        yarn = r.get("rope_type") == "yarn"
        return RopeSpec(
            theta=float(r["rope_theta"]),
            rotary_fraction=float(r.get("partial_rotary_factor", 1)),
            yarn_factor=float(r["factor"]) if yarn else 0.0,
            yarn_original_max_len=int(
                r["original_max_position_embeddings"]) if yarn else 0,
            yarn_beta_fast=float(r["beta_fast"]) if yarn else 32.0,
            yarn_beta_slow=float(r["beta_slow"]) if yarn else 1.0,
            attention_factor=float(r["attention_factor"]) if yarn else 1.0)

    layers = tuple(
        LayerSpec(
            num_heads=int(heads),
            window=(int(config["sliding_window"])
                    if kind == "sliding_attention" else 0),
            rope=rope_spec(kind),
            mlp=mlp)
        for kind, heads, mlp in zip(
            config["layer_types"][:n],
            config["num_attention_heads_per_layer"][:n],
            config["mlp_layer_types"][:n]))
    first, held = dep["experts_held"]
    if held != config["num_experts"]:
        raise ValueError("num_experts is the experts HELD: "
                         f"{config['num_experts']} != {held}")
    return LlamaConfig(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        intermediate_size=int(config["intermediate_size"]),
        num_layers=n,
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        max_seq_len=max_seq_len,
        rope_theta=float(
            config["rope_parameters"]["full_attention"]["rope_theta"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(dep["compute_dtype"]),
        param_dtype=jnp.dtype(dep["param_dtype"]),
        scan_layers=bool(dep.get("scan_layers", True)),
        remat=bool(dep.get("remat", False)),
        remat_policy=dep.get("remat_policy", "nothing_saveable"),
        tie_embeddings=False,
        num_experts=int(dep["experts_published"]),
        moe_top_k=int(config["num_experts_per_tok"]),
        moe_norm_topk_prob=True,
        moe_aux_loss_coef=0.0,
        moe_z_loss_coef=0.0,
        moe_intermediate_size=int(config["moe_intermediate_size"]),
        moe_score_fn="sigmoid",
        moe_routed_scale=float(config["moe_routed_scaling_factor"]),
        moe_shared_width=int(config["shared_expert_intermediate_size"]),
        moe_experts_held=(int(first), int(held)),
        moe_per_expert_init=True,
        attn_head_gate=bool(config["gating"]),
        layers=layers,
    )


def layer_getter(params, cfg):
    """``i -> layer i's parameters`` of a model whose leading layers are
    unrolled (``layer_<i>``) and whose rest is stacked by period
    (``periods/layer_<j>``, leading axis the period's number)."""
    from dlrover_tpu.models.llama import layer_pattern

    lead, period = layer_pattern(cfg.layer_specs)

    def get(i):
        if i < lead:
            return params[f"layer_{i}"]
        r, j = divmod(i - lead, period)
        return _layer_of(params["periods"][f"layer_{j}"], r)

    return get, lead, period


def counts_in_layer_order(sown, cfg, lead, period):
    """[sparse layers, experts] of the sown ``expert_counts``."""
    out = []
    for i, spec in enumerate(cfg.layer_specs):
        if spec.mlp != "sparse":
            continue
        if i < lead:
            leaf = sown[f"layer_{i}"]["mlp"]["expert_counts"]
        else:
            r, j = divmod(i - lead, period)
            leaf = sown["periods"][f"layer_{j}"]["mlp"]["expert_counts"][r]
        out.append(np.asarray(leaf).reshape(-1))
    return np.stack(out)


def run(ctx: Context) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.accel.accelerate import default_loss_fn
    from dlrover_tpu.models.llama import LlamaModel
    from dlrover_tpu.trainer.elastic.trainer import ElasticTrainer
    from dlrover_tpu.utils.compile_cache import cache_counts

    clock = time.perf_counter
    dep, traffic = ctx.config["deployment"], ctx.traffic
    seq = int(dep["seq_len"])
    per_chip = int(dep.get("sequences_per_chip_per_step", 1))
    rows = ctx.chips * per_chip
    cfg = hybrid_config(ctx.config, max_seq_len=seq)
    specs = cfg.layer_specs

    # ---------------------------------------------------------- set-up
    t0 = clock()
    model = LlamaModel(cfg)
    # accelerate()'s default chain, at the traffic file's learning rate
    # (its ``learning_rate_why``)
    optimizer = optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(float(traffic["learning_rate"]), b1=0.9, b2=0.95,
                    weight_decay=0.1))
    trainer = ElasticTrainer(
        model, global_batch_size=rows, micro_batch_per_shard=per_chip,
        seq_len=seq, checkpoint_dir=None, optimizer=optimizer,
        save_memory_interval=0, save_storage_interval=0)
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
        for i in range(int(traffic.get("warmup_steps", 3))):
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
        trace_steps = int(traffic.get("trace_steps", 4))
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
        # the optimizer's chain ran once a step (its update is not compared)
        checks["every_step_applied"] = (
            [int(m["step"]) for m in stepped]
            == list(range(1, len(stepped) + 1)))
        window = stepped[-n:] if n else []
        sparse_layers = sum(s.mlp == "sparse" for s in specs)
        picks = rows * seq * cfg.moe_top_k            # a layer, all experts
        counters = {}
        if window:
            traced = window[trace_from:trace_from + trace_steps]
            held_median = statistics.median(
                m["moe_picks_held"] for m in window)
            counters = {
                "moe.load_max_median": statistics.median(
                    m["moe_load_max"] for m in window),
                "moe.held_share_median": statistics.median(
                    m["moe_held_share"] for m in window),
                "moe.picks_held_traced": sum(
                    m["moe_picks_held"] for m in traced),
                # the sorted buffer: rows live of rows allocated, a layer
                "moe.rows_live_median": held_median / sparse_layers,
                "moe.rows_allocated": picks,
            }
        trainer.state = None
        # the first step's loss against the reference, on the parameters
        # the run started from (the same key makes them again), and the
        # system's own forward of that batch: routing and hidden state
        params = trainer.result.init_fn(key).params
        hidden, sown = jax.jit(lambda p, ids: model.apply(
            {"params": p}, ids, return_hidden=True,
            mutable=["moe_losses"]))(params, batches[0])
        get_layer, lead, period = layer_getter(params, cfg)
        counts = counts_in_layer_order(sown["moe_losses"], cfg, lead, period)
        rng = np.random.RandomState(fold_seed(ctx.seed) % (2 ** 31))
        at_rows = rng.randint(0, rows, HIDDEN_POSITIONS)
        at_pos = rng.randint(0, seq, HIDDEN_POSITIONS)
        sys_hidden = np.asarray(
            hidden[at_rows, at_pos].astype(jnp.float32))
        del hidden, sown
        # the gradient of the loss the step differentiates, brought to the
        # host: the reference's backward needs the room
        loss_fn = default_loss_fn(model)
        sys_grads = jax.device_get(jax.jit(jax.grad(
            lambda p, ids: loss_fn(p, {"input_ids": ids})[0]))(
                params, jnp.asarray(batches[0])))
        sys_layer, _, _ = layer_getter(sys_grads, cfg)
        ctx.say("system's forward and gradient done; reference")
        top_names = ("embed_tokens", "final_norm", "lm_head")
        top = {k: params[k] for k in top_names}
        leaf_err, squares = {}, np.zeros(3)

        @jax.jit
        def leaf_squares(got, want):
            got, want = got.astype(jnp.float32), want.astype(jnp.float32)
            return jnp.stack([jnp.sum(jnp.square(got - want)),
                              jnp.sum(jnp.square(want)),
                              jnp.sum(jnp.square(got))])

        def compare_grads(i, ref_grads):
            theirs = ({k: sys_grads[k] for k in top_names} if i == "top"
                      else sys_layer(i))
            for (path, r), g in zip(
                    jax.tree_util.tree_flatten_with_path(ref_grads)[0],
                    jax.tree_util.tree_leaves(theirs)):
                d = np.asarray(leaf_squares(jnp.asarray(g), r), np.float64)
                squares[:] += d
                leaf_err[f"{i}{jax.tree_util.keystr(path)}"] = math.sqrt(
                    d[0] / d[1])

        ref = reference_laguna.lm_loss_and_grads(
            batches[0], get_layer, top, ctx.config,
            tuple(cfg.moe_experts_held), compare_grads)
        del params, top, sys_grads
        ref_counts = np.asarray(ref["counts"])
        ref_hidden = np.asarray(ref["hidden"][at_rows, at_pos])
        rel = (np.linalg.norm(sys_hidden - ref_hidden, axis=-1)
               / np.linalg.norm(ref_hidden, axis=-1))
        first_held, held = cfg.moe_experts_held
        # a pick that went elsewhere is one too many there, one too few here
        moved = np.abs(counts - ref_counts).sum(axis=-1) / 2.0
        held_by_counts = float(
            counts[:, first_held:first_held + held].sum())
        routed = {k: v for k, v in leaf_err.items()
                  if any(name in k for name in ROUTED_LEAVES)}
        plain = {k: v for k, v in leaf_err.items() if k not in routed}
        worst, worst_routed = max(plain, key=plain.get), max(
            routed, key=routed.get)
        grad_norm_sys = math.sqrt(squares[2])
        checks.update({
            "grad_rel_err_worst": plain[worst],
            "grad_rel_err_worst_leaf": worst,
            "grad_rel_err_worst_routed": routed[worst_routed],
            "grad_rel_err_worst_routed_leaf": worst_routed,
            "grad_rel_err_median": statistics.median(leaf_err.values()),
            "grad_rel_err_all": math.sqrt(squares[0] / squares[1]),
            "grad_leaves": len(leaf_err),
            "grad_norm_reference": math.sqrt(squares[1]),
            "grad_norm_system": grad_norm_sys,
            "grad_norm_first_step": first["grad_norm"],
            "first_loss": first["loss"],
            "reference_loss": float(ref["total"]),
            "loss_abs_diff": abs(first["loss"] - float(ref["total"])),
            "hidden_rel_err_max": float(rel.max()),
            "hidden_rel_err_median": float(np.median(rel)),
            "picks_per_layer": counts.sum(axis=-1).tolist(),
            "picks_moved_per_layer": moved.tolist(),
            "picks_held_first_step": first["moe_picks_held"],
            "picks_held_by_counts": held_by_counts,
            "held_share_first_step": first["moe_held_share"],
            "load_max_first_step": first["moe_load_max"],
        })
        checks["loss_matches_reference"] = (
            checks["loss_abs_diff"] <= LOSS_ATOL)
        checks["grads_match_reference"] = (
            checks["grad_rel_err_worst"] <= GRAD_LEAF_REL
            and checks["grad_rel_err_worst_routed"] <= GRAD_ROUTED_REL)
        checks["step_grad_norm_is_that_gradients"] = (
            abs(first["grad_norm"] - grad_norm_sys)
            <= GRAD_NORM_REL * grad_norm_sys)
        checks["hidden_matches_reference"] = bool(
            np.median(rel) <= HIDDEN_REL)
        checks["every_pick_routed"] = bool(
            (counts.sum(axis=-1) == picks).all()
            and (ref_counts.sum(axis=-1) == picks).all())
        checks["counts_match_reference"] = bool(
            (moved <= PICKS_MAY_DIFFER * picks).all())
        # the step's counter is the forward's held counts: the step ran the
        # same batch on the same parameters, but as another program, whose
        # bf16 activations may round a token's 8th and 9th scores the
        # other way (equal to the last pick on the CPU, in float32)
        checks["picks_held_is_the_held_counts"] = (
            abs(first["moe_picks_held"] - held_by_counts)
            <= PICKS_MAY_DIFFER * held_by_counts)
    finally:
        trainer.close()

    ok = all(v for k, v in checks.items() if isinstance(v, bool))
    full = [s for s in specs if not s.window]
    windowed = [s for s in specs if s.window]
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
        "shapes": {"seq": seq, "rows": rows, "head_dim": cfg.head_dim_,
                   "layers": cfg.num_layers, "remat": bool(cfg.remat),
                   "hidden": cfg.hidden_size,
                   "expert_width": cfg.expert_width,
                   "top_k": cfg.moe_top_k,
                   "full_layers": len(full),
                   "full_heads": full[0].num_heads if full else 0,
                   "window_layers": len(windowed),
                   "window_heads": windowed[0].num_heads if windowed else 0,
                   "window": windowed[0].window if windowed else 0,
                   "sparse_layers": sparse_layers},
        "trace": trace,
        "correct": ok,
        "checks": checks,
        "attempted": n,
        "failed": 0 if checks["losses_finite"] else 1,
    }
