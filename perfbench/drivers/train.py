"""The training loop: ``ElasticTrainer`` steps on seeded tokens, with the
flash checkpoint's memory saves at the traffic file's cadence.

The system under test is the program's own ``ElasticTrainer`` (what
``examples/train_llama.py`` drives), its ``accelerate()`` step and its
``Checkpointer``; the loop, the clock, the data and the checks are here.
"""

from __future__ import annotations

import glob
import math
import os
import time

import numpy as np

from perfbench import reference
from perfbench.harness import Context, llama_config
from perfbench.weights import fold_seed

# first-step loss, system (bf16 matmuls, f32 accumulation, flash kernel)
# against the float32 reference on the same parameters and batch.  The
# loss is a mean over >= 4095 positions of a log-softmax over logits of
# unit scale; bf16 rounding (2^-9 relative per product, random sign)
# moves single logits by ~1e-2 and the mean by far less.  Measured on the
# chip at the published widths (my chip runs, PR 23): 3.1e-4 and 7.3e-5 on
# a loss of 10.88.  The bound is ten times the larger; a step computed in
# fp8, or with a wrong RoPE base or mask, is off by >= 0.05.
LOSS_ATOL = 3e-3


def _batches(ctx: Context, vocab: int, rows: int, seq: int):
    rng = np.random.RandomState(fold_seed(ctx.seed))
    n = int(ctx.traffic.get("distinct_batches", 8))
    return [rng.randint(0, vocab, size=(rows, seq)).astype(np.int32)
            for _ in range(n)]


def _unlink_shm(job: str) -> None:
    """Remove this run's checkpoint segments.  The program keeps them in
    POSIX shared memory (``dlrover_tpu_ckpt_<job>_<rank>[_g1]``) and leaves
    them for the agent on purpose, so the run that made them removes them.
    Only names that hold the whole job id between its ``_`` delimiters
    match: another run's id, whatever its pid, is never a part of one."""
    for path in glob.glob(f"/dev/shm/*_{glob.escape(job)}_*"):
        try:
            os.unlink(path)
        except OSError:
            pass


def _layer_of(stacked, i: int):
    import jax

    return jax.tree_util.tree_map(lambda x: x[i], stacked)


def run(ctx: Context) -> dict:
    import jax

    from dlrover_tpu.models.llama import LlamaModel
    from dlrover_tpu.trainer.elastic.trainer import ElasticTrainer
    from dlrover_tpu.trainer.flash_checkpoint import StorageType
    from dlrover_tpu.utils.compile_cache import cache_counts

    clock = time.perf_counter
    dep, traffic = ctx.config["deployment"], ctx.traffic
    seq = int(dep["seq_len"])
    rows = ctx.chips * int(dep.get("sequences_per_chip_per_step", 1))
    cfg = llama_config(ctx.config, max_seq_len=seq,
                       scan_layers=bool(dep.get("scan_layers", True)))
    save_every = int(traffic.get("save_memory_interval", 0))
    # unique to this run and delimited: no other run's id begins with it
    job = f"perfbench-{os.getpid()}-{os.urandom(4).hex()}"
    os.environ["DLROVER_JOB_UID"] = job
    ckpt_dir = os.path.join(ctx.work_dir, "ckpt") if save_every else None

    # ---------------------------------------------------------- set-up
    t0 = clock()
    trainer = ElasticTrainer(
        LlamaModel(cfg), global_batch_size=rows, micro_batch_per_shard=1,
        seq_len=seq, checkpoint_dir=ckpt_dir,
        save_memory_interval=save_every,
        save_storage_interval=int(traffic.get("save_storage_interval", 0)))
    key = jax.random.PRNGKey(fold_seed(ctx.seed))
    try:
        trainer.prepare(devices=ctx.devices)
        started_at = trainer.restore_or_init(key)
        if started_at != 0:
            raise RuntimeError(f"a fresh run restored step {started_at}")
        jax.block_until_ready(trainer.state)
        t_weights = clock()
        ctx.say("state made; warm-up steps")
        batches = _batches(ctx, cfg.vocab_size, rows, seq)
        losses = []
        for i in range(int(traffic.get("warmup_steps", 2))):
            m = trainer.train_step(batches[i % len(batches)])
            jax.block_until_ready(m)
            losses.append(m["loss"])
        first_loss = float(losses[0])
        t_warm = clock()
        ctx.say("steps warm; pre-faulting saves")
        engine = trainer._ckpt.engine if save_every else None
        for _ in range(int(traffic.get("prefault_saves", 0)) if engine else 0):
            # both shm buffers are created and faulted in here, so a save
            # in the window is a steady save (PERF.md: a first save pays
            # ~10 s of page faults)
            if not trainer._ckpt.save_checkpoint(
                    trainer.step, trainer.state, StorageType.MEMORY,
                    block=True):
                raise RuntimeError("a set-up save did not commit")
        t_fault = clock()
        ctx.say("set-up done; window")
        before = engine.ckpt_metrics() if engine else {}
        setup = {"weights_s": t_weights - t0, "warmup_s": t_warm - t_weights,
                 "prefault_s": t_fault - t_warm, "import_s": t0 - ctx.t_start,
                 "cache_misses": cache_counts()["misses"],
                 "cache_hits": cache_counts()["hits"]}

        # ------------------------------------------------------ window
        trace_steps = int(traffic.get("trace_steps", 8))
        # ``maybe_save`` saves when the trainer's own step count is a
        # multiple of the cadence, so the warm-up steps (the traffic
        # file's ``warmup_steps``) fix where in the window the saves fall:
        # window step n ends as trainer step ``trainer.step + n + 1``
        first_due = ((-(trainer.step + 1)) % save_every) if save_every else 0
        # the traced sub-window starts 3 steps before a save where the
        # cell saves, so that one save's stall is inside it
        trace_from = max(0, first_due - 3) if save_every else 4
        step_s, save_steps = [], []
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
            t_e = clock()
            losses.append(m["loss"])
            with ctx.span("maybe_save"):
                due = bool(save_every) and trainer.step % save_every == 0
                trainer.maybe_save()
            if due:
                save_steps.append(n)
            step_s.append((t_e - t_s, due, clock() - t_e))
            n += 1
            if ctx.profiler.active and n >= trace_from + trace_steps:
                ctx.profiler.stop()
        t_w1 = clock()
        window_s = t_w1 - t_w0
        ctx.say("window done; checks")
        trace = ctx.profiler.result()

        # ----------------------------------------------------- after it
        checks = {}
        losses = [float(x) for x in losses]
        checks["losses_finite"] = all(math.isfinite(x) for x in losses)
        counters = {}
        if engine is not None:
            flushed = engine.flush(timeout=120.0)
            after = engine.ckpt_metrics()
            counters = {k.replace("dlrover_ckpt_", "ckpt."): after[k]
                        - before.get(k, 0.0) for k in after
                        if k.endswith("_total")}
            committed_step = int(after["dlrover_ckpt_committed_step"])
            checks["saves_all_committed"] = bool(
                flushed and after["dlrover_ckpt_saves_committed_total"]
                == after["dlrover_ckpt_saves_staged_total"]
                and after["dlrover_ckpt_save_errors_total"] == 0)
            # restore through the trainer's own path: the last committed
            # step must come back from shared memory as that step
            trainer.state = None
            restored = trainer.restore_or_init(key)
            checks["restore_returns_committed_step"] = (
                restored == committed_step
                and int(trainer.state.step) == committed_step)
            checks["committed_step"] = committed_step
            # ``maybe_save`` returns True whether or not the engine took
            # the save, so a skipped one shows only in the engine's books
            checks["saves_due"] = len(save_steps)
            checks["save_steps"] = save_steps
            checks["every_due_save_staged"] = (
                counters["ckpt.saves_staged_total"] == len(save_steps))
        trainer.state = None
        # the first step's loss against the reference, on the parameters
        # the run started from (the same key makes them again)
        params = trainer.result.init_fn(key).params
        stacked = params["layers"]["layer"]
        top = {k: params[k] for k in
               ("embed_tokens", "final_norm", "lm_head")}
        ref_loss = reference.lm_loss(
            batches[0], lambda i: _layer_of(stacked, i), top,
            cfg.num_layers, cfg.rope_theta, cfg.rms_norm_eps)
        del params, stacked, top
        checks["first_loss"] = first_loss
        checks["reference_loss"] = ref_loss
        checks["loss_abs_diff"] = abs(first_loss - ref_loss)
        checks["loss_matches_reference"] = (
            abs(first_loss - ref_loss) <= LOSS_ATOL)
    finally:
        trainer.close()
        _unlink_shm(job)

    ok = all(v for k, v in checks.items() if isinstance(v, bool))
    tokens = n * rows * seq
    return {
        "end_to_end": {
            "setup_s": setup_s,
            "train_tokens_per_s": tokens / window_s / ctx.chips,
        },
        "setup": setup,
        "window_s": window_s,
        # a traced run opens and closes the profiler between steps, inside
        # the window: seconds that are neither a step's nor a save's
        "profiler_s": ctx.profiler.overhead_s,
        "compiles_in_window": ctx.compiles.inside(t_w0, t_w1),
        "counters": counters,
        "samples": {
            "step_s": [s for s, _, _ in step_s],
            "step_had_save": [d for _, d, _ in step_s],
            "save_call_s": [c for _, d, c in step_s if d],
            "tokens_per_step": rows * seq,
        },
        "shapes": {"seq": seq, "rows": rows, "heads": cfg.num_heads,
                   "head_dim": cfg.head_dim_, "layers": cfg.num_layers,
                   "remat": bool(cfg.remat)},
        "trace": trace,
        "correct": ok,
        "checks": checks,
        "attempted": n,
        "failed": 0 if checks["losses_finite"] else 1,
    }
