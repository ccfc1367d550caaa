"""End-to-end elastic Llama pretraining example.

Launch (standalone, spawns a local master):

    dlrover-tpu-run --nnodes=1 python examples/train_llama.py \
        --steps 50 --ckpt-dir /tmp/ckpt

Everything the framework offers in one script (the counterpart of the
reference's examples/pytorch/mnist + llama2 examples):

- ``init_distributed()``: env contract -> jax.distributed;
- master-driven data sharding (``IndexShardingClient``): a dead worker's
  unconsumed shards are re-dispatched by the master;
- ``ElasticTrainer``: mesh for the current world, fixed global batch via
  grad accumulation, flash-checkpoint restore on (re)start;
- flash checkpoint cadence: shm every step, async disk persist;
- global-step reports feeding the master's SpeedMonitor.

``--model`` names a :class:`LlamaConfig` preset (``tiny`` by default, the
CPU tests' size; ``llama2_7b``, ``olmoe_1b_7b`` or ``laguna_xs2`` for real
widths) and ``--layers`` cuts its depth to what the device at hand holds —
widths are never cut (``laguna_xs2 --layers 9`` keeps its pattern: the
dense layer and two periods of window, window, window, full).

Every boot prints one ``[train] boot {json}`` line after its first step
(platform, device kind, seconds to first step: a restarted worker's is
short when the persistent compile cache hit) — what ``chip_smoke.py``
reads to know the worker itself saw the chip.

Chaos knob: ``DLROVER_CRASH_AT_STEP`` makes the worker kill itself once at
that step — the elastic agent restarts it and training resumes from the
in-memory checkpoint (what the reference's chaosblade experiments verify,
reference: docs/tech_report/fault_tolerance_exps.md).
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


def synth_tokens(index: int, seq_len: int, vocab: int) -> np.ndarray:
    """Deterministic synthetic sample: the data a shard index denotes is
    identical across restarts and world sizes."""
    rng = np.random.RandomState(7 + index)
    return rng.randint(0, vocab, size=(seq_len,)).astype(np.int32)


def main() -> int:
    t_boot = time.time()
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="tiny",
                   help="LlamaConfig preset (models.llama.PRESETS)")
    p.add_argument("--layers", type=int, default=0,
                   help="cut the preset's depth (0 = the preset's own)")
    p.add_argument("--param-dtype", default="float32",
                   choices=("float32", "bfloat16"),
                   help="parameter (and so Adam-moment) storage dtype")
    p.add_argument("--devices", type=int, default=0,
                   help="drive only the first N local devices (0 = all): "
                        "the one-device run a sharded run is compared "
                        "with, on the same host")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--micro-batch", type=int, default=1)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--ckpt-dir", default="/tmp/dlrover_tpu_example_ckpt")
    p.add_argument("--out-file", default="")
    p.add_argument("--save-memory-interval", type=int, default=1)
    p.add_argument("--save-storage-interval", type=int, default=10)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.agent.sharding.client import IndexShardingClient
    from dlrover_tpu.common.constants import NodeEnv
    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.trainer.elastic.distributed import init_distributed
    from dlrover_tpu.trainer.elastic.trainer import ElasticTrainer
    from dlrover_tpu.utils.compile_cache import cache_counts

    env = init_distributed()
    cfg = LlamaConfig.from_preset(
        args.model, args.layers, max_seq_len=args.seq_len,
        param_dtype=jnp.dtype(args.param_dtype),
    )
    model = LlamaModel(cfg)

    trainer = ElasticTrainer(
        model,
        global_batch_size=args.global_batch,
        micro_batch_per_shard=args.micro_batch,
        seq_len=args.seq_len,
        checkpoint_dir=args.ckpt_dir or None,
        save_memory_interval=args.save_memory_interval,
        save_storage_interval=args.save_storage_interval,
    )
    devices = jax.devices()[:args.devices or None]
    trainer.prepare(devices=devices)
    start_step = trainer.restore_or_init(jax.random.PRNGKey(0))
    print(f"[train] starting from step {start_step}", flush=True)
    dev = jax.devices()[0]
    n_params = sum(
        x.size for x in jax.tree_util.tree_leaves(trainer.state.params))

    master_addr = os.getenv(NodeEnv.MASTER_ADDR, "")
    client = sharding = None
    if master_addr:
        client = MasterClient(
            master_addr, node_id=env.node_rank, node_type="worker"
        )
        dataset_size = args.steps * args.global_batch
        sharding = IndexShardingClient(
            client,
            dataset_name="synth",
            batch_size=args.global_batch,
            num_epochs=1,
            # only the first boot creates the dataset; restarts re-attach
            dataset_size=dataset_size if start_step == 0 else 0,
            num_minibatches_per_shard=1,
        )

    crash_at = int(os.getenv("DLROVER_CRASH_AT_STEP", "0"))
    losses = []
    step_seconds, save_seconds = [], []
    step = start_step
    while step < args.steps:
        if sharding is not None:
            indices = sharding.fetch_batch_indices(args.global_batch)
            if not indices:
                print("[train] dataset exhausted", flush=True)
                break
        else:
            base = step * args.global_batch
            indices = list(range(base, base + args.global_batch))
        batch = np.stack(
            [synth_tokens(i, args.seq_len, cfg.vocab_size) for i in indices]
        )
        t0 = time.time()
        metrics = trainer.train_step(batch)
        jax.block_until_ready(metrics)
        step_seconds.append(time.time() - t0)
        step = trainer.step
        loss = float(metrics["loss"])
        losses.append((step, loss))
        if len(losses) == 1:
            print("[train] boot " + json.dumps({
                "start_step": start_step,
                "platform": dev.platform,
                "device_kind": dev.device_kind,
                "device_count": jax.device_count(),
                "devices_used": len(devices),
                "mesh": {k: v for k, v in trainer.result.mesh.shape.items()
                         if v > 1},
                "model": args.model, "layers": cfg.num_layers,
                "param_dtype": args.param_dtype, "params": n_params,
                "seconds_to_first_step": time.time() - t_boot,
                "first_step_seconds": step_seconds[0],
                "first_loss": loss,
                "compile_cache": cache_counts(),
            }), flush=True)
        t0 = time.time()
        # sharded runs BLOCK on the shm commit: the ack below must
        # follow a DURABLE save — with the async double-buffered engine
        # a staged-but-uncommitted save would let a crash resume one
        # step behind the acked shard stream (redoing a step on the
        # NEXT shard's data and finishing a step short)
        saved = trainer.maybe_save(block=sharding is not None)
        if saved:
            save_seconds.append((step, time.time() - t0))
        print(f"[train] step {step} loss {loss:.6f}"
              + "".join(f" {k} {float(v):.4f}" for k, v in metrics.items()
                        if k.startswith("moe_"))  # a MoE preset's routing
              + (f" save {save_seconds[-1][1]:.3f}s" if saved else ""),
              flush=True)
        if sharding is not None:
            # ack AFTER the step + checkpoint: a crash in between makes
            # the master re-dispatch the shard instead of skipping it
            sharding.report_batch_done(len(indices))
        if client is not None:
            try:
                client.report_global_step(step, time.time())
            except Exception:
                pass  # a local master may exit once the dataset completes
        if crash_at and step == crash_at and start_step == 0:
            print(f"[train] simulated crash at step {step}", flush=True)
            os._exit(23)

    if args.out_file:
        text = trainer.compiled_step_text(batch) if losses else ""
        with open(args.out_file, "w") as f:
            json.dump(
                {
                    "start_step": start_step,
                    "final_step": step,
                    "losses": losses,
                    "step_seconds": step_seconds,
                    "save_seconds": save_seconds,
                    "platform": dev.platform,
                    "device_kind": dev.device_kind,
                    "device_count": jax.device_count(),
                    "devices_used": len(devices),
                    "params": n_params,
                    "param_bytes_per_device": {
                        str(d): n for d, n in
                        trainer.param_bytes_per_device().items()},
                    "peak_bytes_in_use": (dev.memory_stats() or {}).get(
                        "peak_bytes_in_use"),
                    # what the compiled step contains, from its own
                    # text: the Pallas kernel, the FSDP collectives
                    "compiled_step": {
                        op: text.count(op) for op in (
                            "tpu_custom_call", "all-gather",
                            "reduce-scatter", "all-reduce")},
                },
                f,
            )
    print(f"[train] done at step {step}", flush=True)
    trainer.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
