"""Selective activation offloading at long context: HBM vs step time.

VERDICT r3 item 7 measurement: the seq-16k primary shape's memory wall
is the saved matmul outputs (PERF.md); `remat_policy="offload_dots"`
stages them to the TPU host's pinned memory during forward and streams
them back for backward (XLA-scheduled D2H/H2D overlap) — the TPU-native
counterpart of the reference's
atorch/atorch/auto/opt_lib/selective_offloading_checkpoint.py:252.

Prints one JSON line per policy: step time + device peak bytes.
Run each policy in its own process (`--policy ...`) so peak-memory
stats are not polluted by the previous compile.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

POLICIES = (
    "dots_with_no_batch_dims_saveable",   # r3 baseline
    "offload_dots",                       # offload every saved dot
    "offload_names:mlp_out,attn_out",     # selective: widest tensors
)

# memory evidence, two ways: ``peak_hbm_gb`` from the device's
# memory_stats, and CAPACITY — the longest context each policy can
# actually train at (batch 1, primary geometry)
CAPACITY_SEQS = (16384, 24576, 32768, 49152)


def run_policy(policy: str, seq: int = 16384, steps: int = 4,
               warmup: int = 2) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.accel.accelerate import AccelerateConfig, accelerate
    from dlrover_tpu.accel.parallel.mesh import (
        MeshSpec,
        mfu_denominator_flops,
    )
    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=8192,
        num_layers=6, num_heads=16, num_kv_heads=4, max_seq_len=seq,
        scan_layers=True, remat=True, remat_policy=policy,
    )
    res = accelerate(
        LlamaModel(cfg),
        optimizer=optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1),
        config=AccelerateConfig(mesh_spec=MeshSpec.for_device_count(1)),
        batch_shape=(1, seq),
    )
    state = res.init_fn(jax.random.PRNGKey(0))
    ids = jax.random.randint(
        jax.random.PRNGKey(1), (1, seq), 0, cfg.vocab_size
    ).astype(jnp.int32)
    batch = {"input_ids": ids}
    for _ in range(warmup):
        state, m = res.train_step(state, batch)
    float(m["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = res.train_step(state, batch)
    loss = float(m["loss"])
    step_s = (time.perf_counter() - t0) / steps
    stats = jax.local_devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", 0)
    out = {
        f"policy": policy,
        "seq_len": seq,
        "step_time_s": round(step_s, 4),
        "loss": round(loss, 4),
        "peak_hbm_gb": round(peak / 2**30, 3),
    }
    peak_flops = mfu_denominator_flops(jax.devices()[0].device_kind)
    from dlrover_tpu.accel.parallel.mesh import model_flops_per_token

    out["mfu"] = round(
        (seq / step_s) * model_flops_per_token(cfg, seq_len=seq)
        / peak_flops, 4)
    return out


def _run_sub(policy: str, seq: int) -> dict:
    proc = subprocess.run(
        [sys.executable, __file__, "--policy", policy, "--seq", str(seq)],
        capture_output=True, text=True, timeout=2400,
        env=dict(os.environ),
    )
    line = (proc.stdout.strip().splitlines() or [""])[-1]
    try:
        return json.loads(line)
    except json.JSONDecodeError:
        return {"policy": policy, "seq_len": seq,
                "error": (proc.stderr or "no output")[-300:]}


def main() -> None:
    rows = []
    for policy in POLICIES:
        out = _run_sub(policy, 16384)
        rows.append(out)
    # capacity sweep: baseline vs full offload
    for policy in (POLICIES[0], POLICIES[1]):
        max_ok = 0
        for seq in CAPACITY_SEQS:
            out = _run_sub(policy, seq)
            if "error" in out:
                rows.append({"policy": policy, "seq_len": seq,
                             "capacity": "OOM/fail",
                             "detail": out.get("error", "")[-120:]})
                break
            max_ok = seq
            rows.append(out)
        rows.append({"policy": policy, "max_seq_trained": max_ok})
    print(json.dumps(rows))


if __name__ == "__main__":
    if "--policy" in sys.argv:
        policy = sys.argv[sys.argv.index("--policy") + 1]
        seq = int(sys.argv[sys.argv.index("--seq") + 1]) \
            if "--seq" in sys.argv else 16384
        print(json.dumps(run_policy(policy, seq=seq)))
    else:
        main()
