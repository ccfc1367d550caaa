"""Serve a Llama-family model with the continuous-batching engine.

The user-facing half of the serving story (reference counterpart: the
vLLM inference backend the reference's RL stack deploys,
atorch/atorch/rl/inference_backend/vllm_backend.py:11-24): load weights
(HF checkpoint or random init), build an :class:`InferenceEngine`, and
stream concurrent requests through it.

What this demonstrates:
- loading an HF checkpoint into serving layout (``--hf path``), or a
  random-init model for a smoke run;
- ``--int8``: weights pre-quantized ONCE into the Pallas kernel layout —
  decode streams int8 from HBM, prefill runs the MXU's native int8 dot
  (both measured >= bf16 on v5e; PERF.md serving notes);
- continuous batching: requests of different lengths admitted into
  slots as they free up, same-bucket bursts prefilled in one dispatch.

Run::

    python examples/serve_llama.py --requests 16 --int8
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--hf", default="",
                   help="HF checkpoint path (empty = random weights)")
    p.add_argument("--model", default="tiny",
                   help="LlamaConfig preset for random weights "
                        "(models.llama.PRESETS)")
    p.add_argument("--layers", type=int, default=0,
                   help="cut the preset's depth (0 = the preset's own)")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--max-new", type=int, default=32)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--int8", action="store_true")
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--speculative", type=int, default=0,
                   help="speculative_k (greedy only; forces temperature 0)")
    p.add_argument("--top-k", type=int, default=40)
    args = p.parse_args()

    import jax

    from dlrover_tpu.serving.engine import InferenceEngine

    if args.hf:
        from dlrover_tpu.models.convert import load_hf_llama

        cfg, params = load_hf_llama(args.hf, scan_layers=False)
        variables = {"params": params}
    else:
        import jax.numpy as jnp

        from dlrover_tpu.models.llama import LlamaConfig, LlamaModel

        cfg = LlamaConfig.from_preset(
            args.model, args.layers, scan_layers=False,
            max_seq_len=max(256, args.prompt_len + args.max_new))
        if args.model != "tiny":
            # initialize in the compute dtype: float32 parameters would
            # double the footprint until the engine re-lays them
            cfg = dataclasses.replace(cfg, param_dtype=cfg.dtype)
        model = LlamaModel(cfg)
        variables = jax.jit(model.init)(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )

    engine = InferenceEngine(
        cfg, variables,
        max_slots=args.slots,
        int8=args.int8,
        temperature=0.0 if args.speculative else args.temperature,
        top_k=0 if args.speculative else args.top_k,
        speculative_k=args.speculative,
    )
    rng = np.random.RandomState(0)
    rids = [
        engine.add_request(
            rng.randint(1, cfg.vocab_size, size=args.prompt_len),
            args.max_new,
        )
        for _ in range(args.requests)
    ]
    t0 = time.perf_counter()
    outputs = engine.run()
    wall = time.perf_counter() - t0

    stats = engine.stats
    total = sum(len(outputs[r]) for r in rids)
    print(f"requests={len(rids)} generated={total} tokens "
          f"wall={wall:.2f}s ({total / wall:.0f} tok/s)")
    print(f"prefill: {stats.prefill_calls} dispatches "
          f"{stats.prefill_seconds:.2f}s; decode {stats.decode_seconds:.2f}s "
          f"({stats.decode_tokens_per_sec:.0f} tok/s device loop)")
    if args.speculative:
        print(f"speculative: accepted {stats.spec_accepted}/"
              f"{stats.spec_proposed} drafts")
    print("first outputs:", {r: outputs[r][:8].tolist() for r in rids[:2]})


if __name__ == "__main__":
    main()
