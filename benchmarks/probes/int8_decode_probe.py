"""Device-side probe: decode-shape (M=8) matmul strategies on v5e.

Which path streams weights at HBM peak?  Candidates:
  bf16        : a_bf16 @ w_bf16 (baseline; 2 bytes/weight)
  pallas_int8 : current prequant_matmul pallas kernel (1 byte/weight)
  xla_int8    : native XLA int8xint8->int32 dot + fused dequant
  w8a16       : int8 weights upcast in-registers, bf16 MXU matmul
                (weight-only quant: 1 byte/weight, no activation quant)

Timing: each op chained 50x inside one jitted fori_loop (device-side,
one dispatch per 50 ops); best of 5 runs.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

M, K, N = 8, 2048, 2048
ITERS = 20000


def timed(fn, *args, runs=5):
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best / ITERS


def chain(op):
    """Run op ITERS times with a data dependency via the activation."""
    @jax.jit
    def run(a, *weights):
        def body(i, a):
            out = op(a, *weights)
            # fold output back to an [M, K] activation (keep shapes)
            return (out[:, :K] * 1e-3).astype(a.dtype)
        return jax.lax.fori_loop(0, ITERS, body, a)
    return run


def main():
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(M, K), jnp.bfloat16)
    w = jnp.asarray(rng.randn(K, N) / np.sqrt(K), jnp.bfloat16)

    from dlrover_tpu.ops.pallas.quant_matmul import (
        prequant_matmul, prequantize_weight, quantize_int8,
    )

    w_q, w_scale = prequantize_weight(np.asarray(w, np.float32))
    w_q = jnp.asarray(w_q)
    w_scale = jnp.asarray(w_scale)

    results = {}

    # bf16 baseline
    results["bf16"] = timed(
        chain(lambda a, w: jnp.dot(a, w)), a, w
    )

    # current pallas kernel
    results["pallas_int8"] = timed(
        chain(lambda a, wq, ws: prequant_matmul(a, wq, ws)),
        a, w_q, w_scale,
    )

    # native XLA int8 dot: quantize activation, int8xint8->int32
    def xla_int8(a, wq, ws):
        a_q, a_s = quantize_int8(a.astype(jnp.float32), axis=-1)
        acc = jax.lax.dot_general(
            a_q, wq, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return acc.astype(jnp.float32) * a_s * ws

    results["xla_int8"] = timed(chain(xla_int8), a, w_q, w_scale)

    # weight-only: upcast int8 weights inside the dot's fusion
    def w8a16(a, wq, ws):
        wf = wq.astype(jnp.bfloat16) * ws.astype(jnp.bfloat16)
        return jnp.dot(a, wf)

    results["w8a16"] = timed(chain(w8a16), a, w_q, w_scale)

    bf16_bytes = K * N * 2
    int8_bytes = K * N
    print(f"decode matmul M={M} K={K} N={N}  ({ITERS} chained iters)")
    for name, t in results.items():
        bytes_ = int8_bytes if "8" in name and name != "bf16" else bf16_bytes
        gbps = bytes_ / t / 1e9
        print(f"  {name:12s} {t*1e6:8.2f} us/op   {gbps:7.1f} GB/s "
              f"  speedup vs bf16: {results['bf16']/t:5.2f}x")


if __name__ == "__main__":
    main()
