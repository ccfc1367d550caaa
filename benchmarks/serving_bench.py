"""Serving-engine benchmark: continuous-batching decode throughput.

Measures the VERDICT r3 item-1 "done" criteria on the real chip:

- ``serving_tok_s_bf16`` / ``serving_tok_s_int8``: aggregate decode
  tokens/sec at 8 concurrent slots (prompt 128, generate 128 each);
- ``serving_int8_speedup``: int8 / bf16 (target >= 1.2 — weights
  pre-quantized into the Pallas kernel layout, streaming from HBM at
  half the bf16 bytes on the bandwidth-bound decode path);
- ``serving_batch_scaling``: slots-8 aggregate throughput / slots-1
  throughput (continuous batching must scale, target >> 1).

Each config runs in its OWN subprocess (one JSON line on stdout) so an
HBM-arena failure or compile flake in one config cannot poison the
others — invoked with no argument, this script fans out over configs
and merges the lines.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

PROMPT_LEN = 128
GEN_LEN = 128
N_REQUESTS = 8


def _platform() -> str:
    import jax

    return jax.devices()[0].platform


def _engine_cfg():
    """The bench-model geometry (496M, bench.py): MXU-saturating shapes.
    Every engine mode measures the chip or nothing — without a TPU it
    fails; it does not shrink to a toy model."""
    from dlrover_tpu.models.llama import LlamaConfig

    if _platform() != "tpu":
        raise RuntimeError(
            f"serving_bench needs a TPU; JAX found {_platform()!r} — a "
            "CPU run is not a smaller measurement of the same thing")
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=8192,
        num_layers=6, num_heads=16, num_kv_heads=4,
        max_seq_len=4096, scan_layers=True, remat=False,
    )
    return cfg, PROMPT_LEN, GEN_LEN, N_REQUESTS


def run_config(mode: str) -> dict:
    import jax
    import numpy as np

    from dlrover_tpu.models.llama import LlamaModel
    from dlrover_tpu.serving.engine import InferenceEngine

    cfg, prompt_len, gen_len, n_req = _engine_cfg()
    int8 = mode.startswith("int8")
    slots = 1 if mode.endswith("slots1") else 8
    model = LlamaModel(cfg)
    probe = jax.numpy.zeros((1, 8), jax.numpy.int32)
    variables = model.init(jax.random.PRNGKey(0), probe)
    eng = InferenceEngine(
        cfg, variables, max_slots=slots, int8=int8, chunk=32,
        temperature=1.0, top_k=50,
        max_len=prompt_len + gen_len, seed=0,
    )
    rng = np.random.RandomState(0)
    prompts = rng.randint(0, cfg.vocab_size,
                          (n_req, prompt_len)).astype(np.int32)
    # warmup: compile prefill + chunk
    for i in range(min(2, n_req)):
        eng.add_request(prompts[i], gen_len)
    eng.run()
    # Best of 3 trials, like every number on this rig: the shared
    # host's dispatch latency and memory bandwidth swing >10x
    # second-to-second, and a single sample measures the neighbor.
    best_wall, best_decode, best_prefill = None, 0.0, None
    best_prefill_calls = 1
    for _ in range(3):
        eng.stats.generated_tokens = 0
        eng.stats.decode_seconds = 0.0
        eng.stats.prefill_seconds = 0.0
        eng.stats.prefill_calls = 0
        t0 = time.perf_counter()
        for i in range(n_req):
            eng.add_request(prompts[i], gen_len)
        eng.run()
        wall = time.perf_counter() - t0
        if best_wall is None or wall < best_wall:
            best_wall = wall
            best_prefill = eng.stats.prefill_seconds
            best_prefill_calls = max(1, eng.stats.prefill_calls)
        best_decode = max(best_decode, eng.stats.decode_tokens_per_sec)
    total_tokens = n_req * gen_len
    out = {
        f"serving_tok_s_{mode}": round(total_tokens / best_wall, 1),
        f"serving_decode_tok_s_{mode}": round(best_decode, 1),
        # AGGREGATE prefill seconds for the whole run: a slots=1 config
        # pays one dispatch per admission while slots=8 batches
        # same-bucket admissions into 1-2 dispatches, so this number is
        # ~n_req x larger at slots=1 on a dispatch-dominated rig — an
        # admission-batching artifact, not a per-request penalty (the
        # per-dispatch number below is flat across configs)
        f"serving_prefill_s_{mode}": round(best_prefill, 3),
        f"serving_prefill_s_per_call_{mode}": round(
            best_prefill / best_prefill_calls, 3),
    }
    out.update(_decode_step_probe(eng, mode))
    return out


def _decode_step_probe(eng, mode: str) -> dict:
    """Device-side decode step time: chained chunk dispatches with ONE
    sync — isolates the model from per-call dispatch latency."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n_chunks, trials = 3, 3
    eng._admit()
    tokens = jnp.asarray(eng._tokens)
    positions = jnp.zeros(eng.max_slots, jnp.int32) + 1
    active = jnp.asarray(np.ones(eng.max_slots, bool))
    cache, rng = eng._cache, eng._rng
    out, tokens, positions, cache, rng = eng._chunk_fn(
        eng.params, cache, tokens, positions, active, rng)
    jax.block_until_ready(out)
    best = None
    for _ in range(trials):
        t0 = time.perf_counter()
        outs = []
        for _ in range(n_chunks):
            out, tokens, positions, cache, rng = eng._chunk_fn(
                eng.params, cache, tokens, positions, active, rng)
            outs.append(out)
        jax.block_until_ready(outs)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    steps = n_chunks * eng.chunk
    eng._cache, eng._rng = cache, rng
    return {
        f"serving_decode_step_ms_{mode}": round(best / steps * 1e3, 3),
    }


def run_spec_config() -> dict:
    """Speculative decoding on a self-similar workload: tokens
    committed per model forward (the speculation win; bar: > 1.5) and
    the TRUE draft accept ratio, measured on the FITTED chain
    instrument (:func:`_fit_chain_model`) rather than random-init
    weights.  Runs ``paged=True``: accepted drafts commit through
    ``scatter_tokens`` into BlockManager blocks (incl. the spec-slack
    overflow block), so this config is the bench proof that
    speculation and paging compose — the books-balance assert below
    would catch a leak.

    Two fixes over the old config (the ``accept_rate=0.0`` artifact
    PR 14 verified pre-existing):

    - the per-trial stat reset wiped the spec counters before they
      were read — trial 1's proposals vanished, and once the
      speculation governor backed off, trials 2-3 proposed nothing, so
      the reported ratio was 0/0 -> a structural 0.0 regardless of
      what speculation actually did.  The spec counters now RESET ONCE
      before the measured trials and ACCUMULATE across them (they are
      a ratio's numerator/denominator, not a wall-clock rate), and the
      config asserts proposals are nonzero so the artifact class
      cannot return silently;
    - random-init weights genuinely accept ~0 drafts (near-uniform
      logits never agree with a prompt-lookup draft), which made the
      governor's back-off the CORRECT behavior and the measurement
      meaningless — the same reason PR 14 fitted the int4 agreement
      instrument.  The chain model's greedy continuation IS the
      periodic chain the drafts are looked up from, so the measured
      ratio reflects what speculation does on a model with real
      margins (~1.0 here; production models land in between)."""
    import numpy as np

    from dlrover_tpu.serving.engine import InferenceEngine

    cfg, params, chain, fit_loss = _fit_chain_model()
    gen_len, n_req = 16, 4
    eng = InferenceEngine(
        cfg, params, max_slots=4, int8=False, chunk=16,
        temperature=0.0, speculative_k=8, paged=True,
        block_size=16, max_len=128, seed=0,
    )
    # prompt = two periods of the mod-64 affine chain: prompt-lookup
    # finds its drafts in the first period, the model (fitted on the
    # chain) accepts them
    prompt = chain(5, 64)
    # warmup with a FULL admission group so the measured run compiles
    # nothing (insert_fn is cached per group size)
    for _ in range(eng.max_slots):
        eng.add_request(prompt, 8)
    eng.run()
    # spec counters reset ONCE: the ratio accumulates across all
    # measured trials (resetting per trial is what created the 0.0
    # artifact); wall-clock counters reset per trial for best-of-3
    eng.stats.spec_proposed = 0
    eng.stats.spec_accepted = 0
    eng.stats.spec_calls = 0
    eng.stats.decode_seconds = 0.0
    best_wall = None
    best_tpf = 0.0
    for _ in range(3):
        eng.stats.generated_tokens = 0
        eng.stats.decode_forwards = 0
        t0 = time.perf_counter()
        for _ in range(n_req):
            eng.add_request(prompt, gen_len)
        eng.run()
        wall = time.perf_counter() - t0
        best_tpf = max(best_tpf, eng.stats.tokens_per_forward)
        best_wall = wall if best_wall is None else min(best_wall, wall)
    wall = best_wall
    assert eng._blockmgr.available_blocks == \
        eng._blockmgr.num_blocks - 1, "paged spec leaked blocks"
    assert eng.stats.spec_proposed > 0, (
        "speculation proposed nothing across 3 trials — the governor "
        "backed off or the drafts never fired; the accept ratio below "
        "would be the 0/0 artifact, not a measurement")
    accept = eng.stats.spec_accept_ratio
    assert accept > 0.0, (
        f"accept ratio 0.0 with {eng.stats.spec_proposed} proposals: "
        "the fitted instrument should accept chain drafts")
    return {
        "serving_tokens_per_forward": round(best_tpf, 2),
        "serving_spec_accept_rate": round(accept, 3),
        "serving_spec_proposed": int(eng.stats.spec_proposed),
        "serving_spec_tok_s": round(
            eng.stats.generated_tokens / wall, 1),
        "serving_spec_fit_loss": round(fit_loss, 5),
        "serving_spec_paged": True,
    }


def run_chunked_config() -> dict:
    """The prefill-stall rig: worst inter-token gap across decoding
    slots WHILE a max-length prompt prefills, chunked vs monolithic.

    Three slots decode steadily; a max-length prompt is then admitted.
    Unchunked, its whole prefill serializes ahead of the next decode
    dispatch — every slot's token cadence stalls for ~the prefill
    (~0.1s on the rig).  With ``prefill_chunk`` the prompt advances
    one bounded chunk per step, so the worst gap is one decode chunk
    plus one prefill chunk (the <=2-decode-chunks acceptance bound).
    Gap = wall time of each engine step from the long admission until
    its first token (each step emits tokens for every decoding slot,
    so step wall IS the inter-token gap); best-of-3 of the per-trial
    worst, like every number on this shared rig."""
    import jax
    import numpy as np

    from dlrover_tpu.models.llama import LlamaModel
    from dlrover_tpu.serving.engine import InferenceEngine

    cfg, prompt_len, gen_len, _ = _engine_cfg()
    long_len = min(cfg.max_seq_len - gen_len, 2048)
    short_len = prompt_len
    chunk = 8
    prefill_chunk = 256
    max_len = long_len + gen_len
    model = LlamaModel(cfg)
    probe = jax.numpy.zeros((1, 8), jax.numpy.int32)
    variables = model.init(jax.random.PRNGKey(0), probe)
    rng = np.random.RandomState(0)
    shorts = rng.randint(0, cfg.vocab_size,
                         (3, short_len)).astype(np.int32)
    long_prompt = rng.randint(0, cfg.vocab_size,
                              long_len).astype(np.int32)

    def worst_gap(pc: int) -> tuple:
        eng = InferenceEngine(
            cfg, variables, max_slots=4, chunk=chunk, temperature=1.0,
            top_k=50, max_len=max_len, prefill_chunk=pc, seed=0,
        )

        def one_trial():
            # companions decode with budget to spare across the
            # whole long prefill
            rids = [eng.add_request(p, max_len - short_len)
                    for p in shorts]
            eng.step()
            # decode-only reference gap (post-compile steady state)
            t0 = time.perf_counter()
            eng.step()
            decode_ms = (time.perf_counter() - t0) * 1e3
            long_rid = eng.add_request(long_prompt, 4)
            gaps = []
            while True:
                t0 = time.perf_counter()
                finished = eng.step()
                gaps.append((time.perf_counter() - t0) * 1e3)
                started = any(
                    r is not None and r.rid == long_rid and r.output
                    for r in eng._slot_req if r is not None
                ) or any(f.rid == long_rid for f in finished)
                if started:
                    break
            # drain: cancel the open-budget companions, finish the rest
            for r in rids:
                eng.cancel(r)
            eng.run()
            return max(gaps), decode_ms

        one_trial()  # warmup: compiles every program shape
        best_gap, best_decode = None, None
        for _ in range(3):
            g, d = one_trial()
            best_gap = g if best_gap is None else min(best_gap, g)
            best_decode = d if best_decode is None \
                else min(best_decode, d)
        return best_gap, best_decode

    stall_chunked, decode_ms = worst_gap(prefill_chunk)
    stall_unchunked, _ = worst_gap(0)

    # SAME-STEP BATCHED prefill: two long prompts admitted together
    # must reach their first tokens in the SAME number of engine
    # steps (their chunks ride one batched verify_step dispatch per
    # step) — round-robin one-chunk-per-step would make the second
    # TTFT ~2x the first in step terms.  Steps, not wall: the
    # deserialization claim is structural and this rig's wall clock
    # is too noisy to show a 2x cleanly.
    eng = InferenceEngine(
        cfg, variables, max_slots=4, chunk=chunk, temperature=1.0,
        top_k=50, max_len=max_len, prefill_chunk=prefill_chunk,
        seed=0,
    )
    long2 = np.stack([long_prompt,
                      np.roll(long_prompt, 7)]).astype(np.int32)
    rids = [eng.add_request(p, 4) for p in long2]
    ttft_steps = {}
    for step_n in range(1, 4 * (long_len // prefill_chunk + 2)):
        finished = eng.step()
        for r in list(eng._slot_req) + list(finished):
            if r is not None and r.rid in rids and r.output \
                    and r.rid not in ttft_steps:
                ttft_steps[r.rid] = step_n
        if len(ttft_steps) == len(rids):
            break
    eng.run()
    first_s = ttft_steps.get(rids[0], 0)
    second_s = ttft_steps.get(rids[1], 0)
    return {
        # worst inter-token gap while the max-length prompt prefills
        "prefill_stall_p99_ms": round(stall_chunked, 3),
        "prefill_stall_unchunked_ms": round(stall_unchunked, 3),
        "prefill_stall_decode_chunk_ms": round(decode_ms, 3),
        "prefill_chunk_tokens": prefill_chunk,
        # the acceptance bound: the gap stays within 2 decode chunks
        "prefill_stall_ok": bool(stall_chunked <= 2.0 * decode_ms),
        "prefill_batch_ttft_steps_first": first_s,
        "prefill_batch_ttft_steps_second": second_s,
        "prefill_batch_ttft_ratio": round(
            second_s / first_s, 3) if first_s else 0.0,
    }


def _paged_throughput_probe(tag: str, kv_dtype) -> tuple:
    """ONE quantized-KV throughput rig (engine build, warmup, best-of-3
    wall, decode-step probe) shared by the int8kv and int4kv modes —
    the timing methodology must not fork between kv dtypes or their
    numbers silently measure different things.  Returns (metrics dict,
    engine) so each mode can add its dtype-specific gates."""
    import jax
    import numpy as np

    from dlrover_tpu.models.llama import LlamaModel
    from dlrover_tpu.serving.engine import InferenceEngine

    cfg, prompt_len, gen_len, n_req = _engine_cfg()
    model = LlamaModel(cfg)
    probe = jax.numpy.zeros((1, 8), jax.numpy.int32)
    variables = model.init(jax.random.PRNGKey(0), probe)
    rng = np.random.RandomState(0)
    prompts = rng.randint(0, cfg.vocab_size,
                          (n_req, prompt_len)).astype(np.int32)
    eng = InferenceEngine(
        cfg, variables, max_slots=8, chunk=32, temperature=1.0,
        top_k=50, max_len=prompt_len + gen_len, paged=True,
        kv_dtype=kv_dtype, seed=0,
    )
    for i in range(min(2, n_req)):
        eng.add_request(prompts[i], gen_len)
    eng.run()  # warmup/compile
    best_wall = None
    for _ in range(3):
        eng.stats.generated_tokens = 0
        t0 = time.perf_counter()
        for i in range(n_req):
            eng.add_request(prompts[i], gen_len)
        eng.run()
        wall = time.perf_counter() - t0
        best_wall = wall if best_wall is None else min(best_wall, wall)
    out = {f"serving_tok_s_{tag}": round(
        n_req * gen_len / best_wall, 1)}
    out.update(_decode_step_probe(eng, tag))
    return out, eng


def run_int8kv_config() -> dict:
    """int8 paged KV: throughput + block budget at the same HBM.  The
    budget claim is structural (kv_budget_x = how many int8 blocks fit
    in one native block's bytes; bar >= 1.9), the throughput numbers
    keep the quantized gather/scatter's cost honest next to the bf16
    paged engine."""
    out = {}
    for tag, kv_dtype in (("paged_bf16", None), ("paged_int8", "int8")):
        probe_out, eng = _paged_throughput_probe(tag, kv_dtype)
        out.update(probe_out)
        if kv_dtype == "int8":
            out["kv_budget_x"] = round(eng.kv_budget_x, 3)
            out["serving_kv_quant_blocks"] = eng.kv_quant_blocks
    # structural gate: int8 blocks per native block's HBM (>= 1.9x
    # doubles-ish the continuous batch the placement ledger can admit)
    out["kv_budget_ok"] = bool(out.get("kv_budget_x", 0.0) >= 1.9)
    return out


def run_pallas_config() -> dict:
    """The fused paged-attention kernel vs the XLA fused gather, at
    the serving engine's real pool geometry — the evidence behind
    ``attention_impl="auto"`` and the ``paged_kernel_ok`` gate.

    Needs a TPU (interpret-mode parity off the chip is
    tests/test_paged_kernel.py's job):

    - PARITY: the compiled kernel vs the gather reference for bf16 and
      int8 pools (packed int4 does not compile on a TPU — recorded as
      ``paged_kernel_int4``, not measured);
    - TIMINGS: best-of-5 per impl per kv dtype via
      ``measure_paged_attention`` on the engine's own pools, plus the
      engine's own build-time auto-pick.  The gate holds ``auto`` to
      its contract: the resolved impl is the measured argmin."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models.llama import LlamaModel
    from dlrover_tpu.models.quantize import quantize_kv_int8
    from dlrover_tpu.ops.pallas.paged_attention import (
        INT4_REFUSAL,
        gather_reference,
        measure_paged_attention,
        paged_decode_attention,
        resolve_attention_impl,
    )
    from dlrover_tpu.serving.engine import InferenceEngine

    cfg, prompt_len, gen_len, _ = _engine_cfg()
    model = LlamaModel(cfg)
    probe = jax.numpy.zeros((1, 8), jax.numpy.int32)
    variables = model.init(jax.random.PRNGKey(0), probe)
    eng = InferenceEngine(
        cfg, variables, max_slots=8, chunk=8, temperature=0.0,
        max_len=prompt_len + gen_len, paged=True, seed=0,
    )
    out = {"serving_attention_impl_auto": eng.attention_impl}
    if eng.attention_impl_us:
        out["serving_paged_auto_xla_us"] = round(
            eng.attention_impl_us["xla"], 1)
        out["serving_paged_auto_pallas_us"] = round(
            eng.attention_impl_us["pallas"], 1)

    # representative operands off the engine's own pool geometry
    rng = np.random.RandomState(0)
    d = cfg.head_dim_
    nb = eng._blockmgr.num_blocks
    mb = eng._max_blocks
    bsz = eng.block_size
    B = eng.max_slots
    q = jnp.asarray(rng.randn(B, cfg.num_heads, d).astype(np.float32))
    kf = jnp.asarray(
        rng.randn(nb, bsz, cfg.num_kv_heads, d).astype(np.float32)
        * 0.3)
    vf = jnp.asarray(
        rng.randn(nb, bsz, cfg.num_kv_heads, d).astype(np.float32)
        * 0.3)
    table = jnp.asarray(
        (np.arange(B * mb) % max(1, nb - 1) + 1)
        .reshape(B, mb).astype(np.int32))
    lengths = jnp.asarray(
        np.linspace(1, mb * bsz, B).astype(np.int32))

    pools = {"bf16": (kf.astype(cfg.dtype), vf.astype(cfg.dtype),
                      None, None)}
    k8, ks8 = quantize_kv_int8(kf)
    v8, vs8 = quantize_kv_int8(vf)
    pools["int8"] = (k8, v8, ks8, vs8)
    # packed int4 pools do not compile on a TPU (PR 21): on record,
    # not measured under the kernel's name
    out["paged_kernel_int4"] = INT4_REFUSAL

    parity_ok = True
    for tag, (kp, vp, ks, vs) in pools.items():
        kern = np.asarray(paged_decode_attention(
            q, kp, vp, table, lengths, k_scale=ks, v_scale=vs))
        ref = np.asarray(gather_reference(
            q, kp, vp, table, lengths, ks, vs))
        err = float(np.max(np.abs(kern - ref)))
        out[f"paged_kernel_parity_err_{tag}"] = round(err, 8)
        scale = float(np.max(np.abs(ref))) or 1.0
        parity_ok = parity_ok and err <= 2e-2 * scale
        t = measure_paged_attention(
            q, kp, vp, table, lengths, ks, vs, trials=5)
        out[f"serving_paged_gather_us_{tag}"] = round(
            t["xla"] * 1e6, 1)
        out[f"serving_paged_kernel_us_{tag}"] = round(
            t["pallas"] * 1e6, 1)
    out["paged_kernel_parity_ok"] = bool(parity_ok)
    # the auto contract: auto picked the argmin of its measurements
    timings = eng.attention_impl_us
    out["paged_kernel_ok"] = bool(
        parity_ok
        and eng.attention_impl
        == resolve_attention_impl("auto", timings))
    return out


def _fit_chain_model(steps: int = 300):
    """A tiny D=64 model briefly FIT on a deterministic next-token
    chain (x' = (3x + 7) mod vocab) — the greedy-agreement instrument
    for quantized KV.  Random-init weights have near-uniform logits
    whose argmax flips under ANY per-element noise above ~1e-2, so
    int4's honest ~10% KV reconstruction error (the 4-bit floor on
    Gaussian data) would read as catastrophic when the real claim
    (KVQuant) is about TRAINED models with real margins; a fitted
    chain model has those margins, so agreement measures what int4
    actually breaks.  ~30s on CPU, seconds on TPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel

    vocab = 64
    cfg = LlamaConfig(
        vocab_size=vocab, hidden_size=128, intermediate_size=256,
        num_layers=2, num_heads=2, num_kv_heads=2, max_seq_len=128,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    model = LlamaModel(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

    def chain(x0, n):
        outp = [int(x0)]
        for _ in range(n - 1):
            outp.append((outp[-1] * 3 + 7) % vocab)
        return np.asarray(outp, np.int32)

    def batch(rng, n=32, length=33):
        return jnp.asarray(np.stack(
            [chain(rng.randint(0, vocab), length) for _ in range(n)]))

    def loss_fn(p, toks):
        logits = model.apply(p, toks[:, :-1])
        lp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(
            lp, toks[:, 1:, None], -1))

    @jax.jit
    def sgd(p, toks):
        loss, g = jax.value_and_grad(loss_fn)(p, toks)
        return jax.tree_util.tree_map(
            lambda w, gw: w - 0.5 * gw, p, g), loss

    rng = np.random.RandomState(0)
    loss = None
    for _ in range(steps):
        params, loss = sgd(params, batch(rng))
    return cfg, params, chain, float(loss)


def run_int4kv_config() -> dict:
    """int4 packed KV: block budget, throughput, and greedy agreement
    — the ``kv4_ok`` gate.  Budget + throughput come from the bench
    geometry (structural + honest-throughput, random weights are
    fine); AGREEMENT comes from the briefly-fitted chain model
    (:func:`_fit_chain_model` explains why random-init margins would
    measure the wrong thing), greedy bf16 twin vs int4 on held-out
    chain prompts, bar 0.9."""
    import numpy as np

    from dlrover_tpu.serving.engine import InferenceEngine

    out, eng = _paged_throughput_probe("paged_int4", "int4")
    out["kv_budget4_x"] = round(eng.kv_budget_x, 3)
    out["serving_kv_int4_blocks"] = eng.kv4_blocks

    # greedy agreement on the fitted instrument
    fit_cfg, fit_params, chain, fit_loss = _fit_chain_model()
    out["kv4_fit_loss"] = round(fit_loss, 5)
    frng = np.random.RandomState(7)
    fprompts = [chain(frng.randint(0, 64), 24) for _ in range(6)]

    def gen(kv_dtype):
        e = InferenceEngine(
            fit_cfg, fit_params, max_slots=4, chunk=4,
            temperature=0.0, paged=True, block_size=16,
            kv_dtype=kv_dtype, max_len=64, seed=0)
        rids = [e.add_request(p, 16) for p in fprompts]
        res = e.run()
        return [res[r] for r in rids]

    agree = float(np.mean([
        np.mean(a == b) for a, b in zip(gen(None), gen("int4"))
    ]))
    out["kv4_greedy_agreement"] = round(agree, 4)
    # structural budget bar: engine multiplier >= 3.5 (bf16 models:
    # 3.76x @ D=64, 3.88x @ D=128; fp32 CPU fallback is higher still)
    out["kv4_ok"] = bool(
        out["kv_budget4_x"] >= 3.5 and agree >= 0.9)
    return out


def run_trace_config() -> dict:
    """Tracing overhead through the FULL router path (gateway span
    stamping + placement/submit/first-token spans + histograms) at
    sample_rate 1.0 vs 0.01, µs per request.  Uses the FakeEngine so
    the number isolates the observability plane from model math — the
    cost a millions-of-users fleet pays per request, and the saving
    the sampling knob buys."""
    import numpy as np

    from dlrover_tpu.serving.router import (
        ContinuousBatchScheduler,
        RequestGateway,
        ServingRouter,
    )
    from dlrover_tpu.serving.remote.worker import FakeEngine

    n_req = 400
    rng = np.random.RandomState(0)
    prompts = rng.randint(0, 32000, (n_req, 32)).astype(np.int32)

    def one_run(rate: float) -> float:
        router = ServingRouter(
            gateway=RequestGateway(
                max_pending=n_req + 1, trace_sample_rate=rate),
            scheduler=ContinuousBatchScheduler(block_size=4),
        )
        router.join_replica(
            "bench-0", FakeEngine(slots=16, tokens_per_step=8,
                                  blocks=1_000_000))
        t0 = time.perf_counter()
        reqs = [router.submit(p, 16) for p in prompts]
        router.run_until_idle()
        wall = time.perf_counter() - t0
        assert all(len(r.output) == 16 for r in reqs)
        return wall / n_req * 1e6  # µs per request

    # INTERLEAVED best-of-5 (rate pairs back to back): this shared
    # host's load drifts second-to-second, and sequential blocks would
    # measure the neighbor, not the knob.  Span STAMPING is always on
    # (incident completeness requires it), so the two numbers are
    # expected to be close — the knob's real saving at scale is ring
    # retention + worker-side span shipping, not router-side stamping.
    fulls, sampleds = [], []
    for _ in range(5):
        fulls.append(one_run(1.0))
        sampleds.append(one_run(0.01))
    full, sampled = min(fulls), min(sampleds)
    return {
        "serving_trace_us_per_req_rate_1": round(full, 2),
        "serving_trace_us_per_req_rate_001": round(sampled, 2),
        "serving_trace_sampling_saving": round(
            (full - sampled) / full, 3),
    }


def main() -> dict:
    out = {}
    for mode in ("bf16", "int8", "bf16_slots1", "spec", "trace",
                 "chunked", "int8kv", "int4kv", "pallas"):
        try:
            proc = subprocess.run(
                [sys.executable, __file__, mode],
                capture_output=True, text=True, timeout=1800,
                env=dict(os.environ),
            )
        except subprocess.TimeoutExpired:
            out[f"serving_error_{mode}"] = "timeout after 1800s"
            continue
        line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() \
            else ""
        try:
            out.update(json.loads(line))
        except (json.JSONDecodeError, ValueError):
            out[f"serving_error_{mode}"] = (
                (proc.stderr or "no output").strip()[-300:])
    if "serving_tok_s_bf16" in out and "serving_tok_s_int8" in out:
        out["serving_int8_speedup"] = round(
            out["serving_tok_s_int8"] / out["serving_tok_s_bf16"], 3)
    if ("serving_decode_step_ms_bf16" in out
            and "serving_decode_step_ms_int8" in out):
        out["serving_int8_decode_speedup"] = round(
            out["serving_decode_step_ms_bf16"]
            / out["serving_decode_step_ms_int8"], 3)
    if "serving_tok_s_bf16" in out and "serving_tok_s_bf16_slots1" in out:
        out["serving_batch_scaling"] = round(
            out["serving_tok_s_bf16"] / out["serving_tok_s_bf16_slots1"],
            2)
    # decode raw-speed gate (ROADMAP: decode step < 2ms).  This process
    # never imports jax (it would hold the chip its children need): the
    # platform is what the children reported
    if out.get("serving_platform") == "tpu" \
            and "serving_decode_step_ms_bf16" in out:
        out["decode_step_bar_ms"] = 2.0
        out["decode_step_ok"] = bool(
            out["serving_decode_step_ms_bf16"]
            <= out["decode_step_bar_ms"])
    return out


_MODES = {
    "spec": run_spec_config,
    "chunked": run_chunked_config,
    "int8kv": run_int8kv_config,
    "int4kv": run_int4kv_config,
    "pallas": run_pallas_config,
}


if __name__ == "__main__":
    if len(sys.argv) > 1:
        mode = sys.argv[1]
        if mode == "trace":       # host-only rig (FakeEngine, no jax)
            result = run_trace_config()
        else:
            from dlrover_tpu.utils.compile_cache import ensure_compile_cache

            ensure_compile_cache()
            result = _MODES[mode]() if mode in _MODES \
                else run_config(mode)
            result["serving_platform"] = _platform()
        print(json.dumps(result))
    else:
        result = main()
        print(json.dumps(result))
        # a mode that errored or timed out fails the whole run
        sys.exit(1 if any(k.startswith("serving_error")
                          for k in result) else 0)
