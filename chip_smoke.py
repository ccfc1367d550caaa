#!/usr/bin/env python3
"""Prove on the chip that the two main paths still start and compute.

    python chip_smoke.py            # one TPU chip: train, then serve
    python chip_smoke.py --chips 4  # four chips: sharded training only

Default run, one phase after another, each child gone before the next:

- **train** — ``dlrover-tpu-run`` (``python -m dlrover_tpu.agent.launcher``)
  -> elastic agent -> ``examples/train_llama.py`` at ``llama2_7b`` widths,
  seq 4096 (the Pallas flash kernel), flash checkpoint on, the worker
  killed at step 3 while it holds the chip; the agent restarts it, the
  new process gets the chip, restores step 3 from shared memory and
  finishes.  ``--network-check`` runs the node check child first.
- **serve** — ``ServingRouter`` + ``WorkerSupervisor(engine="llama")``
  here in the parent, one real worker process at the same widths with a
  paged KV cache: once with bf16 pools through the Pallas paged kernel,
  once with int8 pools; a handful of long prompts, all ``Done``; the
  worker compiles every program it can dispatch and compares the kernel
  with the gather on the chip before it announces its address;
  then the worker is killed and the supervisor's respawn must get the
  chip back and serve again.

``--chips 4`` runs only the sharded training path and what it is compared
with: one worker driving four chips (fsdp=4), then the same seeded steps
on one device.

Depth is cut to what one 16 GB chip holds (printed); widths never are.
The parent never imports jax — a process that has touched JAX holds the
chip — and learns the device from what its children report.  Every phase
prints one JSON line; the LAST line of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any phase that fails, times out, or reports a platform other than
``tpu`` makes the script exit non-zero without that line.

``--rehearse`` runs the same phases at the tiny preset on whatever
backend is there (the CPU): it finds wrong paths and arguments at no chip
time, never prints an ``"ok": true`` line, and is not a chip run.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# llama2_7b widths (h4096, mlp 11008, 32 heads of 128, vocab 32000).
# train: 3 layers / bf16 parameters / AdamW = 869 M parameters, 4.86 GiB
# of state + 3.04 GiB of temporaries (memory_analysis of the real step
# compiled for a described v5e), which leaves room for the one transient
# copy of the state a flash-checkpoint save stages on the device.
# serve: 8 layers in bf16 (3.5 GiB) beside a 2560-block KV pool (5 GiB).
REAL = dict(
    model="llama2_7b", vocab=32000,
    train_layers=3, seq=4096, param_dtype="bfloat16",
    serve_layers=8, serve_dtype="bfloat16", slots=4, max_len=2560,
    block=16, blocks=2560, prefill_chunk=512,
    # 512 fits one prefill chunk, so it takes the bucketed prefill
    # program; the longer ones take the chunked one
    prompt_lens=(512, 1024, 1536, 2048), new_tokens=32,
    train_timeout=600, spawn_timeout=420, serve_timeout=180,
)
TINY = dict(
    model="tiny", vocab=256,
    train_layers=0, seq=64, param_dtype="float32",
    serve_layers=0, serve_dtype="float32", slots=2, max_len=256,
    block=8, blocks=80, prefill_chunk=32,
    prompt_lens=(24, 64, 96, 128), new_tokens=8,
    train_timeout=300, spawn_timeout=240, serve_timeout=120,
)
STEPS, CRASH_AT = 6, 3
# kernel vs gather on the chip.  tests/test_paged_kernel.py holds the
# interpreted kernel to 3e-5; the COMPILED kernel's f32 dots run as bf16
# MXU passes and measured 5.6e-5 (bf16 pools) / 8.0e-5 (int8) on outputs
# up to 1.1 (9.5e-8 with precision=HIGHEST in the kernel, at +29 % kernel
# time — PERF.md, PR 21), so the on-chip bound is MXU rounding, not 3e-5
PARITY_ATOL = 2e-4
# fsdp=4 against one device: __graft_entry__.dryrun_multichip's bound
MULTICHIP_RTOL = 2e-3


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def child_env(work: str, **extra: str) -> dict:
    """The children's environment.  Whatever the agent and its workers
    share on the host besides shared memory (the runtime-metrics and
    paral-config files, stack dumps) goes under ``work``, which is
    removed at the end; their sockets follow ``TMPDIR``
    (``common.constants.runtime_dir``).  Nothing the smoke starts
    writes outside the checkout and the temporary directory."""
    env = dict(os.environ)
    prev = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = REPO + (os.pathsep + prev if prev else "")
    env["DLROVER_RUNTIME_METRICS_PATH"] = os.path.join(
        work, "runtime_metrics.json")
    env["DLROVER_PARAL_CONFIG_PATH"] = os.path.join(
        work, "auto_paral_config.json")
    env["DLROVER_STACK_DUMP_DIR"] = os.path.join(work, "stacks")
    env.update(extra)
    return env


def run_child(cmd, env, log_path: str, timeout: float) -> int:
    """One child (and whatever it starts) in its own process group, gone
    — the whole group — before this returns.  The parent must not have
    touched JAX: it would hold the chip the child needs."""
    assert "jax" not in sys.modules, "the smoke's parent imported jax"
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            cmd, env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    check(rc is not None, f"timed out after {timeout}s: {' '.join(cmd[:6])}"
                          f" (log: {log_path})")
    return rc


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def check_device(where: str, report: dict, chips: int, rehearse: bool):
    dev = {"platform": report.get("platform"),
           "kind": report.get("device_kind"),
           "count": report.get("device_count")}
    if not rehearse:
        check(dev["platform"] == "tpu",
              f"{where} ran on platform {dev['platform']!r}, not tpu")
    check(dev["count"] == chips,
          f"{where} saw {dev['count']} device(s), expected {chips}")
    return dev


# ------------------------------------------------------------------ probe
def probe(out_dir: str, work: str, chips: int, rehearse: bool) -> dict:
    """What JAX finds here, asked of a child that exits (and so frees the
    chip) at once: without an accelerator nothing else is attempted."""
    log = os.path.join(out_dir, "probe.log")
    code = ("import jax, json; d = jax.devices(); print('PROBE ' + "
            "json.dumps({'platform': d[0].platform, 'device_kind': "
            "d[0].device_kind, 'device_count': len(d), "
            "'jax': jax.__version__}))")
    rc = run_child([sys.executable, "-c", code], child_env(work), log, 180)
    check(rc == 0, f"JAX did not start (rc {rc}):\n{tail(log, 8)}")
    lines = [ln for ln in open(log) if ln.startswith("PROBE ")]
    check(bool(lines), f"probe printed nothing:\n{tail(log, 8)}")
    report = json.loads(lines[-1][len("PROBE "):])
    check_device("the probe", report, chips, rehearse)
    emit("probe", **report)
    return report


# ------------------------------------------------------------------ train
def train_command(cfg, out_file, global_batch, ckpt, extra=()):
    return [
        sys.executable, "-m", "dlrover_tpu.agent.launcher",
        "--nnodes=1", "--nproc_per_node=1", "--monitor-interval", "1",
        *extra,
        sys.executable, os.path.join(REPO, "examples", "train_llama.py"),
        "--model", cfg["model"], "--layers", str(cfg["train_layers"]),
        "--param-dtype", cfg["param_dtype"],
        "--seq-len", str(cfg["seq"]),
        "--global-batch", str(global_batch), "--micro-batch", "1",
        "--ckpt-dir", ckpt, "--out-file", out_file,
        # a committed shm save at the crash step and at the last; the
        # agent persists the crash step's to disk at the breakpoint
        "--save-memory-interval", str(CRASH_AT),
        "--save-storage-interval", "0",
    ]


def read_train(log_path: str, out_file: str):
    """What the worker(s) printed — every boot, the crashed one too —
    and the last boot's result file."""
    boots, losses, saves = [], [], []
    for line in open(log_path, errors="replace"):
        if line.startswith("[train] boot "):
            boots.append(json.loads(line[len("[train] boot "):]))
        elif line.startswith("[train] step "):
            parts = line.split()
            losses.append((int(parts[2]), float(parts[4])))
            if "save" in parts:
                saves.append((int(parts[2]), float(parts[-1].rstrip("s"))))
    check(os.path.exists(out_file),
          f"the trainer wrote no result:\n{tail(log_path)}")
    result = json.load(open(out_file))
    result["save_seconds"] = saves
    return boots, losses, result


def unlink_shm(job: str) -> None:
    for path in glob.glob(f"/dev/shm/*{job}*"):
        try:
            os.unlink(path)
        except OSError:
            pass


def phase_train(cfg, out_dir, work, chips, rehearse) -> dict:
    job = f"smoke{os.getpid()}t"
    log = os.path.join(out_dir, "train.log")
    out_file = os.path.join(work, "train.json")
    cmd = train_command(
        cfg, out_file, 1, os.path.join(work, "ckpt"),
        extra=("--network-check", "--max-restarts", "2"))
    cmd += ["--steps", str(STEPS)]
    t0 = time.time()
    try:
        rc = run_child(
            cmd, child_env(work, DLROVER_JOB_UID=job,
                           DLROVER_CRASH_AT_STEP=str(CRASH_AT)),
            log, cfg["train_timeout"])
    finally:
        unlink_shm(job)
    check(rc == 0, f"dlrover-tpu-run exited {rc}:\n{tail(log)}")
    boots, losses, result = read_train(log, out_file)
    dev = check_device("the training worker", result, chips, rehearse)
    check(len(boots) == 2, f"expected 2 worker boots, saw {len(boots)}")
    check(boots[0]["start_step"] == 0 and boots[1]["start_step"] == CRASH_AT
          and result["start_step"] == CRASH_AT,
          f"restart did not resume at step {CRASH_AT}: boots "
          f"{[b['start_step'] for b in boots]}")
    check(result["final_step"] == STEPS,
          f"final step {result['final_step']} != {STEPS}")
    # the agent (a process without jax) persisted the crash step's shm
    # generation to storage at the breakpoint
    check(os.path.isdir(os.path.join(work, "ckpt", f"step-{CRASH_AT}")),
          f"the agent did not persist step {CRASH_AT} at the breakpoint:"
          f"\n{tail(log)}")
    check([s for s, _ in losses] == list(range(1, STEPS + 1)),
          f"steps seen {[s for s, _ in losses]}: a step was lost or redone")
    check(all(math.isfinite(x) for _, x in losses), f"loss not finite: "
          f"{losses}")
    if not rehearse:  # the tiny preset's init is not that close
        check(abs(losses[0][1] - math.log(cfg["vocab"])) < 0.5,
              f"first loss {losses[0][1]} is not near ln(vocab) = "
              f"{math.log(cfg['vocab']):.3f}")
    if not rehearse:  # the tiny step compiles too fast to be cached
        check(boots[1]["compile_cache"]["hits"] >= 1,
              f"the restarted worker compiled from nothing: {boots[1]}")
    compiled = result["compiled_step"]
    if not rehearse:
        check(compiled["tpu_custom_call"] > 0,
              "no Pallas kernel (tpu_custom_call) in the compiled step")
    steady = result["step_seconds"][1:]
    facts = dict(
        device=dev, model=cfg["model"], layers=boots[0]["layers"],
        param_dtype=cfg["param_dtype"], optimizer="adamw",
        params=boots[0]["params"], seq=cfg["seq"], global_batch=1,
        losses=[x for _, x in losses],
        resumed_at=result["start_step"], final_step=result["final_step"],
        seconds_to_first_step={"cold_boot": boots[0]["seconds_to_first_step"],
                               "restart": boots[1]["seconds_to_first_step"]},
        first_step_seconds={"cold_boot": boots[0]["first_step_seconds"],
                            "restart": boots[1]["first_step_seconds"]},
        compile_cache={"cold_boot": boots[0]["compile_cache"],
                       "restart": boots[1]["compile_cache"]},
        step_seconds_after_warmup=steady,
        save_stall_seconds=result["save_seconds"],
        peak_bytes_in_use=result["peak_bytes_in_use"],
        compiled_step=compiled, phase_seconds=time.time() - t0,
    )
    emit("train", **facts)
    return dev


# ------------------------------------------------------------------ serve
def phase_serve(cfg, out_dir, work, variant, kv_dtype, impl, seed, chips,
                rehearse, respawn) -> dict:
    """Router -> fabric -> one real worker process; the router and the
    supervisor live in THIS process and never touch JAX."""
    import numpy as np

    from dlrover_tpu.serving.remote import WorkerSupervisor
    from dlrover_tpu.serving.router import (
        ContinuousBatchScheduler,
        ServingRouter,
    )

    report_file = os.path.join(out_dir, f"serve_{variant}.json")
    if os.path.exists(report_file):
        os.unlink(report_file)
    worker_args = [
        "--model", cfg["model"], "--layers", str(cfg["serve_layers"]),
        "--dtype", cfg["serve_dtype"], "--slots", str(cfg["slots"]),
        "--max-len", str(cfg["max_len"]), "--block-size", str(cfg["block"]),
        "--blocks", str(cfg["blocks"]),
        "--prefill-chunk", str(cfg["prefill_chunk"]),
        "--kv-dtype", kv_dtype, "--attention-impl", impl,
        "--seed", str(seed), "--report-file", report_file,
    ]
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg["vocab"], n).astype(np.int32)
               for n in cfg["prompt_lens"]]

    def read_report():
        check(os.path.exists(report_file),
              f"serve[{variant}]: the worker wrote no report")
        report = json.load(open(report_file))
        check("error" not in report,
              f"serve[{variant}]: worker build failed:\n"
              f"{report.get('error')}")
        return report

    def serve(router, sup, batch):
        reqs = [router.submit(p, cfg["new_tokens"]) for p in batch]
        t0 = time.time()
        while router.has_work and time.time() - t0 < cfg["serve_timeout"]:
            router.step()
            sup.poll()
            time.sleep(0.002)
        states = [str(r.state) for r in reqs]
        check(all(s == "Done" for s in states),
              f"serve[{variant}]: request states {states}")
        for r in reqs:
            check(len(r.output) == cfg["new_tokens"]
                  and all(0 <= t < cfg["vocab"] for t in r.output),
                  f"serve[{variant}]: bad output {r.output}")
        return time.time() - t0

    assert "jax" not in sys.modules, "the smoke's parent imported jax"
    os.environ["PYTHONPATH"] = child_env(work)["PYTHONPATH"]
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=cfg["block"]))
    t0 = time.time()
    with WorkerSupervisor(router=router, engine="llama",
                          spawn_timeout=cfg["spawn_timeout"],
                          worker_args=worker_args, backoff_base=0.1) as sup:
        try:
            record = sup.spawn()
        except Exception as e:
            report = json.load(open(report_file)) \
                if os.path.exists(report_file) else {}
            raise SmokeFailure(
                f"serve[{variant}]: worker did not come up ({e}):\n"
                f"{report.get('error', '')}")
        spawn_seconds = time.time() - t0
        report = read_report()
        dev = check_device(f"the serving worker [{variant}]", report,
                           chips, rehearse)
        parity = report["kernel_parity"]
        check(parity["finite"] and parity["max_abs_err"] <= PARITY_ATOL,
              f"serve[{variant}]: kernel vs gather parity {parity}")
        if impl == "pallas":
            check(report["attention_impl"] == "pallas",
                  f"serve[{variant}]: engine runs "
                  f"{report['attention_impl']}, not the kernel")
        serve_seconds = serve(router, sup, prompts)
        facts = dict(report, device=dev, spawn_seconds=spawn_seconds,
                     prompt_lens=list(cfg["prompt_lens"]),
                     new_tokens=cfg["new_tokens"],
                     requests_done=len(prompts),
                     serve_seconds=serve_seconds)
        if respawn:
            # a dead worker held the chip: its replacement must get it
            first_pid = record.proc.pid
            os.unlink(report_file)
            sup.kill(record.name)
            t1 = time.time()
            while time.time() - t1 < cfg["spawn_timeout"]:
                sup.poll()
                live = [r for r in sup.workers.values()
                        if r.proc.pid != first_pid
                        and r.proc.poll() is None]
                if live:
                    break
                time.sleep(0.2)
            check(bool(live),
                  f"serve[{variant}]: no respawn within "
                  f"{cfg['spawn_timeout']}s")
            again = read_report()
            check_device(f"the respawned worker [{variant}]", again,
                         chips, rehearse)
            serve(router, sup, prompts[:1])
            facts["respawn"] = dict(
                seconds=time.time() - t1,
                build_seconds=again["build_seconds"],
                warmup_seconds=again["warmup_seconds"])
        procs = [r.proc for r in sup.workers.values()]
    for proc in procs:   # the chip is free only once the worker is gone
        proc.wait(timeout=60)
    facts["phase_seconds"] = time.time() - t0
    emit(f"serve[{variant}]", **facts)
    return facts["device"]


# -------------------------------------------------------------- four chips
def phase_multichip(cfg, out_dir, work, chips, rehearse) -> dict:
    """The sharded training path and what it is compared with: the same
    launcher and script, one worker driving ``chips`` devices with the
    default mesh (fsdp=chips), then the same seeded steps on one."""
    steps = 3
    runs = {}
    t0 = time.time()
    for name, devices in (("sharded", chips), ("one_device", 1)):
        job = f"smoke{os.getpid()}{name[0]}"
        log = os.path.join(out_dir, f"multichip_{name}.log")
        out_file = os.path.join(work, f"multichip_{name}.json")
        cmd = train_command(cfg, out_file, chips, "")
        cmd += ["--steps", str(steps), "--devices", str(devices)]
        try:
            rc = run_child(cmd, child_env(work, DLROVER_JOB_UID=job), log,
                           cfg["train_timeout"])
        finally:
            unlink_shm(job)
        check(rc == 0, f"multichip[{name}] exited {rc}:\n{tail(log)}")
        boots, losses, result = read_train(log, out_file)
        runs[name] = dict(boots=boots, losses=[x for _, x in losses],
                          result=result)
    sharded, single = runs["sharded"], runs["one_device"]
    dev = check_device("the sharded worker", sharded["result"], chips,
                       rehearse)
    check(sharded["result"]["devices_used"] == chips
          and single["result"]["devices_used"] == 1,
          f"devices used: {sharded['result']['devices_used']} and "
          f"{single['result']['devices_used']}, expected {chips} and 1")
    check(sharded["boots"][0]["mesh"] == {"fsdp": chips},
          f"mesh {sharded['boots'][0]['mesh']} is not fsdp={chips}")
    check(len(sharded["losses"]) == steps == len(single["losses"]),
          f"steps: {sharded['losses']} vs {single['losses']}")
    worst = max(abs(a - b) / max(abs(b), 1e-9)
                for a, b in zip(sharded["losses"], single["losses"]))
    check(worst <= MULTICHIP_RTOL,
          f"losses disagree (rel {worst:.2e} > {MULTICHIP_RTOL}): "
          f"{sharded['losses']} vs {single['losses']}")
    held = sharded["result"]["param_bytes_per_device"]
    total = sum(single["result"]["param_bytes_per_device"].values())
    check(len(held) == chips and all(
        abs(v - total / chips) <= 0.1 * total / chips
        for v in held.values()),
        f"parameters are not spread: {held} of {total} bytes")
    compiled = sharded["result"]["compiled_step"]
    check(compiled["all-gather"] > 0
          and compiled["reduce-scatter"] + compiled["all-reduce"] > 0,
          f"no FSDP collectives in the compiled step: {compiled}")
    if not rehearse:
        check(compiled["tpu_custom_call"] > 0,
              "no Pallas kernel (tpu_custom_call) in the sharded step")
    emit("multichip", device=dev, model=cfg["model"],
         layers=sharded["boots"][0]["layers"], seq=cfg["seq"],
         global_batch=chips, mesh=sharded["boots"][0]["mesh"],
         losses_sharded=sharded["losses"],
         losses_one_device=single["losses"], max_rel_diff=worst,
         rtol=MULTICHIP_RTOL, param_bytes_per_device=held,
         param_bytes_total=total, compiled_step=compiled,
         step_seconds_sharded=sharded["result"]["step_seconds"],
         step_seconds_one_device=single["result"]["step_seconds"],
         peak_bytes_sharded=sharded["result"]["peak_bytes_in_use"],
         peak_bytes_one_device=single["result"]["peak_bytes_in_use"],
         phase_seconds=time.time() - t0)
    return dev


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the sharded training path and its "
                        "one-device comparison (the builder runs this)")
    p.add_argument("--seed", type=int, default=0,
                   help="prompts and weights are made from this")
    p.add_argument("--rehearse", action="store_true",
                   help="tiny preset on whatever backend is there; "
                        "never an ok line — not a chip run")
    args = p.parse_args(argv)
    if os.environ.get("DLROVER_DISABLE_PALLAS"):
        print("chip_smoke: DLROVER_DISABLE_PALLAS is set — the smoke "
              "exists to run the kernels; unset it", file=sys.stderr)
        return 2
    cfg = TINY if args.rehearse else REAL
    out_dir = os.path.join(REPO, "chiprun_out", "chip_smoke")
    os.makedirs(out_dir, exist_ok=True)
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    t0 = time.time()
    try:
        probe(out_dir, work, args.chips, args.rehearse)
        if args.chips == 4:
            dev = phase_multichip(cfg, out_dir, work, 4, args.rehearse)
        else:
            dev = phase_train(cfg, out_dir, work, 1, args.rehearse)
            for variant, kv, impl, respawn in (
                    ("bf16-pallas", "bf16", "pallas", False),
                    ("int8-auto", "int8", "auto", True)):
                seen = phase_serve(cfg, out_dir, work, variant, kv, impl,
                                   args.seed, 1, args.rehearse, respawn)
                check(seen == dev, f"device changed: {seen} vs {dev}")
    except SmokeFailure as e:
        print(f"chip_smoke FAILED after {time.time() - t0:.0f}s: {e}",
              file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    emit("total", seconds=time.time() - t0)
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True, "device": dev}))
        return 0
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
