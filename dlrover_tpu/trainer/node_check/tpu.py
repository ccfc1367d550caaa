"""Node-check workload: prove this host's accelerators compute and
communicate.

Counterpart of the reference's node-check scripts (reference:
dlrover/trainer/torch/node_check/nvidia_gpu.py:24-38 — a matmul plus an
allreduce in a sub-world), TPU-native: a jitted matmul on every local
device, a ``psum`` across local chips over ICI, and — when the agent's
check rendezvous grouped this host with peers (env
``DLROVER_CHECK_WORLD`` > 1) — a cross-host collective over DCN via a
``jax.distributed`` world of the group members, so inter-host faults are
observable by the master's group-intersection localization.

Run as ``python -m dlrover_tpu.trainer.node_check.tpu``.
"""

from __future__ import annotations

import os
import sys
import time
from functools import partial


def _init_group_world() -> bool:
    """Join the check group's jax.distributed world if one was assigned."""
    world = int(os.environ.get("DLROVER_CHECK_WORLD", "1"))
    coordinator = os.environ.get("DLROVER_CHECK_COORDINATOR", "")
    if world <= 1 or not coordinator:
        return False
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=world,
        process_id=int(os.environ.get("DLROVER_CHECK_RANK", "0")),
        initialization_timeout=120,
    )
    return True


def run_check(matmul_size: int = 1024, iters: int = 3) -> float:
    from dlrover_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    import jax

    multihost = _init_group_world()

    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devices = jax.local_devices()
    if not devices:
        raise RuntimeError("no local accelerator devices")
    start = time.time()

    # per-device matmul (MXU exercise)
    for dev in devices:
        x = jax.device_put(
            jnp.ones((matmul_size, matmul_size), jnp.bfloat16), dev
        )
        y = x
        for _ in range(iters):
            y = jnp.dot(y, x, preferred_element_type=jnp.float32).astype(
                jnp.bfloat16
            )
        if not bool(jnp.isfinite(y.astype(jnp.float32)).all()):
            raise RuntimeError(f"non-finite matmul result on {dev}")

    # cross-device psum over ICI (collective exercise)
    if len(devices) > 1:
        mesh = Mesh(devices, ("x",))
        data = jax.device_put(
            jnp.arange(len(devices) * 128, dtype=jnp.float32).reshape(
                len(devices), 128
            ),
            NamedSharding(mesh, PartitionSpec("x")),
        )

        @jax.jit
        def reduce(d):
            return jnp.sum(d, axis=0)

        total = reduce(data)
        expected = float(
            jnp.sum(
                jnp.arange(len(devices) * 128, dtype=jnp.float32).reshape(
                    len(devices), 128
                ),
                axis=0,
            )[0]
        )
        if abs(float(total[0]) - expected) > 1e-3:
            raise RuntimeError("cross-device reduction mismatch")

    # cross-host collective over DCN (group exercise)
    if multihost:
        from jax.experimental import multihost_utils

        nprocs = jax.process_count()
        me = jax.process_index()
        gathered = multihost_utils.process_allgather(
            jnp.full((8,), float(me), jnp.float32)
        )
        if gathered.shape[0] != nprocs:
            raise RuntimeError(
                f"group allgather returned {gathered.shape[0]} of {nprocs}"
            )
        if abs(float(gathered.sum()) - 8.0 * sum(range(nprocs))) > 1e-3:
            raise RuntimeError("group allgather value mismatch")
    return time.time() - start


def run_comm_perf(mbytes: int = 64, iters: int = 5,
                  include_ici: bool = True,
                  include_dcn: bool = False) -> dict:
    """Collective bandwidth measurement (reference: dlrover-run
    --comm-perf-test): ICI allreduce bus bandwidth across local chips
    and, when ``include_dcn`` (which requires GROUP-WIDE agreement, see
    main()), DCN allgather bandwidth across hosts."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    out: dict = {}
    devices = jax.local_devices()
    n = len(devices)
    if include_ici and n > 1:
        per_dev = mbytes * (1 << 20) // 4 // n
        mesh = Mesh(devices, ("x",))
        sharded = NamedSharding(mesh, PartitionSpec("x"))
        data = jax.device_put(jnp.ones((n, per_dev), jnp.float32), sharded)

        # out_shardings pins the result back onto the 'x' axis: feeding a
        # replicated output into the next iteration would change the
        # input sharding, force a recompile mid-timing, and turn the
        # "allreduce" into a communication-free local sum
        @partial(jax.jit, out_shardings=sharded)
        def allreduce(d):
            # sum over the sharded axis => XLA all-reduce over ICI
            s = jnp.sum(d, axis=0)
            return jnp.broadcast_to(s, d.shape)

        allreduce(data).block_until_ready()  # compile
        t0 = time.time()
        for _ in range(iters):
            data = allreduce(data)
        data.block_until_ready()
        dt = (time.time() - t0) / iters
        nbytes = per_dev * 4 * n
        # ring-allreduce bus bandwidth convention: 2(n-1)/n * payload
        out["ici_allreduce_gbps"] = round(
            2 * (n - 1) / n * nbytes / dt / 1e9, 2)
    if include_dcn:
        from jax.experimental import multihost_utils

        # per-host payload mbytes/8 (the allgather result is world x
        # that, so total traffic stays bounded on big groups)
        payload = jnp.ones((mbytes * (1 << 20) // 8 // 4,), jnp.float32)
        multihost_utils.process_allgather(payload)  # warm up
        t0 = time.time()
        for _ in range(iters):
            gathered = multihost_utils.process_allgather(payload)
        dt = (time.time() - t0) / iters
        out["dcn_allgather_gbps"] = round(
            gathered.nbytes / max(dt, 1e-9) / 1e9, 2)
    return out


def _group_agrees_on_comm_perf() -> bool:
    """DCN perf is a BLOCKING group collective: every member must enter
    or none may (a host whose agent lacked --comm-perf-test would exit
    and strand the others until timeout, and the master would then flag
    healthy hosts as faulty).  Agreement rides a 1-element allgather of
    the local flag — cheap, and safe ONLY because main() runs this vote
    unconditionally on every multihost check process."""
    if int(os.environ.get("DLROVER_CHECK_WORLD", "1")) <= 1:
        return False
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    mine = 1.0 if os.environ.get("DLROVER_COMM_PERF", "") == "1" else 0.0
    votes = multihost_utils.process_allgather(jnp.asarray([mine]))
    agreed = bool((votes > 0).all())
    if mine and not agreed:
        print("comm perf skipped: not all group members enabled it")
    return agreed


def main() -> int:
    try:
        elapsed = run_check()
        # the agreement vote runs on EVERY multihost check process so
        # flag-mismatched groups can't strand each other in a collective
        want_perf = os.environ.get("DLROVER_COMM_PERF", "") == "1"
        group_perf = _group_agrees_on_comm_perf()
        if want_perf or group_perf:
            perf = run_comm_perf(include_ici=want_perf,
                                 include_dcn=group_perf)
            if perf:
                print(f"comm perf: {perf}")
    except Exception as e:  # any failure = unhealthy node
        print(f"node check FAILED: {e}", file=sys.stderr)
        return 1
    print(f"node check ok in {elapsed:.2f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
