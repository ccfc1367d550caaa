"""Chaos matrix: faults beyond plain node kill (VERDICT r3 item 9).

Scenarios (results table in CHAOS.md; reference:
docs/tech_report/fault_tolerance_exps.md:1-100 — the reference's
fault-injection experiment suite):

1. master restart mid-run      -> agents reconnect, run finishes
2. disk full during persist    -> save degrades, training continues,
                                  memory tier stays restorable
3. shm corruption at restore   -> detected, falls back to storage
4. agent killed during commit  -> partial stage dir never visible;
                                  restart restores last COMMITTED step
"""

import os
import signal
import subprocess
import sys
import time
import uuid

import numpy as np
import pytest

from test_elastic_spmd_e2e import wait_until_listening

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fresh_job(name):
    os.environ["DLROVER_JOB_UID"] = f"{name}{uuid.uuid4().hex[:6]}"


def _cleanup_shm():
    job = os.environ.get("DLROVER_JOB_UID", "")
    for f in os.listdir("/dev/shm"):
        if job and job in f:
            try:
                os.unlink(os.path.join("/dev/shm", f))
            except OSError:
                pass


# ---------------------------------------------------------------------------
# 1. master restart mid-run
# ---------------------------------------------------------------------------


def test_master_restart_mid_run(tmp_path):
    """Kill the master while an agent trains; a fresh master on the same
    port takes over; the agent's heartbeats/polls recover and the run
    finishes cleanly (reference: the master HA half of its fault
    matrix)."""
    from dlrover_tpu.common.rpc import find_free_port

    work = str(tmp_path)
    port = find_free_port()

    def start_master():
        return subprocess.Popen(
            [sys.executable, "-m", "dlrover_tpu.master.main",
             "--platform", "local", "--port", str(port),
             "--node_num", "1"],
            stdout=open(os.path.join(work, "master.log"), "a"),
            stderr=subprocess.STDOUT,
        )

    master = start_master()
    env = dict(os.environ)
    env.update(
        DLROVER_FORCE_CPU="1",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        DLROVER_JOB_UID=f"chaosM{uuid.uuid4().hex[:6]}",
        JAX_PLATFORMS="cpu",
    )
    agent = None
    try:
        wait_until_listening(port, master)
        agent = subprocess.Popen(
            [sys.executable, "-m", "dlrover_tpu.agent.launcher",
             "--nnodes=1", "--node_rank=0",
             f"--master-addr=127.0.0.1:{port}",
             "--max-restarts=1", "--monitor-interval=1",
             sys.executable,
             os.path.join(REPO, "examples/train_elastic_spmd.py"),
             "--steps", "8", "--global-batch", "4", "--seq-len", "32",
             "--ckpt-dir", os.path.join(work, "ckpt"),
             "--metrics-file", os.path.join(work, "metrics"),
             "--step-sleep", "1.0"],
            env=env, cwd=REPO,
            stdout=open(os.path.join(work, "agent.log"), "w"),
            stderr=subprocess.STDOUT,
            preexec_fn=os.setsid,
        )
        # wait for training to start
        m0 = os.path.join(work, "metrics.r0")
        deadline = time.time() + 300
        while time.time() < deadline:
            if os.path.exists(m0) and os.path.getsize(m0) > 0:
                break
            assert agent.poll() is None, "agent died before training"
            time.sleep(1)
        else:
            pytest.fail("training never started")

        master.kill()
        master.wait(10)
        time.sleep(3)          # agent sees poll failures meanwhile
        master = start_master()

        rc = agent.wait(300)
        assert rc == 0, f"agent exited {rc} after master restart"
        with open(m0) as f:
            last_step = int(f.read().strip().splitlines()[-1].split()[0])
        assert last_step == 8
    finally:
        if agent is not None and agent.poll() is None:
            try:
                os.killpg(os.getpgid(agent.pid), signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        master.kill()


# ---------------------------------------------------------------------------
# 2. disk full during async persist
# ---------------------------------------------------------------------------


class _DiskFullStorage:
    """Delegating storage whose writes fail with ENOSPC after arming."""

    def __init__(self, inner):
        self._inner = inner
        self.full = False
        self.failed_writes = 0

    def write(self, content, path):
        if self.full:
            self.failed_writes += 1
            raise OSError(28, "No space left on device", path)
        return self._inner.write(content, path)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_disk_full_persist_degrades_but_training_continues(tmp_path):
    _fresh_job("chaosDisk")
    from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
    from dlrover_tpu.common.storage import PosixDiskStorage
    from dlrover_tpu.trainer.flash_checkpoint import (
        Checkpointer,
        SaverMode,
        StorageType,
    )

    storage = _DiskFullStorage(PosixDiskStorage())
    ckpt = Checkpointer(
        str(tmp_path / "ckpt"), storage=storage,
        saver_mode=SaverMode.LOCAL, local_rank=0, local_world_size=1,
        node_rank=0, node_num=1,
    )
    state = {"w": np.arange(64, dtype=np.float32)}
    try:
        assert ckpt.save_checkpoint(1, state, StorageType.DISK)
        ckpt.wait_latest_checkpoint(60)
        storage.full = True           # the disk fills up mid-run
        state2 = {"w": 2.0 * np.arange(64, dtype=np.float32)}
        # persist fails under the hood; the TRAINING-side call must not
        # raise, and the memory tier keeps accepting saves
        ckpt.save_checkpoint(2, state2, StorageType.DISK)
        time.sleep(1.0)               # async persist attempts + fails
        assert storage.failed_writes > 0
        assert ckpt.save_checkpoint(3, state2, StorageType.MEMORY)
        step, loaded = ckpt.load_checkpoint(
            {"w": np.zeros(64, np.float32)})
        assert step == 3              # memory tier still restorable
        np.testing.assert_array_equal(
            np.asarray(loaded["w"]), state2["w"])
        # the disk recovers: persistence works again
        storage.full = False
        assert ckpt.save_checkpoint(4, state2, StorageType.DISK)
        ckpt.wait_latest_checkpoint(60)
    finally:
        ckpt.close()
        AsyncCheckpointSaver.reset()
        _cleanup_shm()


# ---------------------------------------------------------------------------
# 3. shm corruption detected at restore
# ---------------------------------------------------------------------------


def test_shm_corruption_falls_back_to_storage(tmp_path):
    _fresh_job("chaosShm")
    from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
    from dlrover_tpu.trainer.flash_checkpoint import (
        Checkpointer,
        SaverMode,
        StorageType,
    )

    ckpt = Checkpointer(
        str(tmp_path / "ckpt"), saver_mode=SaverMode.LOCAL,
        local_rank=0, local_world_size=1, node_rank=0, node_num=1,
    )
    state = {"w": np.arange(256, dtype=np.float32)}
    try:
        assert ckpt.save_checkpoint(5, state, StorageType.DISK)
        ckpt.wait_latest_checkpoint(60)
        # corrupt the shm metadata: shard claims more bytes than the
        # segment holds (torn write / bit rot on the metadata channel)
        handler = ckpt.engine._shm_handler
        meta = handler._meta.get()
        for leaf in meta["leaves"].values():
            for shard in leaf["shards"]:
                shard["nbytes"] = shard["nbytes"] * 1000
        handler._meta.set(meta)
        step, loaded = ckpt.load_checkpoint(
            {"w": np.zeros(256, np.float32)})
        assert step == 5              # restored from DISK, not shm
        np.testing.assert_array_equal(np.asarray(loaded["w"]), state["w"])
    finally:
        ckpt.close()
        AsyncCheckpointSaver.reset()
        _cleanup_shm()


# ---------------------------------------------------------------------------
# 4. agent killed during commit
# ---------------------------------------------------------------------------


def test_kill_during_commit_keeps_last_committed_step(tmp_path):
    """A persist that never commits (saver killed between shard write
    and rename) must stay INVISIBLE: restart restores the previous
    committed step; the stale stage dir is tolerated."""
    _fresh_job("chaosCommit")
    from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
    from dlrover_tpu.trainer.flash_checkpoint import (
        Checkpointer,
        SaverMode,
        StorageType,
    )

    ckpt_dir = str(tmp_path / "ckpt")
    ckpt = Checkpointer(
        ckpt_dir, saver_mode=SaverMode.LOCAL, local_rank=0,
        local_world_size=1, node_rank=0, node_num=1,
    )
    state5 = {"w": 5.0 * np.ones(64, np.float32)}
    state6 = {"w": 6.0 * np.ones(64, np.float32)}
    try:
        assert ckpt.save_checkpoint(5, state5, StorageType.DISK)
        ckpt.wait_latest_checkpoint(60)
        # step 6: shard data lands in the stage dir but the saver dies
        # before commit — emulated by suppressing the commit call
        saver = AsyncCheckpointSaver.get_ckpt_saver()
        real_commit = saver.commit_checkpoint
        saver.commit_checkpoint = lambda *a, **k: None
        ckpt.save_checkpoint(6, state6, StorageType.DISK)
        time.sleep(1.0)
        saver.commit_checkpoint = real_commit
    finally:
        ckpt.close()
        AsyncCheckpointSaver.reset()
        _cleanup_shm()

    # "restart": fresh checkpointer over the same dir, no shm
    _fresh_job("chaosCommit2")
    ckpt2 = Checkpointer(
        ckpt_dir, saver_mode=SaverMode.LOCAL, local_rank=0,
        local_world_size=1, node_rank=0, node_num=1,
    )
    try:
        step, loaded = ckpt2.load_checkpoint(
            {"w": np.zeros(64, np.float32)})
        assert step == 5, f"uncommitted step leaked: {step}"
        np.testing.assert_array_equal(
            np.asarray(loaded["w"]), state5["w"])
    finally:
        ckpt2.close()
        AsyncCheckpointSaver.reset()
        _cleanup_shm()
