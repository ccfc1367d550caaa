"""Goodput measured end to end — the reference's headline metric
(reference README.md:54-57: "the time spent computing useful new steps
over the elapsed time of the training job", GLM-65B 69% -> 95%).

A real master + agent + worker run with an injected mid-training crash:
the agent detects the dead worker, restarts it, the worker resumes from
the in-memory flash checkpoint, and the master's JobMetricCollector —
fed by the agent's TrainingMonitor step reports — accounts every second
of detection, respawn, recompile, restore and re-done work as downtime.
The gate is steady-state goodput >= 0.90 across the injected kill +
recovery.

Scale model: steps are paced to ~real-TPU step time (seconds) on the
CPU host, and the JAX persistent compilation cache plays the role a
warm compile cache plays on a production cluster (the restarted
process compiles in ~1s instead of ~10s).  The downtime being divided
by is fully real: monitor latency, process respawn, jax init, restore.
"""

import os
import subprocess
import sys

from test_elastic_spmd_e2e import local_master, running_agents

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOTAL_STEPS = 80
CRASH_AT = 12
STEP_SLEEP = 2.5
SEQ, GB = 32, 8

# NOTE like the other distributed e2es: the >=0.90 gate divides real
# productive time by real recovery downtime, so heavy NEIGHBOR load
# (e.g. the multi-process elastic e2es running just before this in one
# session on the 1-core host) stretches recovery and can push a
# genuinely healthy run under the bar.  Judge a failure only from an
# isolated run.  TOTAL_STEPS x STEP_SLEEP is sized to tolerate ~20 s
# of recovery downtime at the 0.90 bar.


def test_goodput_artifact_survives_injected_kill(tmp_path):
    work = str(tmp_path)
    from dlrover_tpu.agent.master_client import MasterClient

    env = dict(os.environ)
    env.update(
        DLROVER_FORCE_CPU="1",
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        DLROVER_JOB_UID="goodputE2e",
        # tight step sampling: the goodput ledger should see (nearly)
        # every step boundary, not 15s aggregates
        DLROVER_MONITOR_INTERVAL="0.5",
        # warm-compile scale model: the restarted worker hits the
        # persistent cache the way a production job hits a warm cache
        # (the test session's, inherited: every program kept)
    )
    with local_master(work, 1) as port, running_agents() as agents:
        agent = agents[0] = subprocess.Popen(
            [
                sys.executable, "-m", "dlrover_tpu.agent.launcher",
                "--nnodes=1", "--node_rank=0",
                f"--master-addr=127.0.0.1:{port}",
                "--max-restarts=2", "--monitor-interval=0.5",
                "--rdzv-waiting-timeout=3",
                sys.executable,
                os.path.join(REPO, "examples/train_elastic_spmd.py"),
                "--steps", str(TOTAL_STEPS),
                "--global-batch", str(GB), "--seq-len", str(SEQ),
                "--ckpt-dir", os.path.join(work, "ckpt"),
                "--metrics-file", os.path.join(work, "metrics"),
                "--step-sleep", str(STEP_SLEEP),
                "--crash-at-step", str(CRASH_AT),
                "--crash-marker", os.path.join(work, "crashed"),
            ],
            env=env, cwd=REPO,
            stdout=open(os.path.join(work, "agent.log"), "w"),
            stderr=subprocess.STDOUT,
            preexec_fn=os.setsid,
        )
        rc = agent.wait(800)
        assert rc == 0, f"agent exited {rc}"
        assert os.path.exists(os.path.join(work, "crashed")), (
            "the injected crash never fired"
        )

        client = MasterClient(
            f"127.0.0.1:{port}", node_id=0, node_type="worker"
        )
        try:
            detail = client.query_job_detail()
        finally:
            client.close()
        g = detail["metrics"]["goodput"]
        assert g["productive_s"] > 0, g
        # the ledger must have SEEN the kill: some of the steady window
        # (post-first-step) is downtime, so steady goodput < 1.
        #
        # Diagnosis of the long-standing seed failure here (ISSUE 9
        # satellite): the GOODPUT ATTRIBUTION was the bug, not this
        # timing assumption.  The worker resumes from the in-memory
        # checkpoint at exactly the crash step, so the first
        # post-restart report is one step AHEAD of the last pre-crash
        # one — no rollback signal — and on a fast recovery (warm
        # compile cache + ~ms shm restore) the bridging interval fell
        # UNDER the ledger's 3x-median stall radar and was credited as
        # fully productive, zeroing the downtime this assert requires.
        # Fixed by `JobMetricCollector.mark_restart()`: the servicer
        # flags the ledger on every NodeFailure report, and the next
        # credited interval is capped at the typical per-step rate —
        # detection + respawn + restore time lands in downtime_s even
        # when recovery is fast.
        assert g["restarts_observed"] >= 1, g
        assert g["steady_wall_s"] - g["productive_s"] > 2.0, g
        assert g["steady_goodput"] < 0.999, g
        # ...and recovery fast enough that steady goodput clears the
        # reference's bar on a run that includes a kill + full recovery
        assert g["steady_goodput"] >= 0.90, g
