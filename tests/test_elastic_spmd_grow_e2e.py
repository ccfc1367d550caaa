"""The growth half of ``test_elastic_spmd_e2e.py``'s story, in a file of
its own: xdist's ``--dist loadfile`` hands one FILE to one worker, and
each of the e2e worlds takes minutes, so a file holds one of them and
the worlds run side by side (every round of every job has a coordinator
port of its own, offered with the rendezvous join)."""

import os
import subprocess

import numpy as np

from test_elastic_spmd_e2e import (
    REPO,
    TOTAL_STEPS,
    agent_cmd,
    assert_steps_consistent,
    local_master,
    read_metrics,
    reference_losses,
    running_agents,
    wait_for_rows,
)


def test_scale_up_mid_run_grows_world(tmp_path):
    """Growth half of the elasticity story with REAL processes: node 0
    trains solo, node 1 joins mid-run, node 0's agent notices the
    waiting member, restarts into the 2-process jax.distributed world,
    and the run continues from shm with the same trajectory."""
    work = str(tmp_path)

    def start_agent(rank, port, agents):
        env = dict(os.environ)
        env.update(
            DLROVER_FORCE_CPU="1",
            XLA_FLAGS="--xla_force_host_platform_device_count=2",
            DLROVER_JAX_HEARTBEAT_TIMEOUT="15",
            DLROVER_JOB_UID=f"spmdGrow{rank}",
            DLROVER_MONITOR_INTERVAL="1",
            JAX_PLATFORMS="cpu",
        )
        agents[rank] = subprocess.Popen(
            # slow steps: the solo phase must outlive the joiner's boot
            agent_cmd(rank, f"127.0.0.1:{port}", work, step_sleep=2.0),
            env=env, cwd=REPO,
            stdout=open(os.path.join(work, f"agent{rank}.log"), "w"),
            stderr=subprocess.STDOUT,
            preexec_fn=os.setsid,
        )

    with local_master(work, 2) as port, running_agents() as agents:
        start_agent(0, port, agents)
        # solo world forms after the last-call window; wait for steps
        m0 = os.path.join(work, "metrics.r0")
        wait_for_rows(
            m0, agents[0],
            lambda rows: any(s >= 2 and w == 1 for s, _, w in rows),
            300, "the solo world at step 2")

        start_agent(1, port, agents)  # join mid-run

        rc0 = agents[0].wait(400)
        assert rc0 == 0, "agent0 failed after scale-up"
        rc1 = agents[1].wait(60)
        assert rc1 == 0, "agent1 failed"

        rows = read_metrics(m0)
        worlds = {s: w for s, _, w in rows}
        assert worlds[TOTAL_STEPS] == 2, (
            f"final steps did not run on the grown world: {rows}"
        )
        grow_step = min(s for s, w in worlds.items() if w == 2)
        assert grow_step > 1
        assert_steps_consistent(rows, max_redos=2)  # 1 growth restart x async commit
        ref = reference_losses()
        for s, loss, _ in rows:
            assert np.isclose(loss, ref[s - 1], rtol=1e-3, atol=1e-3), (
                s, loss, ref[s - 1]
            )
