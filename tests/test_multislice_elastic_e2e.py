"""Multi-slice ELASTICITY end to end (VERDICT r3 missing #3).

Two emulated TPU slices (DLROVER_SLICE_ID, 2 hosts each, node_unit=2)
train on a hybrid DCN mesh — dp replica per slice, fsdp spanning each
slice's hosts (MeshSpec.hybrid).  One host of slice 1 is SIGKILLed:

- the master's slice-aware rendezvous admission drops the WHOLE broken
  slice (its ICI domain is incomplete) — the orphan member is rounded
  out and waits;
- slice 0 re-forms alone (hybrid n_slices=1), restores from its own
  hosts' shm, and keeps training;
- a replacement host joins with the dead host's slice id: both slices
  re-rendezvous and the 2-slice hybrid mesh re-forms;
- the loss trajectory matches an uninterrupted 2-slice reference run
  step for step across all three world phases.

Reference counterpart: node-loss-at-scale rendezvous
(dlrover/python/master/elastic_training/rdzv_manager.py:291-343) +
slice topology grouping (net_topology.py:62).
"""

import os
import signal
import subprocess
import sys

import numpy as np

from test_elastic_spmd_e2e import (
    assert_steps_consistent,
    local_master,
    read_metrics,
    running_agents,
    wait_for_rows,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOTAL_STEPS = 16
KILL_AFTER_STEP = 2
SEQ, GB = 32, 8
SLICE_UNIT = 2  # hosts per slice


def _agent_cmd(node_rank, master_addr, work):
    return [
        sys.executable, "-m", "dlrover_tpu.agent.launcher",
        "--nnodes=2:4", f"--node_rank={node_rank}",
        f"--master-addr={master_addr}",
        "--max-restarts=3", "--monitor-interval=1",
        "--rdzv-waiting-timeout=5", f"--node_unit={SLICE_UNIT}",
        sys.executable, os.path.join(REPO, "examples/train_elastic_spmd.py"),
        "--steps", str(TOTAL_STEPS), "--global-batch", str(GB),
        "--seq-len", str(SEQ), "--slice-unit", str(SLICE_UNIT),
        "--ckpt-dir", os.path.join(work, "ckpt"),
        "--metrics-file", os.path.join(work, "metrics"),
        "--step-sleep", "4.0",
    ]


def _start_agent(rank, port, work, agents, tag=""):
    env = dict(os.environ)
    env.update(
        DLROVER_FORCE_CPU="1",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        DLROVER_JAX_HEARTBEAT_TIMEOUT="20",
        DLROVER_JOB_UID=f"msE2e{rank}{tag}",
        DLROVER_MONITOR_INTERVAL="1",
        DLROVER_SLICE_ID=str(rank // SLICE_UNIT),
        JAX_PLATFORMS="cpu",
        # (the test session's compile cache is inherited: the regrown
        # world re-enters programs the first world already compiled;
        # without it the replacement's cold compile outlives the
        # remaining steps)
    )
    agents[rank] = subprocess.Popen(
        _agent_cmd(rank, f"127.0.0.1:{port}", work),
        env=env, cwd=REPO,
        stdout=open(os.path.join(work, f"agent{rank}{tag}.log"), "w"),
        stderr=subprocess.STDOUT,
        preexec_fn=os.setsid,
    )


def _reference_losses():
    """Uninterrupted in-process 2-slice run: hybrid(2, 4) on 8 devices."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.accel.parallel.mesh import MeshSpec
    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.trainer.elastic.trainer import ElasticTrainer

    cfg = LlamaConfig.tiny(max_seq_len=SEQ, dtype=jnp.float32)
    tr = ElasticTrainer(
        LlamaModel(cfg),
        global_batch_size=GB,
        micro_batch_per_shard=1,
        seq_len=SEQ,
        mesh_spec=MeshSpec.hybrid(2, 4),
    )
    tr.prepare(devices=jax.devices()[:8])
    tr.restore_or_init(jax.random.PRNGKey(0))
    losses = []
    for step in range(TOTAL_STEPS):
        rng = np.random.RandomState(1000 + step)
        batch = rng.randint(
            0, cfg.vocab_size, size=(GB, SEQ)
        ).astype(np.int32)
        losses.append(float(tr.train_step(batch)["loss"]))
    tr.close()
    return losses


def test_slice_loss_shrinks_then_regrows(tmp_path):
    work = str(tmp_path)
    with local_master(work, 4) as port, running_agents() as agents:
        for rank in range(4):
            _start_agent(rank, port, work, agents)

        # phase 1: the 4-host / 2-slice world must train past the kill
        # step (worker_num == 4 in the metrics)
        m0 = os.path.join(work, "metrics.r0")
        wait_for_rows(
            m0, agents[0],
            lambda rows: any(s >= KILL_AFTER_STEP and w == 4
                             for s, _, w in rows),
            600, f"the 2-slice world at step {KILL_AFTER_STEP}")

        # kill ONE host of slice 1 (rank 3): the whole slice must leave
        os.killpg(os.getpgid(agents[3].pid), signal.SIGKILL)
        agents[3].wait(30)

        # phase 2: slice 0 re-forms ALONE (worker_num == 2) and trains
        wait_for_rows(
            m0, agents[0],
            lambda rows: any(w == 2 for _, _, w in rows),
            600, "slice 0 training alone")

        # phase 3: a replacement host for slice 1 joins -> regrow to 4
        _start_agent(3, port, work, agents, tag="b")
        rc0 = agents[0].wait(900)
        assert rc0 == 0, f"agent0 exited {rc0}"

        rows = read_metrics(m0)
        worlds = {s: w for s, _, w in rows}
        steps = assert_steps_consistent(rows, max_redos=4)  # kill+regrow x async commit
        assert steps[-1] == TOTAL_STEPS
        assert 4 in worlds.values() and 2 in worlds.values(), worlds
        shrink_step = min(s for s, w in worlds.items() if w == 2)
        assert shrink_step > KILL_AFTER_STEP
        regrown = {s for s, w in worlds.items()
                   if w == 4 and s > shrink_step}
        assert regrown, f"world never regrew to 2 slices: {worlds}"

        ref = _reference_losses()
        for s, loss, _ in rows:
            assert np.isclose(loss, ref[s - 1], rtol=1e-3, atol=1e-3), (
                s, loss, ref[s - 1])
