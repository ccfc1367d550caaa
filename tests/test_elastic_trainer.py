"""ElasticTrainer: fixed global batch across world sizes + flash-ckpt
resume (reference behavior: dlrover/trainer/torch/elastic/trainer.py
:307-327 grad-accum adjustment; tests mirror
dlrover/trainer/tests/torch/elastic_test.py)."""

import os
import uuid

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.accel.parallel.mesh import MeshSpec
from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
from dlrover_tpu.trainer.elastic.trainer import (
    ElasticTrainer,
    plan_global_batch,
)
from dlrover_tpu.trainer.flash_checkpoint import SaverMode, StorageType


@pytest.fixture(autouse=True)
def _isolate(monkeypatch):
    job = uuid.uuid4().hex[:8]
    monkeypatch.setenv("DLROVER_JOB_UID", job)
    yield
    AsyncCheckpointSaver.reset()
    for f in os.listdir("/dev/shm"):
        if job in f:
            try:
                os.unlink(os.path.join("/dev/shm", f))
            except OSError:
                pass


def test_plan_global_batch_adjusts_accum():
    spec8 = MeshSpec(fsdp=8)
    spec4 = MeshSpec(fsdp=4)
    spec2 = MeshSpec(dp=2)
    p8 = plan_global_batch(32, spec8, micro_batch_per_shard=2)
    p4 = plan_global_batch(32, spec4, micro_batch_per_shard=2)
    p2 = plan_global_batch(32, spec2, micro_batch_per_shard=2)
    assert (p8.grad_accum_steps, p4.grad_accum_steps, p2.grad_accum_steps) == (2, 4, 8)
    for p in (p8, p4, p2):
        assert p.micro_batch_global * p.grad_accum_steps == 32
    with pytest.raises(ValueError):
        plan_global_batch(30, spec8, micro_batch_per_shard=2)


def _model():
    # fp32 end to end for a tight trajectory comparison
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    return LlamaModel(cfg), cfg


def _batch(step: int, global_batch: int, seq: int, vocab: int) -> np.ndarray:
    rng = np.random.RandomState(1000 + step)
    return rng.randint(0, vocab, size=(global_batch, seq)).astype(np.int32)


def test_scale_up_resumes_with_identical_trajectory(tmp_path):
    """3 steps on a 4-device world, save, restart on 8 devices, 3 more
    steps: the loss trajectory must match an uninterrupted 8-device run
    (same fixed global batch, resharded restored state)."""
    devices = jax.devices()
    assert len(devices) >= 8
    seq, gb = 32, 16
    ckpt_dir = str(tmp_path / "ckpt")

    # --- uninterrupted reference run: 8 devices, 6 steps ---
    model, cfg = _model()
    ref = ElasticTrainer(
        model, global_batch_size=gb, micro_batch_per_shard=2, seq_len=seq,
    )
    ref.prepare(devices=devices[:8])
    assert ref.plan.grad_accum_steps == 1
    ref.restore_or_init(jax.random.PRNGKey(0))
    ref_losses = []
    for s in range(6):
        m = ref.train_step(_batch(s, gb, seq, cfg.vocab_size))
        ref_losses.append(float(m["loss"]))

    # --- elastic run, phase A: 4 devices (accum 2) ---
    model2, _ = _model()
    tr = ElasticTrainer(
        model2, global_batch_size=gb, micro_batch_per_shard=2, seq_len=seq,
        checkpoint_dir=ckpt_dir, saver_mode=SaverMode.LOCAL,
    )
    tr.prepare(devices=devices[:4])
    assert tr.plan.grad_accum_steps == 2
    assert tr.restore_or_init(jax.random.PRNGKey(0)) == 0
    a_losses = [
        float(tr.train_step(_batch(s, gb, seq, cfg.vocab_size))["loss"])
        for s in range(3)
    ]
    assert tr.save(StorageType.MEMORY)
    pre_restart_step = tr.step
    tr.close()

    # --- phase B: "restarted" onto 8 devices, restore + continue ---
    model3, _ = _model()
    tr2 = ElasticTrainer(
        model3, global_batch_size=gb, micro_batch_per_shard=2, seq_len=seq,
        checkpoint_dir=ckpt_dir, saver_mode=SaverMode.LOCAL,
    )
    tr2.prepare(devices=devices[:8])
    assert tr2.plan.grad_accum_steps == 1
    restored = tr2.restore_or_init(jax.random.PRNGKey(42))
    assert restored == pre_restart_step == 3
    b_losses = [
        float(tr2.train_step(_batch(s, gb, seq, cfg.vocab_size))["loss"])
        for s in range(3, 6)
    ]
    tr2.close()

    # accum-2 on 4 devices must equal full-batch on 8 devices ...
    np.testing.assert_allclose(a_losses, ref_losses[:3], rtol=2e-4, atol=2e-4)
    # ... and the restarted world continues the exact trajectory
    np.testing.assert_allclose(b_losses, ref_losses[3:], rtol=2e-4, atol=2e-4)


def test_persistent_compile_cache_dir(tmp_path, monkeypatch):
    """prepare() applies the one compile-cache rule
    (utils/compile_cache), so elastic restarts (fresh processes) reuse
    compiled executables from disk: with JAX_COMPILATION_CACHE_DIR set
    nothing is set in code; unset, the fixed in-checkout directory is."""
    import jax

    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
    from dlrover_tpu.trainer.elastic.trainer import ElasticTrainer
    from dlrover_tpu.utils import compile_cache

    trainer = ElasticTrainer(
        LlamaModel(LlamaConfig.tiny(max_seq_len=32)),
        global_batch_size=8,
        micro_batch_per_shard=1,
        seq_len=32,
    )
    prev_dir = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "placed"))
        trainer.prepare()
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv(compile_cache.ENV_VAR)
        trainer.prepare()
        assert jax.config.jax_compilation_cache_dir \
            == compile_cache.DEFAULT_DIR
        assert compile_cache.DEFAULT_DIR.endswith(".jax_cache")
        assert str(tmp_path) not in compile_cache.DEFAULT_DIR
        import numpy as np
        import jax.numpy as jnp

        trainer.restore_or_init(jax.random.PRNGKey(0))
        shape = (trainer.plan.micro_batch_global, 32)
        if trainer.plan.grad_accum_steps > 1:
            shape = (trainer.plan.grad_accum_steps,) + shape
        metrics = trainer.train_step(jnp.zeros(shape, jnp.int32))
        assert np.isfinite(float(metrics["loss"]))
    finally:
        # restore global jax config for the rest of the suite
        jax.config.update("jax_compilation_cache_dir", prev_dir)


def test_restore_consensus_asks_for_the_saver_before_it_reads_shm(
        tmp_path, monkeypatch):
    """In a world of several processes the restore-step consensus reads
    this host's shm meta, which the agent's saver serves: it must ask for
    the saver first, as ``CheckpointEngine.load`` does.  On a host with
    no saver yet (a first start, a replacement) the read otherwise dials
    a socket nobody has bound until its 60 s are over, and every peer
    waits for this host (ROADMAP D22)."""
    trainer = ElasticTrainer(
        LlamaModel(LlamaConfig.tiny(max_seq_len=32)),
        global_batch_size=8, micro_batch_per_shard=1, seq_len=32,
        checkpoint_dir=str(tmp_path), saver_mode=SaverMode.LOCAL,
    )
    eng = trainer.checkpoint_engine
    calls = []
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(eng, "_ensure_saver",
                        lambda: calls.append("saver"))
    monkeypatch.setattr(eng._shm_handler, "get_meta",
                        lambda: calls.append("meta"))
    monkeypatch.setattr(trainer, "_gather_restore_steps",
                        lambda shm, storage: None)
    assert trainer._consensus_restore_decision() is None
    assert calls == ["saver", "meta"]
    trainer.close()
