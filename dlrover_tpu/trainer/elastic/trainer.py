"""ElasticTrainer — fixed-global-batch training that survives world-size
changes.

Counterpart of the reference's ``ElasticTrainer``
(reference: dlrover/trainer/torch/elastic/trainer.py:181-336): there the
trainer wraps the optimizer and adjusts gradient-accumulation so
``micro_batch * world_size * accum == global_batch`` stays constant as
nodes come and go (trainer.py:307-327).  TPU-native differences:

- the "world" is a device mesh, not a process group: on membership change
  the agent restarts the training process, which rebuilds the mesh for the
  new device count and re-jits (a compile cache keyed by the accelerate
  strategy avoids recompiling configurations seen before);
- training state survives the restart through Flash Checkpoint: the shm
  restore path rebuilds GSPMD-sharded arrays under the NEW mesh from the
  saved global-index metadata (resharding is free at restore time);
- gradient accumulation runs inside the jitted step (lax.scan over
  microbatches), so "adjusting accumulation" is part of the strategy, not
  a Python loop change.
"""

from __future__ import annotations

import dataclasses
import os
import re
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import numpy as np

from dlrover_tpu.accel.accelerate import (
    AccelerateConfig,
    AccelerateResult,
    accelerate,
)
from dlrover_tpu.accel.parallel.mesh import (
    MeshSpec,
    logical_rules_context,
    num_data_shards,
)
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.trainer.flash_checkpoint import (
    Checkpointer,
    SaverMode,
    StorageType,
)
from dlrover_tpu.utils.compile_cache import ensure_compile_cache
from dlrover_tpu.utils.profiler import (
    abstract,
    program_texts,
    register_program,
    span,
    step_span,
)

# accelerate() results keyed by (mesh dims, accum, batch shape, seq, model
# id) — a restarted process starts cold, but within one process an
# elasticity experiment revisiting a world size reuses the compiled step.
_COMPILE_CACHE: Dict[Tuple, AccelerateResult] = {}


@dataclasses.dataclass(frozen=True)
class ElasticBatchPlan:
    """How a fixed global batch maps onto the current world."""

    global_batch_size: int
    micro_batch_per_shard: int
    data_shards: int
    grad_accum_steps: int

    @property
    def micro_batch_global(self) -> int:
        return self.micro_batch_per_shard * self.data_shards


def plan_global_batch(
    global_batch_size: int,
    mesh_spec: MeshSpec,
    micro_batch_per_shard: int,
) -> ElasticBatchPlan:
    """Keep the global batch fixed by solving for grad accumulation
    (reference: trainer.py:307-327 ``_adjust_grad_accum``)."""
    shards = num_data_shards(mesh_spec)
    micro_global = micro_batch_per_shard * shards
    if global_batch_size % micro_global:
        raise ValueError(
            f"global batch {global_batch_size} is not divisible by "
            f"micro_batch {micro_batch_per_shard} x {shards} data shards"
        )
    return ElasticBatchPlan(
        global_batch_size=global_batch_size,
        micro_batch_per_shard=micro_batch_per_shard,
        data_shards=shards,
        grad_accum_steps=global_batch_size // micro_global,
    )


def expert_weight_copies(step_text: str) -> int:
    """How many instructions of a compiled step's text
    (:meth:`ElasticTrainer.compiled_step_text`) make an operand
    [groups, k, n] of a grouped expert matmul (``gmm.<n>``) by a
    ``dynamic-slice``, alone or in a fusion: copies of a layer's expert
    weights out of the scan's stack, which a Pallas call cannot slice as
    its operand (``models/moe.py grouped_matmul`` reads the stack in
    place: 0).  The text holds a loop's body once, so this counts by body
    and not by step: 6 for each sparse layer of a body where every call
    gets its copy (3 forward, 3 in the backward's recomputed forward)."""
    made_by, slicing, calls, computation = {}, set(), [], None
    for line in step_text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            computation = head.group(1)
            continue
        m = re.match(
            r"\s+(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\((.*)", line)
        if not m:
            continue
        name, dims, opcode, rest = m.groups()
        if opcode == "dynamic-slice":
            slicing.add(computation)
        if opcode == "custom-call" and re.match(r"gmm(\.\d+)?$", name):
            calls.append(rest.split(")", 1)[0])
        called = re.search(r"calls=%([^\s,]+)", rest)
        made_by[name] = (opcode, called and called.group(1),
                         dims.count(",") + 1)
    copies = set()
    for operands in calls:
        for operand in re.findall(r"%([^\s,)]+)", operands):
            opcode, called, rank = made_by.get(operand, (None, None, 0))
            if rank == 3 and (opcode == "dynamic-slice" or (
                    opcode == "fusion" and called in slicing)):
                copies.add(operand)
    return len(copies)


def _step_texts(res: AccelerateResult, shapes) -> Tuple[str, str]:
    """``utils/profiler.program_texts`` of the train step as compiled
    for ``res``'s mesh over ``shapes`` = (state, batch), arrays or their
    ``abstract`` shapes: a compile-cache hit once the step has run."""
    with logical_rules_context(res.config.logical_rules), res.mesh:
        return program_texts(res.jit_train_step, *shapes)


class ElasticTrainer:
    """Drives fixed-global-batch training across elastic restarts.

    Usage (inside the training script the agent [re]spawns)::

        trainer = ElasticTrainer(
            model, global_batch_size=64, micro_batch_per_shard=2,
            seq_len=2048, checkpoint_dir="/ckpt")
        trainer.prepare(devices=jax.devices())   # mesh for CURRENT world
        trainer.restore_or_init(jax.random.PRNGKey(0))
        while trainer.step < total_steps:
            batch = next(data)      # [accum, global_micro, seq] int32
            metrics = trainer.train_step(batch)
            trainer.maybe_save()
    """

    def __init__(
        self,
        model: Any,
        *,
        global_batch_size: int,
        micro_batch_per_shard: int,
        seq_len: int,
        checkpoint_dir: Optional[str] = None,
        optimizer: Any = None,
        loss_fn: Optional[Callable] = None,
        mesh_spec: Optional[MeshSpec] = None,
        mesh_spec_fn: Optional[Callable[[Sequence[Any]], MeshSpec]] = None,
        accel_config: Optional[AccelerateConfig] = None,
        save_memory_interval: int = 1,
        save_storage_interval: int = 50,
        saver_mode: SaverMode = SaverMode.AUTO,
        metrics_every: int = 1,
        xprof_every_n_steps: int = 0,
        metrics_port: Optional[int] = None,
    ):
        self._model = model
        self._global_batch_size = global_batch_size
        self._micro_batch_per_shard = micro_batch_per_shard
        self._seq_len = seq_len
        self._optimizer = optimizer
        self._loss_fn = loss_fn
        self._mesh_spec = mesh_spec
        # elasticity-aware strategy: called with the CURRENT world's
        # device list on every prepare(), so a multi-host job can keep
        # "dp over hosts x fsdp within host" as the world resizes
        self._mesh_spec_fn = mesh_spec_fn
        self._accel_config = accel_config
        self._save_memory_interval = save_memory_interval
        self._save_storage_interval = save_storage_interval
        self._ckpt = (
            Checkpointer(checkpoint_dir, saver_mode=saver_mode)
            if checkpoint_dir else None
        )
        if self._ckpt is not None:
            self._install_flush_on_term()
        self.result: Optional[AccelerateResult] = None
        # how a step is dispatched: ``_first_step`` after ``prepare``,
        # then the jitted step itself
        self._step: Optional[Callable] = None
        self.plan: Optional[ElasticBatchPlan] = None
        self.state: Any = None
        from dlrover_tpu.utils.profiler import StepTimer

        self._step_timer = StepTimer()
        self._metrics_every = metrics_every
        # transparent per-kernel/collective timing (reference xpu_timer,
        # atorch/dev/xpu_timer/nvidia/hook.cc): every N steps ONE train
        # step runs under an XLA trace; the op breakdown lands on the
        # Prometheus endpoint with zero user instrumentation
        self.auto_profiler = None
        self.metrics_exporter = None
        if xprof_every_n_steps > 0:
            from dlrover_tpu.utils.xprof_metrics import AutoProfiler

            self.auto_profiler = AutoProfiler(every_n=xprof_every_n_steps)
        if metrics_port is not None:
            from dlrover_tpu.utils.profiler import MetricsExporter

            self.metrics_exporter = MetricsExporter(port=metrics_port)
            self.metrics_exporter.add_source(self._step_timer.metrics)
            if self.auto_profiler is not None:
                self.metrics_exporter.add_text_source(
                    self.auto_profiler.prometheus_text)
            self.metrics_exporter.start()
        self._steps_since_report = 0
        self._host_step = 0

    # -- world / strategy -------------------------------------------------
    def prepare(self, devices: Optional[Sequence[Any]] = None) -> None:
        """Build mesh + jitted steps for the current world size."""
        # Persistent (disk) compilation cache: the in-process
        # _COMPILE_CACHE dies with the worker, but elastic restarts
        # respawn the process — the disk cache is what turns the
        # post-restart recompile into a cache hit.
        ensure_compile_cache()
        if devices is None:
            devices = jax.devices()
        if self._mesh_spec_fn is not None:
            spec = self._mesh_spec_fn(devices)
        else:
            spec = self._mesh_spec or MeshSpec.for_device_count(len(devices))
            if spec.size != len(devices):
                spec = MeshSpec.for_device_count(len(devices))
        self.plan = plan_global_batch(
            self._global_batch_size, spec, self._micro_batch_per_shard
        )
        base = self._accel_config or AccelerateConfig()
        config = dataclasses.replace(
            base,
            mesh_spec=spec,
            grad_accum_steps=self.plan.grad_accum_steps,
        )
        key = (
            id(self._model),
            spec,
            config.grad_accum_steps,
            self.plan.micro_batch_global,
            self._seq_len,
            tuple(d.id for d in devices),
        )
        cached = _COMPILE_CACHE.get(key)
        if cached is not None:
            self.result = cached
        else:
            self.result = accelerate(
                self._model,
                optimizer=self._optimizer,
                config=config,
                loss_fn=self._loss_fn,
                batch_shape=(self.plan.micro_batch_global, self._seq_len),
                devices=devices,
            )
            _COMPILE_CACHE[key] = self.result
        self._step = self._first_step
        logger.info(
            "ElasticTrainer prepared: mesh=%s accum=%s micro_global=%s",
            spec.dims, self.plan.grad_accum_steps, self.plan.micro_batch_global,
        )

    # -- state ------------------------------------------------------------
    def restore_or_init(self, rng: jax.Array) -> int:
        """Restore the train state from flash checkpoint (resharding to the
        current mesh), else initialize fresh.  Returns the restored step
        (0 for a fresh start)."""
        assert self.result is not None, "call prepare() first"
        target = self.result.abstract_state
        import flax.linen as nn

        target = nn.unbox(target)
        if self._ckpt is not None:
            decision = self._consensus_restore_decision()
            if decision == "fresh":
                # asymmetric world with no common checkpoint: every host
                # must take the SAME branch — init fresh everywhere
                self.state = self.result.init_fn(rng)
                self._host_step = 0
                return 0
            if isinstance(decision, int):
                step, state = self._ckpt.engine.load_from_storage(
                    target, self.result.state_sharding, step=decision)
            else:
                step, state = self._ckpt.load_checkpoint(
                    target=target, shardings=self.result.state_sharding
                )
            if state is not None:
                self.state = state
                self._host_step = int(step)
                logger.info("Restored train state at step %s", step)
                return int(step)
        self.state = self.result.init_fn(rng)
        self._host_step = 0
        return 0

    def _consensus_restore_decision(self):
        """Multi-host restore-step agreement.

        After an ASYMMETRIC restart (a replacement host with empty shm,
        or an orphan whose shm is stale) hosts' shm checkpoints can
        disagree — a per-host restore would put the world at different
        steps and the first collective diverges.  All hosts gather
        (shm_step, storage_step) ONCE and derive the same decision:
        ``None`` = symmetric, the normal memory-first restore is safe;
        an ``int`` = every host restores that committed storage step;
        ``"fresh"`` = no common checkpoint, every host initializes.
        The decision must be a pure function of the gathered values —
        re-reading storage later would race concurrent commits and
        diverge.  (Reference: rank-consistent resume of the
        flash-checkpoint torch engines.)
        """
        import jax

        if jax.process_count() <= 1:
            return None
        from dlrover_tpu.agent.ckpt_saver import read_latest_step

        eng = self._ckpt.engine
        # as ``load`` does: the agent's saver hosts the shm meta server,
        # and on a host that has not asked for one yet (a first start, a
        # replacement) ``get_meta`` would dial nothing for its whole 60 s
        eng._ensure_saver()
        try:
            meta = eng._shm_handler.get_meta()
            shm_step = meta.step if meta is not None and meta.valid else -1
        except Exception:
            shm_step = -1
        try:
            storage_step = read_latest_step(
                eng.storage, eng.checkpoint_dir)
        except Exception:
            storage_step = -1
        gathered = self._gather_restore_steps(shm_step, storage_step)
        if gathered is None:
            return None  # could not coordinate; plain local restore
        shm_steps = gathered[:, 0]
        if (shm_steps == shm_steps[0]).all():
            return None  # symmetric world: memory-first restore is safe
        # max, not min: the tracker is written AFTER the commit rename,
        # so a step ANY host observed is already fully committed and
        # readable by every host — a host whose own read raced the
        # commit just loads that step directly
        import numpy as np

        common_storage = int(np.max(gathered[:, 1]))
        logger.warning(
            "host checkpoints disagree (shm steps %s); forcing common "
            "restore: %s", shm_steps.tolist(),
            common_storage if common_storage >= 0 else "fresh init",
        )
        if common_storage < 0:
            return "fresh"
        return common_storage

    def _gather_restore_steps(self, shm_step: int, storage_step: int):
        """All-hosts gather of (shm_step, storage_step) -> [P, 2] array.

        Goes through the master KV store when reachable — a CONTROL
        plane exchange; the data-plane (Gloo/ICI) may still be forming
        its first connections at restore time and a collective here can
        hit connect timeouts on loaded hosts.  Falls back to a jax
        allgather without a master (plain multi-process runs), and to
        None (no coordination) if both fail.
        """
        import os as _os

        import numpy as np

        addr = _os.environ.get("DLROVER_MASTER_ADDR", "")
        n = int(_os.environ.get("DLROVER_WORKER_NUM", "0") or 0)
        rank = int(_os.environ.get("DLROVER_WORKER_RANK", "0") or 0)
        rnd = _os.environ.get("DLROVER_RDZV_ROUND", "0")
        if addr and n > 1:
            try:
                from dlrover_tpu.agent.master_client import MasterClient
                from dlrover_tpu.agent.master_kv_store import MasterKVStore

                client = MasterClient(addr, node_id=rank,
                                      node_type="worker")
                store = MasterKVStore(client,
                                      prefix=f"restore_steps/{rnd}")
                store.set(str(rank), f"{shm_step},{storage_step}")
                deadline = time.time() + 120
                keys = [str(r) for r in range(n)]
                while time.time() < deadline:
                    vals = store.multi_get(keys)
                    if all(v for v in vals):
                        client.close()
                        return np.array(
                            [[int(x) for x in v.decode().split(",")]
                             for v in vals], np.int64)
                    time.sleep(0.2)
                client.close()
                logger.warning("restore-step KV gather timed out")
            except Exception as e:
                logger.warning("restore-step KV gather failed: %s", e)
            # with a master configured the KV path is the ONLY gather:
            # falling into a jax collective here while peers returned
            # via KV would strand this host in a barrier nobody joins
            return None
        try:
            from jax.experimental import multihost_utils

            return multihost_utils.process_allgather(
                np.array([shm_step, storage_step], np.int64))
        except Exception as e:
            logger.warning("restore-step allgather failed: %s", e)
            return None

    @property
    def step(self) -> int:
        """Host-side step mirror: incremented per train_step so reading it
        never forces a device sync on the async-dispatched train state."""
        return self._host_step

    @property
    def seq_len(self) -> int:
        return self._seq_len

    # -- training ---------------------------------------------------------
    def _shape_batch(self, batch: Any) -> Any:
        """Accepts [global_batch, seq] (splits into microbatches) or an
        already micro-shaped [accum, micro_global, seq] array/dict."""
        accum = self.plan.grad_accum_steps

        def reshape(x):
            x = np.asarray(x) if not isinstance(x, jax.Array) else x
            if x.ndim >= 2 and x.shape[0] == self._global_batch_size:
                return x.reshape(
                    (accum, self.plan.micro_batch_global) + x.shape[1:]
                ) if accum > 1 else x
            return x

        if isinstance(batch, dict):
            return {k: reshape(v) for k, v in batch.items()}
        return {"input_ids": reshape(batch)}

    def compiled_step_text(self, batch: Any) -> str:
        """The train step's optimized HLO, as compiled for this mesh at
        this batch's shape (a compile-cache hit once the step has run):
        how a caller shows what the step really contains — a Pallas
        kernel is a ``tpu_custom_call``, FSDP is all-gather /
        reduce-scatter."""
        assert self.state is not None, "call restore_or_init() first"
        return _step_texts(
            self.result, abstract((self.state, self._shape_batch(batch))))[1]

    def _first_step(self, state: Any, shaped: Any):
        """The first dispatch after :meth:`prepare`, which compiles the
        step: the program is registered here, over the shapes it is
        compiled for (``utils/profiler.program_scopes`` reads its text
        on demand; the thunk holds shapes, never the state), and every
        later step goes straight to the jitted step."""
        res, shapes = self.result, abstract((state, shaped))
        register_program("train_step", lambda: _step_texts(res, shapes))
        self._step = res.train_step
        return self._step(state, shaped)

    def param_bytes_per_device(self) -> Dict[int, int]:
        """Parameter bytes each local device really holds (its
        addressable shards): whether a sharded run is spread."""
        assert self.state is not None, "call restore_or_init() first"
        held: Dict[int, int] = {}
        for leaf in jax.tree_util.tree_leaves(self.state.params):
            for shard in leaf.addressable_shards:
                held[shard.device.id] = (
                    held.get(shard.device.id, 0) + shard.data.nbytes)
        return dict(sorted(held.items()))

    def train_step(self, batch: Any) -> Dict[str, jax.Array]:
        assert self.state is not None, "call restore_or_init() first"
        t0 = time.time()
        # named by the step this call completes: the number maybe_save
        # and the checkpoint's spans carry for the same step
        with step_span("dlrover.trainer.step", self._host_step + 1):
            with span("dlrover.trainer.shape_batch"):
                shaped = self._shape_batch(batch)

            def dispatch():
                # the call of the jitted step until it RETURNS: the
                # enqueue, and whatever the runtime makes the caller
                # wait for (a donated buffer in use, a full queue)
                with span("dlrover.trainer.dispatch"):
                    return self._step(self.state, shaped)

            if self.auto_profiler is not None:
                self.state, metrics = self.auto_profiler.around_step(
                    dispatch)
            else:
                self.state, metrics = dispatch()
            self._host_step += 1
            self._report_runtime_metrics(time.time() - t0)
        return metrics

    def _report_runtime_metrics(self, elapsed: float) -> None:
        """Write the runtime-metrics file every step so the agent's
        TrainingMonitor can report speed to the master and the hang
        detector sees progress (reference: monitor/training.py:77 — the
        trainer-side half of the metrics-file contract).  Written by the
        host-local rank-0 process: each host's agent tails its own
        host-local file, so gating on the *global* process index would
        starve every other host's monitor."""
        self._step_timer.observe(elapsed)
        if self._metrics_every <= 0:
            return
        if int(os.getenv("DLROVER_LOCAL_RANK", "0")) != 0:
            return
        self._steps_since_report += 1
        if self._steps_since_report < self._metrics_every:
            return
        self._steps_since_report = 0
        from dlrover_tpu.agent.monitor.training import write_runtime_metrics

        write_runtime_metrics(
            self.step, elapsed_per_step=self._step_timer.ema_seconds
        )

    def _install_flush_on_term(self) -> None:
        """Drain the async checkpoint writer on SIGTERM before dying.

        The agent's worker-group stop is SIGTERM + grace: flushing the
        staged generation (milliseconds) keeps every host's committed
        shm step aligned at the collective-lockstep boundary, so a
        growth restart's restore-step consensus stays on the memory
        tier instead of falling back to an older storage step because
        ONE host died mid-commit.  Chained onto any existing handler;
        no-op off the main thread (signal.signal raises there)."""
        import signal as _signal

        prev = _signal.getsignal(_signal.SIGTERM)

        def _flush_then_prev(signum, frame):
            try:
                # lock-free drain: the handler may have interrupted the
                # main thread INSIDE a `with _save_cv:` block — flush()
                # here would self-deadlock on the non-reentrant lock
                self._ckpt.engine.drain_for_signal(timeout=5.0)
            except Exception:
                pass  # dying anyway; the commit either landed or not
            if callable(prev):
                prev(signum, frame)
            elif prev is _signal.SIG_IGN:
                return  # the process deliberately ignores SIGTERM
            else:
                _signal.signal(_signal.SIGTERM, _signal.SIG_DFL)
                os.kill(os.getpid(), _signal.SIGTERM)

        try:
            _signal.signal(_signal.SIGTERM, _flush_then_prev)
        except ValueError:
            pass  # not the main thread: rely on the pipeline barrier

    @property
    def checkpoint_engine(self):
        """The flash-checkpoint engine (``ckpt_metrics()``, ``flush()``),
        or None without a ``checkpoint_dir``.  Read-only: saves go
        through :meth:`maybe_save` / :meth:`save`."""
        return self._ckpt.engine if self._ckpt is not None else None

    def maybe_save(self, block: bool = False) -> bool:
        """Flash-checkpoint cadence: shm every ``save_memory_interval``
        steps, async disk persist every ``save_storage_interval``.
        Returns what the engine answered for a save that was due — False
        when it could not take it (the previous commit still in flight
        past its barrier, or a ``block=True`` commit that did not land) —
        and False when none was due.

        ``block=True`` waits for the shm COMMIT (not just the staging
        hand-off) — required when the caller acknowledges consumed work
        upstream right after saving (e.g. index-sharding acks): the ack
        must follow a durable save or a crash in between resumes one
        step behind the acked stream."""
        if self._ckpt is None:
            return False
        step = self.step
        tier = None
        if self._save_storage_interval \
                and step % self._save_storage_interval == 0:
            tier = StorageType.DISK
        elif self._save_memory_interval \
                and step % self._save_memory_interval == 0:
            tier = StorageType.MEMORY
        with span("dlrover.trainer.maybe_save", due=int(tier is not None),
                  tier=tier.name if tier is not None else "none"):
            if tier is None:
                return False
            return self._ckpt.save_checkpoint(step, self.state, tier,
                                              block=block)

    def save(self, storage_type: StorageType = StorageType.DISK) -> bool:
        if self._ckpt is None:
            return False
        return self._ckpt.save_checkpoint(self.step, self.state, storage_type)

    def close(self) -> None:
        if self._ckpt is not None:
            self._ckpt.close()
        if self.metrics_exporter is not None:
            self.metrics_exporter.stop()
            self.metrics_exporter = None
