"""High-level Trainer: epochs, eval, logging, callbacks, resume.

Parity target: reference atorch/atorch/trainer/atorch_trainer.py:136
(``AtorchTrainer`` — the HF-Trainer-shaped loop: TrainingArguments,
logging/eval/save strategies, callback hooks, resume-from-checkpoint)
layered on the framework's elastic machinery the way AtorchTrainer
layers on atorch's.

TPU-native: the inner step is the jitted sharded train_step built by
``accelerate()`` (via :class:`ElasticTrainer`, which owns the flash
checkpoint + runtime-metrics contracts); this class only sequences
epochs, eval passes, logging, and callbacks — all host-side, outside
jit, so nothing here affects compiled-step performance.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterable, List, Optional

import jax
import numpy as np

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.trainer.elastic.trainer import ElasticTrainer


class IntervalStrategy:
    NO = "no"
    STEPS = "steps"
    EPOCH = "epoch"


@dataclasses.dataclass
class TrainingArguments:
    """The reference AtorchTrainingArgs surface that is meaningful on
    TPU (device-placement/fp16 flags are superseded by accelerate()).

    Optimizer knobs (learning_rate/warmup/scheduler/weight_decay) build
    an optax chain when the caller does not hand ``Trainer`` an explicit
    ``optimizer=`` (reference atorch_trainer.py create_optimizer /
    create_scheduler)."""

    max_steps: int = -1              # -1: derive from epochs * loader len
    num_train_epochs: int = 1
    logging_steps: int = 10
    eval_strategy: str = IntervalStrategy.NO
    eval_steps: int = 100
    save_strategy: str = IntervalStrategy.STEPS
    seed: int = 0
    # optimizer / schedule
    learning_rate: float = 1e-3
    weight_decay: float = 0.0
    adam_beta1: float = 0.9
    adam_beta2: float = 0.999
    adam_epsilon: float = 1e-8
    lr_scheduler_type: str = "cosine"   # cosine | linear | constant
    warmup_steps: int = 0
    warmup_ratio: float = 0.0            # used when warmup_steps == 0
    min_lr_ratio: float = 0.0            # decay floor as lr fraction

    def make_schedule(self, total_steps: int):
        """Warmup + decay schedule (HF/atorch get_scheduler shape)."""
        import optax

        total = max(1, total_steps)
        warmup = self.warmup_steps or int(self.warmup_ratio * total)
        peak, floor = self.learning_rate, self.learning_rate * self.min_lr_ratio
        if self.lr_scheduler_type == "constant":
            decay = optax.constant_schedule(peak)
        elif self.lr_scheduler_type == "linear":
            decay = optax.linear_schedule(
                peak, floor, max(1, total - warmup)
            )
        elif self.lr_scheduler_type == "cosine":
            decay = optax.cosine_decay_schedule(
                peak, max(1, total - warmup), alpha=self.min_lr_ratio
            )
        else:
            raise ValueError(
                f"unknown lr_scheduler_type {self.lr_scheduler_type!r}"
            )
        if warmup <= 0:
            return decay
        return optax.join_schedules(
            [optax.linear_schedule(0.0, peak, warmup), decay], [warmup]
        )

    def make_optimizer(self, total_steps: int):
        import optax

        schedule = self.make_schedule(total_steps)
        return optax.adamw(
            schedule,
            b1=self.adam_beta1,
            b2=self.adam_beta2,
            eps=self.adam_epsilon,
            weight_decay=self.weight_decay,
        ), schedule


class TrainerCallback:
    """Hook points (reference HF/atorch TrainerCallback surface)."""

    def on_train_begin(self, trainer: "Trainer") -> None: ...
    def on_step_end(self, trainer: "Trainer",
                    metrics: Dict[str, float]) -> None: ...
    def on_log(self, trainer: "Trainer", logs: Dict[str, float]) -> None: ...
    def on_evaluate(self, trainer: "Trainer",
                    metrics: Dict[str, float]) -> None: ...
    def on_save(self, trainer: "Trainer") -> None: ...
    def on_train_end(self, trainer: "Trainer") -> None: ...


@dataclasses.dataclass
class TrainOutput:
    global_step: int
    training_loss: float
    metrics: Dict[str, float]


class Trainer:
    """``Trainer(model, args, train_dataloader, ...).train()``.

    ``train_dataloader`` yields batches shaped for the elastic trainer
    ([global_batch, seq] arrays or dicts); ``eval_dataloader`` likewise.
    """

    def __init__(
        self,
        model: Any,
        args: TrainingArguments,
        train_dataloader: Iterable[Any],
        eval_dataloader: Optional[Iterable[Any]] = None,
        callbacks: Optional[List[TrainerCallback]] = None,
        **elastic_kwargs: Any,
    ):
        self.args = args
        self.train_dataloader = train_dataloader
        self.eval_dataloader = eval_dataloader
        self.callbacks = callbacks or []
        self._schedule = None
        if elastic_kwargs.get("optimizer") is None:
            total = args.max_steps
            if total <= 0:
                try:
                    total = args.num_train_epochs * len(train_dataloader)
                except TypeError:
                    # Horizon unknown (streaming loader, no max_steps): a
                    # decaying schedule would silently hit its floor at an
                    # arbitrary step, so force constant LR instead.
                    if args.lr_scheduler_type != "constant":
                        logger.warning(
                            "max_steps not set and dataloader has no len(); "
                            "using constant LR %s instead of %s schedule",
                            args.learning_rate, args.lr_scheduler_type,
                        )
                        args = dataclasses.replace(
                            args, lr_scheduler_type="constant"
                        )
                        self.args = args
                    total = 1
            elastic_kwargs["optimizer"], self._schedule = (
                args.make_optimizer(total)
            )
        self.elastic = ElasticTrainer(model, **elastic_kwargs)
        self.log_history: List[Dict[str, float]] = []
        self._loss_sum = 0.0
        self._loss_count = 0

    # -- hooks -----------------------------------------------------------
    def _fire(self, hook: str, *hook_args) -> None:
        for cb in self.callbacks:
            try:
                getattr(cb, hook)(self, *hook_args)
            except Exception:
                logger.exception("callback %s.%s failed",
                                 type(cb).__name__, hook)

    # -- properties ------------------------------------------------------
    @property
    def global_step(self) -> int:
        return self.elastic.step

    # -- training --------------------------------------------------------
    def train(self) -> TrainOutput:
        self.elastic.prepare()
        start_step = self.elastic.restore_or_init(
            jax.random.PRNGKey(self.args.seed)
        )
        if start_step:
            logger.info("Resuming training at step %s", start_step)
        self._fire("on_train_begin")
        max_steps = self.args.max_steps
        t_last_log = time.time()
        steps_since_log = 0
        done = False
        for epoch in range(self.args.num_train_epochs):
            if done:
                break
            for batch in self.train_dataloader:
                metrics = self.elastic.train_step(batch)
                loss = float(jax.device_get(metrics.get("loss", 0.0)))
                self._loss_sum += loss
                self._loss_count += 1
                self._fire("on_step_end", {"loss": loss})
                step = self.global_step
                steps_since_log += 1
                if (self.args.logging_steps > 0
                        and step % self.args.logging_steps == 0):
                    now = time.time()
                    sps = steps_since_log / max(1e-9, now - t_last_log)
                    logs = {
                        "step": step,
                        "epoch": epoch,
                        "loss": loss,
                        # actual steps in this window (a resume can land
                        # mid-window, so logging_steps would over-count)
                        "steps_per_sec": sps,
                    }
                    # grad_norm, and a MoE model's routing statistics
                    for name in metrics:
                        if name == "grad_norm" or name.startswith("moe_"):
                            logs[name] = float(
                                jax.device_get(metrics[name]))
                    if self._schedule is not None:
                        logs["learning_rate"] = float(self._schedule(step))
                    plan = self.elastic.plan
                    if plan is not None:
                        logs["tokens_per_sec"] = round(
                            sps * plan.global_batch_size
                            * self.elastic.seq_len
                        )
                    t_last_log = now
                    steps_since_log = 0
                    self.log_history.append(logs)
                    logger.info("train: %s", logs)
                    self._fire("on_log", logs)
                if (self.args.eval_strategy == IntervalStrategy.STEPS
                        and self.args.eval_steps > 0
                        and step % self.args.eval_steps == 0):
                    self.evaluate()
                if self.args.save_strategy == IntervalStrategy.STEPS:
                    if self.elastic.maybe_save():
                        self._fire("on_save")
                if 0 < max_steps <= step:
                    done = True
                    break
            if self.args.eval_strategy == IntervalStrategy.EPOCH:
                self.evaluate()
            if self.args.save_strategy == IntervalStrategy.EPOCH:
                self.elastic.save()
                self._fire("on_save")
        self._fire("on_train_end")
        avg = self._loss_sum / max(1, self._loss_count)
        out = TrainOutput(
            global_step=self.global_step,
            training_loss=avg,
            metrics={"train_loss": avg},
        )
        logger.info("Training finished: %s", out)
        return out

    # -- evaluation ------------------------------------------------------
    def evaluate(self) -> Dict[str, float]:
        if self.eval_dataloader is None:
            return {}
        assert self.elastic.result is not None, "train() prepares first"
        losses, weights = [], []
        for batch in self.eval_dataloader:
            # eval_step consumes a single microbatch [micro_global, seq]
            # — no grad-accum reshape (accelerate()'s eval_sharding is
            # the micro spec), so only the dict wrap is applied
            if not isinstance(batch, dict):
                batch = {"input_ids": batch}
            out = self.elastic.result.eval_step(self.elastic.state, batch)
            losses.append(float(jax.device_get(out["loss"])))
            weights.append(float(jax.device_get(out.get("weight", 1.0))))
        if not losses:
            return {}
        total_w = sum(weights)
        eval_loss = float(np.average(losses, weights=weights)) \
            if total_w > 0 else float(np.mean(losses))
        metrics = {"eval_loss": eval_loss, "eval_batches": len(losses)}
        self.log_history.append({"step": self.global_step, **metrics})
        logger.info("eval: %s", metrics)
        self._fire("on_evaluate", metrics)
        return metrics
