"""Flash Checkpoint — trainer-side engine.

Counterpart of the reference's ``CheckpointEngine``
(reference: dlrover/trainer/torch/flash_checkpoint/engine.py:135-405):

- ``save_to_memory(step, state)``: stages the state for an ASYNC copy into
  POSIX shared memory — the in-loop pause is a generation-stamped pointer
  swap (snapshot references + hand-off to the writer thread), not a
  blocking memcpy.  The writer thread copies into the shm handler's
  inactive buffer and publishes the generation atomically (commit-marker
  protocol, see shm_handler.py), so a crash at any instant leaves the
  previous generation restorable, never a torn one;
- ``save_to_storage(step, state)``: memory save + an async persist event to
  the agent-side :class:`~dlrover_tpu.agent.ckpt_saver.AsyncCheckpointSaver`
  (factory-created on first use, reference: engine.py:253-275);
- ``load(...)``: restore preferring shm over storage (reference:
  engine.py:325-336), rebuilding sharded ``jax.Array``s from the per-shard
  index metadata — resharding to a *different* mesh works because shards
  carry global index slices (the analogue of the reference's DCP metadata
  design, fsdp_engine.py:70-157).

JAX specifics: state is any pytree of arrays (e.g. a flax ``TrainState``);
per-host we save only the addressable shards of each GSPMD array, so a
multi-host save never gathers.
"""

from __future__ import annotations

import os
import threading
import time
from enum import Enum
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from dlrover_tpu.agent.ckpt_saver import (
    CKPT_DIR_PREFIX,
    SAVE_EVENT,
    AsyncCheckpointSaver,
    CheckpointEvent,
    notify_agent_to_create_saver,
    read_latest_step,
)
from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.common.multi_process import SharedLock, SharedQueue
from dlrover_tpu.common.serialize import dumps, loads
from dlrover_tpu.common.storage import CheckpointStorage, PosixDiskStorage
from dlrover_tpu.trainer.flash_checkpoint.shm_handler import (
    SharedMemoryHandler,
    leaf_paths,
)
from dlrover_tpu.utils.profiler import name_os_thread, span


class SaverMode(str, Enum):
    AUTO = "auto"
    AGENT = "agent"  # saver lives in the elastic-agent process
    LOCAL = "local"  # standalone: saver thread in this process


def _covers_full(index: List[List[int]], global_shape: Tuple[int, ...]) -> bool:
    return all(
        a == 0 and b == n for (a, b), n in zip(index, global_shape)
    )


def _assemble_leaf(
    global_shape: Tuple[int, ...],
    dtype: str,
    pieces: List[Tuple[List[List[int]], np.ndarray]],
    copy: bool = True,
) -> np.ndarray:
    """Rebuild a full array from (index, data) shards.

    ``index`` is a per-dim [start, stop] list over the global shape (empty
    for scalars / unsharded fallbacks); overlapping pieces (replicas saved
    by different hosts) simply overwrite each other with identical data.

    ``copy=False``: when ONE piece already covers the whole array (the
    unsharded / single-host case — most leaves of a 1-host restore),
    return a zero-copy VIEW into the shm buffer instead of materializing
    a second host copy.  Only safe when the caller consumes the data
    before the next shm save reuses the segment (``_restore_into`` does:
    ``jax.device_put`` copies into the device buffer immediately).
    """
    from dlrover_tpu.common.multi_process import populate_write_ndarray

    if not global_shape:
        return np.array(pieces[0][1], dtype=np.dtype(dtype)).reshape(())
    for index, data in pieces:
        if not index or _covers_full(index, global_shape):
            view = data.reshape(global_shape)
            # the zero-copy path must not silently reinterpret a shard
            # whose stored dtype diverged from the recorded meta dtype
            if copy or view.dtype != np.dtype(dtype):
                # pre-populate the destination: first-write page faults
                # on a fresh allocation are the cold-restore wall
                # (multi_process.populate_write_ndarray)
                out = np.empty(global_shape, dtype=np.dtype(dtype))
                populate_write_ndarray(out)
                np.copyto(out, view, casting="unsafe")
                return out
            return view
    full = np.empty(global_shape, dtype=np.dtype(dtype))
    populate_write_ndarray(full)
    covered = 0
    for index, data in pieces:
        slices = tuple(slice(a, b) for a, b in index)
        full[slices] = data.reshape([b - a for a, b in index])
        covered += data.size
    if covered < int(np.prod(global_shape)):
        raise ValueError(
            f"incomplete checkpoint leaf: {covered} of "
            f"{int(np.prod(global_shape))} elements covered"
        )
    return full


def _assemble_region(
    global_shape: Tuple[int, ...],
    dtype: str,
    pieces: List[Tuple[List[List[int]], np.ndarray]],
    region: Tuple[slice, ...],
) -> Optional[np.ndarray]:
    """Rebuild ONE region (a device shard) of a leaf from whatever
    pieces the local shm holds; None when the pieces do not cover it.

    Coverage is tracked with a mask: dp replicas saved by the same host
    produce overlapping identical pieces, so byte counting would
    over-report.
    """
    shape = tuple(s.stop - s.start for s in region)
    if not shape:
        for index, data in pieces:
            return np.asarray(data, np.dtype(dtype)).reshape(())
        return None
    out = np.empty(shape, np.dtype(dtype))
    mask = np.zeros(shape, bool)
    for index, data in pieces:
        if not index:
            index = [[0, n] for n in global_shape]
        inter = []
        ok = True
        for (a, b), s in zip(index, region):
            lo, hi = max(a, s.start), min(b, s.stop)
            if lo >= hi:
                ok = False
                break
            inter.append((lo, hi))
        if not ok:
            continue
        src = data.reshape([b - a for a, b in index])
        src_sl = tuple(
            slice(lo - a, hi - a)
            for (a, b), (lo, hi) in zip(index, inter)
        )
        dst_sl = tuple(
            slice(lo - s.start, hi - s.start)
            for (lo, hi), s in zip(inter, region)
        )
        out[dst_sl] = src[src_sl]
        mask[dst_sl] = True
    if not mask.all():
        return None
    return out


def _normalize_region(index, global_shape) -> Tuple[slice, ...]:
    """jax device index -> concrete slices over the global shape."""
    return tuple(
        slice(s.start or 0, s.stop if s.stop is not None else n)
        for s, n in zip(index, global_shape)
    )


def _restore_into(target: Any, saved: Dict[str, np.ndarray], shardings: Any):
    """Rebuild ``target``'s pytree from saved full arrays (by leaf path),
    placing each leaf onto its sharding when provided."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(target)
    paths = [p for p, _ in leaf_paths(target)]
    shard_leaves: List[Any] = [None] * len(leaves)
    if shardings is not None:
        shard_leaves = jax.tree_util.tree_flatten(shardings)[0]
        if len(shard_leaves) != len(leaves):
            raise ValueError(
                "shardings tree does not match target state tree: "
                f"{len(shard_leaves)} vs {len(leaves)} leaves"
            )
    out = []
    for path, leaf, sharding in zip(paths, leaves, shard_leaves):
        if path not in saved:
            raise KeyError(f"checkpoint is missing leaf {path!r}")
        arr = saved[path]
        want_dtype = getattr(leaf, "dtype", arr.dtype)
        if arr.dtype != want_dtype:
            arr = arr.astype(want_dtype)
        if sharding is not None:
            out.append(jax.device_put(arr, sharding))
        else:
            out.append(jax.device_put(arr))
    return jax.tree_util.tree_unflatten(treedef, out)


class CheckpointEngine:
    """Per-training-process flash-checkpoint engine.

    One engine per worker process; ``local_rank`` selects the shm segment
    shared with the agent saver.  In ``LOCAL`` mode (no agent — plain
    ``python train.py``) the engine starts the async saver in-process, so
    the user API is identical either way.
    """

    #: bound on the pipeline barrier in save_to_memory: long enough for
    #: any normal in-flight copy (a 1 GiB commit is <1 s), short enough
    #: that a writer parked behind a long saver persist skips instead of
    #: stalling training
    STAGE_BARRIER_S = 5.0

    def __init__(
        self,
        checkpoint_dir: str,
        storage: Optional[CheckpointStorage] = None,
        local_rank: Optional[int] = None,
        local_world_size: Optional[int] = None,
        node_rank: Optional[int] = None,
        node_num: Optional[int] = None,
        saver_mode: SaverMode = SaverMode.AUTO,
        save_timeout: float = 600.0,
        async_save: Optional[bool] = None,
    ):
        self.checkpoint_dir = checkpoint_dir
        self.storage = storage or PosixDiskStorage()
        # which restore path actually ran (VERDICT r4 #5c): the bench
        # and the elastic e2e assert on these so a slow copy path can
        # never silently BE the recovery path while the artifact
        # publishes the zero-copy number
        self.restore_path_counts: Dict[str, int] = {
            "zero_copy": 0, "copy": 0, "partial": 0, "storage": 0,
        }
        env = os.environ
        self._local_rank = (
            int(env.get("DLROVER_LOCAL_RANK", "0"))
            if local_rank is None else local_rank
        )
        self._local_world_size = (
            int(env.get("DLROVER_LOCAL_WORLD_SIZE", "1"))
            if local_world_size is None else local_world_size
        )
        self._node_rank = (
            int(env.get(NodeEnv.NODE_RANK, "0"))
            if node_rank is None else node_rank
        )
        self._node_num = (
            int(env.get(NodeEnv.NODE_NUM, "1"))
            if node_num is None else node_num
        )
        if saver_mode == SaverMode.AUTO:
            # Launched by the elastic agent => the agent hosts the saver.
            saver_mode = (
                SaverMode.AGENT if env.get(NodeEnv.NODE_RANK) is not None
                else SaverMode.LOCAL
            )
        self._saver_mode = saver_mode
        self._save_timeout = save_timeout
        self._saver_started = False
        self._shm_handler = SharedMemoryHandler(self._local_rank)
        self._shm_lock = SharedLock(f"ckpt_{self._local_rank}")
        self._event_queue = SharedQueue("ckpt_event")
        self._latest_memory_step = -1
        self._latest_storage_request = -1
        # -- async double-buffered save (ISSUE 9) ------------------------
        # The in-loop "pause" is the staging hand-off only; the host copy
        # into the shm handler's inactive buffer runs on this writer
        # thread and publishes the generation atomically when done.
        # DLROVER_CKPT_SYNC_SAVE=1 is the kill switch back to the
        # synchronous copy-in-loop behavior.
        if async_save is None:
            async_save = env.get("DLROVER_CKPT_SYNC_SAVE", "") != "1"
        self._async_save = bool(async_save)
        self._save_cv = threading.Condition()
        self._pending: Optional[Tuple[int, Any, bool]] = None
        self._writer_busy = False
        self._writer_stop = False
        self._writer_thread: Optional[threading.Thread] = None
        # accounting (surfaced by ckpt_metrics(): the remaining in-loop
        # pause and the overlapped commit cost stay explicitly attributed
        # instead of silently vanishing from the books)
        self.saves_staged = 0
        self.saves_committed = 0
        self.saves_collapsed = 0
        self.saves_skipped = 0
        self.save_errors = 0
        self.inloop_pause_s_total = 0.0
        self.commit_s_total = 0.0
        self.lock_wait_s_total = 0.0
        self.last_commit_s = 0.0
        self._save_error_streak = 0
        self._stage_skip_streak = 0

    # -- saver bootstrap --------------------------------------------------
    def _ensure_saver(self) -> None:
        if self._saver_started:
            return
        if self._saver_mode == SaverMode.LOCAL:
            AsyncCheckpointSaver.start_async_saving_ckpt(
                checkpoint_dir=self.checkpoint_dir,
                storage=self.storage,
                local_shard_num=self._local_world_size,
                global_shard_num=self._node_num,
                node_rank=self._node_rank,
            )
        elif self._local_rank == 0:
            storage_config = self.storage.to_config()
            if storage_config is None:
                logger.warning(
                    "custom CheckpointStorage is not transferable to the "
                    "agent saver; it will persist with PosixDiskStorage"
                )
            notify_agent_to_create_saver(
                checkpoint_dir=self.checkpoint_dir,
                local_shard_num=self._local_world_size,
                global_shard_num=self._node_num,
                node_rank=self._node_rank,
                storage_config=storage_config,
            )
        self._saver_started = True

    # -- save -------------------------------------------------------------
    def save_to_memory(
        self, step: int, state: Any, block: bool = False,
        _notify_storage: bool = False,
    ) -> bool:
        """Stage ``state`` for an async copy into shared memory.

        The in-loop cost is snapshotting device arrays (an async
        device-side copy, so a caller that DONATES its state into the
        next jitted step cannot invalidate the bytes mid-copy) plus the
        writer hand-off — a pointer swap, not the memcpy.  The writer
        thread brings the snapshot to the host ONCE, a bounded number of
        bytes in flight at a time (``shm_handler.D2H_BUDGET_BYTES``: the
        whole state dispatched at once stands the device for seconds),
        each piece copied into the shm handler's inactive buffer beside
        the next piece's transfer; it publishes the generation
        atomically and lets go of the snapshot as the commit ends.  A
        crash before the publish restores the previous generation
        (never torn).

        The pipeline is depth 1: staging save N first waits out any
        still-copying save N-1 (steady state: already done — a full
        training step elapsed), so a crash right after this call can
        lose at most THIS save, never two.  That residual wait is the
        whole remaining in-loop pause and is attributed explicitly in
        ``ckpt_metrics()``.  ``block=True`` additionally waits for save
        N's own commit (the durability barrier for callers that need
        save N — not N-1 — to survive an immediate crash, at the old
        synchronous-pause cost).

        Returns False only when the save could not be STAGED (previous
        commit still in flight past ``STAGE_BARRIER_S`` — the writer is
        parked behind a saver persist; sync mode: saver holds the shm
        lock) or, with ``block=True``, when the commit did not land
        within the save timeout.
        """
        self._ensure_saver()
        t0 = time.perf_counter()
        if not self._async_save:
            ok = self._save_to_memory_sync(step, state, _notify_storage)
            self.inloop_pause_s_total += time.perf_counter() - t0
            return ok
        with span("dlrover.ckpt.stage", step=step):
            staged_ok = self._stage(step, state, _notify_storage)
        self.inloop_pause_s_total += time.perf_counter() - t0
        if not staged_ok:
            return False
        if block:
            return self.flush(timeout=self._save_timeout) \
                and self._latest_memory_step >= step
        return True

    def _stage(self, step: int, state: Any, notify_storage: bool) -> bool:
        """The in-loop part of an async save: device snapshot, pipeline
        barrier, hand-off to the writer.  False = skipped at the
        barrier."""
        with span("dlrover.ckpt.snapshot"):
            staged = self._snapshot_state(state)
        # pipeline barrier: the previous save must commit before a new
        # one stages (at-most-one-behind crash-loss contract).  The wait
        # is BOUNDED SHORT: a normal in-flight copy finishes in well
        # under STAGE_BARRIER_S, so exceeding it means the writer is
        # parked on the shm lock behind a long saver persist — then we
        # SKIP this save (the old "training never blocks on storage"
        # contract) instead of stalling the training loop for up to the
        # 600s save timeout.
        with span("dlrover.ckpt.barrier"):
            idle = self.flush(timeout=self.STAGE_BARRIER_S)
        if not idle:
            self.saves_skipped += 1
            self._stage_skip_streak += 1
            if self._stage_skip_streak == 1:
                logger.warning(
                    "step %s memory save skipped: previous commit still "
                    "in flight after %.1fs (saver persisting?); further "
                    "skips log at debug until a save lands",
                    step, self.STAGE_BARRIER_S,
                )
            else:
                logger.debug("step %s memory save skipped (streak %s)",
                             step, self._stage_skip_streak)
            return False
        if self._stage_skip_streak:
            logger.info(
                "memory saves resumed at step %s after %s skipped",
                step, self._stage_skip_streak,
            )
            self._stage_skip_streak = 0
        with span("dlrover.ckpt.handoff"), self._save_cv:
            self._ensure_writer()
            if self._pending is not None:  # raced another saver thread
                _, _, prev_notify = self._pending
                notify_storage = notify_storage or prev_notify
                self.saves_collapsed += 1
            self._pending = (step, staged, notify_storage)
            self.saves_staged += 1
            self._save_cv.notify_all()
        return True

    def _save_to_memory_sync(
        self, step: int, state: Any, notify_storage: bool
    ) -> bool:
        """The pre-double-buffer path (DLROVER_CKPT_SYNC_SAVE=1): copy in
        the training loop, skipping when the agent saver holds the shm
        lock mid-persist (reference: engine.py:291-323)."""
        owner = f"writer{self._local_rank}"
        if not self._shm_lock.acquire(blocking=False, owner=owner):
            logger.warning(
                "step %s memory save skipped: saver busy persisting", step
            )
            return False
        try:
            self._shm_handler.save_state_dict(state, step)
            self._latest_memory_step = step
            self.saves_staged += 1
            self.saves_committed += 1
        finally:
            self._shm_lock.release(owner=owner)
        if notify_storage:
            self._notify_storage_event(step)
        return True

    def _snapshot_state(self, state: Any) -> Any:
        """Decouple the staged state from the caller's buffers.

        ``jax.Array`` leaves get an async DEVICE-side copy (dispatch
        returns immediately; HBM->HBM bandwidth, not D2H): the training
        loop may then donate the original into the next step while the
        writer thread reads the snapshot.  Host (numpy) leaves pass by
        reference — the caller contract is not to mutate them in place
        between save and commit (rebinding to new arrays, the jax
        idiom, is always safe); use ``block=True`` otherwise.
        """
        import jax

        def snap(leaf):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                # async device copy, same sharding.  It is a SECOND copy
                # of the leaf in HBM until the writer has staged it: if
                # that does not fit, the allocation error propagates —
                # a save never silently takes another path
                return leaf.copy()
            return leaf  # host leaf, or donated already: writer will log

        return jax.tree_util.tree_map(snap, state)

    def _ensure_writer(self) -> None:
        """Caller holds ``_save_cv``."""
        if self._writer_thread is not None and self._writer_thread.is_alive():
            return
        self._writer_stop = False
        self._writer_thread = threading.Thread(
            target=self._writer_loop, daemon=True,
            name=f"ckpt-writer-{self._local_rank}",
        )
        self._writer_thread.start()

    def _writer_loop(self) -> None:
        name_os_thread(threading.current_thread().name)
        while True:
            with self._save_cv:
                while self._pending is None and not self._writer_stop:
                    self._save_cv.wait(timeout=1.0)
                if self._writer_stop and self._pending is None:
                    return
                with span("dlrover.ckpt.pickup"):
                    step, state, notify = self._pending
                self._pending = None
                self._writer_busy = True
            try:
                t0 = time.perf_counter()
                with span("dlrover.ckpt.commit", step=step):
                    self._commit_staged_save(step, state, notify)
                self.last_commit_s = time.perf_counter() - t0
                self.commit_s_total += self.last_commit_s
            except Exception as e:
                self.save_errors += 1
                self._save_error_streak += 1
                if self._save_error_streak == 1:
                    # once per state change, not per failed save: a
                    # donated-buffer misuse at every step must not log
                    # at every step
                    logger.warning(
                        "async memory save of step %s failed (%s); the "
                        "previous committed generation stays restorable",
                        step, e,
                    )
                else:
                    logger.debug(
                        "async memory save of step %s still failing: %s",
                        step, e,
                    )
            finally:
                # let go of the device snapshot (and of the host bytes
                # cached on its shards) now, not at the next pick-up: it
                # is a second copy of the state in HBM.  A reference
                # dropped, never ``.delete()``: a sync save hands the
                # CALLER's arrays down the same path
                state = None
                with self._save_cv:
                    self._writer_busy = False
                    self._save_cv.notify_all()

    def _commit_staged_save(self, step: int, state: Any, notify: bool) -> None:
        owner = f"writer{self._local_rank}"
        # blocking here is fine — this is the writer thread, not the
        # training loop; the agent saver releases the lock when its
        # persist pass finishes
        t0 = time.perf_counter()
        with span("dlrover.ckpt.lock_wait"):
            locked = self._shm_lock.acquire(owner=owner,
                                            timeout=self._save_timeout)
        self.lock_wait_s_total += time.perf_counter() - t0
        if not locked:
            raise TimeoutError(
                f"shm lock busy for {self._save_timeout}s (saver persist "
                "wedged?); save skipped"
            )
        try:
            self._shm_handler.save_state_dict(state, step)
        finally:
            # a round trip to the lock's server: milliseconds on a loaded
            # machine, and a part of the commit like the wait for it
            with span("dlrover.ckpt.unlock"):
                self._shm_lock.release(owner=owner)
        self._latest_memory_step = step
        self.saves_committed += 1
        if self._save_error_streak:
            logger.info(
                "async memory save recovered at step %s after %s failures",
                step, self._save_error_streak,
            )
            self._save_error_streak = 0
        if notify:
            self._notify_storage_event(step)

    def _notify_storage_event(self, step: int) -> None:
        """Ask the saver to persist shm -> storage.  Sent AFTER the memory
        commit published, so the saver can never persist a generation
        newer than the one the event names was committed for."""
        if self._local_rank != 0:
            return
        self._event_queue.put(
            dumps(CheckpointEvent(SAVE_EVENT, step).to_dict())
        )

    def flush(self, timeout: float = 60.0) -> bool:
        """Wait until every staged save has committed (or failed); True
        when the writer went idle inside the budget."""
        deadline = time.monotonic() + timeout
        with self._save_cv:
            while self._pending is not None or self._writer_busy:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._save_cv.wait(timeout=min(remaining, 1.0))
        return True

    def drain_for_signal(self, timeout: float = 5.0) -> bool:
        """Best-effort writer drain that NEVER takes ``_save_cv`` — safe
        from a signal handler, which may interrupt the main thread while
        it already holds that (non-reentrant) lock; ``flush()`` there
        would self-deadlock.  Plain-attribute polling is enough: both
        fields are only ever written under the cv, and a signal-time
        drain is advisory anyway (the commit either lands or the
        previous generation stands)."""
        deadline = time.monotonic() + timeout
        while self._pending is not None or self._writer_busy:
            if time.monotonic() > deadline:
                return False
            time.sleep(0.01)
        return True

    def ckpt_metrics(self) -> Dict[str, float]:
        """Explicit attribution of the double-buffered save cost (metric
        names registered in utils/metric_registry.py)."""
        return {
            "dlrover_ckpt_saves_staged_total": float(self.saves_staged),
            "dlrover_ckpt_saves_committed_total": float(self.saves_committed),
            "dlrover_ckpt_saves_collapsed_total": float(self.saves_collapsed),
            "dlrover_ckpt_saves_skipped_total": float(self.saves_skipped),
            "dlrover_ckpt_save_errors_total": float(self.save_errors),
            "dlrover_ckpt_inloop_pause_seconds_total": float(
                self.inloop_pause_s_total),
            "dlrover_ckpt_commit_seconds_total": float(self.commit_s_total),
            "dlrover_ckpt_lock_wait_seconds_total": float(
                self.lock_wait_s_total),
            # the writer's two copies, timed where they happen
            # (shm_handler._stream), summed over a generation's pieces
            "dlrover_ckpt_d2h_seconds_total": float(
                self._shm_handler.d2h_s_total),
            "dlrover_ckpt_shm_copy_seconds_total": float(
                self._shm_handler.shm_copy_s_total),
            "dlrover_ckpt_d2h_bytes_total": float(
                self._shm_handler.d2h_bytes_total),
            "dlrover_ckpt_bytes_committed_total": float(
                self._shm_handler.bytes_written_total),
            "dlrover_ckpt_committed_step": float(self._latest_memory_step),
        }

    def save_to_storage(self, step: int, state: Any,
                        block: bool = False) -> bool:
        """Memory save + async persist request to the saver (reference:
        engine.py:354-394).  Local rank 0 enqueues one event per host —
        the saver persists every local shard from it (duplicate per-rank
        events would only thrash the stage dir).  The event rides the
        writer thread: it is enqueued only after the memory generation
        COMMITS, so the saver never persists ahead of the publish.
        ``block=True`` waits for the shm COMMIT (disk persistence stays
        async either way) and returns False if it did not land."""
        ok = self.save_to_memory(step, state, block=block,
                                 _notify_storage=True)
        if ok:
            self._latest_storage_request = step
        return ok

    # -- load -------------------------------------------------------------
    def load(
        self,
        target: Any = None,
        shardings: Any = None,
        host_views: bool = False,
    ) -> Tuple[int, Optional[Any]]:
        """Restore the latest checkpoint, preferring shared memory.

        Returns ``(step, state)``; ``(-1, None)`` when nothing exists.
        ``host_views=True`` returns zero-copy VIEWS into the shm segment
        even without a target — the true recovery-path cost on a TPU
        host, where the next step is a device DMA straight from these
        views.  Caller contract: consume (device_put) before the next
        shm save reuses the segment, and never on the CPU backend's
        aliasing device_put.
        ``target`` is an (abstract or concrete) pytree giving the structure
        and dtypes to restore into; ``shardings`` an optional matching
        pytree of ``jax.sharding.Sharding``s.
        """
        self._ensure_saver()  # shm meta server must exist before we query it
        # drain staged-but-uncommitted saves: a restore right after a
        # save must see that save, not race the writer thread
        if self._async_save and self._writer_thread is not None:
            self.flush(timeout=min(self._save_timeout, 60.0))
        # Freshness across tiers: a host can hold a STALE shm checkpoint
        # (e.g. a node that sat out rounds while its peers trained on and
        # committed newer storage saves — the multi-slice orphan).  Memory
        # wins only when at least as new as the committed storage step.
        try:
            meta = self._shm_handler.get_meta()
            mem_step = meta.step if meta is not None and meta.valid else -1
        except Exception:
            mem_step = -1
        if mem_step >= 0:
            try:
                storage_step = read_latest_step(
                    self.storage, self.checkpoint_dir)
            except Exception as e:
                # a storage blip must not break a pure-memory recovery
                logger.warning(
                    "storage freshness check failed (%s); trusting shm",
                    e)
                storage_step = -1
            if storage_step > mem_step:
                logger.info(
                    "shm checkpoint (step %s) is older than committed "
                    "storage (step %s); restoring from storage",
                    mem_step, storage_step,
                )
                return self.load_from_storage(target, shardings)
        try:
            # With a target the leaves are device_put immediately, so
            # zero-copy shm views skip the 2nd host copy — safe on
            # TPU/GPU where device_put is a real transfer.  The CPU
            # backend ALIASES host numpy memory in device_put, which
            # would hand the caller arrays living inside the reusable
            # shm segment — copy there.
            import jax

            zero_copy_ok = host_views or (
                target is not None and jax.default_backend() != "cpu"
            )
            loaded = self._load_from_memory(copy=not zero_copy_ok)
        except ValueError as e:
            # This host's shm holds only its own addressable shards.
            # When params span hosts (fsdp across processes) the SHARDED
            # restore path places each host's own pieces directly onto
            # its devices (make_array_from_single_device_arrays) — full
            # local coverage is not needed as long as every host restores
            # its own part (the multi-host / multi-slice recovery path).
            loaded = None
            if target is not None and shardings is not None:
                try:
                    loaded = self._load_partial_from_memory(
                        target, shardings)
                except Exception as e2:
                    logger.warning(
                        "per-shard memory restore failed too: %s", e2)
            if loaded is not None:
                step, restored = loaded
                logger.info(
                    "Restored step %s from shared memory (per-host "
                    "shards)", step)
                return step, restored
            # last resort: the committed storage checkpoint (the
            # reference's node-loss semantics — memory restore is
            # per-node, storage is the cross-node recovery tier)
            logger.warning(
                "memory checkpoint incomplete (%s); falling back to "
                "storage restore", e,
            )
        if loaded is not None:
            step, saved = loaded
            if target is None:
                return step, saved
            restored = _restore_into(target, saved, shardings)
            if zero_copy_ok and not host_views:
                # the device_put above read straight out of the shm
                # segment and returns before the transfer has finished;
                # a later save may rewrite or resize that segment, so
                # the transfer must have landed before we hand back
                jax.block_until_ready(restored)
            return step, restored
        return self.load_from_storage(target, shardings)

    def _load_partial_from_memory(
        self, target: Any, shardings: Any
    ) -> Optional[Tuple[int, Any]]:
        """Sharded restore from partial local shm: place each of THIS
        host's device shards from the pieces its shm holds; the global
        arrays form via ``make_array_from_single_device_arrays`` (every
        host contributes its own part).  Raises/returns None when a
        locally-addressable shard is not covered — then storage is the
        only recovery tier."""
        import jax

        result = self._shm_handler.load_arrays()
        if result is None:
            return None
        step, leaves_meta, arrays = result
        leaves, treedef = jax.tree_util.tree_flatten(target)
        paths = [p for p, _ in leaf_paths(target)]
        shard_leaves = jax.tree_util.tree_flatten(shardings)[0]
        if len(shard_leaves) != len(leaves):
            raise ValueError("shardings tree does not match target")
        out = []
        for path, leaf, sharding in zip(paths, leaves, shard_leaves):
            meta = leaves_meta.get(path)
            if meta is None:
                raise ValueError(f"shm checkpoint is missing {path!r}")
            pieces = [
                (meta["shards"][i]["index"], arrays[(path, i)])
                for i in range(len(meta["shards"]))
            ]
            gshape = tuple(meta["global_shape"])
            want_dtype = getattr(leaf, "dtype", np.dtype(meta["dtype"]))
            if sharding is None:
                full = _assemble_leaf(gshape, meta["dtype"], pieces)
                out.append(jax.device_put(full.astype(want_dtype)))
                continue
            index_map = sharding.addressable_devices_indices_map(gshape)
            device_arrays = []
            for device, index in index_map.items():
                region = _normalize_region(index, gshape)
                block = _assemble_region(
                    gshape, meta["dtype"], pieces, region)
                if block is None:
                    raise ValueError(
                        f"local shm does not cover shard {region} of "
                        f"{path!r}")
                device_arrays.append(jax.device_put(
                    block.astype(want_dtype), device))
            out.append(jax.make_array_from_single_device_arrays(
                gshape, sharding, device_arrays))
        # counted on SUCCESS only: a failed partial attempt that falls
        # through to storage must not record the fast tier as taken
        self.restore_path_counts["partial"] += 1
        return step, jax.tree_util.tree_unflatten(treedef, out)

    def _load_from_memory(
        self, copy: bool = True
    ) -> Optional[Tuple[int, Dict[str, np.ndarray]]]:
        try:
            result = self._shm_handler.load_arrays()
        except Exception:
            return None
        if result is None:
            return None
        step, leaves, arrays = result
        saved: Dict[str, np.ndarray] = {}
        for path, meta in leaves.items():
            pieces = [
                (meta["shards"][i]["index"], arrays[(path, i)])
                for i in range(len(meta["shards"]))
            ]
            saved[path] = _assemble_leaf(
                tuple(meta["global_shape"]), meta["dtype"], pieces,
                copy=copy,
            )
        self.restore_path_counts["copy" if copy else "zero_copy"] += 1
        logger.info("Restoring step %s from shared memory (%s)",
                    step, "copy" if copy else "zero-copy")
        return step, saved

    def load_from_storage(
        self,
        target: Any = None,
        shardings: Any = None,
        step: Optional[int] = None,
    ) -> Tuple[int, Optional[Any]]:
        if step is None:
            step = read_latest_step(self.storage, self.checkpoint_dir)
        if step < 0:
            return -1, None
        ckpt_dir = os.path.join(
            self.checkpoint_dir, f"{CKPT_DIR_PREFIX}{step}"
        )
        saved = self._read_shards(ckpt_dir)
        if saved is None:
            return -1, None
        self.restore_path_counts["storage"] += 1
        logger.info("Restoring step %s from %s", step, ckpt_dir)
        if target is None:
            return step, saved
        return step, _restore_into(target, saved, shardings)

    def _read_shards(self, ckpt_dir: str) -> Optional[Dict[str, np.ndarray]]:
        """Merge all shard files of one committed checkpoint dir into full
        per-leaf arrays (reshard-agnostic: indices are global)."""
        metas = [
            f for f in self.storage.listdir(ckpt_dir) if f.endswith(".meta")
        ]
        if not metas:
            return None
        pieces: Dict[str, List[Tuple[List[List[int]], np.ndarray]]] = {}
        leaf_info: Dict[str, Tuple[Tuple[int, ...], str]] = {}
        for meta_name in sorted(metas):
            meta = loads(self.storage.read(
                os.path.join(ckpt_dir, meta_name), "rb"
            ))
            bin_name = meta_name[: -len(".meta")] + ".bin"
            blob = self.storage.read(os.path.join(ckpt_dir, bin_name), "rb")
            if blob is None:
                logger.warning("missing shard data file %s", bin_name)
                return None
            for path, leaf_meta in meta["leaves"].items():
                leaf_info[path] = (
                    tuple(leaf_meta["global_shape"]), leaf_meta["dtype"]
                )
                file_offsets = {
                    o["shard"]: o for o in meta["offsets"].get(path, [])
                }
                for i, shard in enumerate(leaf_meta["shards"]):
                    off = file_offsets.get(i)
                    if off is None:
                        continue
                    raw = blob[off["offset"]: off["offset"] + off["nbytes"]]
                    arr = np.frombuffer(
                        raw, dtype=np.dtype(leaf_meta["dtype"])
                    ).reshape(shard["shape"])
                    pieces.setdefault(path, []).append((shard["index"], arr))
        saved = {}
        for path, (gshape, dtype) in leaf_info.items():
            saved[path] = _assemble_leaf(gshape, dtype, pieces[path])
        return saved

    # -- misc -------------------------------------------------------------
    def latest_storage_step(self) -> int:
        return read_latest_step(self.storage, self.checkpoint_dir)

    def wait_latest_checkpoint(self, timeout: float = 600.0) -> int:
        """Block until the latest *storage-requested* save is committed
        (memory-only saves don't gate this; reference: checkpointer
        ``wait_latest_checkpoint``)."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            step = self.latest_storage_step()
            if step >= self._latest_storage_request:
                return step
            time.sleep(0.2)
        return self.latest_storage_step()

    def close(self) -> None:
        # drain the writer before tearing down shm: an in-flight commit
        # must not race the segment close (DL002: the thread is tracked
        # and joined, not abandoned)
        if self._writer_thread is not None:
            self.flush(timeout=10.0)
            with self._save_cv:
                self._writer_stop = True
                self._save_cv.notify_all()
            self._writer_thread.join(timeout=5.0)
            self._writer_thread = None
        self._shm_handler.close()
        self._shm_lock.close()
        self._event_queue.close()
