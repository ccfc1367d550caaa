"""Paged KV cache: block-pool memory management for the serving engine.

What vLLM gives the reference's RL rollouts (reference:
atorch/atorch/rl/inference_backend/vllm_backend.py:11-24 — paged
attention, prefix reuse), rebuilt TPU-style:

- **block pool**: per layer, K/V live in ``[num_blocks, block_size,
  KV, D]`` pools; a sequence owns a LIST of blocks instead of a dense
  ``max_len`` stripe, so cache memory scales with actual sequence
  lengths and concurrency is bounded by the pool (HBM budget), not by
  ``slots x max_len`` worst-case reservations.
- **prefix caching**: the leading FULL prompt blocks are content-hashed
  (chained, so a hit guarantees the whole prefix matches); admissions
  reuse hit blocks refcounted, and fully-released prefix blocks linger
  in an LRU until the allocator actually needs them — repeated system
  prompts cost their KV once.
- **static shapes**: the device side sees a fixed ``[slots,
  max_blocks]`` int32 table and fixed pools; only the HOST manager is
  dynamic.  Reads gather ``pool[table]`` back to the dense ``[B, L,
  KV, D]`` view the attention kernels already handle — correctness
  first (the gather is XLA-fused with the attention reads); a fused
  Pallas paged-attention kernel is the optimization seam.

A pool's rows need no head axis: a latent-attention model keeps a
latent pool ``[num_blocks, block_size, W]`` and an index-key pool beside
it under the same table (serving/latent.py); ``scatter_tokens`` and the
manager know blocks and offsets only.

Shared (refcount > 1) prefix blocks are READ-ONLY — the copy-on-write
contract (serving/prefixcache):

- a full prompt block whose chained digest matches a committed block
  is MAPPED (refcount bumped), never copied or recomputed;
- prefill write masking (the ``skip_upto`` argument of the scatter
  helpers) routes every write at a shared position to the trash sink,
  so a sharer can never perturb the block it maps — readers see the
  FIRST writer's KV bit-for-bit;
- a sequence that must write INSIDE its shared region (chunked
  prefill starting chunk-unaligned) first diverges those blocks via
  :meth:`BlockManager.cow_block` — still-shared blocks are copied to
  a fresh block, a privately-held committed block is unregistered in
  place — and only then writes.

Generated tokens, speculative-verify slack and bucket-padding junk
all land at positions >= the prompt's full-block prefix, which the
allocator always backs with fresh blocks — so the only writers the
COW machinery must police are the prefill paths above.

A SECOND kind of cache under the same manager (``BlockManager.windows``,
:class:`WindowStore`): a layer whose queries see the last ``w`` keys only
(``LayerSpec.window``) keeps, a slot, a RING of ``ring`` blocks, the row
of position ``p`` in ring block ``(p // block_size) % ring``.  No
allocator, no table on the host: a ring is its slot's for as long as the
slot lives, its bytes are ``slots x ring`` whatever ``num_blocks`` is,
and the device computes a position-ordered table of the ring's blocks
from the positions (:func:`ring_table`), under which the paged kernels
stream them like any other pages, masked to the window.  What a ring
holds of an earlier occupant, or of positions a wrap has passed, lies
outside every window of the present one and is never attended.  Behind
the rings the same pools hold SNAPSHOTS: the last ``w - 1`` rows of a
prompt's shareable prefix (``keep`` blocks a window layer), copied out
when a chunked prefill passes that boundary and copied into the ring of a
later request that warm-starts there (``serving/engine.py
_admit_chunked``): what the window layers need of a cached prefix where
the full layers share its blocks.  A snapshot belongs to the committed
block that ends at its boundary and goes when that block's registration
goes; a sequence whose prefill would begin where no snapshot is held
shares no block at all (``alloc_sequence``) and starts at 0.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.serving.prefixcache import PrefixBlockIndex, chain_key
from dlrover_tpu.utils.profiler import device_scoped

# legacy alias: the chained digest moved to serving/prefixcache (the
# router computes routing heads with the SAME function)
_chain_key = chain_key

# dlint DL012 contract: a block id handed out by the allocator is a
# refcount the caller now owes — every acquire site must return it,
# hand it to a sequence's block list, or push it back through the
# release surface on EVERY path (including exception edges)
_DLINT_RESOURCE_SPECS = (
    {
        "resource": "KV block refcount",
        "acquire": ("_take_block", "evict_one"),
        "release": ("free_sequence", "linger", "forget"),
        "why": "a dropped block id leaves _ref pinned nonzero forever "
               "— the pool shrinks by one block per leak until "
               "alloc_sequence starves every admission",
    },
)


class BlockManager:
    """Host-side pool bookkeeping: allocation, refcounts, prefix COW.

    Committed-prefix state (digests, content verification, the ref-0
    LRU, head tracking, the stats ledger) lives in
    :class:`~dlrover_tpu.serving.prefixcache.PrefixBlockIndex`; this
    class owns ids, the free list and refcounts.  ``sharing=False``
    disables prefix mapping entirely (every allocation gets fresh
    blocks, nothing is committed) — the COW-off half of the golden
    equivalence suite."""

    def __init__(self, num_blocks: int, block_size: int,
                 sharing: bool = True,
                 windows: Optional["WindowStore"] = None):
        # the window layers' rings and snapshots (module docstring); None:
        # the model has no window layer
        self.windows = windows
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.sharing = bool(sharing)
        # block 0 is the TRASH SINK, never allocated: the decode step
        # computes (and writes) junk KV for INACTIVE slots too — their
        # all-zero table rows must route those writes somewhere no live
        # sequence reads (the dense layout absorbs this in the dead
        # slot's own row; paging needs the sentinel)
        self._free: List[int] = list(range(1, num_blocks))[::-1]
        self._ref = np.zeros(num_blocks, np.int32)
        # committed blocks whose KV content has NOT been written yet.
        # Batched prefill writes within the same dispatch that follows
        # allocation, so its registrations are immediately valid; the
        # CHUNKED path registers at alloc time but writes the prompt
        # over many steps — the engine marks those blocks pending and
        # clears them (mark_filled) as its cursor crosses each one, so
        # a second sequence never warm-starts over unwritten content
        self._pending: set = set()
        self.index = PrefixBlockIndex()

    # ------------------------------------------------------------ alloc
    @property
    def available_blocks(self) -> int:
        return len(self._free) + self.index.lru_count()

    def _take_block(self) -> Optional[int]:
        if self._free:
            bid = self._free.pop()
        else:
            # evict the oldest lingering prefix block (LRU); the index
            # stages its head (if it was one) for the next advertisement
            # drain so the router's routing entry invalidates too
            bid = self.index.evict_one()
        if bid is not None:
            self._pending.discard(bid)
            self._drop_window(bid)
        return bid

    def _drop_window(self, bid: int) -> None:
        """``bid`` stands for no prefix any more: the window layers'
        snapshot behind it, if any, goes with the registration."""
        if self.windows is not None:
            self.windows.drop(bid)

    def window_entry(self, bid: int) -> Optional[int]:
        """An entry for a NEW snapshot of the window layers' rows behind
        block ``bid``; None where the block stands for no prefix (sharing
        off, diverged), has its snapshot, or the store is full."""
        if not self.index.is_committed(bid) \
                or self.windows.lookup(bid) is not None:
            return None
        return self.windows.take(bid)

    def mark_pending(self, bids: List[int]) -> None:
        """Declare committed blocks whose KV write is IN FLIGHT (the
        chunked-prefill registration gap).  ``shared_prefix_ready``
        holds admissions that would map them until :meth:`mark_filled`
        publishes each one.  Uncommitted ids (sharing disabled) are
        ignored — nothing can map them anyway."""
        self._pending.update(
            b for b in bids if self.index.is_committed(b))

    def mark_filled(self, bid: int) -> None:
        """The prefill dispatch covering ``bid``'s positions landed:
        its KV content now exists, so other sequences may warm-start
        over it."""
        self._pending.discard(bid)

    def shared_prefix_ready(self, prompt: np.ndarray) -> bool:
        """Would ``prompt``'s committed-prefix hits all hold WRITTEN
        content?

        Pure probe (no stats, no refcounts): walks the digest chain
        exactly like :meth:`alloc_sequence`'s hit loop and returns
        False iff some matching committed block is still pending —
        i.e. the first writer's chunked prefill has not reached it
        yet.  Callers keep the request queued and retry next step
        rather than mapping (and warm-starting past) content that
        does not exist."""
        if not self.sharing or not self._pending:
            return True
        bs = self.block_size
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        chain = b""
        for i in range(prompt.size // bs):
            tok_bytes = prompt[i * bs:(i + 1) * bs].tobytes()
            chain = chain_key(chain, tok_bytes)
            bid = self.index.lookup(chain, tok_bytes)
            if bid is None:
                break
            if bid in self._pending:
                return False
        return True

    def alloc_sequence(
        self, prompt: np.ndarray, total_len: int
    ) -> Optional[Tuple[List[int], int]]:
        """Blocks for a sequence of ``total_len`` positions whose first
        ``len(prompt)`` tokens are known: returns ``(blocks,
        shared_tokens)`` where the first ``shared_tokens`` positions
        are served by refcount-bumped prefix-cache hits, or None when
        the pool cannot cover the request (caller keeps it queued)."""
        bs = self.block_size
        # int32 normalization: digests are over raw token BYTES, and
        # the router's head_key hashes int32 — a caller handing int64
        # tokens must still land on the same chain
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n_blocks = -(-max(int(total_len), 1) // bs)
        # enforce total_len >= len(prompt) at the API boundary: a shorter
        # total_len would otherwise let len(shared) exceed n_blocks and
        # the returned list overflow the engine's fixed table row
        full_prompt_blocks = min(prompt.size // bs, n_blocks)

        shared: List[Tuple[bytes, int]] = []
        chain = b""
        if self.sharing:
            for i in range(full_prompt_blocks):
                tok_bytes = prompt[i * bs:(i + 1) * bs].tobytes()
                chain = chain_key(chain, tok_bytes)
                bid = self.index.lookup(chain, tok_bytes)
                if bid is None:
                    break
                shared.append((chain, bid))
        cold = bool(shared) and self.windows is not None \
            and not self.windows.warm([b for _, b in shared], prompt.size)
        if cold:
            # the window layers share no block: a sequence stands behind
            # cached blocks only where a snapshot holds those layers'
            # rows at the point its prefill would begin.  None there: it
            # takes blocks of its own and starts at 0 (nothing is copied
            # only to be overwritten; its blocks become the prefix's)
            shared = []
        need = n_blocks - len(shared)
        # reviving a shared hit that currently lingers in the LRU also
        # consumes availability (it leaves the evictable set) — without
        # counting those, the guard can pass and _take_block() then come
        # up empty mid-allocation
        revived = sum(1 for _, bid in shared if self._ref[bid] == 0)
        if need > self.available_blocks - revived:
            return None
        if cold:
            self.windows.cold_starts += 1
        blocks: List[int] = []
        for chain_h, bid in shared:
            if self._ref[bid] == 0:
                self.index.revive(bid)  # revive a lingering block
            self._ref[bid] += 1
            self.index.note_hit(bid, bs)
            blocks.append(bid)
        chain = shared[-1][0] if shared else b""
        for i in range(len(shared), n_blocks):
            bid = self._take_block()
            assert bid is not None  # guarded by available_blocks above
            self._ref[bid] = 1
            blocks.append(bid)
            if i < full_prompt_blocks:
                tok_bytes = prompt[i * bs:(i + 1) * bs].tobytes()
                chain = chain_key(chain, tok_bytes)
                if self.sharing:
                    self.index.note_miss()
                    self.index.register(
                        chain, bid, tok_bytes, head=(i == 0))
        return blocks, len(shared) * bs

    def free_sequence(self, blocks: List[int]) -> None:
        for bid in blocks:
            self._ref[bid] -= 1
            assert self._ref[bid] >= 0
            if self._ref[bid] == 0:
                if self.index.is_committed(bid) \
                        and bid not in self._pending:
                    # prefix block: linger in the LRU for reuse
                    self.index.linger(bid)
                else:
                    # uncommitted — or committed but still pending (its
                    # chunked writer was cancelled mid-prefill): the
                    # content is garbage, so drop the registration
                    # instead of letting a future hit map it
                    if self.index.is_committed(bid):
                        self.index.forget(bid)
                        self._drop_window(bid)
                    self._pending.discard(bid)
                    self._free.append(bid)

    # -------------------------------------------------------------- cow
    def cow_block(self, bid: int) -> Optional[Tuple[int, bool]]:
        """Divergence point: the caller is about to WRITE into ``bid``,
        which may be shared.  Returns ``(block, copied)``:

        - still shared (ref > 1): a fresh block with ref 1; ``bid``'s
          ref comes down by one and ``copied=True`` tells the caller
          to copy the pool rows ``bid -> block`` before writing;
        - privately held (ref == 1) but committed: the SAME id with
          its registration dropped (``copied=False``) — no other
          sequence can map it mid-rewrite;
        - None: pool exhausted (no block for the copy) — the caller
          rolls its admission back and keeps the request queued."""
        if self._ref[bid] > 1:
            new = self._take_block()
            if new is None:
                return None
            self._ref[bid] -= 1
            self._ref[new] = 1
            self.index.note_cow()
            return new, True
        if self.index.is_committed(bid):
            self.index.forget(bid)
            self._pending.discard(bid)
            self._drop_window(bid)
        return bid, False

    # ------------------------------------------------------------ books
    def shared_block_count(self) -> int:
        """Blocks currently mapped by more than one sequence."""
        return int((self._ref > 1).sum())

    def prefix_stats(self) -> Dict[str, float]:
        """The ``serving_prefix_*`` ledger for this pool."""
        stats = self.index.stats()
        stats["prefix_shared_blocks"] = float(self.shared_block_count())
        return stats

    def hot_heads(self, n: int = 8) -> List[str]:
        return self.index.hot_heads(n)

    def drain_evicted_heads(self) -> List[str]:
        return self.index.drain_evicted_heads()

    def check_books(self) -> bool:
        """Assert the block books balance: every block except the
        trash sink is in EXACTLY one of {free list, referenced,
        ref-0 LRU}, and LRU membership implies committed.  The fuzz
        and chaos suites call this after every interleaving — a leak
        or double-free fails here, not three allocations later.
        Returns True so callers can write ``assert m.check_books()``."""
        free = set(self._free)
        assert len(free) == len(self._free), "free list holds dupes"
        live = {int(b) for b in np.nonzero(self._ref > 0)[0]}
        lru = {bid for bid in range(self.num_blocks)
               if self.index.in_lru(bid)}
        assert 0 not in free | live | lru, "trash sink was allocated"
        assert not (free & live), f"free AND referenced: {free & live}"
        assert not (free & lru), f"free AND lingering: {free & lru}"
        assert not (live & lru), f"referenced AND lingering: {live & lru}"
        every = free | live | lru
        expect = set(range(1, self.num_blocks))
        assert every == expect, (
            f"leaked blocks: {sorted(expect - every)}; "
            f"phantom blocks: {sorted(every - expect)}")
        for bid in lru:
            assert self.index.is_committed(bid), (
                f"uncommitted block {bid} lingering in LRU")
        return True


# ------------------------------------------------------- window rings
class RingGeometry(NamedTuple):
    """A window layer's cache a slot (module docstring), in blocks."""

    window: int        # keys a query sees, itself counted
    chunk: int         # queries of a prompt chunk
    block_size: int
    ring: int          # blocks a slot and window layer
    reach: int         # blocks one query's window touches at most
    keep: int          # blocks of a snapshot: the last ``window - 1`` rows

    @property
    def rows(self) -> int:
        return self.ring * self.block_size


def ring_geometry(window: int, chunk: int, block_size: int) -> RingGeometry:
    """The ring of a layer of window ``window`` whose prompts arrive in
    chunks of ``chunk`` queries that start at multiples of it: a chunk
    needs the ``window - 1`` rows behind it and writes its own, so
    ``ceil((window - 1) / block) + ceil(chunk / block)`` blocks, one more
    where chunks do not start on a block's first row; never more than
    ``ceil((window - 1 + chunk) / block) + 1``."""
    back = -(-(window - 1) // block_size)
    odd = 1 if chunk % block_size else 0
    ring = min(back + -(-chunk // block_size) + odd,
               -(-(window - 1 + chunk) // block_size) + 1)
    return RingGeometry(window, chunk, block_size, ring, back + 1,
                        back + odd)


def warm_start(shared: int, prompt: int, chunk: int) -> int:
    """Where the chunked prefill of a prompt of ``prompt`` tokens begins
    behind ``shared`` cached positions: the last chunk boundary inside
    them, the FINAL chunk kept live even where the whole prompt is shared
    (sampling the first token needs one real dispatch)."""
    return min(shared // chunk * chunk, (prompt - 1) // chunk * chunk)


class WindowStore:
    """Host-side books of the window layers' caches, a layer two arrays:
    the rings ``[slots, ring, block_size, W]`` and the snapshots
    ``[snapshots, keep, block_size, W]``: which ring blocks hold a
    prefix's last rows, and which prefix each snapshot belongs to.

    A snapshot belongs to the prefix index's BLOCK that ends where it was
    taken (a committed block stands for the whole prefix behind it), and
    lives as long as that block's registration: ``BlockManager`` drops it
    with the block, so the index's LRU is the one policy.  A full store
    keeps nothing new until the index lets a block go; half the slots'
    number of snapshots (four at least) holds the prefixes that requests
    in flight can stand behind."""

    def __init__(self, geometry: RingGeometry, slots: int, sharing: bool):
        self.geometry = geometry
        self.slots = int(slots)
        self.snapshots = max(4, self.slots // 2) if sharing else 0
        self.cold_starts = 0     # sequences a missing snapshot kept from
        #                          standing behind cached blocks
        self._entry_of: Dict[int, int] = {}     # block id -> snapshot
        self._free = list(range(self.snapshots))[::-1]

    def ring_blocks(self, end: int) -> np.ndarray:
        """Which blocks of a slot's ring hold the last ``window - 1``
        positions before ``end``, in position order: ``keep`` indices,
        ``ring`` (no block: a write there is dropped) behind the last."""
        g = self.geometry
        first = max(0, end - (g.window - 1)) // g.block_size
        ids = [ab % g.ring for ab in range(first, -(-end // g.block_size))]
        return np.asarray(ids + [g.ring] * (g.keep - len(ids)), np.int32)

    def lookup(self, bid: int) -> Optional[int]:
        """The snapshot of the prefix that block ``bid`` ends; None where
        there is none."""
        return self._entry_of.get(bid)

    def warm(self, shared: List[int], prompt: int) -> bool:
        """Are the window layers' rows held where a prompt of ``prompt``
        tokens would begin its prefill behind the cached blocks
        ``shared`` (:func:`warm_start`)?"""
        g = self.geometry
        start = warm_start(len(shared) * g.block_size, prompt, g.chunk)
        return start > 0 and shared[start // g.block_size - 1] \
            in self._entry_of

    def take(self, bid: int) -> Optional[int]:
        """An entry for a new snapshot behind block ``bid`` (None: the
        store is full)."""
        if not self._free:
            return None
        self._entry_of[bid] = self._free.pop()
        return self._entry_of[bid]

    def drop(self, bid: int) -> None:
        """Block ``bid`` stands for no prefix any more."""
        entry = self._entry_of.pop(bid, None)
        if entry is not None:
            self._free.append(entry)

    def resident_rows(self, length) -> np.ndarray:
        """Rows of sequences of ``length`` positions that their rings
        hold, a window layer."""
        return np.minimum(np.asarray(length), self.geometry.rows)


def ring_table(slots: jax.Array, first_pos: jax.Array, blocks: int,
               window: int, ring: int, block_size: int):
    """``(table [B, blocks], base [B])``: for each row, blocks of the ring
    of slot ``slots[b]`` (of a pool ``[slots x ring, block_size, W]``) in
    POSITION order, from the block that holds the first key a query at
    ``first_pos[b]`` sees, and the position of that table's first row.  A
    block ahead of the newest position holds what a wrap left there: the
    caller's mask is by position."""
    fb = jnp.maximum(first_pos - (window - 1), 0) // block_size
    ab = fb[:, None] + jnp.arange(blocks)[None, :]
    table = slots[:, None] * ring + ab % ring
    return table.astype(jnp.int32), (fb * block_size).astype(jnp.int32)


@device_scoped("kv_write")
def scatter_ring(pool: jax.Array, slots: jax.Array, rows: jax.Array,
                 positions: jax.Array, real: jax.Array,
                 ring: int) -> jax.Array:
    """Write ``rows`` [B, K, W], the rows of positions ``positions[b] ..
    + K - 1``, into the rings of ``slots`` [B] in ``pool`` [slots x ring,
    block_size, W]; a row ``real`` [B, K] does not mark (a parked slot's,
    what pads a chunk) is written nowhere."""
    b, k = rows.shape[:2]
    bs = pool.shape[1]
    pos = positions[:, None] + jnp.arange(k)[None, :]
    bid = slots[:, None] * ring + (pos // bs) % ring
    bid = jnp.where(real, bid, pool.shape[0])
    return pool.at[bid.reshape(-1), (pos % bs).reshape(-1)].set(
        rows.reshape(b * k, rows.shape[-1]), mode="drop")


# ---------------------------------------------------------------- device
@device_scoped("paged_attn")
def gather_blocks(pool: jax.Array, table: jax.Array) -> jax.Array:
    """``pool [NB, bs, KV, D] x table [B, MB] -> [B, MB*bs, KV, D]`` —
    the dense per-slot view the attention kernels consume."""
    b, mb = table.shape
    g = jnp.take(pool, table, axis=0)          # [B, MB, bs, KV, D]
    return g.reshape(b, mb * pool.shape[1], *pool.shape[2:])


def _block_offsets(table: jax.Array, positions: jax.Array,
                   k: int, bs: int,
                   skip_upto: Optional[jax.Array] = None):
    """``(block_id [B, K], offset [B, K])`` for K consecutive positions
    per slot.  Positions BEYOND the table row route to block 0 (the
    trash sink) instead of gather-clamping to the last column: a
    clamped write would land in the row's LAST listed block at a
    wrapped offset — which for a full-length sequence is a LIVE block
    — whereas parked/inactive slots (chunked prefill holds a slot
    mid-prompt while decode keeps dispatching) legitimately emit
    out-of-range junk positions that must go nowhere.

    ``skip_upto`` [B] is the COW write mask: positions BELOW it are
    served by shared prefix blocks (refcount > 1 — read-only by the
    copy-on-write contract), so their writes route to the trash sink
    too.  The trash detour is cheaper and simpler than predicating the
    scatter itself, and the VALUES being suppressed are recomputed
    bit-identical anyway — the mask exists so a numerically-divergent
    rewrite (different batch geometry) can never perturb a block
    another live sequence is reading."""
    mb = table.shape[1]
    pos = positions[:, None] + jnp.arange(k)[None, :]        # [B, K]
    col = pos // bs
    bidx = jnp.take_along_axis(
        table, jnp.minimum(col, mb - 1), axis=1)             # [B, K]
    bidx = jnp.where(col < mb, bidx, 0)
    if skip_upto is not None:
        bidx = jnp.where(pos < skip_upto[:, None], 0, bidx)
    return bidx, pos % bs


@device_scoped("kv_write")
def scatter_tokens(
    pool: jax.Array,        # [NB, bs, KV, D]
    table: jax.Array,       # [B, MB]
    kv: jax.Array,          # [B, K, KV, D] new entries
    positions: jax.Array,   # [B] position of kv[:, 0]
    skip_upto: Optional[jax.Array] = None,  # [B] COW write mask
) -> jax.Array:
    """Write K consecutive tokens per slot into their blocks."""
    bs = pool.shape[1]
    b, k = kv.shape[:2]
    bidx, off = _block_offsets(table, positions, k, bs, skip_upto)
    return pool.at[bidx.reshape(-1), off.reshape(-1)].set(
        kv.reshape(b * k, *kv.shape[2:])
    )


# -------------------------------------------------- quantized KV pools
def kv_budget_multiplier(ref_dtype, head_dim: int,
                         kv_dtype: str = "int8") -> float:
    """THE single source of KV-budget math: how many ``kv_dtype``
    blocks fit in the HBM of one ``ref_dtype`` block.  A quantized
    (token, head) vector costs its code bytes (``D`` for int8) plus one
    ``KV_SCALE_DTYPE`` scale —
    ``D * itemsize(ref) / (code_bytes + itemsize(scale))``.

    bf16 references: int8 -> 1.94x @ D=64 / 1.97x @ D=128.

    Everything downstream derives from THIS function — the engine
    multiplies its HBM-denominated ``cache_blocks`` budget by it
    (``InferenceEngine.kv_budget_x``), ``alloc_sequence`` admits
    against the multiplied pool, and the router's placement ledger
    (``InferenceEngineAdapter.blocks_free`` locally, the worker's
    HELLO/STATS ``blocks_free`` remotely) reads the same pool — so the
    engine's admission and the router's placement can never disagree
    on what a quantized pool holds (regression-tested in
    tests/test_paged_kernel.py)."""
    from dlrover_tpu.models.quantize import KV_SCALE_DTYPE

    if kv_dtype in (None, "bf16"):
        return 1.0
    if kv_dtype != "int8":
        raise ValueError(f"kv_budget_multiplier: unknown kv_dtype "
                         f"{kv_dtype!r}")
    ref = int(head_dim) * jnp.dtype(ref_dtype).itemsize
    return ref / (float(head_dim) + jnp.dtype(KV_SCALE_DTYPE).itemsize)


@device_scoped("kv_write")
def scatter_tokens_q(
    pool: jax.Array,        # [NB, bs, KV, D] int8 codes
    scale_pool: jax.Array,  # [NB, bs, KV] per-vector scales
    table: jax.Array,       # [B, MB]
    kv: jax.Array,          # [B, K, KV, D] new fp entries
    positions: jax.Array,   # [B]
    skip_upto: Optional[jax.Array] = None,  # [B] COW write mask
):
    """Quantize-and-write K consecutive tokens per slot: codes into the
    int8 pool, per-(token, head) scales into the block-shaped scale
    pool (same index math, so a write is always self-consistent)."""
    from dlrover_tpu.models.quantize import quantize_kv_int8

    bs = pool.shape[1]
    b, k = kv.shape[:2]
    q, scale = quantize_kv_int8(kv)
    bidx, off = _block_offsets(table, positions, k, bs, skip_upto)
    flat_b, flat_o = bidx.reshape(-1), off.reshape(-1)
    return (
        pool.at[flat_b, flat_o].set(q.reshape(b * k, *q.shape[2:])),
        scale_pool.at[flat_b, flat_o].set(
            scale.reshape(b * k, *scale.shape[2:])),
    )


@device_scoped("paged_attn")
def gather_blocks_q(
    pool: jax.Array,        # [NB, bs, KV, D] int8 codes
    scale_pool: jax.Array,  # [NB, bs, KV]
    table: jax.Array,       # [B, MB]
    dtype,
) -> jax.Array:
    """Dense ``[B, MB*bs, KV, D]`` dequantized view of int8 pools — the
    dequant fuses into the consuming attention reads, so the pool
    streams from HBM at int8 width (the whole point: KV budget is what
    caps the continuous batch)."""
    from dlrover_tpu.models.quantize import dequantize_kv_int8

    b, mb = table.shape
    g = jnp.take(pool, table, axis=0)          # [B, MB, bs, KV, D]
    s = jnp.take(scale_pool, table, axis=0)    # [B, MB, bs, KV]
    return dequantize_kv_int8(
        g.reshape(b, mb * pool.shape[1], *pool.shape[2:]),
        s.reshape(b, mb * pool.shape[1], *s.shape[3:]),
        dtype,
    )
