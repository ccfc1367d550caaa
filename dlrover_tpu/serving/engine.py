"""Continuous-batching inference engine (prefill/decode split).

The TPU counterpart of the reference's vLLM inference backend for RL
rollouts (atorch/atorch/rl/inference_backend/vllm_backend.py:11-24) and
its generation config (rl/model_utils/vllm_utils.py): a slotted decode
batch that sequences enter and leave independently —

- ``max_slots`` concurrent sequences decode as ONE jitted step batch;
- a finished slot (EOS / budget) is refilled from the request queue by
  a bucketed prefill WITHOUT stopping the other slots (continuous
  batching, the Orca/vLLM scheduling model);
- decode runs in chunks of ``chunk`` tokens per host sync (multi-step
  scheduling) — sampling stays on-device inside a ``lax.scan``;
- a step dispatches ALL its programs (the prefills of what it admits,
  one prompt chunk of what still prefills, the decode chunk) before it
  waits for any: each slot's last token passes from program to program
  on the device, and the host reads every result once, in dispatch
  order, behind the last dispatch; the decode chunk it leaves UNREAD
  for the next step, which reads it behind its own dispatches, so the
  device has the next chunk queued while the host reads, delivers and
  dispatches (``InferenceEngine.step``);
- ``int8=True`` serves pre-quantized int8 weights through XLA's native
  int8 MXU dot (weights stream from HBM at half the bf16 bytes — decode
  is bandwidth-bound, so this is the serving speedup; measured against
  the hand-tiled Pallas alternative, the native dot wins at every
  serving shape: the numbers are in ``serving/model._mm``).

Static shapes everywhere: prompts right-pad to power-of-two buckets,
the decode batch is fixed at ``max_slots``, EOS only masks. One compile
per (bucket) + one for the decode chunk.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.models.llama import LlamaConfig
from dlrover_tpu.rl.generation import select_token
from dlrover_tpu.serving.model import decode_step, prefill
from dlrover_tpu.serving.params import serving_params_from_llama
from dlrover_tpu.utils.profiler import (
    abstract,
    device_scope,
    event,
    program_texts,
    register_program,
    span,
    spanned,
)

# dlint DL012 contract: a lifetime allocation is owned by the admitting
# path until it is bound to a slot (whose release funnel is
# _release_slot -> free_sequence) or rolled back — an allocation that
# escapes _admit/_admit_chunked any other way strands its refcounts
_DLINT_RESOURCE_SPECS = (
    {
        "resource": "sequence lifetime allocation",
        "acquire": ("_alloc_lifetime", "alloc_sequence"),
        "release": ("free_sequence", "_bind_blocks"),
        "owners": ("allocs",),
        "why": "an admission that drops its allocation on a bail-out "
               "path pins every block in it until restart — the "
               "chunked COW rollback exists exactly for this",
    },
)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # [P] int32
    max_new_tokens: int
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # the request's own clock: ``time.monotonic()``, the router's, which
    # takes these stamps as they are (the programs' ``*_seconds`` keep
    # ``perf_counter``), each read where the host books the fact
    queued_at: float = 0.0        # ``add_request``
    admitted_at: Optional[float] = None  # its slot and blocks booked
    # the READ of the program that sampled its first and its newest
    # tokens: one stamp a program read, the same for every lane of it
    first_token_at: Optional[float] = None
    last_token_at: Optional[float] = None
    prompt_chunks: int = 0        # programs that ran its prompt, one an
    #                               engine step (a bucketed prefill: 1)
    deliveries: int = 0           # reads that handed it tokens
    cached_tokens: int = 0        # where in its prompt its prefill
    #                               began: a chunked warm start's first
    #                               position, the shared region whose
    #                               writes a bucketed prefill masks (it
    #                               computes them all the same), 0 cold
    gap_max: float = 0.0          # its longest wait between deliveries


@dataclasses.dataclass
class EngineStats:
    generated_tokens: int = 0
    # the three ``*_seconds`` add, for each program, the host clock from
    # the later of (its dispatch's start, the previous program's result
    # reaching the host) to its own result reaching the host: dispatch +
    # wait where it was the only program in flight, the program's time on
    # the device where it queued behind another (``_read_results``)
    decode_seconds: float = 0.0
    prefill_seconds: float = 0.0
    prefill_calls: int = 0        # dispatches; < admissions when batched
    prefill_admissions: int = 0   # requests admitted into prefill —
    #                               the batched-prefill win is
    #                               admissions/dispatches, measurable
    #                               only with BOTH counters exposed
    prefill_chunks: int = 0       # chunked-prefill dispatches
    #                               (a subset of prefill_calls)
    prefill_chunk_slots: int = 0  # slot-chunks advanced — with
    #                               same-step batching > chunks when
    #                               several long prompts prefill
    #                               together (the TTFT-deserialization
    #                               win is slots/chunks)
    prefill_chunk_seconds: float = 0.0  # the chunk dispatches' part of
    #                               prefill_seconds (the stall-bound
    #                               budget)
    dispatches: int = 0           # programs sent to the device
    chained_dispatches: int = 0   # ... while an earlier one was still
    #                               unread (of the same step, or the
    #                               decode chunk the step before left in
    #                               flight): its host preparation cost
    #                               the device no gap
    lookahead_steps: int = 0      # steps that returned with their decode
    #                               chunk unread
    wasted_lane_chunks: int = 0   # decode-chunk lanes whose request had
    #                               ended (an end-of-sequence, read one
    #                               chunk late) before the chunk was
    #                               read: their tokens were dropped
    # a latent-attention model's prompt chunks (one layer's, as every
    # layer walks the same): host arithmetic from each chunk's REAL end
    # (what pads a prompt's last chunk to the program's shape attends
    # nothing)
    prefill_key_blocks: int = 0   # key blocks their attention walked:
    #                               whole blocks up to each chunk's last
    #                               real query
    prefill_key_blocks_table: int = 0  # key blocks their tables hold
    prefill_query_tiles: int = 0  # tiles of queries the chunk programs
    #                               held (the attention kernel's)
    prefill_query_tiles_live: int = 0  # ... with a real query among
    #                               them: the only ones that walk keys
    finished_requests: int = 0
    spec_proposed: int = 0        # draft tokens sent to verification
    spec_accepted: int = 0        # draft tokens accepted
    spec_calls: int = 0           # verify dispatches (model forwards)
    decode_forwards: int = 0      # ALL decode-path model forwards
    kv_rows_live: int = 0         # key rows the fused paged kernel's
    #                               decoding slots could see, summed
    #                               over slots and decode forwards
    kv_rows_streamed: int = 0     # key rows that kernel copied for
    #                               them: whole page groups up to each
    #                               slot's length.  Both stay 0 where
    #                               the gather path decodes.  Of a
    #                               latent-attention model, with a
    #                               learned selection or none: one
    #                               layer's latent rows
    #                               (mla_decode_attention); of a
    #                               grouped-query model under a
    #                               selection: every live K/V row, the
    #                               mask's rows among them
    # a model with a learned selection of keys (LlamaConfig.index_topk),
    # summed over queries (decode forwards and prefill chunks alike) and
    # over nothing else: one layer's rows, as every layer reads the same
    dsa_rows_live: int = 0        # key rows the queries could see
    index_rows_scanned: int = 0   # index keys scored for them: whole
    #                               page groups / key blocks up to each
    attn_rows_selected: int = 0   # rows they attended to:
    #                               min(live, index_topk) each
    # sparse experts served as one share (LlamaConfig.moe_experts_held):
    # the router's picks over ALL experts, and those on the experts held
    # here (a device reduction carried in the cache beside the pools)
    moe_picks: int = 0
    moe_picks_held: int = 0
    # ... and how the sparse layers' sorted buffer met them
    # (serving/latent.py sparse_mlp): the walks taken over the held picks
    # and the layer-forwards that took them
    moe_buffer_walks: int = 0
    moe_layer_forwards: int = 0
    # layers that keep a state a slot (LayerSpec.mixer "kda", "ssm" or
    # "retention"), float32 a head and slot: host arithmetic, summed over
    # decode forwards and layers
    state_bytes_live: int = 0     # read + written for the slots that
    #                               decoded: 2 x a slot's state each
    state_bytes_streamed: int = 0  # ... for the slots the decode kernel's
    #                               grid walked (kda_decode_step,
    #                               ssm_decode_step, retention_decode_step:
    #                               the active ones; the jnp path walks all)
    state_resets_total: int = 0   # slots whose state a prompt's first
    #                               chunk started from zeros
    # window layers of latent attention (LayerSpec.window), whose rows live
    # in a ring a slot (serving/paged.py): host arithmetic at each decode
    # dispatch, summed over its forwards and the window layers
    window_rows_in_window: int = 0  # rows the decoding slots' queries
    #                               could see: min(position + 1, window)
    window_rows_streamed: int = 0  # rows their decode attention read: the
    #                               blocks a window touches, whole
    window_rows_resident: int = 0  # a GAUGE: rows the live slots' rings
    #                               hold now, one window layer's
    window_rows_resident_max: int = 0  # ... the most any ONE sequence has
    #                               held: never more than ring x block
    window_warm_starts: int = 0   # admissions that found their shared
    #                               prefix's window rows kept, and began
    #                               behind it
    window_cold_fallbacks: int = 0  # ... that found cached blocks and no
    #                               rows kept where their prefill would
    #                               begin: no block shared, begun at 0
    kda_chunk_rows_real: int = 0  # prompt tokens their chunk kernel took
    kda_chunk_rows_padded: int = 0  # ... and the rows of the 64-token
    #                               chunks it computed for them
    # state-space layers (LayerSpec.mixer "ssm"): the ``state_*`` counters
    # above count their states too; their chunk kernel's rows
    ssm_chunk_rows_real: int = 0
    ssm_chunk_rows_padded: int = 0  # ... in chunks of 128 tokens
    # power retention (LayerSpec.mixer "retention"): the ``state_*``
    # counters count its state AND its sum of keys; its chunk kernel's rows
    retention_chunk_rows_real: int = 0
    retention_chunk_rows_padded: int = 0  # ... in chunks of 128 tokens
    # the requests' own clocks (``Request``), summed over the engine's
    # whole life: sums and counts an operator divides, no percentile here
    slot_wait_seconds: float = 0.0  # ``add_request`` to a slot and its
    #                               blocks, over admissions
    prefill_wall_seconds: float = 0.0  # admission to the read of the
    first_tokens: int = 0         # ... first token, and how many
    token_gap_seconds: float = 0.0  # a delivery's read less the same
    token_gaps: int = 0           # ... request's last one: a first
    #                               delivery has none
    prompt_tokens: int = 0        # of admitted prompts, and those its
    prompt_tokens_cached: int = 0  # ... prefill began behind
    # ... by name (no field: how ``engine_metrics`` hands them on)
    REQUEST_CLOCK = (
        "slot_wait_seconds", "prefill_wall_seconds", "first_tokens",
        "token_gap_seconds", "token_gaps", "prompt_tokens",
        "prompt_tokens_cached")

    @property
    def decode_tokens_per_sec(self) -> float:
        return self.generated_tokens / self.decode_seconds \
            if self.decode_seconds else 0.0

    @property
    def tokens_per_forward(self) -> float:
        """Committed tokens per decode-path model forward — the
        speculative-decoding win metric (1.0 = plain decode; >1 means
        drafts amortized forwards)."""
        return self.generated_tokens / self.decode_forwards \
            if self.decode_forwards else 0.0

    @property
    def spec_accept_ratio(self) -> float:
        """Accepted draft tokens over proposed — the live health signal
        of the speculation governor (``serving_spec_accept_ratio`` on
        /metrics; ``tokens_per_forward`` is the derived win)."""
        return self.spec_accepted / self.spec_proposed \
            if self.spec_proposed else 0.0

    @property
    def dsa_selected_ratio(self) -> float:
        """Rows attended per row live under a learned selection: 1.0
        while contexts are no longer than ``index_topk`` (the selection
        is the identity), ``index_topk / context`` beyond (0.0 for a
        model without one)."""
        return self.attn_rows_selected / self.dsa_rows_live \
            if self.dsa_rows_live else 0.0

    @property
    def moe_held_share(self) -> float:
        """Picks on the experts this replica holds over all picks:
        ``held / num_experts`` when the router spreads evenly (0.0 for
        a dense model)."""
        return self.moe_picks_held / self.moe_picks \
            if self.moe_picks else 0.0

    @property
    def moe_walks_per_layer(self) -> float:
        """Walks of the sorted buffer a sparse layer-forward: 1.0 while
        every layer's held picks fit its buffer, more where a hot expert
        overflowed it, less where layers held no pick (0.0 for a dense
        model)."""
        return self.moe_buffer_walks / self.moe_layer_forwards \
            if self.moe_layer_forwards else 0.0

    @property
    def chained_dispatch_share(self) -> float:
        """Programs dispatched behind an unread one over all programs
        dispatched: how often the device's queue hid the host's
        preparation of the next program (near 1.0 while steps look
        ahead: only a dispatch into an idle engine is not chained)."""
        return self.chained_dispatches / self.dispatches \
            if self.dispatches else 0.0

    @property
    def prefill_key_block_share(self) -> float:
        """Key blocks the prompt chunks' attention walked over the key
        blocks their tables hold: how far the work follows the live
        depth and not the table's width (0.0 for a model whose chunks
        gather a slot's whole table)."""
        return self.prefill_key_blocks / self.prefill_key_blocks_table \
            if self.prefill_key_blocks_table else 0.0

    @property
    def prefill_live_tile_share(self) -> float:
        """Tiles of queries with a real query among them over the tiles
        the prompt chunks' programs held: 1.0 while every chunk is whole,
        lower by what pads prompts' last chunks, which the attention
        skips but the chunk's projections, index scan and MLPs still
        compute (0.0 for a model whose chunks gather a slot's table)."""
        return self.prefill_query_tiles_live / self.prefill_query_tiles \
            if self.prefill_query_tiles else 0.0

    @property
    def state_stream_ratio(self) -> float:
        """Recurrent-state bytes the decode forwards moved per byte of
        the slots that decoded: 1.0 when idle slots are not walked (0.0
        for a model with no such layer)."""
        return self.state_bytes_streamed / self.state_bytes_live \
            if self.state_bytes_live else 0.0

    @property
    def window_stream_ratio(self) -> float:
        """Rows the window layers' decode attention read per row inside
        the windows: 1.0 = a decode reads the window and no more (0.0 for
        a model with no window layer)."""
        return self.window_rows_streamed / self.window_rows_in_window \
            if self.window_rows_in_window else 0.0

    @property
    def kv_stream_ratio(self) -> float:
        """Key rows the paged kernel copied per row a slot could see:
        1.0 is a stream with no dead row, and running every group of
        the table for every slot reads ``slots x table rows / live``
        (0.0 before the kernel has decoded anything)."""
        return self.kv_rows_streamed / self.kv_rows_live \
            if self.kv_rows_live else 0.0


@dataclasses.dataclass
class _Unread:
    """Programs dispatched and not yet read (one bucketed prefill, one
    step's prompt chunks, one decode chunk): what ``_read_results``
    needs to time them and hand their tokens on.  A decode chunk
    outlives the step that dispatched it, so whatever its delivery needs
    travels here: the slots it advanced may hold other requests by
    then."""
    name: str                     # span ``dlrover.engine.<name>``
    attrs: Dict[str, Any]         # ... and its attributes
    started: float                # host clock at the dispatch's start
    outputs: Any                  # device arrays the host has to read
    # the bookkeeping that needs them, ``deliver(rows, values, read at,
    # name)``, and the lanes it is for: tuples of (slot, request, ...),
    # which ``cancel`` thins
    deliver: Callable[[List[tuple], Any, float, str], None]
    rows: List[tuple]
    # ``witness_log`` entries of these programs (``watch``): in the log
    # once the programs are read, never while they run
    witness: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    # the experts' pick counters as these programs left them (a copy
    # beside the donated cache's; None for a model that counts none)
    picks: Any = None


# the ``EngineStats`` clocks a program's time adds to, by ``_Unread.name``
_CLOCKS = {"prefill": ("prefill_seconds",),
           "prefill_chunk": ("prefill_seconds", "prefill_chunk_seconds"),
           "decode_chunk": ("decode_seconds",)}


# a program that hands on first tokens only: no delivery before them
_NO_GAPS = (0, 0.0, 0.0)


def _erids(requests) -> str:
    """Whose prompt a program ran, for its span (joined with spaces: the
    profiler cuts a string at a comma)."""
    return " ".join(str(r.rid) for r in requests)


def _bucket(n: int, buckets: Tuple[int, ...]) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds largest bucket "
                     f"{buckets[-1]}")


class InferenceEngine:
    """Continuous-batching generation over a Llama-family model."""

    def __init__(
        self,
        cfg: LlamaConfig,
        variables: Any,
        *,
        max_slots: int = 8,
        int8: bool = False,
        chunk: int = 8,
        temperature: float = 1.0,
        top_k: int = 0,
        top_p: float = 1.0,
        eos_token: Optional[int] = None,
        max_len: Optional[int] = None,
        prefill_buckets: Optional[Tuple[int, ...]] = None,
        speculative_k: Any = 0,
        spec_accept_floor: float = 0.15,
        paged: bool = False,
        cache_blocks: Optional[int] = None,
        block_size: int = 16,
        kv_dtype: Optional[str] = None,
        prefill_chunk: int = 0,
        attention_impl: str = "auto",
        mesh: Optional[Any] = None,
        seed: int = 0,
        prefix_sharing: bool = True,
    ):
        """``speculative_k > 1`` enables prompt-lookup speculative
        decoding: each dispatch verifies up to ``speculative_k - 1``
        draft tokens found by n-gram lookup in the slot's own context,
        committing up to ``speculative_k`` tokens for ~one decode
        step's cost.  Works with ANY sampling config: greedy verifies
        by argmax match, temperature/top-k/top-p by exact rejection
        sampling (serving/speculative.rejection_commit).

        ``speculative_k="auto"``: start in plain chunk decode, watch
        the (free) draft hit rate, and switch speculation on when
        drafts are available often enough to pay — then self-regulate:
        measured acceptance below ``spec_accept_floor`` backs off to
        chunk decode and re-probes later.

        ``prefill_chunk > 0`` enables CHUNKED prefill: a prompt whose
        bucket exceeds the chunk is admitted into a slot immediately
        but prefilled ``prefill_chunk`` tokens per engine step (a
        ``real_len`` cursor survives across dispatches), interleaved
        with the decode dispatches of the other slots — so the batch's
        worst inter-token gap is bounded by ONE chunk's cost instead
        of a whole max-length prefill (the Sarathi-style stall bound).
        Cancel/failover mid-prefill reclaims the slot and its KV
        blocks like any live slot.

        ``kv_dtype="int8"`` (requires ``paged=True``) stores the K/V
        block pools as int8 codes with per-(token, head) scales in
        block-shaped scale pools (models/quantize machinery).  An
        HBM-denominated ``cache_blocks`` budget is multiplied by
        ``kv_budget_x`` (~2x for bf16 models), which is what doubles
        the continuous batch the placement ledger can admit at fixed
        HBM.

        ``cache_blocks`` counts blocks of ``block_size`` ROWS, a row
        being what one token keeps in one layer: K and V of every KV head
        (``2 x kv_heads x head_dim`` values) or, of a latent-attention
        model, its normed latent row, its rotated key row and its index
        key (512 + 64 + 128 values for GLM-5: 1 408 bytes in bf16, no
        head axis, in two pools under the sequence's ONE block table;
        the latent pool pads its 576 to 640, whole 128-lane tiles, so
        a row costs 1 536 bytes: ``serving/latent.py``).
        Such a model needs ``paged=True``, keeps its rows in the model's
        dtype (``kv_dtype`` None, ``kv_budget_x`` 1) and runs on one
        device with unquantized weights.  A model's layers that keep a
        recurrent STATE (``LayerSpec.mixer`` "kda", linear attention, beside
        latent attention; "ssm", a Mamba-2 scan, beside grouped-query
        attention; "retention", power retention, in EVERY layer) keep no
        rows: the pools are an
        attention layer each, and beside them a float32 state a SLOT and
        such layer under the kind's name (``kda_state`` [slots, H, d, d] and
        ``kda_conv``, ``ssm_state`` [slots, H, P, N] and ``ssm_conv``, or
        ``retention_state`` [slots, Hk, tiles, d, d] and
        ``retention_keysum``: ``serving/linear.py state_shapes``), ONE
        mechanism for all, the same
        size at token 1 and at token 1 000 000, zeroed inside the program
        that takes a slot's first prompt chunk, carried from chunk to
        chunk, held still while the slot is idle or prefilling
        (``cache_nbytes`` counts both kinds).  Such a model takes every
        prompt in chunks (``prefill_chunk`` > 0) and is refused prefix
        sharing, drafts and a mesh by what each would need.  A model with
        NO layer that caches rows has no pool and no block table at all:
        ``paged`` reads False whatever was passed, ``cache_blocks`` and
        ``block_size`` size nothing, and admission (the engine's and a
        router's) is by SLOTS.  Its WINDOW
        layers (``LayerSpec.window``) keep no blocks of the pool either: a
        ring a slot and layer, sized by ``slots x window`` whatever
        ``cache_blocks`` is, and kept copies (half the slots' number, at
        least 4) of a prompt prefix's last ``window - 1`` rows, from which
        a request that shares the prefix starts warm (``serving/paged.py
        WindowStore``).  Such a model too takes every prompt in chunks and
        is refused drafts and a mesh.

        ``attention_impl`` selects the paged decode attention read:
        ``"xla"`` = fused gather (materializes the dequantized dense
        view), ``"pallas"`` = the fused paged kernel (streams blocks
        in place at code width, dequant folded inside), ``"auto"``
        (default) = a one-shot measured comparison on this engine's
        real pool geometry at build, picking the faster — so auto can
        never select a slower impl.  Non-TPU backends resolve auto to
        ``"xla"`` (the interpret-mode kernel is a correctness tool);
        an explicit ``"pallas"`` is honored anywhere (interpret mode
        off-TPU).  The resolved choice is ``self.attention_impl``,
        the measurement (when taken) ``self.attention_impl_us``.  Of a
        latent-attention model it selects the indexer's scan of a slot's
        index keys: ``"pallas"`` = ``paged_index_scores`` (live pages
        only), ``"xla"`` = a gather of the whole table (the off-chip
        harness); ``"auto"`` = the kernel on a TPU, unmeasured.

        ``prefix_sharing=False`` (paged pools only; ignored dense)
        disables copy-on-write prefix-block sharing: every admission
        gets fresh blocks and nothing is committed to the prefix
        index — the control arm of the COW golden-equivalence suite
        and the escape hatch if sharing ever misbehaves in prod."""
        self.cfg = cfg
        self.int8 = int8
        self.chunk = int(chunk)
        self.spec_auto = speculative_k == "auto"
        self.speculative_k = 8 if self.spec_auto else int(speculative_k)
        if self.speculative_k == 1 or self.speculative_k < 0:
            raise ValueError(
                f"speculative_k={self.speculative_k} is invalid: use 0 "
                "to disable, >= 2 to speculate, or 'auto'"
            )
        self.spec_accept_floor = float(spec_accept_floor)
        # speculation state machine: "on" = verify rounds; "watching" =
        # chunk decode + free draft-hit-rate probe (auto mode's start);
        # "backoff" = chunk decode for _spec_cooldown rounds after
        # measured low acceptance, then back to on/watching
        self._spec_state = "watching" if self.spec_auto else "on"
        self._spec_cooldown = 0
        self._spec_window: deque = deque(maxlen=32)
        self._draft_hits: deque = deque(maxlen=32)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.eos_token = eos_token
        self.max_len = int(max_len or cfg.max_seq_len)
        assert self.max_len <= cfg.max_seq_len
        if prefill_buckets is None:
            b, buckets = 32, []
            while b < self.max_len:
                buckets.append(b)
                b *= 2
            buckets.append(self.max_len)
            prefill_buckets = tuple(buckets)
        self.buckets = tuple(sorted(prefill_buckets))
        self.max_slots = int(max_slots)
        # ``mesh``: tensor-parallel serving — params/cache placed with
        # Megatron-style col/row shardings (params.shard_serving_state),
        # jit propagates them and GSPMD inserts the collectives.  Needs
        # the unfused projection layout (fused [q|k|v] columns would
        # shard head-incorrectly).
        self.mesh = mesh
        # layers that keep a recurrent state (serving/linear.py: linear
        # attention, "kda", a state-space scan, "ssm", or power retention,
        # "retention"): a state a SLOT, not rows in blocks, of ONE kind a
        # model.  What cannot be right yet is refused by what is missing,
        # not served wrongly
        kinds = sorted({s.mixer for s in cfg.layer_specs
                        if s.mixer != "attn"})
        self._state_kind = kinds[0] if kinds else None
        self._state_layers = sum(s.mixer != "attn" for s in cfg.layer_specs)
        if "conv" in kinds:
            raise ValueError(
                "layers whose mixer is a gated short convolution "
                "(LayerSpec.mixer='conv') are trained, not served.  "
                "Missing: a kind in serving/linear.py state_shapes (the one "
                "place that says which arrays a kind keeps a slot) that "
                "keeps a convolution's rows and NO float32 state, and its "
                "steps in serving/latent.py::_state_mixer (ROADMAP Reach A4)")
        if kinds:
            name = {"kda": "linear-attention", "ssm": "state-space",
                    "retention": "power-retention"}.get(kinds[0], kinds[0])
            if len(kinds) > 1:
                raise ValueError(
                    f"layers of {kinds} in one model: a slot's recurrent "
                    "state is of one kind (serving/linear.py state_shapes)")
            if prefix_sharing:
                raise ValueError(
                    f"prefix_sharing=True with {name} layers: a "
                    "warm start behind a shared prefix needs the recurrent "
                    "state at the prefix's end, and nothing keeps it.  "
                    "Missing: a snapshot of recurrent state at a shared "
                    "prefix's end in serving/prefixcache/ (ROADMAP Reach "
                    "A6); pass prefix_sharing=False")
            if self.speculative_k:
                raise ValueError(
                    f"speculative_k={speculative_k!r} with {name} "
                    "layers: a rejected draft has already advanced the "
                    "state.  Missing: roll-back of recurrent state under "
                    "drafts (ROADMAP Reach A6); pass speculative_k=0")
            if not prefill_chunk:
                raise ValueError(
                    f"{name} layers take their prompts in chunks "
                    "(the state is carried from one to the next): pass "
                    "prefill_chunk > 0")
            if mesh is not None:
                raise ValueError(
                    f"a mesh with {name} layers: a slot's state "
                    "is kept whole on one device.  Missing: the recurrent "
                    "state and its kernels sharded over heads (ROADMAP "
                    "Reach A6)")
        # window layers (serving/latent.py _window_layer): a ring a slot,
        # not rows in blocks
        windows = sorted({s.window for s in cfg.layer_specs
                          if s.mixer == "attn" and s.window})
        self._window_layers = sum(
            bool(s.mixer == "attn" and s.window) for s in cfg.layer_specs)
        if windows:
            if len(windows) > 1:
                raise ValueError(
                    f"window layers of {windows} keys in one model: the "
                    "rings of a slot have one geometry (serving/paged.py "
                    "ring_geometry)")
            if self.speculative_k:
                raise ValueError(
                    f"speculative_k={speculative_k!r} with window layers: "
                    "a rejected draft's rows have already overwritten the "
                    "ring's oldest.  Missing: a window under drafts "
                    "(ROADMAP Reach A4); pass speculative_k=0")
            if not prefill_chunk:
                raise ValueError(
                    "window layers take their prompts in chunks (a ring "
                    "holds one chunk and the window behind it): pass "
                    "prefill_chunk > 0")
            if mesh is not None:
                raise ValueError(
                    "a mesh with window layers: a slot's ring is kept "
                    "whole on one device.  Missing: the rings sharded "
                    "with the latent pools (ROADMAP Reach A4)")
        # every prompt goes through the chunked path: no bucketed prefill
        # (nor has the loop of layer kinds one behind its grouped-query
        # block: serving/latent.py prefill)
        self._chunked_only = bool(
            self._state_layers or windows
            or (cfg.layer_kinds and not cfg.kv_lora_rank))
        if self._chunked_only and not prefill_chunk:
            raise ValueError(
                "a grouped-query model with sparse experts or multipliers "
                "is served by the loop of layer kinds (serving/latent.py), "
                "which takes its prompts in chunks: pass prefill_chunk > 0")
        self.params = serving_params_from_llama(
            variables, cfg, int8=int8, fuse=mesh is None)
        # speculative slack: a verify near the end of a sequence writes
        # up to K-1 entries past its last real position; without the
        # extra rows dynamic_update_slice would CLAMP the start and
        # silently overwrite earlier (live) cache entries
        cache_len = self.max_len + max(0, self.speculative_k)
        self.prefill_chunk = int(prefill_chunk or 0)
        if self.prefill_chunk < 0:
            raise ValueError("prefill_chunk must be >= 0 (0 disables)")
        self._park_pos = 0
        if self.prefill_chunk:
            # PARK-ROW slack: while a slot prefills in chunks, the
            # decode/verify dispatches of the OTHER slots still compute
            # (and write) junk K/V for it at its frozen position.  Real
            # writes never pass cache_len-1, so parking the slot at
            # position `cache_len` and growing the cache by max(1, K)
            # rows keeps every junk write — including the dense verify
            # path's K-row block write (dynamic_update_slice clamps its
            # start to cache_len-K, which the slack makes == park) —
            # inside rows no live query's `key <= pos` mask can see.
            # Paged twin: park positions map to columns past the
            # allocation, which paged._block_offsets routes to the
            # trash sink.
            self._park_pos = cache_len
            cache_len += max(1, self.speculative_k)
        self.paged = bool(paged)
        if kv_dtype in (None, "bf16"):
            self.kv_dtype = None
        elif kv_dtype == "int8":
            if not self.paged:
                raise ValueError(
                    f"kv_dtype={kv_dtype!r} is a paged-pool feature "
                    "(per-block-scale quantized K/V pools); pass "
                    "paged=True")
            self.kv_dtype = kv_dtype
        else:
            raise ValueError(
                f"kv_dtype={kv_dtype!r} not supported: use None/'bf16' "
                "(native) or 'int8'")
        self.kv_budget_x = 1.0
        # latent attention (serving/latent.py): rows without a head axis
        # in a latent pool and, of a model with an indexer, an index-key
        # pool
        self._latent = bool(cfg.kv_lora_rank)
        # the loop of layer kinds (``LlamaConfig.layer_kinds``): latent
        # attention, or grouped-query layers beside a state a slot or
        # sparse MLPs; its programs count the experts' picks and keep a
        # witness
        self._kinds = bool(cfg.layer_kinds)
        # ... and of those a model with NO layer that caches rows (every
        # layer keeps a state a slot): no pool, no block table in any
        # program, nothing for ``cache_blocks`` to size or for a placement
        # ledger to charge.  Admission is by slots
        self.rowless = self._kinds and not any(
            s.mixer == "attn" for s in cfg.layer_specs)
        if self.rowless:
            self.paged = False
        if self._kinds and not (
                (self.paged or self.rowless) and self.kv_dtype is None
                and mesh is None and not int8):
            raise ValueError(
                "a latent-attention model, and any model of layer kinds "
                "(a recurrent state a slot, sparse experts), is served "
                "from paged pools in "
                "the model's dtype on one device: pass paged=True, no "
                "kv_dtype, no mesh, int8=False")
        if self.rowless:
            self._cache = {"watch_slot": jnp.asarray(-1, jnp.int32)}
            if cfg.num_experts:
                self._cache["moe_picks"] = jnp.zeros(4, jnp.uint32)
            self._cache.update(self._slot_states())
        elif self.paged:
            # block-pool cache (serving/paged.py): per-sequence memory
            # scales with ACTUAL lengths, concurrency is bounded by the
            # pool (HBM budget) instead of slots x max_len reservations,
            # and common prompt prefixes share blocks
            from dlrover_tpu.serving.paged import (
                BlockManager,
                kv_budget_multiplier,
            )

            self.block_size = int(block_size)
            self._max_blocks = -(-cache_len // self.block_size)
            # THE budget function — the same source the regression
            # test pins the router ledger to (serving/paged.py)
            if not self._latent:
                self.kv_budget_x = kv_budget_multiplier(
                    cfg.dtype, cfg.head_dim_, self.kv_dtype)
            # +1: block 0 is the trash sink (never allocated), so the
            # default must still let every slot hold a full-length
            # sequence.  An EXPLICIT cache_blocks is an HBM budget
            # denominated in native-dtype blocks — int8 pools multiply
            # it by kv_budget_x, which is the whole point of the knob
            # (same bytes, ~2x the blocks, ~2x the continuous batch).
            if cache_blocks:
                n_blocks = int(int(cache_blocks) * self.kv_budget_x)
            else:
                n_blocks = self.max_slots * self._max_blocks + 1
            store = None
            if windows:
                from dlrover_tpu.serving.paged import (WindowStore,
                                                       ring_geometry)

                store = WindowStore(
                    ring_geometry(windows[0], int(prefill_chunk),
                                  self.block_size), self.max_slots,
                    bool(prefix_sharing))
            self._blockmgr = BlockManager(
                n_blocks, self.block_size,
                sharing=bool(prefix_sharing), windows=store)
            self._slot_blocks: List[Optional[List[int]]] = (
                [None] * self.max_slots
            )
            self._table_dirty = False
            self._table_np = np.zeros(
                (self.max_slots, self._max_blocks), np.int32
            )
            kvd = (n_blocks, self.block_size,
                   cfg.num_kv_heads, cfg.head_dim_)
            if self._kinds:
                from dlrover_tpu.serving.latent import latent_row_width

                shape = (n_blocks, self.block_size)
                paged_specs = [s for s in cfg.layer_specs
                               if s.mixer == "attn" and not s.window]
                # one pool (K and V: two) an ATTENTION layer that sees
                # every key: a layer that keeps a state a slot keeps no
                # rows, a window layer a ring a slot
                pools = {"latent_pool": [
                    jnp.zeros(shape + (latent_row_width(cfg, s),),
                              cfg.dtype) for s in paged_specs]} \
                    if self._latent else {
                        name: [jnp.zeros(kvd, cfg.dtype) for _ in paged_specs]
                        for name in ("k_pool", "v_pool")}
                self._cache = {
                    **pools,
                    "table": jnp.asarray(self._table_np),
                    # the slot whose forward the programs hand back
                    # (``watch``); -1: none
                    "watch_slot": jnp.asarray(-1, jnp.int32),
                }
                if cfg.index_topk:
                    # an index key a token beside the layer's rows, of a
                    # latent layer or a grouped-query one: under the one
                    # block table, shared and copied with its blocks
                    from dlrover_tpu.serving.latent import index_row_width

                    self._cache["index_pool"] = [
                        jnp.zeros(shape + (index_row_width(cfg),), cfg.dtype)
                        for s in paged_specs if cfg.latent_dims(s)[2]]
                if store is not None:
                    g = store.geometry
                    wide = [latent_row_width(cfg, s) for s in cfg.layer_specs
                            if s.mixer == "attn" and s.window]
                    self._cache["window_ring"] = [
                        jnp.zeros((self.max_slots, g.ring, self.block_size,
                                   w), cfg.dtype) for w in wide]
                    self._cache["window_keep"] = [
                        jnp.zeros((store.snapshots, g.keep, self.block_size,
                                   w), cfg.dtype) for w in wide]
                if cfg.num_experts:
                    # [picks, picks on held experts, buffer walks, sparse
                    # layer-forwards], wrapping: the host adds differences
                    # (_book_moe_picks)
                    self._cache["moe_picks"] = jnp.zeros(4, jnp.uint32)
                self._cache.update(self._slot_states())
            elif self.kv_dtype == "int8":
                from dlrover_tpu.models.quantize import KV_SCALE_DTYPE

                self._cache = {
                    "k_pool": [jnp.zeros(kvd, jnp.int8)
                               for _ in range(cfg.num_layers)],
                    "v_pool": [jnp.zeros(kvd, jnp.int8)
                               for _ in range(cfg.num_layers)],
                    "k_scale": [jnp.zeros(kvd[:3], KV_SCALE_DTYPE)
                                for _ in range(cfg.num_layers)],
                    "v_scale": [jnp.zeros(kvd[:3], KV_SCALE_DTYPE)
                                for _ in range(cfg.num_layers)],
                    "table": jnp.asarray(self._table_np),
                }
            else:
                self._cache = {
                    "k_pool": [jnp.zeros(kvd, cfg.dtype)
                               for _ in range(cfg.num_layers)],
                    "v_pool": [jnp.zeros(kvd, cfg.dtype)
                               for _ in range(cfg.num_layers)],
                    "table": jnp.asarray(self._table_np),
                }
        else:
            kvd = (self.max_slots, cache_len,
                   cfg.num_kv_heads, cfg.head_dim_)
            # per-layer buffers (a pytree of lists): donated in place by
            # the decode chunk, no stacked-cache copies
            self._cache = {
                "k": [jnp.zeros(kvd, cfg.dtype)
                      for _ in range(cfg.num_layers)],
                "v": [jnp.zeros(kvd, cfg.dtype)
                      for _ in range(cfg.num_layers)],
            }
        if mesh is not None:
            from dlrover_tpu.serving.params import shard_serving_state

            self.params, self._cache = shard_serving_state(
                self.params, self._cache, mesh, cfg)
        self._rng = jax.random.PRNGKey(seed)
        self._cache_len = cache_len
        # host-side slot state
        self._slot_req: List[Optional[Request]] = [None] * self.max_slots
        # chunked-prefill cursors: _prefilling marks slots holding a
        # request whose prompt is still being written chunk-by-chunk
        # (excluded from decode); _prefill_pos is the real_len cursor —
        # how many prompt tokens are already in the cache — surviving
        # across dispatches.  All prefilling slots advance one chunk
        # per step in ONE batched dispatch (_advance_prefill), so the
        # stall bound holds AND concurrent long prompts don't
        # serialize each other's TTFT
        self._prefilling = np.zeros(self.max_slots, bool)
        self._prefill_pos = np.zeros(self.max_slots, np.int32)
        # per-slot incrementally-filled context (prompt + committed
        # tokens) for the speculative draft lookup — rebuilding it from
        # the output list every round would be O(n^2) per request.
        # +1 column: a full-length prompt with max_new_tokens=0 still
        # receives its one prefill token at index max_len
        self._ctx_buf = np.zeros(
            (self.max_slots, self.max_len + 1), np.int32)
        self._ctx_len = np.zeros(self.max_slots, np.int32)
        self._positions = np.zeros(self.max_slots, np.int32)
        self._tokens = np.zeros(self.max_slots, np.int32)
        # ``_tokens`` as the programs hand it on to each other on the
        # device: a CACHE of the host's copy, which is whole again once
        # everything in flight is read.  None = not valid (whatever
        # writes ``_tokens`` outside that chain reads everything first,
        # then drops it): the next dispatch uploads the host's
        self._last_dev: Optional[jax.Array] = None
        # what has been dispatched and not read, in dispatch order (between
        # steps: the last step's decode chunk), and how many programs
        # that is
        self._unread: List[_Unread] = []
        self._in_flight = 0
        # whether the oldest program in flight was dispatched with nothing
        # ahead of it (every other one is chained), and the host clock at
        # which the last result read reached the host
        self._head_alone = False
        self._reached = 0.0
        self._remaining = np.zeros(self.max_slots, np.int32)
        self._queue: deque[Request] = deque()
        self._finished: List[Request] = []
        self._next_rid = 0
        self.stats = EngineStats()
        self._moe_picks_seen = np.zeros(4, np.uint32)
        self._watch = None                 # ``watch``'s predicate
        self._watch_slot = -1
        self.witness_log: List[Dict[str, Any]] = []
        # slots one prefill-chunk dispatch advances: all that prefill,
        # or of a model of layer kinds one (its attention walks a row's
        # live key blocks one row after another anyway, and one group size
        # is one program to compile, not max_slots)
        self._prefill_group = 1 if self._kinds else self.max_slots
        # names of the per-layer pool lists a bucketed prefill's results
        # are scattered into (a latent model's) and a diverging sequence's
        # blocks are copied in (every model's)
        self._pool_names = (("latent_pool",) if self._latent
                            else ("k_pool", "v_pool")) + (
            ("index_pool",) if self._kinds and cfg.index_topk else ())
        # paged decode attention: gather (xla) vs fused kernel
        # (pallas), resolved ONCE at build — "auto" measures both on
        # this engine's real pool geometry and picks the faster
        # (resolve_attention_impl is the pure, tested decision)
        self.attention_impl_requested = str(attention_impl)
        from dlrover_tpu.ops.pallas import interpret_off_chip

        self._kernel_interpret = interpret_off_chip()
        self.attention_impl_why = ""
        self.attention_impl, self.attention_impl_us = \
            self._resolve_attention()
        self._build_programs()

    def _slot_states(self) -> Dict[str, List[jax.Array]]:
        """``<kind>_<name>``: what the layers that keep a state a slot
        hold, a list over those layers of each array ``serving/linear.py
        state_shapes`` names: indexed by SLOT, donated through every
        program like the pools, zeroed inside the program that takes a
        slot's first chunk ({} for a model with no such layer)."""
        if not self._state_layers:
            return {}
        from dlrover_tpu.serving.linear import state_shapes

        kept = state_shapes(self.cfg, self.max_slots, self._state_kind)
        # a slot's recurrent arrays of one layer at the bytes they are kept
        # in (power retention's sum of keys beside its state; a
        # convolution's rows are not counted): ``_book_state_bytes``
        self._state_slot_bytes = sum(
            int(np.prod(held.shape)) * 4 // self.max_slots
            for name, held in kept.items() if name != "conv")
        return {
            self._state_kind + "_" + name: [
                jnp.zeros(held.shape, held.dtype)
                for _ in range(self._state_layers)]
            for name, held in kept.items()}

    # ----------------------------------------------- attention impl
    def _resolve_attention(self):
        from dlrover_tpu.ops.pallas.paged_attention import (
            resolve_attention_impl,
        )

        req = self.attention_impl_requested
        if req not in ("auto", "xla", "pallas"):
            raise ValueError(
                f"attention_impl={req!r} not supported: use 'auto', "
                "'xla' or 'pallas'")
        if not self.paged and not self.rowless:
            if req == "pallas":
                raise ValueError(
                    "attention_impl='pallas' reads paged block pools "
                    "in place; pass paged=True")
            return "xla", None
        if req in ("xla", "pallas"):
            self.attention_impl_why = "requested"
            return req, None
        if self._kinds and not self._kernel_interpret:
            self.attention_impl_why = (
                "auto: the decode kernels read live pages only, the gather "
                "the whole table; not measured")
            return "pallas", None
        if self._kernel_interpret:
            # no TPU: the interpret-mode kernel is a parity harness,
            # not a perf candidate — auto must not "measure" it
            self.attention_impl_why = (
                "auto off-chip: the interpret-mode kernel is not timed")
            return "xla", None
        timings = self._measure_attention()
        self.attention_impl_why = "auto: measured, faster impl kept"
        # stored in MICROseconds to match the attribute name (the
        # measurement itself is perf_counter seconds)
        return resolve_attention_impl("auto", timings), {
            k: v * 1e6 for k, v in timings.items()}

    def _measure_attention(self):
        """One-shot timing of both paged attention impls on THIS
        engine's pools at worst-case context (every table column
        live): the evidence behind the auto-pick, kept on the engine
        (``attention_impl_us``) so a worker's report can print it.  The
        kernel's time follows the lengths it is handed, the gather's
        does not: at FULL lengths this is the kernel's slowest case,
        so a pick of the kernel holds at every shorter context, and a
        pick of the gather is a worst-case decision, not a typical
        one."""
        from dlrover_tpu.ops.pallas.paged_attention import (
            measure_paged_attention,
        )

        cfg = self.cfg
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(
            key, (self.max_slots, cfg.num_heads, cfg.head_dim_),
            jnp.float32).astype(cfg.dtype)
        nb = self._blockmgr.num_blocks
        mb = self._max_blocks
        table = jnp.asarray(
            (np.arange(self.max_slots * mb) % max(1, nb - 1) + 1)
            .reshape(self.max_slots, mb).astype(np.int32))
        lengths = jnp.full(
            (self.max_slots,), min(self._cache_len, mb * self.block_size),
            jnp.int32)
        kw = {}
        if self.kv_dtype == "int8":
            kw = dict(k_scale=self._cache["k_scale"][0],
                      v_scale=self._cache["v_scale"][0])
        return measure_paged_attention(
            q, self._cache["k_pool"][0], self._cache["v_pool"][0],
            table, lengths, interpret=self._kernel_interpret, **kw)

    # ------------------------------------------------------------ jit
    def _build_programs(self) -> None:
        cfg = self.cfg
        temperature, top_k, top_p = self.temperature, self.top_k, self.top_p
        n_steps = self.chunk

        impl = self.attention_impl
        kernel_interpret = self._kernel_interpret

        @functools.partial(jax.jit, donate_argnums=(1,))
        def chunk_fn(params, cache, tokens, positions, active, rng):
            def step(carry, _):
                toks, pos, cache, key = carry
                logits, cache = decode_step(
                    params, cfg, cache, toks, pos,
                    attention_impl=impl,
                    kernel_interpret=kernel_interpret,
                    active=active)
                cache = dict(cache)
                seen = cache.pop("witness", None)
                key, sub = jax.random.split(key)
                with device_scope("pick"):
                    nxt = select_token(
                        logits, sub, temperature, top_k, top_p)
                toks = jnp.where(active, nxt.astype(toks.dtype), toks)
                pos = jnp.where(active, pos + 1, pos)
                return (toks, pos, cache, key), (nxt, seen)

            with device_scope("decode_chunk"):
                (tokens, positions, cache, rng), (out, witness) = \
                    jax.lax.scan(
                        step, (tokens, positions, cache, rng), None,
                        length=n_steps,
                    )
            # ``witness``: the watched slot's forwards, one a step (None
            # for a model that keeps none: ``watch``).  Last, the experts'
            # pick counters a second time, outside the donated cache (None
            # for a model that counts none): the host reads them with this
            # chunk's tokens, while the cache's own is the next program's
            return (out.T, tokens, positions, cache, rng, witness,
                    cache.get("moe_picks"))

        paged = self.paged
        pool_names = self._pool_names
        kv_quant = self.kv_dtype == "int8"

        @functools.partial(jax.jit, donate_argnums=(1,))
        def insert_fn(params, cache, tokens, real_len, slots, skip, rng,
                      last):
            """Prefill a GROUP of same-bucket prompts ([G, Lp]) and
            scatter their K/V into cache slots ``slots`` [G] in one
            dispatch (jit caches one program per (G, bucket) pair).
            ``last`` [max_slots] is every slot's last token, the vector
            the decode chunk carries: it comes back with the sampled
            first tokens at ``slots``, so the chunk that follows needs no
            host in between.
            ``skip`` [G] is the per-row shared-prefix length: those
            leading positions live in SHARED (read-only) prefix blocks
            already holding the first writer's K/V, so their writes
            route to the trash sink (paged COW contract) — the prefill
            COMPUTE still covers them (logits need the full prompt),
            only the cache write is masked.  Traced, so one program
            serves every skip value; the dense layout has no sharing
            and ignores it."""
            lp = tokens.shape[1]
            with device_scope("prefill"):
                logits, ks, vs = prefill(params, cfg, tokens, real_len)
            if paged and kv_quant:
                from dlrover_tpu.serving.paged import scatter_tokens_q

                rows = jnp.take(cache["table"], slots, axis=0)  # [G, MB]
                zero = jnp.zeros(slots.shape, jnp.int32)
                kp, ksc, vp, vsc = [], [], [], []
                for p, sp, k in zip(cache["k_pool"], cache["k_scale"],
                                    ks):
                    np_, ns_ = scatter_tokens_q(
                        p, sp, rows, k, zero, skip)
                    kp.append(np_)
                    ksc.append(ns_)
                for p, sp, v in zip(cache["v_pool"], cache["v_scale"],
                                    vs):
                    np_, ns_ = scatter_tokens_q(
                        p, sp, rows, v, zero, skip)
                    vp.append(np_)
                    vsc.append(ns_)
                new_cache = dict(cache, k_pool=kp, k_scale=ksc,
                                 v_pool=vp, v_scale=vsc)
            elif paged:
                from dlrover_tpu.serving.paged import scatter_tokens

                rows = jnp.take(cache["table"], slots, axis=0)  # [G, MB]
                zero = jnp.zeros(slots.shape, jnp.int32)
                new_cache = dict(cache, **{
                    name: [
                        scatter_tokens(p, rows, x.astype(p.dtype),
                                       zero, skip)
                        for p, x in zip(cache[name], new)
                    ] for name, new in zip(pool_names, (ks, vs))})
            else:
                new_cache = {
                    "k": [
                        ck.at[slots, :lp].set(k.astype(ck.dtype))
                        for ck, k in zip(cache["k"], ks)
                    ],
                    "v": [
                        cv.at[slots, :lp].set(v.astype(cv.dtype))
                        for cv, v in zip(cache["v"], vs)
                    ],
                }
            rng, sub = jax.random.split(rng)
            with device_scope("pick"):
                first = select_token(
                    logits, sub, temperature, top_k, top_p)
            return new_cache, first, rng, last.at[slots].set(
                first.astype(last.dtype))

        self._chunk_fn = chunk_fn
        self._insert_fn = insert_fn

        self._prefill_chunk_fn = None
        if self.prefill_chunk:
            from dlrover_tpu.serving.model import verify_step

            @functools.partial(jax.jit, donate_argnums=(1,))
            def prefill_chunk_fn(params, cache, tokens, start, slots,
                                 last_idx, rng, last, final):
                """ONE bounded prompt chunk for slot subset ``slots``:
                a draft-free verify run attending to what previous
                chunks cached (one compile — the chunk shape is fixed
                at [G, prefill_chunk]).  ``last_idx`` picks the single
                position whose logits feed sampling; the sampled token
                counts only for a row on its FINAL chunk (``final`` [G],
                a host fact): ``last`` (``insert_fn``) comes back with
                it at that row's slot, and with the parked slot's 0 at
                the others."""
                with device_scope("prefill_chunk"):
                    logits, cache = verify_step(
                        params, cfg, cache, tokens, start,
                        slots=slots, logits_index=last_idx,
                        attention_impl=impl,
                        kernel_interpret=kernel_interpret)
                cache = dict(cache)
                witness = cache.pop("witness", None)
                rng, sub = jax.random.split(rng)
                with device_scope("pick"):
                    first = select_token(
                        logits[:, 0, :], sub, temperature, top_k, top_p)
                last = last.at[slots].set(
                    jnp.where(final, first.astype(last.dtype), 0))
                return (cache, first, rng, witness, last,
                        cache.get("moe_picks"))

            self._prefill_chunk_fn = prefill_chunk_fn

        self._window_keep_fn = self._window_restore_fn = None
        if self._window_layers:
            def window_copy(cache, slot, entry, blocks, restore):
                """Every window layer's rows of ONE prefix boundary between
                the ring of ``slot`` and the snapshot ``entry``: ``blocks``
                [keep] names the ring's blocks in position order
                (``WindowStore.ring_blocks``; behind the last, the ring's
                size: read as any block, written nowhere).  ``restore``:
                snapshot -> ring, else ring -> snapshot."""
                rings, keeps = [], []
                for ring, keep in zip(cache["window_ring"],
                                      cache["window_keep"]):
                    if restore:
                        ring = ring.at[slot, blocks].set(
                            jnp.take(keep, entry, axis=0), mode="drop")
                    else:
                        keep = keep.at[entry].set(jnp.take(
                            jnp.take(ring, slot, axis=0),
                            jnp.minimum(blocks, ring.shape[1] - 1), axis=0))
                    rings.append(ring)
                    keeps.append(keep)
                return dict(cache, window_ring=rings, window_keep=keeps)

            self._window_keep_fn, self._window_restore_fn = (
                jax.jit(functools.partial(window_copy, restore=restore),
                        donate_argnums=(0,)) for restore in (False, True))

        self._spec_fn = None
        if self.speculative_k > 1:
            from dlrover_tpu.serving.model import verify_step
            from dlrover_tpu.serving.speculative import rejection_commit

            @functools.partial(jax.jit, donate_argnums=(1,))
            def spec_fn(params, cache, tokens, positions, draft_len,
                        rng):
                with device_scope("verify"):
                    logits, cache = verify_step(
                        params, cfg, cache, tokens, positions,
                        attention_impl=impl,
                        kernel_interpret=kernel_interpret)
                cache = dict(cache)
                cache.pop("witness", None)
                rng, sub = jax.random.split(rng)
                with device_scope("pick"):
                    out, n_commit = rejection_commit(
                        logits, tokens[:, 1:], draft_len, sub,
                        temperature=temperature, top_k=top_k, top_p=top_p,
                    )
                return out, n_commit, cache, rng

            self._spec_fn = spec_fn

    def warmup(self) -> int:
        """Compile, by running each once on an IDLE engine, every
        program this engine can dispatch: the decode chunk, the
        speculative verify, and at every admission group size the
        chunked-prefill program and the bucketed prefill of each bucket
        ``_admit`` sends there (with chunked prefill on, buckets above
        ``prefill_chunk`` never reach it).  A serving worker calls this
        BEFORE it announces its address, so no request ever waits on a
        compile behind a liveness window sized for steady-state steps.
        Nothing live is touched: an idle paged engine's table rows are
        all zero, which routes every write to the trash block (the
        dense layout's junk lands in rows the next admission
        overwrites or masks), no slot is active, and the sampling key
        is left as it was.  Each program is registered as it is run
        (``utils/profiler.register_program``: ``decode_chunk``,
        ``verify``, ``prefill_chunk.g<G>``, ``prefill.g<G>.b<bucket>``),
        so a trace of this engine can be read by the program's own
        device scopes.  Returns the number of programs run."""
        assert not self.has_work, "warmup needs an idle engine"
        rng, b = self._rng, self.max_slots

        def zeros(*shape):
            return jnp.zeros(shape, jnp.int32)

        def run(label, fn, *args):
            # the program's text on demand (``program_scopes``), over the
            # arguments' shapes: no weight or pool is kept for it
            shapes = abstract(args)
            register_program(label, lambda: program_texts(fn, *shapes))
            return fn(*args)

        _, _, _, self._cache, _, _, _ = run(
            "decode_chunk", self._chunk_fn,
            self.params, self._cache, zeros(b), zeros(b),
            jnp.zeros(b, bool), rng)
        ran = 1
        if self._spec_fn is not None:
            _, _, self._cache, _ = run(
                "verify", self._spec_fn,
                self.params, self._cache, zeros(b, self.speculative_k),
                zeros(b), zeros(b), rng)
            ran += 1
        chunked = self._prefill_chunk_fn is not None
        buckets = [n for n in self.buckets
                   if (not chunked or n <= self.prefill_chunk)
                   and not self._chunked_only]
        for g in range(1, b + 1):
            slots = jnp.arange(g, dtype=jnp.int32)
            if chunked and g <= self._prefill_group:
                self._cache, _, _, _, _, _ = run(
                    f"prefill_chunk.g{g}", self._prefill_chunk_fn,
                    self.params, self._cache,
                    zeros(g, self.prefill_chunk), zeros(g), slots,
                    zeros(g), rng, zeros(b), jnp.zeros(g, bool))
                ran += 1
            for bucket in buckets:
                self._cache, _, _, _ = run(
                    f"prefill.g{g}.b{bucket}", self._insert_fn,
                    self.params, self._cache, zeros(g, bucket),
                    jnp.ones(g, jnp.int32), slots, zeros(g), rng,
                    zeros(b))
                ran += 1
        store = self._blockmgr.windows if self.paged else None
        if store is not None and store.snapshots:
            # (an idle engine: slot 0's ring and snapshot 0 are nobody's)
            for label, fn in (("window_keep", self._window_keep_fn),
                              ("window_restore", self._window_restore_fn)):
                self._cache = run(
                    label, fn, self._cache, jnp.asarray(0, jnp.int32),
                    jnp.asarray(0, jnp.int32),
                    jnp.asarray(store.ring_blocks(0)))
                ran += 1
        jax.block_until_ready(self._cache)
        return ran

    # ------------------------------------------------------- requests
    def add_request(self, prompt_ids, max_new_tokens: int) -> int:
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        assert prompt.size >= 1
        total = prompt.size + int(max_new_tokens)
        if total > self.max_len:
            raise ValueError(
                f"prompt {prompt.size} + max_new {max_new_tokens} "
                f"exceeds engine max_len {self.max_len}")
        if self.paged:
            # fail fast on a request the pool can NEVER hold (waiting
            # in the queue would spin run() forever)
            worst = max(
                total + max(0, self.speculative_k),
                _bucket(prompt.size, self.buckets),
            )
            need = -(-worst // self.block_size)
            if need > self._blockmgr.num_blocks - 1:
                raise ValueError(
                    f"request needs {need} cache blocks but the pool "
                    f"holds {self._blockmgr.num_blocks - 1} usable "
                    "(cache_blocks too small for this request)")
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append(Request(rid, prompt, int(max_new_tokens),
                                   queued_at=time.monotonic()))
        return rid

    def _admit(self) -> None:
        """Admit waiting requests and read their first tokens (and
        whatever else is in flight): for a caller outside ``step``, which
        dispatches more before it reads."""
        self._dispatch_admissions()
        self._read_results()

    @spanned("dlrover.engine.admit")
    def _dispatch_admissions(self) -> None:
        """Admit waiting requests into free slots.  Consecutive queue
        entries whose prompts land in the SAME length bucket prefill as
        one batched dispatch — at G admissions per dispatch this cuts
        the prefill launch count up to G-fold (the vLLM-style batched
        prefill; on this rig dispatch latency dominates prefill, so the
        cut is a direct wall-clock win).

        Nothing here waits for the device.  What the host decides needs
        no sampled token: slot, blocks, position and budget are booked
        at dispatch, and the first tokens stay on the device (in the
        ``last`` vector for the programs behind, in an ``_Unread`` for
        ``_read_results``).  A slot whose first token ends its request
        is therefore held until the step's reads, not refilled within
        this call; one that the step before's decode chunk finished by
        count is free already, its last tokens still on the device
        (``_dispatch_decode``)."""
        while self._queue:
            free = [
                s for s in range(self.max_slots)
                if self._slot_req[s] is None
            ]
            if not free:
                return
            bucket = _bucket(self._queue[0].prompt.size, self.buckets)
            if self._prefill_chunk_fn is not None and (
                    bucket > self.prefill_chunk or self._chunked_only):
                # (a state or a ring a slot is carried chunk by chunk: a
                # model with linear-attention or window layers has no
                # bucketed prefill)
                if not self._admit_chunked(free[0]):
                    return  # pool exhausted: keep queued, keep order
                continue
            group: List[Request] = []
            allocs: List[Any] = []
            while (
                self._queue
                and len(group) < len(free)
                and _bucket(self._queue[0].prompt.size, self.buckets)
                == bucket
            ):
                if self.paged:
                    # a committed-prefix hit whose CHUNKED writer is
                    # still mid-prefill must wait — the content is not
                    # written yet.  (This group's own registrations are
                    # never pending: the insert dispatch below writes
                    # them before anything reads.)
                    if not self._blockmgr.shared_prefix_ready(
                            self._queue[0].prompt):
                        break
                    # capacity gate: blocks for the whole lifetime
                    # (bucket-padded prefill writes + gen + spec slack);
                    # pool exhaustion keeps the request QUEUED — that is
                    # the HBM-budget-bound admission paging exists for
                    alloc = self._alloc_lifetime(self._queue[0], bucket)
                    if alloc is None:
                        break
                    allocs.append(alloc)
                group.append(self._queue.popleft())
            if not group:
                return
            slots = free[: len(group)]
            if self.paged:
                for g, s in enumerate(slots):
                    self._bind_blocks(s, allocs[g][0])
                self._push_table()
            admitted = time.monotonic()
            padded = np.zeros((len(group), bucket), np.int32)
            lens = np.empty(len(group), np.int32)
            for g, req in enumerate(group):
                padded[g, : req.prompt.size] = req.prompt
                lens[g] = req.prompt.size
            # per-row shared-prefix length: positions below it are
            # mapped shared blocks whose K/V the first writer already
            # holds — insert_fn masks their cache writes
            skips = (np.asarray([a[1] for a in allocs], np.int32)
                     if self.paged
                     else np.zeros(len(group), np.int32))
            started = time.perf_counter()
            with self._dispatching("prefill"):
                self._cache, firsts, self._rng, self._last_dev = \
                    self._launch(
                        self._insert_fn,
                        self.params, self._cache, jnp.asarray(padded),
                        jnp.asarray(lens), jnp.asarray(slots, jnp.int32),
                        jnp.asarray(skips), self._rng, self._last_tokens(),
                    )
            self.stats.prefill_calls += 1
            self.stats.prefill_admissions += len(group)
            for s, req, skip in zip(slots, group, skips):
                self._slot_req[s] = req
                self._positions[s] = req.prompt.size
                self._remaining[s] = req.max_new_tokens - 1
                req.prompt_chunks = 1
                self._book_admission(s, req, admitted, int(skip), 1)
            self._unread.append(_Unread(
                "prefill", {"bucket": bucket, "n": len(group),
                            "erids": _erids(group)}, started,
                [firsts], self._deliver_firsts,
                [(s, req, g) for g, (s, req) in enumerate(
                    zip(slots, group))]))

    def _book_admission(self, s: int, req: Request, now: float,
                        cached: int, chunks: int) -> None:
        """``req`` has slot ``s`` and its blocks: its wait for them ended
        at ``now``, its prefill begins ``cached`` tokens into its prompt
        and takes ``chunks`` programs."""
        req.admitted_at = now
        req.cached_tokens = cached
        waited = now - req.queued_at
        st = self.stats
        st.slot_wait_seconds += waited
        st.prompt_tokens += req.prompt.size
        st.prompt_tokens_cached += cached
        event("dlrover.request.admitted", erid=req.rid, slot=s,
              slot_wait_ms=waited * 1e3,
              prompt_tokens=int(req.prompt.size), cached_tokens=cached,
              chunks=chunks)

    def _deliver_firsts(self, rows: List[Tuple[int, Request, int]],
                        firsts: List[np.ndarray], now: float,
                        program: str) -> None:
        """Hand prefill programs' first tokens (one array a program),
        read at ``now``, to their requests: ``rows`` names a slot, the
        request (which the step's decode dispatch may have taken off the
        slot already: a budget that its chunk spends) and its index in
        the tokens."""
        firsts = np.concatenate(firsts)
        for s, req, g in rows:
            first = int(firsts[g])
            req.output.append(first)
            req.first_token_at = req.last_token_at = now
            req.deliveries = 1
            wall = now - req.admitted_at
            self.stats.first_tokens += 1
            self.stats.prefill_wall_seconds += wall
            event("dlrover.request.first_token", erid=req.rid,
                  prefill_ms=wall * 1e3,
                  since_queued_ms=(now - req.queued_at) * 1e3,
                  steps=req.prompt_chunks)
            p = req.prompt.size
            self._ctx_buf[s, :p] = req.prompt
            self._ctx_buf[s, p] = first
            self._ctx_len[s] = p + 1
            self._tokens[s] = first
            if first == self.eos_token or (
                    self._slot_req[s] is req and self._remaining[s] <= 0):
                self._finish(s, req)
        self._say_delivered(program, len(rows), len(rows), _NO_GAPS)

    def _alloc_lifetime(self, req: Request, bucket: int):
        """ONE capacity formula for every admission path (batched AND
        chunked) — and it must stay in lockstep with the router's
        ``blocks_needed``: blocks for the request's whole lifetime,
        i.e. max(bucket-padded prefill writes, prompt + generation +
        speculative slack).  None = pool exhausted (caller keeps the
        request queued)."""
        total = max(
            req.prompt.size + req.max_new_tokens
            + max(0, self.speculative_k),
            bucket,
        )
        return self._blockmgr.alloc_sequence(req.prompt, total)

    def _bind_blocks(self, s: int, blocks: List[int]) -> None:
        """Point slot ``s``'s table row at its allocated blocks
        (zero-filled tail = the trash sink); the caller owns the
        host->device table push."""
        self._slot_blocks[s] = blocks
        self._table_np[s, : len(blocks)] = blocks
        self._table_np[s, len(blocks):] = 0

    def _admit_chunked(self, s: int) -> bool:
        """Admit the queue head into slot ``s`` for CHUNKED prefill:
        blocks for the whole lifetime are allocated now (same capacity
        formula as the router's ``blocks_needed``), but the prompt is
        written ``prefill_chunk`` tokens per step by
        :meth:`_advance_prefill`.  The slot is parked out of decode
        (``_prefilling``; position = the never-read park row) until
        the cursor reaches the prompt end.  False = pool exhausted,
        request stays queued."""
        req = self._queue[0]
        start = 0
        if self.paged:
            # never map (and warm-start past) committed blocks whose
            # writer has not finished writing them: wait in the queue
            # until the prefix is FILLED, then admit with a real hit
            if not self._blockmgr.shared_prefix_ready(req.prompt):
                return False
            alloc = self._alloc_lifetime(
                req, _bucket(req.prompt.size, self.buckets))
            if alloc is None:
                return False
            blocks, shared = alloc
            # blocks past the shared region were REGISTERED at alloc
            # but their content arrives one chunk per step: hold other
            # admissions off them until the cursor publishes each
            # (mark_filled in _advance_prefill)
            self._blockmgr.mark_pending(
                blocks[shared // self.block_size:
                       req.prompt.size // self.block_size])
            store = self._blockmgr.windows
            if shared:
                from dlrover_tpu.serving.paged import warm_start

                # warm start: shared blocks already hold the prefix's
                # K/V, so the cursor begins at the last chunk boundary
                # inside the shared region instead of 0 — the TTFT win
                # (the final chunk stays live: paged.warm_start)
                start = warm_start(shared, req.prompt.size,
                                   self.prefill_chunk)
                # the chunk program WRITES positions [start, ...), so
                # every shared block it overlaps must diverge first
                # (COW) — unlike batched prefill there is no write
                # mask here (verify_step's scatter covers the whole
                # chunk), so the contract is enforced by ownership
                src: List[int] = []
                dst: List[int] = []
                for j in range(start // self.block_size,
                               shared // self.block_size):
                    r = self._blockmgr.cow_block(blocks[j])
                    if r is None:
                        # pool exhausted mid-divergence: roll the whole
                        # admission back (cow_block already moved our
                        # reference into blocks[j] for completed
                        # copies, so one free_sequence balances it)
                        self._blockmgr.free_sequence(blocks)
                        return False
                    new_bid, copied = r
                    if copied:
                        src.append(blocks[j])
                        dst.append(new_bid)
                        blocks[j] = new_bid
                if src:
                    self._copy_blocks(src, dst)
            if store is not None and start:
                # the window layers share no block: the prefix's last
                # ``window - 1`` rows, kept when a prefill passed this
                # boundary, go into the slot's ring (held, or
                # ``alloc_sequence`` had shared nothing: the request then
                # starts COLD at 0 on blocks of its own, never wrong)
                kept = store.lookup(blocks[start // self.block_size - 1])
                self._cache = self._window_restore_fn(
                    self._cache, jnp.asarray(s, jnp.int32),
                    jnp.asarray(kept, jnp.int32),
                    jnp.asarray(store.ring_blocks(start)))
                self.stats.window_warm_starts += 1
            if store is not None:
                self.stats.window_cold_fallbacks = store.cold_starts
            self._bind_blocks(s, blocks)
            self._table_dirty = True
        self._queue.popleft()
        self._slot_req[s] = req
        self._watch_if_wanted(s, req)
        self._prefilling[s] = True
        self._prefill_pos[s] = start
        self._tokens[s] = 0
        self._positions[s] = self._park_pos
        self._remaining[s] = req.max_new_tokens
        self.stats.prefill_admissions += 1
        self._book_admission(
            s, req, time.monotonic(), start,
            -(-(req.prompt.size - start) // self.prefill_chunk))
        return True

    def _copy_blocks(self, src: List[int], dst: List[int]) -> None:
        """COW divergence copies: pool rows ``src[i] -> dst[i]`` across
        every layer's K/V pools (and scale pools when quantized), so
        the diverging sequence starts from the shared content it is
        about to overwrite the tail of."""
        si = jnp.asarray(src, jnp.int32)
        di = jnp.asarray(dst, jnp.int32)
        cache = dict(self._cache)
        for key in self._pool_names + ("k_scale", "v_scale"):
            pools = cache.get(key)
            if pools is not None:
                cache[key] = [p.at[di].set(p[si]) for p in pools]
        self._cache = cache

    def _advance_prefill(self) -> None:
        """One bounded prompt chunk for EVERY prefilling slot, batched
        into a single ``verify_step`` dispatch (the ``slots=`` subset
        machinery): rows are independent, so N concurrent long prompts
        advance together instead of round-robining one per step —
        which serialized their TTFTs N-fold while still paying one
        dispatch of latency each step.  The per-step budget that
        bounds every decoding slot's inter-token gap stays ONE chunk
        dispatch (jit caches one program per live group size, bounded
        by max_slots).  When a cursor reaches its prompt end, sample
        that row's first token and hand the slot to decode.

        Nothing here waits for the device: what a chunk's end means
        for its slot (cursor, published blocks, and on a prompt's last
        chunk the hand-over to decode at a position the host knows) is
        booked at dispatch, and the first tokens are read with the rest
        of the step (``_read_results``).

        A latent-attention model's step sends the same work as ONE
        DISPATCH A PREFILLING SLOT (``_prefill_group`` 1), one behind
        another: its attention walks a
        row's live key blocks row by row anyway, so a dispatch of g rows
        would take g times one row's (compute-bound at 512 queries
        against tens of thousands of keys), and one group size is one
        program to compile where 1 .. max_slots are max_slots.  So what
        bounds a decoding slot's gap there is not one dispatch but
        ``prefilling slots x one chunk``: up to max_slots chunks a step
        (5-13 of ~105 ms before each decode chunk in the benchmark's
        cell, PERF.md section 5).  Nothing caps the prefilling slots a
        step; a cap would trade throughput for that gap."""
        slots = [s for s in range(self.max_slots) if self._prefilling[s]]
        if not slots:
            return
        c = self.prefill_chunk
        g = len(slots)
        chunk = np.zeros((g, c), np.int32)
        starts = np.zeros(g, np.int32)
        last_idx = np.zeros(g, np.int32)
        ends = np.zeros(g, np.int32)
        final = np.zeros(g, bool)       # rows on their prompt's last chunk
        for i, s in enumerate(slots):
            req = self._slot_req[s]
            assert req is not None
            req.prompt_chunks += 1
            start = int(self._prefill_pos[s])
            end = min(start + c, req.prompt.size)
            chunk[i, : end - start] = req.prompt[start:end]
            starts[i] = start
            ends[i] = end
            final[i] = end == req.prompt.size
            # index (within the chunk) of the prompt's final token:
            # only meaningful on a row's final chunk; clamped junk
            # otherwise (that row's sampled token is discarded)
            last_idx[i] = max(0, min(end, req.prompt.size) - 1 - start)
        if self.paged and self._table_dirty:
            self._push_table()
        started = time.perf_counter()
        attrs = {"n": g,
                 "erids": _erids(self._slot_req[s] for s in slots),
                 **self._book_selection(starts, ends),
                 **self._book_key_blocks(starts, ends),
                 **self._book_state_chunks(starts, ends)}
        with self._dispatching("prefill_chunk"):
            # one dispatch for all rows, or one a row where the model
            # asks for that (``_prefill_group``); the cache and the last
            # tokens thread through them
            firsts, witness, picks = [], [], None
            for i in range(0, g, self._prefill_group):
                rows = slice(i, i + self._prefill_group)
                (self._cache, first, self._rng, seen, self._last_dev,
                 picks) = self._launch(
                        self._prefill_chunk_fn,
                        self.params, self._cache, jnp.asarray(chunk[rows]),
                        jnp.asarray(starts[rows]),
                        jnp.asarray(slots[rows], jnp.int32),
                        jnp.asarray(last_idx[rows]),
                        self._rng, self._last_tokens(),
                        jnp.asarray(final[rows]),
                    )
                if self._watch_slot in slots[rows]:
                    witness.append(self._witness(
                        "run", self._prefill_pos, seen))
                firsts.append(first)
        self.stats.prefill_calls += 1
        self.stats.prefill_chunks += 1
        self.stats.prefill_chunk_slots += g
        self._keep_window_rows(slots, ends)
        ended = []
        for i, s in enumerate(slots):
            req = self._slot_req[s]
            end = int(ends[i])
            self._prefill_pos[s] = end
            if self.paged:
                # the chunk just dispatched completes every prompt block
                # it crosses the end of — publish them so waiting
                # admissions (shared_prefix_ready) can warm-start: the
                # device runs its queue in order, so the block is
                # written before any later program reads it
                bs = self.block_size
                blocks = self._slot_blocks[s]
                for j in range(int(starts[i]) // bs,
                               min(end // bs, req.prompt.size // bs)):
                    self._blockmgr.mark_filled(blocks[j])
            if not final[i]:
                continue
            self._prefilling[s] = False
            self._positions[s] = req.prompt.size
            self._remaining[s] = req.max_new_tokens - 1
            ended.append((s, req, i))
        # read even where no row ends its prompt: the wait is the clock
        # of this dispatch, and without it the decode chunk's would
        # count this program's time as its own
        self._unread.append(_Unread(
            "prefill_chunk", attrs, started, firsts,
            self._deliver_firsts, ended, witness, picks))

    def _keep_window_rows(self, slots, ends) -> None:
        """Behind a prompt chunk's dispatch: where a slot's cursor now
        stands at the last chunk boundary inside its prompt's whole
        blocks, the point a later request that shares the prompt as a
        prefix warm-starts from, copy the window layers' last ``window -
        1`` rows out of the slot's ring (``WindowStore``), unless that
        prefix's are kept already, the block that ends there stands for
        no prefix (diverged, sharing off) or the store is full.  One small
        program a prompt, queued behind the chunk that wrote the rows."""
        store = self._blockmgr.windows if self.paged else None
        if store is None or not store.snapshots:
            return
        c, bs = self.prefill_chunk, self.block_size
        for s, end in zip(slots, ends):
            prompt = self._slot_req[s].prompt
            if not end or end != (prompt.size // bs * bs) // c * c:
                continue
            entry = self._blockmgr.window_entry(
                self._slot_blocks[s][int(end) // bs - 1])
            if entry is None:
                continue
            self._cache = self._window_keep_fn(
                self._cache, jnp.asarray(s, jnp.int32),
                jnp.asarray(entry, jnp.int32),
                jnp.asarray(store.ring_blocks(int(end))))

    def _finish_if_done(self, s: int, last_token: int) -> bool:
        """End the request in slot ``s`` if ``last_token`` is the
        end-of-sequence or its budget is spent (the paths that read
        before they dispatch again: ``_spec_step``, ``_drain_fixed``)."""
        req = self._slot_req[s]
        assert req is not None
        if (self.eos_token is not None and last_token == self.eos_token) \
                or self._remaining[s] <= 0:
            self._finish(s, req)
            return True
        return False

    def _finish(self, s: int, req: Request) -> None:
        """``req``, admitted into slot ``s``, has its whole output: hand
        it to the step's return, and free the slot unless a decode
        dispatch has (a finish by count: ``_dispatch_decode``)."""
        req.done = True
        self._finished.append(req)
        self.stats.finished_requests += 1
        event("dlrover.request.finished", erid=req.rid,
              tokens=len(req.output),
              decode_ms=(req.last_token_at - req.first_token_at) * 1e3,
              deliveries=req.deliveries, gap_ms_max=req.gap_max * 1e3)
        if self._slot_req[s] is req:
            self._release_slot(s)

    def _release_slot(self, s: int) -> None:
        """Return slot ``s`` to the free set — completion AND
        cancellation both land here, so a half-prefilled slot reclaims
        exactly like a decoding one."""
        self._slot_req[s] = None
        self._prefilling[s] = False
        self._prefill_pos[s] = 0
        if s == self._watch_slot:
            self._watch_slot = -1
        if self.paged and self._slot_blocks[s] is not None:
            # blocks return to the pool (shared prefix blocks just
            # decref; fully-released ones linger in the prefix LRU).
            # The table row must reset to the trash block NOW: the
            # dead slot keeps writing junk KV every step, and its
            # freed blocks may be reallocated to a live sequence.
            self._blockmgr.free_sequence(self._slot_blocks[s])
            self._slot_blocks[s] = None
            self._table_np[s, :] = 0
            # batched: several slots often finish in one round, and
            # a table transfer per finish would pay the host->device
            # hop each time — dispatch sites push once when dirty
            self._table_dirty = True

    def cancel(self, rid: int) -> bool:
        """Withdraw a request wherever it lives — the engine queue, a
        live decode slot, a decode chunk still in flight, or a slot still
        MID-CHUNKED-PREFILL (the cursor state is discarded and the
        lifetime block allocation freed) — reclaiming slot + paged KV
        immediately, without waiting for the device.  Always True:
        local delivery cannot fail, and an already-finished rid is a
        successfully-delivered no-op (the router-side cancel
        contract)."""
        for i, req in enumerate(self._queue):
            if req.rid == rid:
                del self._queue[i]
                return True
        for sent in self._unread:
            # a lane of the chunk in flight: its tokens go nowhere (also
            # where the chunk's dispatch took the request off its slot)
            sent.rows[:] = [r for r in sent.rows if r[1].rid != rid]
        for s, req in enumerate(self._slot_req):
            if req is not None and req.rid == rid:
                # (the device's vector of last tokens stays: a free
                # slot's entry is read by nothing before an admission
                # writes it)
                self._release_slot(s)
                return True
        return True

    @spanned("dlrover.engine.push_table")
    def _push_table(self) -> None:
        # a copy: the next admission binds its blocks in ``_table_np``
        # while programs that read this table may still be in flight
        table = jnp.asarray(self._table_np.copy())
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            table = jax.device_put(
                table, NamedSharding(self.mesh, PartitionSpec()))
        self._cache = dict(self._cache, table=table)
        self._table_dirty = False

    @property
    def kv_quant_blocks(self) -> int:
        """Blocks in a quantized (int8) KV pool (0 when the pool is
        native-dtype) — the ``serving_kv_quant_blocks`` gauge."""
        if self.paged and self.kv_dtype == "int8":
            return self._blockmgr.num_blocks
        return 0

    def prefix_stats(self) -> Dict[str, float]:
        """The ``serving_prefix_*`` ledger of the paged prefix cache
        (hits, misses, evictions, COW copies, shared blocks/tokens) —
        {} for dense layouts, which have no sharing to account."""
        if not self.paged:
            return {}
        return self._blockmgr.prefix_stats()

    def prefix_heads(self, n: int = 8) -> List[str]:
        """This replica's hottest committed prefix-head digests (hex)
        — what the worker advertises over STATS so the router's
        prefix-routing table can steer warm traffic here."""
        if not self.paged:
            return []
        return self._blockmgr.hot_heads(n)

    # ----------------------------------------------------------- step
    @property
    def has_work(self) -> bool:
        # (an unread chunk holds requests' last tokens: their finishes
        # are a step's to return; one whose every lane was cancelled
        # holds nobody's, and is read when there is work again)
        return bool(self._queue) or any(
            r is not None for r in self._slot_req) or any(
            sent.rows for sent in self._unread)

    @spanned("dlrover.engine.step")
    def step(self) -> List[Request]:
        """Admit waiting requests, advance at most ONE bounded prefill
        chunk a prefilling slot, run one decode chunk (or speculative
        verify), return requests whose whole output this step read.  The
        ordering IS the stall bound: a max-length prompt costs every
        other slot one chunk per decode round, never a whole prefill
        (one dispatch for all prefilling slots, or one a slot for a
        latent-attention model: ``_advance_prefill``).

        The step dispatches ALL of that before it waits for any of it,
        and it LOOKS AHEAD over its own boundary: its decode chunk it
        leaves unread, for the next step to read behind that step's own
        dispatches.  On the host the order is: dispatch step N
        (admissions, prompt chunks, decode chunk N) -> read chunk N - 1,
        then what step N dispatched ahead of its chunk, in dispatch order
        -> deliver, finish, return.  On the device the order of programs
        is what it always was, and chunk N is queued behind chunk N - 1
        while the host waits for that one, delivers it, returns to its
        caller, takes new requests and dispatches again.

        That needs nothing a token could say.  Which slots are free,
        prefill or decode, every position, every block and every budget
        are host facts, booked when a program is DISPATCHED
        (``_dispatch_decode`` takes a chunk's tokens off each budget and
        frees a slot whose budget the chunk spends, so the next step
        admits into it, one step after the dispatch); the one thing a
        program needs of the one before is each slot's last token, and
        that passes between them on the device (``_last_tokens``).  A
        request is returned by the step that READS its last tokens: one
        step after the dispatch that finished it, with its whole output
        (``has_work`` stays true until then; a step with nothing to
        dispatch reads what is left).  What a sampled token alone can
        say, that it ends its request, is learnt at the read, one chunk
        late: a request that ends inside chunk N already has a lane in
        chunk N + 1, whose tokens are dropped
        (``EngineStats.wasted_lane_chunks``), and its slot and blocks are
        freed at chunk N's read.  ``EngineStats.lookahead_steps`` counts
        the steps that returned with a chunk unread.

        With speculation on there is no look-ahead: drafts come from the
        host's context, so that step reads everything before it
        verifies."""
        before = len(self._finished)
        self._dispatch_admissions()
        if self.prefill_chunk:
            self._advance_prefill()
        if self._spec_fn is not None and self._spec_state == "on":
            # drafts come from the host's context: read first
            self._read_results()
            if self._decoding().any():
                self._spec_step()
            return self._finished[before:]
        active = self._decoding()
        decoding = bool(active.any())
        if decoding:
            self._dispatch_decode(active)
        self._read_results(ahead=decoding)
        if decoding and self._spec_fn is not None:
            self._after_chunk_round()
        return self._finished[before:]

    def _decoding(self) -> np.ndarray:
        """The slots a decode dispatch advances: those holding a request
        that is past its prefill and, as far as the host can know before
        it has read the last token, not at its end (a budget of one
        token is spent by the prefill's)."""
        return np.array([
            r is not None and not self._prefilling[s]
            and self._remaining[s] > 0
            for s, r in enumerate(self._slot_req)
        ])

    def _dispatch_decode(self, active: np.ndarray) -> None:
        """One decode chunk for the slots ``active``, not waited for.
        What it does to each slot is booked here, by arithmetic: the
        position, and the budget.  A slot whose budget the chunk spends
        is taken off its request now (``_release_slot``: the slot, its
        blocks and its table row are the next step's admissions' to
        have, whose programs the device runs behind this chunk); the
        request travels with the chunk and ends when its tokens are
        read (``_deliver_chunk``)."""
        if self.paged and self._table_dirty:
            self._push_table()
        live, streamed = self._book_kv_rows(active)
        lengths = self._positions[active][:, None] + np.arange(
            1, self.chunk + 1)[None, :]
        rows = {"kv_rows_live": live, "kv_rows_streamed": streamed,
                **self._book_selection(lengths - 1, lengths),
                **self._book_state_bytes(active),
                **self._book_window_rows(lengths)}
        started = time.perf_counter()
        with self._dispatching("decode_chunk"):
            out, self._last_dev, _, self._cache, self._rng, seen, picks = \
                self._launch(
                    self._chunk_fn,
                    self.params, self._cache, self._last_tokens(),
                    # a copy: the host goes on writing ``_positions``
                    # while the device may not have fetched them yet
                    jnp.asarray(self._positions.copy()),
                    jnp.asarray(active), self._rng,
                )
        witness = []
        if self._watch_slot >= 0 and active[self._watch_slot]:
            witness.append(self._witness("decode", self._positions, seen))
        # what the program does to them: no round trip
        self._positions[active] += self.chunk
        self.stats.decode_forwards += self.chunk
        lanes = []
        for s in np.flatnonzero(active):
            take = min(self.chunk, int(self._remaining[s]))
            self._remaining[s] -= take
            last = bool(self._remaining[s] <= 0)
            lanes.append((int(s), self._slot_req[s], take, last))
            if last:
                self._release_slot(s)
        self._unread.append(_Unread(
            "decode_chunk", rows, started, out, self._deliver_chunk, lanes,
            witness, picks))

    # ------------------------------------- dispatch now, read afterwards
    def _last_tokens(self) -> jax.Array:
        """Every slot's last token on the device: what the step's last
        program handed on, or the host's ``_tokens`` where that is not
        valid."""
        if self._last_dev is None:
            # a copy, as of ``_positions``: the reads write ``_tokens``
            self._last_dev = jnp.asarray(self._tokens.copy())
        return self._last_dev

    def _launch(self, program, *args):
        """Dispatch one program, counted, and as chained where an
        earlier one is still unread."""
        self.stats.dispatches += 1
        self.stats.chained_dispatches += self._in_flight > 0
        self._head_alone |= not self._in_flight
        self._in_flight += 1
        return program(*args)

    def _dispatching(self, name: str):
        """The span of a dispatch with nothing in flight: the device
        stands while the host is in it, so it is part of that program's
        time as its wait is (``_read_results`` opens the name again
        around the wait, with the attributes).  A chained dispatch has
        none: the device is busy, and the time is the host's own."""
        if self._in_flight:
            return contextlib.nullcontext()
        return span("dlrover.engine." + name)

    def _read_results(self, ahead: bool = False) -> None:
        """Read what is in flight, in dispatch order, and do the
        bookkeeping that needed it: the decode chunk the step before
        left, then this step's first tokens, each followed by its
        finishes, so requests finish in the order of the dispatches.
        ``ahead``: all but the newest, this step's own decode chunk,
        which the next call reads.  Each wait is a span under its
        program's name and adds to that program's ``*_seconds``
        (``EngineStats``), from the later of its dispatch and the result
        before it reaching the host, be that a step ago."""
        split = len(self._unread) - ahead
        unread, self._unread = self._unread[:split], self._unread[split:]
        kept = len(self._unread)          # one program: a decode chunk
        self.stats.lookahead_steps += kept
        if not unread:
            return
        reading = self._in_flight - kept
        with span("dlrover.engine.reads", dispatches=reading,
                  chained=reading - self._head_alone):
            for sent in unread:
                with span("dlrover.engine." + sent.name, **sent.attrs):
                    values = jax.tree_util.tree_map(
                        np.asarray, sent.outputs)
                now = time.perf_counter()
                read_at = time.monotonic()   # the requests' clock
                for clock in _CLOCKS[sent.name]:
                    setattr(self.stats, clock, getattr(self.stats, clock)
                            + now - max(sent.started, self._reached))
                self._reached = now
                self.witness_log.extend(sent.witness)
                self._book_moe_picks(sent.picks)
                sent.deliver(sent.rows, values, read_at, sent.name)
            self._in_flight = kept
            self._head_alone = False

    def _book_kv_rows(self, active: np.ndarray,
                      chunks: int = 1) -> Tuple[int, int]:
        """Add to ``stats.kv_rows_live`` / ``kv_rows_streamed`` what
        the fused paged kernel (of a latent-attention model:
        ``mla_decode_attention``, one layer's latent rows, which a
        learned selection streams too, under its mask: beside
        ``_book_selection``'s ``attn_rows_selected`` that is what the
        forward reads against what it attends)
        reads in the next ``chunks`` decode
        chunks dispatched from ``_positions`` for the slots ``active``
        (the lengths it will be handed: ``position + 1``, one more
        each forward; every other slot gets length 0 and reads
        nothing), and return the two sums.  Host integer arithmetic
        on a [slots, forwards] array; (0, 0) where the gather path
        decodes.  ``live`` is a slot's rows each, whoever shares them.
        ``streamed`` is what the kernel COPIES: where grouped-query
        layers attend under a selection (``paged_decode_attention`` with
        a ``bias``) a run of leading page groups that several active
        slots' rows of ``_table_np`` hold in common is copied, and booked,
        once a forward for all of them, so streamed / live falls under 1
        as far as pages are shared; every other kernel copies, and books,
        each slot's groups."""
        if self.attention_impl != "pallas" or self.rowless:
            return 0, 0
        if self._latent:
            from dlrover_tpu.ops.pallas.mla_decode import streamed_rows
        else:
            from dlrover_tpu.ops.pallas.paged_attention import \
                streamed_rows

        lengths = self._positions[active][:, None] \
            + np.arange(1, chunks * self.chunk + 1)[None, :]
        live = int(np.minimum(
            lengths, self._max_blocks * self.block_size).sum())
        selected = "index_pool" in self._pool_names and not self._latent \
            and self._max_blocks * self.block_size > self.cfg.index_topk
        streamed = streamed_rows(
            lengths, self.block_size, self._max_blocks,
            table=self._table_np[active] if selected else None)
        self.stats.kv_rows_live += live
        self.stats.kv_rows_streamed += streamed
        return live, streamed

    def _book_selection(self, starts, ends) -> Dict[str, int]:
        """Book what a learned selection of keys reads for runs of
        queries at positions ``starts[i] .. ends[i] - 1`` (a decode
        forward is a run of one): rows live (a query at position p sees
        p + 1), rows attended (``min(p + 1, index_topk)``) and index keys
        scored (the decode kernel's page groups up to each length; a
        run's key blocks up to its last query, once a query).  One
        layer's rows.  Returns the three for the dispatch's span; {} for
        a model without a selection, whose spans carry what they did."""
        cfg = self.cfg
        if not (self._kinds and cfg.index_topk):
            return {}
        from dlrover_tpu.ops.pallas.paged_index import scanned_rows
        from dlrover_tpu.serving.latent import KEY_BLOCK_PAGES

        starts, ends = np.asarray(starts), np.asarray(ends)
        n = (ends - starts).astype(np.int64)
        live = n * (starts + ends + 1) // 2          # sum of p + 1
        topk = cfg.index_topk
        full = np.clip(ends - np.maximum(starts, topk - 1), 0, None)
        low = n - full                                # queries below topk
        selected = full * topk + low * (2 * starts + low + 1) // 2
        if starts.ndim == 2:                          # decode forwards
            scanned = scanned_rows(ends, self.block_size, self._max_blocks)
        else:
            kb = KEY_BLOCK_PAGES * self.block_size
            scanned = int((n * (-(-ends // kb) * kb)).sum())
        book = {"kv_rows_live": int(live.sum()),
                "index_rows_scanned": int(scanned),
                "attn_rows_selected": int(selected.sum())}
        self.stats.dsa_rows_live += book["kv_rows_live"]
        self.stats.index_rows_scanned += book["index_rows_scanned"]
        self.stats.attn_rows_selected += book["attn_rows_selected"]
        return book

    def _book_key_blocks(self, starts, ends) -> Dict[str, int]:
        """Book what the attention of a latent-attention model's prompt
        chunks walks, whose real queries stand at ``starts[i] ..
        ends[i] - 1`` (behind them a chunk is padding, which attends
        nothing): key blocks up to each last real query, in the blocks
        of the path that walks them (``serving/latent.py _attend_run``),
        beside what their tables hold; and the attention kernel's tiles
        of queries with a real query among them, beside the tiles the
        chunk programs hold.  Returns what was walked for the dispatch's
        span ({} for any other model)."""
        if not self._latent:
            return {}
        from dlrover_tpu.ops.pallas.mla_prefill import (key_blocks,
                                                        query_tiles)
        from dlrover_tpu.serving.latent import KEY_BLOCK_PAGES

        table = -(-self._max_blocks // KEY_BLOCK_PAGES) * KEY_BLOCK_PAGES
        walked, held = key_blocks(
            ends, self.block_size, table,
            None if self.attention_impl == "pallas" else KEY_BLOCK_PAGES)
        live, tiles = query_tiles(
            np.asarray(ends) - np.asarray(starts), self.prefill_chunk)
        self.stats.prefill_key_blocks += walked
        self.stats.prefill_key_blocks_table += held
        self.stats.prefill_query_tiles += tiles
        self.stats.prefill_query_tiles_live += live
        return {"key_blocks": walked, "query_tiles": tiles,
                "query_tiles_live": live}

    def _book_state_bytes(self, active: np.ndarray) -> Dict[str, int]:
        """Book what the layers that keep a state a slot (linear attention
        or a state-space scan) move in one decode chunk
        of their slots' states: read + written for the slots ``active``
        (live), and for the slots the path walks (the decode kernel's
        grid: the active ones; the ``jnp`` step: every slot).  Returns the
        two for the dispatch's span ({} for a model with no such layer)."""
        if not self._state_layers:
            return {}
        from dlrover_tpu.ops.pallas.kda import decode_states_walked

        each = 2 * self._state_slot_bytes * self._state_layers * self.chunk
        live = decode_states_walked(active) * each
        walked = live if self.attention_impl == "pallas" \
            else self.max_slots * each
        self.stats.state_bytes_live += live
        self.stats.state_bytes_streamed += walked
        return {"state_bytes_live": live, "state_bytes_streamed": walked}

    def _book_window_rows(self, lengths: np.ndarray) -> Dict[str, int]:
        """Book what the window layers of one decode chunk read, for
        slots whose queries see ``lengths`` [slots, forwards] keys with no
        window: rows inside the windows, rows their attention streams
        (the ``reach`` blocks a window can touch, whole, whichever path
        decodes),
        summed over the window layers; and the rows the live slots'
        rings hold now, one layer's.  Returns them for the dispatch's
        span ({} for a model with no window layer)."""
        store = self._blockmgr.windows if self.paged else None
        if store is None:
            return {}
        g = store.geometry
        inside = np.minimum(lengths, g.window)
        # (kernel and gather alike walk the whole of so small a table)
        streamed = lengths.size * g.reach * g.block_size
        held = store.resident_rows([
            self._prefill_pos[s] if self._prefilling[s]
            else self._positions[s] + self.chunk
            for s, r in enumerate(self._slot_req) if r is not None])
        book = {"window_rows_in_window":
                int(inside.sum()) * self._window_layers,
                "window_rows_streamed": streamed * self._window_layers,
                "window_rows_resident": int(held.sum())}
        st = self.stats
        st.window_rows_in_window += book["window_rows_in_window"]
        st.window_rows_streamed += book["window_rows_streamed"]
        st.window_rows_resident = book["window_rows_resident"]
        st.window_rows_resident_max = max(
            st.window_rows_resident_max, int(held.max(initial=0)))
        return book

    def _book_state_chunks(self, starts, ends) -> Dict[str, int]:
        """Book what the chunk kernel of the layers that keep a state a
        slot takes of
        prompt chunks whose real tokens stand at ``starts[i] .. ends[i] -
        1``: the real tokens, the rows of the chunks (KDA's 64 tokens, the
        state-space scan's 128) it computes
        for them (one layer's, as every layer walks the same), and the
        slots whose state starts from zeros.  Returns them for the
        dispatch's span, the first two under the kind's name ({} for a
        model with no such layer)."""
        if not self._state_layers:
            return {}
        from dlrover_tpu.ops.pallas import kda, retention, ssm

        kind = self._state_kind
        chunk = retention.chunk_of(self.prefill_chunk) \
            if kind == "retention" \
            else {"kda": kda, "ssm": ssm}[kind].CHUNK    # its kernel's
        # (the ``jnp`` recurrence walks a program's every row)
        kernel = self.attention_impl == "pallas" and chunk \
            and self.prefill_chunk % chunk == 0
        real, padded = kda.chunk_rows(
            np.asarray(ends) - np.asarray(starts), self.prefill_chunk,
            chunk if kernel else self.prefill_chunk)
        resets = int(np.count_nonzero(np.asarray(starts) == 0))
        book = {f"{kind}_chunk_rows_real": real,
                f"{kind}_chunk_rows_padded": padded}
        for name, n in book.items():
            setattr(self.stats, name, getattr(self.stats, name) + n)
        self.stats.state_resets_total += resets
        return dict(book, state_resets=resets)

    @property
    def cache_nbytes(self) -> int:
        """Bytes of everything this engine keeps a sequence in: the paged
        pools (or the dense K/V) and, a slot, the states and convolution
        rows of the layers that keep one (linear attention, a state-space
        scan)."""
        return sum(self.cache_nbytes_by_kind.values())

    @property
    def cache_nbytes_by_kind(self) -> Dict[str, int]:
        """:attr:`cache_nbytes` by what grows it: ``paged`` (the pools of
        blocks: ``cache_blocks``), ``window`` (the window layers' rings
        and kept prefixes: slots x window) and ``state`` (a slot's
        recurrent state)."""
        kinds = {"paged": 0, "window": 0, "state": 0}
        for key, val in self._cache.items():
            if isinstance(val, list):
                kind = "window" if key.startswith("window_") else (
                    "state" if key.startswith(f"{self._state_kind}_")
                    else "paged")
                kinds[kind] += int(sum(x.nbytes for x in val))
        return kinds

    def watch(self, wanted) -> None:
        """Keep what the engine's own programs do for ONE request at a
        time: the first admitted to chunked prefill for which
        ``wanted(request)`` holds is watched until it ends, then the
        next.  Every prefill-chunk and decode-chunk dispatch that
        advances it appends to ``witness_log`` a dict of ``request``,
        ``kind`` ("run": a prompt chunk, "decode": a chunk of forwards,
        one query each), ``start`` (the position of its first query) and
        ``seen``, that program's own ``witness`` output for the slot
        (``serving/latent.py verify_step``; device arrays, a decode
        chunk's with a leading axis of forwards): the rows each query
        attended to (of a model with no selection of keys: its logits)
        and the first sparse MLP's input and output, which a
        selection of keys and a share of the experts otherwise leave to
        show only in the logits; of a model with linear-attention layers
        also the slot's recurrent state behind each forward, of the first
        and the last such layer (``kda_state``; of state-space layers
        ``ssm_state`` and ``ssm_conv``).  ``None`` stops.  The engine of a
        model of layer kinds only (latent attention, a state a slot,
        sparse experts): no other program keeps a
        witness."""
        if not self._kinds:
            raise ValueError("only the programs of the loop of layer kinds "
                             "keep a witness (serving/latent.py)")
        self._watch = wanted

    def _watch_if_wanted(self, s: int, req: Request) -> None:
        if self._watch is None or self._watch_slot >= 0 \
                or not self._watch(req):
            return
        self._watch_slot = s
        self._cache = dict(self._cache,
                           watch_slot=jnp.asarray(s, jnp.int32))

    def _witness(self, kind: str, positions, seen) -> Dict[str, Any]:
        """The ``witness_log`` entry of a program just dispatched for the
        watched slot; it joins the log when the program is read."""
        s = self._watch_slot
        return {"request": self._slot_req[s], "kind": kind,
                "start": int(positions[s]), "seen": seen}

    def _book_moe_picks(self, counted: Optional[jax.Array]) -> None:
        """Add to ``stats.moe_picks`` / ``moe_picks_held`` /
        ``moe_buffer_walks`` / ``moe_layer_forwards`` what the programs up
        to the one just read counted on the device since the last call
        (``counted``: four wrapping uint32 that it handed back beside the
        cache that carries them, None for a model that counts none; the
        counters lag by what is in flight)."""
        if counted is None:
            return
        now = np.asarray(counted, np.uint32)
        delta = (now - self._moe_picks_seen).astype(np.uint32)
        self._moe_picks_seen = now
        for name, more in zip(("moe_picks", "moe_picks_held",
                               "moe_buffer_walks", "moe_layer_forwards"),
                              delta):
            setattr(self.stats, name, getattr(self.stats, name) + int(more))

    def _handed(self, req: Request, now: float, gaps: List[float]) -> None:
        """``req`` is handed tokens that were read at ``now``: its gap
        since its last delivery joins the engine's sums, its own longest
        and ``gaps`` (count, seconds, longest: one delivery's)."""
        gap = now - req.last_token_at
        req.last_token_at = now
        req.deliveries += 1
        req.gap_max = max(req.gap_max, gap)
        self.stats.token_gaps += 1
        self.stats.token_gap_seconds += gap
        gaps[0] += 1
        gaps[1] += gap
        gaps[2] = max(gaps[2], gap)

    def _say_delivered(self, program: str, lanes: int, tokens: int,
                       gaps: List[float]) -> None:
        """``dlrover.engine.deliver``: what one program's read handed on,
        said once it is handed (the sums are what ``_handed`` booked)."""
        if lanes:
            event("dlrover.engine.deliver", program=program, lanes=lanes,
                  tokens=tokens, gaps=int(gaps[0]),
                  gap_ms_sum=gaps[1] * 1e3, gap_ms_max=gaps[2] * 1e3)

    def _deliver_chunk(self, lanes: List[Tuple[int, Request, int, bool]],
                       out: np.ndarray, now: float, program: str) -> None:
        """Hand a decode chunk's tokens ([B, chunk]), read at ``now``,
        on: ``lanes`` names each slot it advanced, the request that held
        the slot at its dispatch, how many of the lane's tokens that
        request's budget had room for, and whether those are its last.
        A request that an earlier read has ended (an end-of-sequence:
        the chunk before's, or its first token) keeps nothing of its
        lane; one whose budget the chunk spent, or that ends inside it,
        ends here."""
        gaps = [0, 0.0, 0.0]
        before = self.stats.generated_tokens
        for s, req, take, last in lanes:
            if self._slot_req[s] is req or self._slot_req[s] is None:
                # (a later occupant's token is its own program's)
                self._tokens[s] = out[s, -1]
            if req.done:
                self.stats.wasted_lane_chunks += 1
                continue
            toks = out[s, :take].tolist()
            if self.eos_token is not None and self.eos_token in toks:
                toks = toks[: toks.index(self.eos_token) + 1]
            req.output.extend(toks)
            if self._spec_fn is not None and self._slot_req[s] is req:
                # keep the draft-lookup context fresh so a later
                # switch back to speculation sees these tokens
                n = int(self._ctx_len[s])
                end = min(n + len(toks), self._ctx_buf.shape[1])
                self._ctx_buf[s, n:end] = toks[: end - n]
                self._ctx_len[s] = end
            self.stats.generated_tokens += len(toks)
            self._handed(req, now, gaps)
            if last or toks[-1] == self.eos_token:
                self._finish(s, req)
        self._say_delivered(program, len(lanes),
                            self.stats.generated_tokens - before, gaps)

    def _after_chunk_round(self) -> None:
        """Speculation governor, chunk-decode side: count down a
        backoff, or (auto mode) probe the FREE draft hit rate and
        switch speculation on when drafts are available often enough."""
        from dlrover_tpu.serving.speculative import find_draft

        if self._spec_state == "backoff":
            self._spec_cooldown -= 1
            if self._spec_cooldown <= 0:
                self._spec_state = "watching" if self.spec_auto else "on"
                self._spec_window.clear()
            return
        if self._spec_state != "watching":
            return
        for s, req in enumerate(self._slot_req):
            if req is None or self._prefilling[s]:
                continue
            n = int(self._ctx_len[s])
            context = self._ctx_buf[s, max(0, n - 2048):n]
            self._draft_hits.append(
                find_draft(context, self.speculative_k - 1) is not None
            )
        if len(self._draft_hits) >= 8 and (
            sum(self._draft_hits) / len(self._draft_hits) >= 0.4
        ):
            self._spec_state = "on"
            self._draft_hits.clear()

    def _spec_step(self) -> None:
        """One speculative round: draft K-1 tokens per slot by prompt
        lookup, verify all slots in ONE dispatch, commit the exact-
        distribution sample (greedy prefix match, or rejection sampling
        under temperature/top-k/top-p — speculative.rejection_commit)."""
        from dlrover_tpu.serving.speculative import find_draft

        k = self.speculative_k
        window = 2048  # bounded lookup tail: keeps the n-gram scan O(1)
        tokens = np.zeros((self.max_slots, k), np.int32)
        tokens[:, 0] = self._tokens
        draft_lens = np.zeros(self.max_slots, np.int32)
        for s, req in enumerate(self._slot_req):
            if req is None or self._prefilling[s]:
                continue
            n = int(self._ctx_len[s])
            context = self._ctx_buf[s, max(0, n - window):n]
            draft = find_draft(context, k - 1)
            if draft is not None:
                tokens[s, 1:1 + draft.size] = draft
                draft_lens[s] = draft.size
        if self.paged and self._table_dirty:
            self._push_table()
        t0 = time.perf_counter()
        with span("dlrover.engine.verify"):
            out, n_commit, self._cache, self._rng = self._launch(
                self._spec_fn,
                self.params, self._cache, jnp.asarray(tokens),
                jnp.asarray(self._positions), jnp.asarray(draft_lens),
                self._rng,
            )
            out = np.asarray(out)
            n_commit = np.asarray(n_commit)
        now = time.monotonic()          # the requests' clock
        self._in_flight = 0
        # the commits below write the host's ``_tokens`` only
        self._last_dev = None
        self.stats.decode_seconds += time.perf_counter() - t0
        self.stats.spec_calls += 1
        self.stats.decode_forwards += 1
        round_proposed = 0
        round_accepted = 0
        gaps = [0, 0.0, 0.0]
        lanes = 0
        before = self.stats.generated_tokens
        for s in range(self.max_slots):
            req = self._slot_req[s]
            if req is None or self._prefilling[s]:
                continue
            accepted = int(n_commit[s]) - 1
            round_proposed += int(draft_lens[s])
            round_accepted += accepted
            self.stats.spec_proposed += int(draft_lens[s])
            self.stats.spec_accepted += accepted
            toks = out[s, : accepted + 1].tolist()
            take = min(len(toks), int(self._remaining[s]))
            toks = toks[:take]
            if self.eos_token is not None and self.eos_token in toks:
                toks = toks[: toks.index(self.eos_token) + 1]
            if not toks:
                continue
            req.output.extend(toks)
            lanes += 1
            self._handed(req, now, gaps)
            n = int(self._ctx_len[s])
            self._ctx_buf[s, n:n + len(toks)] = toks
            self._ctx_len[s] = n + len(toks)
            self._remaining[s] -= len(toks)
            self.stats.generated_tokens += len(toks)
            self._tokens[s] = toks[-1]
            self._positions[s] += len(toks)
            self._finish_if_done(s, toks[-1])
        self._say_delivered("verify", lanes,
                            self.stats.generated_tokens - before, gaps)
        # governor: measured low acceptance -> back off to chunk decode
        # (a missing draft costs one wasted verify's worth of drafts
        # every round; backing off makes the miss genuinely free)
        self._spec_window.append((round_proposed, round_accepted))
        proposed = sum(p for p, _ in self._spec_window)
        if proposed >= 64:
            rate = sum(a for _, a in self._spec_window) / proposed
            if rate < self.spec_accept_floor:
                self._spec_state = "backoff"
                self._spec_cooldown = 8
                self._spec_window.clear()

    def run(self) -> Dict[int, np.ndarray]:
        """Drain the queue; returns {request_id: generated tokens}."""
        while self.has_work:
            if self.eos_token is None and self._spec_fn is None \
                    and not self.prefill_chunk:
                # fixed-budget drain needs a KNOWN number of dispatches;
                # speculative acceptance makes progress data-dependent
                # (and chunked prefill interleaves chunk dispatches),
                # so both modes always go through step()
                self._drain_fixed()
            else:
                self.step()
        return {r.rid: np.asarray(r.output, np.int32)
                for r in self._finished}

    def _drain_fixed(self) -> None:
        """No-EOS fast path: until the EARLIEST slot completion the
        number of decode chunks is known, so dispatch them all
        back-to-back and sync the host ONCE — per-chunk host round
        trips would otherwise dominate decode latency (multi-step
        scheduling taken to its fixed-budget limit).  Reads first
        whatever a step left in flight (``_admit``)."""
        self._admit()
        active = np.array([r is not None for r in self._slot_req])
        if not active.any():
            return
        min_remaining = min(
            int(self._remaining[s]) for s in range(self.max_slots)
            if self._slot_req[s] is not None)
        n_chunks = max(1, -(-min_remaining // self.chunk))
        self._book_kv_rows(active, n_chunks)
        t0 = time.perf_counter()
        outs = []
        tokens = jnp.asarray(self._tokens)
        positions = jnp.asarray(self._positions)
        active_j = jnp.asarray(active)
        for _ in range(n_chunks):
            out, tokens, positions, self._cache, self._rng, _, picks = \
                self._launch(
                    self._chunk_fn,
                    self.params, self._cache, tokens, positions,
                    active_j, self._rng,
                )
            outs.append(out)
        out = np.concatenate([np.asarray(o) for o in outs], axis=1)
        self._in_flight = 0
        self._book_moe_picks(picks)
        self._tokens = np.array(tokens)
        self._positions = np.array(positions)
        self._last_dev = None
        self.stats.decode_seconds += time.perf_counter() - t0
        now, gaps = time.monotonic(), [0, 0.0, 0.0]
        before = self.stats.generated_tokens
        for s in range(self.max_slots):
            req = self._slot_req[s]
            if req is None:
                continue
            take = min(out.shape[1], int(self._remaining[s]))
            toks = out[s, :take].tolist()
            req.output.extend(toks)
            if toks:
                self._handed(req, now, gaps)
            self._remaining[s] -= len(toks)
            self.stats.generated_tokens += len(toks)
            self._finish_if_done(s, toks[-1] if toks else -1)
        self._say_delivered("decode_chunk", int(gaps[0]),
                            self.stats.generated_tokens - before, gaps)

    # ----------------------------------------- batch-generate (RL API)
    def generate(
        self,
        prompt_ids,
        max_new_tokens: int,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """``sample_sequences``-compatible batch API for RL rollouts:
        returns (tokens [B, P+new], response_mask [B, P+new]).  A
        sequence that stopped early at EOS pads the remainder with the
        EOS token but the mask covers ONLY the actually-sampled tokens
        (through the EOS) — training signals must not weight filler the
        policy never produced."""
        prompts = np.asarray(prompt_ids, np.int32)
        batch, p_len = prompts.shape
        rids = [self.add_request(prompts[i], max_new_tokens)
                for i in range(batch)]
        outputs = self.run()
        total = p_len + max_new_tokens
        tokens = np.zeros((batch, total), np.int32)
        mask = np.zeros((batch, total), np.int32)
        for i, rid in enumerate(rids):
            out = outputs[rid]
            n_real = min(out.size, max_new_tokens)
            fill = np.full(
                max_new_tokens,
                out[-1] if out.size else 0, np.int32)
            fill[:n_real] = out[:max_new_tokens]
            tokens[i, :p_len] = prompts[i]
            tokens[i, p_len:] = fill
            mask[i, p_len:p_len + n_real] = 1
        # engine state stays warm for the next batch
        self._finished.clear()
        self._last_dev = None
        return tokens, mask
