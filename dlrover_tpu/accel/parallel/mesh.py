"""Named-dimension device mesh — the TPU-native analogue of atorch's
``create_parallel_group``.

The reference builds named NCCL process groups from a spec like
``[("tensor", 4), ("pipe", 2), ("data", 2)]`` with stride-based rank slicing
(reference: atorch/atorch/distributed/distributed.py:266-396).  On TPU the
idiomatic equivalent is a single :class:`jax.sharding.Mesh` whose axis names
*are* the parallelism dimensions; XLA GSPMD inserts the collectives that the
reference builds by hand.

Axis vocabulary (fixed order, innermost last so tensor-parallel collectives
ride ICI neighbours):

=========  =============================================================
``dp``     pure data parallel (gradients all-reduced, params replicated)
``fsdp``   data parallel with fully-sharded params (ZeRO-3 equivalent —
           reference: atorch auto/opt_lib/zero_optimization.py)
``pp``     pipeline stages (reference: pipeline_parallel_optimization.py)
``cp``     context parallel: ring flash attention over seq chunks
           (beyond-reference — the reference's SP is all-to-all only,
           SURVEY.md §2.3; ring attention scales seq past one chip's HBM)
``sp``     sequence parallel, Ulysses all-to-all equivalent
           (reference: atorch/atorch/distributed/distributed.py:435-501)
``ep``     expert parallel for MoE (reference: atorch/atorch/modules/moe/)
``tp``     tensor parallel (reference: modules/distributed_modules/layers.py)
=========  =============================================================

Logical→mesh sharding rules follow the t5x/maxtext convention: model code
annotates arrays with *logical* axis names; a rules table maps those to mesh
axes.  Changing the parallelism strategy = changing the rules table, not the
model.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Fixed axis order: collectives on later (inner) axes map to closer ICI
# neighbours, and tensor-parallel all-reduces are the most latency-sensitive.
MESH_AXES: Tuple[str, ...] = ("dp", "fsdp", "pp", "cp", "sp", "ep", "tp")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Parallelism strategy as named mesh-dimension sizes.

    The analogue of the reference's parallel-group config
    ``[("tensor", t), ("pipe", p), ("data", d)]`` (reference:
    atorch/atorch/distributed/distributed.py:323-396).  A size of 1 means
    the dimension is unused (the axis still exists in the mesh; size-1 axes
    are free).
    """

    dp: int = 1
    fsdp: int = 1
    pp: int = 1
    cp: int = 1
    sp: int = 1
    ep: int = 1
    tp: int = 1
    # Hybrid ICI x DCN layout: the outer `dcn_dp` factor of the dp axis
    # strides across slices/hosts (DCN links), everything else stays
    # inside one slice (ICI).  Pure layout metadata — the mesh axes and
    # their sizes are unchanged; only the device assignment differs.
    # (reference: atorch distributed.py:323-396 node-spanning data groups
    # + net_topology.py:62 locality-aware dp placement; scaling-book
    # recipe: dp outer over DCN.)
    dcn_dp: int = 1

    def __post_init__(self) -> None:
        for name in MESH_AXES + ("dcn_dp",):
            v = getattr(self, name)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"mesh dim {name!r} must be a positive int, got {v!r}")
        if self.dp % self.dcn_dp:
            raise ValueError(
                f"dcn_dp={self.dcn_dp} must divide dp={self.dp} (the DCN "
                "replicas are the outer factor of the dp axis)"
            )

    @property
    def size(self) -> int:
        return math.prod(getattr(self, name) for name in MESH_AXES)

    @property
    def dims(self) -> Tuple[Tuple[str, int], ...]:
        return tuple((name, getattr(self, name)) for name in MESH_AXES)

    def build_mesh(self, devices: Optional[Sequence[Any]] = None) -> Mesh:
        """Build a :class:`jax.sharding.Mesh` over ``devices`` (default: all)."""
        if devices is None:
            devices = jax.devices()
        n = len(devices)
        if self.size != n:
            raise ValueError(
                f"MeshSpec size {self.size} ({self.dims}) != device count {n}"
            )
        shape = tuple(getattr(self, name) for name in MESH_AXES)
        if self.dcn_dp > 1:
            return Mesh(
                _hybrid_device_array(self, devices), MESH_AXES
            )
        try:
            # Let JAX pick an ICI-friendly physical layout when possible.
            from jax.experimental import mesh_utils

            device_array = mesh_utils.create_device_mesh(
                shape, devices=np.asarray(devices)
            )
        except Exception:
            device_array = np.asarray(devices).reshape(shape)
        return Mesh(device_array, MESH_AXES)

    @classmethod
    def for_device_count(
        cls,
        n: int,
        tp: int = 1,
        pp: int = 1,
        cp: int = 1,
        sp: int = 1,
        ep: int = 1,
        fsdp: Optional[int] = None,
    ) -> "MeshSpec":
        """Fill the data dimensions to cover ``n`` devices.

        By default everything not claimed by tp/pp/cp/sp/ep goes to ``fsdp``
        (the reference's default strategy is FSDP too — its headline bench is
        Llama2 FSDP, atorch/examples/llama2/README.md).  Pass ``fsdp`` to
        split the remainder between ``fsdp`` and pure ``dp``.
        """
        denom = tp * pp * cp * sp * ep
        if n % denom:
            raise ValueError(
                f"device count {n} not divisible by tp*pp*cp*sp*ep={denom}"
            )
        rest = n // denom
        if fsdp is None:
            fsdp = rest
        if rest % fsdp:
            raise ValueError(f"remainder {rest} not divisible by fsdp={fsdp}")
        return cls(dp=rest // fsdp, fsdp=fsdp, pp=pp, cp=cp, sp=sp, ep=ep, tp=tp)

    @classmethod
    def hybrid(
        cls,
        n_slices: int,
        devices_per_slice: int,
        **inner: int,
    ) -> "MeshSpec":
        """Multi-slice spec: pure-dp replicas over DCN (one per slice),
        ``inner`` axes (fsdp/tp/pp/...) inside each slice over ICI.

        ``MeshSpec.hybrid(2, 4, fsdp=4)`` = 2 slices x 4 chips, FSDP
        within the slice, gradient all-reduce across slices over DCN —
        the scaling-book layout for multi-pod training.
        """
        inner_size = math.prod(inner.values()) if inner else 1
        if devices_per_slice % inner_size:
            raise ValueError(
                f"inner axes {inner} (size {inner_size}) do not divide "
                f"devices_per_slice={devices_per_slice}"
            )
        inner_dp = inner.pop("dp", 1) * (devices_per_slice // inner_size)
        if "fsdp" not in inner and inner_size == 1:
            # no inner strategy given: default the slice-local remainder
            # to fsdp (mirrors for_device_count), dp carries only DCN
            inner["fsdp"] = devices_per_slice
            inner_dp = 1
        return cls(dp=n_slices * inner_dp, dcn_dp=n_slices, **inner)


def _device_slice_groups(
    devices: Sequence[Any], n_groups: int
) -> list:
    """Partition ``devices`` into DCN granules (slices/hosts).

    Priority: the TPU ``slice_index`` attribute (real multi-slice), then
    ``process_index`` (multi-host CPU/GPU), then contiguous chunks (a
    single-process emulation, e.g. the virtual-device dryrun).
    """
    for attr in ("slice_index", "process_index"):
        keys = []
        for d in devices:
            k = getattr(d, attr, None)
            if k is None:
                keys = None
                break
            keys.append(k)
        if keys and len(set(keys)) > 1:
            groups: dict = {}
            for d, k in zip(devices, keys):
                groups.setdefault(k, []).append(d)
            return [groups[k] for k in sorted(groups)]
    chunk = len(devices) // n_groups
    return [
        list(devices[i * chunk: (i + 1) * chunk]) for i in range(n_groups)
    ]


def _hybrid_device_array(spec: MeshSpec, devices: Sequence[Any]) -> np.ndarray:
    """Device array whose outer dp factor strides across DCN granules.

    Shape ``(dp, fsdp, pp, cp, sp, ep, tp)`` where dp index
    ``g * inner_dp + i`` lives entirely in granule ``g`` for the non-dp
    axes — so fsdp/tp/cp/sp/ep collectives ride ICI and only the dp
    gradient all-reduce crosses DCN.
    """
    groups = _device_slice_groups(devices, spec.dcn_dp)
    if len(groups) % spec.dcn_dp:
        raise ValueError(
            f"found {len(groups)} device granules, not divisible by "
            f"dcn_dp={spec.dcn_dp}"
        )
    # several granules per DCN replica (e.g. 2 hosts per slice): merge
    # consecutive granules
    per = len(groups) // spec.dcn_dp
    merged = [
        [d for g in groups[i * per: (i + 1) * per] for d in g]
        for i in range(spec.dcn_dp)
    ]
    inner_dp = spec.dp // spec.dcn_dp
    inner_shape = (inner_dp,) + tuple(
        getattr(spec, name) for name in MESH_AXES[1:]
    )
    blocks = []
    for g, devs in enumerate(merged):
        if len(devs) != math.prod(inner_shape):
            raise ValueError(
                f"granule {g} has {len(devs)} devices, expected "
                f"{math.prod(inner_shape)} for inner shape {inner_shape}"
            )
        try:
            from jax.experimental import mesh_utils

            block = mesh_utils.create_device_mesh(
                inner_shape, devices=np.asarray(devs)
            )
        except Exception:
            block = np.asarray(devs).reshape(inner_shape)
        blocks.append(block)
    return np.concatenate(blocks, axis=0)


def check_dcn_adjacency(mesh: Mesh, dcn_dp: int) -> None:
    """Assert the hybrid layout invariant: each dp-outer block (one DCN
    replica) lives entirely inside one DCN granule, i.e. the high-traffic
    fsdp/tp/cp/sp/ep collectives never cross DCN; only dp-outer
    neighbours do."""
    arr = mesh.devices
    devices = sorted(arr.flatten().tolist(), key=lambda d: d.id)
    groups = _device_slice_groups(devices, dcn_dp)
    per = max(1, len(groups) // dcn_dp)
    label: dict = {}
    for gi, g in enumerate(groups):
        for d in g:
            label[d.id] = gi // per
    inner_dp = arr.shape[0] // dcn_dp
    block_labels = []
    for b in range(dcn_dp):
        block = arr[b * inner_dp: (b + 1) * inner_dp]
        labels = {label[d.id] for d in block.flat}
        if len(labels) != 1:
            raise AssertionError(
                f"dp-outer block {b} spans DCN granules {labels}; "
                "fsdp/tp collectives would cross DCN"
            )
        block_labels.append(labels.pop())
    if len(set(block_labels)) != dcn_dp:
        raise AssertionError(
            f"dp-outer blocks map to granules {block_labels}; each DCN "
            "replica must own a distinct granule"
        )


# ---------------------------------------------------------------------------
# Logical axis rules
# ---------------------------------------------------------------------------

# (logical axis name, mesh axes it shards over).  First matching rule wins.
# None means replicate.  These defaults express: batch over all data axes,
# params sharded over fsdp (ZeRO-3) and tp (Megatron), sequence over sp,
# experts over ep.
DEFAULT_LOGICAL_RULES: Tuple[Tuple[str, Any], ...] = (
    ("batch", ("dp", "fsdp")),
    # cp-major, sp-minor: after the Ulysses all-to-all gathers the sp
    # sub-chunks, each cp peer holds one CONTIGUOUS global seq range —
    # exactly what the ring's block-causal masking assumes.
    ("seq", ("cp", "sp")),
    ("kv_seq", None),
    ("embed", "fsdp"),          # param embed dim: ZeRO-3 shard
    ("act_embed", None),        # activation embed dim: replicated
    # Embedding TABLE axes: rows (vocab) sharded, embed dim replicated.
    # Sharding the table's embed dim over fsdp makes the token gather's
    # output embed-sharded, and XLA cannot reshard gather output to the
    # (batch, seq) activation sharding without an involuntary full
    # rematerialization of the embedding; row sharding keeps ZeRO-3
    # memory scaling and lowers to a masked-lookup + psum instead.
    ("vocab_tbl", ("tp", "fsdp")),
    ("embed_tbl", None),
    ("heads", "tp"),
    ("kv_heads", "tp"),
    ("head_dim", None),
    ("mlp", "tp"),
    ("vocab", "tp"),
    ("expert", "ep"),
    ("norm", None),
    ("layers", None),           # scan-over-layers leading axis
    ("stage", "pp"),
)


# Active rules used by with_logical_constraint / logical_to_spec when no
# explicit rules are passed.  accelerate() installs its rules around every
# trace and call (logical_rules_context) so model-internal activation
# constraints always agree with the param shardings of the model being run,
# even when several accelerate() results with different rules coexist.
_ACTIVE_RULES: Tuple[Tuple[str, Any], ...] = DEFAULT_LOGICAL_RULES


def set_logical_rules(rules: Sequence[Tuple[str, Any]]) -> None:
    global _ACTIVE_RULES
    _ACTIVE_RULES = tuple(tuple(r) for r in rules)


def get_logical_rules() -> Tuple[Tuple[str, Any], ...]:
    return _ACTIVE_RULES


class logical_rules_context:
    """Temporarily install a rules table (re-entrant, restores on exit)."""

    def __init__(self, rules: Sequence[Tuple[str, Any]]):
        self._rules = rules
        self._saved: Optional[Tuple[Tuple[str, Any], ...]] = None

    def __enter__(self) -> "logical_rules_context":
        self._saved = get_logical_rules()
        set_logical_rules(self._rules)
        return self

    def __exit__(self, *exc) -> None:
        set_logical_rules(self._saved)


def logical_to_spec(
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Sequence[Tuple[str, Any]]] = None,
) -> PartitionSpec:
    """Map a tuple of logical axis names to a :class:`PartitionSpec`.

    A mesh axis may be used at most once in a spec; later logical axes that
    would reuse a taken mesh axis fall back to replication (same resolution
    the reference's shard planners apply when a dim is already consumed).
    """
    if rules is None:
        rules = _ACTIVE_RULES
    table = dict(rules)
    used: set = set()
    out = []
    for name in logical_axes:
        axes = table.get(name) if name is not None else None
        if axes is None:
            out.append(None)
            continue
        if isinstance(axes, str):
            axes = (axes,)
        free = tuple(a for a in axes if a not in used)
        if not free:
            out.append(None)
            continue
        used.update(free)
        out.append(free if len(free) > 1 else free[0])
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def named_sharding(
    mesh: Mesh,
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Sequence[Tuple[str, Any]]] = None,
) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical_axes, rules))


_warned_mesh_probe = False


def ambient_mesh() -> Optional[Mesh]:
    """The mesh of the enclosing ``with mesh:`` context, or None.

    Single home for the private-API probe (jax may move
    ``thread_resources`` across versions; a failure logs once and degrades
    to None — callers fall back to mesh-less behavior).
    """
    global _warned_mesh_probe
    try:
        from jax._src.mesh import thread_resources

        mesh = thread_resources.env.physical_mesh
        return None if mesh.empty else mesh
    except Exception as e:
        if not _warned_mesh_probe:
            _warned_mesh_probe = True
            import logging

            logging.getLogger("dlrover_tpu").warning(
                "ambient-mesh probe failed (%s: %s) — sharding constraints "
                "and Ulysses sp dispatch degraded; jax internals may have "
                "moved", type(e).__name__, e,
            )
        return None


def with_logical_constraint(
    x: jax.Array,
    logical_axes: Sequence[Optional[str]],
    rules: Optional[Sequence[Tuple[str, Any]]] = None,
) -> jax.Array:
    """``with_sharding_constraint`` by logical axis names.

    No-op outside a mesh context so model code runs un-jitted on CPU tests.
    """
    if rules is None:
        rules = _ACTIVE_RULES
    physical_mesh = ambient_mesh()
    if physical_mesh is None:
        return x
    spec = logical_to_spec(logical_axes, rules)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(physical_mesh, spec)
    )


def axes_size(mesh: Mesh, entry: Any) -> int:
    """Product of mesh-axis sizes named by one PartitionSpec entry
    (None -> 1, str -> that axis, tuple -> product)."""
    if entry is None:
        return 1
    if isinstance(entry, str):
        entry = (entry,)
    size = 1
    for a in entry:
        size *= mesh.shape.get(a, 1)
    return size


def batch_spec(rules: Optional[Sequence[Tuple[str, Any]]] = None) -> PartitionSpec:
    """PartitionSpec for a ``[batch, seq, ...]`` input array."""
    return logical_to_spec(("batch", "seq"), rules)


def num_data_shards(spec: MeshSpec) -> int:
    """How many distinct data shards the input pipeline must produce."""
    return spec.dp * spec.fsdp


def model_flops_per_token(cfg, seq_len: Optional[int] = None) -> float:
    """Training model-FLOPs per token: ``6*N`` for the matmuls plus the
    attention quadratic term (``12 * L * s * h`` fwd+bwd).  The single
    source of an MFU numerator (its last caller left the tree with
    the pre-chip benchmark, ROADMAP D7) —
    recompute from rematerialization is deliberately NOT counted (it
    shows up as lost MFU, keeping the accounting honest)."""
    n = cfg.num_params
    seq = seq_len if seq_len is not None else cfg.max_seq_len
    return 6.0 * n + 12 * cfg.num_layers * seq * cfg.hidden_size


#: Peak dense bf16 FLOP/s of one chip, keyed by the EXACT
#: ``jax.Device.device_kind`` string (vendor-published peaks; v5e:
#: Google Cloud "TPU v5e" documentation).  A device that is not here is
#: an error, not a default — add its kind and its published peak.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,   # v5e
    "TPU v6 lite": 918e12,   # v6e (Trillium)
    "TPU v5": 459e12,        # v5p
    "TPU v4": 275e12,
    "TPU v3": 123e12,
    "TPU v2": 45e12,
}


def mfu_denominator_flops(device_kind: str) -> float:
    """Peak bf16 FLOP/s for MFU accounting.  Raises for a device the
    table does not know: an MFU against a guessed peak would be
    silently wrong, and one that silently disappears hides the device."""
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published bf16 peak on record for device_kind "
            f"{device_kind!r}: add it to mesh.PEAK_BF16_FLOPS (known: "
            f"{sorted(PEAK_BF16_FLOPS)})") from None
