"""Local SGD / DiLoCo-style training: infrequent sync + merge methods.

Parity target: reference atorch/atorch/local_sgd/ — workers run H inner
steps without gradient sync, then an outer step merges per-replica
deltas: ``reduce_methods/linear.py`` (weighted mean),
``generalized_task_arithmetic.py`` (sign-consensus GTA merge),
``sparsify.py`` (magnitude top-k), driven by an outer optimizer with
momentum; HSDP composes this with intra-group sharding.

TPU-native shape: replicas are the ``dp`` mesh axis.  Inner steps jit
WITHOUT any cross-dp collective (each dp group holds its own params via
``shard_map``); every ``sync_every`` steps one jitted sync program
computes pseudo-gradients (global - local), merges them across dp with
a single ``psum``-based reduction, and applies a Nesterov outer step.
Total dp traffic drops by ~H× vs per-step gradient allreduce — the same
bandwidth story that motivates the reference, but over ICI/DCN instead
of NCCL.  HSDP = this over ``dp`` composed with the existing ``fsdp``
axis sharding from accelerate().
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# merge methods — pure pytree functions over stacked replica deltas
# (leading axis R).  Each returns the merged delta pytree (no leading
# axis).
# ---------------------------------------------------------------------------

def linear_merge(deltas: Any, weights: Optional[jax.Array] = None) -> Any:
    """Weighted mean (reference reduce_methods/linear.py)."""

    def merge(x):
        if weights is None:
            return x.mean(axis=0)
        w = weights.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
        return (x * w).sum(axis=0) / w.sum()

    return jax.tree.map(merge, deltas)


def gta_merge(deltas: Any) -> Any:
    """Generalized task arithmetic (reference
    reduce_methods/generalized_task_arithmetic.py): elect a per-element
    sign by summed magnitude, zero out disagreeing replicas, average the
    agreeing ones."""

    def merge(x):
        elected = jnp.sign(x.sum(axis=0))
        agree = (jnp.sign(x) == elected) & (elected != 0)
        num = jnp.where(agree, x, 0.0).sum(axis=0)
        cnt = jnp.maximum(agree.sum(axis=0), 1)
        return num / cnt.astype(x.dtype)

    return jax.tree.map(merge, deltas)


def sparsify_merge(deltas: Any, density: float = 0.25) -> Any:
    """Magnitude top-k per replica then mean (reference
    reduce_methods/sparsify.py): keep the largest ``density`` fraction of
    each replica's delta, zero the rest."""

    def merge(x):
        flat = x.reshape(x.shape[0], -1)
        k = max(1, int(flat.shape[1] * density))
        thresh = jnp.sort(jnp.abs(flat), axis=1)[:, -k][:, None]
        kept = jnp.where(jnp.abs(flat) >= thresh, flat, 0.0)
        return kept.mean(axis=0).reshape(x.shape[1:])

    return jax.tree.map(merge, deltas)


MERGE_METHODS = {
    "linear": linear_merge,
    "gta": gta_merge,
    "sparsify": sparsify_merge,
}


# ---------------------------------------------------------------------------
# outer optimizer + state
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LocalSGDConfig:
    sync_every: int = 16            # H inner steps per sync
    merge_method: str = "linear"
    outer_lr: float = 0.7           # DiLoCo defaults
    outer_momentum: float = 0.9
    nesterov: bool = True


class LocalSGD:
    """Pure-function outer loop: ``init`` -> repeated ``sync``.

    ``sync(state, replica_params)`` takes the per-replica params stacked
    on a leading axis R and returns (new_global_params, new_state); the
    caller broadcasts the globals back to every replica (under
    shard_map this is where the only cross-dp communication happens).
    """

    def __init__(self, config: Optional[LocalSGDConfig] = None):
        self.config = config or LocalSGDConfig()
        if self.config.merge_method not in MERGE_METHODS:
            raise ValueError(
                f"unknown merge method {self.config.merge_method!r}")

    def init(self, global_params: Any) -> dict:
        return {
            "global": global_params,
            "momentum": jax.tree.map(jnp.zeros_like, global_params),
        }

    def sync(self, state: dict, replica_params: Any) -> Tuple[Any, dict]:
        cfg = self.config
        merge = MERGE_METHODS[cfg.merge_method]
        # pseudo-gradient: how far each replica moved, sign-flipped so the
        # outer step DESCENDS toward the replicas (DiLoCo Eq. 2)
        deltas = jax.tree.map(
            lambda g, r: g[None] - r, state["global"], replica_params
        )
        merged = merge(deltas)
        mom = jax.tree.map(
            lambda m, d: cfg.outer_momentum * m + d,
            state["momentum"], merged,
        )
        step_dir = jax.tree.map(
            lambda m, d: cfg.outer_momentum * m + d, mom, merged
        ) if cfg.nesterov else mom
        new_global = jax.tree.map(
            lambda g, s: g - cfg.outer_lr * s, state["global"], step_dir
        )
        return new_global, {"global": new_global, "momentum": mom}


# ---------------------------------------------------------------------------
# shard_map integration: per-dp-replica inner steps + on-device sync
# ---------------------------------------------------------------------------

def build_local_sgd_step(
    mesh,
    inner_step: Callable[[Any, Any], Any],
    config: Optional[LocalSGDConfig] = None,
    axis: str = "dp",
    param_spec=None,
    batch_spec=None,
):
    """Returns jitted (inner_fn, sync_fn) over ``mesh``'s dp axis.

    ``inner_step(params, batch) -> params`` is the per-replica update
    (NO cross-replica collective inside).  ``inner_fn`` maps it over the
    dp axis with params held per-replica (leading axis R sharded over
    dp).  ``sync_fn(state, replica_params)`` merges on-device: the only
    dp communication in the whole scheme.

    HSDP: pass ``param_spec=PartitionSpec("dp", "fsdp")`` (and a matching
    ``batch_spec``) to keep each replica's params SHARDED over the fsdp
    axis inside the shard_map — inner steps then run on fsdp-local
    shards and the sync reduction moves shard-sized payloads only
    (reference local_sgd/HSDP composition).
    """
    from jax.sharding import PartitionSpec as P

    cfg = config or LocalSGDConfig()
    local = LocalSGD(cfg)
    rep = param_spec if param_spec is not None else P(axis)
    bspec = batch_spec if batch_spec is not None else rep

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(rep, bspec), out_specs=rep, check_vma=False,
    )
    def inner_fn(replica_params, batch):
        params = jax.tree.map(lambda x: x[0], replica_params)
        b = jax.tree.map(lambda x: x[0], batch)
        out = inner_step(params, b)
        return jax.tree.map(lambda x: x[None], out)

    # sync stays ON DEVICE: replica_params keep their [R, ...] dp
    # sharding; jitting local.sync lets GSPMD insert the cross-dp
    # collective for the merge reduction — the only dp communication in
    # the whole scheme (multi-host safe; no host round-trip).
    sync_fn = jax.jit(local.sync)

    return jax.jit(inner_fn), sync_fn, local
