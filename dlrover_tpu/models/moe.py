"""Mixture-of-Experts layer: dropless sorted dispatch over a grouped matmul.

Parity targets in the reference:
- ``MOELayer`` token dispatch (atorch/atorch/modules/moe/moe_layer.py:87)
- top-k gating (atorch/atorch/modules/moe/topk_gating.py)
- grouped-GEMM experts (atorch/atorch/modules/moe/grouped_gemm_moe.py)

TPU-native design.  The router picks ``top_k`` experts per token by
``jax.lax.top_k`` over a float32 softmax.  The ``T x top_k`` (token,
expert) picks are sorted by expert, so that each expert's rows are one
contiguous group of ``[T * top_k, hidden]``; the three expert matmuls
are grouped matmuls over those groups (:func:`grouped_matmul`); the
result goes back to token order and is summed with the router's
weights.  No pick is ever dropped and no buffer has a capacity: a
group is as long as its expert's picks.  Both moves between token
order and expert order are row gathers in forward AND backward
(:func:`_to_expert_order`, :func:`_to_token_order`: each is the other's
transpose), so the layer holds no scatter.

Experts carry the ``expert`` logical axis (``ep`` in the mesh rules), so
their stored parameters and optimizer state shard over ``ep``.  The
layer's own work is not partitioned yet: the picks are sorted over ALL
tokens and a Pallas call has no partitioning rule, so on a mesh the
router's probabilities and the grouped matmul's operands are replicated
first (:func:`_replicated`) and every device computes the whole layer.
The result is the same; the all-to-all by hand is ROADMAP A2.

Variants (all off by default, and then the traced layer is as it was):
``score_fn="sigmoid"`` scores each expert by the sigmoid of its logit;
``routed_scale`` multiplies the routed sum; ``shared_width`` adds one
dense SwiGLU expert on every token (scope ``moe_shared``);
``experts_held=(first, count)`` makes the layer ONE SHARE of an
expert-parallel group: the router keeps all ``num_experts`` outputs and
picks ``top_k`` of them, the layer holds ``count`` experts' weights, and a
pick on an absent expert adds nothing, forward or backward.  Nothing
stands in for the absent devices.

The sorted buffer of a share holds the picks on HELD experts only:
:func:`buffer_rows` rows, ``C``, 3 / 2 of what an even routing sends the
share (24 576 of ``train-conv-moe-8k``'s 65 536 picks and of
``train-hybrid-8k``'s 131 072).  Every pass around the three grouped
matmuls (placing the tokens' rows, the selects behind the held groups,
``silu(gate) * up``, the weights, the sum back to tokens, and the
transposes of all of these) is over ``C`` rows and not ``T x top_k``.  Rows
go in by ONE gather of ``C`` rows and come back by a gather of ``[top_k,
T]`` rows summed over the choices in float32 (:func:`_sum_of_rows`; each is
the other's transpose, so the walk holds no scatter).  The layer's
derivative is written out (:func:`_walked`): JAX's own, through a ``cond``,
makes every branch write what any branch keeps and asked 17.0 GB of the
chip's 15.75 (PR 56).

No pick is dropped under any routing.  Where the share gets more picks
than ``C``, the rows of expert order behind the buffer are walked behind
ONE ``cond`` a layer and direction (:class:`_HeldWalk` ``further`` /
``further_back``), a SEGMENT at a time: up to ``SEGMENT_ROWS`` rows of one
expert, gathered, through three plain matmuls against that expert's
weights, added to their tokens in float32.  That path is rare (never in
``train-conv-moe-8k``; one layer of eight in most steps of
``train-hybrid-8k``), every sparse layer's program holds it forward and
backward, and the kernels' roofline is read from exactly 12 ``gmm`` /
``tgmm`` calls a layer: so it calls no kernel, keeps nothing for its
backward (which runs its forward again), has no temporary larger than a
segment, and is written to be SHORT, not fast.  A layer that holds every
expert, or whose buffer would be every pick, keeps the buffer of every
pick and traces what it did.

What the layer sows into the ``"moe_losses"`` collection, once a layer:
``aux_loss`` (the coefficients times the two terms below: what
:func:`aux_loss` averages over layers into the task loss),
``balance_loss`` (``e * sum_e f_e P_e``, ``f_e`` the share of ALL
``T x top_k`` picks that chose e, ``P_e`` the mean router probability),
``z_loss`` (``mean_t logsumexp(logits_t)^2``), ``expert_counts``
(the picks of each of ALL ``num_experts``) and, of a share,
``held_counts`` (its groups' sizes) and ``overflowed`` (1 where they
exceed the compact buffer).  :func:`routing_stats` reduces them for the
step's metrics.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from dlrover_tpu.accel.parallel.mesh import (ambient_mesh,
                                              with_logical_constraint)
from dlrover_tpu.utils.profiler import device_scope


# (rows, contraction, columns) of one tile of the grouped matmul.  Chosen on
# the v5e at OLMoE's shapes, 32768 rows in 64 uneven groups against
# [64, 2048, 1024] and [64, 1024, 2048], forward + backward of the three
# matmuls (my chip runs, PR 26): (256, 1024, 1024) 15.5 ms, (512, 1024,
# 1024) 16.8, (128, 1024, 1024) 17.4, (256, 1024, 512) and (256, 512, 1024)
# 17.7, (256, 2048, 512) 19.0, (512, 512, 512) 20.9, (64, 1024, 1024) 24.4,
# the kernel's default (128, 128, 128) 149 ms; ``jax.lax.ragged_dot``
# 23.5 ms.  A side of 2048 beside one of 1024, and (1024, 1024, 1024), do
# not fit the chip's fast memory.  The time does not move with the skew.
GMM_TILING = (256, 1024, 1024)

# Rows of the sorted buffer of a layer that holds a share of its experts,
# over the picks an even routing sends it, as (numerator, denominator).  ONE
# rule (:func:`buffer_rows`) for the trained layer below and the served one
# (serving/latent.py ``sparse_mlp``), which share it and nothing else:
# serving places its rows by 0 / 1 matmuls and walks every window with the
# kernels, which PR 51 measured for decode-sized forwards.  Chosen there, on
# the v5e at granite's prompt chunk (512 tokens x top 10, 18 of 72 experts
# held, hidden 4096, width 768; my chip runs, PR 51): sixteen seeded routings
# sent 1 245-1 328 picks where even is 1 280, and what is around the grouped
# matmuls costs by the row (placing 125 us and bringing back 221 us at 2 048
# rows) while the matmuls themselves do not (768 / 771 / 808 us at 1 792 /
# 2 048 / 5 120 rows: they walk the groups, not the buffer).  5 / 4 would
# save ~40 us a layer and walk twice at a load 9 % over even; 3 / 2 walks
# once up to 50 %.  In training (builder's chip runs, PR 56, which the driver
# never measured): ``train-conv-moe-8k`` overflowed in no layer of 119 steps,
# ``train-hybrid-8k`` (Zipf ids at random router weights, a load of 14.6-18.8
# x the mean) in ONE of its eight sparse layers in most steps of three seeds
# in four; a floor fitted to that cell, 49 152 rows, was slower (978 ms a
# step against 858) and was struck in review.
BUFFER_HEADROOM = (3, 2)
# Rows of one segment of the walk behind an overflowing buffer: rows of ONE
# expert, so that its matmuls are plain ones.  The MXU's side, because the
# walk is in every sparse layer's program and has to be short: compiled for
# a described v5e at ``train-hybrid-8k``'s widths, XLA's matmul of 128 or 256
# rows is 0.07 MB of executable and of 512 rows 0.14-0.18, its gather of
# float32 rows 0.18 / 0.41 / 0.41 (PR 57).  A segment's time is its expert's
# weights read and their gradient's float32 sum carried, not its rows.
SEGMENT_ROWS = 128


def buffer_rows(picks: int, held: int, num_experts: int) -> int:
    """Rows of the sorted buffer for ``picks`` (tokens x ``top_k``) of
    which an even routing sends ``held / num_experts`` here: the smallest
    multiple of the grouped matmul's row tile not under ``BUFFER_HEADROOM``
    times that, and never more than ``picks`` (all of them where every
    expert is held)."""
    num, den = BUFFER_HEADROOM
    tile = GMM_TILING[0]
    rows = -(-num * picks * held // (den * num_experts))
    return min(picks, -(-rows // tile) * tile)


# the expert weights of a ``MoEMLP``, as its parameters name them
_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _interpret() -> bool:
    """Off the TPU the kernel runs in Pallas's interpreter: the same
    program, slowly (the CPU tests)."""
    return jax.default_backend() != "tpu"


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   stacked: Optional[Tuple[jax.Array, jax.Array]] = None
                   ) -> jax.Array:
    """``lhs[rows of group g] @ rhs[g]`` for contiguous row groups: the
    ``megablox`` kernels JAX ships, forward and backward (``tgmm`` makes
    the weights' gradient), float32 accumulation.

    lhs [n, k]; rhs [groups, k, m]; group_sizes [groups] int32 summing to
    n (less where rows behind the last group are no group's).  Returns
    [n, m] in ``lhs.dtype``.

    ``stacked=(stack, layer)`` says where ``rhs`` lies: it is row ``layer``
    of ``stack`` [layers, groups, k, m], the weights of all layers as a scan
    over layers holds them.  The kernel then reads its tiles THERE, the
    layer added to the group its index map selects, and ``rhs`` is never
    made: a Pallas call, unlike XLA's own dots, cannot take a slice of the
    stack as its operand, so XLA copies one out before every call (0.8 ms
    for OLMoE's 268 MB, six times a layer and step).  ``rhs`` still stands
    for the layer's weights in the derivative: its gradient, one layer's,
    is what the scan stacks, and ``stack`` gets none (pass it under
    ``stop_gradient``).  Only on one device (``LlamaModel`` decides).

    On a mesh of several the operands are replicated first, and so is the
    result (whose constraint does the same for the cotangent on the way
    back): a Pallas call is not partitioned, and left to itself GSPMD
    shards the interpreter's loops on the CPU meshes of the tests, with
    collectives inside them that the CPU runtime can deadlock on."""
    n, k = lhs.shape
    tm, tk, tn = GMM_TILING
    # a tile's rows must divide n; its other sides may overhang
    tiling = (math.gcd(n, tm), min(tk, k), min(tn, rhs.shape[2]))
    if stacked is None:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        out = gmm(_replicated(lhs), _replicated(rhs), group_sizes, lhs.dtype,
                  tiling, interpret=_interpret())
        return _replicated(out)
    stack, layer = stacked
    # All layers' groups in one row, and the layer as a NEGATIVE first group:
    # ``group_offset`` is ``megablox``'s parameter for a shard of the groups,
    # used here for what its documentation does not name.  Three things in
    # ``gmm`` (jax 0.9.0) make that read this layer's tiles and only those;
    # a JAX that moves one of them would read another layer's weights, and
    # tests/test_moe.py's bit-exact comparisons with the sliced call are
    # what says so (run them on every JAX upgrade):
    #  - the right-hand side's index map is ``group_ids[tile] -
    #    group_offset``, so group g is read at row ``layer * groups + g``;
    #  - ``make_group_metadata`` keeps group g where ``start_group <= g <=
    #    start_group + rhs.shape[0] - 1``: the first is not above 0 and the
    #    last not below ``groups - 1``, so every group is kept, and no tile
    #    is rolled to the front (no group's number is below ``start_group``);
    #  - ``rhs.shape[0]`` (layers x groups) is not below the number of
    #    groups, so ``_zero_uninitialized_memory`` does not run.
    return _gmm_in_stack(lhs, rhs, group_sizes,
                         stack.reshape(-1, *stack.shape[2:]),
                         -(layer.astype(jnp.int32) * group_sizes.shape[0]),
                         tiling)


def stacked_expert_weights(scanned, dtype):
    """For the scan over layers that owns ``scanned`` (``{layer's name:
    the parameters of its MLP}``, every leaf stacked over the scan's
    steps): what :class:`MoEMLP` takes as ``stacked[0]`` in each sparse
    layer of a step, ``{layer's name: {"w_gate": [steps, experts, hidden,
    width], ...}}`` under ``stop_gradient``.  None where no kernel could
    read a stack: no layer is sparse, or the weights are kept in another
    dtype than the matmuls' ``dtype`` (a layer's cast is a copy of its
    own)."""
    stacks = {layer: {name: mlp[name] for name in _EXPERT_WEIGHTS}
              for layer, mlp in scanned.items() if _EXPERT_WEIGHTS[0] in mlp}
    leaves = jax.tree_util.tree_leaves(stacks)
    if not leaves or any(x.dtype != dtype for x in leaves):
        return None
    return jax.lax.stop_gradient(stacks)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gmm_in_stack(lhs, rhs, group_sizes, rows, first, tiling):
    """The grouped matmul of ``lhs`` with a layer's weights ``rhs``, whose
    VALUES are read from ``rows`` [layers x groups, k, m], the layer's
    starting ``-first`` groups in.  ``megablox``'s own derivative would
    make a gradient as large as ``rows``; this one makes the layer's, and
    gives it to ``rhs``."""
    return _gmm_in_stack_fwd(lhs, rhs, group_sizes, rows, first, tiling)[0]


def _gmm_in_stack_fwd(lhs, rhs, group_sizes, rows, first, tiling):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    out = gmm(lhs, rows, group_sizes, lhs.dtype, tiling, first,
              interpret=_interpret())
    return out, (lhs, group_sizes, rows, first)


def _gmm_in_stack_bwd(tiling, residuals, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    lhs, group_sizes, rows, first = residuals
    d_lhs = gmm(g, rows, group_sizes, lhs.dtype, tiling, first,
                transpose_rhs=True, interpret=_interpret())
    d_rhs = tgmm(lhs.swapaxes(0, 1), g, group_sizes, rows.dtype, tiling,
                 num_actual_groups=group_sizes.shape[0],
                 interpret=_interpret())
    return d_lhs, d_rhs, None, None, None


_gmm_in_stack.defvjp(_gmm_in_stack_fwd, _gmm_in_stack_bwd)


def _replicated(x: jax.Array) -> jax.Array:
    """``x`` constrained to no sharding on the mesh of the context (by a
    bare ``PartitionSpec``: inside the pipeline's ``shard_map`` that is the
    mesh with its manual axis, which a ``NamedSharding`` of the physical
    mesh does not match); ``x`` itself where there is no mesh."""
    if ambient_mesh() is None:
        return x
    return jax.lax.with_sharding_constraint(x, PartitionSpec())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _to_expert_order(x, order, inverse, top_k):
    """Rows of ``x`` [T, m] repeated ``top_k`` times and put in expert
    order: out[j] = x[order[j] // top_k]."""
    return x[order // top_k]


def _to_expert_order_fwd(x, order, inverse, top_k):
    return x[order // top_k], inverse


def _to_expert_order_bwd(top_k, inverse, g):
    # the transpose of a row gather is a scatter-add; with the inverse
    # permutation at hand it is a gather and a sum over each token's picks
    t = g.shape[0] // top_k
    picks = g[inverse].reshape(t, top_k, g.shape[-1])
    return picks.astype(jnp.float32).sum(axis=1).astype(g.dtype), None, None


_to_expert_order.defvjp(_to_expert_order_fwd, _to_expert_order_bwd)


@jax.custom_vjp
def _to_token_order(y, order, inverse):
    """Rows of ``y`` [T * top_k, m] from expert order back to pick order:
    out[i] = y[inverse[i]] (pick i = token i // top_k, choice i % top_k)."""
    return y[inverse]


def _to_token_order_fwd(y, order, inverse):
    return y[inverse], order


def _to_token_order_bwd(order, g):
    return g[order], None, None


_to_token_order.defvjp(_to_token_order_fwd, _to_token_order_bwd)


@jax.jit
def _sum_of_rows(rows, slot, weights=None):
    """Buffer rows back to tokens: out[i] = the sum over token i's
    ``top_k`` picks j of ``rows[slot[j, i]]`` (times ``weights[j, i]``) in
    float32, [T, m].  ``rows`` [C, m]; ``slot`` [top_k, T] holds a pick's
    row in the buffer, or C for a pick the buffer does not hold, which
    adds nothing.  A gather of T rows a CHOICE, which the compiler fuses
    into the one pass that writes the sum: ONE gather of ``[top_k, T]``
    rows is written out before it is summed (537 MB a pass in
    ``train-hybrid-8k``'s compiled step, PR 57), and rows gathered
    ``[T, top_k]`` are, at top 4, a relayout of half-filled tiles (2.66
    ms against 1.68 alone on the chip; builder, PR 56).  Jitted for its
    trace, as ``_traced_once`` below is."""
    c = rows.shape[0]
    total = jnp.zeros((slot.shape[1], rows.shape[-1]), jnp.float32)
    for j, choice in enumerate(slot):
        picked = jnp.where((choice < c)[:, None],
                           rows[jnp.minimum(choice, c - 1)],
                           jnp.zeros((), rows.dtype)).astype(jnp.float32)
        if weights is not None:
            picked = picked * weights[j][:, None]
        total = total + picked
    return total


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rows_to_tokens(y, top_p, source, slot, live, top_k):
    """The routed sum [T, m] in float32 from the compact buffer's rows
    ``y`` [C, m]: :func:`_sum_of_rows` with the router's weights ``top_p``
    [T, top_k].  ``source`` [C] is the pick a row holds, ``slot`` [top_k,
    T] the row that holds a pick, ``live`` [C, 1] the rows that hold one
    (a row behind them is never read).  Backward, a row's gradient is its
    token's times its pick's weight, and a pick's weight gets its row's
    product with its token's gradient, placed by ``slot``: gathers both,
    and no pass over ``T x top_k`` rows."""
    return _sum_of_rows(y, slot, top_p.T)


def _rows_to_tokens_fwd(y, top_p, source, slot, live, top_k):
    return (_rows_to_tokens(y, top_p, source, slot, live, top_k),
            (y, top_p, source, slot, live))


def _rows_to_tokens_bwd(top_k, residuals, g):
    y, top_p, source, slot, live = residuals
    g_rows = jnp.where(live, g[source // top_k], 0.0)      # float32 [C, m]
    y = jnp.where(live, y, jnp.zeros((), y.dtype))
    d_weight = jnp.sum(y.astype(jnp.float32) * g_rows, axis=-1)
    d_top_p = jnp.concatenate([d_weight, jnp.zeros((1,), jnp.float32)])[slot]
    return ((g_rows * top_p.reshape(-1, 1)[source]).astype(y.dtype),
            d_top_p.T.astype(top_p.dtype), None, None, None)


_rows_to_tokens.defvjp(_rows_to_tokens_fwd, _rows_to_tokens_bwd)


# A method of ``_HeldWalk`` jitted INSIDE the step's own jit, the walk (a
# hashable value) static: its trace is then made once a model and not once a
# sparse layer and pass (forward, recomputed, backward), which was 1.4 s of
# ``train-conv-moe-8k``'s warm set-up and 3 of ``train-hybrid-8k``'s (PR 57);
# the compiler inlines the calls.
_traced_once = functools.partial(jax.jit, static_argnums=0)


@dataclasses.dataclass(frozen=True)
class _HeldWalk:
    """The arithmetic of a share's compact buffer (``MoEMLP`` with
    ``experts_held``; the module's docstring), for :func:`_walked`.

    ``operands`` are ``(x [T, m], top_p [T, top_k], w_gate, w_up,
    w_down)``, what the layer is differentiated in; ``aux`` is a dict of
    what it is not: ``stacked`` (``MoEMLP.__call__``), ``order`` [T x
    top_k] (the picks in expert order, held ones ahead), ``ends`` [held]
    (where each held group ends in it), ``source`` [bound] / ``live``
    [bound, 1] / ``sizes`` [held] (the buffer's picks, which of its rows
    hold one, its groups) and ``slot`` [top_k, T] (the row that holds a
    pick; ``bound``: none)."""

    top_k: int
    bound: int
    dtype: Any
    fp8: bool

    def _quant(self):
        if self.fp8:
            from dlrover_tpu.ops.fp8 import fake_quant_fp8, grad_quant_fp8

            return fake_quant_fp8, grad_quant_fp8
        return (lambda v: v), (lambda v: v)

    def rows(self, x, aux):
        """The buffer's rows of ``x``, zeros behind the held picks."""
        with device_scope("moe_dispatch"):
            return jnp.where(aux["live"], x[aux["source"] // self.top_k],
                             jnp.zeros((), x.dtype))

    @_traced_once
    def first(self, xs, top_p, weights, aux):
        """The routed sum [T, m] in float32 of the buffer's rows ``xs``:
        the kernels, over the buffer's groups."""
        fake_quant, grad_quant = self._quant()
        stacked, sizes, live = aux["stacked"], aux["sizes"], aux["live"]

        def lies_in(name):
            return stacked and (stacked[0][name], stacked[1])

        with device_scope("moe_experts"):
            wg, wu, wd = (fake_quant(w.astype(self.dtype)) for w in weights)
            xq = fake_quant(xs)
            gate = grad_quant(
                grouped_matmul(xq, wg, sizes, lies_in("w_gate")))
            up = grad_quant(grouped_matmul(xq, wu, sizes, lies_in("w_up")))
            if self.fp8:
                # fp8 scales by the largest entry, of ``act`` forward and
                # of these two's gradients backward (the selects'
                # transposes): not one of rows that no matmul wrote
                gate, up = (jnp.where(live, v, jnp.zeros((), v.dtype))
                            for v in (gate, up))
            out = grad_quant(grouped_matmul(
                fake_quant(nn.silu(gate) * up), wd, sizes,
                lies_in("w_down")))
        with device_scope("moe_combine"):
            return _rows_to_tokens(out, top_p, aux["source"], aux["slot"],
                                   live, self.top_k)

    # ... and of the rows of expert order BEHIND the buffer, a segment at
    # a time: rows ``p`` to ``stop`` of ONE expert, so plain matmuls do

    def _segment_at(self, p, aux):
        """``(expert, stop, picks [SEGMENT_ROWS], valid)`` of the segment
        that starts at row ``p`` of expert order: it ends with its
        expert's group or ``SEGMENT_ROWS`` on."""
        ends = aux["ends"]
        expert = jnp.sum(ends <= p).astype(jnp.int32)
        stop = jnp.minimum(ends[expert], p + SEGMENT_ROWS)
        picks = jax.lax.dynamic_slice(
            jnp.pad(aux["order"], (0, SEGMENT_ROWS)), (p,), (SEGMENT_ROWS,))
        return expert, stop, picks, jnp.arange(SEGMENT_ROWS) < stop - p

    def _segment_weights(self, weights, aux):
        """``expert -> (wg, wu, wd)`` of one expert as the matmuls take
        them: read in the scan's stack where there is one (a layer's
        weights as an operand of the ``cond`` would be a copy of them,
        201 MB in ``train-hybrid-8k``, made whether it is taken or not);
        fp8 scales by a whole tensor's largest entry."""
        if self.fp8:
            fake_quant, _ = self._quant()
            weights = [fake_quant(w.astype(self.dtype)) for w in weights]
        elif aux["stacked"]:
            stack, layer = aux["stacked"]
            return lambda expert: [stack[name][layer, expert]
                                   for name in _EXPERT_WEIGHTS]
        return lambda expert: [w[expert].astype(self.dtype) for w in weights]

    def _segment(self, rows, wg, wu, wd, weight):
        """A segment's weighted results [SEGMENT_ROWS, m] in float32: the
        arithmetic of :meth:`first` for one expert (a row of zeros gives
        zeros, so nothing is selected)."""
        fake_quant, grad_quant = self._quant()

        def matmul(lhs, w):
            return grad_quant(jnp.dot(
                fake_quant(lhs), w,
                preferred_element_type=jnp.float32).astype(lhs.dtype))

        with device_scope("moe_experts"):
            out = matmul(nn.silu(matmul(rows, wg)) * matmul(rows, wu), wd)
        with device_scope("moe_combine"):
            return out.astype(jnp.float32) * weight[:, None]

    def _segment_operands(self, x, top_p, picks, valid):
        with device_scope("moe_dispatch"):
            return (jnp.where(valid[:, None], x[picks // self.top_k],
                              jnp.zeros((), x.dtype)),
                    jnp.where(valid, top_p.reshape(-1)[picks], 0.0))

    @_traced_once
    def further(self, y, operands, aux):
        """``y`` plus the results of the held picks behind the buffer."""
        x, top_p, *weights = operands
        of = self._segment_weights(weights, aux)

        def segment(carry):
            p, y = carry
            expert, stop, picks, valid = self._segment_at(p, aux)
            rows, weight = self._segment_operands(x, top_p, picks, valid)
            out = self._segment(rows, *of(expert), weight)
            with device_scope("moe_combine"):
                # a row that is no pick's adds zeros to some token
                return stop, y.at[picks // self.top_k].add(out)

        return jax.lax.while_loop(
            lambda carry: carry[0] < aux["ends"][-1], segment,
            (jnp.int32(self.bound), y))[1]

    @_traced_once
    def further_back(self, g, grads, operands, aux):
        """``grads`` (of ``operands``, the first being ``x``'s in FLOAT32)
        plus those of :meth:`further` under ``y``'s gradient ``g``.
        Nothing was kept: a segment's forward runs again.  A token's row
        gradient is summed in float32, and so is an expert's weights'
        over its segments (``summed``: the buffer's share of it, then each
        segment's), rounded where the expert's last segment writes it."""
        x, top_p, *weights = operands
        of = self._segment_weights(weights, aux)

        def segment(carry):
            p, last, summed, d_x, d_top_p, d_weights = carry
            expert, stop, picks, valid = self._segment_at(p, aux)
            rows, weight = self._segment_operands(x, top_p, picks, valid)
            with device_scope("moe_combine"):
                g_rows = jnp.where(valid[:, None], g[picks // self.top_k],
                                   0.0)
            d_rows, *d_expert, d_weight = jax.vjp(
                self._segment, rows, *of(expert), weight)[1](g_rows)
            with device_scope("moe_experts"):
                summed = [jnp.where(expert == last, so_far,
                                  whole[expert].astype(jnp.float32))
                        + d.astype(jnp.float32)
                        for so_far, whole, d in zip(summed, d_weights,
                                                    d_expert)]
                d_weights = [whole.at[expert].set(c.astype(whole.dtype))
                             for whole, c in zip(d_weights, summed)]
            with device_scope("moe_dispatch"):
                d_x = d_x.at[picks // self.top_k].add(
                    d_rows.astype(jnp.float32))
            with device_scope("moe_combine"):
                d_top_p = d_top_p.reshape(-1).at[picks].add(
                    d_weight.astype(d_top_p.dtype)).reshape(d_top_p.shape)
            return stop, expert, summed, d_x, d_top_p, d_weights

        d_x, d_top_p, *d_weights = grads
        summed = [jnp.zeros(w.shape[1:], jnp.float32) for w in d_weights]
        *_, d_x, d_top_p, d_weights = jax.lax.while_loop(
            lambda carry: carry[0] < aux["ends"][-1], segment,
            (jnp.int32(self.bound), jnp.int32(-1), summed, d_x, d_top_p,
             d_weights))
        return (d_x, d_top_p, *d_weights)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _walked(walk: _HeldWalk, operands, aux):
    """The routed sum [T, m] in float32 of a share's layer: ``walk.first``
    through the compact buffer, in line, and ``walk.further`` behind ONE
    ``cond`` where the held picks overflow it.  Differentiable in
    ``operands``.

    The derivative is written out because JAX's own, through a ``cond``,
    makes every branch write whatever any branch keeps for its backward
    (zeros where it keeps none), and keeps copies of the operands it only
    passes on, the scan's stacked expert weights among them: 17.0 GB of
    the chip's 15.75 in ``train-conv-moe-8k`` (PR 56).  Here the first walk
    keeps what its backward needs, as JAX would, and the further one
    nothing.  ``x``'s gradient is summed over a token's picks in float32,
    the buffer's and the overflow's, and rounded once."""
    x, top_p, *weights = operands
    return _further(walk, walk.first(walk.rows(x, aux), top_p, weights, aux),
                    operands, aux)


def _further(walk, y, operands, aux):
    return jax.lax.cond(aux["ends"][-1] > walk.bound,
                        lambda y: walk.further(y, operands, aux),
                        lambda y: y, y)


def _walked_fwd(walk, operands, aux):
    x, top_p, *weights = operands
    y, back = jax.vjp(
        lambda xs, top_p, weights: walk.first(xs, top_p, weights, aux),
        walk.rows(x, aux), top_p, weights)
    return _further(walk, y, operands, aux), (operands, aux, back)


def _walked_bwd(walk, residuals, g):
    operands, aux, back = residuals
    d_rows, d_top_p, d_weights = back(g)
    with device_scope("moe_dispatch"):
        # the transpose of ``walk.rows``: a gather too
        d_x = _sum_of_rows(d_rows, aux["slot"])
    d_x, *rest = jax.lax.cond(
        aux["ends"][-1] > walk.bound,
        lambda grads: walk.further_back(g, grads, operands, aux),
        lambda grads: grads, (d_x, d_top_p, *d_weights))
    return (d_x.astype(operands[0].dtype), *rest), None


_walked.defvjp(_walked_fwd, _walked_bwd)


def _leaves_named(tree, name: str):
    """The leaves of a ``moe_losses`` tree sown under ``name``, each
    flattened: one entry a layer under ``nn.scan``'s stacking or not."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [leaf for path, leaf in flat
            if any(getattr(p, "key", None) == name for p in path)]


def _layer_mean(moe_losses, name: str):
    """Mean over layers of the scalar sown under ``name``; None for a
    model that sowed none."""
    leaves = _leaves_named(moe_losses, name)
    if not leaves:
        return None
    return jnp.mean(jnp.concatenate(
        [jnp.ravel(leaf).astype(jnp.float32) for leaf in leaves]))


def aux_loss(moe_losses) -> jax.Array:
    """MEAN over layers of the sown ``aux_loss`` (zero for a dense model):
    a depth cut does not rescale the term."""
    mean = _layer_mean(moe_losses, "aux_loss")
    return jnp.zeros((), jnp.float32) if mean is None else mean


def routing_stats(moe_losses) -> Dict[str, jax.Array]:
    """The step's routing metrics from the sown collection ({} for a dense
    model): ``moe_load_max`` / ``moe_load_min`` are the worst layer's
    largest / smallest group over the mean group (1.0 = even), the two
    losses are means over layers, without their coefficients.  Of a model
    whose layers hold a share of their experts (``experts_held``), the
    load is over the HELD groups, ``moe_picks_held`` is the picks on them
    summed over layers, ``moe_held_share`` that over all picks and
    ``moe_overflow_layers`` the layers whose held picks exceeded the
    compact buffer (``MoEMLP``: the rare walk behind its ``cond`` ran)."""
    def stacked(name):
        return jnp.concatenate(
            [c.reshape(-1, c.shape[-1]).astype(jnp.float32)
             for c in _leaves_named(moe_losses, name)])

    if not _leaves_named(moe_losses, "expert_counts"):
        return {}
    counts = stacked("expert_counts")
    mean = jnp.mean(counts, axis=-1, keepdims=True)
    held = {}
    if _leaves_named(moe_losses, "held_counts"):
        # one share of an expert-parallel group: the load is over the
        # groups it HOLDS (a layer's router may send it nothing at all),
        # and its picks are counted beside all picks
        picks, counts = jnp.sum(counts), stacked("held_counts")
        mean = jnp.maximum(jnp.mean(counts, axis=-1, keepdims=True), 1.0)
        held = {"moe_picks_held": jnp.sum(counts),
                "moe_held_share": jnp.sum(counts) / picks,
                "moe_overflow_layers": sum(
                    (jnp.sum(c.astype(jnp.float32))
                     for c in _leaves_named(moe_losses, "overflowed")),
                    jnp.zeros((), jnp.float32))}
    load = counts / mean
    return {
        "moe_load_max": jnp.max(load),
        "moe_load_min": jnp.min(load),
        "moe_balance_loss": _layer_mean(moe_losses, "balance_loss"),
        "moe_z_loss": _layer_mean(moe_losses, "z_loss"),
        **held,
    }


def route(logits: jax.Array, top_k: int, score_fn: str,
          norm_topk_prob: bool, routed_scale: float,
          select_bias: Optional[jax.Array] = None):
    """The router's arithmetic, for the training layer and the served one
    (serving/latent.py): ``logits`` [t, experts] float32 -> ``(weights
    [t, top_k], experts [t, top_k], probs [t, experts])``.  ``softmax``
    scores over all experts, or the ``sigmoid`` of each logit (``probs``
    then the scores over their sum); the ``top_k`` largest scores, with
    ``select_bias`` [experts] the largest of ``score + bias`` while the
    weights stay the scores' own (``noaux_tc``); ``norm_topk_prob``
    divides the weights by their sum, ``routed_scale`` multiplies them."""
    # replicated: the picks are sorted over ALL tokens by the caller, and
    # XLA's partitioner aborts on a batch-sharded top-k inside the
    # pipeline's partly manual shard_map
    if score_fn == "softmax":
        scores = probs = _replicated(jax.nn.softmax(logits, axis=-1))
    else:
        scores = _replicated(jax.nn.sigmoid(logits))
    if select_bias is None:
        top_p, top_e = jax.lax.top_k(scores, top_k)  # [t, k]
    else:
        _, top_e = jax.lax.top_k(scores + select_bias, top_k)
        top_p = jnp.take_along_axis(scores, top_e, axis=-1)
    if score_fn != "softmax":
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    if norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if routed_scale != 1.0:
        top_p = top_p * routed_scale
    return top_p, top_e, probs


class MoEMLP(nn.Module):
    """Sparse SwiGLU FFN (drop-in for the dense MLP): every token's
    ``top_k`` experts, none dropped.

    Expert params carry the ``expert`` logical axis, so the rules table
    shards them over ``ep`` (``num_experts`` divisible by its size).
    """

    hidden_size: int
    intermediate_size: int
    num_experts: int
    top_k: int = 2
    # renormalise the top-k weights to sum to one (Mixtral) or keep the
    # softmax's own values (OLMoE, ``norm_topk_prob: false``)
    norm_topk_prob: bool = True
    aux_loss_coef: float = 0.01
    z_loss_coef: float = 1e-3
    dtype: type = jnp.bfloat16
    param_dtype: type = jnp.float32
    # fp8 expert GEMMs (the model's FLOPs majority); the router stays
    # f32 — routing decisions are the standard fp8-recipe exclusion
    fp8: bool = False
    # "softmax" over all experts, or the "sigmoid" of each logit
    score_fn: str = "softmax"
    routed_scale: float = 1.0
    # one shared expert of this width on every token (0 = none)
    shared_width: int = 0
    # (first, count) of the experts this device holds (None = all)
    experts_held: Optional[Tuple[int, int]] = None
    # a bias on the scores for the CHOICE of the top_k only (route):
    # zeros at initialisation, or N(0, select_bias_std)
    select_bias: bool = False
    select_bias_std: float = 0.0
    # LeCun fan-in of ONE expert's matrix.  Off, the fan-in is the whole
    # stack's (the expert axis counts as receptive field), which makes an
    # expert's output (experts^-1/2)^3 of a dense layer's at
    # initialisation: 1/512 at 64 experts, too little for a comparison
    # with a reference to see the routed sum at all
    per_expert_init: bool = False

    @nn.compact
    def __call__(self, x: jax.Array, stacked=None) -> jax.Array:
        """``stacked``, inside a scan over layers: the three expert
        weights as the scan stacks them (``{"w_gate": [layers, experts,
        hidden, width], ...}``, in ``self.dtype``, under ``stop_gradient``)
        and this layer's index, for :func:`grouped_matmul` to read them in
        place."""
        b, s, m = x.shape
        e, h, k = self.num_experts, self.intermediate_size, self.top_k
        t = b * s
        init = nn.initializers.lecun_normal()

        router = nn.DenseGeneral(
            e,
            use_bias=False,
            dtype=jnp.float32,
            param_dtype=self.param_dtype,
            kernel_init=nn.with_logical_partitioning(init, ("embed", "expert")),
            name="router",
        )

        expert_init = (nn.initializers.lecun_normal(batch_axis=(0,))
                       if self.per_expert_init else init)

        def expert_param(name, shape, axes):
            return self.param(
                name,
                nn.with_logical_partitioning(expert_init, axes),
                shape,
                self.param_dtype,
            )

        if self.score_fn not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router score_fn {self.score_fn!r}")
        first, held = self.experts_held or (0, e)
        w_gate = expert_param(
            "w_gate", (held, m, h), ("expert", "embed", "mlp")
        )
        w_up = expert_param("w_up", (held, m, h), ("expert", "embed", "mlp"))
        w_down = expert_param(
            "w_down", (held, h, m), ("expert", "mlp", "embed")
        )

        with device_scope("moe_route"):
            logits = router(x).reshape(t, e)  # f32
            bias = None
            if self.select_bias:
                # ``noaux_tc``: moved by the load, never by a gradient
                bias = jax.lax.stop_gradient(self.param(
                    "select_bias", nn.with_logical_partitioning(
                        nn.initializers.normal(self.select_bias_std)
                        if self.select_bias_std
                        else nn.initializers.zeros_init(), ("expert",)),
                    (e,), jnp.float32))
            top_p, top_e, probs = route(
                logits, k, self.score_fn, self.norm_topk_prob,
                self.routed_scale, bias)
            picks = top_e.reshape(t * k)
            counts = jnp.sum(jax.nn.one_hot(picks, e, dtype=jnp.int32), axis=0)
            share = counts.astype(jnp.float32) / (t * k)
            balance = e * jnp.sum(share * jnp.mean(probs, axis=0))
            z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
        for name, value in (
            ("aux_loss", self.aux_loss_coef * balance + self.z_loss_coef * z),
            ("balance_loss", balance),
            ("z_loss", z),
            ("expert_counts", counts),
        ):
            # one layer, one value: under nn.scan the collection stacks
            self.sow("moe_losses", name, value,
                     reduce_fn=lambda _, new: new, init_fn=lambda: None)

        if self.experts_held is not None:
            # this share's picks ahead of all others, by held expert; the
            # groups are the held experts', and end where the others start
            with device_scope("moe_route"):
                local = picks - first
                is_held = jnp.logical_and(local >= 0, local < held)
                picks = jnp.where(is_held, local, held)
                counts = counts[first:first + held]
            self.sow("moe_losses", "held_counts", counts,
                     reduce_fn=lambda _, new: new, init_fn=lambda: None)

        bound = buffer_rows(t * k, held, e)
        if bound < t * k:
            # a share's compact buffer: the picks it HOLDS, ``bound`` rows
            with device_scope("moe_dispatch"):
                order = jnp.argsort(picks, stable=True)  # held ones ahead
                inverse = jnp.argsort(order)
                ends = jnp.cumsum(counts)
                aux = {
                    "stacked": stacked, "order": order, "ends": ends,
                    "source": order[:bound],
                    "live": (jnp.arange(bound) < ends[-1])[:, None],
                    "sizes": jnp.minimum(ends, bound)
                    - jnp.minimum(ends - counts, bound),
                    "slot": jnp.where(
                        inverse < jnp.minimum(ends[-1], bound), inverse,
                        bound).reshape(t, k).T,
                }
            self.sow("moe_losses", "overflowed",
                     (ends[-1] > bound).astype(jnp.int32),
                     reduce_fn=lambda _, new: new, init_fn=lambda: None)
            y = _walked(
                _HeldWalk(k, bound, self.dtype, self.fp8),
                (x.reshape(t, m).astype(self.dtype), top_p, w_gate, w_up,
                 w_down), aux)
        else:
            with device_scope("moe_dispatch"):
                order = jnp.argsort(picks, stable=True)  # expert-major
                inverse = jnp.argsort(order)
                xs = _to_expert_order(
                    x.reshape(t, m).astype(self.dtype), order, inverse, k)
                if self.experts_held is not None:
                    # rows behind the held groups are no expert's: the
                    # grouped matmuls leave them unwritten, forward
                    # (``out``) and backward (the rows' gradient, which
                    # this select's transpose zeroes)
                    live = (jnp.arange(t * k) < jnp.sum(counts))[:, None]
                    xs = jnp.where(live, xs, jnp.zeros((), xs.dtype))

            if self.fp8:
                from dlrover_tpu.ops.fp8 import fake_quant_fp8, grad_quant_fp8
            else:
                fake_quant_fp8 = grad_quant_fp8 = lambda x: x  # noqa: E731

            def lies_in(name):
                return stacked and (stacked[0][name], stacked[1])

            with device_scope("moe_experts"):
                wg = fake_quant_fp8(w_gate.astype(self.dtype))
                wu = fake_quant_fp8(w_up.astype(self.dtype))
                wd = fake_quant_fp8(w_down.astype(self.dtype))
                xq = fake_quant_fp8(xs)
                gate = grad_quant_fp8(
                    grouped_matmul(xq, wg, counts, lies_in("w_gate")))
                up = grad_quant_fp8(
                    grouped_matmul(xq, wu, counts, lies_in("w_up")))
                act = nn.silu(gate) * up
                if self.fp8 and self.experts_held is not None:
                    # fp8 scales by the largest entry: not one of rows that
                    # no matmul wrote
                    act = jnp.where(live, act, jnp.zeros((), act.dtype))
                out = grad_quant_fp8(grouped_matmul(
                    fake_quant_fp8(act), wd, counts, lies_in("w_down")))

            with device_scope("moe_combine"):
                if self.experts_held is not None:
                    out = jnp.where(live, out, jnp.zeros((), out.dtype))
                out = _to_token_order(out, order, inverse).reshape(t, k, m)
                y = jnp.sum(out.astype(jnp.float32) * top_p[..., None], axis=1)
        if self.shared_width:
            with device_scope("moe_shared"):
                y = y + self._shared_expert(x).reshape(t, m)
        with device_scope("moe_combine"):
            y = y.astype(self.dtype).reshape(b, s, m)
            return with_logical_constraint(y, ("batch", "seq", "act_embed"))

    def _shared_expert(self, x: jax.Array) -> jax.Array:
        """The dense SwiGLU expert every token visits: matmuls in
        ``self.dtype``, the result in float32 for the routed sum."""
        init = nn.initializers.lecun_normal()
        if self.fp8:
            from dlrover_tpu.ops.fp8 import fp8_dot_general as dot_general
        else:
            dot_general = jax.lax.dot_general

        def dense(features, axes, name):
            return nn.DenseGeneral(
                features, use_bias=False, dtype=self.dtype,
                param_dtype=self.param_dtype, dot_general=dot_general,
                kernel_init=nn.with_logical_partitioning(init, axes),
                name=name)

        gate = dense(self.shared_width, ("embed", "mlp"), "shared_gate")(x)
        up = dense(self.shared_width, ("embed", "mlp"), "shared_up")(x)
        act = with_logical_constraint(nn.silu(gate) * up,
                                      ("batch", "seq", "mlp"))
        return dense(self.hidden_size, ("mlp", "embed"), "shared_down")(
            act).astype(jnp.float32)
