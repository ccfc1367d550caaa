"""The main paths' Pallas kernels, compiled for a TPU v5e that is
DESCRIBED, not attached (the ``on-chip-measurement`` guide, section 2).

Interpret-mode parity cannot see what the chip's compiler refuses — a
slice off the tiling, a kernel GSPMD cannot partition — and PR 21 found
both in this tree.  These compiles guard every later PR at no chip time.
Nothing runs, so nothing here says anything about results or speed.

Rules of this file: the topology is described inside a module-scoped
fixture (never at import or collection — only one process may load the
TPU library, and every xdist worker imports every test file), the
compiles run in the test's own process, everything stays in this ONE
file, and JAX's persistent compilation cache is off around them (an
entry compiled for a described chip cannot be read back without one).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from dlrover_tpu.ops.attention import dot_product_attention
from dlrover_tpu.ops.pallas.flash_attention import flash_attention
from dlrover_tpu.ops.pallas.paged_attention import paged_decode_attention
from dlrover_tpu.ops.pallas.quant_matmul import int8_matmul


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _attn_loss(fn):
    def loss(q, k, v, seg):
        return fn(q, k, v, seg).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("segments", [False, True])
def test_flash_fwd_bwd_7b_width_one_chip(one_chip, segments):
    """llama2_7b attention (32 MHA heads of 128) at seq 4096, forward
    and backward, with and without segment ids."""
    x = jax.ShapeDtypeStruct((1, 4096, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    seg = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip) \
        if segments else None
    grad = _attn_loss(lambda q, k, v, s: flash_attention(
        q, k, v, causal=True, segment_ids=s))
    text = jax.jit(grad).lower(x, x, x, seg).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("heads,kv_heads", [(32, 8), (16, 16)])
def test_flash_fwd_bwd_cells_heads_one_chip(one_chip, heads, kv_heads):
    """The training cells' attention (Mistral-7B: 32 query / 8 KV heads;
    OLMoE: 16 / 16) at seq 4096: the tiled kernels' slices of the DMA
    block (256- and 512-wide, rows from a tile's edge) are on the tiling."""
    q = jax.ShapeDtypeStruct((1, 4096, heads, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4096, kv_heads, 128), jnp.bfloat16,
                              sharding=one_chip)
    grad = _attn_loss(lambda q, k, v, s: flash_attention(
        q, k, v, causal=True, segment_ids=s))
    text = jax.jit(grad).lower(q, kv, kv, None).compile().as_text()
    assert text.count("tpu_custom_call") >= 3  # forward, dq, dkv


def test_flash_fwd_bwd_at_a_head_of_64_one_chip(one_chip):
    """The ``train-conv-moe-8k`` cell's attention (LFM2-8B-A1B: 32 query /
    8 KV heads of 64) at seq 8192, 2 sequences: a head fills half a vreg
    row and half the MXU's contraction, and the forward, ``dq`` and
    ``dkv`` kernels still lower at the tile defaults a head of 128 reads."""
    q = jax.ShapeDtypeStruct((2, 8192, 32, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 8192, 8, 64), jnp.bfloat16,
                              sharding=one_chip)
    grad = _attn_loss(lambda q, k, v, s: flash_attention(
        q, k, v, causal=True, segment_ids=s))
    text = jax.jit(grad).lower(q, kv, kv, None).compile().as_text()
    assert text.count("tpu_custom_call") >= 3  # forward, dq, dkv


@pytest.mark.parametrize("heads", [64, 48])
def test_window_flash_fwd_bwd_hybrid_cell_one_chip(one_chip, heads):
    """The ``train-hybrid-8k`` cell's window layers (Laguna-XS.2: 64 query
    heads over 8 KV heads; 48 / 8 is its full layers' count, here with the
    window too) at seq 8192, 2 sequences, w 512: the three named kernels,
    whose k-grid walks back from the row's last live block."""
    q = jax.ShapeDtypeStruct((2, 8192, heads, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 8192, 8, 128), jnp.bfloat16,
                              sharding=one_chip)
    grad = _attn_loss(lambda q, k, v, s: flash_attention(
        q, k, v, causal=True, window=512))
    text = jax.jit(grad).lower(q, kv, kv, None).compile().as_text()
    for name in ("flash_window_fwd", "flash_window_dq", "flash_window_dkv"):
        assert name in text
    assert text.count("tpu_custom_call") >= 3


def test_flash_under_fsdp_mesh_is_shard_mapped(topo):
    """The default four-chip mesh (fsdp=4): the dispatch must wrap the
    kernel in shard_map over the batch — a bare Mosaic call on GSPMD
    arrays is refused ('cannot be automatically partitioned') — and
    must not gather q/k/v to do it.  ``use_pallas=True`` is passed
    because ``jax.default_backend()`` is the CPU here."""
    from dlrover_tpu.accel.parallel.mesh import MeshSpec

    mesh = MeshSpec.for_device_count(4).build_mesh(topo.devices)
    assert dict(mesh.shape)["fsdp"] == 4
    sharding = NamedSharding(mesh, P(("dp", "fsdp"), None, "tp", None))
    x = jax.ShapeDtypeStruct((4, 4096, 32, 128), jnp.bfloat16,
                             sharding=sharding)
    grad = _attn_loss(lambda q, k, v, s: dot_product_attention(
        q, k, v, causal=True, use_pallas=True))
    with mesh:
        compiled = jax.jit(grad).lower(x, x, x, None).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text
    # each device works on its own quarter of the batch
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device == 3 * (4096 * 32 * 128 * 2)


# the bench geometry (h2048, GQA 16:4), the smoke's (llama2_7b, 32 MHA
# heads) and the ``serve-batch-closed`` cell's own (mistral-7b-serve:
# GQA 32:8, 8 slots of 2305 rows = 145 pages, which the kernel pads to
# 10 groups of 16, in a pool of 1161 blocks); head_dim 128, 16-row pages
_GEOMETRIES = {
    "bench16x4": dict(heads=16, kv_heads=4),
    "llama2_7b": dict(heads=32, kv_heads=32),
    "mistral7b": dict(heads=32, kv_heads=8, mb=145, nb=1161),
}


def _paged_args(one_chip, heads, kv_heads, kv_dtype, d=128, bs=16,
                nb=512, slots=8, mb=32):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    code = {"bf16": jnp.bfloat16, "int8": jnp.int8}[kv_dtype]
    pool = s((nb, bs, kv_heads, d), code)
    scale = None if kv_dtype == "bf16" else \
        s((nb, bs, kv_heads), jnp.bfloat16)
    return (s((slots, heads, d), jnp.bfloat16), pool, pool,
            s((slots, mb), jnp.int32), s((slots,), jnp.int32),
            scale, scale)


def _paged(q, k, v, table, lengths, ks, vs):
    return paged_decode_attention(q, k, v, table, lengths,
                                  k_scale=ks, v_scale=vs)


@pytest.mark.parametrize("geometry", sorted(_GEOMETRIES))
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode_kernel_compiles(one_chip, geometry, kv_dtype):
    """bf16 and int8 pools stream in place, the group loop's trip
    count read from ``lengths`` at run time.  int8 was refused before
    PR 21 ('Slice shape along dimension 2 must be aligned to tiling
    (128), but is 4': the per-token scale slice)."""
    args = _paged_args(one_chip, kv_dtype=kv_dtype,
                       **_GEOMETRIES[geometry])
    text = jax.jit(_paged).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_index_score_kernel_compiles_at_glm5_widths(one_chip):
    """``paged_index_scores`` at the served cell's geometry: 32 slots, 32
    index heads of 128, pages of 128 rows, a table of 259."""
    from dlrover_tpu.ops.pallas.paged_index import paged_index_scores

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(paged_index_scores).lower(
        s((32, 32, 128), jnp.bfloat16), s((32, 32), jnp.float32),
        s((2700, 128, 128), jnp.bfloat16), s((32, 259), jnp.int32),
        s((32,), jnp.int32)).compile().as_text()
    assert "paged_index_scores" in text and "tpu_custom_call" in text


def test_selected_attention_kernel_compiles_at_keye_widths(one_chip):
    """``paged_decode_attention`` under a selection's bias at
    ``serve-docqa-sparse-gqa``'s geometry (32 slots, 32 query heads on 4
    KV heads of 128, pages of 128 rows, a table of 258): the kernel that
    streams a shared run of pages once, every slot's bias in VMEM beside
    the runs' carries (its own ``vmem_limit_bytes``), the runs derived in
    the same program."""
    from dlrover_tpu.ops.pallas.paged_attention import SELECTED_ATTENTION

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def run(q, k, v, table, lengths, bias):
        return paged_decode_attention(q, k, v, table, lengths, bias=bias)

    pool = s((2200, 128, 4, 128), jnp.bfloat16)
    text = jax.jit(run).lower(
        s((32, 32, 128), jnp.bfloat16), pool, pool, s((32, 258), jnp.int32),
        s((32,), jnp.int32), s((32, 258 * 128), jnp.float32)
    ).compile().as_text()
    assert SELECTED_ATTENTION in text and "tpu_custom_call" in text


def test_latent_prefill_kernel_compiles_under_its_scope(one_chip):
    """A prefill chunk's query run at the served cell's geometry (512
    queries of which ``n_real`` are the prompt's, 64 heads, rows of 640, a
    table of 264 pages of 128): the
    selection, then ``mla_prefill_attention`` as ONE custom call that the
    program's own scopes place under ``mla_attn`` by the name the trace
    will show, ``mla_prefill_attn``."""
    from dlrover_tpu.models.llama import LlamaConfig
    from dlrover_tpu.serving import latent
    from dlrover_tpu.utils.profiler import device_scope, parse_program

    cfg = LlamaConfig.glm5(num_layers=1, dtype=jnp.bfloat16)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def run(qq, q_i, w, q_pos, lat, idx, table, n_real):
        with device_scope("prefill_chunk"):
            return latent._attend_run(
                qq, q_i, w, q_pos, lat, idx, table, cfg,
                latent.KEY_BLOCK_PAGES, "pallas", n_real=n_real)

    lowered = jax.jit(run).lower(
        s((512, 64, 640), jnp.bfloat16), s((512, 32, 128), jnp.bfloat16),
        s((512, 32), jnp.float32), s((512,), jnp.int32),
        s((2700, 128, 640), jnp.bfloat16), s((2700, 128, 128), jnp.bfloat16),
        s((264,), jnp.int32), s((), jnp.int32))
    table = parse_program(
        "run", lowered.compile().as_text(),
        {"prefill_chunk", "dsa_index", "dsa_select", "mla_attn"},
        lowered.as_text(debug_info=True))
    assert table.complete, table.missing
    kernels = {n: scope for n, scope in table.scope_of.items()
               if n.startswith("mla_prefill_attn")}
    assert kernels and set(kernels.values()) == {"mla_attn"}, kernels


def test_latent_decode_kernel_compiles_at_sarvam_widths(one_chip):
    """``mla_decode_attention`` at the served cell's geometry: 32 slots,
    64 heads, rows of 640, pages of 128 rows, a table of 258."""
    from dlrover_tpu.ops.pallas.mla_decode import mla_decode_attention

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(
        lambda q, p, t, n: mla_decode_attention(q, p, t, n, c=512,
                                                scale=0.135)).lower(
        s((32, 64, 640), jnp.bfloat16), s((2500, 128, 640), jnp.bfloat16),
        s((32, 258), jnp.int32), s((32,), jnp.int32)).compile().as_text()
    assert "mla_decode_attn" in text and "tpu_custom_call" in text


def _decode_forward(one_chip, cfg, seeded, cache: dict, slots: int):
    """One decode forward of a latent model (``seeded``: its seeded
    parameters' class) over ``cache``'s pools and table, lowered and
    compiled for the described chip under the scope ``decode_chunk``:
    ``(lowered, compiled)``."""
    from dlrover_tpu.serving.model import decode_step
    from dlrover_tpu.serving.params import serving_params_from_llama
    from dlrover_tpu.utils.profiler import device_scope

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    sp = jax.eval_shape(lambda: serving_params_from_llama(
        {"params": seeded(cfg, 3)}, cfg))
    S = jax.ShapeDtypeStruct

    def forward(p, c, t, pos, act):
        with device_scope("decode_chunk"):
            return decode_step(p, cfg, c, t, pos, attention_impl="pallas",
                               active=act)

    lowered = jax.jit(forward, donate_argnums=(1,)).lower(*on_chip((
        sp, dict(cache, moe_picks=S((4,), jnp.uint32),
                 watch_slot=S((), jnp.int32)),
        S((slots,), jnp.int32), S((slots,), jnp.int32),
        S((slots,), jnp.bool_))))
    return lowered, lowered.compile()


def _decode_kernel_operands(text: str) -> list:
    """The operand shapes of every ``mla_decode_attn`` call in a compiled
    program's text: table, lengths, queries, pool and, of a model with a
    learned selection, the mask."""
    import re

    calls = [line for line in text.splitlines()
             if re.search(r"%mla_decode_attn[.\d]* = .* custom-call\(", line)]
    return [re.findall(r"[a-z]+\d+\[[\d,]*\]", line.split(
        "operand_layout_constraints={")[1].split("}}")[0]) for line in calls]


def test_window_layer_kernels_compile_at_dots3_widths(one_chip):
    """A WINDOW layer of ``dots3-note-serve`` at the cell's geometry (64
    heads, rows of 1 152 over a latent of 1 024, rings of 8 pages of 128
    rows a slot, a window of 513): the decode forward's attention is the
    decode kernel over the 5 pages a window touches, a prompt chunk's the
    run kernel over the ring, each ONE custom call under ``swa_attn`` by a
    name of its own; and a FULL layer's run kernel at 128 heads (16
    queries a tile) fits the chip's fast memory."""
    from dlrover_tpu.models.llama import LlamaConfig
    from dlrover_tpu.serving import latent
    from dlrover_tpu.utils.profiler import device_scope, parse_program

    cfg = LlamaConfig.dots3_note(num_layers=6, dtype=jnp.bfloat16)
    full, spec = cfg.layer_specs[1], cfg.layer_specs[2]

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def decode(qq, pool, pos, active):
        with device_scope("decode_chunk"):
            return latent._attend_window_decode(
                qq, pool, pos, active, cfg, spec, 8, "pallas", False)

    def run(qq, q_pos, pool, slot, n_real):
        with device_scope("prefill_chunk"):
            return latent._attend_window_run(
                qq, q_pos, pool, slot, n_real, cfg, spec, 8, "pallas",
                False)

    def full_run(qq, q_i, w, q_pos, lat, idx, table, n_real):
        with device_scope("prefill_chunk"):
            return latent._attend_run(
                qq, q_i, w, q_pos, lat, idx, table, cfg,
                latent.KEY_BLOCK_PAGES, "pallas", n_real=n_real, spec=full)

    rings = s((32 * 8, 128, 1152), jnp.bfloat16)
    for fn, args, scopes, kernel, scope in (
        (decode, (s((32, 64, 1152), jnp.bfloat16), rings,
                  s((32,), jnp.int32), s((32,), jnp.bool_)),
         {"decode_chunk", "swa_attn"}, "mla_window_decode_attn",
         "swa_attn"),
        (run, (s((512, 64, 1152), jnp.bfloat16), s((512,), jnp.int32),
               rings, s((), jnp.int32), s((), jnp.int32)),
         {"prefill_chunk", "swa_attn"}, "mla_window_prefill_attn",
         "swa_attn"),
        (full_run, (s((512, 128, 640), jnp.bfloat16),
                    s((512, 64, 128), jnp.bfloat16),
                    s((512, 64), jnp.float32), s((512,), jnp.int32),
                    s((2760, 128, 640), jnp.bfloat16),
                    s((2760, 128, 128), jnp.bfloat16), s((256,), jnp.int32),
                    s((), jnp.int32)),
         {"prefill_chunk", "dsa_index", "dsa_select", "mla_attn"},
         "mla_prefill_attn", "mla_attn")):
        lowered = jax.jit(fn).lower(*args)
        table = parse_program("window", lowered.compile().as_text(), scopes,
                              lowered.as_text(debug_info=True))
        assert table.complete, table.missing
        kernels = {n: sc for n, sc in table.scope_of.items()
                   if n.split(".")[0] == kernel}
        assert kernels and set(kernels.values()) == {scope}, (
            kernel, table.scope_of)


def test_glm5_decode_forward_streams_under_the_mask(one_chip, monkeypatch):
    """One decode forward of ``glm5-serve`` at the cell's widths (32 slots,
    tables of 258 pages of 128 rows, experts 0-15 of 256, an eighth of the
    vocabulary; the leading dense layer and one sparse layer): the
    selection is a threshold and a mask.  A layer is ONE
    ``paged_index_scores`` under ``dsa_index`` and ONE ``mla_decode_attn``
    under ``mla_attn`` whose fifth operand is the float32 mask, a row a
    slot; the program sorts nothing but the router's scores (the experts'
    picks are placed by counting, ``sparse_mlp``), and holds no
    copy of ``slots x index_topk`` latent rows nor of every row a table
    could hold."""
    import re

    from dlrover_tpu.models import moe
    from dlrover_tpu.models.llama import LlamaConfig
    from dlrover_tpu.serving import latent
    from dlrover_tpu.utils.profiler import parse_program
    from perfbench.weights_glm5 import SeededGlm5Params

    monkeypatch.setattr(moe, "_interpret", lambda: False)
    cfg = LlamaConfig.glm5(
        num_layers=2, moe_first_dense=1, moe_experts_held=(0, 16),
        vocab_size=19360, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    slots, mb, bs, nb = 32, 258, 128, 2700
    S = jax.ShapeDtypeStruct
    lowered, compiled = _decode_forward(one_chip, cfg, SeededGlm5Params, {
        "latent_pool": [S((nb, bs, latent.latent_row_width(cfg)),
                          jnp.bfloat16)] * cfg.num_layers,
        "index_pool": [S((nb, bs, cfg.index_head_dim),
                         jnp.bfloat16)] * cfg.num_layers,
        "table": S((slots, mb), jnp.int32)}, slots)
    text = compiled.as_text()
    table = parse_program(
        "decode", text, {"decode_chunk", "mla_attn", "mla_proj", "dsa_index",
                         "dsa_select", "moe_route", "moe_experts",
                         "moe_shared", "mlp"},
        lowered.as_text(debug_info=True))
    assert table.complete, table.missing
    kernels = sorted((n.split(".")[0], scope)
                     for n, scope in table.scope_of.items()
                     if n.startswith(("mla_decode_attn", "paged_index")))
    assert kernels == [("mla_decode_attn", "mla_attn")] * 2 \
        + [("paged_index_scores", "dsa_index")] * 2, kernels
    # the kernel's groups of 8 pages: 264 of them, 33 792 rows of mask
    mask = f"f32[{slots},1,{-(-mb // 8) * 8 * bs}]"
    assert _decode_kernel_operands(text) == [
        [f"s32[{slots},264]", f"s32[{slots}]", f"bf16[{slots},64,640]",
         f"bf16[{nb},{bs},640]", mask]] * 2
    sorts = {table.scope_of.get(m) for m in re.findall(
        r"%(sort[.\d]*) = \S+ sort\(", text)}
    assert sorts <= {"moe_route"}, sorts
    for rows in (cfg.index_topk, mb * bs):        # latent rows, copied
        assert not re.search(rf"\[{slots},{rows},(640|512)\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2**20


def test_sarvam_decode_forward_streams_live_pages_only(one_chip,
                                                       monkeypatch):
    """One decode forward of ``sarvam-105b-serve`` at the cell's widths (32
    slots, tables of 258 pages of 128 rows, experts 0-31 of 128, a quarter
    of the vocabulary; the leading dense layer and one sparse layer):
    the attention is ONE custom call a layer under ``mla_attn`` by the
    name the trace will show, and the program's text holds no buffer of
    ``slots x max_blocks x block_size`` rows: neither the dense gather of
    every row the table could hold nor scores against them."""
    import re

    from dlrover_tpu.models import moe
    from dlrover_tpu.models.llama import LlamaConfig
    from dlrover_tpu.serving import latent
    from dlrover_tpu.utils.profiler import parse_program
    from perfbench.weights_sarvam import SeededSarvamParams

    monkeypatch.setattr(moe, "_interpret", lambda: False)
    cfg = LlamaConfig.sarvam_105b(
        num_layers=2, moe_experts_held=(0, 32), vocab_size=65536,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    slots, mb, bs, nb = 32, 258, 128, 2500
    S = jax.ShapeDtypeStruct
    lowered, compiled = _decode_forward(one_chip, cfg, SeededSarvamParams, {
        "latent_pool": [S((nb, bs, latent.latent_row_width(cfg)),
                          jnp.bfloat16)] * cfg.num_layers,
        "table": S((slots, mb), jnp.int32)}, slots)
    text = compiled.as_text()
    table = parse_program(
        "decode", text, {"decode_chunk", "mla_attn", "mla_proj",
                         "moe_route", "moe_experts", "moe_shared", "mlp"},
        lowered.as_text(debug_info=True))
    assert table.complete, table.missing
    kernels = {n: scope for n, scope in table.scope_of.items()
               if n.startswith("mla_decode_attn")}
    assert len(kernels) == cfg.num_layers \
        and set(kernels.values()) == {"mla_attn"}, kernels
    # no selection, no mask: the call the tree before the mask compiled
    assert _decode_kernel_operands(text) == [
        [f"s32[{slots},264]", f"s32[{slots}]", f"bf16[{slots},64,640]",
         f"bf16[{nb},{bs},640]"]] * cfg.num_layers
    rows = mb * bs
    assert not re.search(rf"\[{slots},(\d+,)?{rows}[,\]]", text)
    assert not re.search(rf"\[{slots},{rows},\d+\]", text)
    # the logits and a layer's activations, not gigabytes of gathered rows
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 2**20


@pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
def test_kimi_linear_programs_keep_their_state_in_place(one_chip,
                                                        monkeypatch,
                                                        program):
    """One period of kimi-linear-48b-serve (KDA, KDA, KDA, MLA) at its
    published widths and the cell's 128 slots: the decode forward holds
    ``kda_decode_step`` and the prompt chunk ``kda_chunk_fwd``, each under
    ``kda_scan``, the MLA layer its own kernel under ``mla_attn``; the
    donated states come back aliased, and nothing in the program is a
    copy of a layer's 268 MB of state."""
    from dlrover_tpu.models import moe
    from dlrover_tpu.models.llama import LlamaConfig
    from dlrover_tpu.serving import latent, linear
    from dlrover_tpu.serving.model import decode_step
    from dlrover_tpu.serving.params import serving_params_from_llama
    from dlrover_tpu.utils.profiler import device_scope, parse_program
    from perfbench.weights_kimi_linear import SeededKimiLinearParams

    monkeypatch.setattr(moe, "_interpret", lambda: False)
    cfg = LlamaConfig.kimi_linear_48b(
        num_layers=4, moe_experts_held=(0, 32), vocab_size=20480,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    slots, mb, bs, nb = 128, 33, 128, 5000

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    sp = on_chip(jax.eval_shape(lambda: serving_params_from_llama(
        {"params": SeededKimiLinearParams(cfg, 3)}, cfg)))
    S = jax.ShapeDtypeStruct
    state, conv = (a.shape for a in linear.state_shapes(
        cfg, slots).values())
    cache = on_chip({
        "latent_pool": [S((nb, bs, latent.latent_row_width(cfg)),
                          jnp.bfloat16)],
        "kda_state": [S(state, jnp.float32)] * 3,
        "kda_conv": [S(conv, jnp.bfloat16)] * 3,
        "table": S((slots, mb), jnp.int32),
        "moe_picks": S((4,), jnp.uint32),
        "watch_slot": S((), jnp.int32)})
    if program == "decode":
        def forward(p, c, t, pos, act):
            with device_scope("decode_chunk"):
                return decode_step(p, cfg, c, t, pos,
                                   attention_impl="pallas", active=act)

        args = on_chip((S((slots,), jnp.int32), S((slots,), jnp.int32),
                        S((slots,), jnp.bool_)))
        kernel, attn = "kda_decode_step", "mla_decode_attn"
    else:
        def forward(p, c, t, pos, sl, li):
            with device_scope("prefill_chunk"):
                return latent.verify_step(
                    p, cfg, c, t, pos, slots=sl, logits_index=li,
                    attention_impl="pallas")

        args = on_chip((S((1, 512), jnp.int32),) + (S((1,), jnp.int32),) * 3)
        kernel, attn = "kda_chunk_fwd", "mla_prefill_attn"
    lowered = jax.jit(forward, donate_argnums=(1,)).lower(sp, cache, *args)
    compiled = lowered.compile()
    if program == "decode":          # no selection, no mask operand
        assert _decode_kernel_operands(compiled.as_text()) == [
            [f"s32[{slots},40]", f"s32[{slots}]", f"bf16[{slots},32,640]",
             f"bf16[{nb},{bs},640]"]]
    table = parse_program(
        program, compiled.as_text(),
        {program if program != "decode" else "decode_chunk", "kda_proj",
         "kda_scan", "kda_out", "mla_attn", "mla_proj", "moe_route",
         "moe_experts", "moe_shared", "mlp"},
        lowered.as_text(debug_info=True))
    assert table.complete, table.missing
    scopes = {n: scope for n, scope in table.scope_of.items()
              if n.startswith((kernel, attn))}
    assert sorted(scopes.values()) == ["kda_scan"] * 3 + ["mla_attn"], scopes
    memory = compiled.memory_analysis()
    one_state = 128 * 32 * 128 * 128 * 4
    assert memory.alias_size_in_bytes >= 3 * one_state
    assert memory.temp_size_in_bytes < one_state


@pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
def test_granite_programs_keep_their_state_in_place(one_chip, monkeypatch,
                                                    program):
    """One period of granite-4.0-h-small-serve (Mamba-2 x 5, attention,
    Mamba-2 x 4) at its published widths and the cell's 128 slots: the
    decode forward holds ``ssm_decode_step`` and the prompt chunk
    ``ssm_chunk_fwd``, each under ``ssm_scan``, the attention layer's decode
    kernel under ``paged_attn``; the donated states come back aliased, and
    nothing in the program is a copy of a layer's 537 MB of state.  The
    chunk's sparse layers hold no array of all 5 120 picks' rows: their
    sorted buffer is 2 048 (``sparse_mlp``)."""
    from dlrover_tpu.models import moe
    from dlrover_tpu.models.llama import LlamaConfig
    from dlrover_tpu.serving import latent, linear
    from dlrover_tpu.serving.model import decode_step
    from dlrover_tpu.serving.params import serving_params_from_llama
    from dlrover_tpu.utils.profiler import device_scope, parse_program
    from perfbench.weights_granite import SeededGraniteParams

    monkeypatch.setattr(moe, "_interpret", lambda: False)
    cfg = LlamaConfig.granite_4_h_small(
        num_layers=10, moe_experts_held=(0, 18), vocab_size=25088,
        max_seq_len=5248, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    slots, mb, bs, nb = 128, 41, 128, 3072

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    sp = on_chip(jax.eval_shape(lambda: serving_params_from_llama(
        {"params": SeededGraniteParams(cfg, 3)}, cfg)))
    S = jax.ShapeDtypeStruct
    state, conv = (a.shape for a in linear.state_shapes(
        cfg, slots, "ssm").values())
    cache = on_chip({
        "k_pool": [S((nb, bs, 8, 128), jnp.bfloat16)],
        "v_pool": [S((nb, bs, 8, 128), jnp.bfloat16)],
        "ssm_state": [S(state, jnp.float32)] * 9,
        "ssm_conv": [S(conv, jnp.bfloat16)] * 9,
        "table": S((slots, mb), jnp.int32),
        "moe_picks": S((4,), jnp.uint32),
        "watch_slot": S((), jnp.int32)})
    if program == "decode":
        def forward(p, c, t, pos, act):
            with device_scope("decode_chunk"):
                return decode_step(p, cfg, c, t, pos,
                                   attention_impl="pallas", active=act)

        args = on_chip((S((slots,), jnp.int32), S((slots,), jnp.int32),
                        S((slots,), jnp.bool_)))
        kernels = ["ssm_decode_step"] * 9 + ["paged_decode_attention"]
    else:
        def forward(p, c, t, pos, sl, li):
            with device_scope("prefill_chunk"):
                return latent.verify_step(
                    p, cfg, c, t, pos, slots=sl, logits_index=li,
                    attention_impl="pallas")

        args = on_chip((S((1, 512), jnp.int32),) + (S((1,), jnp.int32),) * 3)
        kernels = ["ssm_chunk_fwd"] * 9
    lowered = jax.jit(forward, donate_argnums=(1,)).lower(sp, cache, *args)
    compiled = lowered.compile()
    table = parse_program(
        program, compiled.as_text(),
        {program if program != "decode" else "decode_chunk", "ssm_proj",
         "ssm_scan", "ssm_out", "attn_proj", "kv_write", "paged_attn",
         "moe_route", "moe_experts", "moe_shared", "head"},
        lowered.as_text(debug_info=True))
    assert table.complete, table.missing
    scopes = {n: scope for n, scope in table.scope_of.items()
              if n.startswith(("ssm_decode_step", "ssm_chunk_fwd",
                               "paged_decode_attention"))}
    assert sorted(scopes.values()) == sorted(
        "ssm_scan" if k.startswith("ssm") else "paged_attn"
        for k in kernels), scopes
    if program == "prefill_chunk":
        import re

        text = compiled.as_text()
        assert "[2048,4096]" in text and not re.search(
            r"\[5120,(4096|768)\]", text)
    memory = compiled.memory_analysis()
    one_state = 128 * 128 * 64 * 128 * 4
    assert memory.alias_size_in_bytes >= 9 * one_state
    assert memory.temp_size_in_bytes < one_state


@pytest.mark.parametrize("program", ["decode", "prefill_chunk"])
def test_brumby_programs_keep_their_state_in_place(one_chip, program):
    """Two layers of brumby-14b-serve at its published widths and the
    cell's 24 slots, with NO pool and no table in the cache: the decode
    forward holds ``retention_decode_step`` and the prompt chunk
    ``retention_chunk_fwd``, each under ``ret_scan``; the donated states
    and sums of keys come back aliased, and nothing in the program is a
    copy of a layer's 824 MB of state."""
    from dlrover_tpu.models.llama import LlamaConfig
    from dlrover_tpu.serving import latent, linear
    from dlrover_tpu.serving.model import decode_step
    from dlrover_tpu.serving.params import serving_params_from_llama
    from dlrover_tpu.utils.profiler import device_scope, parse_program
    from perfbench.weights_brumby import SeededBrumbyParams

    cfg = LlamaConfig.brumby_14b(
        num_layers=2, max_seq_len=5248, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    slots = 24

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    sp = on_chip(jax.eval_shape(lambda: serving_params_from_llama(
        {"params": SeededBrumbyParams(cfg, 3)}, cfg)))
    S = jax.ShapeDtypeStruct
    cache = on_chip({
        **{"retention_" + name: [S(held.shape, held.dtype)] * 2
           for name, held in linear.state_shapes(
               cfg, slots, "retention").items()},
        "watch_slot": S((), jnp.int32)})
    if program == "decode":
        def forward(p, c, t, pos, act):
            with device_scope("decode_chunk"):
                return decode_step(p, cfg, c, t, pos,
                                   attention_impl="pallas", active=act)

        args = on_chip((S((slots,), jnp.int32), S((slots,), jnp.int32),
                        S((slots,), jnp.bool_)))
        kernel = "retention_decode_step"
    else:
        def forward(p, c, t, pos, sl, li):
            with device_scope("prefill_chunk"):
                return latent.verify_step(
                    p, cfg, c, t, pos, slots=sl, logits_index=li,
                    attention_impl="pallas")

        args = on_chip((S((1, 512), jnp.int32),) + (S((1,), jnp.int32),) * 3)
        kernel = "retention_chunk_fwd"
    lowered = jax.jit(forward, donate_argnums=(1,)).lower(sp, cache, *args)
    compiled = lowered.compile()
    table = parse_program(
        program, compiled.as_text(),
        {program if program != "decode" else "decode_chunk", "ret_proj",
         "ret_scan", "ret_out", "mlp", "head"},
        lowered.as_text(debug_info=True))
    assert table.complete, table.missing
    scopes = {n: scope for n, scope in table.scope_of.items()
              if n.startswith(kernel)}
    assert sorted(scopes.values()) == ["ret_scan"] * 2, scopes
    memory = compiled.memory_analysis()
    one_state = slots * 8 * 65 * 128 * 129 * 4
    assert memory.alias_size_in_bytes >= 2 * one_state
    assert memory.temp_size_in_bytes < one_state


@pytest.mark.parametrize("rows", [8, 4096])
def test_int8_matmul_compiles(one_chip, rows):
    """The W8A8 projection at llama2_7b's MLP width, at a decode row
    count (padded to one 128-row block) and a prefill one."""
    m = max(rows, 128)
    a = jax.ShapeDtypeStruct((m, 4096), jnp.bfloat16, sharding=one_chip)
    b = jax.ShapeDtypeStruct((4096, 11008), jnp.bfloat16,
                             sharding=one_chip)
    fn = jax.jit(lambda a, b: int8_matmul(
        a, b, block_m=128, block_n=256, block_k=512))
    assert "tpu_custom_call" in fn.lower(a, b).compile().as_text()


def test_grouped_expert_matmul_compiles_at_olmoe_widths(one_chip,
                                                       monkeypatch):
    """OLMoE's three expert matmuls (32 768 rows in 64 groups against
    [64, 2048, 1024] and back), forward and backward, as the kernel and
    not its interpreter: the tile of ``GMM_TILING`` must fit the chip's
    fast memory (a larger one was refused on the chip, PR 26)."""
    from dlrover_tpu.models import moe

    # the backend here is the CPU: steer the layer onto the chip's path
    monkeypatch.setattr(moe, "_interpret", lambda: False)

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, wg, wu, wd, sizes):
        act = jax.nn.silu(moe.grouped_matmul(x, wg, sizes)) \
            * moe.grouped_matmul(x, wu, sizes)
        return moe.grouped_matmul(act, wd, sizes).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        s((32768, 2048)), s((64, 2048, 1024)), s((64, 2048, 1024)),
        s((64, 1024, 2048)), s((64,), jnp.int32)).compile().as_text()
    # 2 forward calls the gradient needs, 3 for the rows' gradient, 3 for
    # the weights' (the last forward matmul's output is not needed)
    assert text.count("tpu_custom_call") >= 8


def test_grouped_expert_matmul_compiles_at_the_hybrid_cells_share(
        one_chip, monkeypatch):
    """``train-hybrid-8k``'s expert matmuls: the sorted buffer's static
    bound of 16 384 x 8 rows, of which the 32 HELD experts' groups are the
    head, against [32, 2048, 512] and back, forward and backward, with the
    tile ``grouped_matmul`` will use (256 rows; the other sides clamp to
    the matrices')."""
    from dlrover_tpu.models import moe

    monkeypatch.setattr(moe, "_interpret", lambda: False)

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, wg, wu, wd, sizes):
        act = jax.nn.silu(moe.grouped_matmul(x, wg, sizes)) \
            * moe.grouped_matmul(x, wu, sizes)
        return moe.grouped_matmul(act, wd, sizes).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        s((16384 * 8, 2048)), s((32, 2048, 512)), s((32, 2048, 512)),
        s((32, 512, 2048)), s((32,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") >= 8


@pytest.mark.parametrize("heads,half,dtype", [
    (64, 64, jnp.bfloat16), (48, 32, jnp.bfloat16), (8, 64, jnp.bfloat16),
    (8, 32, jnp.float32)],
    ids=["window-q", "full-q", "window-k", "full-k-f32"])
def test_rope_rotate_compiles_at_the_hybrid_cells_shapes(
        one_chip, heads, half, dtype):
    """``train-hybrid-8k``'s rotations (2 x 8192 tokens, heads of 128, a
    window layer rotating whole heads and a full layer half of each),
    forward and rotating back: one kernel call each and nothing else,
    given and giving the flash kernels' layout (the whole step's text
    shows the projections and the flash calls on either side of it)."""
    from dlrover_tpu.ops.pallas.rope import rope_rotate

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    for conj in (False, True):
        text = jax.jit(
            lambda x, c, sn: rope_rotate(
                x.transpose(0, 2, 1, 3), c, sn, half, conj).transpose(
                    0, 2, 1, 3)
        ).lower(s((2, heads, 8192, 128), dtype), s((8192, 128), jnp.float32),
                s((8192, 128), jnp.float32)).compile().as_text()
        assert text.count("tpu_custom_call") == 1
        assert " transpose(" not in text and " copy(" not in text


def test_gated_conv_pair_compiles_at_the_conv_cells_shape(one_chip):
    """``train-conv-moe-8k``'s gates and taps (2 x 8192 tokens, three
    planes of 2048 channels, 3 taps, bf16): the forward and the backward
    are one kernel call each; beside the backward's only the taps'
    gradient summed over the two sequences."""
    from dlrover_tpu.ops.pallas import short_conv

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    bcu, taps, dy = s((2, 3, 8192, 2048)), s((3, 2048)), s((2, 8192, 2048))
    assert short_conv.kernel_takes(bcu, taps)
    for fn, args in ((short_conv.gated_conv_fwd, (bcu, taps)),
                     (short_conv.gated_conv_bwd, (dy, bcu, taps))):
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert text.count("tpu_custom_call") == 1
        assert f'%{fn.__name__}' in text
        assert " transpose(" not in text and " copy(" not in text


def _olmoe_cell():
    from dlrover_tpu.models.llama import LlamaConfig

    return LlamaConfig.olmoe_1b_7b(
        num_layers=3, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
        remat=True), 1, 4096


def _hybrid_cell():
    from dlrover_tpu.models.llama import LlamaConfig

    return LlamaConfig.laguna_xs2(
        num_layers=9, moe_experts_held=(0, 32), vocab_size=12544,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, remat=True), 2, 8192


def _conv_cell():
    import json
    import os

    from perfbench.drivers.train_conv import conv_config

    # the cell's configuration file as its driver reads it: published
    # layers 1-13, one leading conv layer and three periods of (attention,
    # conv, conv, conv), heads of 64
    with open(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "perfbench/configs/lfm2-8b-a1b-train.json")) as f:
        config = json.load(f)
    dep = config["deployment"]
    return (conv_config(config, max_seq_len=dep["seq_len"]),
            dep["sequences_per_chip_per_step"], dep["seq_len"])


# Serialized executable of a held cell's step on the PARENT of PR 57
# (97b77cf), compiled here for the same described v5e, in MB, and what the
# step may be of it.  ISSUE 57 asked for 1.06 (146 MB of
# ``train-hybrid-8k``'s 138.0; PR 56's tree was 153.7): the step reads
# 145.9 MB with the layer's pieces traced in line and 146.7 as it stands,
# with them behind nested ``jit``s, which take 1.4-3 s of TRACING off a
# warm set-up and put 0.8 MB of calls and names into the executable.
# ``train-conv-moe-8k`` reads 96.1 MB of 87.2: XLA's matmul of 128 rows at
# a width of 1 792 is twice the executable it is at 512, and its walk
# behind the buffer is 8.2 MB where the hybrid cell's is 6.9 (PR 57).
_PARENT_STEP_MB = {"train-hybrid-8k": (138.02, 1.07),
                   "train-conv-moe-8k": (87.17, 1.11)}


@pytest.mark.parametrize("cell,stack,sparse_layers_a_loop,flash_calls", [
    (_olmoe_cell, (3, 64, 2048, 1024), 1, {"attn": 4}),
    (_hybrid_cell, (2, 32, 2048, 512), 4, {
        "attn_full": 8, "flash_window_fwd": 6, "flash_window_dq": 3,
        "flash_window_dkv": 3, "rope_rotate": 30}),
    (_conv_cell, (3, 8, 2048, 1792), 4, {
        "attn_full": 4, "gated_conv_fwd": 8, "gated_conv_bwd": 4}),
], ids=["train-moe-dropless", "train-hybrid-8k", "train-conv-moe-8k"])
def test_sparse_cells_step_reads_expert_weights_in_the_stack(
        topo, monkeypatch, request, cell, stack, sparse_layers_a_loop,
        flash_calls):
    """The whole train step of the two sparse cells (``ElasticTrainer``'s
    own jitted step, bf16 state, remat, the scan over layers / periods) as
    the chip's compiler makes it: no copy of a layer's expert weights out
    of the stack ahead of a grouped matmul (6 a sparse layer before
    PR 33, 0.8 ms each at OLMoE's sizes), and the kernels still named
    ``gmm.<n>`` / ``tgmm.<n>``, 12 a sparse layer (3 forward, 3
    recomputed, 3 + 3 backward), and the flash calls ``attn.<n>`` /
    ``attn_full.<n>`` / ``flash_window_*``: what the benchmark's readers
    find them by.  The text holds a loop's body once.  Since PR 45 a
    layer with a ``LayerSpec`` rotates q and k through ``rope_rotate``
    (forward, recomputed, transposed: 6 a layer, the leading layer and a
    period of four in the text) and no rotated HALF of a head is an array
    of its own (PERF.md section 6, PR 45).  Since PR 57 a layer that
    holds a SHARE of its experts (the hybrid and the conv cell) walks a
    compact sorted buffer: the kernels run 12 times a layer and NO more
    (what overflows the buffer is walked behind one ``cond`` each way, the
    recomputed forward's dead, in XLA's own matmuls against ONE expert's
    weights read in the stack), no array has ``T x top_k`` rows by the
    hidden size or the experts' width, the step fits the chip (the compile
    fails where it does not) and its executable stays inside its budget
    (``_PARENT_STEP_MB``).  Since PR 61 a convolution layer's gates and
    taps are ``gated_conv_fwd`` (forward and recomputed) and
    ``gated_conv_bwd``, handed ``in_proj``'s output as the compiler lays
    it out: under ``conv_mix`` no float32 array of tokens x channels and
    no ``copy`` / ``transpose`` / ``concatenate`` of the projection's
    (364 such float32 instructions before; ``[b, s, 3 h]`` rows cost two
    copies of 201 MB a layer: PERF.md section 6, PR 61)."""
    import collections
    import re

    import flax.linen as nn

    from dlrover_tpu.accel.parallel.mesh import logical_rules_context
    from dlrover_tpu.models.llama import LlamaModel
    from dlrover_tpu.trainer.elastic.trainer import (
        ElasticTrainer, expert_weight_copies)

    # the backend here is the CPU: steer attention and the experts onto
    # the chip's kernels
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, rows, seq = cell()
    trainer = ElasticTrainer(
        LlamaModel(cfg), global_batch_size=rows, micro_batch_per_shard=rows,
        seq_len=seq, checkpoint_dir=None, save_memory_interval=0,
        save_storage_interval=0)
    trainer.prepare(devices=[topo.devices[0]])
    res = trainer.result
    state = nn.unbox(res.abstract_state)
    scanned = state.params["layers"]["layer"] if cfg.layers is None \
        else state.params["periods"]["layer_0"]
    convs = sum(s.mixer == "conv" for s in cfg.layer_specs)
    if convs:
        # a period's layers have different trees: attention, then convs
        assert "attn" in scanned and "conv" not in scanned
        assert state.params["periods"]["layer_1"]["conv"]["taps"].shape \
            == (3, 3, 2048)
    assert scanned["mlp"]["w_gate"].shape == stack
    batch = {"input_ids": jax.ShapeDtypeStruct((rows, seq), jnp.int32)}
    with logical_rules_context(res.config.logical_rules), res.mesh:
        compiled = res.jit_train_step.lower(state, batch).compile()
    text = compiled.as_text()
    assert expert_weight_copies(text) == 0
    held = cfg.moe_experts_held is not None
    # the walk behind a compact buffer: a ``cond`` a sparse layer, forward
    # and backward, none where every expert is held
    assert len(re.findall(r" conditional\(", text)) == (
        2 * sparse_layers_a_loop if held else 0)
    if held:
        # ... whose matmuls read ONE expert's weights where they lie (9
        # slices a layer: 3 forward, 6 backward for 9 matmuls), counted
        # apart from the copies above: no LAYER's weights are sliced out
        _, _, m, h = stack
        one = rf"bf16\[1,1,(?:{m},{h}|{h},{m})\]"
        sliced = re.findall(
            rf"= {one}\S* dynamic-slice\(", text)
        assert len(sliced) == 9 * sparse_layers_a_loop
        picks = rows * seq * cfg.moe_top_k
        assert not re.findall(rf"(?:bf16|f32)\[{picks},(?:{m}|{h})\]", text)
        assert not re.findall(
            rf"(?:bf16|f32)\[{cfg.moe_top_k},{rows * seq},(?:{m}|{h})\]",
            text)
        from jax.experimental.serialize_executable import serialize

        parent_mb, room = _PARENT_STEP_MB[request.node.callspec.id]
        assert len(serialize(compiled)[0]) / 1e6 <= room * parent_mb
    calls = re.findall(r"^\s+%(t?gmm)(?:\.\d+)? = ", text, re.M)
    assert calls.count("gmm") == 9 * sparse_layers_a_loop
    assert calls.count("tgmm") == 3 * sparse_layers_a_loop
    # the flash calls too: an unnamed ``pallas_call`` takes the innermost
    # scope's name, so ``utils/profiler.device_scope`` wraps what is AROUND
    # a kernel and never the call (PR 36: ``attn_proj``, ``mlp``, ``head``)
    kernels = collections.Counter(re.findall(
        r"^\s+%([\w\-]+?)(?:\.\d+)? = .* custom-call\(.*"
        r"custom_call_target=\"tpu_custom_call\"", text, re.M))
    assert kernels == flash_calls | {
        "gmm": 9 * sparse_layers_a_loop, "tgmm": 3 * sparse_layers_a_loop}
    # all layers' groups in one row: the operand the kernels index into
    assert f"bf16[{stack[0] * stack[1]},{stack[2]},{stack[3]}]" in text
    # PR 49: nothing of the head is computed twice.  The hybrid cell's step
    # is rematerialised by the COMPILER to fit; with the loss's ``d_logits``
    # pinned to memory it ran the head's d-hidden matmul a second time
    # (``fusion.2177.remat``, 4.4 ms a step on the chip; PERF.md section 6)
    # (the TIED head of ``train-conv-moe-8k`` is not held to it: there the
    # compiler recomputes the logits' matmul, ``embed_tokens.attend``, to
    # fit; PERF.md section 7)
    if not cfg.tie_embeddings:
        assert not re.findall(
            r'^\s+%([\w.\-]*remat[\w.\-]*) = .*op_name="[^"]*[/(]head[/)]',
            text, re.M)
    for scope in ("conv_proj", "conv_mix") if convs else ():
        assert re.search(rf'op_name="[^"]*[/(]{scope}[/)]', text), scope
    if convs:
        mixed = [line for line in text.splitlines() if re.search(
            r'op_name="[^"]*[/(]conv_mix[/)]', line)]
        assert sum(" custom-call(" in line for line in mixed) == 12
        h = cfg.hidden_size
        tokens = rf"(?:{rows},{seq}|{rows * seq})"
        wide = re.compile(rf"= \(?f32\[{tokens},(?:\d+,)?{h}\]")
        moved = re.compile(
            rf"= bf16\[(?:{tokens},(?:3,{h}|{3 * h})|{rows},3,{seq},{h})\]"
            r"\S* (?:copy|transpose|concatenate)\(")
        assert not [line for line in mixed
                    if wide.search(line) or moved.search(line)]
    if cfg.layers is not None and cfg.head_dim_ == 128:
        half_a_head = re.compile(
            rf"(?:bf16|f32)\[{rows},{seq},\d+,(?:{cfg.head_dim_ // 2}|"
            rf"{cfg.head_dim_ // 4})\]")
        assert not half_a_head.search(text)


def test_expert_weight_copies_counts_a_sliced_operand():
    """The counter on a step text made by hand: one fusion that slices
    feeds two ``gmm`` calls (one copy), a plain slice a third, and a
    sliced operand of another rank or of another kernel is not a copy of
    expert weights."""
    from dlrover_tpu.trainer.elastic.trainer import expert_weight_copies

    text = """
%fused_computation.1 (p0: bf16[3,4,8,8], p1: s32[]) -> bf16[4,8,8] {
  %p0 = bf16[3,4,8,8]{3,2,1,0} parameter(0)
  %p1 = s32[] parameter(1)
  %dynamic-slice.1 = bf16[1,4,8,8]{3,2,1,0} dynamic-slice(%p0, %p1), dynamic_slice_sizes={1,4,8,8}
  ROOT %bitcast.1 = bf16[4,8,8]{2,1,0} bitcast(%dynamic-slice.1)
}

%fused_computation.2 (p0: bf16[16,8]) -> bf16[16,8] {
  %p0.1 = bf16[16,8]{1,0} parameter(0)
  ROOT %negate.1 = bf16[16,8]{1,0} negate(%p0.1)
}

ENTRY %main (a: bf16[3,4,8,8], i: s32[], x: bf16[16,8]) -> bf16[16,8] {
  %a = bf16[3,4,8,8]{3,2,1,0} parameter(0)
  %i = s32[] parameter(1)
  %x = bf16[16,8]{1,0} parameter(2)
  %dynamic-slice_bitcast_fusion.1 = bf16[4,8,8]{2,1,0:T(8,128)(2,1)} fusion(%a, %i), kind=kLoop, calls=%fused_computation.1, metadata={}
  %fusion.2 = bf16[16,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.2
  %dynamic-slice.2 = bf16[4,8,8]{2,1,0} dynamic-slice(%a, %i), dynamic_slice_sizes={4,8,8}
  %dynamic-slice.3 = bf16[16,8]{1,0} dynamic-slice(%x, %i), dynamic_slice_sizes={16,8}
  %gmm.1 = bf16[16,8]{1,0} custom-call(%i, %fusion.2, %dynamic-slice_bitcast_fusion.1), custom_call_target="tpu_custom_call"
  %gmm.2 = bf16[16,8]{1,0} custom-call(%i, %gmm.1, %dynamic-slice_bitcast_fusion.1), custom_call_target="tpu_custom_call"
  %gmm = bf16[16,8]{1,0} custom-call(%i, %dynamic-slice.3, %dynamic-slice.2), custom_call_target="tpu_custom_call"
  %attn.4 = bf16[16,8]{1,0} custom-call(%i, %gmm, %dynamic-slice.2), custom_call_target="tpu_custom_call"
  ROOT %tgmm.1 = bf16[4,8,8]{2,1,0} custom-call(%i, %gmm.2, %attn.4), custom_call_target="tpu_custom_call"
}
"""
    assert expert_weight_copies(text) == 2
    assert expert_weight_copies(text.replace("gmm", "other")) == 0
