"""Device time by the program's own scopes (``utils/profiler.device_scope``
/ ``program_scopes``, ``utils/xprof_metrics.scope_seconds``,
``perfbench/device_scopes.py``), on the CPU: the parser on a written HLO
text, the tables of tiny programs, the join on plain lists, a thunk after
``close()``, and a table that is another tree's.

What the CPU cannot show is in ``tests/test_tpu_compile.py``: that no
scope names a kernel call of the two sparse training cells as the chip's
compiler makes them."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.utils import profiler, xprof_metrics
from dlrover_tpu.utils.profiler import (ProgramRegistry, ProgramTable,
                                        innermost_scope, parse_program)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DECLARED = {"loss_and_grad", "optimizer", "attn_proj", "mlp", "head",
            "attn_full"}

# a compiled step in miniature: a scan's ``while`` around a body, a fusion
# with its own metadata, a fusion the compiler made (no metadata; its
# computation's instructions agree), a prefetch (no metadata; its consumer
# says whose wait it is), a kernel call named by the scope around it, and
# an instruction nobody can place
HLO = '''HloModule jit__train_step, is_scheduled=true

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(_train_step)/loss_and_grad/transpose(jvp(LlamaModel))/layers/layer/attn/attn_proj/q_proj/dot_general" stack_frame_id=3}
}

%fused_computation.2.clone (p1: f32[8]) -> f32[8] {
  %p1 = f32[8]{0} parameter(0)
  %sin.1 = f32[8]{0} sine(%p1), metadata={op_name="jit(_train_step)/loss_and_grad/jvp(LlamaModel)/layers/layer/attn/attn_proj/sin"}
  ROOT %sub.1 = f32[8]{0} subtract(%sin.1, %p1), metadata={op_name="jit(_train_step)/loss_and_grad/jvp(LlamaModel)/layers/layer/attn/attn_proj/sub"}
}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%arg), index=1
  %fusion.1 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_train_step)/loss_and_grad/transpose(jvp(LlamaModel))/while/body/closed_call/checkpoint/rematted_computation/layers/layer/mlp/up_proj/dot_general" stack_frame_id=7}
  %subtract_convert_fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2.clone
  %copy-start.1 = (f32[8]{0}, f32[8]{0:S(1)}, u32[]) copy-start(%subtract_convert_fusion.2)
  %copy-done.1 = f32[8]{0:S(1)} copy-done(%copy-start.1)
  %bitcast.1 = f32[8]{0:S(1)} bitcast(%copy-done.1)
  %attn_full.3 = f32[8]{0} custom-call(%bitcast.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(_train_step)/loss_and_grad/jvp(LlamaModel)/layers/layer/attn/attn_full/pallas_call"}
  %convolution.4 = f32[8]{0} convolution(%attn_full.3, %gte.1), metadata={op_name="jit(_train_step)/loss_and_grad/jvp(LlamaModel)/head/lm_head/dot_general"}
  %gte.0 = s32[] get-tuple-element(%arg), index=0
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte.0, %convolution.4)
}

ENTRY %main (state: f32[8]) -> f32[8] {
  %state = f32[8]{0} parameter(0), metadata={op_name="state.params['w']"}
  %zero = s32[] constant(0)
  %init = (s32[], f32[8]{0}) tuple(%zero, %state)
  %while.1 = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(_train_step)/loss_and_grad/jvp(LlamaModel)/while"}
  %out = f32[8]{0} get-tuple-element(%while.1), index=1
  %adam.9 = f32[8]{0} add(%out, %state), metadata={op_name="jit(_train_step)/optimizer/add"}
  ROOT %stray.5 = f32[8]{0} negate(%adam.9)
}
'''


def test_innermost_scope_passes_over_what_is_no_scope():
    path = ("jit(_train_step)/loss_and_grad/transpose(jvp(LlamaModel))/"
            "while/body/closed_call/checkpoint/rematted_computation/"
            "layers/layer/attn/attn_proj/q_proj/dot_general")
    assert innermost_scope(path, DECLARED) == "attn_proj"
    assert innermost_scope("jit(step)/transpose(jvp(attn_full))/mul",
                           DECLARED) == "attn_full"
    assert innermost_scope("jit(step)/layers/layer/attn/mul",
                           DECLARED) is None
    assert innermost_scope("state.params['mlp']['kernel']", DECLARED) is None


def test_parser_on_a_written_text():
    table = parse_program("train_step", HLO, DECLARED)
    assert table.module == "jit__train_step" and table.complete
    scope = table.scope_of
    # its own metadata, though the fused computation says otherwise
    assert scope["fusion.1"] == "mlp"
    # the compiler's fusion has none: its computation's instructions agree
    assert scope["subtract_convert_fusion.2"] == "attn_proj"
    # a prefetch and the bitcast behind it are their consumer's wait
    assert scope["copy-done.1"] == scope["copy-start.1"] == "attn_full"
    assert scope["bitcast.1"] == "attn_full"
    assert scope["attn_full.3"] == "attn_full"
    assert scope["convolution.4"] == "head"
    assert scope["while.1"] == "loss_and_grad"
    assert scope["adam.9"] == "optimizer"
    # no op_name, and nothing to place it by: unscoped
    assert scope["stray.5"] is None
    # consumers that disagree (attn_proj's fusion and the head's matmul)
    assert scope["gte.1"] is None


def test_a_text_from_another_tree_is_not_complete(monkeypatch):
    """(e) The lowered module says the trace entered ``kv_write``; the
    executable's text (a compile cache another tree filled) has no such
    scope: no reading may be made from the table."""
    from perfbench import device_scopes, program_spans

    traced = ('#loc1 = loc("jit(_train_step)/loss_and_grad/mul"(#loc0))\n'
              '#loc2 = loc("layers/layer/kv_write/scatter"(#loc0))\n'
              # a traceback's frame, a FUNCTION named like a scope: no path
              '#loc3 = loc("prefill"(#loc0))\n')
    foreign = parse_program("train_step", HLO,
                            DECLARED | {"kv_write", "prefill"}, traced)
    assert foreign.missing == ("kv_write",) and not foreign.complete
    devices = {"/device:TPU:0": {
        "ops": [["adam.9", 0.0, 50.0]],
        "modules": [["jit__train_step(7)", 0.0, 60.0]]}}
    rec = xprof_metrics.join(devices, {"train_step": foreign})["train_step"]
    assert rec["complete"] is False and rec["scopes"] == {}
    assert rec["unscoped"] == pytest.approx(50e-9)

    # what a reader gets of it: nothing; of this tree's table: a reading
    run = {"trace": {"xplane": "/nowhere/foreign.xplane.pb"}}
    monkeypatch.setattr(program_spans, "of_run",
                        lambda run: {"window": (0.0, 60.0)})

    def reduced_to(record):
        monkeypatch.setitem(device_scopes._reduced, run["trace"]["xplane"], {
            "programs": {"train_step": record}, "busy_s": 50e-9,
            "rehearsal": False})

    reduced_to(rec)
    assert device_scopes.scope_ms(run, "train_step", ("optimizer",)) is None
    assert device_scopes.unscoped_share(run) is None
    reduced_to(xprof_metrics.join(devices, {"train_step": parse_program(
        "train_step", HLO, DECLARED)})["train_step"])
    ms, executions = device_scopes.scope_ms(run, "train_step",
                                            ("optimizer",))
    assert ms == pytest.approx(50e-6) and executions == 1
    assert device_scopes.unscoped_share(run) == 0.0


# ----------------------------------------------------------- (c) the join

def _table(label, module, scopes):
    return ProgramTable(label, module, dict(scopes))


def test_join_takes_self_time_and_keeps_programs_apart():
    """A ``while`` is not counted twice; ``fusion.1`` is ``head`` in one
    program and ``pick`` in the other; an executable nobody registered is
    ``(other programs)``; two programs of one module name are told apart
    by the instructions their executions show."""
    tables = {
        "train_step": _table("train_step", "jit__train_step", {
            "while.1": "loss_and_grad", "fusion.1": "head",
            "dot.2": "mlp", "copy.3": None}),
        "prefill.g1": _table("prefill.g1", "jit_insert_fn", {
            "fusion.1": "pick", "x.1": "mlp"}),
        "prefill.g2": _table("prefill.g2", "jit_insert_fn", {
            "fusion.1": "pick", "x.1": "mlp", "y.2": "head"}),
    }
    ops = [["while.1", 0, 100], ["fusion.1", 10, 20], ["dot.2", 40, 50],
           ["copy.3", 100, 10],
           ["fusion.1", 200, 30], ["x.1", 230, 10],
           ["upload.9", 300, 5],
           ["fusion.1", 400, 10], ["y.2", 410, 10]]
    modules = [["jit__train_step(11)", 0, 110], ["jit_insert_fn(22)", 200, 50],
               ["jit_convert_element_type(33)", 300, 5],
               ["jit_insert_fn(44)", 400, 20]]
    got = xprof_metrics.join(
        {"/device:TPU:0": {"ops": ops, "modules": modules}}, tables)
    step = got["train_step"]
    assert step["executions"] == 1
    assert step["scopes"] == pytest.approx(
        {"loss_and_grad": 30e-9, "head": 20e-9, "mlp": 50e-9})
    assert step["unscoped_ops"] == pytest.approx({"copy.3": 10e-9})
    # executable 22 showed nothing that tells g1 from g2: one record for
    # both, and a scope only where both agree
    assert got["prefill.*"]["scopes"] == pytest.approx(
        {"pick": 30e-9, "mlp": 10e-9})
    # executable 44 showed ``y.2``, which only g2 has
    assert got["prefill.g2"]["scopes"] == pytest.approx(
        {"pick": 10e-9, "head": 10e-9})
    other = got[xprof_metrics.OTHER]
    assert other["executions"] == 1 and other["unscoped"] == \
        pytest.approx(5e-9)
    busy = 110 + 40 + 5 + 20          # the union of the events, ns
    assert xprof_metrics.total_seconds(got) == pytest.approx(busy * 1e-9)


def test_join_disagreeing_candidates_count_as_unscoped():
    tables = {
        "prefill.g1": _table("prefill.g1", "jit_insert_fn",
                             {"fusion.1": "pick"}),
        "prefill.g2": _table("prefill.g2", "jit_insert_fn",
                             {"fusion.1": "head"}),
    }
    got = xprof_metrics.join({"/device:TPU:0": {
        "ops": [["fusion.1", 0, 10]],
        "modules": [["jit_insert_fn(5)", 0, 10]]}}, tables)
    assert got["prefill.*"]["scopes"] == {}
    assert got["prefill.*"]["unscoped"] == pytest.approx(10e-9)


def test_join_without_a_module_line_goes_by_instruction_name():
    """The CPU backend: a name that two programs give different scopes is
    unscoped; one that a single program has is that program's."""
    tables = {
        "decode_chunk": _table("decode_chunk", "jit_chunk_fn",
                               {"fusion.1": "head", "dot.7": "mlp"}),
        "prefill.g1": _table("prefill.g1", "jit_insert_fn",
                             {"fusion.1": "pick", "dot.8": "mlp"}),
    }
    got = xprof_metrics.join({xprof_metrics.CPU_PLANE: {
        "ops": [["fusion.1", 0, 10], ["dot.7", 10, 10], ["dot.8", 20, 10],
                ["memcpy.1", 30, 10]],
        "modules": []}}, tables)
    assert got["decode_chunk"]["scopes"] == pytest.approx({"mlp": 10e-9})
    assert got["prefill.g1"]["scopes"] == pytest.approx({"mlp": 10e-9})
    assert got[xprof_metrics.SEVERAL]["unscoped"] == pytest.approx(10e-9)
    assert got[xprof_metrics.OTHER]["unscoped"] == pytest.approx(10e-9)
    assert all(rec["executions"] == 0 for rec in got.values())


def test_join_total_is_busy_time_on_a_recorded_v5e_capture():
    """The invariant on the recorded ``train-flashsave`` capture (its
    nested ``while``s included): whatever the programs and tables, the
    join's total is ``trace_reduce``'s ``busy_s`` of the same window."""
    from perfbench import trace_reduce as tr

    with open(os.path.join(ROOT, "perfbench", "tests", "data",
                           "train-flashsave.v5e.events.json")) as f:
        events = json.load(f)["events"]
    window = tr.window_of(events)
    busy = tr.reduce_events(events, window)["busy_s"]
    devices, names = {}, set()
    for plane, ops in events["devices"].items():
        lo = min(s for _, s, _ in ops)
        hi = max(s + d for _, s, d in ops)
        mid = (lo + hi) / 2.0
        # two programs: the step until the middle, a save's program after
        devices[plane] = {"ops": ops, "modules": [
            ["jit__train_step(1)", lo, mid - lo],
            ["jit_snapshot(2)", mid, hi - mid]]}
        names |= {n for n, _, _ in ops}
    scopes = ["optimizer", "head", None]
    tables = {"train_step": _table(
        "train_step", "jit__train_step",
        {n: scopes[i % 3] for i, n in enumerate(sorted(names))})}
    got = xprof_metrics.join(devices, tables, window)
    assert xprof_metrics.total_seconds(got) == pytest.approx(busy, rel=5e-3)
    assert got["train_step"]["executions"] == 1
    assert got[xprof_metrics.OTHER]["unscoped"] > 0
    # a ``while`` keeps only what its body does not account for
    whiles = [n for n in names if n.startswith("while")]
    assert whiles
    flat = sum(d for ops in events["devices"].values() for _, _, d in ops)
    assert flat / 1e9 / len(devices) > 1.2 * busy


# ------------------------------------------------- (b), (d) real programs

@pytest.fixture
def registry(monkeypatch):
    """A registry of the test's own in the process-wide one's place."""
    fresh = ProgramRegistry()
    monkeypatch.setattr(profiler, "_PROGRAMS", fresh)
    return fresh


# a scope that named a kernel call would show as an instruction's name
NEW_SCOPES = ("attn_proj", "mlp", "head", "embed", "clip", "grad_norm",
              "kv_write", "paged_attn", "pick")
KERNEL_NAMES = re.compile(r"^(attn|gmm|tgmm|paged_|mla_prefill)")


def _no_kernel_renamed(table):
    """On the CPU no Pallas call is an instruction (interpret mode), so
    the parent's programs have NO instruction of a kernel's name
    (recorded from the parent's tree: none in any of the four programs),
    and neither may these; nor one named after a new scope."""
    assert not [n for n in table.scope_of if KERNEL_NAMES.match(n)]
    assert not [n for n in table.scope_of
                if n.split(".")[0] in NEW_SCOPES]


def _trainer(cfg):
    from dlrover_tpu.models.llama import LlamaModel
    from dlrover_tpu.trainer.elastic.trainer import ElasticTrainer

    trainer = ElasticTrainer(
        LlamaModel(cfg), global_batch_size=2, micro_batch_per_shard=2,
        seq_len=32)
    trainer.prepare(devices=jax.devices()[:1])
    trainer.restore_or_init(jax.random.PRNGKey(0))
    return trainer


def _moe_shaped():
    from dlrover_tpu.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=16, num_layers=2,
        num_heads=4, num_kv_heads=4, max_seq_len=32, num_experts=8,
        moe_top_k=3, moe_norm_topk_prob=False, qk_norm=True,
        dtype=jnp.float32, param_dtype=jnp.float32, scan_layers=True,
        remat=True)


def _dense():
    from dlrover_tpu.models.llama import LlamaConfig

    return LlamaConfig.tiny(scan_layers=True, remat=True, max_seq_len=32)


@pytest.mark.parametrize("config,leaves", [
    (_dense, {"embed", "attn_proj", "mlp", "head", "clip", "optimizer"}),
    (_moe_shaped, {"embed", "attn_proj", "head", "clip", "optimizer",
                   "moe_route", "moe_dispatch", "moe_experts",
                   "moe_combine"}),
], ids=["dense", "olmoe-shaped"])
def test_train_step_is_tiled_by_its_scopes(fresh_compiles, registry, config,
                                           leaves):
    """(b), (d) The step registers itself when it is first dispatched;
    its thunk works after ``close()`` with the state gone, keeps no
    buffer alive, and every scope the trace entered is in the text."""
    trainer = _trainer(config())
    batch = np.zeros((2, 32), np.int32)
    assert registry.labels() == []
    jax.block_until_ready(trainer.train_step(batch))
    assert registry.labels() == ["train_step"]
    jax.block_until_ready(trainer.train_step(batch))   # the jitted step
    assert trainer._step == trainer.result.train_step
    text = trainer.compiled_step_text(batch)
    trainer.state = None
    trainer.close()
    live = len(jax.live_arrays())
    table = profiler.program_scopes()["train_step"]
    assert len(jax.live_arrays()) == live
    assert table.complete, table.missing
    assert table.module == "jit__train_step"
    present = set(table.scope_of.values())
    assert leaves <= present, leaves - present
    assert {"loss_and_grad"} <= registry.scopes
    # ``compiled_step_text`` is a caller of the same code: the same text
    assert parse_program("again", text, registry.scopes).scope_of \
        == table.scope_of
    _no_kernel_renamed(table)


def _engine(cfg, variables, **kw):
    from dlrover_tpu.serving.engine import InferenceEngine

    args = dict(max_slots=2, chunk=4, temperature=0.0, max_len=96,
                prefill_buckets=(16,), paged=True, block_size=8,
                prefill_chunk=16)
    args.update(kw)
    return InferenceEngine(cfg, variables, **args)


def _dense_engine():
    from dlrover_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = LlamaConfig.tiny(scan_layers=False, max_seq_len=96,
                           dtype=jnp.float32)
    variables = LlamaModel(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return _engine(cfg, variables), {
        "decode_chunk": {"attn_proj", "kv_write", "paged_attn", "mlp",
                         "head", "pick"},
        "prefill_chunk.g1": {"attn_proj", "kv_write", "paged_attn", "mlp",
                             "head", "pick"},
        "prefill.g1.b16": {"attn_proj", "kv_write", "mlp", "head", "pick"},
    }


def _latent_engine(**kw):
    from perfbench.weights_glm5 import SeededGlm5Params
    from tests.test_glm5_reference import tiny

    cfg = tiny()
    seven = {"mla_proj", "dsa_index", "dsa_select", "mla_attn",
             "moe_route", "moe_experts", "moe_shared"}
    return _engine(cfg, {"params": SeededGlm5Params(cfg, 1)}, **kw), {
        "decode_chunk": seven | {"head", "pick", "kv_write", "mlp"},
        "prefill_chunk.g1": seven | {"head", "pick", "kv_write", "mlp"},
    }


def _latent_kernel_engine():
    """The engine whose decode chunk takes ``paged_index_scores`` and
    whose prompt chunks take ``mla_prefill_attention`` (interpreted
    here: the kernels' bodies are the programs' own instructions, under
    the scopes around the calls)."""
    return _latent_engine(attention_impl="pallas")


@pytest.mark.parametrize(
    "build", [_dense_engine, _latent_engine, _latent_kernel_engine],
    ids=["dense", "latent", "latent-kernels"])
def test_engine_warmup_registers_every_program(fresh_compiles, registry,
                                               build):
    """(b) Each program ``warmup`` runs is registered under its label;
    the tables come from shapes alone (the engine and its weights may be
    gone), compile nothing anew, and hold every scope the trace entered."""
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **kw: compiles.append(kw.get("fun_name"))
        if event == "/jax/core/compile/backend_compile_duration" else None)
    engine, expected = build()
    ran = engine.warmup()
    assert len(registry.labels()) == ran
    assert set(expected) <= set(registry.labels())
    del engine
    before = len(compiles)
    tables = profiler.program_scopes()
    assert compiles[before:] == []      # the executables it already ran
    assert len(tables) == ran
    for label, table in tables.items():
        assert table.complete, (label, table.missing)
        _no_kernel_renamed(table)
    for label, leaves in expected.items():
        present = set(tables[label].scope_of.values())
        assert leaves <= present, (label, leaves - present)
    modules = {t.module for t in tables.values()}
    assert modules == {"jit_chunk_fn", "jit_insert_fn",
                       "jit_prefill_chunk_fn"}
