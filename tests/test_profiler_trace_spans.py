"""The program's spans in the profiler's own trace (``utils/profiler.span``:
``jax.profiler.TraceAnnotation``): a tiny trainer with two flash saves and
tiny engines behind a router run under ``jax.profiler.trace``; every span of
the vocabulary must be in the ``.xplane.pb``, under its parent, on the
right thread.  Beside them: the checkpoint counters timed at the same
boundaries, what a span costs with no session open, the stamps on
``ServingRequest`` and what ``maybe_save`` answers."""

import contextlib
import os
import statistics
import time
import uuid

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
from dlrover_tpu.models.llama import LlamaConfig, LlamaModel
from dlrover_tpu.serving.router import (
    ContinuousBatchScheduler,
    InferenceEngineAdapter,
    ServingRouter,
)
from dlrover_tpu.trainer.elastic.trainer import ElasticTrainer
from dlrover_tpu.trainer.flash_checkpoint import SaverMode
from dlrover_tpu.trainer.flash_checkpoint.engine import CheckpointEngine
from dlrover_tpu.utils import profiler
from dlrover_tpu.utils.metric_registry import METRIC_HELP
from perfbench import program_spans as ps
from perfbench import trace_reduce

WRITER = "ckpt-writer-"

# span -> (a span that must enclose it on the same thread, thread kind)
TRAIN_SPANS = {
    "dlrover.trainer.step": (None, "main"),
    "dlrover.trainer.shape_batch": ("dlrover.trainer.step", "main"),
    "dlrover.trainer.dispatch": ("dlrover.trainer.step", "main"),
    "dlrover.trainer.maybe_save": (None, "main"),
    "dlrover.ckpt.stage": ("dlrover.trainer.maybe_save", "main"),
    "dlrover.ckpt.snapshot": ("dlrover.ckpt.stage", "main"),
    "dlrover.ckpt.barrier": ("dlrover.ckpt.stage", "main"),
    "dlrover.ckpt.handoff": ("dlrover.ckpt.stage", "main"),
    "dlrover.ckpt.pickup": (None, "writer"),
    "dlrover.ckpt.commit": (None, "writer"),
    "dlrover.ckpt.lock_wait": ("dlrover.ckpt.commit", "writer"),
    "dlrover.ckpt.d2h_dispatch": ("dlrover.ckpt.commit", "writer"),
    "dlrover.ckpt.d2h_wait": ("dlrover.ckpt.commit", "writer"),
    "dlrover.ckpt.shm_alloc": ("dlrover.ckpt.commit", "writer"),
    "dlrover.ckpt.shm_copy": ("dlrover.ckpt.commit", "writer"),
    "dlrover.ckpt.publish": ("dlrover.ckpt.commit", "writer"),
    "dlrover.ckpt.unlock": ("dlrover.ckpt.commit", "writer"),
}
ROUTER_PHASES = ("expire", "cancel", "brownout", "failover", "schedule",
                 "hedge", "deliver", "pump", "retire", "observe",
                 "autoscale", "flush")
SERVE_SPANS = {
    "dlrover.router.submit": (None, "main"),
    "dlrover.router.step": (None, "main"),
    **{f"dlrover.router.phase.{p}": ("dlrover.router.step", "main")
       for p in ROUTER_PHASES},
    "dlrover.router.pump": ("dlrover.router.phase.pump", "main"),
    "dlrover.engine.step": ("dlrover.router.pump", "main"),
    "dlrover.engine.admit": ("dlrover.engine.step", "main"),
    # a program's name opens around its wait, under a step's reads (a
    # decode chunk's: the NEXT step's, which reads it behind its own
    # dispatches), and around its dispatch too where nothing was in
    # flight (a bucketed prefill's into an idle engine: under the
    # step's admit)
    "dlrover.engine.prefill": ("dlrover.engine.step", "main"),
    "dlrover.engine.reads": ("dlrover.engine.step", "main"),
    "dlrover.engine.prefill_chunk": ("dlrover.engine.step", "main"),
    "dlrover.engine.push_table": ("dlrover.engine.step", "main"),
    "dlrover.engine.decode_chunk": ("dlrover.engine.step", "main"),
    "dlrover.engine.verify": ("dlrover.engine.step", "main"),
    "dlrover.engine.deliver": ("dlrover.engine.step", "main"),
}
COMMIT_CHILDREN = [n for n, (parent, _) in TRAIN_SPANS.items()
                   if parent == "dlrover.ckpt.commit"]
NEW_COUNTERS = ("dlrover_ckpt_d2h_seconds_total",
                "dlrover_ckpt_shm_copy_seconds_total",
                "dlrover_ckpt_lock_wait_seconds_total",
                "dlrover_ckpt_bytes_committed_total",
                "dlrover_ckpt_d2h_bytes_total",
                "dlrover_ckpt_saves_skipped_total")


@contextlib.contextmanager
def _ckpt_job():
    """A checkpoint namespace of its own (shm, IPC sockets, the in-process
    saver), torn down after: what each test that saves runs inside."""
    uid = "spans" + uuid.uuid4().hex[:8]
    was = os.environ.get("DLROVER_JOB_UID")
    os.environ["DLROVER_JOB_UID"] = uid
    try:
        yield uid
    finally:
        AsyncCheckpointSaver.reset()
        if was is None:
            os.environ.pop("DLROVER_JOB_UID", None)
        else:
            os.environ["DLROVER_JOB_UID"] = was
        for f in os.listdir("/dev/shm"):
            if uid in f:
                try:
                    os.unlink(os.path.join("/dev/shm", f))
                except OSError:
                    pass


@pytest.fixture()
def job():
    with _ckpt_job() as uid:
        yield uid


@contextlib.contextmanager
def _traced(trace_dir):
    """A profiler session with the reader's window marker around all of
    it (without one the window is first to last device event)."""
    with profiler.trace(str(trace_dir)):
        with profiler.span("bench.window"):
            yield


def _parse(trace_dir):
    return ps.load(trace_reduce.newest_xplane(str(trace_dir)),
                   cpu_rehearsal=True)


def _tokens(step, rows, seq, vocab):
    return np.random.RandomState(step).randint(
        0, vocab, size=(rows, seq)).astype(np.int32)


@pytest.fixture(scope="module")
def train_run(tmp_path_factory):
    """Four steps of a tiny trainer, memory saves after steps 2 and 4,
    under the profiler; the engine's counters before and after."""
    with _ckpt_job():
        return _train_run(tmp_path_factory.mktemp("train"))


def _train_run(tmp):
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    trainer = ElasticTrainer(
        LlamaModel(cfg), global_batch_size=2, micro_batch_per_shard=2,
        seq_len=16, checkpoint_dir=str(tmp / "ckpt"),
        save_memory_interval=2, save_storage_interval=0,
        saver_mode=SaverMode.LOCAL)
    trainer.prepare(devices=jax.devices()[:1])
    trainer.restore_or_init(jax.random.PRNGKey(0))
    engine = trainer.checkpoint_engine
    # compile outside the trace
    jax.block_until_ready(
        trainer.train_step(_tokens(0, 2, 16, cfg.vocab_size)))
    before = engine.ckpt_metrics()
    saved = []
    with _traced(tmp / "trace"):
        for step in range(1, 5):
            jax.block_until_ready(
                trainer.train_step(_tokens(step, 2, 16, cfg.vocab_size)))
            saved.append(trainer.maybe_save())
        assert engine.flush(timeout=60.0)
    after = engine.ckpt_metrics()
    trainer.close()
    leaves = len(jax.tree_util.tree_leaves(trainer.state))
    return {"parsed": _parse(tmp / "trace"), "before": before,
            "after": after, "saved": saved, "trainer_step": trainer.step,
            "leaves": leaves}


@pytest.fixture(scope="module")
def serve_run(tmp_path_factory):
    """A router over two tiny paged engines, under the profiler: the first
    prefills in chunks and decodes in chunks, the second speculates."""
    from dlrover_tpu.serving.engine import InferenceEngine

    tmp = tmp_path_factory.mktemp("serve")
    cfg = LlamaConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    variables = LlamaModel(cfg).init(jax.random.PRNGKey(0),
                                     jnp.zeros((1, 8), jnp.int32))
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=16))
    chunked = InferenceEngine(cfg, variables, max_slots=2, chunk=4,
                              paged=True, block_size=16, prefill_chunk=16,
                              temperature=0.0, attention_impl="pallas")
    speculating = InferenceEngine(cfg, variables, max_slots=2, chunk=4,
                                  paged=True, block_size=16,
                                  speculative_k=3, temperature=0.0)
    rng = np.random.RandomState(0)

    def serve(lengths):
        reqs = [router.submit(
            rng.randint(1, cfg.vocab_size, n).astype(np.int32), 6)
            for n in lengths]
        router.run_until_idle(max_steps=500)
        assert all(r.state == "Done" for r in reqs)
        return reqs

    with _traced(tmp / "trace"):
        router.join_replica("chunked", InferenceEngineAdapter(chunked))
        serve([8, 24])          # one bucketed prefill, one chunked
        router.begin_drain("chunked")
        router.join_replica("speculating",
                            InferenceEngineAdapter(speculating))
        reqs = serve([8])
    return {"parsed": _parse(tmp / "trace"), "reqs": reqs,
            "stats": [chunked.stats, speculating.stats]}


def _check_span(parsed, name, parent, kind):
    found = ps.named(parsed, name)
    assert found, f"no {name} in the trace"
    main = {line for line in parsed["threads"] if not line.startswith(WRITER)}
    for line, start, dur, _ in found:
        assert line.startswith(WRITER) == (kind == "writer"), (name, line)
        if parent is not None:
            assert any(pl == line and s <= start and s + d >= start + dur
                       for pl, s, d, _ in ps.named(parsed, parent)), \
                f"{name} at {start} is under no {parent} on {line}"
    assert len(main) == 1   # one training / serving thread in these runs


@pytest.mark.parametrize("name", sorted(TRAIN_SPANS))
def test_training_span_is_in_the_trace_under_its_parent(train_run, name):
    _check_span(train_run["parsed"], name, *TRAIN_SPANS[name])


@pytest.mark.parametrize("name", sorted(SERVE_SPANS))
def test_serving_span_is_in_the_trace_under_its_parent(serve_run, name):
    _check_span(serve_run["parsed"], name, *SERVE_SPANS[name])


def test_span_attributes_are_the_events_stats(train_run, serve_run):
    parsed = train_run["parsed"]
    steps = [a["step_num"] for _, _, _, a in
             ps.named(parsed, "dlrover.trainer.step")]
    assert steps == [2, 3, 4, 5]        # named by the step they complete
    saves = [(a["due"], a["tier"]) for _, _, _, a in
             ps.named(parsed, "dlrover.trainer.maybe_save")]
    assert saves == [(1, "MEMORY"), (0, "none"), (1, "MEMORY"), (0, "none")]
    assert train_run["saved"] == [True, False, True, False]
    assert [a["step"] for _, _, _, a in
            ps.named(parsed, "dlrover.ckpt.commit")] == [2, 4]
    assert all(a["bytes"] > 0 for _, _, _, a in
               ps.named(parsed, "dlrover.ckpt.shm_copy"))
    served = serve_run["parsed"]
    assert {a["replica"] for _, _, _, a in
            ps.named(served, "dlrover.router.pump")} \
        == {"chunked", "speculating"}
    # a program's attributes are on the span that ends in its result;
    # the span of a dispatch with nothing in flight has none
    prefills = [a for _, _, _, a in
                ps.named(served, "dlrover.engine.prefill") if a]
    booked = serve_run["stats"][0]
    assert len(prefills) == sum(s.prefill_calls - s.prefill_chunks
                                for s in serve_run["stats"])
    assert all(a["bucket"] >= 8 and a["n"] == 1 for a in prefills)
    reads = [a for _, _, _, a in ps.named(served, "dlrover.engine.reads")]
    assert sum(a["dispatches"] for a in reads) + sum(
        s.spec_calls for s in serve_run["stats"]) \
        == sum(s.dispatches for s in serve_run["stats"])
    assert sum(a["chained"] for a in reads) \
        == sum(s.chained_dispatches for s in serve_run["stats"]) > 0
    # the paged kernel's rows, booked before each chunk's dispatch (the
    # speculating engine decodes through the gather: it books none)
    chunks = [a for _, _, _, a in
              ps.named(served, "dlrover.engine.decode_chunk") if a]
    assert sum(a["kv_rows_live"] for a in chunks) \
        == booked.kv_rows_live > 0
    assert sum(a["kv_rows_streamed"] for a in chunks) \
        == booked.kv_rows_streamed >= booked.kv_rows_live


def test_counters_are_registered_monotone_and_inside_the_commit(train_run):
    before, after = train_run["before"], train_run["after"]
    assert set(NEW_COUNTERS) <= set(after)
    assert all(after[k] >= before[k] for k in after if k.endswith("_total"))
    assert after["dlrover_ckpt_saves_committed_total"] \
        - before["dlrover_ckpt_saves_committed_total"] == 2
    assert after["dlrover_ckpt_bytes_committed_total"] \
        > before["dlrover_ckpt_bytes_committed_total"]
    parts = sum(after[k] - before[k] for k in NEW_COUNTERS
                if k.endswith("_seconds_total"))
    commit = after["dlrover_ckpt_commit_seconds_total"] \
        - before["dlrover_ckpt_commit_seconds_total"]
    assert 0 < parts <= commit
    # the counters and the spans are stamped at the same boundaries: a
    # piece's span lies INSIDE the interval its counter takes, so the
    # counter is the spans plus every span's own entering and leaving
    # (microseconds apiece, more on a loaded machine: this state's
    # copies take about a millisecond in all)
    copy = ps.totals(train_run["parsed"])["dlrover.ckpt.shm_copy"]
    counted = after["dlrover_ckpt_shm_copy_seconds_total"] \
        - before["dlrover_ckpt_shm_copy_seconds_total"]
    assert copy["seconds"] - 2e-4 <= counted \
        <= 1.2 * copy["seconds"] + 2e-4 + 5e-5 * copy["count"]


@pytest.mark.parametrize("name", NEW_COUNTERS)
def test_new_checkpoint_counter_is_in_the_registry(name):
    assert METRIC_HELP[name].strip()


def test_commit_children_tile_the_commit(job, tmp_path):
    """32 MB through the real writer: lock wait, the two D2H spans, shm
    alloc, the copy, the publish and the lock's release (one round trip,
    3-10 ms on a loaded machine: ``dlrover.ckpt.unlock``) add up to the
    commit."""
    engine = CheckpointEngine(str(tmp_path / "ckpt"),
                              saver_mode=SaverMode.LOCAL)
    state = {f"w{i}": jnp.full((1024, 1024), float(i), jnp.float32)
             for i in range(8)}
    try:
        with _traced(tmp_path / "trace"):
            jax.block_until_ready(jnp.zeros(8) + 1)    # a device event
            for step in (1, 2):
                assert engine.save_to_memory(step, state, block=True)
        spans = ps.totals(_parse(tmp_path / "trace"))
        assert spans["dlrover.ckpt.commit"]["count"] == 2
        commit = spans["dlrover.ckpt.commit"]["seconds"]
        children = sum(spans[n]["seconds"] for n in COMMIT_CHILDREN)
        assert children <= commit
        assert children == pytest.approx(commit, rel=0.05)
        assert spans["dlrover.ckpt.commit"]["self_seconds"] \
            == pytest.approx(commit - children, abs=1e-6)
    finally:
        engine.close()


def test_commit_has_one_d2h_wait_a_piece_with_its_bytes(train_run):
    """The streaming pass: under each commit one ``d2h_wait`` and one
    ``shm_copy`` a piece (here a leaf: one device), one ``d2h_dispatch`` a
    device piece, each with the piece's ``bytes``; the waits' bytes add up
    to what the counters say crossed and was committed."""
    parsed = train_run["parsed"]
    before, after = train_run["before"], train_run["after"]
    commits = ps.named(parsed, "dlrover.ckpt.commit")
    waits = ps.named(parsed, "dlrover.ckpt.d2h_wait")
    copies = ps.named(parsed, "dlrover.ckpt.shm_copy")
    dispatches = ps.named(parsed, "dlrover.ckpt.d2h_dispatch")
    pieces = train_run["leaves"]
    assert len(commits) == 2
    assert len(waits) == len(copies) == len(dispatches) == 2 * pieces
    for line, start, dur, _ in waits + copies + dispatches:
        assert any(cl == line and s <= start and s + d >= start + dur
                   for cl, s, d, _ in commits)
    crossed = after["dlrover_ckpt_d2h_bytes_total"] \
        - before["dlrover_ckpt_d2h_bytes_total"]
    assert sum(a["bytes"] for _, _, _, a in waits) == crossed \
        == sum(a["bytes"] for _, _, _, a in dispatches) \
        == after["dlrover_ckpt_bytes_committed_total"] \
        - before["dlrover_ckpt_bytes_committed_total"]
    # the bytes in flight as a wait opens: its own piece at the least,
    # never more than the budget beside a piece that fits it
    from dlrover_tpu.trainer.flash_checkpoint import shm_handler

    for _, _, _, a in waits:
        assert a["bytes"] <= a["in_flight"] <= max(
            a["bytes"], shm_handler.D2H_BUDGET_BYTES)


def test_a_save_skipped_at_the_barrier_is_counted(job, tmp_path):
    engine = CheckpointEngine(str(tmp_path / "ckpt"),
                              saver_mode=SaverMode.LOCAL)
    engine.STAGE_BARRIER_S = 0.0        # on the instance: no waiting
    state = {"w": np.arange(16, dtype=np.float32)}
    try:
        # the saver "persists": the writer parks in lock_wait
        engine._ensure_saver()
        assert engine._shm_lock.acquire(blocking=False, owner="saver")
        assert engine.save_to_memory(1, state)
        assert engine.save_to_memory(2, state) is False
        m = engine.ckpt_metrics()
        assert m["dlrover_ckpt_saves_skipped_total"] == 1
        assert m["dlrover_ckpt_saves_staged_total"] == 1
        engine._shm_lock.release(owner="saver")
        assert engine.flush(timeout=30.0)
        m = engine.ckpt_metrics()
        assert m["dlrover_ckpt_saves_committed_total"] == 1
        assert m["dlrover_ckpt_lock_wait_seconds_total"] > 0
        assert m["dlrover_ckpt_saves_skipped_total"] == 1
    finally:
        engine.close()


@pytest.mark.parametrize("step,engine_says,want", [
    (2, True, True), (2, False, False), (3, True, False)])
def test_maybe_save_returns_what_the_engine_answered(
        monkeypatch, job, tmp_path, step, engine_says, want):
    trainer = ElasticTrainer(
        LlamaModel(LlamaConfig.tiny()), global_batch_size=2,
        micro_batch_per_shard=2, seq_len=16,
        checkpoint_dir=str(tmp_path / "ckpt"), save_memory_interval=2,
        save_storage_interval=0, saver_mode=SaverMode.LOCAL)
    try:
        assert trainer.checkpoint_engine is trainer._ckpt.engine
        calls = []
        monkeypatch.setattr(
            trainer._ckpt, "save_checkpoint",
            lambda *a, **kw: calls.append(a) or engine_says)
        trainer._host_step = step
        assert trainer.maybe_save() is want
        assert len(calls) == (step % 2 == 0)
    finally:
        trainer.close()


def test_a_trainer_without_checkpoints_has_no_engine():
    trainer = ElasticTrainer(
        LlamaModel(LlamaConfig.tiny()), global_batch_size=2,
        micro_batch_per_shard=2, seq_len=16)
    assert trainer.checkpoint_engine is None
    assert trainer.maybe_save() is False


def test_a_span_with_no_session_costs_microseconds_and_leaves_nothing(
        tmp_path):
    costs = []
    for _ in range(10_000):
        t0 = time.perf_counter_ns()
        with profiler.span("dlrover.test.before_session", step=3):
            pass
        costs.append(time.perf_counter_ns() - t0)
    assert statistics.median(costs) < 5_000
    phases = profiler.PhaseSpans("dlrover.test.phase.")
    phases.enter("a")
    phases.close()
    # nothing was buffered: a session opened afterwards holds only what
    # ran inside it
    with _traced(tmp_path):
        jax.block_until_ready(jnp.zeros(8) + 1)
        with profiler.span("dlrover.test.inside_session"):
            pass
        phases.enter("b")
        phases.enter("c")
        phases.enter(None)
    names = set(ps.totals(_parse(tmp_path)))
    assert names == {"dlrover.test.inside_session", "dlrover.test.phase.b",
                     "dlrover.test.phase.c"}


def test_request_stamps_admission_and_deliveries_on_the_real_clock():
    from dlrover_tpu.serving.remote.worker import FakeEngine

    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4))
    # in process this engine streams nothing: its tokens arrive when done
    router.join_replica("fake", FakeEngine(slots=2, tokens_per_step=4))
    t0 = time.monotonic()
    req = router.submit(np.arange(1, 9, dtype=np.int32), 8, now=5.0)
    assert req.admitted_at is None and req.deliveries == 0
    router.step(now=6.0)        # placed and pumped once
    assert req.admitted_at == req.dispatched_at >= t0
    # the router's own ``now`` (here a test's) stamps first_token_at ...
    assert req.first_token_at == 6.0
    assert (req.deliveries, req.last_delivery_at) == (0, None)
    router.step(now=7.0)
    # ... a delivery reads the clock itself, when the tokens change hands
    assert req.state == "Done" and len(req.output) == 8
    assert req.deliveries == 1
    assert req.admitted_at <= req.last_delivery_at <= time.monotonic()
    req.restart_stream()
    assert (req.admitted_at, req.deliveries, req.last_delivery_at) \
        == (None, 0, None)


def test_served_requests_carry_the_stamps(serve_run):
    # a streaming engine: the first token, then a delivery a decode round
    for req in serve_run["reqs"]:
        assert req.deliveries >= 2 and len(req.output) == 6
        assert req.admitted_at <= req.last_delivery_at


def test_engine_spans_time_what_the_engine_counters_time(serve_run):
    """``decode_seconds`` and ``prefill_seconds`` run, for each program,
    from its dispatch (or the result before it) to its result: they hold
    the program's spans (its wait, and its dispatch where nothing was in
    flight) and the host's dispatching of the programs chained behind
    it.  A decode chunk is read by the step AFTER the one that
    dispatched it (the step looks ahead), so its time runs across the
    step's boundary: the clocks lie inside the stretch from the first
    step's start to the last step's end, not inside the steps, and the
    chunk's wait is the first span under the next step's reads.  A
    program that is alone in its step, as a verify is, reads as its one
    span (both engines were made for this trace and ran inside it
    only)."""
    parsed = serve_run["parsed"]
    spans = ps.totals(parsed)
    chunked, speculating = serve_run["stats"]
    counted = sum(s.decode_seconds + s.prefill_seconds
                  for s in serve_run["stats"])
    traced = sum(spans[n]["seconds"] for n in (
        "dlrover.engine.decode_chunk", "dlrover.engine.verify",
        "dlrover.engine.prefill", "dlrover.engine.prefill_chunk"))
    steps = ps.named(parsed, "dlrover.engine.step")
    stretch = max(t + d for _, t, d, _ in steps) \
        - min(t for _, t, _, _ in steps)
    assert 0 < traced <= counted <= stretch * 1e-9
    assert chunked.lookahead_steps > 0 == speculating.lookahead_steps
    waits = sorted(
        (t, name) for name in ("dlrover.engine.decode_chunk",
                               "dlrover.engine.prefill",
                               "dlrover.engine.prefill_chunk")
        for _, t, _, a in ps.named(parsed, name) if a)
    for _, start, dur, _ in ps.named(parsed, "dlrover.engine.reads"):
        inside = [n for t, n in waits if start <= t < start + dur]
        assert "dlrover.engine.decode_chunk" not in inside[1:], inside
    assert 0 < spans["dlrover.engine.prefill_chunk"]["seconds"] \
        <= chunked.prefill_chunk_seconds <= chunked.prefill_seconds
    assert spans["dlrover.engine.verify"]["seconds"] \
        == pytest.approx(speculating.decode_seconds, rel=0.02)


# -- a request's own clock (ISSUE 52) -----------------------------------------

REQUEST_EVENTS = ("placed", "admitted", "first_token", "first_delivery",
                  "finished")
# (prompt tokens, what it shares): more requests than the two slots, so
# some wait in the router's queue; the last stands behind the first 32
# tokens (two blocks) of a prompt served before it
REQUEST_PROMPTS = [(40, None), (8, None), (24, None), (9, None), (30, None)]
WARM_PROMPT = (48, 32)


def _serve_requests():
    """One tiny paged engine of two slots that prefills in chunks of 16
    and shares prefixes, behind a router: ``REQUEST_PROMPTS`` at once,
    then the warm one.  Returns the router's requests, the engine and
    the engine's own requests by ``erid``."""
    from dlrover_tpu.serving.engine import InferenceEngine

    cfg = LlamaConfig.tiny(max_seq_len=96, dtype=jnp.float32)
    variables = LlamaModel(cfg).init(jax.random.PRNGKey(0),
                                     jnp.zeros((1, 8), jnp.int32))
    engine = InferenceEngine(cfg, variables, max_slots=2, chunk=4,
                             paged=True, block_size=16, prefill_chunk=16,
                             temperature=0.0, max_len=96)
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=16))
    router.join_replica("one", InferenceEngineAdapter(engine))
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
               for n, _ in REQUEST_PROMPTS]
    reqs = [router.submit(p, 9) for p in prompts]
    router.run_until_idle(max_steps=500)
    size, shared = WARM_PROMPT
    warm = np.concatenate([prompts[0][:shared], rng.randint(
        1, cfg.vocab_size, size - shared).astype(np.int32)])
    reqs.append(router.submit(warm, 9))
    router.run_until_idle(max_steps=500)
    assert all(r.state == "Done" and len(r.output) == 9 for r in reqs)
    return reqs, engine, {r.rid: r for r in engine._finished}


@pytest.fixture(scope="module")
def request_run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("requests")
    with _traced(tmp / "trace"):
        reqs, engine, ereqs = _serve_requests()
    return {"parsed": _parse(tmp / "trace"), "reqs": reqs,
            "engine": engine, "ereqs": ereqs}


def _request_events(parsed, what, key):
    found = {}
    for _, _, _, a in ps.named(parsed, "dlrover.request." + what):
        assert a[key] not in found, (what, a)      # exactly one each
        found[a[key]] = a
    return found


def test_a_request_is_one_key_from_the_queue_to_its_last_chunk(request_run):
    parsed, reqs = request_run["parsed"], request_run["reqs"]
    placed = _request_events(parsed, "placed", "rid")
    first_delivery = _request_events(parsed, "first_delivery", "rid")
    admitted, first_token, finished = (
        _request_events(parsed, what, "erid")
        for what in ("admitted", "first_token", "finished"))
    rids = {r.rid for r in reqs}
    assert set(placed) == set(first_delivery) == rids
    erids = {placed[rid]["erid"] for rid in rids}
    assert set(admitted) == set(first_token) == set(finished) == erids \
        == {r.engine_rid for r in reqs}
    step_ms = max(d for _, _, d, _ in
                  ps.named(parsed, "dlrover.router.step")) / 1e6
    waited = 0
    for req in reqs:
        rid, erid = req.rid, req.engine_rid
        assert placed[rid]["replica"] == "one"
        assert placed[rid]["prompt_tokens"] == req.prompt.size \
            == admitted[erid]["prompt_tokens"]
        # the router's clock to the engine's read of the first token is
        # the three waits; the engine takes the request a little into the
        # router step whose start ended the first
        parts = placed[rid]["queue_wait_ms"] \
            + admitted[erid]["slot_wait_ms"] \
            + first_token[erid]["prefill_ms"]
        assert 0 <= first_delivery[rid]["ttft_ms"] - parts <= step_ms
        assert first_token[erid]["since_queued_ms"] == pytest.approx(
            admitted[erid]["slot_wait_ms"]
            + first_token[erid]["prefill_ms"])
        waited += placed[rid]["queue_wait_ms"] > step_ms
        assert finished[erid]["tokens"] == 9
        # the first token, then a delivery a decode chunk of four; the
        # router hands the two chunks on together (the last chunk's
        # dispatch took the request off its slot, and a pump streams
        # what holds a slot: PERF.md section 7, the look-ahead (c))
        assert finished[erid]["deliveries"] == 3 == req.deliveries + 1
        assert 0 < finished[erid]["gap_ms_max"] \
            <= finished[erid]["decode_ms"]
        # its prompt's programs are found among the device's by its erid
        ran = [a for name in ("prefill", "prefill_chunk")
               for _, _, _, a in ps.named(parsed, "dlrover.engine." + name)
               if str(erid) in str(a.get("erids", "")).split()]
        assert len(ran) == admitted[erid]["chunks"] \
            == first_token[erid]["steps"]
    assert waited >= 2          # two slots: the others queued in the router


def test_deliveries_in_the_trace_add_up_to_the_engines_counters(request_run):
    """The engine was made inside the session: what its ``.deliver`` and
    request events say is what ``EngineStats`` summed."""
    parsed, stats = request_run["parsed"], request_run["engine"].stats
    delivers = [a for _, _, _, a in
                ps.named(parsed, "dlrover.engine.deliver")]
    assert {a["program"] for a in delivers} \
        == {"prefill_chunk", "decode_chunk"}
    assert sum(a["gaps"] for a in delivers) == stats.token_gaps == 12
    assert sum(a["gap_ms_sum"] for a in delivers) == pytest.approx(
        stats.token_gap_seconds * 1e3)
    assert max(a["gap_ms_max"] for a in delivers) <= max(
        r.gap_max for r in request_run["ereqs"].values()) * 1e3 + 1e-6
    assert sum(a["tokens"] for a in delivers) \
        == stats.generated_tokens + stats.first_tokens == 6 * 9
    firsts = _request_events(parsed, "first_token", "erid").values()
    admitted = _request_events(parsed, "admitted", "erid").values()
    assert len(firsts) == stats.first_tokens == 6
    assert sum(a["prefill_ms"] for a in firsts) == pytest.approx(
        stats.prefill_wall_seconds * 1e3)
    assert sum(a["slot_wait_ms"] for a in admitted) == pytest.approx(
        stats.slot_wait_seconds * 1e3)
    assert sum(a["prompt_tokens"] for a in admitted) \
        == stats.prompt_tokens == sum(n for n, _ in REQUEST_PROMPTS) + 48
    # ... and they ride the replica's dict as they are
    handed = InferenceEngineAdapter(request_run["engine"]).engine_metrics()
    assert {n: handed[n] for n in stats.REQUEST_CLOCK} \
        == {n: float(getattr(stats, n)) for n in stats.REQUEST_CLOCK}


def test_a_warm_start_books_where_its_prefill_began(request_run):
    from dlrover_tpu.serving.paged import warm_start

    size, shared = WARM_PROMPT
    began = warm_start(shared, size, 16)
    assert began == 32
    admitted = _request_events(request_run["parsed"], "admitted", "erid")
    *cold, warm = request_run["reqs"]
    ereqs = request_run["ereqs"]
    assert ereqs[warm.engine_rid].cached_tokens == began \
        == admitted[warm.engine_rid]["cached_tokens"]
    # one program ran the 16 tokens behind them
    assert ereqs[warm.engine_rid].prompt_chunks == 1 \
        == admitted[warm.engine_rid]["chunks"]
    for req in cold:
        assert ereqs[req.engine_rid].cached_tokens == 0 \
            == admitted[req.engine_rid]["cached_tokens"]
        assert ereqs[req.engine_rid].prompt_chunks \
            == -(-req.prompt.size // 16)
    assert request_run["engine"].stats.prompt_tokens_cached == began


def test_with_no_session_the_events_leave_nothing(request_run, tmp_path):
    """The same requests with no profiler open: the engine steps to the
    same tokens and books the same counts, and a session opened
    afterwards holds no event of theirs."""
    reqs, engine, ereqs = _serve_requests()
    assert [r.output for r in reqs] \
        == [r.output for r in request_run["reqs"]]
    traced = request_run["engine"].stats
    for name in ("first_tokens", "token_gaps", "prompt_tokens",
                 "prompt_tokens_cached", "generated_tokens", "dispatches"):
        assert getattr(engine.stats, name) == getattr(traced, name), name
    assert all(r.first_token_at <= r.last_token_at and r.deliveries == 3
               for r in ereqs.values())
    with _traced(tmp_path):
        jax.block_until_ready(jnp.zeros(8) + 1)
    assert not any(n.startswith("dlrover.request.")
                   or n == "dlrover.engine.deliver"
                   for n in ps.totals(_parse(tmp_path)))
