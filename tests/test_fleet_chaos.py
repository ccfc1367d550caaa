"""Fleet-migration chaos suite (ISSUE 11): crash-safe train⇄serve
chip repurposing with lease-fenced exactly-once capacity handoff.

The harness is a full in-thread fleet on a synthetic clock: three
training hosts behind a REAL ElasticTrainingRendezvousManager (driven
fake agents), a REAL JobMetricCollector goodput ledger, a REAL Flash
Checkpoint blocking-save barrier (tiny numpy state through the actual
shm engine), a serving router with a brown-out ladder and a two-replica
base fleet, and the FleetCoordinator under test with a journal on
tmp_path.

The chaos acceptance (CHAOS.md F1-F6): coordinator killed mid-borrow
and mid-return (a NEW incarnation reconstructs every lease from master
+ supervisor ground truth, stale-epoch claims fenced), the borrowed
worker killed mid-boot, the master restarted mid-shrink — and through
all of it: zero lost serving requests, training resuming exactly on
the committed checkpoint step, every lease ending single-owner, every
handoff debt retired exactly once.
"""

import os
import time
import uuid

import numpy as np
import pytest

from dlrover_tpu.common.constants import (  # noqa: E402
    FLEET_HOST_TRANSITIONS,
    FleetOwner,
)
from dlrover_tpu.fleet import (  # noqa: E402
    FleetCoordinator,
    LeaseLedger,
    LeaseTransitionError,
    ServingPlane,
    StaleLeaseError,
    TrainingPlane,
)
from dlrover_tpu.master.elastic_training.rdzv_manager import (  # noqa: E402
    ElasticTrainingRendezvousManager,
)
from dlrover_tpu.master.stats.job_collector import (  # noqa: E402
    JobMetricCollector,
)
from dlrover_tpu.serving.remote.supervisor import (  # noqa: E402
    WorkerRecord,
    WorkerSupervisor,
)
from dlrover_tpu.serving.remote.worker import FakeEngine  # noqa: E402
from dlrover_tpu.serving.router import (  # noqa: E402
    PRIORITY_NORMAL,
    BrownoutPolicy,
    ContinuousBatchScheduler,
    RouterMetrics,
    ServingRouter,
)
from dlrover_tpu.serving.router.replica import (  # noqa: E402
    base_replica_name,
)
from dlrover_tpu.trainer.flash_checkpoint import (  # noqa: E402
    Checkpointer,
    SaverMode,
    StorageType,
)


@pytest.fixture(autouse=True)
def _isolate(monkeypatch):
    """Unique job uid per test so checkpoint shm segments/queues never
    collide across harnesses; reset the saver singleton and sweep the
    job's shm afterwards (same hygiene as test_flash_checkpoint)."""
    from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver

    job = uuid.uuid4().hex[:8]
    monkeypatch.setenv("DLROVER_JOB_UID", job)
    yield
    AsyncCheckpointSaver.reset()
    for fn in os.listdir("/dev/shm"):
        if job in fn:
            try:
                os.unlink(os.path.join("/dev/shm", fn))
            except OSError:
                pass


def _prompt(i, n=8):
    return np.full(n, i % 251, np.int32)


class _StubProc:
    def __init__(self, pid):
        self.pid = pid
        self.returncode = None

    def poll(self):
        return self.returncode


class _StubProxy:
    def close(self, goodbye=True):
        pass


class _FleetStubSupervisor(WorkerSupervisor):
    """spawn() without fork/exec, but WITH a router join: a fleet boot
    becomes a FakeEngine replica, so the borrowed host really takes
    traffic through the router's pump.  ``fail_next`` makes the next N
    spawns die mid-boot (the worker SIGKILLed before its announce —
    exactly what the supervisor's announce timeout surfaces).  ``clock``
    is the harness's synthetic clock: the join happens at ITS time, so
    the replica's first heartbeat and the router's steps share a clock
    whatever the machine's uptime is."""

    def __init__(self, clock, **kw):
        super().__init__(**kw)
        self.clock = clock
        self._pid = 5000
        self.fail_next = 0
        self.boot_failures = 0
        self.spawn_counts = {}

    def spawn(self, name=None, join=True, managed=True):
        if self.fail_next > 0:
            self.fail_next -= 1
            self.boot_failures += 1
            raise RuntimeError(
                "worker killed mid-boot: announce never arrived")
        self._pid += 1
        record = WorkerRecord(
            name, _StubProc(self._pid), "127.0.0.1:0", _StubProxy(),
            managed)
        with self._lock:
            self.workers[name] = record
        if join and self.router is not None:
            self.router.join_replica(
                name, FakeEngine(slots=2, tokens_per_step=2),
                now=self.clock())
        self.spawn_counts[name] = self.spawn_counts.get(name, 0) + 1
        return record


class _Fleet:
    """One fleet under fire, in a box (see module docstring)."""

    def __init__(self, tmp_path, n_hosts=3, min_train_hosts=2,
                 base_replicas=2, journal=True, dwell=0.3):
        # min_train_hosts=2 of 3 hosts -> exactly ONE lendable host
        # (host-2), which keeps every exactly-once count deterministic
        self.t = 1000.0
        self.rdzv = ElasticTrainingRendezvousManager()
        self.collector = JobMetricCollector()
        self.collector.mark_job_start(self.t)
        self.bo = BrownoutPolicy(enter_pressure=2.0,
                                 exit_pressure=0.5,
                                 dwell_seconds=0.2)
        self.router = ServingRouter(
            scheduler=ContinuousBatchScheduler(block_size=4),
            metrics=RouterMetrics(window_seconds=0.5),
            brownout=self.bo,
        )
        for i in range(base_replicas):
            self.router.join_replica(
                f"serving-replica-{i}",
                FakeEngine(slots=2, tokens_per_step=2), now=self.t)
        self.sup = _FleetStubSupervisor(
            lambda: self.t, router=self.router, respawn=False,
            recorder=self.router.recorder)
        self.hosts = {f"host-{r}": r for r in range(n_hosts)}
        self.ckpt = Checkpointer(
            str(tmp_path / "ckpt"), saver_mode=SaverMode.LOCAL,
            local_rank=0, local_world_size=1, node_rank=0, node_num=1)
        self.ckpt_fail = False
        self.barrier_steps = []      # committed steps per barrier call
        self.plane = TrainingPlane(
            self.rdzv, self.hosts, self._ckpt_barrier,
            collector=self.collector, min_nodes=1,
            recorder=self.router.recorder,
            wall_clock=lambda: self.t)
        self.serving = ServingPlane(self.router, self.sup)
        self.journal_path = str(tmp_path / "leases.json") if journal \
            else None
        self.min_train_hosts = min_train_hosts
        self.coord = FleetCoordinator(
            self.plane, self.serving,
            journal_path=self.journal_path,
            min_train_hosts=min_train_hosts,
            borrow_stage=1, dwell_seconds=dwell, boot_attempts=4,
            now=self.t)
        # simulated trainer.  Restart detection keys on (manager
        # identity, round): a master restart resets round numbering,
        # and a bare round compare can alias across the swap.
        self.step_n = 0
        self._world_key = (id(self.rdzv), self.rdzv.rdzv_round)
        self._restart_lag = 0        # ticks of restore/compile pause
        self.resume_steps = []       # restore step at each restart
        self.requests = []

    # ------------------------------------------------------- trainer sim
    def _ckpt_barrier(self):
        if self.ckpt_fail:
            raise RuntimeError("injected commit failure")
        ok = self.ckpt.save_checkpoint(
            self.step_n, {"w": np.full(8, self.step_n, np.float32)},
            StorageType.MEMORY, block=True)
        if not ok:
            raise RuntimeError("memory save refused")
        self.barrier_steps.append(self.step_n)
        return self.step_n

    def _restore_step(self):
        step, state = self.ckpt.engine.load()
        return int(step) if state is not None else 0

    def _drive_agents(self):
        """Fake per-host agents: join when expected-but-absent, and
        rejoin (the growth restart) when the master says waiting nodes
        could enlarge the world."""
        expected = set(self.plane.expected_hosts())
        for h, r in self.hosts.items():
            if h in expected and not self.rdzv.joined(r):
                self.rdzv.join_rendezvous(r, r, 1)
        if self.rdzv.num_nodes_waiting() > 0:
            for r in self.rdzv.current_world_ranks():
                self.rdzv.join_rendezvous(r, r, 1)
        self.rdzv.get_comm_world(0)  # drives round completion

    def _train_tick(self):
        world = self.rdzv.current_world_ranks()
        if not world or len(world) != self.plane.target_world:
            return
        if (id(self.rdzv), self.rdzv.rdzv_round) != self._world_key:
            # a membership change restarted the trainer: resume from
            # the committed checkpoint generation — THE assertion
            # surface for "training resumes exactly on the committed
            # step"
            self._world_key = (id(self.rdzv), self.rdzv.rdzv_round)
            restored = self._restore_step()
            if restored > 0:
                self.step_n = restored
            self.resume_steps.append(restored)
            # restore + recompile latency: a few ticks of pause, so
            # the bridging interval is a REAL stall (>3x the per-tick
            # median) the goodput radar can see and the planned-
            # elasticity attribution can claim
            self._restart_lag = 4
        if self._restart_lag > 0:
            self._restart_lag -= 1
            return
        self.step_n += 1
        self.collector.report_global_step(self.step_n, self.t)
        # per-step blocking memory save: every step is a committed
        # generation (tiny state; on real hardware this is the async
        # double-buffered path, blocking here makes restores exact)
        self.ckpt.save_checkpoint(
            self.step_n, {"w": np.full(8, self.step_n, np.float32)},
            StorageType.MEMORY, block=True)

    # ---------------------------------------------------------- the tick
    def tick(self, dt=0.05, coordinator=True):
        self.t += dt
        self._drive_agents()
        self._train_tick()
        self.sup.poll(now=self.t)
        self.router.step(now=self.t)
        if coordinator:
            self.coord.poll(now=self.t)
        # a fleet worker whose replica left the router (drain retired
        # or reaped dead) exits: GOODBYE -> rc 0 (the real worker's
        # voluntary-exit contract); the next sup.poll reaps it
        joined = {base_replica_name(n)
                  for n in self.router.replica_names}
        with self.sup._lock:
            records = list(self.sup.workers.values())
        for rec in records:
            if rec.proc.returncode is None and \
                    base_replica_name(rec.name) not in joined:
                rec.proc.returncode = 0

    def run(self, n, dt=0.05, until=None, coordinator=True):
        for _ in range(n):
            self.tick(dt, coordinator=coordinator)
            if until is not None and until():
                return True
        return until is None

    def spike(self, n=40, max_new=32, priority=PRIORITY_NORMAL):
        reqs = [self.router.submit(_prompt(i), max_new,
                                   priority=priority, now=self.t)
                for i in range(n)]
        self.requests.extend(reqs)
        return reqs

    def owners(self):
        return self.coord.ledger.owners()

    def close(self):
        self.ckpt.close()


@pytest.fixture
def fleet(tmp_path):
    f = _Fleet(tmp_path)
    yield f
    f.close()


# ---------------------------------------------------------------- F1/F6

def test_borrow_and_return_full_cycle_zero_lost(fleet):
    """The happy-path acceptance: sustained pressure borrows a host
    (durable ckpt commit -> shrink -> worker boots -> serves), falling
    pressure returns it (zero-lost drain -> regrow -> training resumes
    on the committed step), zero requests lost, every debt retired
    exactly once, every lease single-owner."""
    f = fleet
    # settle: world forms, trainer steps
    f.run(8)
    assert f.plane.world_hosts() == ["host-0", "host-1", "host-2"]
    assert all(o == FleetOwner.TRAINING for o in f.owners().values())

    f.spike(60)
    assert f.run(600, until=lambda: f.coord.borrows_total == 1), \
        f"borrow never completed: {f.coord.migrations} {f.owners()}"
    assert f.owners()["host-2"] == FleetOwner.SERVING
    # the release barrier ran, blocking, BEFORE the shrink
    assert f.barrier_steps, "checkpoint barrier never invoked"
    assert f.plane.last_committed_step == f.barrier_steps[-1]
    # training world shrank and resumed from the committed generation
    assert f.plane.world_hosts() == ["host-0", "host-1"]
    assert f.resume_steps and \
        f.resume_steps[-1] == f.barrier_steps[-1], (
            f.resume_steps, f.barrier_steps)
    # the borrowed host REALLY serves: its replica took placements
    handle = next(
        h for n, h in f.router.manager.replicas.items()
        if base_replica_name(n) == "host-2")
    # ISSUE 12: while on loan, the borrowed replica's origin is the
    # borrow trace, so request attempts landing on host-2 link back
    # to the decision that created it (pruned again at return-drain
    # retirement — a returned host carries no serving origin)
    origin = f.router.replica_origins.get("host-2")
    assert origin is not None and origin["kind"] == "fleet_borrow"
    # drain the spike so pressure falls; the return decision follows
    assert f.run(900, until=lambda: f.coord.returns_total == 1), \
        f"return never completed: {f.coord.migrations} {f.owners()}"
    assert handle.ever_placed, "borrowed replica never took traffic"
    assert f.owners()["host-2"] == FleetOwner.TRAINING
    f.run(10)
    assert f.plane.world_hosts() == ["host-0", "host-1", "host-2"]

    # ZERO lost serving requests: every admitted request completed
    for r in f.requests:
        r.result(timeout=5)
    assert f.router.gateway.poisoned == 0
    assert f.router.metrics.completed == len(f.requests)

    # exactly-once debts: one borrow + one return, each retired once
    assert f.coord.debts_retired_total == 2
    assert f.coord.open_debts() == []
    retired = sorted(
        (d["key"], d["retired_reason"]) for d in
        f.coord.debts.values())
    assert retired == [("borrow:host-2", "serving_joined"),
                       ("return:host-2", "training_joined")]

    # single-owner invariant + handoff latencies recorded
    assert f.coord.verify() == []
    assert f.coord.last_borrow_handoff_s > 0
    assert f.coord.last_return_handoff_s > 0

    # goodput: both windows were PLANNED elasticity, not downtime, and
    # no restart was ever charged
    g = f.collector.goodput()
    assert g["planned_windows"] >= 2, g
    assert g["planned_elasticity_s"] > 0, g
    assert g["restarts_observed"] == 0, g

    # migration traces are always-sampled and closed
    trees = f.router.tracer.traces_named("fleet_migration", limit=50)
    assert len(trees) >= 2
    assert {tr["spans"][0]["attrs"]["direction"] for tr in trees} >= \
        {"borrow", "return"}
    assert {tr["status"] for tr in trees if tr["status"]} <= \
        {"ok", "aborted"}

    # ISSUE 12 span links: the borrow trace references the pressure
    # evidence that pulled the trigger (no autoscaler here, so a
    # minted serving_pressure snapshot of the brown-out stage)
    borrow_tree = next(
        tr for tr in trees
        if tr["spans"][0]["attrs"]["direction"] == "borrow")
    links = borrow_tree["spans"][0].get("links") or []
    assert links, "the borrow root must link to its demand evidence"
    assert links[0]["attrs"]["rel"] == "evidence"
    evidence = f.router.tracer.get_tree(links[0]["trace_id"])
    assert evidence is not None \
        and evidence["name"] == "serving_pressure"
    assert evidence["spans"][0]["attrs"]["stage"] >= 1
    # the origin registered mid-loan (asserted above) was pruned when
    # the returned host's replica retired — no stale decision link
    # survives for a name that left the serving fleet
    assert "host-2" not in f.router.replica_origins


# ------------------------------------------------------------------- F2

def test_coordinator_killed_mid_borrow_recovers_and_finishes(tmp_path):
    """SIGKILL the coordinator between the world shrink and the worker
    boot (the worst instant: the host is in NEITHER world).  A new
    incarnation reconstructs from ground truth + journal intent,
    finishes the boot, and the handoff converges — the host is never
    double-provisioned."""
    f = _Fleet(tmp_path)
    try:
        f.run(8)
        f.spike(60)
        # wedge the boot so the migration parks between shrink and join
        f.sup.fail_next = 10 ** 6
        assert f.run(400, until=lambda: (
            "host-2" in f.coord.migrations
            and f.coord.migrations["host-2"]["phase"] == "boot"
            and f.plane.last_committed_step >= 0))
        assert "host-2" not in f.plane.alive_hosts()
        committed = f.plane.last_committed_step
        old = f.coord

        # the coordinator "process" dies; a new incarnation boots from
        # the journal + ground truth
        f.sup.fail_next = 0
        f.coord = FleetCoordinator(
            f.plane, f.serving, journal_path=f.journal_path,
            min_train_hosts=2, borrow_stage=1, dwell_seconds=0.3,
            boot_attempts=4, now=f.t)
        assert f.coord.ledger.epoch == old.epoch + 1
        # recovery classified the orphan as a mid-borrow host
        assert f.coord.ledger.owner("host-2") == \
            FleetOwner.MIGRATING_OUT
        assert f.run(400, until=lambda: f.coord.borrows_total == 1)
        assert f.coord.ledger.owner("host-2") == FleetOwner.SERVING
        # exactly once: ONE successful boot across both incarnations
        assert f.sup.spawn_counts.get("host-2") == 1
        # training kept running on the shrunk world from the committed
        # step throughout the coordinator outage
        assert f.resume_steps and f.resume_steps[-1] == committed
        assert f.coord.verify() == []
    finally:
        f.close()


def test_zombie_coordinator_is_fenced_after_recovery(tmp_path):
    """The old incarnation is not dead, only presumed dead — when it
    wakes up and tries to finish ITS migration, the lease epoch fences
    every claim (stale-epoch counter proves the fence fired)."""
    f = _Fleet(tmp_path)
    try:
        f.run(8)
        f.spike(60)
        f.sup.fail_next = 10 ** 6
        assert f.run(400, until=lambda: (
            "host-2" in f.coord.migrations
            and f.coord.migrations["host-2"]["phase"] == "boot"))
        zombie = f.coord
        # successor SHARES the ledger object (same journal authority)
        f.coord = FleetCoordinator(
            f.plane, f.serving, ledger=zombie.ledger,
            min_train_hosts=2, borrow_stage=1, dwell_seconds=0.3,
            boot_attempts=4, now=f.t)
        f.sup.fail_next = 0
        # the zombie wakes and tries to drive its stale migration to
        # completion: the first lease write is fenced, the zombie goes
        # inert instead of corrupting single-ownership
        fenced_before = zombie.ledger.stale_claims_fenced
        for _ in range(50):
            f.tick(coordinator=False)
            zombie.poll(now=f.t)
            if zombie.fenced:
                break
        assert zombie.fenced
        assert zombie.ledger.stale_claims_fenced > fenced_before
        # the successor still converges the handoff
        assert f.run(400, until=lambda: f.coord.borrows_total == 1)
        assert f.coord.verify() == []
        assert f.sup.spawn_counts.get("host-2") == 1
    finally:
        f.close()


# ------------------------------------------------------------------- F3

def test_coordinator_killed_mid_return_recovers_and_finishes(tmp_path):
    """Crash between the drain decision and the rendezvous regrow: the
    new incarnation reads the journal intent (MIGRATING_BACK), finishes
    the drain zero-lost, and training regrows to the full world."""
    f = _Fleet(tmp_path)
    try:
        f.run(8)
        f.spike(60)
        assert f.run(600, until=lambda: f.coord.borrows_total == 1)
        # let pressure fall until the return decision fires, then kill
        # the coordinator while the replica is still draining
        assert f.run(900, until=lambda: (
            "host-2" in f.coord.migrations
            and f.coord.migrations["host-2"]["kind"] == "return"))
        old = f.coord
        f.coord = FleetCoordinator(
            f.plane, f.serving, journal_path=f.journal_path,
            min_train_hosts=2, borrow_stage=1, dwell_seconds=0.3,
            boot_attempts=4, now=f.t)
        assert f.coord.ledger.epoch == old.epoch + 1
        assert f.run(900, until=lambda: f.coord.returns_total == 1)
        assert f.coord.ledger.owner("host-2") == FleetOwner.TRAINING
        f.run(10)
        assert f.plane.world_hosts() == \
            ["host-0", "host-1", "host-2"]
        # zero lost through the crash-straddling drain
        for r in f.requests:
            r.result(timeout=5)
        assert f.router.gateway.poisoned == 0
        assert f.coord.verify() == []
    finally:
        f.close()


# ------------------------------------------------------------------- F4

def test_borrowed_worker_killed_mid_boot_is_retried(fleet):
    """The freed host's worker dies before it can announce (SIGKILL
    mid-boot): the coordinator retries within its attempt budget and
    the borrow still lands — one debt, retired once."""
    f = fleet
    f.run(8)
    f.sup.fail_next = 2  # two boots die mid-announce
    f.spike(60)
    assert f.run(600, until=lambda: f.coord.borrows_total == 1)
    assert f.sup.boot_failures == 2
    assert f.sup.spawn_counts.get("host-2") == 1
    assert f.owners()["host-2"] == FleetOwner.SERVING
    retired = [d for d in f.coord.debts.values() if d["retired"]]
    assert [d["key"] for d in retired] == ["borrow:host-2"]
    assert f.coord.verify() == []


def test_boot_budget_exhausted_aborts_borrow_and_returns_host(
        tmp_path):
    """A host that cannot serve (every boot dies) is handed BACK:
    borrow aborted, world regrown, lease back to TRAINING — the fleet
    is never silently smaller."""
    f = _Fleet(tmp_path)
    try:
        f.run(8)
        f.sup.fail_next = 10 ** 6
        f.spike(60)
        assert f.run(900, until=lambda: (
            f.coord.borrow_aborts_total >= 1
            and f.owners().get("host-2") == FleetOwner.TRAINING))
        aborted = f.coord.debts["borrow:host-2"]
        assert aborted["retired"] and \
            aborted["retired_reason"] == "boot_failed"
        # pressure is still high, so the coordinator may try (and
        # abort) the borrow again — each cycle must stay safe.  End
        # the spike and the fleet converges back to the full world.
        for r in f.requests:
            r.cancel()
        assert f.run(600, until=lambda: (
            not f.coord.migrations
            and f.plane.world_hosts() ==
            ["host-0", "host-1", "host-2"]))
        assert f.owners()["host-2"] == FleetOwner.TRAINING
        assert f.coord.verify() == []
    finally:
        f.close()


def test_borrowed_worker_death_mid_serve_reopens_debt(fleet):
    """A borrowed worker dying while ON LOAN is a new capacity loss:
    the debt reopens as a new episode (PR-8 reopen discipline) and the
    host is re-booted — each episode retired exactly once."""
    f = fleet
    f.run(8)
    f.spike(60)
    assert f.run(600, until=lambda: f.coord.borrows_total == 1)
    # SIGKILL the borrowed worker mid-serve
    name = next(n for n in f.router.replica_names
                if base_replica_name(n) == "host-2")
    f.router.fail_replica(name)
    with f.sup._lock:
        rec = next(r for r in f.sup.workers.values()
                   if base_replica_name(r.name) == "host-2")
    rec.proc.returncode = 9
    assert f.run(200, until=lambda:
                 f.coord.debts_reopened_total == 1)
    assert f.run(200, until=lambda:
                 f.coord.debts["borrow:host-2"]["retired"])
    assert f.serving.worker_joined("host-2")
    assert f.sup.spawn_counts.get("host-2") == 2
    debt = f.coord.debts["borrow:host-2"]
    assert debt["retired_reason"] == "serving_joined"
    assert f.coord.verify() == []


# ------------------------------------------------------------------- F5

def test_master_restart_mid_shrink_converges(tmp_path):
    """The master dies and comes back EMPTY mid-shrink (worst case for
    ground truth): agents re-register, the coordinator's recovery keeps
    journal intent for the silent hosts, and the borrow converges with
    training resuming on the committed step."""
    f = _Fleet(tmp_path)
    try:
        f.run(8)
        f.spike(60)
        f.sup.fail_next = 10 ** 6   # park the migration post-shrink
        assert f.run(400, until=lambda: (
            "host-2" in f.coord.migrations
            and f.coord.migrations["host-2"]["phase"] == "boot"))
        committed = f.plane.last_committed_step
        # master restart: a FRESH rendezvous manager with empty state
        fresh = ElasticTrainingRendezvousManager()
        f.rdzv = fresh
        f.plane.adopt_rdzv(fresh)
        # and the coordinator dies with it — full control-plane loss
        f.coord = FleetCoordinator(
            f.plane, f.serving, journal_path=f.journal_path,
            min_train_hosts=2, borrow_stage=1, dwell_seconds=0.3,
            boot_attempts=4, now=f.t)
        # journal intent survives: hosts 0/1 stay TRAINING-owned even
        # though the fresh master knows nobody yet; host-2 resumes its
        # borrow
        assert f.coord.ledger.owner("host-0") == FleetOwner.TRAINING
        assert f.coord.ledger.owner("host-2") == \
            FleetOwner.MIGRATING_OUT
        f.sup.fail_next = 0
        assert f.run(600, until=lambda: f.coord.borrows_total == 1)
        assert f.run(100, until=lambda: len(
            f.plane.world_hosts()) == 2)
        # the survivors re-formed THEIR world and resumed from the
        # committed generation
        assert f.resume_steps and f.resume_steps[-1] >= committed
        assert f.coord.verify() == []
    finally:
        f.close()


# ----------------------------------------------------- guards & ledger

def test_checkpoint_barrier_failure_aborts_borrow(fleet):
    """No commit verdict, no shrink: the release barrier failing rolls
    the lease straight back — the training world never changed."""
    f = fleet
    f.run(8)
    f.ckpt_fail = True
    f.spike(60)
    assert f.run(300, until=lambda: f.coord.borrow_aborts_total >= 1)
    assert f.owners()["host-2"] == FleetOwner.TRAINING
    assert f.plane.world_hosts() == ["host-0", "host-1", "host-2"]
    debt = f.coord.debts["borrow:host-2"]
    assert debt["retired"] and debt["retired_reason"] == "ckpt_failed"
    assert f.coord.verify() == []


def test_starvation_guard_never_borrows_below_min(tmp_path):
    """``min_train_hosts`` is a hard floor: however hard serving
    burns, the coordinator refuses to loan the training world away."""
    f = _Fleet(tmp_path, min_train_hosts=2)
    try:
        f.run(8)
        f.spike(80)
        f.run(400, until=lambda: f.coord.borrows_total == 1)
        # sustained pressure (HIGH: never shed by the brown-out, so
        # admission cannot interfere), but never a second borrow
        from dlrover_tpu.serving.router import PRIORITY_HIGH

        f.spike(80, priority=PRIORITY_HIGH)
        f.run(300)
        training_owned = [h for h, o in f.owners().items()
                          if o == FleetOwner.TRAINING]
        assert len(training_owned) >= 2
        assert f.coord.borrows_total <= 1
    finally:
        f.close()


def test_lease_ledger_contract(tmp_path):
    """Unit contract: undeclared transitions refuse, stale epochs
    fence, the journal round-trips, and a torn journal degrades to
    ground-truth-only recovery instead of crashing."""
    path = str(tmp_path / "leases.json")
    led = LeaseLedger(journal_path=path)
    epoch = led.bump_epoch()
    led.acquire("h0", FleetOwner.TRAINING, epoch, now=1.0)
    # declared edge works
    led.transition("h0", FleetOwner.MIGRATING_OUT, epoch, now=2.0)
    # undeclared edge refuses (TRAINING is not reachable... SERVING
    # direct from MIGRATING_BACK-less state): MIGRATING_OUT ->
    # MIGRATING_BACK is NOT in the spec
    with pytest.raises(LeaseTransitionError):
        led.transition("h0", FleetOwner.MIGRATING_BACK, epoch)
    # stale epoch fences
    with pytest.raises(StaleLeaseError):
        led.transition("h0", FleetOwner.SERVING, epoch - 1)
    assert led.stale_claims_fenced == 1
    # journal round-trip
    led2 = LeaseLedger(journal_path=path)
    assert led2.epoch == epoch
    assert led2.owner("h0") == FleetOwner.MIGRATING_OUT
    # torn journal: unreadable file = start clean, not crash
    with open(path, "w") as fh:
        fh.write('{"epoch": 3, "leases": {tor')
    led3 = LeaseLedger(journal_path=path)
    assert led3.epoch == 0 and led3.owners() == {}
    # the spec itself is total over the enum (mirrors dlint's drift
    # pass at runtime)
    states = {v for k, v in vars(FleetOwner).items()
              if not k.startswith("_")}
    assert set(FLEET_HOST_TRANSITIONS) == states
    for targets in FLEET_HOST_TRANSITIONS.values():
        assert targets, "fleet owner cycle has no terminal states"
        assert set(targets) <= states


def test_fleet_metrics_surface(fleet):
    """Every dlrover_fleet_* gauge is emitted and registered."""
    from dlrover_tpu.utils.metric_registry import METRIC_HELP

    f = fleet
    f.run(8)
    m = f.coord.metrics()
    assert m["dlrover_fleet_hosts_training"] == 3.0
    assert m["dlrover_fleet_lease_epoch"] >= 1.0
    for name in m:
        assert name in METRIC_HELP, f"{name} missing from registry"


# ----------------------------------------------- slow subprocess twin

@pytest.mark.slow
def test_fleet_real_worker_processes_sigkill_mid_serve(tmp_path):
    """Nightly twin with REAL worker subprocesses: the borrow boots an
    actual ``python -m dlrover_tpu.serving.remote.worker`` process on
    the freed host, the process is SIGKILLed while serving (the debt
    reopens, a second real process boots), and the return drains
    zero-lost back to training — driven on the real clock end to end."""
    import signal as _signal

    pytest.importorskip("msgpack", reason="remote fabric frames")
    from dlrover_tpu.master.stats.job_collector import (
        JobMetricCollector,
    )

    rdzv = ElasticTrainingRendezvousManager()
    collector = JobMetricCollector()
    collector.mark_job_start()
    router = ServingRouter(
        scheduler=ContinuousBatchScheduler(block_size=4),
        metrics=RouterMetrics(window_seconds=0.5),
        brownout=BrownoutPolicy(enter_pressure=2.0, exit_pressure=0.5,
                                dwell_seconds=0.2),
    )
    for i in range(2):
        router.join_replica(f"serving-replica-{i}",
                            FakeEngine(slots=2, tokens_per_step=2))
    sup = WorkerSupervisor(router=router, engine="fake",
                           respawn=False, recorder=router.recorder)
    hosts = {f"host-{r}": r for r in range(3)}
    ckpt = Checkpointer(
        str(tmp_path / "ckpt"), saver_mode=SaverMode.LOCAL,
        local_rank=0, local_world_size=1, node_rank=0, node_num=1)
    step_box = {"n": 0}

    def barrier():
        assert ckpt.save_checkpoint(
            step_box["n"], {"w": np.full(64, step_box["n"],
                                         np.float32)},
            StorageType.MEMORY, block=True)
        return step_box["n"]

    plane = TrainingPlane(rdzv, hosts, barrier, collector=collector,
                          min_nodes=1, recorder=router.recorder)
    coord = FleetCoordinator(
        plane, ServingPlane(router, sup),
        journal_path=str(tmp_path / "leases.json"),
        min_train_hosts=2, borrow_stage=1, dwell_seconds=0.3,
        boot_attempts=4)
    last_round = [None]

    def tick():
        expected = set(plane.expected_hosts())
        for h, r in hosts.items():
            if h in expected and not rdzv.joined(r):
                rdzv.join_rendezvous(r, r, 1)
        if rdzv.num_nodes_waiting() > 0:
            for r in rdzv.current_world_ranks():
                rdzv.join_rendezvous(r, r, 1)
        rdzv.get_comm_world(0)
        world = rdzv.current_world_ranks()
        if world and len(world) == plane.target_world:
            if rdzv.rdzv_round != last_round[0]:
                last_round[0] = rdzv.rdzv_round
                restored, st = ckpt.engine.load()
                if st is not None and restored > 0:
                    step_box["n"] = int(restored)
            step_box["n"] += 1
            collector.report_global_step(step_box["n"], time.time())
        sup.poll()
        router.step()
        coord.poll()
        time.sleep(0.005)

    def run_until(cond, budget, what):
        deadline = time.monotonic() + budget
        while not cond():
            assert time.monotonic() < deadline, \
                f"{what}: {coord.migrations} {coord.ledger.owners()}"
            tick()

    try:
        run_until(lambda: rdzv.current_world_ranks(), 30, "world")
        reqs = [router.submit(_prompt(i), 256) for i in range(150)]
        run_until(lambda: coord.borrows_total == 1, 60, "borrow")
        committed = plane.last_committed_step
        # the borrowed host runs a REAL process: SIGKILL it mid-serve
        run_until(lambda: any(
            base_replica_name(n) == "host-2"
            for n in router.replica_names), 30, "join")
        sup.kill("host-2", _signal.SIGKILL)
        run_until(lambda: coord.debts_reopened_total == 1, 60,
                  "debt reopen")
        run_until(lambda: coord.serving.worker_joined("host-2"), 60,
                  "re-boot")
        for r in reqs:
            r.cancel()
        run_until(lambda: coord.returns_total == 1, 90, "return")
        run_until(lambda: len(plane.world_hosts()) == 3, 30, "regrow")
        # invariants: zero lost (every request terminal, none
        # poisoned), committed-step resume, single-owner leases
        assert router.gateway.poisoned == 0
        assert step_box["n"] >= committed
        assert coord.verify() == []
        assert coord.ledger.owners() == {
            h: FleetOwner.TRAINING for h in hosts}
        debt = coord.debts["borrow:host-2"]
        assert debt["retired"]
    finally:
        sup.shutdown()
        ckpt.close()


def test_reboot_budget_exhausted_returns_borrowed_host(fleet):
    """A borrowed host whose worker dies ON LOAN and then refuses every
    re-boot is not serving capacity — the coordinator walks it back to
    training through the declared lease edges (SERVING ->
    MIGRATING_BACK -> TRAINING via the regrow), never jumping them."""
    f = fleet
    f.run(8)
    f.spike(60)
    assert f.run(600, until=lambda: f.coord.borrows_total == 1)
    # kill the borrowed worker and wedge every re-boot
    name = next(n for n in f.router.replica_names
                if base_replica_name(n) == "host-2")
    f.router.fail_replica(name)
    with f.sup._lock:
        rec = next(r for r in f.sup.workers.values()
                   if base_replica_name(r.name) == "host-2")
    rec.proc.returncode = 9
    f.sup.fail_next = 10 ** 6
    assert f.run(400, until=lambda: f.coord.debts_reopened_total == 1)
    assert f.run(600, until=lambda: (
        f.owners().get("host-2") == FleetOwner.TRAINING))
    # the reboot's debt episode retired as boot_failed (read from the
    # recorder NOW — sustained pressure may legitimately re-borrow the
    # host and overwrite the debt entry with a fresh episode)
    assert any(
        e["kind"] == "fleet_debt_retired"
        and e["key"] == "borrow:host-2"
        and e["reason"] == "boot_failed"
        for e in f.router.recorder.events(256))
    f.sup.fail_next = 0
    for r in f.requests:
        r.cancel()
    assert f.run(600, until=lambda: (
        not f.coord.migrations
        and f.plane.world_hosts() == ["host-0", "host-1", "host-2"]))
    debt = f.coord.debts["borrow:host-2"]
    assert debt["retired"]
    assert f.coord.verify() == []


def test_full_control_plane_rebuild_mid_loan(tmp_path):
    """The review scenario: the coordinator PROCESS dies mid-loan and
    the new incarnation rebuilds the TrainingPlane too (a fresh plane
    starts expecting EVERY host).  Recovery must exclude the on-loan
    host from the expected membership — otherwise the strict-world
    rendezvous waits forever for a host that is busy serving and the
    survivors never train."""
    f = _Fleet(tmp_path)
    try:
        f.run(8)
        f.spike(60)
        assert f.run(600, until=lambda: f.coord.borrows_total == 1)
        step_before = f.step_n
        # full restart: new plane (fresh expected set) + new coordinator
        f.plane = TrainingPlane(
            f.rdzv, f.hosts, f._ckpt_barrier,
            collector=f.collector, min_nodes=1,
            recorder=f.router.recorder, wall_clock=lambda: f.t)
        assert f.plane.target_world == 3  # the naive fresh state
        f.coord = FleetCoordinator(
            f.plane, f.serving, journal_path=f.journal_path,
            min_train_hosts=2, borrow_stage=1, dwell_seconds=0.3,
            boot_attempts=4, now=f.t)
        # recovery reconciled the fresh plane with the loan
        assert f.plane.target_world == 2
        assert f.plane.expected_hosts() == ["host-0", "host-1"]
        assert f.coord.ledger.owner("host-2") == FleetOwner.SERVING
        # the survivors keep training (the rendezvous is NOT waiting
        # for the serving host)
        f.run(30)
        assert f.plane.world_hosts() == ["host-0", "host-1"]
        assert f.step_n > step_before
        # and the loan still comes home
        for r in f.requests:
            r.cancel()
        assert f.run(900, until=lambda: f.coord.returns_total == 1)
        f.run(10)
        assert f.plane.world_hosts() == ["host-0", "host-1", "host-2"]
        assert f.coord.verify() == []
    finally:
        f.close()


def test_borrow_refused_when_node_unit_misaligned(tmp_path):
    """Slice alignment: with node_unit=2, borrowing ONE host would
    leave a world size the unit-rounded rendezvous can never form —
    the coordinator must refuse rather than wedge the survivors."""
    f = _Fleet(tmp_path, n_hosts=4, min_train_hosts=1)
    try:
        # the deployment's slice unit, preserved by _apply_params
        f.rdzv.update_rdzv_params(
            min_nodes=4, max_nodes=4, waiting_timeout=0.0,
            node_unit=2)
        f.plane._apply_params()
        assert f.plane.node_unit == 2
        f.run(8)
        assert len(f.plane.world_hosts()) == 4
        f.spike(80)
        f.run(200)
        assert f.coord.borrows_total == 0
        assert all(o == FleetOwner.TRAINING
                   for o in f.owners().values())
        assert len(f.plane.world_hosts()) == 4  # never wedged
    finally:
        f.close()


def test_reboot_counts_apart_from_borrows(fleet):
    """A borrowed worker dying on loan and re-booting is a reopened
    debt episode, NOT a second borrow: borrows_total stays 1 and the
    real decision->join handoff latency is not overwritten by the
    cheap respawn."""
    f = fleet
    f.run(8)
    f.spike(60)
    assert f.run(600, until=lambda: f.coord.borrows_total == 1)
    first_handoff = f.coord.last_borrow_handoff_s
    name = next(n for n in f.router.replica_names
                if base_replica_name(n) == "host-2")
    f.router.fail_replica(name)
    with f.sup._lock:
        rec = next(r for r in f.sup.workers.values()
                   if base_replica_name(r.name) == "host-2")
    rec.proc.returncode = 9
    assert f.run(400, until=lambda:
                 f.coord.worker_reboots_total == 1)
    assert f.coord.borrows_total == 1
    assert f.coord.last_borrow_handoff_s == first_handoff
    assert f.coord.metrics()[
        "dlrover_fleet_worker_reboots_total"] == 1.0


def test_recovery_exclude_does_not_restart_healthy_world(tmp_path):
    """Coordinator bounce with a host on loan: recovery re-excludes
    the serving host, whose rank already left the round at the
    original shrink — the healthy survivors' admitted world must NOT
    be invalidated (no spurious training restart per coordinator
    restart)."""
    f = _Fleet(tmp_path)
    try:
        f.run(8)
        f.spike(60)
        assert f.run(600, until=lambda: f.coord.borrows_total == 1)
        f.run(10)
        round_before = f.rdzv.rdzv_round
        world_before = f.plane.world_hosts()
        assert world_before == ["host-0", "host-1"]
        # clean coordinator restart (plane survives, as in-process)
        f.coord = FleetCoordinator(
            f.plane, f.serving, journal_path=f.journal_path,
            min_train_hosts=2, borrow_stage=1, dwell_seconds=0.3,
            boot_attempts=4, now=f.t)
        # the admitted round survived the recovery untouched
        assert f.rdzv.rdzv_round == round_before
        assert f.plane.world_hosts() == world_before
        f.run(10)
        assert f.rdzv.rdzv_round == round_before, \
            "recovery must not force the survivors to re-rendezvous"
    finally:
        f.close()


def test_recovery_prunes_ghost_journal_leases(tmp_path):
    """A journal naming a decommissioned host must not resurrect it:
    the ghost lease is pruned at recovery, so no phantom return can
    inflate the strict-world target into a size that never forms."""
    path = str(tmp_path / "leases.json")
    led = LeaseLedger(journal_path=path)
    epoch = led.bump_epoch()
    for h in ("host-0", "host-1", "host-2"):
        led.acquire(h, FleetOwner.TRAINING, epoch)
    led.transition("host-2", FleetOwner.MIGRATING_OUT, epoch)
    led.transition("host-2", FleetOwner.SERVING, epoch)
    # host-5: a lease from an inventory that no longer exists
    led.acquire("host-5", FleetOwner.SERVING, epoch)
    f = _Fleet(tmp_path, journal=False)
    try:
        f.journal_path = path
        f.coord = FleetCoordinator(
            f.plane, f.serving, journal_path=path,
            min_train_hosts=2, borrow_stage=1, dwell_seconds=0.3,
            boot_attempts=4, now=f.t)
        assert f.coord.ledger.owner("host-5") is None
        assert set(f.coord.ledger.owners()) <= set(f.hosts)
        f.run(20)
        # the world forms at the real inventory; nothing waits on the
        # ghost, and no phantom return ever targets it
        assert f.plane.world_hosts() == \
            ["host-0", "host-1", "host-2"]
        assert "host-5" not in f.coord.migrations
    finally:
        f.close()
