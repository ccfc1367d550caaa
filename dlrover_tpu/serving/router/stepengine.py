"""The sharded router front: N independent step loops behind one door.

The second candidate behind the step-engine seam (the first is the
consolidated single-threaded event loop, ``ServingRouter(
step_engine="event")`` — see router.py).  The question the ROADMAP
poses — "single-threaded event loop or sharded routers behind a
consistent front — pick per measurement, not per taste" — was answered
by running BOTH on the full-pipeline open-loop rig
(``loadgen.run_router_rig``) over ``FakeEngine`` replicas on a CPU:
CHANGES.md PR 15 records the A/B (a tie at rate, the event loop ahead
on a deep blocked queue), and the event loop is the shipped default.
No cell of ``BENCHMARK.json`` runs the sharded front (ROADMAP D5).

Design:

- **requests partition by hashed admission counter**: the front hashes
  a monotonically increasing admission ordinal to pick the shard (the
  "rid hash" discipline — stateless, uniform, no routing table); the
  request's ACTUAL rid is then minted by that shard's gateway, in a
  per-shard disjoint space so fleet-level views never see two shards
  hand out the same rid.  Each admission lands on exactly one shard's
  gateway, so no request is ever visible to two step loops and the
  zero-lost/books discipline holds per shard and therefore globally;
- **replicas partition at join** (least-loaded shard): one replica
  belongs to one shard — two step loops must never race placements
  into one engine's capacity ledger;
- **shared brown-out view**: one :class:`BrownoutPolicy` object serves
  every shard's gateway for admission shedding, but its watermark is
  updated ONLY by the front with fleet-global queued demand and
  capacity (each shard runs ``brownout_external=True``), so the ladder
  cannot flap per-shard on a lopsided queue;
- **two drive modes**: deterministic (``threaded=False``; ``step()``
  steps every shard in order on the caller's thread — what the
  equivalence tests replay seeded workloads through) and threaded
  (``threaded=True``; ``start()`` spawns one loop thread per shard —
  the "N independent step loops" the A/B measures, honestly including
  whatever the GIL takes back on this host).

Cross-shard placement (work stealing from a busy shard's queue onto an
idle shard's replicas) is deliberately absent: it would re-introduce
exactly the shared-ledger locking this front exists to remove.  The
cost is fleet utilization on skewed partitions — rid-hash admission
keeps the skew statistical, and the rig measures the result.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, Optional

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.serving.router.gateway import (
    PRIORITY_NORMAL,
    ServingRequest,
)
from dlrover_tpu.serving.router.router import ServingRouter


def shard_of(rid: int, num_shards: int) -> int:
    """The rid-hash partition (Knuth multiplicative hash so adjacent
    rids spread instead of striping with any stride a caller batches
    in)."""
    return ((rid * 2654435761) >> 16) % num_shards


class ShardedRouterFront:
    """N independent :class:`ServingRouter` step loops behind one
    submit/step/has_work door (duck-compatible with the slice of the
    router surface the rig and the drive helpers use)."""

    def __init__(
        self,
        num_shards: int = 2,
        router_factory=None,
        brownout=None,
        threaded: bool = False,
        step_engine: str = "event",
        tenants=None,
    ):
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1: {num_shards}")
        self.num_shards = int(num_shards)
        self.threaded = bool(threaded)
        self.brownout = brownout
        self.tenants = tenants
        factory = router_factory or (
            lambda shard: ServingRouter(step_engine=step_engine))
        self.shards: List[ServingRouter] = [
            factory(i) for i in range(self.num_shards)]
        for i, shard in enumerate(self.shards):
            # disjoint rid spaces: each shard's gateway mints its own
            # request ids, and a front-level results()/books view must
            # never see two shards hand out the same rid
            shard.gateway._next_rid = i * (10 ** 12)
        for shard in self.shards:
            if brownout is not None:
                # ONE policy object: admission shedding on every
                # shard's gateway consults the same (front-updated)
                # stage; the shard applies but never updates it
                shard.brownout = brownout
                shard.gateway.brownout = brownout
                shard.brownout_external = True
            if tenants is not None:
                # ONE registry object shared by every shard's gateway:
                # quotas meter FLEET traffic (a per-shard registry
                # would multiply every quota_qps by num_shards); the
                # registry's own lock makes bucket consumption safe
                # across shard threads
                shard.gateway.tenants = tenants
        # admission ordinal for the shard hash (itertools.count.next
        # is GIL-atomic, so concurrent client submits draw distinct
        # ordinals without a lock)
        self._arrivals = itertools.count()
        self._join_rr = 0
        self._threads: List[threading.Thread] = []
        self._stop = threading.Event()

    # ------------------------------------------------------ membership
    def join_replica(self, name: str, engine, node=None,
                     now: Optional[float] = None):
        """Join onto the least-populated shard (ties: round-robin) —
        one replica belongs to exactly one step loop."""
        sizes = [len(s.manager.replicas) for s in self.shards]
        idx = min(range(self.num_shards),
                  key=lambda i: (sizes[i], (i - self._join_rr)
                                 % self.num_shards))
        self._join_rr = (idx + 1) % self.num_shards
        return self.shards[idx].join_replica(
            name, engine, node=node, now=now)

    @property
    def replica_names(self) -> List[str]:
        return [n for s in self.shards for n in s.replica_names]

    def shard_of_replica(self, name: str) -> Optional[ServingRouter]:
        for s in self.shards:
            if name in s.manager.replicas:
                return s
        return None

    # --------------------------------------------------------- client
    def submit(self, prompt_ids, max_new_tokens: int,
               priority: int = PRIORITY_NORMAL,
               timeout: Optional[float] = None,
               now: Optional[float] = None,
               tenant: Optional[str] = None) -> ServingRequest:
        shard = self.shards[
            shard_of(next(self._arrivals), self.num_shards)]
        return shard.submit(prompt_ids, max_new_tokens,
                            priority=priority, timeout=timeout,
                            now=now, tenant=tenant)

    # ----------------------------------------------------------- pump
    def _update_shared_brownout(self, now: float) -> None:
        if self.brownout is None:
            return
        depth = 0
        capacity = 0.0
        for s in self.shards:
            depth += s.gateway.depth()
            # under the shard's step lock: in threaded mode the
            # watermark thread races the shard loop's reap/retire
            # mutations of manager.replicas, and an unguarded
            # iteration would die with "dict changed size" — killing
            # the daemon thread and freezing the fleet's brown-out
            # stage forever.  One shard lock at a time (never nested),
            # so no ordering cycle (DL008).
            with s._lock:
                handles = s.manager.schedulable(now)
            for handle in handles:
                try:
                    capacity += (handle.slots_free()
                                 + len(handle.inflight))
                except Exception:
                    continue  # a dying replica's ledger is not capacity
        prev = self.brownout.stage
        stage = self.brownout.update(now, depth, capacity)
        if stage != prev:
            for s in self.shards:
                s.recorder.record(
                    "brownout_stage", stage=stage, prev=prev,
                    name=self.brownout.stage_name, fleet_global=True,
                    now=now)
            log = logger.warning if stage > prev else logger.info
            log("sharded front brown-out stage %d -> %d (%s): "
                "fleet depth %d, capacity %.0f slots",
                prev, stage, self.brownout.stage_name, depth, capacity)

    def step(self, now: Optional[float] = None) -> List[ServingRequest]:
        """Deterministic drive: one round of every shard, in shard
        order, on the caller's thread.  In threaded mode the loops
        drive themselves and this briefly yields instead (so drive
        loops written against the router surface stay correct)."""
        if self.threaded and self._threads:
            time.sleep(0.0005)
            return []
        now = time.monotonic() if now is None else now
        self._update_shared_brownout(now)
        completed: List[ServingRequest] = []
        for shard in self.shards:
            completed.extend(shard.step(now))
        return completed

    # ------------------------------------------------- threaded drive
    def start(self, poll_seconds: float = 0.0005) -> None:
        """Threaded mode: one independent step loop per shard plus the
        front's brown-out watermark tick.  Each loop owns its shard
        exclusively — the only shared object is the brown-out policy,
        which the shards read and only the front writes."""
        if not self.threaded:
            raise RuntimeError("start() requires threaded=True")
        if self._threads:
            return
        self._stop.clear()

        def _loop(shard: ServingRouter) -> None:
            while not self._stop.is_set():
                shard.step()
                if not shard.has_work:
                    self._stop.wait(poll_seconds)

        def _watermark() -> None:
            while not self._stop.wait(0.005):
                self._update_shared_brownout(time.monotonic())

        for i, shard in enumerate(self.shards):
            t = threading.Thread(
                target=_loop, args=(shard,), daemon=True,
                name=f"router-shard-{i}")
            t.start()
            self._threads.append(t)
        if self.brownout is not None:
            t = threading.Thread(
                target=_watermark, daemon=True,
                name="router-front-watermark")
            t.start()
            self._threads.append(t)

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=5.0)
        self._threads = []

    # ------------------------------------------------------ aggregates
    @property
    def has_work(self) -> bool:
        return any(s.has_work for s in self.shards)

    def run_until_idle(self, max_steps: int = 100000,
                       now_fn=None) -> int:
        now_fn = now_fn or time.monotonic
        steps = 0
        while self.has_work:
            if steps >= max_steps:
                depths = [s.gateway.depth() for s in self.shards]
                raise RuntimeError(
                    f"sharded front still busy after {max_steps} "
                    f"steps (depths={depths})")
            self.step(now_fn())
            steps += 1
            if self.threaded and self._threads:
                time.sleep(0.001)
        return steps

    def counters(self) -> Dict[str, float]:
        """Fleet-global lifecycle counters summed across shards — the
        books-balance surface (submitted == completed + timed_out +
        cancelled + poisoned + engine-rejected; shed admissions never
        entered)."""
        keys = (
            "serving_requests_submitted_total",
            "serving_requests_completed_total",
            "serving_requests_rejected_total",
            "serving_requests_timed_out_total",
            "serving_requests_requeued_total",
            "serving_requests_poisoned_total",
            "serving_requests_cancelled_total",
            "serving_cancel_send_failures_total",
            "serving_generated_tokens_total",
            "serving_queue_depth",
            "serving_inflight",
        )
        out: Dict[str, float] = {k: 0.0 for k in keys}
        for s in self.shards:
            m = s.metrics.metrics()
            for k in keys:
                out[k] += float(m.get(k, 0.0))
        return out

    def results(self, requests: List[ServingRequest],
                timeout: Optional[float] = None):
        return {r.rid: r.result(timeout) for r in requests}
