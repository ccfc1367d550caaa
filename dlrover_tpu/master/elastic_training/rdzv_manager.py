"""Master-side elastic rendezvous and network-check managers.

Counterpart of reference
dlrover/python/master/elastic_training/rdzv_manager.py:58-566.

``ElasticTrainingRendezvousManager`` collects joining hosts into a waiting
list and completes a round when (a) every alive host has joined, or (b) the
waiting window expired with >= min_nodes joined, rounded down to a multiple
of ``node_unit`` (on TPU, node_unit = hosts per pod slice: a partial slice
cannot run an SPMD program).

``NetworkCheckRendezvousManager`` pairs hosts into small check groups over
two rounds so a faulty host/slice can be localized by intersecting the
groups that failed (reference: rdzv_manager.py:349-530); stragglers are
flagged by comparing per-node elapsed time to the median (reference:
:550-565). On TPU this check exercises host<->chip liveness and ICI/DCN
collectives between paired hosts.
"""

import math
import time
from abc import ABCMeta, abstractmethod
from threading import Lock
from typing import Dict, List, Optional, Tuple

from dlrover_tpu.common.constants import NetworkFailureReason
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.master.elastic_training.net_topology import (
    NodeTopologyMeta,
    SliceTopologySorter,
)


class RendezvousParameters:
    def __init__(
        self,
        min_nodes: int = 1,
        max_nodes: int = 1,
        waiting_timeout: float = 30.0,
        node_unit: int = 1,
        join_timeout: float = 600.0,
    ):
        self.min_nodes = min_nodes
        self.max_nodes = max_nodes
        self.waiting_timeout = waiting_timeout
        self.node_unit = node_unit
        self.join_timeout = join_timeout


class RendezvousManager(metaclass=ABCMeta):
    def __init__(self):
        self._lock = Lock()
        self._name = ""
        self._waiting_nodes: Dict[int, NodeTopologyMeta] = {}
        self._rdzv_nodes: Dict[int, NodeTopologyMeta] = {}
        self._lastcall_time: float = 0.0
        self._rdzv_params = RendezvousParameters()
        self._rdzv_round = 0
        self._alive_nodes: set = set()
        self._node_rdzv_times: Dict[int, float] = {}
        self._latest_rdzv_nodes: List[int] = []
        self._start_rdzv_ts = 0.0

    @property
    def name(self) -> str:
        return self._name

    @property
    def rdzv_round(self) -> int:
        return self._rdzv_round

    def update_rdzv_params(
        self,
        min_nodes: int,
        max_nodes: int,
        waiting_timeout: float = 30.0,
        node_unit: int = 1,
        join_timeout: float = 600.0,
    ) -> None:
        with self._lock:
            self._rdzv_params = RendezvousParameters(
                min_nodes, max_nodes, waiting_timeout, node_unit, join_timeout
            )

    def get_rdzv_params(self) -> RendezvousParameters:
        """Current parameters (callers adjusting ONE field — e.g. the
        fleet coordinator resizing the world — read the rest from here
        instead of silently resetting node_unit/join_timeout, since
        ``update_rdzv_params`` replaces the whole object)."""
        with self._lock:
            return self._rdzv_params

    def add_alive_node(self, node_rank: int) -> None:
        with self._lock:
            self._alive_nodes.add(node_rank)

    def remove_alive_node(self, node_rank: int) -> None:
        with self._lock:
            self._alive_nodes.discard(node_rank)
            if node_rank in self._waiting_nodes:
                del self._waiting_nodes[node_rank]

    def join_rendezvous(
        self,
        node_id: int,
        node_rank: int,
        local_world_size: int,
        node_ip: str = "",
        slice_id: int = 0,
        node_port: int = 0,
    ) -> int:
        """Add a host to the waiting list; returns the next round id."""
        with self._lock:
            if node_rank in self._rdzv_nodes:
                # A member of the completed round re-joining means its
                # workers restarted: invalidate the round so every member
                # must re-rendezvous (reference: rdzv_manager.py join resets
                # the node dict on every join).  A *new* node joining leaves
                # the current round valid — it waits for the next one.
                self._rdzv_nodes = {}
            if not self._waiting_nodes:
                self._start_rdzv_ts = time.time()
            self._waiting_nodes[node_rank] = NodeTopologyMeta(
                node_id=node_id,
                node_rank=node_rank,
                process_num=local_world_size,
                node_ip=node_ip,
                node_port=node_port,
                slice_id=slice_id,
            )
            self._alive_nodes.add(node_rank)
            self._node_rdzv_times[node_rank] = time.time()
            self._lastcall_time = time.time()
        return self._rdzv_round

    def num_nodes_waiting(self) -> int:
        """Agents poll this to notice membership growth (restart trigger)."""
        with self._lock:
            return len(self._waiting_nodes)

    def current_world_ranks(self) -> List[int]:
        """Node ranks of the ADMITTED world (empty while a round is
        forming) — the fleet coordinator's training-side ground truth."""
        with self._lock:
            return sorted(self._rdzv_nodes)

    def alive_ranks(self) -> List[int]:
        """Ranks the master currently counts as alive (admitted or
        waiting) — what lease reconstruction classifies as
        training-owned after a coordinator crash: an evicted host is
        removed from here BEFORE its serving worker exists, so it can
        never be double-owned."""
        with self._lock:
            return sorted(self._alive_nodes)

    def evict_node(self, node_rank: int) -> None:
        """Deliberately remove one member from the world (fleet
        coordinator shrink): the rank leaves the alive/waiting sets AND
        the completed round is invalidated, so the survivors must
        re-rendezvous into the smaller world — the same round-reset
        contract a member re-join triggers, but initiated by the
        control plane instead of a failure.  Callers shrink
        ``max_nodes`` (update_rdzv_params) in the same breath so the
        new round completes without waiting for the evicted host."""
        with self._lock:
            was_member = node_rank in self._rdzv_nodes
            self._alive_nodes.discard(node_rank)
            self._waiting_nodes.pop(node_rank, None)
            if node_rank in self._latest_rdzv_nodes:
                self._latest_rdzv_nodes.remove(node_rank)
            if was_member:
                # invalidate the round: every survivor re-joins (their
                # collective over the evicted host's chips is dead
                # anyway — this makes the restart deliberate, not a
                # timeout discovery).  Evicting a rank that is NOT a
                # member (recovery re-excluding a host already on
                # loan) must not restart a healthy world.
                self._rdzv_nodes = {}
        if was_member:
            logger.info(
                "Rendezvous %s: node %s evicted by the fleet "
                "coordinator; survivors will re-rendezvous",
                self._name, node_rank)

    def _check_rdzv_completed(self) -> bool:
        """Caller holds the lock.

        Completion rules (ordered):
        1. full world joined -> immediately;
        2. every *previously admitted, still-alive* member has (re)joined
           and min_nodes is met -> immediately (fast recovery after the
           master removed a dead node; a lone late joiner does NOT
           qualify — it must wait for the members' round invalidation,
           otherwise two staggered nodes complete two divergent
           singleton worlds);
        3. otherwise, the last-call window: min_nodes joined and no new
           joiner for waiting_timeout.
        """
        waiting = set(self._waiting_nodes)
        params = self._rdzv_params
        if not waiting:
            return False
        if len(waiting) >= params.max_nodes:
            return True
        known = set(self._latest_rdzv_nodes) & self._alive_nodes
        if known and known <= waiting and len(waiting) >= params.min_nodes:
            return True
        since_lastcall = time.time() - self._lastcall_time
        return (
            len(waiting) >= params.min_nodes
            and since_lastcall >= params.waiting_timeout
        )

    def _complete_rdzv(self) -> bool:
        """Caller holds the lock: admit a node_unit-rounded set of nodes.
        Returns False (and leaves state untouched) if rounding admits 0.

        When nodes carry distinct ``slice_id``s the unit applies PER
        SLICE: only complete slices (>= unit members) are admitted —
        losing one member of a slice drops that whole slice from the
        world (its ICI domain is broken; a partial slice cannot train),
        while other slices train on (reference rdzv_manager.py:291-343
        node-loss-at-scale semantics + net_topology slice grouping).
        """
        params = self._rdzv_params
        unit = max(params.node_unit, 1)
        slice_ids = {m.slice_id for m in self._waiting_nodes.values()}
        if unit > 1 and len(slice_ids) > 1:
            by_slice: Dict[int, list] = {}
            for r in sorted(self._waiting_nodes.keys()):
                m = self._waiting_nodes[r]
                by_slice.setdefault(m.slice_id, []).append(r)
            ranks = []
            for sid in sorted(by_slice):
                members = by_slice[sid]
                take = (len(members) // unit) * unit
                if take and len(ranks) + take <= params.max_nodes:
                    ranks.extend(members[:take])
            # the slice-filtered set must still honor the job's
            # min_nodes contract (the raw waiting count satisfied the
            # completion rules, but broken slices don't count)
            if not ranks or len(ranks) < params.min_nodes:
                return False
        else:
            admitted_num = (len(self._waiting_nodes) // unit) * unit
            admitted_num = min(admitted_num, params.max_nodes)
            if admitted_num == 0:
                return False
            ranks = sorted(self._waiting_nodes.keys())[:admitted_num]
        nodes = {r: self._waiting_nodes[r] for r in ranks}
        sorter = SliceTopologySorter()
        self._rdzv_nodes = sorter.sort(nodes)
        self._latest_rdzv_nodes = list(self._rdzv_nodes.keys())
        for r in ranks:
            del self._waiting_nodes[r]
        self._rdzv_round += 1
        elapsed = time.time() - self._start_rdzv_ts
        logger.info(
            "Rendezvous %s round %s completed with %s nodes in %.1fs: %s",
            self._name, self._rdzv_round, len(self._rdzv_nodes),
            elapsed, list(self._rdzv_nodes.keys()),
        )
        return True

    @abstractmethod
    def get_comm_world(
        self, node_rank: int
    ) -> Tuple[int, int, Dict[int, NodeTopologyMeta]]:
        """Return (round, group, {rank: meta}) or an empty world if not
        yet complete."""

    def joined(self, node_rank: int) -> bool:
        with self._lock:
            return (
                node_rank in self._waiting_nodes
                or node_rank in self._rdzv_nodes
            )


class ElasticTrainingRendezvousManager(RendezvousManager):
    """(reference: rdzv_manager.py:291-343)."""

    def __init__(self):
        super().__init__()
        self._name = "elastic-training"

    def num_nodes_waiting(self) -> int:
        """Only report waiting nodes that could actually enlarge the world
        — otherwise agents restart in a loop for a node that can never be
        admitted (node_unit rounding or max_nodes cap)."""
        with self._lock:
            params = self._rdzv_params
            unit = max(params.node_unit, 1)
            slice_ids = {m.slice_id for m in self._waiting_nodes.values()}
            if unit > 1 and len(slice_ids) > 1:
                # slice-aware: only members of COMPLETE waiting slices
                # can ever be admitted — a broken slice's orphan must
                # not keep healthy agents in a restart loop while it
                # waits (possibly forever) for a replacement host
                by_slice: Dict[int, int] = {}
                for m in self._waiting_nodes.values():
                    by_slice[m.slice_id] = by_slice.get(m.slice_id, 0) + 1
                waiting = sum(
                    (count // unit) * unit for count in by_slice.values()
                )
            else:
                waiting = len(self._waiting_nodes)
            if waiting < unit and self._rdzv_nodes:
                return 0
            cur = len(self._rdzv_nodes)
            potential = min(((cur + waiting) // unit) * unit, params.max_nodes)
            if cur and potential <= cur:
                return 0
            return waiting

    def get_comm_world(
        self, node_rank: int
    ) -> Tuple[int, int, Dict[int, NodeTopologyMeta]]:
        with self._lock:
            if self._waiting_nodes and self._check_rdzv_completed():
                self._complete_rdzv()
            if node_rank in self._rdzv_nodes:
                return self._rdzv_round, 0, dict(self._rdzv_nodes)
            return self._rdzv_round, 0, {}


class NetworkCheckRendezvousManager(RendezvousManager):
    """(reference: rdzv_manager.py:349-565)."""

    GROUP_SIZE = 2

    def __init__(self):
        super().__init__()
        self._name = "network-check"
        self._node_status: Dict[int, bool] = {}
        self._node_times: Dict[int, float] = {}
        self._check_round = 2
        self._node_groups: List[List[int]] = []
        self._fault_nodes: set = set()
        self._straggler_nodes: set = set()
        self._reported_nodes: set = set()
        self._round_idx = 0

    def get_comm_world(
        self, node_rank: int
    ) -> Tuple[int, int, Dict[int, NodeTopologyMeta]]:
        with self._lock:
            if self._waiting_nodes and self._check_rdzv_completed():
                if self._complete_rdzv():
                    self._build_node_groups()
            for group_idx, group in enumerate(self._node_groups):
                if node_rank in group:
                    world = {
                        r: self._rdzv_nodes[r]
                        for r in group
                        if r in self._rdzv_nodes
                    }
                    return self._rdzv_round, group_idx, world
            return self._rdzv_round, 0, {}

    def _build_node_groups(self) -> None:
        """Pair nodes; in round 1 pair sequentially, in round 2 re-pair so
        that a node that failed twice is definitively faulty (reference:
        rdzv_manager.py:430-505)."""
        ranks = list(self._rdzv_nodes.keys())
        self._reported_nodes = set()
        self._round_idx += 1
        groups: List[List[int]] = []
        if self._round_idx % 2 == 1 or not self._fault_nodes:
            # Sequential pairing.
            for i in range(0, len(ranks), self.GROUP_SIZE):
                groups.append(ranks[i : i + self.GROUP_SIZE])
        else:
            # Re-pair each previously-abnormal node with a known-good peer.
            normal = [r for r in ranks if r not in self._fault_nodes]
            abnormal = [r for r in ranks if r in self._fault_nodes]
            used_normal = list(normal)
            groups = []
            rest = []
            for bad in abnormal:
                if used_normal:
                    groups.append([bad, used_normal.pop(0)])
                else:
                    rest.append(bad)
            for i in range(0, len(used_normal), self.GROUP_SIZE):
                groups.append(used_normal[i : i + self.GROUP_SIZE])
            if rest:
                groups.append(rest)
        # Merge a trailing singleton into the previous group.
        if len(groups) > 1 and len(groups[-1]) == 1:
            groups[-2].extend(groups.pop())
        self._node_groups = groups
        logger.info("Network-check groups: %s", groups)

    def report_network_check_result(
        self, node_rank: int, normal: bool, elapsed_time: float
    ) -> None:
        with self._lock:
            self._reported_nodes.add(node_rank)
            self._node_status[node_rank] = normal
            self._node_times[node_rank] = elapsed_time
            if not normal:
                self._fault_nodes.add(node_rank)
            else:
                self._fault_nodes.discard(node_rank)

    def check_fault_node(self) -> Tuple[List[int], str]:
        """(reference: rdzv_manager.py:507-548)."""
        with self._lock:
            if not self._rdzv_nodes:
                return [], NetworkFailureReason.NO_INIT
            all_reported = self._reported_nodes >= set(
                self._rdzv_nodes.keys()
            )
            if not all_reported:
                return [], NetworkFailureReason.WAITING_NODE
            faults = sorted(self._fault_nodes)
            if faults:
                return faults, NetworkFailureReason.NODE_FAILURE
            return [], ""

    def check_straggler(self) -> Tuple[List[int], str]:
        """Median rule (reference: rdzv_manager.py:550-565)."""
        with self._lock:
            times = [
                t for r, t in self._node_times.items()
                if r in self._rdzv_nodes
            ]
            if len(times) < 2:
                return [], ""
            sorted_times = sorted(times)
            n = len(sorted_times)
            median = (
                sorted_times[n // 2]
                if n % 2
                else 0.5 * (sorted_times[n // 2 - 1] + sorted_times[n // 2])
            )
            stragglers = [
                r
                for r, t in self._node_times.items()
                if r in self._rdzv_nodes and median > 0 and t > 2 * median
            ]
            self._straggler_nodes = set(stragglers)
            return sorted(stragglers), ""

    def network_check_success(self) -> Tuple[bool, str]:
        faults, reason = self.check_fault_node()
        if reason == NetworkFailureReason.WAITING_NODE:
            return False, reason
        return len(faults) == 0, reason
