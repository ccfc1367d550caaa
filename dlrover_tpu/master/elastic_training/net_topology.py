"""Topology-aware ordering of nodes in a comm world.

Counterpart of reference
dlrover/python/master/elastic_training/net_topology.py:21-89. On TPU the
locality domain is the pod slice (ICI) rather than the access switch:
hosts of the same slice are placed at adjacent ranks so that data-parallel
collectives ride ICI and only cross-slice traffic uses DCN.
"""

from dataclasses import dataclass, field
from typing import Dict


@dataclass
class NodeTopologyMeta:
    node_id: int = 0
    node_rank: int = 0
    process_num: int = 1  # local world size (TPU chips driven by this host)
    slice_id: int = 0
    node_ip: str = ""
    node_port: int = 0  # offered with the join, fresh every round
    asw: str = ""  # access switch, used for DCN locality between slices


class DefaultTopologySorter:
    def sort(
        self, nodes: Dict[int, NodeTopologyMeta]
    ) -> Dict[int, NodeTopologyMeta]:
        return dict(sorted(nodes.items(), key=lambda kv: kv[0]))


class SliceTopologySorter:
    """Group hosts by (slice_id, asw), contiguous per group — the TPU
    analog of ``DpTopologySorter`` (reference: net_topology.py:62).

    Like the reference, the group containing the ORIGINAL rank 0 comes
    first: rank 0 hosts the rendezvous coordinator and often rank-0-only
    services, so re-sorting must not displace it from position 0.
    Within and across the remaining groups, order is deterministic
    (slice, asw, rank) so every master replica computes the same world.
    """

    def sort(
        self, nodes: Dict[int, NodeTopologyMeta]
    ) -> Dict[int, NodeTopologyMeta]:
        if not nodes:
            return {}
        rank0 = min(nodes.values(), key=lambda n: n.node_rank)
        head_key = (rank0.slice_id, rank0.asw)

        def key(n: NodeTopologyMeta):
            group = (n.slice_id, n.asw)
            return (group != head_key, n.slice_id, n.asw, n.node_rank)

        ordered = sorted(nodes.values(), key=key)
        return {n.node_rank: n for n in ordered}
