"""Cluster-manager schema: nodes, targets, chains, routing info, lease.

Re-expresses src/fbs/mgmtd (RoutingInfo.h:11-41, MgmtdTypes.h,
MgmtdLeaseInfo.h:9-22): versioned routing snapshots of nodes + chain tables +
chains + targets, public/local target states from docs/design_notes.md
"Failure detection", and the primary-election lease record.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional


class NodeType(enum.IntEnum):
    MGMTD = 1
    META = 2
    STORAGE = 3
    CLIENT = 4
    FUSE = 5


class NodeStatus(enum.IntEnum):
    HEARTBEAT_CONNECTING = 0
    HEARTBEAT_CONNECTED = 1      # ref MgmtdTypes.h:30-36
    HEARTBEAT_FAILED = 2
    DISABLED = 3


class PublicTargetState(enum.IntEnum):
    """Read/write admission per design_notes table:
    serving R+W, syncing W-only, waiting/lastsrv/offline none."""

    SERVING = 1
    SYNCING = 2
    WAITING = 3
    LASTSRV = 4
    OFFLINE = 5

    @property
    def can_read(self) -> bool:
        return self == PublicTargetState.SERVING

    @property
    def can_write(self) -> bool:
        return self in (PublicTargetState.SERVING, PublicTargetState.SYNCING)


class LocalTargetState(enum.IntEnum):
    UPTODATE = 1
    ONLINE = 2
    OFFLINE = 3


@dataclass
class ChainTarget:
    """A target's position in a chain, with both state views."""

    target_id: int
    public_state: PublicTargetState = PublicTargetState.SERVING
    local_state: LocalTargetState = LocalTargetState.UPTODATE


@dataclass
class TargetInfo:
    target_id: int
    node_id: int = 0
    disk_index: int = 0
    chain_id: int = 0
    public_state: PublicTargetState = PublicTargetState.OFFLINE
    local_state: LocalTargetState = LocalTargetState.OFFLINE
    used_size: int = 0


@dataclass
class ChainInfo:
    chain_id: int
    chain_version: int = 1
    targets: List[ChainTarget] = field(default_factory=list)
    preferred_order: List[int] = field(default_factory=list)
    # EC chain-table type (ref deploy/data_placement data_placement.py:30
    # chain_table_type Literal["EC","CR"]): ec_k/ec_m nonzero makes this an
    # erasure-coded group — target at preferred_order position i holds shard
    # i of every stripe (i < ec_k data, else parity); (0, 0) = CRAQ chain
    ec_k: int = 0
    ec_m: int = 0

    @property
    def is_ec(self) -> bool:
        return self.ec_k > 0

    def shard_index(self, target_id: int) -> int:
        """Stable shard position of a target (chain_sm may reorder
        `targets`; `preferred_order` preserves the layout positions)."""
        return self.preferred_order.index(target_id)

    def target_of_shard(self, shard: int) -> Optional[ChainTarget]:
        if shard >= len(self.preferred_order):
            return None
        tid = self.preferred_order[shard]
        return next((t for t in self.targets if t.target_id == tid), None)

    def serving_targets(self) -> List[ChainTarget]:
        return [t for t in self.targets if t.public_state == PublicTargetState.SERVING]

    def head(self) -> Optional[ChainTarget]:
        serving = self.serving_targets()
        return serving[0] if serving else None

    def tail(self) -> Optional[ChainTarget]:
        serving = self.serving_targets()
        return serving[-1] if serving else None

    def writer_chain(self) -> List[ChainTarget]:
        """Targets that receive writes, in propagation order (serving+syncing)."""
        return [t for t in self.targets if t.public_state.can_write]


@dataclass
class ChainTable:
    table_id: int
    version: int = 1
    chain_ids: List[int] = field(default_factory=list)


@dataclass
class NodeInfo:
    node_id: int
    type: NodeType
    status: NodeStatus = NodeStatus.HEARTBEAT_CONNECTING
    host: str = ""
    port: int = 0
    last_heartbeat: float = 0.0
    heartbeat_version: int = 0
    config_version: int = 0
    tags: Dict[str, str] = field(default_factory=dict)


@dataclass
class ServingEndpoint:
    """One process's KVCache serving endpoint (tpu3fs/serving): where
    peers reach its peerRead service, published through RoutingInfo like
    chain tables so discovery is gossip-light — every routing refresh IS
    the peer directory. TTL-leased: an endpoint that stops re-registering
    is pruned by the mgmtd tick (a crashed serving process must fall out
    of peer selection even before breakers open)."""

    node_id: int
    host: str = ""
    port: int = 0
    registered_at: float = 0.0
    ttl_s: float = 30.0


@dataclass
class MetaPartition:
    """One metadata partition's assignment row (tpu3fs/metashard): the
    namespace is split into a FIXED number of partitions (directory-hash
    over the parent path for by-path ops; the partition id baked into the
    high bits of every inode id for by-inode ops) and mgmtd assigns each
    partition to exactly one live META node, publishing the table through
    RoutingInfo like chain tables. ``epoch`` bumps on every ownership
    change — a meta server fences ops against the epoch it loaded, so a
    reassigned partition's old owner answers META_WRONG_PARTITION instead
    of racing the new owner."""

    partition_id: int
    node_id: int = 0          # 0 = unassigned (no live meta node)
    epoch: int = 0
    # ops/s the owner reported for this partition on its last heartbeat
    # (admin_cli meta-partitions' load column; informational only)
    load: float = 0.0


@dataclass
class LeaseInfo:
    """Primary election record (ref MgmtdLeaseInfo.h:9-22); mutated only via
    KV compare-and-set inside a transaction (MgmtdStore::extendLease)."""

    primary_node_id: int = 0
    lease_start: float = 0.0
    lease_end: float = 0.0
    release_version: int = 0


@dataclass
class RoutingInfo:
    """Versioned cluster snapshot served to all services and clients
    (ref src/fbs/mgmtd/RoutingInfo.h:11-41)."""

    version: int = 0
    nodes: Dict[int, NodeInfo] = field(default_factory=dict)
    chain_tables: Dict[int, ChainTable] = field(default_factory=dict)
    chains: Dict[int, ChainInfo] = field(default_factory=dict)
    targets: Dict[int, TargetInfo] = field(default_factory=dict)
    # KVCache serving endpoints (tpu3fs/serving peer directory) — trailing
    # field on purpose: serde decoders default missing trailing fields, so
    # pre-serving peers interop (rpc/serde.py evolution rule)
    serving: Dict[int, ServingEndpoint] = field(default_factory=dict)
    # metadata partition table (tpu3fs/metashard) — also trailing: decoders
    # predating the metashard plane read an empty table and keep treating
    # the meta plane as a single unpartitioned process
    meta_partitions: Dict[int, MetaPartition] = field(default_factory=dict)

    def meta_owner(self, partition_id: int) -> Optional[NodeInfo]:
        """The NodeInfo currently owning one meta partition (None when
        the table is empty or the partition is unassigned)."""
        row = self.meta_partitions.get(partition_id)
        if row is None or not row.node_id:
            return None
        return self.nodes.get(row.node_id)

    def chain_of_target(self, target_id: int) -> Optional[ChainInfo]:
        info = self.targets.get(target_id)
        return self.chains.get(info.chain_id) if info else None

    def node_of_target(self, target_id: int) -> Optional[NodeInfo]:
        info = self.targets.get(target_id)
        return self.nodes.get(info.node_id) if info else None


def routing_invalidator(provider) -> Callable[[], None]:
    """The hook that expires the snapshot a routing provider holds, so
    that its next call polls mgmtd: ``provider.invalidate`` on a callable
    object, ``invalidate_routing`` on the owner of a bound method
    (``MgmtdRpcClient.cached_routing``), a no-op for providers that hold
    nothing (the fabric's). Retry ladders and resolves that missed call
    it before they resolve again (docs/robustness.md)."""
    owner = getattr(provider, "__self__", None)
    return (getattr(provider, "invalidate", None)
            or getattr(owner, "invalidate_routing", None)
            or (lambda: None))
