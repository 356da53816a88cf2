"""Incremental rebalance planner: MINIMAL chain diffs for topology deltas.

The full solver (placement/solver.py) lays a balanced table from scratch;
re-running it after a topology change would reshuffle everything — O(all
data) movement for an O(1/N) capacity change. This planner instead takes
the LIVE chain table plus a delta (nodes joined / draining / dead) and
emits the smallest ordered set of per-chain membership replacements that

- empties every draining/dead node (each affected chain gets ONE
  replacement per plan — re-plan after a wave for pathological multi-
  failure chains),
- fills every joined node to its fair share, floor(total/(N+joined)),
  so joining 1 node to an N-node balanced table moves
  ≤ ceil(total/(N+1)) chains (the minimality acceptance bound),
- keeps the pairwise co-occurrence λ (the quantity whose balance bounds
  any one peer's recovery traffic — solver docstring, ref
  deploy/data_placement) within tolerance: destinations are chosen
  greedily to minimize (λ spike with the chain's remaining members,
  resulting node load),
- never plans a move that would drop a chain below its write-quorum
  mid-execution (``check_plan``): CR needs a surviving serving source;
  EC needs every other member SERVING because the swap itself spends the
  chain's one spare redundancy unit.

A NO-OP delta produces an EMPTY plan — the planner never "improves" a
table nobody asked it to touch (operators re-layout with the solver).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from tpu3fs.mgmtd.types import (
    NodeStatus,
    NodeType,
    PublicTargetState,
    RoutingInfo,
)
from tpu3fs.migration.types import MoveSpec
from tpu3fs.monitor.recorder import ValueRecorder

_rec_plan_moves = ValueRecorder("placement.plan_moves")
_rec_lambda = ValueRecorder("placement.lambda_max")

DRAINING_TAG = "draining"


@dataclass
class TopologyDelta:
    joined: List[int] = field(default_factory=list)
    draining: List[int] = field(default_factory=list)
    dead: List[int] = field(default_factory=list)

    @property
    def empty(self) -> bool:
        return not (self.joined or self.draining or self.dead)

    @classmethod
    def from_routing(cls, routing: RoutingInfo) -> "TopologyDelta":
        """Derive the delta an operator usually means: storage nodes that
        are connected but own no chain membership JOINED; nodes tagged
        ``draining=1`` DRAINING; heartbeat-failed nodes still owning
        memberships DEAD."""
        hosting: Dict[int, int] = {}
        for info in routing.targets.values():
            if info.chain_id:
                hosting[info.node_id] = hosting.get(info.node_id, 0) + 1
        joined, draining, dead = [], [], []
        for node in routing.nodes.values():
            if node.type != NodeType.STORAGE:
                continue
            if node.tags.get(DRAINING_TAG):
                if hosting.get(node.node_id):
                    draining.append(node.node_id)
                continue
            if node.status == NodeStatus.HEARTBEAT_FAILED:
                if hosting.get(node.node_id):
                    dead.append(node.node_id)
                continue
            if node.status == NodeStatus.HEARTBEAT_CONNECTED \
                    and not hosting.get(node.node_id):
                joined.append(node.node_id)
        return cls(sorted(joined), sorted(draining), sorted(dead))


@dataclass
class PlannedMove:
    chain_id: int
    out_target: int
    src_node: int
    dst_node: int
    is_ec: bool = False

    def spec(self) -> MoveSpec:
        return MoveSpec(chain_id=self.chain_id, out_target=self.out_target,
                        dst_node=self.dst_node)


@dataclass
class PlanStats:
    lambda_max: int = 0
    lambda_lower_bound: int = 0
    recovery_traffic_factor: int = 1
    per_node: Dict[int, int] = field(default_factory=dict)


@dataclass
class RebalancePlan:
    moves: List[PlannedMove] = field(default_factory=list)
    before: PlanStats = field(default_factory=PlanStats)
    after: PlanStats = field(default_factory=PlanStats)
    #: chains that need ANOTHER wave after this plan lands (several
    #: members on leaving nodes at once): re-plan when this wave is done
    deferred_chains: List[int] = field(default_factory=list)

    @property
    def empty(self) -> bool:
        return not self.moves


def _chain_members(routing: RoutingInfo, chain) -> List[Tuple[int, int]]:
    """[(target_id, node_id)] for a chain, routing-resolved."""
    out = []
    for t in chain.targets:
        info = routing.targets.get(t.target_id)
        out.append((t.target_id, info.node_id if info else 0))
    return out


def incidence_of_routing(
    routing: RoutingInfo, node_ids: List[int],
    chain_ids: Optional[List[int]] = None,
) -> np.ndarray:
    """(chains × nodes) 0/1 incidence of the LIVE table over ``node_ids``
    — the solver's matrix shape, derived from routing instead of laid
    fresh, so solver-side validators (``check_solution`` properties,
    ``recovery_traffic_factor``) apply to the running cluster."""
    chain_ids = chain_ids or sorted(routing.chains)
    idx = {n: i for i, n in enumerate(node_ids)}
    M = np.zeros((len(chain_ids), len(node_ids)), dtype=np.int8)
    for g, cid in enumerate(chain_ids):
        chain = routing.chains[cid]
        for _tid, node in _chain_members(routing, chain):
            if node in idx:
                M[g, idx[node]] = 1
    return M


def _stats(M: np.ndarray, node_ids: List[int], factor: int) -> PlanStats:
    # float64 BLAS then round: integer matmul has no BLAS path in numpy
    # and runs ~100x slower at 10k-chain tables;
    # co-occurrence counts are << 2^53 so the float trip is exact
    Mf = M.astype(np.float64)
    C = (Mf.T @ Mf).astype(np.int64)
    off = C - np.diag(np.diag(C))
    width = int(M.sum(axis=1).max()) if len(M) else 0
    b = len(M)
    v = max(len(node_ids), 1)
    lb = 0
    if v > 1 and b:
        num = b * width * (width - 1)
        lb = -(-num // (v * (v - 1)))
    return PlanStats(
        lambda_max=int(off.max()) if off.size else 0,
        lambda_lower_bound=lb,
        recovery_traffic_factor=factor,
        per_node={n: int(M[:, i].sum()) for i, n in enumerate(node_ids)},
    )


def plan_rebalance(
    routing: RoutingInfo,
    delta: Optional[TopologyDelta] = None,
    *,
    chain_ids: Optional[List[int]] = None,
    fill_joined: bool = True,
) -> RebalancePlan:
    """-> minimal ordered move list for ``delta`` (derived from routing
    tags/heartbeats when not given). Pure function of its inputs — safe
    to call for preview (admin_cli placement-plan) and again for apply.

    ``fill_joined=False`` skips the fair-share FILL phase: joined nodes
    still count as eligible EVACUATION destinations (an empty restarted
    node is often the only place a leaving member can go), but no moves
    are planned purely to give them load — the migration worker's auto
    re-plan uses this so capacity rebalancing stays an operator
    decision."""
    delta = delta or TopologyDelta.from_routing(routing)
    chain_ids = chain_ids or sorted(routing.chains)
    chains = {cid: routing.chains[cid] for cid in chain_ids
              if cid in routing.chains}
    factor = 1
    for c in chains.values():
        if c.is_ec:
            factor = max(factor, c.ec_k + c.ec_m - 1)

    leaving = set(delta.draining) | set(delta.dead)
    hosting = set()
    for cid, chain in chains.items():
        for _t, n in _chain_members(routing, chain):
            if n:
                hosting.add(n)
    final_nodes = sorted((hosting | set(delta.joined)) - leaving)
    all_nodes = sorted(hosting | set(delta.joined) | leaving)
    before = _stats(incidence_of_routing(routing, all_nodes, chain_ids),
                    all_nodes, factor)
    plan = RebalancePlan(before=before)
    if delta.empty or not final_nodes:
        plan.after = before
        _rec_plan_moves.set(0)
        return plan

    # working state: membership node-sets per chain + per-node loads +
    # pairwise co-occurrence over final nodes, updated as moves are chosen
    idx = {n: i for i, n in enumerate(final_nodes)}
    nvec = len(final_nodes)
    loads = np.zeros(nvec, dtype=np.int64)
    C = np.zeros((nvec, nvec), dtype=np.int64)
    member_nodes: Dict[int, set] = {}
    for cid, chain in chains.items():
        ns = {n for _t, n in _chain_members(routing, chain) if n in idx}
        member_nodes[cid] = ns
        for n in ns:
            loads[idx[n]] += 1
        for a in ns:
            for b in ns:
                if a != b:
                    C[idx[a], idx[b]] += 1

    # failure-domain labels (mgmtd node tags): a destination may not push
    # any domain past the chain's loss budget — width-1 for CR, ec_m for
    # EC (docs/scale.md). Unlabeled clusters stay domain-blind.
    node_domain = {n.node_id: n.tags["domain"]
                   for n in routing.nodes.values()
                   if n.tags.get("domain")}

    def domain_ok(cid: int, members, dst: int) -> bool:
        dom = node_domain.get(dst)
        if dom is None:
            return True
        chain = chains[cid]
        cap = chain.ec_m if chain.is_ec \
            else max(len(chain.targets) - 1, 1)
        count = 1 + sum(1 for m in members if node_domain.get(m) == dom)
        return count <= cap

    def pick_dst(cid: int) -> Optional[int]:
        """Least-(λ-spike, load) eligible destination for one chain.
        None when every candidate is taken or would breach the chain's
        failure-domain budget — the caller defers the chain."""
        taken = member_nodes[cid]
        best = None
        for n in final_nodes:
            if n in taken or not domain_ok(cid, taken, n):
                continue
            i = idx[n]
            spike = max((C[i, idx[m]] + 1 for m in taken), default=1)
            key = (spike, loads[i], n)
            if best is None or key < best[0]:
                best = (key, n)
        return best[1] if best is not None else None

    def commit(cid: int, out_target: int, src_node: int, dst: int,
               is_ec: bool) -> None:
        taken = member_nodes[cid]
        if src_node in idx:
            loads[idx[src_node]] -= 1
            for m in taken:
                if m != src_node and m in idx:
                    C[idx[src_node], idx[m]] -= 1
                    C[idx[m], idx[src_node]] -= 1
        taken.discard(src_node)
        for m in taken:
            if m in idx:
                C[idx[dst], idx[m]] += 1
                C[idx[m], idx[dst]] += 1
        taken.add(dst)
        loads[idx[dst]] += 1
        plan.moves.append(PlannedMove(cid, out_target, src_node, dst,
                                      is_ec=is_ec))

    # 1) EVACUATE leaving nodes: one replacement per chain per wave
    for cid in sorted(chains):
        chain = chains[cid]
        on_leaving = [(t, n) for t, n in _chain_members(routing, chain)
                      if n in leaving]
        if not on_leaving:
            continue
        out_target, src_node = on_leaving[0]
        dst = pick_dst(cid)
        if dst is None:
            plan.deferred_chains.append(cid)
            continue
        commit(cid, out_target, src_node, dst, chain.is_ec)
        if len(on_leaving) > 1:
            plan.deferred_chains.append(cid)

    # 2) FILL joined nodes to their fair share — and not one chain more
    total = int(loads.sum())
    fair = (total // max(len(final_nodes), 1)) if fill_joined else 0
    moved_chains = {m.chain_id for m in plan.moves}
    for _ in range(total):
        under = [n for n in delta.joined
                 if n in idx and loads[idx[n]] < fair]
        if not under:
            break
        dst = min(under, key=lambda n: (loads[idx[n]], n))
        # donor: most loaded node above the fair ceiling; among its
        # chains pick the one whose move spikes λ least
        best = None
        ceiling = -(-total // len(final_nodes))  # ceil fair share
        for cid in sorted(chains):
            if cid in moved_chains:
                continue  # one move per chain per plan
            chain = chains[cid]
            if dst in member_nodes[cid]:
                continue
            for t, n in _chain_members(routing, chain):
                if n not in idx or n in leaving:
                    continue
                if loads[idx[n]] < ceiling or n in delta.joined:
                    continue
                if not domain_ok(cid, member_nodes[cid] - {n}, dst):
                    continue
                spike = max((C[idx[dst], idx[m]] + 1
                             for m in member_nodes[cid] if m != n
                             and m in idx), default=1)
                key = (-loads[idx[n]], spike, cid)
                if best is None or key < best[0]:
                    best = (key, cid, t, n)
        if best is None:
            break
        _key, cid, out_target, src_node = best
        commit(cid, out_target, src_node, dst, chains[cid].is_ec)
        moved_chains.add(cid)

    # predicted table = working state
    Mafter = np.zeros((len(chains), nvec), dtype=np.int8)
    for g, cid in enumerate(sorted(chains)):
        for n in member_nodes[cid]:
            if n in idx:
                Mafter[g, idx[n]] = 1
    plan.after = _stats(Mafter, final_nodes, factor)
    _rec_plan_moves.set(len(plan.moves))
    _rec_lambda.set(plan.after.lambda_max)
    return plan


def check_plan(routing: RoutingInfo, plan: RebalancePlan,
               delta: Optional[TopologyDelta] = None) -> List[str]:
    """Quorum preflight: problems (empty = safe to apply). A move is safe
    when the chain keeps a usable write/read quorum at EVERY intermediate
    step of its job:

    - CR: at least one member OFF the dead set stays SERVING (the copy
      source; the outgoing member itself counts while draining — it only
      leaves after its replacement serves);
    - EC: every OTHER member SERVING — the shard swap spends the chain's
      only spare redundancy unit, so it must actually be spare.
    """
    delta = delta or TopologyDelta.from_routing(routing)
    dead = set(delta.dead)
    node_domain = {n.node_id: n.tags["domain"]
                   for n in routing.nodes.values()
                   if n.tags.get("domain")}
    problems: List[str] = []
    for mv in plan.moves:
        chain = routing.chains.get(mv.chain_id)
        if chain is None:
            problems.append(f"chain {mv.chain_id}: not in routing")
            continue
        others = [t for t in chain.targets if t.target_id != mv.out_target]
        dst_dom = node_domain.get(mv.dst_node)
        if dst_dom is not None:
            cap = chain.ec_m if chain.is_ec \
                else max(len(chain.targets) - 1, 1)
            stay = [routing.targets[t.target_id].node_id for t in others
                    if t.target_id in routing.targets]
            count = 1 + sum(1 for n in stay
                            if node_domain.get(n) == dst_dom)
            if count > cap:
                problems.append(
                    f"chain {mv.chain_id}: landing {mv.out_target}'s "
                    f"replacement on {mv.dst_node} puts {count} members "
                    f"in domain {dst_dom!r} (budget {cap}) — a single-"
                    f"domain kill would break quorum")
        if chain.is_ec:
            bad = [t.target_id for t in others
                   if t.public_state != PublicTargetState.SERVING]
            if bad:
                problems.append(
                    f"chain {mv.chain_id}: EC swap of {mv.out_target} "
                    f"while members {bad} are not SERVING would drop the "
                    "stripe below its k-quorum")
            continue
        sources = []
        for t in chain.targets:
            info = routing.targets.get(t.target_id)
            node = info.node_id if info else 0
            if node in dead:
                continue
            if t.public_state == PublicTargetState.SERVING:
                sources.append(t.target_id)
        if not sources:
            problems.append(
                f"chain {mv.chain_id}: no surviving SERVING copy source "
                f"for replacing {mv.out_target}")
    return problems
