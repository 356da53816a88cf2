"""Scale-fabric property tests: thousands of in-process nodes against
the REAL control plane (tpu3fs/scale, docs/scale.md).

The fast subset runs in tier-1 (one N=1000 end-to-end property plus
small-N properties for churn/placement/fast-reply); the full sweep —
every domain killed and restarted in turn at N=1000, cold routing
fan-out — is slow-marked.
"""

import numpy as np
import pytest

from tpu3fs.mgmtd.types import LocalTargetState
from tpu3fs.placement.solver import (
    PlacementProblem,
    check_solution,
    domain_overflow,
    solve_placement,
)
from tpu3fs.rpc.serde import serialize
from tpu3fs.rpc.services import RoutingRsp
from tpu3fs.scale import ScaleConfig, ScaleFabric


class TestScaleFabricSmall:
    def test_boot_lays_domain_clean_table(self):
        sf = ScaleFabric(ScaleConfig(num_nodes=30, num_domains=3))
        assert len(sf.chain_ids) == sf.cfg.num_chains == 30
        assert sf.domain_violations() == []
        # every solver output satisfies the structural contract too
        assert len(sf.incidence) == len(sf.chain_ids)

    def test_domain_kill_keeps_every_quorum(self):
        sf = ScaleFabric(ScaleConfig(num_nodes=30, num_domains=3))
        killed = sf.kill_domain("d0")
        assert len(killed) == 10
        q = sf.quorum_report()
        assert q["broken"] == 0 and q["ok"] == len(sf.chain_ids)

    def test_domain_restart_recovers(self):
        sf = ScaleFabric(ScaleConfig(num_nodes=30, num_domains=3))
        sf.kill_domain("d1")
        sf.restart_domain("d1")
        # restarted nodes report ONLINE (not UPTODATE): the chain state
        # machine readmits them — no chain may lose quorum meanwhile
        assert sf.quorum_report()["broken"] == 0
        for nid in sf.domain_nodes("d1"):
            assert all(s == LocalTargetState.ONLINE
                       for s in sf.nodes[nid].local_states.values())

    def test_domain_blind_ab(self):
        """The A/B the constraint exists for: the SAME contiguous-block
        domain layout, placed blind, over-concentrates chains in single
        domains and a whole-domain kill breaks quorum."""
        blind = ScaleFabric(ScaleConfig(num_nodes=30, num_domains=3,
                                        domain_aware=False))
        assert len(blind.domain_violations()) > 0
        blind.kill_domain("d0")
        assert blind.quorum_report()["broken"] > 0

    def test_routing_fast_reply_version_gated(self):
        """getRoutingInfo(current_version) -> None, counted on
        mgmtd.routing_not_modified; any routing change reopens the full
        snapshot path (what saves the fleet-wide fan-out)."""
        sf = ScaleFabric(ScaleConfig(num_nodes=12, num_domains=3))
        ri = sf.mgmtd.get_routing_info(-1)
        assert ri is not None
        v0 = ri.version  # snapshot: get_routing_info returns the LIVE object
        assert sf.mgmtd.get_routing_info(v0) is None
        rec = sf.mgmtd._not_modified_rec
        assert rec is not None and rec._value >= 1
        before = rec._value
        assert sf.mgmtd.get_routing_info(v0) is None
        assert rec._value == before + 1
        # the unchanged reply is tiny next to a snapshot re-serialization
        small = len(serialize(RoutingRsp(changed=False, routing=None)))
        full = len(serialize(RoutingRsp(changed=True, routing=ri)))
        assert small * 50 < full
        # a real routing change reopens the full path at the new version
        sf.kill_domain("d0")
        ri2 = sf.mgmtd.get_routing_info(v0)
        assert ri2 is not None and ri2.version != v0

    def test_routing_fanout_warm_vs_cold(self):
        sf = ScaleFabric(ScaleConfig(num_nodes=100, num_domains=5))
        cold_b, _ = sf.routing_fanout(up_to_date=False)
        warm_b, _ = sf.routing_fanout(up_to_date=True)
        assert warm_b * 100 < cold_b

    def test_heartbeat_intake_bounded_memory(self):
        """Sustained heartbeat traffic must not grow the MVCC store:
        the pruner keeps per-key history bounded, so footprint after 40
        rounds is about what it was after 10 (not 4x)."""
        sf = ScaleFabric(ScaleConfig(num_nodes=100, num_domains=5))
        for _ in range(10):
            sf.heartbeat_round()
        f10 = sf.kv_footprint()
        for _ in range(30):
            sf.heartbeat_round()
        f40 = sf.kv_footprint()
        assert f40["keys"] == f10["keys"]
        assert f40["history"] <= f10["history"] * 1.5 + 64

    def test_meta_assignment_stable_under_churn(self):
        """Partition-table assignment stability: killing one META owner
        moves ONLY its rows (epoch-bumped, to least-loaded survivors);
        every retained (owner, epoch) pair is byte-identical. A rejoin
        rebalances to within one row per owner without churning rows it
        doesn't claim."""
        sf = ScaleFabric(ScaleConfig(num_nodes=12, num_domains=3,
                                     meta_nodes=3, meta_partitions=16))
        before = sf.meta_assignment()
        assert len(before) == 16
        victim = sf.meta_node_ids[0]
        sf.kill_meta_node(victim)
        after = sf.meta_assignment()
        moved = {pid for pid in before if before[pid] != after[pid]}
        for pid in moved:
            assert before[pid][0] == victim              # only its rows
            assert after[pid][0] != victim
            assert after[pid][1] > before[pid][1]        # epoch bumped
        for pid in set(before) - moved:
            assert after[pid] == before[pid]             # retained: frozen
        # rejoin: balanced within one, retained rows still frozen
        sf.restart_meta_node(victim)
        rejoined = sf.meta_assignment()
        loads: dict = {}
        for nid, _epoch in rejoined.values():
            loads[nid] = loads.get(nid, 0) + 1
        assert max(loads.values()) - min(loads.values()) <= 1
        for pid in rejoined:
            if rejoined[pid] == after[pid]:
                continue
            assert rejoined[pid][0] == victim            # only pulls, no shuffles
            assert rejoined[pid][1] > after[pid][1]


class TestSolverDomainProperties:
    def test_random_domain_configs_always_satisfied(self):
        """Property: for every feasible (v, k, r, D) drawn, the solver's
        output passes check_solution and has zero domain overflow."""
        rng = np.random.default_rng(7)
        for trial in range(8):
            d = int(rng.integers(3, 6))
            per = int(rng.integers(3, 7))
            v = d * per
            k = int(rng.integers(2, min(d, 4) + 1))
            r = int(rng.choice([x for x in (1, 2, 3, k) if (v * x) % k == 0]
                               or [k]))
            domains = [f"d{i * d // v}" for i in range(v)]
            problem = PlacementProblem(
                num_nodes=v, group_size=k, targets_per_node=r,
                chain_table_type="CR", domains=domains,
                max_per_domain=max(k - 1, 1))
            M = solve_placement(problem, steps=0, seed=trial)
            assert domain_overflow(M, problem) == 0
            assert check_solution(M, problem)

    def test_infeasible_domain_config_raises(self):
        # one domain holds everything: no 3-group can stay under cap 2
        with pytest.raises(ValueError, match="infeasible"):
            PlacementProblem(num_nodes=6, group_size=3, targets_per_node=1,
                             chain_table_type="CR",
                             domains=["d0"] * 6, max_per_domain=2)

    def test_domains_require_cap_and_vice_versa(self):
        with pytest.raises(ValueError):
            PlacementProblem(num_nodes=6, group_size=3, targets_per_node=1,
                             chain_table_type="CR",
                             domains=["d0", "d1"] * 3, max_per_domain=None)


class TestThousandNodes:
    def test_thousand_node_day(self):
        """The fast end-to-end property at full scale: boot 1000 nodes /
        1000 chains across 10 domains on the real mgmtd, verify the
        placement constraint holds for every chain, sustain heartbeat
        fan-in with bounded KV memory, kill an entire domain, and lose
        no chain's quorum."""
        sf = ScaleFabric(ScaleConfig(num_nodes=1000, num_domains=10))
        assert len(sf.chain_ids) == 1000
        assert sf.domain_violations() == []

        lat = sf.heartbeat_round()
        assert len(lat) == 1000
        f1 = sf.kv_footprint()
        for _ in range(3):
            sf.heartbeat_round()
        f4 = sf.kv_footprint()
        assert f4["keys"] == f1["keys"]
        assert f4["history"] <= f1["history"] * 1.5 + 64

        killed = sf.kill_domain("d0")
        assert len(killed) == 100
        q = sf.quorum_report()
        assert q["broken"] == 0 and q["ok"] == 1000

        sf.restart_domain("d0")
        assert sf.quorum_report()["broken"] == 0


@pytest.mark.slow
class TestThousandNodeSweep:
    def test_every_domain_killable_in_turn(self):
        sf = ScaleFabric(ScaleConfig(num_nodes=1000, num_domains=10))
        for d in range(10):
            sf.kill_domain(f"d{d}")
            assert sf.quorum_report()["broken"] == 0, f"domain d{d}"
            sf.restart_domain(f"d{d}")
            sf.complete_resync(f"d{d}")
        assert sf.domain_violations() == []
        assert sf.quorum_report()["broken"] == 0

    def test_cold_fanout_at_scale(self):
        sf = ScaleFabric(ScaleConfig(num_nodes=1000, num_domains=10))
        cold_b, _ = sf.routing_fanout(up_to_date=False)
        warm_b, _ = sf.routing_fanout(up_to_date=True)
        assert warm_b * 1000 < cold_b
