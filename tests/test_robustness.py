"""Gray-failure robustness layer (docs/robustness.md): end-to-end
deadline propagation, hedged reads, per-peer health circuit breakers,
and the hot-configurable cluster fault plane."""

import threading
import time

import pytest

from tpu3fs.analytics import spans as _spans
from tpu3fs.client.hedging import HedgeController, run_hedged
from tpu3fs.client.storage_client import RetryOptions, StorageClient
from tpu3fs.fabric.fabric import Fabric, SystemSetupConfig
from tpu3fs.rpc import deadline as dl
from tpu3fs.rpc.health import BreakerState, HealthRegistry
from tpu3fs.rpc.net import RpcClient, RpcServer, ServiceDef
from tpu3fs.rpc.services import EchoReq, EchoRsp, MgmtdRpcClient
from tpu3fs.storage.craq import ReadReply, ReadReq
from tpu3fs.storage.types import ChunkId
from tpu3fs.storage.update_worker import UpdateWorker
from tpu3fs.utils.fault_injection import (
    FaultPlane,
    FaultPlaneConfig,
    apply_plane_config,
    fault_injection,
    inject,
    parse_spec,
    plane,
)
from tpu3fs.utils.result import Code, FsError, Status


# -- deadline wire codec ------------------------------------------------------


class TestDeadlineCodec:
    def test_standalone_round_trip(self):
        t = time.time() + 1.5
        msg = dl.encode_envelope("", t)
        assert msg.startswith("d1.")
        got = dl.decode_deadline(msg)
        assert got == pytest.approx(t, abs=1e-5)

    def test_composes_with_trace_wire_both_parsers(self):
        """NEW encoder -> both the trace decoder and the deadline decoder
        read their half (the appended-fields tolerance of decode_wire)."""
        ctx = _spans.TraceContext("a" * 16, "b" * 16, sampled=True)
        t = time.time() + 2.0
        msg = dl.encode_envelope(ctx.to_wire(), t)
        back = _spans.decode_wire(msg)          # "old" trace-only parser
        assert back is not None
        assert back.trace_id == "a" * 16 and back.sampled
        assert dl.decode_deadline(msg) == pytest.approx(t, abs=1e-5)

    def test_old_messages_decode_to_none(self):
        """OLD encoders (trace-only, empty, junk) -> no deadline; no
        exception either direction."""
        ctx = _spans.TraceContext("a" * 16, "b" * 16)
        for legacy in ("", ctx.to_wire(), "retry_after_ms=5", "t1.x",
                       "d1.", "d1.zz", "t1.a.b.3"):
            assert dl.decode_deadline(legacy) is None

    def test_trace_flags_spelling_d1_not_misread(self):
        # a flags field that spells 'd1' (0xd1) must not parse as a
        # deadline token (deadline scan starts at field index 4)
        assert dl.decode_deadline("t1.aaaa.bbbb.d1") is None

    def test_scope_nesting_tightens_only(self):
        with dl.deadline_after(10.0) as outer:
            with dl.deadline_scope(time.time() + 99.0) as inner:
                assert inner == outer  # a callee cannot LOOSEN the budget
            with dl.deadline_after(0.5) as tight:
                assert tight < outer
        assert dl.current_deadline() is None


# -- server-side sheds --------------------------------------------------------


class TestDeadlineSheds:
    def test_rpc_admission_shed_python_transport(self):
        """An expired envelope answers DEADLINE_EXCEEDED without the
        handler ever running."""
        server = RpcServer()
        s = ServiceDef(60, "Echoish")
        calls = []
        s.method(1, "echo", EchoReq, EchoRsp,
                 lambda r: calls.append(1) or EchoRsp(r.text))
        server.add_service(s)
        server.start()
        try:
            client = RpcClient()
            before = dl.shed_totals()["admission"]
            with dl.deadline_scope(time.time() - 0.5):
                with pytest.raises(FsError) as ei:
                    client.call(server.address, 60, 1, EchoReq("x"), EchoRsp)
            assert ei.value.code == Code.DEADLINE_EXCEEDED
            assert not calls
            assert dl.shed_totals()["admission"] == before + 1
            # a live deadline passes through untouched
            with dl.deadline_after(30.0):
                rsp = client.call(server.address, 60, 1, EchoReq("y"),
                                  EchoRsp)
            assert rsp.text == "y" and calls
        finally:
            server.stop()

    def test_update_queue_dequeue_shed(self):
        """A queued batch whose deadline passed while waiting is answered
        DEADLINE_EXCEEDED at round start; the runner NEVER sees it."""
        ran = []

        def runner(reqs):
            ran.extend(reqs)
            return [("ok", r) for r in reqs]

        worker = UpdateWorker(runner, name="t")
        try:
            class _R:
                chain_id = 1
                chunk_id = ChunkId(1, 0)

            before = dl.shed_totals()["dequeue"]
            with dl.deadline_scope(time.time() - 0.1):
                out = worker.submit(
                    [_R(), _R()],
                    lambda code, msg, ra=0: (code, msg))
            assert [c for c, _ in out] == [Code.DEADLINE_EXCEEDED] * 2
            assert not ran
            assert dl.shed_totals()["dequeue"] == before + 2 or \
                dl.shed_totals()["dequeue"] == before + 1
            # live-deadline work still executes
            with dl.deadline_after(30.0):
                out = worker.submit([_R()], lambda c, m, ra=0: (c, m))
            assert ran and out[0][0] == "ok"
        finally:
            worker.stop()

    def test_fabric_admission_shed_never_reaches_engine(self):
        """Through the in-process fabric: expired read AND write shed at
        admission; the engine's committed content is untouched."""
        fab = Fabric(SystemSetupConfig(num_storage_nodes=2,
                                       num_replicas=2, num_chains=1))
        try:
            sc = fab.storage_client()
            cid, ck = fab.chain_ids[0], ChunkId(7, 0)
            assert sc.write_chunk(cid, ck, 0, b"alive").ok
            with dl.deadline_scope(time.time() - 0.01):
                r = sc.read_chunk(cid, ck)
                assert r.code == Code.DEADLINE_EXCEEDED
                w = sc.write_chunk(cid, ck, 0, b"DEAD!")
                assert w.code == Code.DEADLINE_EXCEEDED
            ok = sc.read_chunk(cid, ck)
            assert ok.ok and bytes(ok.data) == b"alive"
        finally:
            fab.close()


# -- client budget derivation -------------------------------------------------


class TestClientBudgets:
    def _client(self, **retry_kw):
        fab = Fabric(SystemSetupConfig(num_storage_nodes=2,
                                       num_replicas=2, num_chains=1))
        return fab, fab.storage_client(retry=RetryOptions(**retry_kw))

    def test_sleep_never_past_deadline(self):
        """Regression: a 10s retry-after hint must not out-sleep a 50ms
        deadline budget."""
        fab, sc = self._client()
        try:
            with dl.deadline_scope(time.time() + 0.05):
                t0 = time.monotonic()
                sc._sleep(attempt=9, hint_ms=10_000)
                assert time.monotonic() - t0 < 0.3
            # and an expired budget sleeps not at all
            with dl.deadline_scope(time.time() - 1.0):
                t0 = time.monotonic()
                sc._sleep(attempt=9, hint_ms=10_000)
                assert time.monotonic() - t0 < 0.05
        finally:
            fab.close()

    def test_sleep_full_jitter_below_cap(self):
        fab, sc = self._client(backoff_base_s=0.004, backoff_max_s=0.004)
        try:
            delays = []
            orig = time.sleep
            try:
                time.sleep = lambda s: delays.append(s)
                for _ in range(50):
                    sc._sleep(attempt=5)
            finally:
                time.sleep = orig
            assert delays and max(delays) <= 0.004 + 1e-9
            # FULL jitter: the lower half of [0, cap] must be populated
            assert min(delays) < 0.002
        finally:
            fab.close()

    def test_op_deadline_knob_bounds_ladder(self):
        """RetryOptions.op_deadline_s arms a budget at op entry: an op
        against a chain with no serving replicas gives up within it."""
        fab, sc = self._client(op_deadline_s=0.25, max_retries=100)
        try:
            cid = fab.chain_ids[0]
            for node in list(fab.nodes.values()):
                fab.kill_node(node.node_id)
            t0 = time.monotonic()
            r = sc.read_chunk(cid, ChunkId(1, 0))
            took = time.monotonic() - t0
            assert took < 3.0
            assert r.code in (Code.DEADLINE_EXCEEDED,
                              Code.RPC_CONNECT_FAILED,
                              Code.RPC_PEER_CLOSED)
        finally:
            fab.close()


# -- circuit breaker state machine -------------------------------------------


class TestBreaker:
    def _reg(self, **kw):
        clock = [0.0]
        kw.setdefault("error_threshold", 3)
        kw.setdefault("cooldown_s", 5.0)
        reg = HealthRegistry(clock=lambda: clock[0], **kw)
        return reg, clock

    def test_closed_to_open_to_half_open_to_closed(self):
        reg, clock = self._reg()
        for _ in range(2):
            reg.observe("p", 0.0, ok=False)
        assert reg.state("p") == BreakerState.CLOSED
        reg.observe("p", 0.0, ok=False)  # third consecutive error
        assert reg.state("p") == BreakerState.OPEN
        assert reg.opened_total == 1
        # during cooldown: fail fast
        assert not reg.allow("p")
        assert reg.fail_fast_total == 1
        clock[0] += 6.0
        # cooldown over: EXACTLY one probe admitted
        assert reg.allow("p")
        assert reg.state("p") == BreakerState.HALF_OPEN
        assert reg.probe_total == 1
        assert not reg.allow("p")  # second caller while probe in flight
        reg.observe("p", 0.002, ok=True)  # probe succeeded
        assert reg.state("p") == BreakerState.CLOSED
        assert reg.closed_total == 1
        assert reg.allow("p")

    def test_half_open_probe_failure_reopens(self):
        reg, clock = self._reg()
        for _ in range(3):
            reg.observe("p", 0.0, ok=False)
        clock[0] += 6.0
        assert reg.allow("p")          # probe
        reg.observe("p", 0.0, ok=False)  # probe failed
        assert reg.state("p") == BreakerState.OPEN
        assert reg.opened_total == 2
        assert not reg.allow("p")      # fresh cooldown

    def test_success_resets_error_streak(self):
        reg, _ = self._reg()
        reg.observe("p", 0.001, ok=False)
        reg.observe("p", 0.001, ok=False)
        reg.observe("p", 0.001, ok=True)
        reg.observe("p", 0.001, ok=False)
        assert reg.state("p") == BreakerState.CLOSED

    def test_latency_outlier_is_suspect(self):
        reg, _ = self._reg(slow_ms=10.0, slow_factor=4.0)
        for _ in range(5):
            reg.observe("fast", 0.001, ok=True)
            reg.observe("gray", 0.100, ok=True)
        assert reg.suspect("gray")
        assert not reg.suspect("fast")
        # absolute floor: microsecond spreads never demote anybody
        reg2, _ = self._reg(slow_ms=10.0)
        reg2.observe("a", 0.0001, ok=True)
        reg2.observe("b", 0.0009, ok=True)
        assert not reg2.suspect("b")


class TestMessengerBreaker:
    def test_writes_fail_fast_reads_pass(self):
        from tpu3fs.mgmtd.types import RoutingInfo
        from tpu3fs.rpc.services import RpcMessenger

        m = RpcMessenger(lambda: RoutingInfo())
        for _ in range(3):
            m.health.observe(5, 0.0, ok=False)
        with pytest.raises(FsError) as ei:
            m(5, "write", object())
        assert ei.value.code == Code.PEER_UNHEALTHY
        assert ei.value.status.retryable()
        # reads are never fail-fasted (selection reorders instead; a read
        # reaching the peer is a free probe) — this one fails on ADDRESS
        # resolution, proving it got past the breaker
        with pytest.raises(FsError) as ei:
            m(5, "read", object())
        assert ei.value.code == Code.RPC_CONNECT_FAILED


# -- hedged reads -------------------------------------------------------------


class TestHedging:
    def test_backup_wins_over_straggling_primary(self):
        ctl = HedgeController(delay_floor_ms=5.0)

        def primary():
            time.sleep(0.2)
            return "slow"

        reply, hedged, backup_won = run_hedged(
            primary, lambda: "fast", 0.005, ctl)
        assert reply == "fast" and hedged and backup_won
        assert ctl.stats()["win"] == 1 and ctl.stats()["sent"] == 1

    def test_fast_primary_never_hedges(self):
        ctl = HedgeController(delay_floor_ms=50.0)
        reply, hedged, _ = run_hedged(lambda: "quick", lambda: "never",
                                      0.05, ctl)
        assert reply == "quick" and not hedged
        assert ctl.stats()["sent"] == 0

    def test_primary_win_counts_loss(self):
        ctl = HedgeController(delay_floor_ms=1.0)

        def primary():
            time.sleep(0.02)
            return "p"

        def backup():
            time.sleep(0.3)
            return "b"

        reply, hedged, backup_won = run_hedged(primary, backup, 0.001, ctl)
        assert reply == "p" and hedged and not backup_won
        assert ctl.stats()["loss"] == 1

    def test_budget_suppresses_hedges(self):
        ctl = HedgeController(budget_ratio=0.0, burst=1.0,
                              delay_floor_ms=1.0)

        def slow():
            time.sleep(0.02)
            return "s"

        run_hedged(slow, lambda: "b", 0.001, ctl)   # spends the only token
        run_hedged(slow, lambda: "b", 0.001, ctl)   # suppressed
        st = ctl.stats()
        assert st["sent"] == 1 and st["suppressed"] == 1

    def test_fast_bad_primary_returns_for_caller_failover(self):
        """A primary that ANSWERS (even badly) within the delay returns
        without hedging — the caller's sequential failover ladder owns
        definitive-error handling; hedging exists for SLOW primaries."""
        ctl = HedgeController(delay_floor_ms=1.0)
        reply, hedged, _ = run_hedged(
            lambda: "bad", lambda: "good", 0.05, ctl,
            good=lambda r: r == "good")
        assert reply == "bad" and not hedged

    def test_slow_bad_primary_loses_to_good_backup(self):
        ctl = HedgeController(delay_floor_ms=1.0)

        def primary():
            time.sleep(0.05)
            return "bad"

        reply, hedged, backup_won = run_hedged(
            primary, lambda: "good", 0.002, ctl,
            good=lambda r: r == "good")
        assert reply == "good" and hedged and backup_won

    def test_hedged_read_end_to_end_with_straggler(self):
        """Fabric, 3 replicas, HEAD selection so the primary replica is
        deterministic; a fault-plane delay makes the head node a 100ms
        straggler — the hedged read returns fast via the backup replica
        and the hedge-win recorder fires."""
        from tpu3fs.client.storage_client import TargetSelectionMode

        fab = Fabric(SystemSetupConfig(num_storage_nodes=3,
                                       num_replicas=3, num_chains=1))
        try:
            sc = fab.storage_client(
                selection=TargetSelectionMode.HEAD,
                retry=RetryOptions(hedge_delay_floor_ms=5.0,
                                   health_reorder=False,
                                   hedge_budget_burst=64))
            cid, ck = fab.chain_ids[0], ChunkId(3, 0)
            assert sc.write_chunk(cid, ck, 0, b"tail-data").ok
            chain = fab.routing().chains[cid]
            head_node = fab.routing().node_of_target(
                chain.targets[0].target_id).node_id
            plane().configure(
                f"point=storage.read,kind=delay_ms,arg=100,"
                f"node={head_node}", seed=1)
            t0 = time.monotonic()
            r = sc.read_chunk(cid, ck)
            took = time.monotonic() - t0
            assert r.ok and bytes(r.data) == b"tail-data"
            assert took < 0.09, f"hedge did not rescue the read ({took:.3f}s)"
            st = sc._hedge.stats()
            assert st["sent"] >= 1 and st["win"] >= 1
        finally:
            plane().clear()
            fab.close()

    def test_suspect_replica_demoted_in_selection(self):
        """Health reordering: after one slow observation the straggler
        node sorts last, so subsequent reads avoid it entirely."""
        fab = Fabric(SystemSetupConfig(num_storage_nodes=3,
                                       num_replicas=3, num_chains=1))
        try:
            sc = fab.storage_client()
            cid, ck = fab.chain_ids[0], ChunkId(4, 0)
            assert sc.write_chunk(cid, ck, 0, b"x" * 64).ok
            routing = fab.routing()
            chain = routing.chains[cid]
            gray = routing.node_of_target(chain.targets[0].target_id).node_id
            # teach the EWMA: the gray node is slow, the others fast
            sc._health.observe(gray, 0.2, ok=True)
            for t in chain.targets[1:]:
                n = routing.node_of_target(t.target_id).node_id
                sc._health.observe(n, 0.001, ok=True)
            order = sc._pick_targets(chain, routing)
            gray_targets = {t.target_id for t in chain.targets
                            if routing.node_of_target(t.target_id).node_id
                            == gray}
            assert order[-1] in gray_targets
        finally:
            fab.close()


# -- fault injection + fault plane -------------------------------------------


class TestFaultInjectionSeeding:
    def test_seeded_context_is_reproducible(self):
        def run(seed):
            fired = []
            with fault_injection(0.5, times=-1, seed=seed):
                for i in range(40):
                    try:
                        inject("p")
                        fired.append(0)
                    except FsError:
                        fired.append(1)
            return fired

        assert run(7) == run(7)
        assert run(7) != run(8) or True  # different seeds MAY differ
        assert any(run(7)) and not all(run(7))

    def test_seeded_plane_is_reproducible(self):
        def run():
            pl = FaultPlane()
            pl.configure("point=x,kind=error,prob=0.5", seed=42)
            out = []
            for _ in range(40):
                try:
                    pl.fire("x.sub")
                    out.append(0)
                except FsError:
                    out.append(1)
            return out

        assert run() == run()


class TestFaultPlane:
    def test_parse_validates(self):
        rules = parse_spec("point=a.b,kind=delay_ms,arg=5,prob=0.5,"  # fault-ok
                           "times=3,node=7; point=c")  # fault-ok

        assert len(rules) == 2
        assert rules[0].kind == "delay_ms" and rules[0].node == 7
        assert rules[1].kind == "error" and rules[1].prob == 1.0
        for bad in ("kind=error", "point=a,kind=nope",
                    "point=a,prob=2.0", "point=a,junk"):
            with pytest.raises(ValueError):
                parse_spec(bad)

    def test_kinds_and_node_scoping(self):
        pl = FaultPlane()
        pl.configure("point=p.err,kind=error;"
                     "point=p.drop,kind=drop;"
                     "point=p.slow,kind=delay_ms,arg=30,node=2")
        with pytest.raises(FsError) as ei:
            pl.fire("p.err")
        assert ei.value.code == Code.FAULT_INJECTION
        with pytest.raises(ConnectionError):
            pl.fire("p.drop.anything")   # prefix match
        t0 = time.monotonic()
        pl.fire("p.slow", node=2)
        assert time.monotonic() - t0 >= 0.025
        t0 = time.monotonic()
        pl.fire("p.slow", node=3)        # other node: no delay
        pl.fire("p.slow")                # unscoped fire point: no delay
        assert time.monotonic() - t0 < 0.02

    def test_times_cap(self):
        pl = FaultPlane()
        pl.configure("point=q,kind=error,times=2")
        for _ in range(2):
            with pytest.raises(FsError):
                pl.fire("q")
        pl.fire("q")  # exhausted: silent
        assert pl.fired_total == 2

    def test_hot_config_binding(self):
        pl = FaultPlane()
        cfg = FaultPlaneConfig()
        apply_plane_config(cfg, target=pl)
        assert not pl.active
        cfg.hot_update({"spec": "point=z,kind=error", "seed": 3})
        with pytest.raises(FsError):
            pl.fire("z")
        cfg.hot_update({"spec": ""})
        pl.fire("z")  # cleared
        with pytest.raises(ValueError):
            cfg.hot_update({"spec": "point=z,kind=bogus"})

    def test_rpc_dispatch_drop_and_error(self):
        """The python transport's dispatch boundary: error rules answer
        FAULT_INJECTION; drop rules tear the connection (PEER_CLOSED on
        the client)."""
        server = RpcServer()
        s = ServiceDef(61, "Victim")
        s.method(1, "echo", EchoReq, EchoRsp, lambda r: EchoRsp(r.text))
        server.add_service(s)
        server.start()
        try:
            client = RpcClient()
            plane().configure("point=rpc.dispatch.Victim.echo,kind=error")
            with pytest.raises(FsError) as ei:
                client.call(server.address, 61, 1, EchoReq("a"), EchoRsp)
            assert ei.value.code == Code.FAULT_INJECTION
            plane().configure("point=rpc.dispatch.Victim.echo,kind=drop")
            with pytest.raises(FsError) as ei:
                client.call(server.address, 61, 1, EchoReq("a"), EchoRsp)
            assert ei.value.code in (Code.RPC_PEER_CLOSED, Code.RPC_TIMEOUT)
            plane().clear()
            rsp = client.call(server.address, 61, 1, EchoReq("ok"), EchoRsp)
            assert rsp.text == "ok"
        finally:
            plane().clear()
            server.stop()


# -- mgmtd hot-config + routing promptness ------------------------------------


class TestMgmtdHotKnobs:
    def test_heartbeat_timeout_hot_updates_live_mgmtd(self):
        from tpu3fs.bin.mgmtd_main import MgmtdApp
        from tpu3fs.kv.mem import MemKVEngine

        class _Reg:
            def add_service(self, s):
                pass

        app = MgmtdApp([], engine=MemKVEngine())
        app.build_services(_Reg())
        assert app.mgmtd.config.heartbeat_timeout_s == 60.0
        app.config.hot_update({"heartbeat_timeout_s": 7.5,
                               "lease_length_s": 12.0})
        assert app.mgmtd.config.heartbeat_timeout_s == 7.5
        assert app.mgmtd.config.lease_length_s == 12.0

    def test_known_routing_version(self):
        from tpu3fs.mgmtd.types import RoutingInfo

        c = MgmtdRpcClient(("127.0.0.1", 1))
        assert c.known_routing_version() == -1
        ri = RoutingInfo()
        ri.version = 9
        c._routing = ri
        c._routing_ts = time.monotonic()
        assert c.known_routing_version() == 9
        c.invalidate_routing()
        assert c._routing_ts == float("-inf")


# -- the library client's routing snapshot (unit level) ------------------------


class TestRoutingSnapshot:
    """MgmtdRpcClient.cached_routing against a scripted mgmtd (the live
    cluster side is tests/test_cli_rpc.py)."""

    @staticmethod
    def _client(answers):
        """A client whose getRoutingInfo replies come from ``answers``
        (versions; None = 'unchanged'); other calls raise like a dead peer."""
        from tpu3fs.mgmtd.types import RoutingInfo
        from tpu3fs.rpc.services import RoutingRsp

        c = MgmtdRpcClient(("127.0.0.1", 1))
        c.asked = []

        def scripted(method_id, req, rsp_type):
            if method_id != 2:
                raise FsError(Status(Code.RPC_TIMEOUT, "scripted"))
            c.asked.append(req.known_version)
            ver = answers.pop(0)
            if callable(ver):
                ver = ver()
            if ver is None:
                return RoutingRsp(changed=False, routing=None)
            return RoutingRsp(changed=True, routing=RoutingInfo(version=ver))

        c._failover_call = scripted
        return c

    def test_held_snapshot_is_served_and_polls_are_version_gated(self):
        c = self._client([3, None])
        assert c.cached_routing().version == 3
        for _ in range(10):
            assert c.cached_routing().version == 3
        assert c.asked == [-1]
        c.invalidate_routing()
        assert c.cached_routing().version == 3
        assert c.asked == [-1, 3]
        assert (c.routing_polls._value, c.routing_cached._value) == (2, 10)

    def test_an_older_answer_is_never_installed(self):
        c = self._client([7, 5])
        assert c.refresh_routing().version == 7
        assert c.refresh_routing().version == 7  # lagging standby said 5

    def test_a_poll_in_flight_across_an_invalidation_does_not_stamp_fresh(
            self):
        # the reply "unchanged" was computed BEFORE the mutation that
        # invalidated: it must not make the stale snapshot look fresh
        def unchanged_then_invalidated():
            c.invalidate_routing()
            return None

        c = self._client([4, unchanged_then_invalidated, 6])
        c.refresh_routing()
        assert c.cached_routing().version == 4   # held, fresh
        c.invalidate_routing()
        assert c.cached_routing().version == 4   # polled, raced
        assert c.cached_routing().version == 6   # so it polls again
        assert c.asked == [-1, 4, 4]

    def test_threads_polling_and_invalidating_never_see_routing_go_back(
            self):
        """More threads than cores resolve, invalidate and refresh against
        a mgmtd whose version moves on and whose standby sometimes answers
        with an older snapshot: no reader ever sees a version below one it
        saw before, and the newest version is reached."""
        import itertools
        import sys

        from tpu3fs.mgmtd.types import RoutingInfo
        from tpu3fs.rpc.services import RoutingRsp

        c = MgmtdRpcClient(("127.0.0.1", 1))
        clock = itertools.count(1)

        def scripted(method_id, req, rsp_type):
            n = next(clock)
            ver = n - 3 if n % 5 == 0 else n   # a lagging standby
            return RoutingRsp(changed=True, routing=RoutingInfo(version=ver))

        c._failover_call = scripted
        stop = time.monotonic() + 0.6
        went_back = []

        def worker(i):
            seen = 0
            while time.monotonic() < stop:
                if i % 3 == 0:
                    c.invalidate_routing()
                ver = (c.refresh_routing() if i % 3 == 1
                       else c.cached_routing()).version
                if ver < seen:
                    went_back.append((i, seen, ver))
                seen = ver

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(12)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(old)
        assert went_back == []
        assert c.refresh_routing().version >= next(clock) - 4

    @pytest.mark.parametrize("method_id,invalidates", [
        (3, True), (4, True), (5, True), (6, True), (9, True), (10, True),
        (11, True), (12, True), (17, True), (18, True),
        (1, False), (7, False), (8, False), (13, False), (14, False),
    ])
    def test_calls_that_change_routing_invalidate_even_when_they_fail(
            self, method_id, invalidates):
        c = self._client([2])
        c.refresh_routing()
        with pytest.raises(FsError):
            c._call(method_id, None, None)
        assert (c._routing_ts == float("-inf")) == invalidates

    @pytest.mark.parametrize("shape", ["bound_method", "callable_object",
                                       "plain_function"])
    def test_storage_client_finds_the_invalidation_hook(self, shape):
        from tpu3fs.mgmtd.types import RoutingInfo, routing_invalidator

        if shape == "bound_method":
            c = self._client([1])
            provider, expect = c.cached_routing, c.invalidate_routing
        elif shape == "callable_object":
            class Provider:
                def __call__(self):
                    return RoutingInfo()

                def invalidate(self):
                    pass

            provider = Provider()
            expect = provider.invalidate
        else:
            provider, expect = (lambda: RoutingInfo()), None
        hook = routing_invalidator(provider)
        sc = StorageClient("hook", provider, lambda *a: None)
        if expect is None:
            assert hook() is None  # nothing held, nothing to expire
        else:
            assert hook == expect
            assert sc._routing_invalidate == expect
        sc.close()


# -- idempotency table --------------------------------------------------------


class TestIdempotencyTable:
    def test_hedge_targets_are_idempotent(self):
        from tpu3fs.rpc.idempotency import (
            HEDGE_SAFE_MESSENGER_METHODS,
            hedge_safe,
        )

        for svc, method in HEDGE_SAFE_MESSENGER_METHODS.values():
            assert hedge_safe(svc, method)
        assert not hedge_safe("StorageSerde", "write")
        assert not hedge_safe("StorageSerde", "batchWrite")

    def test_registry_check_is_clean(self):
        import tools.check_rpc_registry as chk

        errors, _notes = chk.run_checks()
        assert errors == []
