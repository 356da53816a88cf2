"""RPC-over-TCP tests: echo, error mapping, and the storage/meta/mgmtd
cluster running over real sockets (ref tests/common/net/TestEcho.cc and the
RPC halves of the client suites)."""

from dataclasses import dataclass

import pytest

from tpu3fs.kv import MemKVEngine
from tpu3fs.meta.store import ChainAllocator, MetaStore
from tpu3fs.mgmtd.service import Mgmtd
from tpu3fs.mgmtd.types import LocalTargetState, NodeType
from tpu3fs.rpc.net import RpcClient, RpcServer, ServiceDef
from tpu3fs.rpc.services import (
    EchoReq,
    EchoRsp,
    Empty,
    MetaRpcClient,
    MgmtdRpcClient,
    RpcMessenger,
    StrReply,
    bind_core_service,
    bind_meta_service,
    bind_mgmtd_service,
    bind_storage_service,
)
from tpu3fs.storage.craq import StorageService
from tpu3fs.storage.resync import ResyncWorker
from tpu3fs.storage.target import StorageTarget
from tpu3fs.storage.types import ChunkId
from tpu3fs.client.storage_client import StorageClient
from tpu3fs.utils.result import Code, FsError


class TestTransport:
    def test_echo_and_timestamps(self):
        server = RpcServer()
        bind_core_service(server)
        server.start()
        try:
            client = RpcClient()
            rsp = client.call(server.address, 10001, 1, EchoReq("ping"), EchoRsp)
            assert rsp.text == "ping"
        finally:
            server.stop()

    def test_unknown_service_and_method(self):
        server = RpcServer()
        bind_core_service(server)
        server.start()
        try:
            client = RpcClient()
            with pytest.raises(FsError) as ei:
                client.call(server.address, 999, 1, EchoReq("x"), EchoRsp)
            assert ei.value.code == Code.RPC_SERVICE_NOT_FOUND
            with pytest.raises(FsError) as ei:
                client.call(server.address, 10001, 99, EchoReq("x"), EchoRsp)
            assert ei.value.code == Code.RPC_METHOD_NOT_FOUND
        finally:
            server.stop()

    def test_handler_error_propagates_code(self):
        from tpu3fs.utils.result import Status

        server = RpcServer()
        s = ServiceDef(50, "Boom")

        def boom(_req):
            raise FsError(Status(Code.CHUNK_NOT_FOUND, "nope"))

        s.method(1, "boom", EchoReq, EchoRsp, boom)
        server.add_service(s)
        server.start()
        try:
            client = RpcClient()
            with pytest.raises(FsError) as ei:
                client.call(server.address, 50, 1, EchoReq(""), EchoRsp)
            assert ei.value.code == Code.CHUNK_NOT_FOUND
            assert "nope" in ei.value.status.message
        finally:
            server.stop()

    def test_connect_failure(self):
        client = RpcClient(connect_timeout=0.2)
        with pytest.raises(FsError) as ei:
            client.call(("127.0.0.1", 1), 1, 1, EchoReq(""), EchoRsp)
        assert ei.value.code == Code.RPC_CONNECT_FAILED


@pytest.fixture
def rpc_cluster():
    """mgmtd + 3 storage nodes + meta, all talking over real TCP sockets."""
    kv = MemKVEngine()
    mgmtd = Mgmtd(1, kv)
    mgmtd.extend_lease()
    mgmtd_server = RpcServer()
    bind_mgmtd_service(mgmtd_server, mgmtd)
    mgmtd_server.start()
    servers = [mgmtd_server]
    services = {}
    chain_id = 900_001
    target_ids = [1000, 1001, 1002]
    node_ids = [10, 11, 12]
    shared_client = RpcClient()
    for node_id, target_id in zip(node_ids, target_ids):
        mcli = MgmtdRpcClient(mgmtd_server.address, shared_client)
        svc = StorageService(node_id, mcli.refresh_routing)
        svc.set_messenger(RpcMessenger(mcli.refresh_routing, shared_client))
        svc.add_target(StorageTarget(target_id, chain_id, chunk_size=4096))
        server = RpcServer()
        bind_storage_service(server, svc)
        server.start()
        mgmtd.register_node(node_id, NodeType.STORAGE,
                            host=server.host, port=server.port)
        mgmtd.create_target(target_id, node_id=node_id)
        services[node_id] = svc
        servers.append(server)
    mgmtd.upload_chain(chain_id, target_ids)
    mgmtd.upload_chain_table(1, [chain_id])
    for i, node_id in enumerate(node_ids):
        mgmtd.heartbeat(node_id, 1, {target_ids[i]: LocalTargetState.UPTODATE})
    meta = MetaStore(kv, ChainAllocator(1, [chain_id]), default_chunk_size=4096)
    meta_server = RpcServer()
    bind_meta_service(meta_server, meta)
    bind_core_service(meta_server)
    meta_server.start()
    servers.append(meta_server)
    yield {
        "mgmtd": mgmtd,
        "mgmtd_addr": mgmtd_server.address,
        "meta_addr": meta_server.address,
        "services": services,
        "chain_id": chain_id,
        "client": shared_client,
    }
    for s in servers:
        s.stop()


class TestRpcCluster:
    def test_chain_write_read_over_sockets(self, rpc_cluster):
        mcli = MgmtdRpcClient(rpc_cluster["mgmtd_addr"], rpc_cluster["client"])
        messenger = RpcMessenger(mcli.refresh_routing, rpc_cluster["client"])
        sc = StorageClient("c1", mcli.refresh_routing, messenger)
        chain = rpc_cluster["chain_id"]
        data = b"over-the-wire" * 100
        reply = sc.write_chunk(chain, ChunkId(1, 0), 0, data, chunk_size=4096)
        assert reply.ok and reply.commit_ver == 1
        got = sc.read_chunk(chain, ChunkId(1, 0))
        assert got.ok and got.data == data
        # every replica converged (forwarding really crossed sockets)
        for svc in rpc_cluster["services"].values():
            for t in svc.targets():
                assert t.engine.read(ChunkId(1, 0)) == data

    def test_resync_over_sockets(self, rpc_cluster):
        mcli = MgmtdRpcClient(rpc_cluster["mgmtd_addr"], rpc_cluster["client"])
        messenger = RpcMessenger(mcli.refresh_routing, rpc_cluster["client"])
        sc = StorageClient("c2", mcli.refresh_routing, messenger)
        chain = rpc_cluster["chain_id"]
        sc.write_chunk(chain, ChunkId(2, 0), 0, b"resync-me", chunk_size=4096)
        # clear the tail replica behind the cluster's back, then resync
        svc_tail = rpc_cluster["services"][12]
        svc_tail.target(1002).engine.remove(ChunkId(2, 0))
        mgmtd = rpc_cluster["mgmtd"]
        # drive the tail into SYNCING through the real protocol: report the
        # target offline, let the chain updater demote it, then report it
        # back online (WAITING -> SYNCING)
        from tpu3fs.mgmtd.types import PublicTargetState as PS

        mgmtd.heartbeat(12, 2, {1002: LocalTargetState.OFFLINE})
        mgmtd.update_chains()
        mgmtd.heartbeat(12, 3, {1002: LocalTargetState.ONLINE})
        mgmtd.update_chains()
        ri = mcli.refresh_routing()
        assert ri.chains[chain].targets[-1].public_state == PS.SYNCING
        # the syncing target's PREDECESSOR in the writer chain drives resync
        pred_svc = rpc_cluster["services"][11]
        moved = ResyncWorker(pred_svc, messenger).run_once()
        assert moved == 1
        assert svc_tail.target(1002).engine.read(ChunkId(2, 0)) == b"resync-me"

    def test_meta_over_sockets(self, rpc_cluster):
        meta = MetaRpcClient([rpc_cluster["meta_addr"]],
                             rpc_cluster["client"], client_id="mc1")
        meta.mkdirs("/a/b", recursive=True)
        rsp = meta.create("/a/b/f.txt", flags=2)
        assert rsp.session_id
        inode = meta.close(rsp.inode.id, rsp.session_id, length_hint=123)
        assert inode.length == 123
        assert meta.stat("/a/b/f.txt").length == 123
        assert [e.name for e in meta.list_dir("/a/b")] == ["f.txt"]
        meta.rename("/a/b/f.txt", "/a/g.txt")
        assert meta.get_real_path("/a/g.txt") == "/a/g.txt"
        with pytest.raises(FsError) as ei:
            meta.stat("/a/b/f.txt")
        assert ei.value.code == Code.META_NOT_FOUND
        fs = meta.stat_fs()
        assert fs.files == 1

    def test_core_config_render_over_sockets(self, rpc_cluster):
        client = rpc_cluster["client"]
        rsp = client.call(rpc_cluster["meta_addr"], 10001, 2, Empty(), StrReply)
        assert isinstance(rsp.value, str)

    def test_batched_io_over_sockets(self, rpc_cluster):
        """BatchRead/BatchWrite serde round-trips: many ops, one request."""
        from tpu3fs.client.storage_client import ReadReq

        mcli = MgmtdRpcClient(rpc_cluster["mgmtd_addr"], rpc_cluster["client"])
        messenger = RpcMessenger(mcli.refresh_routing, rpc_cluster["client"])
        sc = StorageClient("cb", mcli.refresh_routing, messenger)
        chain = rpc_cluster["chain_id"]
        writes = [
            (chain, ChunkId(7, i), 0, bytes([i]) * 500) for i in range(6)
        ]
        replies = sc.batch_write(writes, chunk_size=4096)
        assert all(r.ok for r in replies)
        got = sc.batch_read([ReadReq(chain, ChunkId(7, i), 0, -1)
                             for i in range(6)])
        for i, r in enumerate(got):
            assert r.ok and r.data == bytes([i]) * 500


class TestEcOverSockets:
    def test_stripe_write_read_rebuild_over_sockets(self):
        """EC chains work across the real TCP transport: ShardWriteReq and
        the batched shard install serde-roundtrip, and the rebuild worker
        drives remote reads/writes through sockets."""
        from tpu3fs.rpc.services import MgmtdAdminRpcClient, bind_mgmtd_admin

        kv = MemKVEngine()
        mgmtd = Mgmtd(1, kv)
        mgmtd.extend_lease()
        mgmtd_server = RpcServer()
        svc_def = bind_mgmtd_service(mgmtd_server, mgmtd)
        bind_mgmtd_admin(svc_def, mgmtd)
        mgmtd_server.start()
        servers = [mgmtd_server]
        services = {}
        chain_id = 900_001
        k, m = 3, 1
        chunk = 1 << 14
        from tpu3fs.ops.stripe import shard_size_of

        S = shard_size_of(chunk, k)
        shared = RpcClient()
        try:
            target_ids = [2000, 2001, 2002, 2003]
            node_ids = [20, 21, 22, 23]
            # EC chain creation goes through the ADMIN RPC surface — the
            # same path an operator's admin_cli takes against a live
            # cluster, not the in-process mgmtd object
            admin = MgmtdAdminRpcClient(mgmtd_server.address, shared)
            for node_id, target_id in zip(node_ids, target_ids):
                mcli = MgmtdRpcClient(mgmtd_server.address, shared)
                svc = StorageService(node_id, mcli.refresh_routing)
                svc.set_messenger(RpcMessenger(mcli.refresh_routing, shared))
                svc.add_target(StorageTarget(target_id, chain_id, chunk_size=S))
                server = RpcServer()
                bind_storage_service(server, svc)
                server.start()
                mgmtd.register_node(node_id, NodeType.STORAGE,
                                    host=server.host, port=server.port)
                admin.create_target(target_id, node_id=node_id)
                services[node_id] = svc
                servers.append(server)
            admin.upload_chain(chain_id, target_ids, ec_k=k, ec_m=m)
            for i, node_id in enumerate(node_ids):
                mgmtd.heartbeat(node_id, 1,
                                {target_ids[i]: LocalTargetState.UPTODATE})
            mcli = MgmtdRpcClient(mgmtd_server.address, shared)
            messenger = RpcMessenger(mcli.refresh_routing, shared)
            sc = StorageClient("ec1", mcli.refresh_routing, messenger)
            import numpy as np

            rng = np.random.default_rng(0)
            items = [(ChunkId(9, i),
                      rng.integers(0, 256, chunk, dtype=np.uint8).tobytes())
                     for i in range(3)]
            replies = sc.write_stripes(chain_id, items, chunk_size=chunk)
            assert all(r.ok for r in replies)
            for cid, data in items:
                got = sc.read_stripe(chain_id, cid, 0, chunk, chunk_size=chunk)
                assert got.ok and got.data == data
            # degraded read across sockets: wipe shard 2's engine
            victim = services[22]
            orig = victim.target(2002).engine.read(ChunkId(9, 0))
            from tpu3fs.storage.engine import MemChunkEngine

            victim.target(2002).engine = MemChunkEngine()
            got = sc.read_stripe(chain_id, ChunkId(9, 0), 0, chunk,
                                 chunk_size=chunk)
            assert got.ok and got.data == items[0][1]
            # rebuild the wiped target through the socket messenger
            from tpu3fs.mgmtd.types import PublicTargetState as PS
            from tpu3fs.storage.ec_resync import EcResyncWorker

            mgmtd.heartbeat(21, 2, {2001: LocalTargetState.UPTODATE})
            # force the wiped target into SYNCING via the real protocol
            mgmtd.heartbeat(22, 2, {2002: LocalTargetState.OFFLINE})
            mgmtd.tick()
            mgmtd.heartbeat(22, 3, {2002: LocalTargetState.ONLINE})
            mgmtd.tick()
            chain_now = mcli.refresh_routing().chains[chain_id]
            t_state = next(t.public_state for t in chain_now.targets
                           if t.target_id == 2002)
            assert t_state == PS.SYNCING
            coordinator = services[20]
            moved = EcResyncWorker(
                coordinator, RpcMessenger(mcli.refresh_routing, shared)
            ).run_once()
            assert moved >= 3
            assert victim.target(2002).engine.read(ChunkId(9, 0)) == orig
        finally:
            for s in servers:
                s.stop()

    def test_batch_set_attr_over_sockets(self, rpc_cluster):
        meta = MetaRpcClient([rpc_cluster["meta_addr"]],
                             rpc_cluster["client"], client_id="mc2")
        meta.mkdirs("/touch", recursive=True)
        ids = []
        for i in range(3):
            rsp = meta.create(f"/touch/f{i}", flags=2)
            meta.close(rsp.inode.id, rsp.session_id, length_hint=1)
            ids.append(rsp.inode.id)
        # by path, with one failure entry (MetaStore parity)
        out = meta.batch_set_attr(["/touch/f0", "/touch/nope"],
                                  mtime=1111.0)
        assert out[0].id == ids[0]
        assert isinstance(out[1], FsError)
        assert out[1].code == Code.META_NOT_FOUND
        assert meta.stat("/touch/f0").mtime == 1111.0
        # walk-free by inode id
        out = meta.batch_set_attr(inode_ids=ids, atime=2222.0)
        assert [o.id for o in out] == ids
        assert meta.stat("/touch/f2").atime == 2222.0


# -- batchStatByPath (MetaSerde 30): the batched stat's RPC twin ------------

def _call_spy(mc):
    """Record (service id, method id) of every RPC the client's transport
    sends; returns the list."""
    seen = []
    real = mc._client.call

    def spy(addr, service_id, method_id, req, rsp_type, *a, **kw):
        seen.append((service_id, method_id))
        return real(addr, service_id, method_id, req, rsp_type, *a, **kw)

    mc._client.call = spy
    return seen


def _stat_or_none(mc, path):
    try:
        return mc.stat(path)
    except FsError:
        return None


class TestBatchStatByPathOverRpc:
    """MetaRpcClient.batch_stat_by_path answers what per-path stat answers
    (None where stat raises missing/forbidden), in request order, in one
    RPC a BATCH_STAT_PATHS_MAX paths."""

    @pytest.fixture
    def served(self):
        meta = MetaStore(MemKVEngine(), ChainAllocator(1, [101, 102]))
        server = RpcServer()
        bind_meta_service(server, meta)
        server.start()
        mc = MetaRpcClient([server.address], client_id="bs")
        mc.mkdirs("/d/sub", recursive=True)
        for i in range(3):
            rsp = mc.create(f"/d/f{i}", flags=2)
            mc.close(rsp.inode.id, rsp.session_id, length_hint=10 + i)
        mc.symlink("/d/ln", "/d/f1")
        mc.symlink("/d/dangling", "/d/nowhere")
        yield mc
        server.stop()

    CASES = {
        "present": ["/d/f0", "/d/f1", "/d/f2"],
        "missing": ["/d/nope", "/nodir/f", "/d/f0/under_a_file"],
        "directory": ["/d", "/d/sub", "/"],
        "symlink": ["/d/ln", "/d/dangling"],
        "duplicates": ["/d/f1", "/d/f1", "/d/nope", "/d/f1", "/d/nope"],
        "mixed_in_request_order": ["/d/f2", "/d/nope", "/d", "/d/ln",
                                   "/d/f0", "/nodir/x", "/d/f2"],
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_answers_what_stat_answers(self, served, case):
        paths = self.CASES[case]
        want = [_stat_or_none(served, p) for p in paths]
        seen = _call_spy(served)
        got = served.batch_stat_by_path(paths)
        assert got == want
        # one round trip, and it is not a stat
        assert seen == [(4, 30)]

    def test_symlink_is_followed_like_stat(self, served):
        ln, dangling = served.batch_stat_by_path(["/d/ln", "/d/dangling"])
        assert ln.id == served.stat("/d/f1").id and ln.length == 11
        assert dangling is None

    def test_empty_list_makes_no_call(self, served):
        seen = _call_spy(served)
        assert served.batch_stat_by_path([]) == []
        assert seen == []

    def test_long_list_splits_at_the_client_constant(self, served):
        from tpu3fs.rpc.services import BATCH_STAT_PATHS_MAX

        assert BATCH_STAT_PATHS_MAX > 64  # longer than a store transaction
        n = 2 * BATCH_STAT_PATHS_MAX + 7
        paths = [f"/d/f{i % 3}" if i % 5 else f"/d/miss{i}"
                 for i in range(n)]
        want = {p: _stat_or_none(served, p) for p in set(paths)}
        seen = _call_spy(served)
        got = served.batch_stat_by_path(paths)
        assert got == [want[p] for p in paths]
        assert seen == [(4, 30)] * 3

    def test_list_at_the_constant_is_one_call(self, served):
        from tpu3fs.rpc.services import BATCH_STAT_PATHS_MAX

        seen = _call_spy(served)
        got = served.batch_stat_by_path(["/d/f0"] * BATCH_STAT_PATHS_MAX)
        assert len(got) == BATCH_STAT_PATHS_MAX and None not in got
        assert seen == [(4, 30)]

    def test_accepts_any_iterable_of_paths(self, served):
        got = served.batch_stat_by_path(p for p in ("/d/f0", "/d/nope"))
        assert got[0].id == served.stat("/d/f0").id and got[1] is None

    def test_raises_when_no_meta_server_answers(self, served):
        # the loop of stat calls swallowed this as misses; an unreachable
        # server is an error, not N misses
        dead = MetaRpcClient([("127.0.0.1", 1)])
        with pytest.raises(FsError) as ei:
            dead.batch_stat_by_path(["/d/f0", "/d/f1"])
        assert ei.value.code == Code.RPC_CONNECT_FAILED

    def test_fails_over_to_the_next_server(self, served):
        live = served._addrs[0]
        mc = MetaRpcClient([("127.0.0.1", 1), live])
        got = mc.batch_stat_by_path(["/d/f0", "/d/nope"])
        assert got[0].id == served.stat("/d/f0").id and got[1] is None

    def test_op_span_carries_the_path_count(self, served, tmp_path):
        from tpu3fs.analytics import spans
        from tpu3fs.analytics.trace import read_records

        old = spans._TRACER
        tracer = spans._TRACER = spans.Tracer()
        try:
            tracer.configure(service="c", node=0, directory=str(tmp_path),
                             sample_rate=1.0)
            served.batch_stat_by_path(["/d/f0", "/d/nope", "/d/f1"])
            tracer.flush()
        finally:
            spans._TRACER = old
        ops = [(r["op"], r["nbytes"]) for p in tracer.span_paths
               for r in read_records(p) if not r["stage"]]
        assert ("meta.batchStatByPath", 3) in ops
        assert not any(op == "meta.stat" for op, _ in ops)


class TestBatchStatByPathAuth:
    """Auth mode: the user is the token's; a path that user may not walk
    comes back as nothing, exactly where stat raises NO_PERMISSION."""

    @pytest.fixture
    def authed(self):
        from tpu3fs.core.user import UserStore

        engine = MemKVEngine()
        users = UserStore(engine)
        meta = MetaStore(engine, ChainAllocator(1, [101, 102]))
        server = RpcServer()
        bind_meta_service(server, meta, user_store=users, acl_ttl_s=0.0)
        server.start()
        meta.mkdirs("/pub", perm=0o777)
        meta.mkdirs("/private", perm=0o700)  # root-owned, no group/other
        meta.create("/pub/f")
        meta.create("/private/f")
        yield server, users
        server.stop()

    def test_forbidden_path_is_nothing(self, authed):
        server, users = authed
        alice = users.add_user(1000, "alice")
        mc = MetaRpcClient([server.address], token=alice.token)
        with pytest.raises(FsError) as ei:
            mc.stat("/private/f")
        assert ei.value.code == Code.META_NO_PERMISSION
        got = mc.batch_stat_by_path(["/pub/f", "/private/f", "/pub/nope"])
        assert got[0].id == mc.stat("/pub/f").id
        assert got[1] is None and got[2] is None

    def test_root_user_sees_it(self, authed):
        server, users = authed
        boss = users.add_user(9999, "boss", root=True)
        mc = MetaRpcClient([server.address], token=boss.token)
        got = mc.batch_stat_by_path(["/private/f", "/pub/f"])
        assert None not in got

    def test_bad_token_is_an_error_not_misses(self, authed):
        server, _ = authed
        for mc in (MetaRpcClient([server.address]),
                   MetaRpcClient([server.address], token="ffff" * 8)):
            with pytest.raises(FsError) as ei:
                mc.batch_stat_by_path(["/pub/f"])
            assert ei.value.code == Code.META_NO_PERMISSION
