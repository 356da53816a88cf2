"""Admin CLI against a LIVE socket cluster (operator mode).

The round-2 gap: EC chains could only be created by touching the in-process
mgmtd object. Now the admin_cli drives a running cluster over the admin RPC
surface — create-target / upload-chain --ec-k/--ec-m / upload-chain-table —
the way the reference's admin_cli drives mgmtd (src/client/cli/admin/,
src/client/mgmtd/MgmtdClient.cc ForAdmin role).
"""

import numpy as np
import pytest

from tpu3fs.cli import AdminCli, RpcFabricView
from tpu3fs.kv import MemKVEngine
from tpu3fs.mgmtd.service import Mgmtd
from tpu3fs.mgmtd.types import LocalTargetState, NodeType
from tpu3fs.ops.stripe import shard_size_of
from tpu3fs.rpc.net import RpcClient, RpcServer
from tpu3fs.rpc.services import (
    RpcMessenger,
    bind_mgmtd_admin,
    bind_mgmtd_service,
    bind_storage_service,
)
from tpu3fs.storage.craq import StorageService
from tpu3fs.storage.target import StorageTarget
from tpu3fs.storage.types import ChunkId


@pytest.fixture
def socket_cluster():
    """mgmtd (+admin surface) + 4 storage servers over real sockets, with
    NO chains yet — topology comes from the CLI under test."""
    kv = MemKVEngine()
    mgmtd = Mgmtd(1, kv)
    mgmtd.extend_lease()
    mgmtd_server = RpcServer()
    svc_def = bind_mgmtd_service(mgmtd_server, mgmtd)
    bind_mgmtd_admin(svc_def, mgmtd)
    mgmtd_server.start()
    servers = [mgmtd_server]
    services = {}
    shared = RpcClient()
    node_ids = [20, 21, 22, 23]
    chunk = 1 << 14
    S = shard_size_of(chunk, 3)
    for node_id in node_ids:
        from tpu3fs.rpc.services import MgmtdRpcClient

        mcli = MgmtdRpcClient(mgmtd_server.address, shared)
        svc = StorageService(node_id, mcli.refresh_routing)
        svc.set_messenger(RpcMessenger(mcli.refresh_routing, shared))
        server = RpcServer()
        bind_storage_service(server, svc)
        server.start()
        mgmtd.register_node(node_id, NodeType.STORAGE,
                            host=server.host, port=server.port)
        services[node_id] = svc
        servers.append(server)
    yield {
        "mgmtd": mgmtd,
        "mgmtd_addr": mgmtd_server.address,
        "servers": servers,
        "services": services,
        "node_ids": node_ids,
        "chunk": chunk,
        "shard": S,
    }
    for s in servers:
        s.stop()


class TestAdminCliOverSockets:
    def test_view_storage_clients_get_unique_wire_ids(self, socket_cluster):
        """Two storage_client() instances from one view must NOT share a
        wire client id: the server's exactly-once channel table is keyed
        (client id, channel, seq), and a second instance restarting its
        channel seqs under the same id has its writes silently deduped
        as replays (found by the live dataload drive — a fresh client's
        state-file write 'succeeded' without landing)."""
        view = RpcFabricView(socket_cluster["mgmtd_addr"],
                             client_id="dup")
        a = view.storage_client()
        b = view.storage_client()
        assert a.client_id != b.client_id
        # and ids from a SECOND process-like view differ too
        view2 = RpcFabricView(socket_cluster["mgmtd_addr"],
                              client_id="dup")
        assert view2.storage_client().client_id not in (
            a.client_id, b.client_id)
        for c in (a, b):
            c.close()

    def test_ec_chain_created_via_cli_serves_stripes(self, socket_cluster):
        c = socket_cluster
        view = RpcFabricView(c["mgmtd_addr"])
        cli = AdminCli(view)
        chain_id = 910_001
        # targets must exist server-side before the chain references them
        tids = [3000, 3001, 3002, 3003]
        for node_id, tid in zip(c["node_ids"], tids):
            out = cli.run(f"create-target --target-id {tid} "
                          f"--node-id {node_id}")
            assert "created" in out
            c["services"][node_id].add_target(
                StorageTarget(tid, chain_id, chunk_size=c["shard"]))
        out = cli.run(
            f"upload-chain --chain-id {chain_id} "
            f"--targets {','.join(map(str, tids))} --ec-k 3 --ec-m 1")
        assert "EC(3,1)" in out
        out = cli.run(f"upload-chain-table --table-id 1 --chains {chain_id}")
        assert "uploaded" in out
        for i, node_id in enumerate(c["node_ids"]):
            c["mgmtd"].heartbeat(node_id, 1,
                                 {tids[i]: LocalTargetState.UPTODATE})
        chain = view.routing().chains[chain_id]
        assert chain.is_ec and chain.ec_k == 3 and chain.ec_m == 1
        # the CLI-created chain is a real serving path: stripes round-trip
        sc = view.storage_client()
        rng = np.random.default_rng(1)
        data = rng.integers(0, 256, c["chunk"], dtype=np.uint8).tobytes()
        replies = sc.write_stripes(
            chain_id, [(ChunkId(77, 0), data)], chunk_size=c["chunk"])
        assert all(r.ok for r in replies)
        got = sc.read_stripe(chain_id, ChunkId(77, 0), 0, c["chunk"],
                             chunk_size=c["chunk"])
        assert got.ok and got.data == data

    def test_cli_list_chains_shows_cli_created_cr_chain(self, socket_cluster):
        c = socket_cluster
        cli = AdminCli(RpcFabricView(c["mgmtd_addr"]))
        chain_id = 910_002
        tids = [3100, 3101]
        for node_id, tid in zip(c["node_ids"][:2], tids):
            cli.run(f"create-target --target-id {tid} --node-id {node_id}")
            c["services"][node_id].add_target(
                StorageTarget(tid, chain_id, chunk_size=4096))
        out = cli.run(f"upload-chain --chain-id {chain_id} "
                      f"--targets {tids[0]},{tids[1]}")
        assert "CR" in out
        assert str(chain_id) in cli.run("list-chains")

    def test_solver_emits_ec_commands_cli_can_execute(self, socket_cluster):
        """gen_chain_table_commands(ec_k, ec_m) output replays through the
        CLI against the live cluster (the gen_chain_table.py flow)."""
        from tpu3fs.placement import (
            PlacementProblem,
            gen_chain_table_commands,
            solve_placement,
        )

        c = socket_cluster
        cli = AdminCli(RpcFabricView(c["mgmtd_addr"]))
        p = PlacementProblem(num_nodes=4, group_size=4, targets_per_node=1,
                             chain_table_type="EC")
        M = solve_placement(p, steps=5)
        cmds = gen_chain_table_commands(
            M, first_target_id=3200, first_chain_id=920_001,
            node_ids=c["node_ids"], ec_k=3, ec_m=1)
        assert any("--ec-k 3 --ec-m 1" in x for x in cmds)
        for cmd in cmds:
            out = cli.run(cmd)
            assert "error" not in out, (cmd, out)
        chain = cli.fab.routing().chains[920_001]
        assert chain.is_ec and chain.ec_k == 3


# -- the library client's routing snapshot ------------------------------------


class _Spy:
    """Messenger proxy: records (node, method, reply code) of single-op
    calls and passes everything else (pipelined batches, health, rings)
    through to the real RpcMessenger."""

    def __init__(self, inner):
        self._inner = inner
        self.calls = []

    def __call__(self, node_id, method, payload):
        reply = self._inner(node_id, method, payload)
        self.calls.append((node_id, method, getattr(reply, "code", None)))
        return reply

    def __getattr__(self, name):
        return getattr(self._inner, name)


CR_CHUNK = 4096


def _make_cr_chain(c, admin, chain_id, first_tid, nodes):
    """A CR chain over ``nodes`` made through ``admin`` (an RPC admin
    client, or the in-process Mgmtd for a mutation BEHIND every client's
    back), every target reported up to date."""
    tids = [first_tid + i for i in range(len(nodes))]
    for node_id, tid in zip(nodes, tids):
        admin.create_target(tid, node_id=node_id)
        c["services"][node_id].add_target(
            StorageTarget(tid, chain_id, chunk_size=CR_CHUNK))
    admin.upload_chain(chain_id, tids)
    c["hb"] = c.get("hb", 0) + 1
    for node_id, tid in zip(nodes, tids):
        c["mgmtd"].heartbeat(node_id, c["hb"],
                             {tid: LocalTargetState.UPTODATE})
    return tids


@pytest.fixture
def cr_view(socket_cluster):
    """A view over two CR-3 chains, its snapshot held and fresh."""
    c = socket_cluster
    view = RpcFabricView(c["mgmtd_addr"], client_id="snap")
    c["tids"] = {
        930_001: _make_cr_chain(c, view.mgmtd, 930_001, 4000,
                                c["node_ids"][:3]),
        930_002: _make_cr_chain(c, view.mgmtd, 930_002, 4100,
                                c["node_ids"][1:]),
    }
    view.mgmtd.upload_chain_table(1, [930_001, 930_002])
    assert len(view.routing().chains[930_001].serving_targets()) == 3
    return view


def _file(inode_id=77):
    from tpu3fs.meta.types import Acl, Inode, InodeType, Layout

    return Inode(inode_id, InodeType.FILE, Acl(),
                 layout=Layout(chains=[930_001, 930_002],
                               chunk_size=CR_CHUNK))


def _polls(view):
    return view.mgmtd.routing_polls._value


def _count_get_routing(mgmtd):
    """Count getRoutingInfo calls as mgmtd serves them."""
    seen = []
    real = mgmtd.get_routing_info

    def counted(known_version=-1):
        seen.append(known_version)
        return real(known_version)

    mgmtd.get_routing_info = counted
    return seen


class TestLibraryClientRoutingSnapshot:
    def test_factories_hand_out_a_provider_with_the_invalidation_hook(
            self, cr_view):
        sc = cr_view.storage_client()
        assert sc._routing == cr_view.mgmtd.cached_routing
        assert sc._routing_invalidate == cr_view.mgmtd.invalidate_routing
        assert (cr_view._messenger._routing_invalidate
                == cr_view.mgmtd.invalidate_routing)
        sc.close()

    @pytest.mark.parametrize("n", [8, 48])
    def test_n_writes_and_n_reads_poll_a_bounded_number_of_times(
            self, cr_view, n):
        fio = cr_view.file_client()
        inode = _file()
        rng = np.random.default_rng(n)
        blobs = [rng.integers(0, 256, CR_CHUNK, dtype=np.uint8).tobytes()
                 for _ in range(n)]
        polls, cached = _polls(cr_view), cr_view.mgmtd.routing_cached._value
        for i, blob in enumerate(blobs):
            assert fio.write(inode, i * CR_CHUNK, blob) == CR_CHUNK
        for i, blob in enumerate(blobs):
            assert fio.read(inode, i * CR_CHUNK, CR_CHUNK) == blob
        # not one an op: at most the one poll that installs what the
        # fixture's last admin call invalidated, whatever n is
        assert _polls(cr_view) - polls <= 1
        assert cr_view.mgmtd.routing_cached._value - cached >= 2 * n
        fio.close()

    def test_batch_read_of_64_chunks_polls_nothing_once_the_snapshot_is_held(
            self, cr_view):
        fio = cr_view.file_client()
        inode = _file(78)
        rng = np.random.default_rng(64)
        data = rng.integers(0, 256, 64 * CR_CHUNK, dtype=np.uint8).tobytes()
        assert fio.write(inode, 0, data) == len(data)
        polls = _polls(cr_view)
        got = fio.batch_read_files(
            [(inode, i * CR_CHUNK, CR_CHUNK) for i in range(64)])
        assert b"".join(got) == data
        assert _polls(cr_view) == polls
        fio.close()

    def test_stale_chain_version_is_refused_once_then_the_write_lands(
            self, socket_cluster, cr_view):
        c = socket_cluster
        sc = cr_view.storage_client()
        spy = sc._messenger = _Spy(sc._messenger)
        ck = ChunkId(79, 0)
        assert sc.write_chunk(930_001, ck, 0, b"before", chunk_size=CR_CHUNK).ok
        held = cr_view.mgmtd.cached_routing().chains[930_001].chain_version
        # demote the tail BEHIND the client: the chain's version moves on
        tail_node, tail = c["node_ids"][2], c["tids"][930_001][2]
        c["mgmtd"].heartbeat(tail_node, 50, {tail: LocalTargetState.OFFLINE})
        assert c["mgmtd"].update_chains() == 1
        spy.calls.clear()
        polls = _polls(cr_view)
        reply = sc.write_chunk(930_001, ck, 0, b"after!", chunk_size=CR_CHUNK)
        assert reply.ok
        from tpu3fs.utils.result import Code

        assert [code for _, m, code in spy.calls if m == "write"] == [
            Code.CHAIN_VERSION_MISMATCH, Code.OK]
        assert _polls(cr_view) - polls == 1
        assert (cr_view.mgmtd.cached_routing().chains[930_001].chain_version
                > held)
        got = sc.read_chunk(930_001, ck)
        assert got.ok and got.data == b"after!"
        sc.close()

    def test_read_of_an_offlined_target_moves_to_a_serving_replica(
            self, socket_cluster, cr_view):
        from tpu3fs.client.storage_client import TargetSelectionMode
        from tpu3fs.utils.result import Code

        c = socket_cluster
        sc = cr_view.storage_client(selection=TargetSelectionMode.HEAD)
        spy = sc._messenger = _Spy(sc._messenger)
        ck = ChunkId(80, 0)
        payload = b"replicated" * 40
        assert sc.write_chunk(930_001, ck, 0, payload, chunk_size=CR_CHUNK).ok
        head_node, head = c["node_ids"][0], c["tids"][930_001][0]
        c["mgmtd"].heartbeat(head_node, 51, {head: LocalTargetState.OFFLINE})
        assert c["mgmtd"].update_chains() == 1
        spy.calls.clear()
        got = sc.read_chunk(930_001, ck)
        assert got.ok and got.data == payload
        reads = [(node, code) for node, m, code in spy.calls if m == "read"]
        # the held snapshot still names the old head: its server refuses,
        # the walk goes on to a replica that serves
        assert reads[0] == (head_node, Code.TARGET_OFFLINE)
        assert reads[-1][1] == Code.OK and reads[-1][0] != head_node
        sc.close()

    def test_a_chain_made_after_the_snapshot_is_found_by_one_poll(
            self, socket_cluster, cr_view):
        c = socket_cluster
        sc = cr_view.storage_client()
        assert sc.write_chunk(930_001, ChunkId(81, 0), 0, b"x",
                              chunk_size=CR_CHUNK).ok  # snapshot held
        # made in mgmtd itself: nothing tells this client
        _make_cr_chain(c, c["mgmtd"], 930_003, 4200, c["node_ids"][:2])
        assert 930_003 not in cr_view.mgmtd.cached_routing().chains
        polls = _polls(cr_view)
        assert sc.write_chunk(930_003, ChunkId(81, 1), 0, b"new chain",
                              chunk_size=CR_CHUNK).ok
        assert _polls(cr_view) - polls == 1
        got = sc.batch_read([_read_req(930_003, ChunkId(81, 1))])
        assert got[0].ok and bytes(got[0].data) == b"new chain"
        sc.close()

    def test_a_node_registered_after_the_snapshot_is_found_by_one_poll(
            self, socket_cluster, cr_view):
        c = socket_cluster
        cr_view.mgmtd.cached_routing()  # snapshot held
        svc = StorageService(30, cr_view.mgmtd.refresh_routing)
        server = RpcServer()
        bind_storage_service(server, svc)
        server.start()
        try:
            c["mgmtd"].register_node(30, NodeType.STORAGE,
                                     host=server.host, port=server.port)
            assert 30 not in cr_view.mgmtd.cached_routing().nodes
            polls = _polls(cr_view)
            assert cr_view.send(30, "space_info", None).chunk_count == 0
            assert _polls(cr_view) - polls == 1
        finally:
            server.stop()

    def test_a_node_that_came_back_on_another_port_is_found_by_one_poll(
            self, socket_cluster, cr_view):
        """Nobody listens at the held address: the connect failure expires
        the snapshot, and the call goes to the address mgmtd now names."""
        c = socket_cluster
        node_id = c["node_ids"][3]
        assert cr_view.send(node_id, "space_info", None) is not None
        old = cr_view._messenger._resolved[node_id]
        svc = StorageService(node_id, cr_view.mgmtd.refresh_routing)
        server = RpcServer()
        bind_storage_service(server, svc)
        server.start()
        try:
            c["servers"][1 + 3].stop()
            c["mgmtd"].register_node(node_id, NodeType.STORAGE,
                                     host=server.host, port=server.port)
            cr_view._rpc.close()  # no pooled connection to the old port
            polls = _polls(cr_view)
            assert cr_view.send(node_id, "space_info", None) is not None
            assert _polls(cr_view) - polls == 1
            assert cr_view._messenger._resolved[node_id] == (
                server.host, server.port) != old
        finally:
            server.stop()

    @pytest.mark.parametrize("op", ["read", "write", "batch_read",
                                    "batch_write", "send"])
    def test_the_truly_unknown_still_fails_after_exactly_one_poll(
            self, cr_view, op):
        from tpu3fs.utils.result import Code, FsError

        sc = cr_view.storage_client()
        cr_view.mgmtd.cached_routing()  # snapshot held
        polls = _polls(cr_view)
        if op == "read":
            codes = [sc.read_chunk(424_242, ChunkId(1, 0)).code]
        elif op == "write":
            codes = [sc.write_chunk(424_242, ChunkId(1, 0), 0, b"x").code]
        elif op == "batch_read":
            codes = [r.code for r in sc.batch_read(
                [_read_req(424_242, ChunkId(1, i)) for i in range(8)])]
        elif op == "batch_write":
            codes = [r.code for r in sc.batch_write(
                [(424_242, ChunkId(1, i), 0, b"x") for i in range(8)])]
        else:
            with pytest.raises(FsError) as ei:
                cr_view.send(4242, "space_info", None)
            assert ei.value.code == Code.RPC_CONNECT_FAILED
            codes = []
        assert all(code == Code.CHAIN_NOT_FOUND for code in codes)
        assert _polls(cr_view) - polls == 1
        sc.close()

    def test_admin_mutation_through_the_view_invalidates_its_snapshot(
            self, socket_cluster, cr_view):
        sc = cr_view.storage_client()
        sc.read_chunk(930_001, ChunkId(82, 0))
        polls = _polls(cr_view)
        sc.read_chunk(930_001, ChunkId(82, 0))
        assert _polls(cr_view) == polls  # held
        cr_view.mgmtd.create_target(4300, node_id=socket_cluster["node_ids"][0])
        sc.read_chunk(930_001, ChunkId(82, 0))
        assert _polls(cr_view) - polls == 1
        assert 4300 in cr_view.mgmtd.cached_routing().targets
        sc.close()

    def test_snapshot_older_than_the_poll_interval_is_polled_again(
            self, cr_view, monkeypatch):
        import time

        from tpu3fs.rpc import services

        cr_view.mgmtd.cached_routing()
        polls = _polls(cr_view)
        cr_view.mgmtd.cached_routing()
        assert _polls(cr_view) == polls
        monkeypatch.setattr(services, "ROUTING_POLL_INTERVAL_S", 0.05)
        time.sleep(0.06)
        cr_view.mgmtd.cached_routing()
        cr_view.mgmtd.cached_routing()
        assert _polls(cr_view) - polls == 1

    @pytest.mark.parametrize("who", ["view.routing", "refresh_routing"])
    def test_the_operators_read_and_a_direct_refresh_poll_every_call(
            self, socket_cluster, cr_view, who):
        from tpu3fs.rpc.services import MgmtdRpcClient

        ask = (cr_view.routing if who == "view.routing" else MgmtdRpcClient(
            socket_cluster["mgmtd_addr"]).refresh_routing)
        ask()
        seen = _count_get_routing(socket_cluster["mgmtd"])
        for _ in range(5):
            assert 930_001 in ask().chains
        assert len(seen) == 5

    def test_what_the_operators_read_installed_serves_the_data_plane(
            self, cr_view):
        cr_view.mgmtd.invalidate_routing()
        cr_view.routing()
        polls = _polls(cr_view)
        cr_view.mgmtd.cached_routing()
        assert _polls(cr_view) == polls


def _read_req(chain_id, chunk_id):
    from tpu3fs.client.storage_client import ReadReq

    return ReadReq(chain_id, chunk_id, 0, -1)
