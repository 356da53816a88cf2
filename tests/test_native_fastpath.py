"""Native storage read fast path (native/rpc_net.cpp FpState +
tpu3fs/storage/native_fastpath.py): batchRead served end to end in C++ —
decode, chunk-engine read, encode, writev — without entering Python.

The contract under test: fast-path replies are byte-identical to the
Python dispatch's, anything ambiguous falls back (and still answers
correctly), and the registry follows target/routing state."""

import pytest

from tpu3fs.client.storage_client import ReadReq as ClientReadReq
from tpu3fs.kv.mem import MemKVEngine
from tpu3fs.mgmtd.service import Mgmtd
from tpu3fs.mgmtd.types import LocalTargetState, NodeType
from tpu3fs.rpc.native_net import NativeRpcClient, NativeRpcServer
from tpu3fs.rpc.services import (
    MgmtdRpcClient,
    RpcMessenger,
    bind_mgmtd_service,
    bind_storage_service,
)
from tpu3fs.storage import native_fastpath
from tpu3fs.storage.craq import StorageService
from tpu3fs.storage.native_fastpath import sync_read_fastpath
from tpu3fs.storage.target import StorageTarget
from tpu3fs.storage.types import ChunkId
from tpu3fs.utils.result import Code

CHUNK = 4096
CHAIN = 700_001


@pytest.fixture
def native_node(tmp_path):
    """mgmtd + ONE native-transport storage node with a native-engine
    target, plus a connected client."""
    mgmtd = Mgmtd(1, MemKVEngine())
    mgmtd.extend_lease()
    mgmtd_server = NativeRpcServer()
    bind_mgmtd_service(mgmtd_server, mgmtd)
    mgmtd_server.start()
    client = NativeRpcClient()
    mcli = MgmtdRpcClient(mgmtd_server.address, client)
    svc = StorageService(10, mcli.refresh_routing)
    svc.set_messenger(RpcMessenger(mcli.refresh_routing, client))
    target = StorageTarget(1000, CHAIN, engine="native",
                           path=str(tmp_path / "t1000"), chunk_size=CHUNK)
    svc.add_target(target)
    server = NativeRpcServer()
    bind_storage_service(server, svc)
    server.start()
    mgmtd.register_node(10, NodeType.STORAGE, host=server.host,
                        port=server.port)
    mgmtd.create_target(1000, node_id=10)
    mgmtd.upload_chain(CHAIN, [1000])
    mgmtd.upload_chain_table(1, [CHAIN])
    mgmtd.heartbeat(10, 1, {1000: LocalTargetState.UPTODATE})
    yield {
        "svc": svc,
        "server": server,
        "client": client,
        "mcli": mcli,
        "target": target,
        "mgmtd": mgmtd,
    }
    client.close()
    server.stop()
    mgmtd_server.stop()


def _client_for(env):
    from tpu3fs.client.storage_client import StorageClient

    return StorageClient(
        "fp-test", env["mcli"].refresh_routing,
        RpcMessenger(env["mcli"].refresh_routing, env["client"]))


def test_loaded_so_abi_matches_bindings():
    """Stale-.so guard: the library this process actually dlopen'd must
    report the ABI the Python bindings were written against. The loader's
    pre-dlopen probe rebuilds on mismatch, but a cached module object or
    a probe/build race could still hand out an old ABI — and a stale .so
    behind the v5 write-path bindings corrupts the callback stack, so
    this has to hold in-process, not just at probe time."""
    from tpu3fs.rpc import native_net

    try:
        lib = native_net._load_lib()
    except Exception as e:
        pytest.skip(f"native toolchain unavailable: {e!r}")
    assert lib.tpu3fs_rpc_abi_version() == native_net._ABI_VERSION


class TestNativeReadFastpath:
    def test_fastpath_hits_and_matches_python_dispatch(self, native_node):
        env = native_node
        sc = _client_for(env)
        payloads = {i: bytes([i]) * (CHUNK - i * 7) for i in range(1, 6)}
        for i, p in payloads.items():
            assert sc.write_chunk(CHAIN, ChunkId(5, i), 0, p,
                                  chunk_size=CHUNK).ok
        reqs = [ClientReadReq(CHAIN, ChunkId(5, i), 0, -1)
                for i in payloads]
        # python-dispatch golden: fastpath disabled (empty registry)
        golden = sc.batch_read(reqs)
        h0, f0 = env["server"].fastpath_stats()
        assert h0 == 0 and f0 > 0  # every batchRead fell back so far
        # enable + re-read: same answers, served natively
        assert sync_read_fastpath(env["server"], env["svc"]) == 1
        fast = sc.batch_read(reqs)
        h1, _ = env["server"].fastpath_stats()
        assert h1 >= 1
        for g, f in zip(golden, fast):
            assert (g.code, g.data, g.commit_ver, g.checksum.value,
                    g.logical_len) == (f.code, f.data, f.commit_ver,
                                       f.checksum.value, f.logical_len)
        assert fast[0].data == payloads[1]

    def test_ranged_reads_and_missing_chunks(self, native_node):
        env = native_node
        sc = _client_for(env)
        blob = bytes(range(256)) * 16  # 4096
        assert sc.write_chunk(CHAIN, ChunkId(6, 0), 0, blob,
                              chunk_size=CHUNK).ok
        sync_read_fastpath(env["server"], env["svc"])
        got = sc.batch_read([
            ClientReadReq(CHAIN, ChunkId(6, 0), 100, 50),
            ClientReadReq(CHAIN, ChunkId(6, 404), 0, -1),  # absent
        ])
        assert got[0].ok and got[0].data == blob[100:150]
        # the absent chunk surfaces exactly like the python path: the
        # client's mop-up ladder turns it into CHUNK_NOT_FOUND
        assert got[1].code == Code.CHUNK_NOT_FOUND
        hits, _ = env["server"].fastpath_stats()
        assert hits >= 1

    def test_registry_follows_target_state(self, native_node):
        env = native_node
        sc = _client_for(env)
        assert sc.write_chunk(CHAIN, ChunkId(7, 0), 0, b"x" * 100,
                              chunk_size=CHUNK).ok
        assert sync_read_fastpath(env["server"], env["svc"]) == 1
        # local offlining drops the registry entry IMMEDIATELY (the
        # offline_target contract) — no re-sync scan needed
        env["svc"].offline_target(1000)
        h_before, f_before = env["server"].fastpath_stats()
        # reads now fall back to python dispatch (which refuses: offline)
        got = sc.batch_read([ClientReadReq(CHAIN, ChunkId(7, 0), 0, -1)])
        assert not got[0].ok
        h_after, f_after = env["server"].fastpath_stats()
        assert h_after == h_before and f_after > f_before
        # and a later sync keeps it out
        assert sync_read_fastpath(env["server"], env["svc"]) == 0

    def test_mem_engine_targets_never_register(self, native_node, tmp_path):
        env = native_node
        env["svc"].add_target(StorageTarget(1001, 700_002, engine="mem",
                                            chunk_size=CHUNK))
        # only the native-engine target registers
        assert sync_read_fastpath(env["server"], env["svc"]) == 1


class TestFastpathEcShards:
    def test_ec_shard_reads_identical_via_fastpath(self, native_node,
                                                   tmp_path):
        """EC shard targets register too (target-addressed engine reads
        with the aux/logical_len tag riding the reply): fast-path replies
        must be byte-identical to the Python dispatch, including
        logical_len for short stripes."""
        import numpy as np

        env = native_node
        mgmtd = env["mgmtd"]
        # build an EC(2,1) chain across three native targets on this node
        ec_chain = 800_001
        tids = (1100, 1101, 1102)
        for tid in tids:
            env["svc"].add_target(StorageTarget(
                tid, ec_chain, engine="native",
                path=str(tmp_path / f"ec{tid}"), chunk_size=2048))
        for tid in tids:
            mgmtd.create_target(tid, node_id=10)
        mgmtd.upload_chain(ec_chain, list(tids), ec_k=2, ec_m=1)
        mgmtd.upload_chain_table(2, [ec_chain])
        mgmtd.heartbeat(10, 9, {tid: LocalTargetState.UPTODATE
                                for tid in (1000,) + tids})
        sc = _client_for(env)
        rng = np.random.default_rng(11)
        payloads = {
            0: rng.integers(0, 256, 4096, dtype=np.uint8).tobytes(),
            1: rng.integers(0, 256, 1234, dtype=np.uint8).tobytes(),  # short
        }
        for i, p in payloads.items():
            r = sc.write_stripe(ec_chain, ChunkId(9, i), p, chunk_size=4096)
            assert r.ok, r
        # golden via python dispatch (registry cleared), then fastpath
        env["server"].fastpath_sync(None, {})
        golden = {i: sc.read_stripe(ec_chain, ChunkId(9, i), 0, 4096,
                                    chunk_size=4096)
                  for i in payloads}
        n = sync_read_fastpath(env["server"], env["svc"])
        assert n >= len(tids)  # EC shard targets registered
        h0, _ = env["server"].fastpath_stats()
        fast = {i: sc.read_stripe(ec_chain, ChunkId(9, i), 0, 4096,
                                  chunk_size=4096)
                for i in payloads}
        h1, _ = env["server"].fastpath_stats()
        assert h1 > h0  # shard reads rode the C++ path
        for i in payloads:
            g, f = golden[i], fast[i]
            assert (g.code, g.data, g.logical_len) == (
                f.code, f.data, f.logical_len), i
            assert f.data[:f.logical_len] == payloads[i]


@pytest.fixture
def native_chain(tmp_path):
    """mgmtd + TWO native-transport storage nodes forming one 2-replica
    chain (head on node 10, tail on node 11, both native-engined), plus a
    connected client — the write fast path's shape: the head forwards a
    staged batch to a registered tail."""
    mgmtd = Mgmtd(1, MemKVEngine())
    mgmtd.extend_lease()
    mgmtd_server = NativeRpcServer()
    bind_mgmtd_service(mgmtd_server, mgmtd)
    mgmtd_server.start()
    client = NativeRpcClient()
    mcli = MgmtdRpcClient(mgmtd_server.address, client)

    nodes = {}
    for node_id, tid in ((10, 1000), (11, 1001)):
        svc = StorageService(node_id, mcli.refresh_routing)
        svc.set_messenger(RpcMessenger(mcli.refresh_routing, client))
        target = StorageTarget(tid, CHAIN, engine="native",
                               path=str(tmp_path / f"t{tid}"),
                               chunk_size=CHUNK)
        svc.add_target(target)
        server = NativeRpcServer()
        bind_storage_service(server, svc)
        server.start()
        mgmtd.register_node(node_id, NodeType.STORAGE, host=server.host,
                            port=server.port)
        mgmtd.create_target(tid, node_id=node_id)
        nodes[node_id] = {"svc": svc, "server": server, "target": target}
    mgmtd.upload_chain(CHAIN, [1000, 1001])
    mgmtd.upload_chain_table(1, [CHAIN])
    for node_id, tid in ((10, 1000), (11, 1001)):
        mgmtd.heartbeat(node_id, 1, {tid: LocalTargetState.UPTODATE})
    yield {"nodes": nodes, "client": client, "mcli": mcli, "mgmtd": mgmtd}
    client.close()
    for n in nodes.values():
        n["server"].stop()
        n["svc"].stop_workers()
    mgmtd_server.stop()


class TestNativeWriteFastpath:
    def _sync_all(self, env) -> dict:
        """Sync both nodes' registries; -> {node_id: registered reads}."""
        return {nid: sync_read_fastpath(n["server"], n["svc"])
                for nid, n in env["nodes"].items()}

    def test_tail_batch_update_served_natively(self, native_chain):
        env = native_chain
        sc = _client_for(env)
        self._sync_all(env)
        tail = env["nodes"][11]["server"]
        h0, _ = tail.fastpath_stats()
        payloads = {i: bytes([0x40 + i]) * (CHUNK - 11 * i)
                    for i in range(1, 7)}
        ops = [(CHAIN, ChunkId(21, i), 0, p) for i, p in payloads.items()]
        replies = sc.batch_write(ops, chunk_size=CHUNK)
        assert all(r.ok for r in replies), replies
        h1, _ = tail.fastpath_stats()
        assert h1 > h0, "tail batchUpdate must be served by the fast path"
        # both replicas hold identical committed bytes + metadata
        for i, p in payloads.items():
            for tid, node_id in ((1000, 10), (1001, 11)):
                eng = env["nodes"][node_id]["target"].engine
                assert eng.read(ChunkId(21, i)) == p
                meta = eng.get_meta(ChunkId(21, i))
                assert meta.committed_ver == 1 and meta.pending_ver == 0
        # reads through the normal path verify end to end
        got = sc.batch_read([ClientReadReq(CHAIN, ChunkId(21, i), 0, -1)
                             for i in payloads])
        assert [g.data for g in got] == list(payloads.values())

    def test_replies_match_python_tail(self, native_chain):
        """Fast-path replies must be field-identical to the Python tail's:
        same writes against disjoint chunks through each path, then the
        reply fields and both engines' contents compared."""
        from tpu3fs.ops.crc32c import crc32c

        env = native_chain
        sc = _client_for(env)
        self._sync_all(env)
        payload = bytes(range(250)) * 2  # 500 bytes
        fast = sc.batch_write(
            [(CHAIN, ChunkId(22, 1), 0, payload)], chunk_size=CHUNK)
        # disable the write registry: the same-shaped write now takes the
        # Python tail
        env["nodes"][11]["server"].fastpath_sync(None, {})
        golden = sc.batch_write(
            [(CHAIN, ChunkId(22, 2), 0, payload)], chunk_size=CHUNK)
        f, g = fast[0], golden[0]
        assert f.ok and g.ok
        assert (f.update_ver, f.commit_ver) == (g.update_ver, g.commit_ver)
        assert f.checksum.value == g.checksum.value == crc32c(payload)
        assert f.checksum.length == g.checksum.length == len(payload)

    def test_overwrites_and_partial_offsets(self, native_chain):
        env = native_chain
        sc = _client_for(env)
        self._sync_all(env)
        cid = ChunkId(23, 0)
        assert sc.write_chunk(CHAIN, cid, 0, b"a" * 1000,
                              chunk_size=CHUNK).ok
        # partial overwrite at an offset: COW merge on BOTH replicas
        assert sc.write_chunk(CHAIN, cid, 500, b"b" * 700,
                              chunk_size=CHUNK).ok
        want = b"a" * 500 + b"b" * 700
        for node_id in (10, 11):
            eng = env["nodes"][node_id]["target"].engine
            assert eng.read(cid) == want

    def test_chain_version_skew_falls_back(self, native_chain):
        """A registry whose chain_ver is stale must refuse (fall back), and
        the Python path still answers correctly."""
        env = native_chain
        sc = _client_for(env)
        self._sync_all(env)
        # poison the registry with a stale chain version: the guard must
        # refuse every op of the batch (deterministic skew — upload_chain
        # with an unchanged member list keeps the version, so a real bump
        # needs a membership change this 2-node harness can't survive)
        tail_srv = env["nodes"][11]["server"]
        eng = env["nodes"][11]["target"].engine
        tail_srv.fastpath_sync_write(None, {
            CHAIN: (eng._h, 1001, 999, CHUNK)})
        h0, f0 = tail_srv.fastpath_stats()
        ops = [(CHAIN, ChunkId(24, 1), 0, b"z" * 600)]
        replies = sc.batch_write(ops, chunk_size=CHUNK)
        assert all(r.ok for r in replies)
        h1, f1 = tail_srv.fastpath_stats()
        assert h1 == h0 and f1 > f0
        for node_id in (10, 11):
            eng = env["nodes"][node_id]["target"].engine
            assert eng.read(ChunkId(24, 1)) == b"z" * 600

    def _forwarded_reqs(self, env, items):
        """Build chain-internal (forwarded-shape) WriteReqs: from_target
        set, update_ver assigned, current chain version — the method-15
        wire shape the head emits."""
        from tpu3fs.storage.craq import WriteReq

        chain = env["mcli"].refresh_routing().chains[CHAIN]
        return [WriteReq(
            chain_id=CHAIN, chain_ver=chain.chain_version, chunk_id=cid,
            offset=0, data=data, chunk_size=CHUNK, update_ver=ver,
            from_target=1000) for cid, data, ver in items]

    def _send_batch_update(self, env, node_id, reqs):
        return RpcMessenger(
            env["mcli"].refresh_routing, env["client"])(
                node_id, "batch_update", reqs)

    def test_duplicate_chunks_in_batch_fall_back(self, native_chain):
        """A crafted method-15 batch with duplicate chunk ids must hit the
        C++ dedup guard (fallback, not a fast-path hit) and still apply in
        order through the Python path."""
        env = native_chain
        self._sync_all(env)
        tail = env["nodes"][11]["server"]
        h0, f0 = tail.fastpath_stats()
        cid = ChunkId(25, 0)
        reqs = self._forwarded_reqs(env, [
            (cid, b"1" * 400, 1), (cid, b"2" * 400, 2)])
        replies = self._send_batch_update(env, 11, reqs)
        assert all(r.ok for r in replies)
        h1, f1 = tail.fastpath_stats()
        assert h1 == h0 and f1 > f0, "dup batch must fall back"
        # final content is the LAST write (Python's ordered dup path)
        assert env["nodes"][11]["target"].engine.read(cid) == b"2" * 400

    def test_head_node_never_registers_write_chain(self, native_chain):
        """Node 10 hosts the HEAD: its registry must carry no write chain,
        so a crafted method-15 request sent there falls back to Python
        (a fast-path answer at the head would skip staging/forwarding)."""
        env = native_chain
        self._sync_all(env)
        head = env["nodes"][10]["server"]
        h0, f0 = head.fastpath_stats()
        reqs = self._forwarded_reqs(
            env, [(ChunkId(26, 0), b"q" * 100, 1)])
        replies = self._send_batch_update(env, 10, reqs)
        h1, f1 = head.fastpath_stats()
        assert h1 == h0 and f1 > f0, "head must never fast-path writes"
        # the Python path answered (as the chain's first local writer it
        # stages AND forwards to the real tail)
        assert all(r.ok for r in replies)
        assert env["nodes"][11]["target"].engine.read(
            ChunkId(26, 0)) == b"q" * 100


def _python_head(monkeypatch) -> None:
    """From the next sync on, every node's head writes ride the Python
    dispatch: `_sync_head` stands the native head down while it observes
    a write fault armed on the node, and this makes it observe one."""
    monkeypatch.setattr(native_fastpath, "_write_faults_armed",
                        lambda node_id: True)


class TestNativeHeadWritePath:
    """Client-entry write/batchWrite served end to end by the C++ head
    (fp_try_head_write): decode, admission, exactly-once, engine stage,
    chain forward, CRC cross-check, commit — all below the GIL. The
    contract: byte-identical to the Python dispatch (which the head
    stands down to while a write fault is armed on the node: the seam
    `_python_head` pulls), exactly-once intact across the
    fast-path/fallback boundary, and the planted skip-crc chaos bug
    observable only when armed."""

    def _sync_all(self, env):
        for n in env["nodes"].values():
            sync_read_fastpath(n["server"], n["svc"])

    def test_byte_identity_and_worker_bypass(self, native_chain,
                                                      monkeypatch):
        """The same payloads against disjoint chunks through each path:
        field-identical replies, identical replica bytes + metadata — and
        the native path must never enqueue a Python update-worker round
        (that bypass IS the optimisation)."""
        from tpu3fs.ops.crc32c import crc32c
        from tpu3fs.storage import update_worker

        env = native_chain
        sc = _client_for(env)
        self._sync_all(env)
        head = env["nodes"][10]["server"]
        payloads = {i: bytes([0x60 + i]) * (CHUNK - 13 * i)
                    for i in range(1, 5)}
        s0 = head.fastpath_write_stats()
        r0 = update_worker.rounds_run()
        fast = sc.batch_write(
            [(CHAIN, ChunkId(30, i), 0, p) for i, p in payloads.items()],
            chunk_size=CHUNK)
        assert all(r.ok for r in fast), fast
        assert head.fastpath_write_stats()[0] > s0[0], \
            "head batchWrite must be served natively"
        assert update_worker.rounds_run() == r0, \
            "a natively served write must never run a Python worker round"
        # an armed write fault stands the head down at the next sync; the
        # same writes then ride the Python dispatch
        _python_head(monkeypatch)
        self._sync_all(env)
        s1 = head.fastpath_write_stats()
        golden = sc.batch_write(
            [(CHAIN, ChunkId(31, i), 0, p) for i, p in payloads.items()],
            chunk_size=CHUNK)
        assert all(r.ok for r in golden), golden
        assert head.fastpath_write_stats()[0] == s1[0], \
            "stood down: the head must not serve natively"
        assert update_worker.rounds_run() > r0, \
            "the Python head path runs through the update workers"
        for f, g, p in zip(fast, golden, payloads.values()):
            assert (f.code, f.update_ver, f.commit_ver, f.retry_after_ms) \
                == (g.code, g.update_ver, g.commit_ver, g.retry_after_ms)
            assert f.checksum.value == g.checksum.value == crc32c(p)
            assert f.checksum.length == g.checksum.length == len(p)
        for i, p in payloads.items():
            for node_id in (10, 11):
                eng = env["nodes"][node_id]["target"].engine
                for fam in (30, 31):
                    cid = ChunkId(fam, i)
                    assert eng.read(cid) == p
                    meta = eng.get_meta(cid)
                    assert (meta.committed_ver, meta.pending_ver) == (1, 0)
                    assert meta.checksum.value == crc32c(p)

    def test_exactly_once_replay_across_path_swap(self, native_chain,
                                                  monkeypatch):
        """One channel table serves both paths: a retry replayed natively,
        and then replayed AGAIN after the head stood down to Python,
        must splice back the stored reply — applied exactly once."""
        from tpu3fs.rpc.services import RpcMessenger
        from tpu3fs.storage.craq import WriteReq

        env = native_chain
        self._sync_all(env)
        head = env["nodes"][10]["server"]
        send = RpcMessenger(env["mcli"].refresh_routing, env["client"])
        chain_ver = env["mcli"].refresh_routing().chains[CHAIN].chain_version
        cid = ChunkId(32, 0)

        def req(seq, data):
            return WriteReq(
                chain_id=CHAIN, chain_ver=chain_ver, chunk_id=cid,
                offset=0, data=data, chunk_size=CHUNK,
                client_id="xo-cli", channel_id=9, seqnum=seq)

        s0 = head.fastpath_write_stats()
        first = send(10, "write", req(1, b"once" * 100))
        assert first.ok, first
        assert head.fastpath_write_stats()[0] > s0[0], \
            "single write must be served natively"
        # same (client, channel, seqnum) replayed natively: stored reply
        replay = send(10, "write", req(1, b"once" * 100))
        assert (replay.code, replay.update_ver, replay.commit_ver,
                replay.checksum.value) == (
                    first.code, first.update_ver, first.commit_ver,
                    first.checksum.value)
        # an OLDER seqnum on the channel is refused, never applied
        stale = send(10, "write", req(0, b"never"))
        assert stale.code == Code.CHUNK_STALE_UPDATE
        # swap the head to the Python dispatch: the C channel table is
        # SHARED, so the same replays still dedupe across the boundary
        _python_head(monkeypatch)
        self._sync_all(env)
        replay2 = send(10, "write", req(1, b"once" * 100))
        assert (replay2.code, replay2.update_ver, replay2.commit_ver,
                replay2.checksum.value) == (
                    first.code, first.update_ver, first.commit_ver,
                    first.checksum.value)
        assert send(10, "write", req(0, b"never")).code == \
            Code.CHUNK_STALE_UPDATE
        # applied exactly once, end to end, on both replicas
        for node_id in (10, 11):
            eng = env["nodes"][node_id]["target"].engine
            assert eng.read(cid) == b"once" * 100
            assert eng.get_meta(cid).committed_ver == 1

    def test_skip_crc_bug_commits_divergent_replicas(self, native_chain):
        """Planted chaos bug native_commit_skip_crc (tpu3fs/chaos/bugs.py):
        disarmed, replica divergence makes the native head REFUSE (fall
        back) and the Python mismatch path spells it out; armed inside an
        active fault plane, the head commits + acks with no verification
        and the replicas' committed CRCs silently disagree."""
        from tpu3fs.chaos import bugs
        from tpu3fs.client.storage_client import RetryOptions, StorageClient
        from tpu3fs.utils.fault_injection import plane

        env = native_chain
        sc = StorageClient(
            "skipcrc-test", env["mcli"].refresh_routing,
            RpcMessenger(env["mcli"].refresh_routing, env["client"]),
            retry=RetryOptions(max_retries=0, backoff_base_s=0.001))
        self._sync_all(env)
        head = env["nodes"][10]["server"]
        chain_ver = env["mcli"].refresh_routing().chains[CHAIN].chain_version
        cid = ChunkId(33, 0)
        assert sc.write_chunk(CHAIN, cid, 0, b"s" * 1000,
                              chunk_size=CHUNK).ok
        # manufacture divergence below the chain: both replicas committed
        # at ver 2 with DIFFERENT bytes — the state an in-flight
        # corruption leaves behind
        for node_id, fill in ((10, b"H"), (11, b"T")):
            eng = env["nodes"][node_id]["target"].engine
            eng.update(cid, 2, chain_ver, fill * 1000, 0, chunk_size=CHUNK)
            eng.commit(cid, 2, chain_ver)
        # cross-check ON: staged CRCs disagree -> native falls back, the
        # Python head answers CHUNK_CHECKSUM_MISMATCH — never a clean OK
        s0 = head.fastpath_write_stats()
        r = sc.write_chunk(CHAIN, cid, 100, b"x" * 50, chunk_size=CHUNK)
        s1 = head.fastpath_write_stats()
        assert s1[1] > s0[1], "divergence must fall back, not serve"
        assert s1[0] == s0[0]
        assert not r.ok and "successor" in r.message
        # armed + plane active: a NON-write-point rule keeps the plane
        # active WITHOUT standing the native head down (write-point rules
        # disable native serving entirely — the C workers can't evaluate
        # plane rules per request)
        bugs.arm("native_commit_skip_crc")
        plane().configure("point=storage.read,kind=delay_ms,arg=0")
        try:
            self._sync_all(env)
            s2 = head.fastpath_write_stats()
            r2 = sc.write_chunk(CHAIN, cid, 200, b"y" * 50,
                                chunk_size=CHUNK)
            assert r2.ok, r2
            assert head.fastpath_write_stats()[0] > s2[0], \
                "the bug must fire on the NATIVE path"
            metas = {nid: env["nodes"][nid]["target"].engine.get_meta(cid)
                     for nid in (10, 11)}
            assert metas[10].committed_ver == metas[11].committed_ver == 3
            assert metas[10].checksum.value != metas[11].checksum.value, \
                "the skipped cross-check is what kept replicas converged"
        finally:
            bugs.disarm()
            plane().clear()
            self._sync_all(env)

    def test_write_fault_rule_stands_head_down(self, native_chain):
        """While the fault plane carries a rule that could fire on this
        node's PYTHON write path, head serving stands down for the sync —
        the chaos schedule must keep injecting into the path it armed."""
        from tpu3fs.utils.fault_injection import plane

        env = native_chain
        sc = _client_for(env)
        plane().configure("point=storage.update,kind=delay_ms,arg=0")
        try:
            self._sync_all(env)
            head = env["nodes"][10]["server"]
            s0 = head.fastpath_write_stats()
            assert sc.write_chunk(CHAIN, ChunkId(34, 0), 0, b"d" * 100,
                                  chunk_size=CHUNK).ok
            assert head.fastpath_write_stats()[0] == s0[0], \
                "armed write-point rule must disable native head serving"
        finally:
            plane().clear()
        self._sync_all(env)
        s1 = env["nodes"][10]["server"].fastpath_write_stats()
        assert sc.write_chunk(CHAIN, ChunkId(34, 1), 0, b"d" * 100,
                              chunk_size=CHUNK).ok
        assert env["nodes"][10]["server"].fastpath_write_stats()[0] > s1[0]


class TestNativeHeadWriteGates:
    def test_tenant_throttle_rides_native_and_python_identically(
            self, native_node, monkeypatch):
        """TENANT_THROTTLED + typed retry_after_ms through the native head
        gate, and the same hint through the Python dispatch once the head
        stood down (the hints must survive the path swap)."""
        from tpu3fs.client.storage_client import RetryOptions, StorageClient
        from tpu3fs.qos.core import AdmissionController, QosConfig
        from tpu3fs.tenant import registry, tenant_scope

        env = native_node
        server, svc = env["server"], env["svc"]
        if not hasattr(server._lib, "tpu3fs_rpc_tenant_set"):
            pytest.skip("stale libtpu3fs_rpc.so: no tenant gate")
        sc = StorageClient(
            "wg-test", env["mcli"].refresh_routing,
            RpcMessenger(env["mcli"].refresh_routing, env["client"]),
            retry=RetryOptions(max_retries=0, backoff_base_s=0.001))
        assert sc.write_chunk(CHAIN, ChunkId(40, 0), 0, b"x" * 512,
                              chunk_size=CHUNK).ok
        # admission installed AFTER the setup write; the registry reload
        # hook pushes wg-alice's quota into the C gate
        server.set_admission(AdmissionController(QosConfig()))
        assert sync_read_fastpath(server, svc) == 1
        try:
            registry().configure("tenant=wg-alice,iops=2,burst_s=1")
            s0 = server.fastpath_write_stats()
            shed0 = server.tenant_shed_count()
            with tenant_scope("wg-alice"):
                native = [sc.batch_write(
                    [(CHAIN, ChunkId(40, 1), 0, b"n" * 256)],
                    chunk_size=CHUNK)[0] for _ in range(10)]
            assert server.fastpath_write_stats()[0] > s0[0], \
                "flood never reached the native head path"
            assert server.tenant_shed_count() > shed0, \
                "flood never reached the native tenant gate"
            throttled = [r for r in native
                         if r.code == Code.TENANT_THROTTLED]
            assert throttled, [r.code for r in native]
            assert all(r.retry_after_ms > 0 for r in throttled)
            # the same flood through the Python dispatch carries the
            # same typed hint
            _python_head(monkeypatch)
            sync_read_fastpath(server, svc)
            s1 = server.fastpath_write_stats()
            with tenant_scope("wg-alice"):
                pyth = [sc.batch_write(
                    [(CHAIN, ChunkId(40, 2), 0, b"p" * 256)],
                    chunk_size=CHUNK)[0] for _ in range(10)]
            assert server.fastpath_write_stats()[0] == s1[0], \
                "stood down: the head must not serve natively"
            py_throttled = [r for r in pyth
                            if r.code == Code.TENANT_THROTTLED]
            assert py_throttled, [r.code for r in pyth]
            assert all(r.retry_after_ms > 0 for r in py_throttled)
            # untenanted (default, unconfigured) traffic is untouched
            assert sc.write_chunk(CHAIN, ChunkId(40, 3), 0, b"z" * 64,
                                  chunk_size=CHUNK).ok
        finally:
            registry().clear()
