"""Zero-copy pipelined write path (docs/writepath.md): bulk-frame gather,
striped pipelined batch_write fan-out, server receive-view hand-off, and
the overlapped chain forward — plus the invariants the new path must
preserve: exactly-once channel replay dedupe and OVERLOADED sheds with
retry-after hints."""

import os
import threading
import time

import pytest

from tpu3fs.storage.craq import ReadReq, WriteReq
from tpu3fs.storage.types import ChunkId
from tpu3fs.utils.result import Code

CHUNK = 64 << 10
FILE = 70


@pytest.fixture
def rpc_cluster():
    from rpc_cluster import RpcCluster

    cluster = RpcCluster(replicas=2, chains=2, size=CHUNK)
    yield cluster
    cluster.close()


def _head_service(cluster, chain_id):
    """(service hosting the chain's head target, head target)."""
    routing = cluster.mgmtd.get_routing_info()
    head = routing.chains[chain_id].head()
    for svc in cluster.services:
        t = svc.target(head.target_id)
        if t is not None:
            return svc, t
    raise AssertionError("head target not hosted")


def _tail_service(cluster, chain_id):
    routing = cluster.mgmtd.get_routing_info()
    tail = routing.chains[chain_id].targets[-1]
    for svc in cluster.services:
        t = svc.target(tail.target_id)
        if t is not None:
            return svc, t
    raise AssertionError("tail target not hosted")


class _SlowEngine:
    """Engine proxy adding a fixed delay to batched staging — the
    injected slow local engine of the overlap acceptance test."""

    def __init__(self, inner, delay_s: float):
        self._inner = inner
        self._delay = delay_s
        self.calls = 0

    def batch_update(self, ops, chain_ver):
        self.calls += 1
        time.sleep(self._delay)
        return self._inner.batch_update(ops, chain_ver)

    def __getattr__(self, name):
        return getattr(self._inner, name)


class _SpyEngine:
    """Records the payload types the engine was handed."""

    def __init__(self, inner):
        self._inner = inner
        self.data_types = []

    def batch_update(self, ops, chain_ver):
        self.data_types.extend(type(op.data) for op in ops)
        return self._inner.batch_update(ops, chain_ver)

    def __getattr__(self, name):
        return getattr(self._inner, name)


class TestBulkWriteGather:
    def test_batch_write_roundtrip_spanning_shapes(self, rpc_cluster):
        """Full chunks, offset writes and short tails through the
        pipelined bulk path land byte-exact on every replica."""
        client = rpc_cluster.storage_client()
        chain = rpc_cluster.chain_ids[0]
        payloads = [
            (ChunkId(FILE, 0), 0, bytes(range(256)) * (CHUNK // 256)),
            (ChunkId(FILE, 1), 0, b"\xab" * (CHUNK // 2 + 13)),
            (ChunkId(FILE, 2), 100, b"\xcd" * 999),
        ]
        replies = client.batch_write(
            [(chain, cid, off, data) for cid, off, data in payloads],
            chunk_size=CHUNK)
        assert all(r.ok for r in replies), replies
        for cid, off, data in payloads:
            got = client.read_chunk(chain, cid, off, len(data))
            assert got.ok and bytes(got.data) == data
        client.close()

    def test_memoryview_gather_is_wire_equal(self, rpc_cluster):
        """The client gathers memoryview slices of one user buffer (the
        FileIoClient.write shape) with no assembly copy; the server must
        install identical bytes."""
        client = rpc_cluster.storage_client()
        chain = rpc_cluster.chain_ids[1]
        blob = os.urandom(3 * CHUNK + 77)
        mv = memoryview(blob)
        writes = []
        for i in range(0, len(blob), CHUNK):
            part = mv[i:i + CHUNK]
            writes.append((chain, ChunkId(FILE, 100 + i // CHUNK), 0, part))
        assert all(r.ok for r in client.batch_write(writes,
                                                    chunk_size=CHUNK))
        got = b"".join(
            bytes(client.read_chunk(chain, cid, 0, -1).data)
            for _, cid, _, _ in writes)
        assert got == blob
        client.close()

    def test_server_hands_views_to_engine(self, rpc_cluster):
        """The bulk section of an incoming write reaches the engine as a
        memoryview over the receive buffer — no intermediate copy
        (services._attach)."""
        chain = rpc_cluster.chain_ids[0]
        svc, target = _head_service(rpc_cluster, chain)
        spy = _SpyEngine(target.engine)
        target.engine = spy
        try:
            client = rpc_cluster.storage_client()
            r = client.batch_write(
                [(chain, ChunkId(FILE, 200), 0, b"v" * CHUNK)],
                chunk_size=CHUNK)
            assert r[0].ok
            assert memoryview in spy.data_types, spy.data_types
            client.close()
        finally:
            target.engine = spy._inner


class TestPipelinedStripedWrites:
    def test_striped_fanout_equivalence(self, rpc_cluster):
        """Forced striping (every node group splits across connections)
        must return the same replies/content as the unstriped path."""
        client = rpc_cluster.storage_client()
        m = client._messenger
        m._write_stripe_min_bytes = CHUNK  # any 2-op group stripes
        chain = rpc_cluster.chain_ids[0]
        writes = [(chain, ChunkId(FILE, 300 + i), 0,
                   bytes([i]) * (CHUNK - i)) for i in range(8)]
        assert all(r.ok for r in client.batch_write(writes,
                                                    chunk_size=CHUNK))
        for _, cid, _, data in writes:
            got = client.read_chunk(chain, cid, 0, -1)
            assert got.ok and bytes(got.data) == data
        client.close()

    def test_transport_error_fills_span_replies(self, rpc_cluster):
        """A dead node's stripes answer with the transport code instead
        of raising past the batch."""
        client = rpc_cluster.storage_client()
        m = client._messenger
        reqs = [WriteReq(
            chain_id=rpc_cluster.chain_ids[0], chain_ver=1,
            chunk_id=ChunkId(FILE, 500), offset=0, data=b"x" * 100,
            chunk_size=CHUNK, client_id="t", channel_id=1, seqnum=1)]
        out = m.batch_write_pipelined([(999, reqs)])  # unknown node id
        assert len(out) == 1 and len(out[0]) == 1
        assert out[0][0].code == Code.RPC_CONNECT_FAILED
        client.close()


class TestChainForwardOverlap:
    DELAY = 0.25

    def _one_write(self, cluster, chunk_index):
        client = cluster.storage_client()
        chain = cluster.chain_ids[0]
        t0 = time.perf_counter()
        r = client.batch_write(
            [(chain, ChunkId(FILE, chunk_index), 0, b"o" * CHUNK)],
            chunk_size=CHUNK)
        dt = time.perf_counter() - t0
        assert r[0].ok, r
        client.close()
        return dt

    def test_head_to_tail_latency_is_max_not_sum(self, rpc_cluster,
                                                 monkeypatch):
        """With a slow local engine on BOTH hops, head-to-tail write
        latency must approach max(local, forward) — the local stage and
        the successor's whole pipeline run concurrently — and revert to
        the sum on a host with one hardware thread, where the rule
        (`craq._overlap_enabled`) stands the overlap down."""
        chain = rpc_cluster.chain_ids[0]
        hsvc, htarget = _head_service(rpc_cluster, chain)
        tsvc, ttarget = _tail_service(rpc_cluster, chain)
        assert htarget is not ttarget
        head_slow = _SlowEngine(htarget.engine, self.DELAY)
        tail_slow = _SlowEngine(ttarget.engine, self.DELAY)
        htarget.engine = head_slow
        ttarget.engine = tail_slow
        try:
            monkeypatch.setattr(os, "cpu_count", lambda: 1)
            dt_seq = self._one_write(rpc_cluster, 600)
            monkeypatch.setattr(os, "cpu_count", lambda: 2)
            # the least of a few: a loaded host can only lengthen a write
            dt_overlap = min(self._one_write(rpc_cluster, 601 + i)
                             for i in range(3))
        finally:
            htarget.engine = head_slow._inner
            ttarget.engine = tail_slow._inner
        assert head_slow.calls >= 2 and tail_slow.calls >= 2
        # sequential: head stage + (forward -> tail stage) >= 2*DELAY
        assert dt_seq >= 2 * self.DELAY, dt_seq
        # overlapped: ~max(head stage, forward+tail stage) ~= DELAY + rpc
        assert dt_overlap < dt_seq - 0.4 * self.DELAY, (dt_overlap, dt_seq)
        assert dt_overlap >= self.DELAY, dt_overlap

    def test_overlap_content_converges_on_all_replicas(self, rpc_cluster):
        """Overlapped forwards still commit head->tail with the checksum
        cross-check: every replica ends byte-identical."""
        client = rpc_cluster.storage_client()
        chain = rpc_cluster.chain_ids[0]
        data = os.urandom(CHUNK)
        r = client.batch_write([(chain, ChunkId(FILE, 610), 0, data)],
                               chunk_size=CHUNK)
        assert r[0].ok
        routing = rpc_cluster.mgmtd.get_routing_info()
        for t in routing.chains[chain].targets:
            for svc in rpc_cluster.services:
                tgt = svc.target(t.target_id)
                if tgt is not None:
                    assert bytes(tgt.engine.read(ChunkId(FILE, 610))) == data
        client.close()


class TestInvariantsOnNewPath:
    def test_exactly_once_replay_dedupes(self, rpc_cluster):
        """A replayed (client, channel, seq) batch write answers from the
        channel table — the engine applies the update exactly once."""
        chain = rpc_cluster.chain_ids[0]
        client = rpc_cluster.storage_client()
        m = client._messenger
        routing = rpc_cluster.mgmtd.get_routing_info()
        head = routing.chains[chain].head()
        node = routing.node_of_target(head.target_id)
        req = WriteReq(
            chain_id=chain, chain_ver=routing.chains[chain].chain_version,
            chunk_id=ChunkId(FILE, 700), offset=0, data=b"once" * 100,
            chunk_size=CHUNK, client_id="dedupe-t", channel_id=7, seqnum=3)
        first = m.batch_write_pipelined([(node.node_id, [req])])[0][0]
        assert first.ok
        replay = m.batch_write_pipelined([(node.node_id, [req])])[0][0]
        assert replay.ok and replay.commit_ver == first.commit_ver
        svc, target = _head_service(rpc_cluster, chain)
        meta = target.engine.get_meta(ChunkId(FILE, 700))
        assert meta.committed_ver == first.commit_ver  # not re-applied
        client.close()

    def test_overloaded_shed_carries_retry_hint(self, rpc_cluster):
        """An admission shed on the head answers OVERLOADED with the
        retry-after hint through the pipelined bulk path."""
        chain = rpc_cluster.chain_ids[0]
        svc, _ = _head_service(rpc_cluster, chain)

        class _DenyAll:
            def try_admit(self, service, method, tclass, cost=1.0,
                          tenant=None):
                return None, 25

        svc._qos = _DenyAll()
        try:
            client = rpc_cluster.storage_client()
            m = client._messenger
            routing = rpc_cluster.mgmtd.get_routing_info()
            head = routing.chains[chain].head()
            node = routing.node_of_target(head.target_id)
            req = WriteReq(
                chain_id=chain,
                chain_ver=routing.chains[chain].chain_version,
                chunk_id=ChunkId(FILE, 710), offset=0, data=b"s" * 100,
                chunk_size=CHUNK, client_id="shed-t", channel_id=2,
                seqnum=1)
            out = m.batch_write_pipelined([(node.node_id, [req])])[0][0]
            assert out.code == Code.OVERLOADED
            assert out.retry_after_ms == 25
            client.close()
        finally:
            svc._qos = None


class TestBatchWriteFiles:
    def test_kvcache_batch_put_rides_batched_writes(self):
        """KVCacheClient.batch_put == N puts, observed through get, with
        ONE batched write underneath (fabric fan-out still batches)."""
        from tpu3fs.fabric.fabric import Fabric, SystemSetupConfig
        from tpu3fs.kvcache.cache import KVCacheClient

        fab = Fabric(SystemSetupConfig(num_chains=2, chunk_size=4096))
        kv = KVCacheClient(fab.meta, fab.file_client(), root="/kvc")
        items = [(f"bp/{i}", bytes([i]) * (3000 + i)) for i in range(6)]
        kv.batch_put(items)
        for key, value in items:
            assert kv.get(key) == value
        fab.close()

    def test_batch_write_files_returns_counts_and_content(self):
        from tpu3fs.fabric.fabric import Fabric, SystemSetupConfig
        from tpu3fs.meta.store import OpenFlags

        fab = Fabric(SystemSetupConfig(num_chains=2, chunk_size=4096))
        fio = fab.file_client()
        blobs = [os.urandom(4096 * 2 + 7), os.urandom(100), b""]
        opened = []
        for i, blob in enumerate(blobs):
            res = fab.meta.create(f"/bwf{i}", flags=OpenFlags.WRITE,
                                  client_id="t")
            opened.append(res)
        counts = fio.batch_write_files(
            [(res.inode, 0, blob) for res, blob in zip(opened, blobs)])
        assert counts == [len(b) for b in blobs]
        for res, blob in zip(opened, blobs):
            inode = fab.meta.close(res.inode.id, res.session_id,
                                   length_hint=len(blob), wrote=True)
            assert fio.read(inode, 0, len(blob) + 10) == blob
        fab.close()


# -- fresh partial EC stripes on the batch path, over real sockets -----------

EC_K, EC_M = 3, 1
EC_LENGTHS = {"one_byte": 1, "kvcache_entry": CHUNK * 9 // 16 + 64,
              "chunk_minus_1": CHUNK - 1}


@pytest.fixture
def ec_cluster():
    from rpc_cluster import RpcCluster

    cluster = RpcCluster(replicas=0, chains=2, size=CHUNK, ec=(EC_K, EC_M))
    yield cluster
    cluster.close()


def _ec_inode(cluster, file_id, chains=None):
    from tpu3fs.meta.types import Acl, Inode, InodeType, Layout

    return Inode(id=file_id, type=InodeType.FILE, acl=Acl(),
                 layout=Layout(table_id=1,
                               chains=list(chains or cluster.chain_ids[:1]),
                               chunk_size=CHUNK))


def _counting(client):
    """Count the client's RPCs by (method, node): single calls and the
    pipelined batch rounds alike."""
    m = client._messenger
    calls = []
    inner_call, inner_pipe = m.__class__.__call__, m.batch_write_pipelined

    class Counting(m.__class__):
        def __call__(self, node_id, method, payload):
            calls.append((method, node_id))
            return inner_call(self, node_id, method, payload)

        def batch_write_pipelined(self, groups, method="batch_write"):
            calls.extend((method, node_id) for node_id, _ in groups)
            return inner_pipe(groups, method=method)

    m.__class__ = Counting
    return calls


def _ec_stored(cluster, chain_id, cid):
    routing = cluster.mgmtd.get_routing_info()
    out = []
    for t in routing.chains[chain_id].targets:
        (tgt,) = [svc.target(t.target_id) for svc in cluster.services
                  if svc.target(t.target_id) is not None]
        meta = tgt.engine.get_meta(cid)
        assert meta is not None and meta.pending_ver == 0
        out.append((bytes(tgt.engine.read(cid)), meta.checksum.value,
                    meta.aux, meta.length))
    return out


class TestEcHeadPartialOverSockets:
    @pytest.mark.parametrize("name", sorted(EC_LENGTHS))
    def test_fresh_batch_rpc_counts_and_stored_shards(self, ec_cluster, name):
        """N fresh head-partial files: one stat_chunks, one
        batch_write_shard a node a phase, nothing else on the wire; what
        the targets hold equals the single-stripe ladder's."""
        from tpu3fs.client.file_io import FileIoClient

        n_bytes, N = EC_LENGTHS[name], 4
        chain = ec_cluster.chain_ids[0]
        client = ec_cluster.storage_client()
        fio = FileIoClient(client)
        calls = _counting(client)
        bodies = [os.urandom(n_bytes) for _ in range(N)]
        inodes = [_ec_inode(ec_cluster, 800 + i) for i in range(N)]
        assert fio.batch_write_files(
            [(ino, 0, body) for ino, body in zip(inodes, bodies)]) \
            == [n_bytes] * N
        assert [m for m, _ in calls].count("stat_chunks") == 1
        rounds = [n for m, n in calls if m == "batch_write_shard"]
        assert len(rounds) == 2 * len(set(rounds)) <= 2 * 3
        assert {m for m, _ in calls} == {"stat_chunks", "batch_write_shard"}
        assert client._ec_head_batched._value == N
        for i, (ino, body) in enumerate(zip(inodes, bodies)):
            twin = ChunkId(900 + i, 0)
            assert client.write_stripe(chain, twin, body,
                                       chunk_size=CHUNK).ok
            assert _ec_stored(ec_cluster, chain, ChunkId(ino.id, 0)) == \
                _ec_stored(ec_cluster, chain, twin)
            assert fio.read(ino, 0, n_bytes) == body
        client.close()

    def test_existing_stripes_keep_their_tails_fresh_ones_batch(
            self, ec_cluster):
        """One call over two chains: full stripes, fresh head-partials and
        one over a longer committed stripe — that one alone is laddered."""
        from tpu3fs.client.file_io import FileIoClient

        client = ec_cluster.storage_client()
        fio = FileIoClient(client)
        a = _ec_inode(ec_cluster, 820, ec_cluster.chain_ids)
        b = _ec_inode(ec_cluster, 821, ec_cluster.chain_ids)
        fio.write(b, 0, b"old" * 500)
        calls = _counting(client)
        body_a = os.urandom(2 * CHUNK + 321)
        fio.batch_write_files([(a, 0, body_a), (b, 0, b"new" * 100)])
        assert [m for m, _ in calls].count("stat_chunks") == 2
        assert client._ec_head_ladder._value == 1
        assert client._ec_head_batched._value == 2   # b's first, a's tail
        assert fio.read(a, 0, len(body_a)) == body_a
        assert fio.read(b, 0, 1500) == b"new" * 100 + (b"old" * 500)[300:]
        client.close()

    def test_failed_stage_round_ends_on_the_ladder(self, ec_cluster):
        """A node's stage batch lost on the wire: those stripes finish on
        the single-stripe ladder at the version the batch chose."""
        from tpu3fs.client.file_io import FileIoClient
        from tpu3fs.client.storage_client import RetryOptions
        from tpu3fs.utils.result import FsError, Status

        chain = ec_cluster.chain_ids[0]
        client = ec_cluster.storage_client(retry=RetryOptions(
            max_retries=3, backoff_base_s=0.001, backoff_max_s=0.01))
        fio = FileIoClient(client)
        m = client._messenger
        inner = m.batch_write_pipelined
        lost = []

        def lossy(groups, method="batch_write"):
            if method == "batch_write_shard" and not lost:
                lost.append(groups[0][0])
                got = inner(groups[1:], method=method)
                return [[UpdateReply(Code.RPC_PEER_CLOSED)
                         for _ in groups[0][1]]] + got
            return inner(groups, method=method)

        from tpu3fs.storage.craq import UpdateReply

        m.batch_write_pipelined = lossy
        calls = _counting(client)
        inodes = [_ec_inode(ec_cluster, 840 + i) for i in range(3)]
        bodies = [os.urandom(1000 + i) for i in range(3)]
        fio.batch_write_files(
            [(ino, 0, body) for ino, body in zip(inodes, bodies)])
        assert lost and "write_shard" in {m for m, _ in calls}
        for ino, body in zip(inodes, bodies):
            stored = _ec_stored(ec_cluster, chain, ChunkId(ino.id, 0))
            assert {aux for _, _, aux, _ in stored} == {len(body)}
            assert fio.read(ino, 0, len(body)) == body
        client.close()
