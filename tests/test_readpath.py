"""Zero-copy read-path pipeline: prefetch-cache correctness, pipelined
striped fan-out equivalence, zero-copy bulk framing, and the QoS class
bits threaded through the native handler ABI.

The prefetcher contract under test (client/prefetch.py): sequential runs
arm readahead and serve hits; THIS client's write/truncate/remove
invalidate; memory stays bounded under adversarial patterns; reads after
writes through FileIoClient AND FUSE see fresh data; prefetch fetches run
under the arming reader's traffic class.
"""

import threading

import pytest

from tpu3fs.client.file_io import FileIoClient
from tpu3fs.client.prefetch import PrefetchConfig, ReadaheadPrefetcher
from tpu3fs.fabric.fabric import Fabric, SystemSetupConfig
from tpu3fs.meta.store import OpenFlags
from tpu3fs.utils.result import Code

CHUNK = 64 << 10


@pytest.fixture
def fab():
    f = Fabric(SystemSetupConfig(num_storage_nodes=3, num_chains=2,
                                 num_replicas=2, chunk_size=CHUNK))
    yield f
    f.close()


def _mkfile(fab, path: str, data: bytes):
    res = fab.meta.create(path, flags=OpenFlags.WRITE, client_id="t")
    fio = fab.file_client()
    fio.write(res.inode, 0, data)
    fab.meta.close(res.inode.id, res.session_id, length_hint=len(data),
                   wrote=True)
    return fab.meta.stat(path)


def _pfio(fab, **cfg):
    config = PrefetchConfig(**cfg) if cfg else PrefetchConfig()
    return FileIoClient(fab.storage_client(), prefetch=config)


class TestPrefetchCorrectness:
    def test_sequential_scan_hits_and_matches(self, fab):
        data = bytes(range(256)) * (8 * CHUNK // 256)
        inode = _mkfile(fab, "/seq", data)
        fio = _pfio(fab, window_bytes=2 * CHUNK, min_run=2)
        step = CHUNK // 4
        got = bytearray()
        for off in range(0, len(data), step):
            got += fio.read(inode, off, step)
        assert bytes(got) == data
        pf = fio.prefetcher
        assert pf.hits._value > 0, "sequential scan never hit readahead"
        fio.close()

    def test_invalidation_on_write(self, fab):
        data = b"a" * (4 * CHUNK)
        inode = _mkfile(fab, "/waw", data)
        fio = _pfio(fab, window_bytes=2 * CHUNK, min_run=2)
        step = CHUNK // 2
        for off in range(0, len(data), step):
            fio.read(inode, off, step)
        assert fio.prefetcher.cached_bytes() > 0
        # overwrite THROUGH THE SAME CLIENT: cache must drop, reads fresh
        fio.write(inode, 0, b"b" * (4 * CHUNK))
        assert fio.prefetcher.cached_bytes() == 0
        for off in range(0, len(data), step):
            assert fio.read(inode, off, step) == b"b" * step
        fio.close()

    def test_invalidation_on_truncate_and_remove(self, fab):
        data = b"c" * (4 * CHUNK)
        inode = _mkfile(fab, "/trunc", data)
        fio = _pfio(fab, window_bytes=2 * CHUNK, min_run=2)
        for off in range(0, len(data), CHUNK):
            fio.read(inode, off, CHUNK)
        assert fio.prefetcher.cached_bytes() > 0
        fio.truncate_chunks(inode, CHUNK)
        assert fio.prefetcher.cached_bytes() == 0
        # repopulate then remove
        for off in range(0, CHUNK, CHUNK // 4):
            fio.read(inode, off, CHUNK // 4)
        fio.remove_chunks(inode)
        assert fio.prefetcher.cached_bytes() == 0
        fio.close()

    def test_read_after_write_visibility_same_client(self, fab):
        inode = _mkfile(fab, "/rw", b"x" * (2 * CHUNK))
        fio = _pfio(fab, min_run=1, window_bytes=2 * CHUNK)
        assert fio.read(inode, 0, CHUNK) == b"x" * CHUNK
        assert fio.read(inode, CHUNK, CHUNK) == b"x" * CHUNK
        fio.write(inode, 0, b"y" * CHUNK)
        assert fio.read(inode, 0, CHUNK) == b"y" * CHUNK
        fio.close()

    def test_bounded_memory_adversarial(self, fab):
        """Random access never arms; a tiny cache cap holds even when
        sequential runs DO arm across many files."""
        cap = 4 * CHUNK
        files = [
            _mkfile(fab, f"/adv{i}", bytes([i]) * (8 * CHUNK))
            for i in range(4)
        ]
        fio = _pfio(fab, window_bytes=2 * CHUNK, min_run=2,
                    max_cache_bytes=cap, max_inflight=2)
        # random (never two adjacent reads): nothing cached
        import random as _random

        rng = _random.Random(3)
        offs = [o * CHUNK for o in range(8)]
        for _ in range(4):
            rng.shuffle(offs)
            prev = None
            for inode in files:
                for off in offs:
                    if prev is not None and prev == off:
                        continue
                    fio.read(inode, off, CHUNK // 2)
                    prev = off + CHUNK // 2
        assert fio.prefetcher.cached_bytes() == 0
        # sequential scans over every file: cap still holds
        for inode in files:
            for off in range(0, 8 * CHUNK, CHUNK):
                fio.read(inode, off, CHUNK)
        _drain(fio.prefetcher)
        assert fio.prefetcher.cached_bytes() <= cap
        fio.close()

    def test_prefetch_runs_under_callers_class(self, fab):
        from tpu3fs.qos.core import TrafficClass, current_class, tagged

        inode = _mkfile(fab, "/cls", b"q" * (8 * CHUNK))
        fio = _pfio(fab, window_bytes=2 * CHUNK, min_run=2)
        seen = []
        orig = fio.prefetcher._fetch

        def spy(ino, off, n):
            seen.append(current_class())
            return orig(ino, off, n)

        fio.prefetcher._fetch = spy
        with tagged(TrafficClass.CKPT):
            for off in range(0, 8 * CHUNK, CHUNK):
                fio.read(inode, off, CHUNK)
        _drain(fio.prefetcher)
        assert seen, "no prefetch fetch ran"
        assert all(c == TrafficClass.CKPT for c in seen)
        fio.close()

    def test_shuffled_batches_do_not_thrash_readahead(self, fab):
        """The dataload-loader shape: sorted per-batch extents with gaps
        and the odd file-adjacent pair. min_run alone armed (and fetched
        a window) on EVERY adjacent pair — dozens of wasted windows per
        epoch; the jump-fraction thrash guard must keep readahead
        bounded to at most the cold-start window or two, fetched before
        any jump history exists (a fresh sequential reader is
        indistinguishable at that point)."""
        import random as _random

        nrec = 64
        rec = CHUNK // 4
        window = 2 * CHUNK
        inode = _mkfile(fab, "/shuf", b"r" * (nrec * rec))
        fio = _pfio(fab, window_bytes=window, min_run=2)
        rng = _random.Random(17)
        adjacent_pairs = 0
        for _step in range(16):
            batch = sorted(rng.sample(range(nrec), 12))
            adjacent_pairs += sum(
                1 for a, b in zip(batch, batch[1:]) if b - a == 1)
            for ri in batch:
                fio.read(inode, ri * rec, rec)
        # the pattern really contained the adjacency that used to thrash
        assert adjacent_pairs > 10
        _drain(fio.prefetcher)
        pf = fio.prefetcher
        assert pf.prefetched_bytes._value <= 2 * window, \
            "shuffled batches kept arming readahead (thrash)"
        fio.close()

    def test_guard_recovers_for_sequential_reader(self, fab):
        """After a shuffled phase, a genuinely sequential scan re-arms
        within about one history window of reads."""
        import random as _random

        inode = _mkfile(fab, "/recov", b"s" * (64 * CHUNK))
        fio = _pfio(fab, window_bytes=2 * CHUNK, min_run=2)
        rng = _random.Random(5)
        offs = rng.sample(range(0, 64), 32)
        for o in offs:
            fio.read(inode, o * CHUNK, CHUNK // 2)
        assert fio.prefetcher.cached_bytes() == 0
        for off in range(0, 64 * CHUNK, CHUNK):
            fio.read(inode, off, CHUNK)
        _drain(fio.prefetcher)
        assert fio.prefetcher.hits._value > 0, \
            "sequential reader never re-armed after the shuffled phase"
        fio.close()

    def test_kvcache_and_loader_paths_ride_batches(self, fab):
        """batch_read_files consults the prefetch cache and still returns
        exact contents (the kvcache.batch_get / ckpt loader path)."""
        datas = [bytes([i + 1]) * (2 * CHUNK) for i in range(3)]
        inodes = [_mkfile(fab, f"/brf{i}", d)
                  for i, d in enumerate(datas)]
        fio = _pfio(fab, window_bytes=2 * CHUNK, min_run=1)
        # arm windows by reading the files sequentially first
        for inode in inodes:
            fio.read(inode, 0, CHUNK)
            fio.read(inode, CHUNK, CHUNK)
        _drain(fio.prefetcher)
        got = fio.batch_read_files([(ino, 0, 2 * CHUNK) for ino in inodes])
        assert got == datas
        fio.close()


def _drain(pf: ReadaheadPrefetcher, timeout: float = 5.0) -> None:
    """Wait for in-flight prefetches to settle."""
    import time as _time

    deadline = _time.monotonic() + timeout
    while _time.monotonic() < deadline:
        with pf._mu:
            if not pf._inflight:
                return
        _time.sleep(0.01)


class TestPrefetchUnit:
    def test_waiters_hit_inflight_window(self):
        """lookup blocks on a covering in-flight fetch instead of missing
        (the double-buffer property)."""
        gate = threading.Event()

        class Ino:
            id = 1
            length = 1 << 20

        def fetch(inode, off, n):
            gate.wait(5)
            return b"z" * n

        pf = ReadaheadPrefetcher(fetch, PrefetchConfig(
            window_bytes=4096, min_run=1))
        ino = Ino()
        pf.record_read(ino, 0, 4096)     # arms [4096, 8192)
        _wait_inflight(pf)
        got = []
        t = threading.Thread(
            target=lambda: got.append(pf.lookup(1, 4096, 4096)))
        t.start()
        gate.set()
        t.join(5)
        assert got and got[0] == b"z" * 4096

    def test_stale_inflight_not_waited_after_invalidate(self):
        gate = threading.Event()

        class Ino:
            id = 2
            length = 1 << 20

        def fetch(inode, off, n):
            gate.wait(5)
            return b"s" * n

        pf = ReadaheadPrefetcher(fetch, PrefetchConfig(
            window_bytes=4096, min_run=1))
        pf.record_read(Ino(), 0, 4096)
        _wait_inflight(pf)
        pf.invalidate(2)
        # stale fetch must not be waited on NOR installed
        assert pf.lookup(2, 4096, 4096) is None
        gate.set()
        _drain(pf)
        assert pf.cached_bytes() == 0
        pf.close()


def _wait_inflight(pf, timeout: float = 5.0) -> None:
    import time as _time

    deadline = _time.monotonic() + timeout
    while _time.monotonic() < deadline:
        with pf._mu:
            if pf._inflight:
                return
        _time.sleep(0.005)
    raise AssertionError("prefetch never went in flight")


class TestFusePrefetch:
    def test_fuse_read_after_write_and_truncate(self, fab):
        from tpu3fs.fuse.ops import FuseOps

        fio = FileIoClient(fab.storage_client(),
                           prefetch=PrefetchConfig(window_bytes=2 * CHUNK,
                                                   min_run=1))
        ops = FuseOps(fab.meta, fio)
        fh = ops.create("/fusepf", 0o644)
        ops.write(fh, 0, b"m" * (4 * CHUNK))
        ops.fsync(fh)
        # sequential reads arm + populate
        assert ops.read(fh, 0, CHUNK) == b"m" * CHUNK
        assert ops.read(fh, CHUNK, CHUNK) == b"m" * CHUNK
        _drain(fio.prefetcher)
        # write through FUSE: the next read must see it
        ops.write(fh, CHUNK, b"n" * CHUNK)
        assert ops.read(fh, CHUNK, CHUNK) == b"n" * CHUNK
        # truncate through FUSE (meta-side chunk drop): cache must drop
        for off in range(0, 4 * CHUNK, CHUNK):
            ops.read(fh, off, CHUNK)
        _drain(fio.prefetcher)
        ops.truncate("/fusepf", CHUNK)
        assert fio.prefetcher.cached_bytes() == 0
        ops.release(fh)
        fio.close()


class TestZeroCopyFraming:
    """Socket-served reads hand out memoryviews over the transport's
    receive buffer; contents must match the written bytes exactly."""

    @pytest.fixture
    def rpc_cluster(self):
        from rpc_cluster import RpcCluster

        cluster = RpcCluster(replicas=2, chains=2, size=CHUNK)
        yield cluster
        cluster.close()

    def test_batch_read_zero_copy_and_exact(self, rpc_cluster):
        from rpc_cluster import FILE_ID
        from tpu3fs.client.storage_client import ReadReq, RetryOptions
        from tpu3fs.storage.types import ChunkId

        client = rpc_cluster.storage_client(
            retry=RetryOptions(backoff_base_s=0.001))
        payloads = {i: bytes([i + 1]) * (CHUNK - 13 * i)
                    for i in range(6)}
        for i, p in payloads.items():
            assert client.write_chunk(
                rpc_cluster.chain_ids[i % 2], ChunkId(FILE_ID, i), 0, p,
                chunk_size=CHUNK).ok
        reqs = [ReadReq(rpc_cluster.chain_ids[i % 2], ChunkId(FILE_ID, i),
                        0, -1) for i in payloads]
        replies = client.batch_read(reqs)
        for i, r in zip(payloads, replies):
            assert r.ok
            # ZERO-COPY: data rides as a memoryview over the recv buffer
            assert isinstance(r.data, memoryview)
            assert r.data == payloads[i]
        # single read too
        r = client.read_chunk(rpc_cluster.chain_ids[0], ChunkId(FILE_ID, 0))
        assert r.ok and r.data == payloads[0]
        client.close()

    def test_striped_fanout_equivalence(self, rpc_cluster):
        """Forced striping returns byte-identical results to unstriped."""
        from rpc_cluster import FILE_ID
        from tpu3fs.client.storage_client import ReadReq, RetryOptions
        from tpu3fs.storage.types import ChunkId

        client = rpc_cluster.storage_client(
            retry=RetryOptions(backoff_base_s=0.001))
        for i in range(16):
            assert client.write_chunk(
                rpc_cluster.chain_ids[i % 2], ChunkId(FILE_ID + 7, i), 0,
                bytes([i + 1]) * CHUNK, chunk_size=CHUNK).ok
        reqs = [ReadReq(rpc_cluster.chain_ids[i % 2],
                        ChunkId(FILE_ID + 7, i), 0, -1) for i in range(16)]
        golden = [bytes(r.data) for r in client.batch_read(reqs)]
        # force striping: every multi-op group splits
        client._messenger._stripe_min_bytes = 1
        client._messenger._stripes = 4
        striped = client.batch_read(reqs)
        assert all(r.ok for r in striped)
        assert [bytes(r.data) for r in striped] == golden
        client.close()


class TestNativeClassBits:
    """QoS traffic-class bits ride the native handler ABI (v3): a tagged
    peer's class reaches the Python admission AND the C-side per-class
    gates covering fast-path reads."""

    def test_tagged_class_reaches_admission(self, tmp_path):
        # one-node native cluster (mirrors test_native_fastpath's fixture)
        from tpu3fs.kv.mem import MemKVEngine
        from tpu3fs.mgmtd.service import Mgmtd
        from tpu3fs.mgmtd.types import LocalTargetState, NodeType
        from tpu3fs.qos.core import (
            AdmissionController,
            QosConfig,
            TrafficClass,
            tagged,
        )
        from tpu3fs.rpc.native_net import NativeRpcClient, NativeRpcServer
        from tpu3fs.rpc.services import (
            MgmtdRpcClient,
            RpcMessenger,
            bind_mgmtd_service,
            bind_storage_service,
        )
        from tpu3fs.storage.craq import StorageService
        from tpu3fs.storage.native_fastpath import sync_read_fastpath
        from tpu3fs.storage.target import StorageTarget
        from tpu3fs.storage.types import ChunkId

        mgmtd = Mgmtd(1, MemKVEngine())
        mgmtd.extend_lease()
        mgmtd_server = NativeRpcServer()
        bind_mgmtd_service(mgmtd_server, mgmtd)
        mgmtd_server.start()
        client = NativeRpcClient()
        mcli = MgmtdRpcClient(mgmtd_server.address, client)
        svc = StorageService(10, mcli.refresh_routing)
        svc.set_messenger(RpcMessenger(mcli.refresh_routing, client))
        target = StorageTarget(1000, 700_001, engine="native",
                               path=str(tmp_path / "t"), chunk_size=4096)
        svc.add_target(target)
        server = NativeRpcServer()
        bind_storage_service(server, svc)
        server.start()
        mgmtd.register_node(10, NodeType.STORAGE, host=server.host,
                            port=server.port)
        mgmtd.create_target(1000, node_id=10)
        mgmtd.upload_chain(700_001, [1000])
        mgmtd.upload_chain_table(1, [700_001])
        mgmtd.heartbeat(10, 1, {1000: LocalTargetState.UPTODATE})
        try:
            from tpu3fs.client.storage_client import (
                ReadReq,
                RetryOptions,
                StorageClient,
            )

            sc = StorageClient(
                "cls-test", mcli.refresh_routing,
                RpcMessenger(mcli.refresh_routing, client),
                retry=RetryOptions(max_retries=0, backoff_base_s=0.001))
            assert sc.write_chunk(700_001, ChunkId(5, 1), 0, b"x" * 4096,
                                  chunk_size=4096).ok
            # choke the RESYNC class only; fast-path reads go through C
            cfg = QosConfig()
            cfg.resync.rate = 0.001
            cfg.resync.burst = 1.0
            adm = AdmissionController(cfg)
            server.set_admission(adm)
            assert sync_read_fastpath(server, svc) == 1
            reqs = [ReadReq(700_001, ChunkId(5, 1), 0, -1, 1000)]
            # untagged (fg) reads sail through the C fast path
            for _ in range(8):
                assert all(r.ok for r in sc.batch_read(reqs))
            shed0 = server.qos_shed_count()
            with tagged(TrafficClass.RESYNC):
                replies = [sc.batch_read(reqs)[0] for _ in range(8)]
            shed1 = server.qos_shed_count()
            assert shed1 > shed0, \
                "tagged class never reached the native per-class gate"
            assert any(r.code == Code.OVERLOADED for r in replies)
            # fg still healthy after resync shed
            assert all(r.ok for r in sc.batch_read(reqs))
        finally:
            client.close()
            server.stop()
            mgmtd_server.stop()


# -- the read span plan: cut to what a frame and a ring can carry -------------

SCALE = 16                       # the cells' sizes over 16, as CHUNK is
FRAME = (64 << 20) // SCALE      # net.MAX_PACKET, services.USRBIO_IOV_BYTES
STRIPE_MIN = (4 << 20) // SCALE  # services.READ_STRIPE_MIN_BYTES


def _parent_spans(sizes, stripes, min_bytes):
    """The plan before spans were bounded by bytes: at most `stripes`
    equal stripes, whatever the group's bytes."""
    n = len(sizes)
    est = sum(sizes)
    if n <= 1 or stripes <= 1 or est < 2 * min_bytes:
        return [(0, n)]
    k = min(stripes, n, max(1, est // min_bytes))
    base, rem = divmod(n, k)
    spans, lo = [], 0
    for i in range(k):
        hi = lo + base + (1 if i < rem else 0)
        spans.append((lo, hi))
        lo = hi
    return spans


@pytest.fixture
def small_frames(monkeypatch):
    """A frame, a ring's buffer and the stripe minimum at a sixteenth, so
    that 64-KiB chunks stand to them as the cells' 1-MiB chunks do."""
    from tpu3fs.rpc import net, services

    monkeypatch.setattr(net, "MAX_PACKET", FRAME)
    monkeypatch.setattr(services, "USRBIO_IOV_BYTES", FRAME)
    monkeypatch.setattr(services.RpcMessenger, "_stripe_min_bytes",
                        STRIPE_MIN)


class TestReadSpanPlan:
    """A batched read's node groups become spans their carrier can
    answer, READ_STRIPES of a node in flight (docs/readpath.md)."""

    OPS = 1340    # a train_moonlight_cr3 restore's chunks

    def _cluster(self, carrier, ops):
        """4 nodes, 4 CR-3 chains, `ops` chunks striped over the chains
        -> (cluster, client, reqs, payloads)."""
        from rpc_cluster import FILE_ID, RpcCluster
        from tpu3fs.client.storage_client import ReadReq, RetryOptions
        from tpu3fs.storage.types import ChunkId

        cluster = RpcCluster(replicas=3, chains=4, size=CHUNK, nodes=4,
                             usrbio=carrier == "ring")
        client = cluster.storage_client(
            retry=RetryOptions(backoff_base_s=0.001))
        payloads = [bytes([i % 251 + 1]) * CHUNK for i in range(ops)]
        ids = [(cluster.chain_ids[i % 4], ChunkId(FILE_ID, i))
               for i in range(ops)]
        for lo in range(0, ops, 128):
            assert all(r.ok for r in client.batch_write(
                [(chain, cid, 0, p) for (chain, cid), p in
                 zip(ids[lo:lo + 128], payloads[lo:lo + 128])],
                chunk_size=CHUNK))
        reqs = [ReadReq(chain, cid, 0, CHUNK) for chain, cid in ids]
        return cluster, client, reqs, payloads

    @staticmethod
    def _close(cluster, client):
        client.close()
        client._messenger.close_rings()
        cluster.close()

    @pytest.mark.parametrize("carrier", ["socket", "ring"])
    def test_groups_over_four_frames_come_back_batched(
            self, small_frames, monkeypatch, carrier):
        """Node groups of about 335 chunks, 21 MiB where a frame is 4:
        every op OK with its bytes and none through the single-op ladder
        (all 1340 of them before the spans were bounded); a ring carries
        every span, none falls to a socket."""
        from tpu3fs.client.storage_client import StorageClient

        cluster, client, reqs, payloads = self._cluster(carrier, self.OPS)
        ladder, sockets = [], []
        single = StorageClient._read_chunk_op
        monkeypatch.setattr(
            StorageClient, "_read_chunk_op",
            lambda self, *a, **k: ladder.append(a) or single(
                self, *a, **k))
        rpc = client._messenger._client
        start_call = rpc.start_call
        monkeypatch.setattr(
            rpc, "start_call",
            lambda *a, **k: sockets.append(a) or start_call(*a, **k))
        try:
            groups = []
            pipelined = client._messenger.batch_read_pipelined
            monkeypatch.setattr(
                client._messenger, "batch_read_pipelined",
                lambda g: groups.extend(g) or pipelined(g))
            replies = client.batch_read(reqs)
            assert len(groups) == 4
            assert all(len(ops) * CHUNK > 4 * FRAME for _, ops in groups)
            assert all(r.ok for r in replies)
            assert [bytes(r.data) for r in replies] == payloads
            assert ladder == []
            assert client._read_ladder_ops._value == 0
            if carrier == "ring":
                assert sockets == []
                assert all(ring is not None for ring in
                           client._messenger._usrbio_rings.values())
            else:
                assert len(sockets) > 4 * 4
            del replies
        finally:
            self._close(cluster, client)

    # (ops of 64 KiB but for `big`, which is one op of 2 MiB in the
    # middle; the cap is 1 MiB: 15 ops of 64 KiB + 160 B fit, 16 do not)
    @pytest.mark.parametrize("ops,big,want", [
        (1, None, "parent"),            # one op
        (7, None, "parent"),            # under 2 x the stripe minimum
        (40, None, "parent"),           # 4 stripes under the cap
        (60, None, "parent"),           # 4 stripes of 15: just under
        (61, None, [(0, 15), (15, 30), (30, 45), (45, 60), (60, 61)]),
        (335, None, [(lo, min(lo + 15, 335)) for lo in range(0, 335, 15)]),
        (21, 10, [(0, 10), (10, 11), (11, 21)]),   # an op over the cap
    ], ids=["one-op", "under-2x-min", "4-under-cap", "just-under-cap",
            "just-over-cap", "restore-group", "op-over-cap"])
    def test_span_list(self, ops, big, want):
        from tpu3fs.rpc.services import RpcMessenger
        from tpu3fs.storage.craq import ReadReq
        from tpu3fs.storage.types import ChunkId

        messenger = RpcMessenger(lambda: None)
        messenger._stripe_min_bytes = STRIPE_MIN
        cap = 1 << 20
        sizes = [2 << 20 if i == big else CHUNK for i in range(ops)]
        reqs = [ReadReq(1, ChunkId(1, i), 0, n)
                for i, n in enumerate(sizes)]
        parent = _parent_spans(sizes, 4, STRIPE_MIN)
        if want == "parent":
            want = parent
        assert messenger._stripe_spans(reqs, cap) == want
        assert messenger._stripe_spans(reqs) == parent    # no cap: as ever
        # read-to-end stands at the chunk size
        to_end = [ReadReq(1, ChunkId(1, i), 0, -1, chunk_size=n)
                  for i, n in enumerate(sizes)]
        assert messenger._stripe_spans(to_end, cap) == want
        for lo, hi in want:
            assert hi - lo == 1 \
                or messenger._read_rsp_est(reqs[lo:hi]) <= cap

    def test_the_cap_follows_the_frame_and_the_ring(self, small_frames):
        """Nothing a user sets: MAX_PACKET, the ring's registered buffer
        and READ_STRIPES decide it."""
        from types import SimpleNamespace

        from tpu3fs.rpc.services import READ_STRIPES, RpcMessenger
        from tpu3fs.usrbio.transport import RSP_CTRL_BYTES

        messenger = RpcMessenger(lambda: None)
        on_socket = messenger._read_span_cap(None)
        assert FRAME - FRAME // 32 < on_socket < FRAME

        def ring(size):
            return SimpleNamespace(iov=SimpleNamespace(size=size))

        on_ring = messenger._read_span_cap(ring(FRAME))
        # READ_STRIPES reply regions with their requests, and one more
        # being turned over, fit the buffer side by side
        assert (READ_STRIPES + 1) * (on_ring + RSP_CTRL_BYTES) < FRAME
        assert on_ring > FRAME // (READ_STRIPES + 2)
        # a span that misses the ring goes on a socket: a larger buffer
        # does not lift the cap past a frame
        assert messenger._read_span_cap(ring(64 * FRAME)) == on_socket

    @pytest.mark.parametrize("carrier", ["socket", "ring"])
    def test_at_most_read_stripes_of_a_node_in_flight(
            self, small_frames, monkeypatch, carrier):
        from tpu3fs.rpc.services import READ_STRIPES

        cluster, client, reqs, payloads = self._cluster(carrier, self.OPS)
        messenger = client._messenger
        flying, peak, spans = {}, {}, {}
        start, finish = (messenger._start_read_span,
                         messenger._finish_read_span)

        def started(node_id, plan, span):
            flying[node_id] = flying.get(node_id, 0) + 1
            peak[node_id] = max(peak.get(node_id, 0), flying[node_id])
            spans[node_id] = spans.get(node_id, 0) + 1
            return start(node_id, plan, span)

        def finished(node_id, p, span, detach):
            try:
                return finish(node_id, p, span, detach)
            finally:
                flying[node_id] -= 1

        monkeypatch.setattr(messenger, "_start_read_span", started)
        monkeypatch.setattr(messenger, "_finish_read_span", finished)
        try:
            replies = client.batch_read(reqs)
            assert [bytes(r.data) for r in replies] == payloads
            assert len(peak) == 4
            # every node's group needs more spans than the window holds
            assert all(n > READ_STRIPES for n in spans.values())
            assert set(peak.values()) == {READ_STRIPES}
            assert set(flying.values()) == {0}
            del replies
        finally:
            self._close(cluster, client)

    def test_a_dead_node_s_spans_still_reach_the_ladder(self, monkeypatch):
        """A span that fails for a real reason sends its ops down
        read_chunk, and client.read_ladder_ops counts them."""
        cluster, client, reqs, payloads = self._cluster("socket", 96)
        groups = []
        pipelined = client._messenger.batch_read_pipelined
        monkeypatch.setattr(
            client._messenger, "batch_read_pipelined",
            lambda g: groups.extend(g) or pipelined(g))
        try:
            dead = 10
            cluster.stop_node(dead)
            replies = client.batch_read(reqs)
            lost = [len(ops) for node_id, ops in groups
                    if node_id == dead]
            assert lost and lost[0] > 0
            assert client._read_ladder_ops._value == lost[0]
            assert all(r.ok for r in replies)
            assert [bytes(r.data) for r in replies] == payloads
        finally:
            self._close(cluster, client)

    def test_an_ec_load_keeps_the_ring(self, monkeypatch):
        """An EC shard read names no size on the wire and is estimated at
        1 MiB: a load of 64 blocks is 112 shard reads a node, four
        stripes of 28 MiB by the estimate where the ring's buffer is 16.
        Bounded by the estimate every span rides the ring (the old plan's
        stripes were refused by it and went over sockets, silently)."""
        from rpc_cluster import FILE_ID, RpcCluster
        from tpu3fs.client.storage_client import ReadReq, RetryOptions
        from tpu3fs.rpc import services
        from tpu3fs.storage.types import ChunkId

        monkeypatch.setattr(services, "USRBIO_IOV_BYTES", 16 << 20)
        cluster = RpcCluster(replicas=0, chains=1, size=CHUNK, ec=(12, 4),
                             nodes=4, usrbio=True)
        client = cluster.storage_client(
            retry=RetryOptions(backoff_base_s=0.001))
        chain = cluster.chain_ids[0]
        block = bytes(range(256)) * 144          # 36 KiB: shards 0..6
        try:
            for i in range(64):
                assert client.write_stripe(chain, ChunkId(FILE_ID, i), block,
                                           chunk_size=CHUNK).ok
            reqs = [ReadReq(chain, ChunkId(FILE_ID, i), 0, len(block),
                            chunk_size=CHUNK) for i in range(64)]
            assert all(r.ok for r in client.batch_read(reqs[:2]))  # rings up
            sockets = []
            rpc = client._messenger._client
            start_call = rpc.start_call
            monkeypatch.setattr(
                rpc, "start_call",
                lambda *a, **k: sockets.append(a) or start_call(*a, **k))
            replies = client.batch_read(reqs)
            assert all(r.ok and bytes(r.data) == block for r in replies)
            assert sockets == []
            del replies
        finally:
            self._close(cluster, client)
