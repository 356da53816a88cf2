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
