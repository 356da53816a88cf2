"""Chunk engine contract tests, run against BOTH engines (mem + native C++),
mirroring the reference's trick of running one suite over multiple stores.
Plus native-only durability tests (WAL replay after close/reopen)."""

import numpy as np
import pytest

from tpu3fs.storage.engine import MemChunkEngine
from tpu3fs.storage.native_engine import NativeChunkEngine, _load_lib
from tpu3fs.storage.types import ChunkId
from tpu3fs.utils.result import Code, FsError
from tpu3fs.ops.crc32c import crc32c

CS = 1 << 16  # chunk size for tests


@pytest.fixture(params=["mem", "native"])
def engine(request, tmp_path):
    if request.param == "mem":
        eng = MemChunkEngine()
    else:
        eng = NativeChunkEngine(str(tmp_path / "engine"))
    yield eng
    eng.close()


def cid(i, j=0):
    return ChunkId(i, j)


class TestEngineContract:
    def test_update_commit_read(self, engine):
        engine.update(cid(1), 1, 1, b"hello", 0, chunk_size=CS)
        with pytest.raises(FsError) as ei:
            engine.read(cid(1))
        assert ei.value.code == Code.CHUNK_NOT_COMMIT  # pending only
        meta = engine.commit(cid(1), 1, 1)
        assert meta.committed_ver == 1 and meta.length == 5
        assert engine.read(cid(1)) == b"hello"
        assert meta.checksum.value == crc32c(b"hello")

    def test_partial_cow_update(self, engine):
        engine.update(cid(1), 1, 1, b"A" * 100, 0, chunk_size=CS)
        engine.commit(cid(1), 1, 1)
        engine.update(cid(1), 2, 1, b"B" * 50, 25, chunk_size=CS)
        # committed content unchanged until commit
        assert engine.read(cid(1)) == b"A" * 100
        engine.commit(cid(1), 2, 1)
        assert engine.read(cid(1)) == b"A" * 25 + b"B" * 50 + b"A" * 25

    def test_version_classification(self, engine):
        engine.update(cid(1), 1, 1, b"x", 0, chunk_size=CS)
        engine.commit(cid(1), 1, 1)
        with pytest.raises(FsError) as ei:
            engine.update(cid(1), 1, 1, b"y", 0, chunk_size=CS)
        assert ei.value.code == Code.CHUNK_STALE_UPDATE
        with pytest.raises(FsError) as ei:
            engine.update(cid(1), 3, 1, b"y", 0, chunk_size=CS)
        assert ei.value.code == Code.CHUNK_MISSING_UPDATE
        engine.update(cid(1), 2, 1, b"y", 0, chunk_size=CS)
        with pytest.raises(FsError) as ei:
            engine.update(cid(1), 3, 1, b"z", 0, chunk_size=CS)
        assert ei.value.code == Code.CHUNK_ADVANCE_UPDATE

    def test_restage_same_pending_idempotent(self, engine):
        engine.update(cid(1), 1, 1, b"first", 0, chunk_size=CS)
        engine.update(cid(1), 1, 1, b"retry", 0, chunk_size=CS)  # same ver
        engine.commit(cid(1), 1, 1)
        assert engine.read(cid(1)) == b"retry"

    def test_duplicate_commit_ok(self, engine):
        engine.update(cid(1), 1, 1, b"x", 0, chunk_size=CS)
        engine.commit(cid(1), 1, 1)
        meta = engine.commit(cid(1), 1, 1)  # duplicate
        assert meta.committed_ver == 1

    def test_full_replace_abandons_pending(self, engine):
        engine.update(cid(1), 1, 1, b"old", 0, chunk_size=CS)
        engine.commit(cid(1), 1, 1)
        engine.update(cid(1), 2, 1, b"pending", 0, chunk_size=CS)
        engine.update(cid(1), 5, 2, b"replaced", 0, full_replace=True,
                      chunk_size=CS)
        meta = engine.get_meta(cid(1))
        assert meta.committed_ver == 5 and meta.pending_ver == 0
        assert engine.read(cid(1)) == b"replaced"

    def test_remove_and_query_prefix(self, engine):
        for i in range(3):
            engine.update(cid(7, i), 1, 1, b"d", 0, chunk_size=CS)
            engine.commit(cid(7, i), 1, 1)
        engine.update(cid(8, 0), 1, 1, b"d", 0, chunk_size=CS)
        engine.commit(cid(8, 0), 1, 1)
        metas = engine.query(ChunkId.file_prefix(7))
        assert [m.chunk_id.index for m in metas] == [0, 1, 2]
        assert engine.remove(cid(7, 1))
        assert not engine.remove(cid(7, 1))  # already gone
        assert [m.chunk_id.index for m in engine.query(ChunkId.file_prefix(7))] == [0, 2]

    def test_truncate(self, engine):
        engine.update(cid(1), 1, 1, b"0123456789", 0, chunk_size=CS)
        engine.commit(cid(1), 1, 1)
        meta = engine.truncate(cid(1), 4, 2)
        assert meta.length == 4
        assert engine.read(cid(1)) == b"0123"
        # extend-truncate zero-fills
        engine.truncate(cid(1), 8, 2)
        assert engine.read(cid(1)) == b"0123\x00\x00\x00\x00"

    def test_read_offsets(self, engine):
        engine.update(cid(1), 1, 1, b"abcdefgh", 0, chunk_size=CS)
        engine.commit(cid(1), 1, 1)
        assert engine.read(cid(1), 2, 3) == b"cde"
        assert engine.read(cid(1), 6) == b"gh"
        assert engine.read(cid(1), 100, 5) == b""  # past end

    def test_oversized_write_rejected(self, engine):
        with pytest.raises(FsError) as ei:
            engine.update(cid(1), 1, 1, b"x" * (CS + 1), 0, chunk_size=CS)
        assert ei.value.code == Code.INVALID_ARG

    def test_used_size(self, engine):
        engine.update(cid(1), 1, 1, b"x" * 1000, 0, chunk_size=CS)
        engine.commit(cid(1), 1, 1)
        assert engine.used_size() == 1000

    def test_large_random_roundtrip(self, engine):
        rng = np.random.default_rng(0)
        blob = rng.integers(0, 256, 50_000).astype("u1").tobytes()
        engine.update(cid(2), 1, 1, blob, 0, chunk_size=1 << 20)
        engine.commit(cid(2), 1, 1)
        assert engine.read(cid(2)) == blob
        assert engine.get_meta(cid(2)).checksum.value == crc32c(blob)


class TestNativeDurability:
    def test_wal_replay_after_reopen(self, tmp_path):
        path = str(tmp_path / "e")
        eng = NativeChunkEngine(path)
        eng.update(cid(1), 1, 7, b"persist-me", 0, chunk_size=CS)
        eng.commit(cid(1), 1, 7)
        eng.update(cid(2), 1, 7, b"pending-only", 0, chunk_size=CS)
        eng.close()
        eng2 = NativeChunkEngine(path)
        assert eng2.read(cid(1)) == b"persist-me"
        meta = eng2.get_meta(cid(2))
        assert meta.pending_ver == 1 and meta.committed_ver == 0
        eng2.commit(cid(2), 1, 7)  # pending survives restart and can commit
        assert eng2.read(cid(2)) == b"pending-only"
        eng2.close()

    def test_torn_wal_tail_ignored(self, tmp_path):
        path = str(tmp_path / "e")
        eng = NativeChunkEngine(path)
        eng.update(cid(1), 1, 1, b"good", 0, chunk_size=CS)
        eng.commit(cid(1), 1, 1)
        eng.close()
        with open(path + "/wal.log", "ab") as f:
            f.write(b"\x01\x02torn-garbage")
        eng2 = NativeChunkEngine(path)
        assert eng2.read(cid(1)) == b"good"
        eng2.close()

    def test_compaction_preserves_state(self, tmp_path):
        path = str(tmp_path / "e")
        eng = NativeChunkEngine(path)
        for ver in range(1, 30):
            eng.update(cid(1), ver, 1, bytes([ver]) * 64, 0, chunk_size=CS)
            eng.commit(cid(1), ver, 1)
        eng.compact()
        eng.close()
        eng2 = NativeChunkEngine(path)
        assert eng2.read(cid(1)) == bytes([29]) * 64
        assert eng2.get_meta(cid(1)).committed_ver == 29
        eng2.close()

    def test_native_crc_matches_python(self):
        lib = _load_lib()
        data = b"The quick brown fox jumps over the lazy dog"
        assert lib.ce_crc32c(data, len(data)) == crc32c(data)

    def test_block_reuse_after_remove(self, tmp_path):
        import os

        path = str(tmp_path / "e")
        eng = NativeChunkEngine(path)
        for i in range(20):
            eng.update(cid(1, i), 1, 1, b"z" * 4096, 0, chunk_size=CS)
            eng.commit(cid(1, i), 1, 1)
        size_before = os.path.getsize(path + "/data_0.bin")
        for i in range(20):
            eng.remove(cid(1, i))
        for i in range(20):
            eng.update(cid(2, i), 1, 1, b"w" * 4096, 0, chunk_size=CS)
            eng.commit(cid(2, i), 1, 1)
        # freed blocks were reused: the class file did not grow
        assert os.path.getsize(path + "/data_0.bin") <= size_before * 2
        assert eng.read(cid(2, 5)) == b"w" * 4096
        eng.close()


class TestNativeFabric:
    def test_cluster_on_native_engine(self, tmp_path):
        from tpu3fs.fabric import Fabric, SystemSetupConfig
        from tpu3fs.meta import OpenFlags

        fab = Fabric(SystemSetupConfig(num_storage_nodes=2, num_chains=2,
                                       num_replicas=2, chunk_size=4096,
                                       engine="native"))
        fio = fab.file_client()
        res = fab.meta.create("/f", flags=OpenFlags.WRITE, client_id="c",
                              stripe=2)
        blob = np.random.default_rng(1).integers(0, 256, 20_000).astype("u1").tobytes()
        fio.write(res.inode, 0, blob)
        inode = fab.meta.close(res.inode.id, res.session_id)
        assert inode.length == len(blob)
        assert fio.read(inode, 0, len(blob)) == blob


class TestRegressionFixes:
    def test_rejected_update_leaves_no_phantom(self, engine):
        """A rejected chain-internal update must not materialize an empty
        chunk (which would turn holes into spurious CHUNK_NOT_COMMIT)."""
        with pytest.raises(FsError) as ei:
            engine.update(cid(42), 5, 1, b"late", 0, chunk_size=CS)
        assert ei.value.code == Code.CHUNK_MISSING_UPDATE
        assert engine.get_meta(cid(42)) is None
        with pytest.raises(FsError) as ei:
            engine.read(cid(42))
        assert ei.value.code == Code.CHUNK_NOT_FOUND

    def test_removed_base_chunk_not_resurrected_by_failed_install(
            self, tmp_path):
        """Round-5 advisor (high): compact() makes a chunk base-resident;
        remove() then masks it via dead_. A failed VALIDATED install
        (wrong CRC) pins the key — erasing the dead_ mask — and the
        refusal path must restore the mask, or the next lookup would
        resurrect the removed chunk from the base with block refs that
        remove() already freed (reads of another chunk's data, later
        double-free)."""
        eng = NativeChunkEngine(str(tmp_path / "eng"))
        try:
            data = b"v" * 256
            eng.update(cid(7), 1, 1, data, 0, full_replace=True,
                       chunk_size=CS)
            eng.compact()          # chunk 7 is now base-resident
            assert eng.remove(cid(7))
            assert eng.get_meta(cid(7)) is None
            # wrong-CRC validated install (the EC shard-install shape)
            with pytest.raises(FsError) as ei:
                eng.update(cid(7), 2, 1, data, 0, stage_replace=True,
                           chunk_size=CS,
                           expected_crc=(crc32c(data) ^ 0xDEAD))
            assert ei.value.code == Code.CHUNK_CHECKSUM_MISMATCH
            # the regression: E_NOT_FOUND, not the resurrected base record
            assert eng.get_meta(cid(7)) is None
            assert all(m.chunk_id != cid(7) for m in eng.all_metadata())
            with pytest.raises(FsError):
                eng.read(cid(7))
            # a second remove must be a no-op, not a double free
            assert not eng.remove(cid(7))
            # and a correct install over the removed key works cleanly
            meta = eng.update(cid(7), 3, 1, data, 0, full_replace=True,
                              chunk_size=CS, expected_crc=crc32c(data))
            assert meta.committed_ver == 3
            assert eng.read(cid(7)) == data
        finally:
            eng.close()

    def test_cow_failure_after_pin_restores_dead_mask(self, tmp_path):
        """The COW-mode (mode 0) flavor of the same leak: a post-pin
        refusal during a plain chain update on a removed base-resident
        key must also drop the phantom + restore the dead_ mask."""
        eng = NativeChunkEngine(str(tmp_path / "eng"))
        try:
            data = b"w" * 64
            eng.update(cid(8), 1, 1, data, 0, full_replace=True,
                       chunk_size=CS)
            eng.compact()
            assert eng.remove(cid(8))
            # COW update at cv+1 passes the version algebra, pins the key,
            # then the validated-install CRC check refuses post-pin
            with pytest.raises(FsError) as ei:
                eng.update(cid(8), 1, 1, data, 0, chunk_size=CS,
                           expected_crc=(crc32c(data) ^ 1))
            assert ei.value.code == Code.CHUNK_CHECKSUM_MISMATCH
            assert eng.get_meta(cid(8)) is None
            assert not eng.remove(cid(8))
        finally:
            eng.close()

    def test_empty_file_reads_empty(self):
        from tpu3fs.fabric import Fabric, SystemSetupConfig
        from tpu3fs.meta import OpenFlags

        fab = Fabric(SystemSetupConfig(num_storage_nodes=2, num_chains=1,
                                       num_replicas=2, chunk_size=4096))
        fio = fab.file_client()
        res = fab.meta.create("/empty", flags=OpenFlags.WRITE, client_id="c")
        inode = fab.meta.close(res.inode.id, res.session_id)
        assert fio.read(inode, 0, 4096) == b""  # EOF, not fabricated zeros


class TestPendingIndex:
    """pending_metas() is the healthy-chain EC repair probe: it must be
    exact across stage/commit/remove/replay and O(pendings) by design
    (MemChunkEngine keeps a key set; the native engine an in-engine
    std::set surfaced via ce_query_pending)."""

    def _exercise(self, eng):
        from tpu3fs.storage.types import ChunkId

        eng.update(ChunkId(5, 0), 1, 1, b"a" * 64, 0, chunk_size=4096)
        eng.update(ChunkId(5, 1), 1, 1, b"b" * 64, 0, chunk_size=4096,
                   stage_replace=True)
        assert sorted(m.chunk_id.index for m in eng.pending_metas()) == [0, 1]
        eng.commit(ChunkId(5, 0), 1, 1)
        assert [m.chunk_id.index for m in eng.pending_metas()] == [1]
        eng.remove(ChunkId(5, 1))
        assert eng.pending_metas() == []

    def test_mem_engine(self):
        from tpu3fs.storage.engine import MemChunkEngine

        self._exercise(MemChunkEngine())

    def test_native_engine_with_replay(self, tmp_path):
        from tpu3fs.storage.native_engine import NativeChunkEngine
        from tpu3fs.storage.types import ChunkId

        try:
            eng = NativeChunkEngine(str(tmp_path))
        except Exception:
            import pytest

            pytest.skip("native engine unavailable")
        self._exercise(eng)
        # a staged-but-uncommitted pending must survive reopen (WAL replay
        # rebuilds the index)
        eng.update(ChunkId(6, 0), 1, 1, b"c" * 64, 0, chunk_size=4096,
                   stage_replace=True)
        eng.close()
        eng2 = NativeChunkEngine(str(tmp_path))
        pm = eng2.pending_metas()
        assert len(pm) == 1 and pm[0].pending_ver == 1
        eng2.close()


class TestPagedMetaIndex:
    """The mmap'd base-run + delta metadata design (round-4 verdict #5):
    state survives rewrites and reopens exactly, counters stay O(1)-exact,
    and the CI-sized soak keeps RSS growth and reopen time bounded."""

    def test_rewrite_reopen_exactness(self, tmp_path):
        from tpu3fs.storage.native_engine import NativeChunkEngine
        from tpu3fs.storage.types import ChunkId

        try:
            eng = NativeChunkEngine(str(tmp_path))
        except Exception:
            import pytest

            pytest.skip("native engine unavailable")
        N = 500
        for i in range(N):
            eng.update(ChunkId(3, i), 1, 1, bytes([i & 0xFF]) * (50 + i),
                       0, chunk_size=4096)
            eng.commit(ChunkId(3, i), 1, 1)
        for i in range(0, N, 5):
            eng.remove(ChunkId(3, i))
        eng.update(ChunkId(4, 0), 9, 1, b"p" * 32, 0, chunk_size=4096,
                   stage_replace=True)
        want = (len(eng.all_metadata()), eng.used_size(),
                [m.chunk_id.index for m in eng.pending_metas()])
        eng.compact()  # base rewrite
        assert (len(eng.all_metadata()), eng.used_size(),
                [m.chunk_id.index for m in eng.pending_metas()]) == want
        # delta over the fresh base: overwrite + erase base-resident keys
        eng.update(ChunkId(3, 1), 2, 2, b"v2" * 40, 0, chunk_size=4096)
        eng.commit(ChunkId(3, 1), 2, 2)
        eng.remove(ChunkId(3, 2))
        eng.close()
        eng2 = NativeChunkEngine(str(tmp_path))
        assert eng2.read(ChunkId(3, 1)) == b"v2" * 40
        assert eng2.get_meta(ChunkId(3, 2)) is None
        assert eng2.get_meta(ChunkId(3, 3)).committed_ver == 1
        assert len(eng2.pending_metas()) == 1
        # ordered query merges base + delta in key order
        metas = eng2.all_metadata()
        keys = [m.chunk_id.to_bytes() for m in metas]
        assert keys == sorted(keys)
        assert want[0] == len(metas) + 1  # -overwrite no, -removed 1
        eng2.close()

    @staticmethod
    def _soak(chunks: int, payload: int) -> dict:
        """Create+commit `chunks` small chunks through the batched engine
        API, reopen, spot-verify -> RSS growth, reopen time, used bytes:
        the two bounds the design claims are that the delta cap, not the
        chunk count, determines resident metadata, and that a reopen is
        one pass over the base run plus a bounded WAL window."""
        import shutil
        import tempfile
        import time

        from tpu3fs.storage.engine import EngineUpdateOp
        from tpu3fs.storage.native_engine import NativeChunkEngine
        from tpu3fs.storage.types import ChunkId

        def rss_mb() -> float:
            with open("/proc/self/status") as f:
                for line in f:
                    if line.startswith("VmRSS:"):
                        return int(line.split()[1]) / 1024.0
            return 0.0

        d = tempfile.mkdtemp(prefix="engine-soak-")
        try:
            rss0 = rss_mb()
            eng = NativeChunkEngine(d)
            blob = b"\x5a" * payload
            peak = 0.0
            batch = 512
            for base in range(0, chunks, batch):
                n = min(batch, chunks - base)
                ops = [EngineUpdateOp(chunk_id=ChunkId(7, base + j),
                                      data=blob, offset=0, update_ver=1,
                                      chunk_size=4096)
                       for j in range(n)]
                assert all(r.ok for r in eng.batch_update(ops, 1))
                assert all(r.ok for r in eng.batch_commit(
                    [(ChunkId(7, base + j), 1) for j in range(n)], 1))
                if (base // batch) % 256 == 0:
                    peak = max(peak, rss_mb())
            peak = max(peak, rss_mb())
            count = len(eng.all_metadata())
            eng.close()

            t0 = time.perf_counter()
            eng2 = NativeChunkEngine(d)
            reopen_s = time.perf_counter() - t0
            # spot-verify across the whole id range after reopen
            for cid in (0, chunks // 2, chunks - 1):
                assert eng2.read(ChunkId(7, cid)) == blob, cid
            assert len(eng2.all_metadata()) == count
            used = eng2.used_size()
            eng2.close()
        finally:
            shutil.rmtree(d, ignore_errors=True)
        return {"rss_growth_mb": peak - rss0, "reopen_s": reopen_s,
                "used_bytes": used}

    def test_ci_sized_soak_bounds(self):
        import pytest

        try:
            out = self._soak(60_000, payload=64)
        except Exception as e:
            pytest.skip(f"native engine unavailable: {e!r}")
        # bounded RSS: resident growth stays far below the full-index
        # footprint (60k metas would be ~6 MB as a std::map; the bound
        # here allows delta + allocator + noise)
        assert out["rss_growth_mb"] < 60, out
        assert out["reopen_s"] < 2.0, out
        assert out["used_bytes"] == 60_000 * 64
