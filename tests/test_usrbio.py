"""USRBIO tests: ring ABI, batched IO through the agent against a real
cluster, cross-thread wakeups (mirrors tests/fuse/usrbio.py intent)."""

import threading

import numpy as np
import pytest

from tpu3fs.fabric import Fabric, SystemSetupConfig
from tpu3fs.rpc import deadline as dl
from tpu3fs.tenant import tenant_scope
from tpu3fs.usrbio import Iov, IoRing, UsrbioAgent, UsrbioClient
from tpu3fs.utils.result import Code, FsError


@pytest.fixture
def cluster():
    fab = Fabric(SystemSetupConfig(num_storage_nodes=2, num_chains=2,
                                   num_replicas=2, chunk_size=4096))
    agent = UsrbioAgent(fab.meta, fab.file_client())
    client = UsrbioClient(agent)
    yield fab, agent, client
    agent.stop()


class TestRingAbi:
    def test_sqe_cqe_roundtrip(self):
        ring = IoRing(8, create=True)
        try:
            assert ring.prep_io(0, 100, 4096, 5, read=True, userdata=42) == 0
            assert ring.prep_io(128, 50, 0, 5, read=False, userdata=43) == 1
            sqes = ring.drain_sqes()
            assert len(sqes) == 2
            assert sqes[0].is_read and sqes[0].length == 100
            assert sqes[0].file_offset == 4096 and sqes[0].userdata == 42
            assert not sqes[1].is_read
            ring.push_cqe(100, 42)
            out = ring.wait_for_ios(1, timeout=1)
            assert out == [(100, 42)]
        finally:
            ring.close(unlink=True)

    def test_ring_full_until_reaped(self):
        ring = IoRing(2, create=True)
        try:
            assert ring.prep_io(0, 1, 0, 1, read=True) == 0
            assert ring.prep_io(0, 1, 0, 1, read=True) == 1
            assert ring.prep_io(0, 1, 0, 1, read=True) == -1  # full
            # agent progress alone does NOT free capacity: in-flight ops are
            # bounded until their completions are reaped
            for sqe in ring.drain_sqes():
                ring.push_cqe(1, sqe.userdata)
            assert ring.prep_io(0, 1, 0, 1, read=True) == -1
            ring.reap()
            assert ring.prep_io(0, 1, 0, 1, read=True) >= 0  # space again
        finally:
            ring.close(unlink=True)

    def test_shm_visible_across_opens(self):
        iov = Iov(4096, create=True)
        try:
            iov.write(100, b"cross-mapping")
            other = Iov(4096, name=iov.name, create=False)
            assert other.read(100, 13) == b"cross-mapping"
            other.close()
        finally:
            iov.close(unlink=True)


class TestUsrbioEndToEnd:
    def test_write_then_read_batch(self, cluster):
        fab, agent, client = cluster
        iov = client.iovcreate(1 << 20)
        ring = client.iorcreate(32, [iov], for_read=False)
        fd = client.reg_fd("/data.bin", write=True)
        rng = np.random.default_rng(0)
        blob = rng.integers(0, 256, 40_000).astype("u1").tobytes()
        # stage the payload in the shared buffer, submit 4 batched writes
        step = 10_000
        for i in range(4):
            iov.write(i * step, blob[i * step : (i + 1) * step])
            client.prep_io(ring, iov, i * step, step, fd, i * step,
                           read=False, userdata=i)
        client.submit_ios(ring)
        done = client.wait_for_ios(ring, 4, timeout=10)
        assert sorted(ud for _, ud in done) == [0, 1, 2, 3]
        assert all(res == step for res, _ in done)
        client.dereg_fd(fd, length_hint=len(blob))
        # read it back through a read ring into a fresh buffer region
        fd = client.reg_fd("/data.bin")
        rring = client.iorcreate(32, [iov], for_read=True)
        for i in range(4):
            client.prep_io(rring, iov, 512 * 1024 + i * step, step, fd,
                           i * step, read=True, userdata=10 + i)
        client.submit_ios(rring)
        done = client.wait_for_ios(rring, 4, timeout=10)
        assert all(res == step for res, _ in done)
        got = iov.read(512 * 1024, len(blob))
        assert got == blob
        client.iordestroy(ring)
        client.iordestroy(rring)
        client.iovdestroy(iov)

    def test_read_past_eof_short(self, cluster):
        fab, agent, client = cluster
        iov = client.iovcreate(8192)
        ring = client.iorcreate(8, [iov])
        fd = client.reg_fd("/small", write=True)
        iov.write(0, b"tiny")
        client.prep_io(ring, iov, 0, 4, fd, 0, read=False)
        client.submit_ios(ring)
        client.wait_for_ios(ring, 1, timeout=5)
        client.prep_io(ring, iov, 1024, 4096, fd, 0, read=True, userdata=9)
        client.submit_ios(ring)
        done = client.wait_for_ios(ring, 1, timeout=5)
        assert done[0][0] == 4  # short read at EOF
        assert iov.read(1024, 4) == b"tiny"
        client.iordestroy(ring)
        client.iovdestroy(iov)

    def test_close_fd_moves_mtime_only_after_writes(self, cluster):
        import time as _time

        fab, agent, client = cluster
        iov = client.iovcreate(4096)
        ring = client.iorcreate(8, [iov], for_read=False)
        fd = client.reg_fd("/mt.bin", write=True)
        iov.write(0, b"data")
        client.prep_io(ring, iov, 0, 4, fd, 0, read=False)
        client.submit_ios(ring)
        client.wait_for_ios(ring, 1, timeout=5)
        client.dereg_fd(fd, length_hint=4)
        m1 = fab.meta.stat("/mt.bin").mtime
        # read-only open+close must not look like a modification
        _time.sleep(0.02)
        fd = client.reg_fd("/mt.bin")
        client.dereg_fd(fd)
        assert fab.meta.stat("/mt.bin").mtime == m1
        # another write session must move it
        _time.sleep(0.02)
        fd = client.reg_fd("/mt.bin", write=True)
        client.prep_io(ring, iov, 0, 4, fd, 4, read=False)
        client.submit_ios(ring)
        client.wait_for_ios(ring, 1, timeout=5)
        client.dereg_fd(fd, length_hint=8)
        assert fab.meta.stat("/mt.bin").mtime > m1
        client.iordestroy(ring)
        client.iovdestroy(iov)

    def test_bad_fd_reports_error_cqe(self, cluster):
        fab, agent, client = cluster
        iov = client.iovcreate(4096)
        ring = client.iorcreate(8, [iov])
        client.prep_io(ring, iov, 0, 10, 9999, 0, read=True, userdata=1)
        client.submit_ios(ring)
        done = client.wait_for_ios(ring, 1, timeout=5)
        assert done[0][0] == -int(Code.META_NOT_FOUND)
        client.iordestroy(ring)
        client.iovdestroy(iov)

    def test_oob_iov_offset_rejected(self, cluster):
        fab, agent, client = cluster
        iov = client.iovcreate(4096)
        ring = client.iorcreate(8, [iov])
        fd = client.reg_fd("/x", write=True)
        client.prep_io(ring, iov, 4000, 1000, fd, 0, read=False, userdata=2)
        client.submit_ios(ring)
        done = client.wait_for_ios(ring, 1, timeout=5)
        assert done[0][0] == -int(Code.INVALID_ARG)
        client.iordestroy(ring)
        client.iovdestroy(iov)

    def test_concurrent_submitters(self, cluster):
        fab, agent, client = cluster
        iov = client.iovcreate(1 << 16)
        ring = client.iorcreate(64, [iov], for_read=False)
        fd = client.reg_fd("/conc", write=True)
        lock = threading.Lock()

        def submit(i):
            with lock:  # SQ is single-producer; serialize preps
                iov.write(i * 100, bytes([i]) * 100)
                client.prep_io(ring, iov, i * 100, 100, fd, i * 100,
                               read=False, userdata=i)
                client.submit_ios(ring)

        threads = [threading.Thread(target=submit, args=(i,)) for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        done = client.wait_for_ios(ring, 16, timeout=10)
        assert len(done) == 16 and all(res == 100 for res, _ in done)
        client.dereg_fd(fd, length_hint=1600)
        inode = fab.meta.stat("/conc")
        data = fab.file_client().read(inode, 0, 1600)
        for i in range(16):
            assert data[i * 100 : (i + 1) * 100] == bytes([i]) * 100
        client.iordestroy(ring)
        client.iovdestroy(iov)


class TestRingBackpressure:
    def test_unreaped_cqes_never_overwritten(self):
        ring = IoRing(4, create=True)
        try:
            for i in range(4):
                assert ring.prep_io(0, 1, 0, 1, read=True, userdata=100 + i) >= 0
            for sqe in ring.drain_sqes():
                ring.push_cqe(7, sqe.userdata)
            # SQ slots freed, but CQEs unreaped: further preps must refuse
            # (in-flight bounded by entries) so completions are never lost
            assert ring.prep_io(0, 1, 0, 1, read=True, userdata=200) == -1
            got = sorted(ud for _, ud in ring.reap())
            assert got == [100, 101, 102, 103]
            assert ring.prep_io(0, 1, 0, 1, read=True, userdata=200) >= 0
        finally:
            ring.close(unlink=True)


class TestReadInto:
    """read_into: replies land directly in a caller buffer (the zero-copy
    USRBIO read path) with read()-identical hole/EOF semantics."""

    def test_read_into_matches_read_with_holes(self):
        from tpu3fs.fabric.fabric import Fabric, SystemSetupConfig
        from tpu3fs.meta.store import OpenFlags

        fab = Fabric(SystemSetupConfig(num_chains=2, chunk_size=4096))
        fio = fab.file_client()
        res = fab.meta.create("/ri", flags=OpenFlags.WRITE, client_id="c")
        # chunk 0 written, chunk 1 is a hole, chunk 2 short
        fio.write(res.inode, 0, b"A" * 4096)
        fio.write(res.inode, 8192, b"B" * 100)
        inode = fab.meta.stat("/ri")
        want = fio.read(inode, 0, 3 * 4096)
        buf = bytearray(3 * 4096)
        n = fio.read_into(inode, 0, 3 * 4096, memoryview(buf))
        assert bytes(buf[:n]) == want
        # EC files take the same path
        fab2 = Fabric(SystemSetupConfig(
            num_storage_nodes=4, num_chains=1, chunk_size=12 << 10,
            ec_k=3, ec_m=1))
        fio2 = fab2.file_client()
        res2 = fab2.meta.create("/ri2", flags=OpenFlags.WRITE, client_id="c")
        payload = bytes(range(256)) * 96         # 2 stripes
        fio2.write(res2.inode, 0, payload)
        inode2 = fab2.meta.stat("/ri2")
        buf2 = bytearray(len(payload))
        n2 = fio2.read_into(inode2, 0, len(payload), memoryview(buf2))
        assert n2 == len(payload) and bytes(buf2) == payload


# -- the batched drain ---------------------------------------------------------

CS = 4096          # the cluster fixture's chunk size
LEN_A = 3 * CS + 1500


def _lay_files(fab):
    """/a: 3.4 chunks of bytes over both chains; /h: chunk 0, a hole where
    chunk 1 would be, a short chunk 2."""
    from tpu3fs.meta.store import OpenFlags

    fio = fab.file_client()
    rng = np.random.default_rng(7)
    a = rng.integers(0, 256, LEN_A, dtype=np.uint8).tobytes()
    res = fab.meta.create("/a", flags=OpenFlags.WRITE, client_id="t")
    fio.write(res.inode, 0, a)
    fab.meta.close(res.inode.id, res.session_id, length_hint=len(a),
                   wrote=True)
    res = fab.meta.create("/h", flags=OpenFlags.WRITE, client_id="t")
    fio.write(res.inode, 0, b"H" * CS)
    fio.write(res.inode, 2 * CS, b"T" * 100)
    fab.meta.close(res.inode.id, res.session_id, length_hint=2 * CS + 100,
                   wrote=True)


#: name -> [(file or a bad SQE's kind, file offset, length)]: one drain
DRAINS = {
    "mixed_files": [("/a", 0, 512), ("/h", 0, 512), ("/a", 5000, 512),
                    ("/h", 2 * CS, 64), ("/a", 9000, 100)],
    "chunk_and_stripe_boundaries": [
        ("/a", CS - 96, 200),            # chunk 0 -> 1: the other chain
        ("/a", 2 * CS - 200, 400),       # chunk 1 -> 2: back to the first
        ("/a", 100, 3 * CS),             # four chunks of one range
    ],
    "across_eof": [("/a", LEN_A - 100, CS), ("/a", LEN_A, 64),
                   ("/a", 0, 64)],
    "hole": [("/h", CS, CS), ("/h", CS - 10, 30), ("/h", 2 * CS - 8, 200)],
    "a_bad_sqe_fails_alone": [("/a", 0, 256), ("unknown_fd", 0, 256),
                              ("/a", 256, 256), ("iov_overflow", 0, 256),
                              ("/h", 0, 256)],
}


class TestBatchedDrain:
    """A drain of a read ring is ONE batch, held to the serial reference:
    the same (fd, offset, length, slot) answered one by one through
    FileIoClient.read give the same CQE results and the same bytes."""

    SLOT = 4 * CS

    def _submit(self, client, ring, iov, fds, sqes):
        for i, (what, off, n) in enumerate(sqes):
            fd, slot = fds.get(what, 9999), i * self.SLOT
            if what == "iov_overflow":
                fd, slot = fds["/a"], iov.size - n + 1
            client.prep_io(ring, iov, slot, n, fd, off, read=True,
                           userdata=1000 + i)
        client.submit_ios(ring)

    def _reference(self, fab, sqes):
        fio = fab.file_client()
        want = []
        for what, off, n in sqes:
            if what == "unknown_fd":
                want.append((-int(Code.META_NOT_FOUND), b""))
            elif what == "iov_overflow":
                want.append((-int(Code.INVALID_ARG), b""))
            else:
                data = bytes(fio.read(fab.meta.stat(what), off, n))
                want.append((len(data), data))
        return want

    @pytest.mark.parametrize("case", sorted(DRAINS))
    def test_a_drain_equals_the_serial_reads(self, cluster, case):
        fab, agent, client = cluster
        _lay_files(fab)
        sqes = DRAINS[case]
        iov = client.iovcreate(len(sqes) * self.SLOT)
        ring = client.iorcreate(16, [iov], io_depth=len(sqes))
        fds = {p: client.reg_fd(p) for p in ("/a", "/h")}
        calls = {"stat": 0, "read": 0}
        inner_stat, inner_read = agent._meta.batch_stat, \
            agent._fio.storage.batch_read

        def batch_stat(ids):
            calls["stat"] += 1
            return inner_stat(ids)

        def batch_read(reqs):
            calls["read"] += 1
            return inner_read(reqs)

        agent._meta.batch_stat = batch_stat
        agent._fio.storage.batch_read = batch_read
        try:
            iov.write(0, b"\xEE" * iov.size)
            self._submit(client, ring, iov, fds, sqes)
            done = dict((ud, res) for res, ud in
                        client.wait_for_ios(ring, len(sqes), timeout=10))
        finally:
            agent._meta.batch_stat = inner_stat
            agent._fio.storage.batch_read = inner_read
        want = self._reference(fab, sqes)
        assert sorted(done) == [1000 + i for i in range(len(sqes))]
        for i, (res, data) in enumerate(want):
            assert done[1000 + i] == res, (case, i)
            if res > 0:
                assert iov.read(i * self.SLOT, res) == data, (case, i)
        # one batch_stat and one batch_read for the whole drain
        assert calls == {"stat": 1, "read": 1}
        assert agent.totals["batches"] == 1
        assert agent.totals["sqes"] == len(sqes)
        assert agent.totals["sqe_errors"] == sum(r < 0 for r, _ in want)
        client.iordestroy(ring)
        client.iovdestroy(iov)

    def test_a_batch_after_a_write_reads_the_new_bytes(self, cluster):
        fab, agent, client = cluster
        _lay_files(fab)
        iov = client.iovcreate(8 * CS)
        ring = client.iorcreate(8, [iov], io_depth=3)
        fd = client.reg_fd("/a", write=True)
        # ONE drain: a read, a write over part of its range, the read again
        iov.write(0, b"\x00" * iov.size)
        iov.write(CS, b"new!" * 64)
        client.prep_io(ring, iov, 0, 512, fd, CS - 100, read=True, userdata=1)
        client.prep_io(ring, iov, CS, 256, fd, CS - 50, read=False,
                       userdata=2)
        client.prep_io(ring, iov, 2 * CS, 512, fd, CS - 100, read=True,
                       userdata=3)
        client.submit_ios(ring)
        done = dict((ud, res) for res, ud in
                    client.wait_for_ios(ring, 3, timeout=10))
        assert done == {1: 512, 2: 256, 3: 512}
        old, new = iov.read(0, 512), iov.read(2 * CS, 512)
        assert new[50:306] == b"new!" * 64 and old[50:306] != new[50:306]
        assert new[:50] == old[:50] and new[306:] == old[306:]
        # and a later batch through a fresh reader agrees
        data = bytes(fab.file_client().read(fab.meta.stat("/a"), CS - 100,
                                            512))
        assert data == new
        client.iordestroy(ring)
        client.iovdestroy(iov)


class TestIoDepth:
    """hf3fs_iorcreate's io_depth through the agent: 0 serves at once,
    N > 0 holds a drain until N are queued, N < 0 serves a short batch
    after the wait."""

    def _ring(self, cluster, entries, io_depth):
        fab, agent, client = cluster
        _lay_files(fab)
        iov = client.iovcreate(entries * 64)
        ring = client.iorcreate(entries, [iov], io_depth=io_depth)
        return agent, client, iov, ring, client.reg_fd("/a")

    def _prep(self, client, ring, iov, fd, lo, hi):
        for i in range(lo, hi):
            client.prep_io(ring, iov, (i % ring.entries) * 64, 64, fd,
                           i * 64, read=True, userdata=i)
        client.submit_ios(ring)

    def test_zero_serves_what_is_there_at_once(self, cluster):
        agent, client, iov, ring, fd = self._ring(cluster, 16, 0)
        self._prep(client, ring, iov, fd, 0, 3)
        done = client.wait_for_ios(ring, 3, timeout=5)
        assert sorted(ud for _, ud in done) == [0, 1, 2]
        assert agent.totals["short_drains"] == 0
        client.iordestroy(ring)
        client.iovdestroy(iov)

    def test_n_holds_a_drain_until_n_are_queued(self, cluster):
        agent, client, iov, ring, fd = self._ring(cluster, 16, 8)
        self._prep(client, ring, iov, fd, 0, 7)
        assert client.wait_for_ios(ring, 1, timeout=0.4) == []
        assert agent.totals["batches"] == 0
        self._prep(client, ring, iov, fd, 7, 8)
        done = client.wait_for_ios(ring, 8, timeout=5)
        assert sorted(ud for _, ud in done) == list(range(8))
        assert all(res == 64 for res, _ in done)
        # sixteen queued at once are two drains of eight, none short
        self._prep(client, ring, iov, fd, 8, 24)
        done = client.wait_for_ios(ring, 16, timeout=5)
        assert sorted(ud for _, ud in done) == list(range(8, 24))
        assert agent.totals["batches"] == 3
        assert agent.totals["sqes"] == 24
        assert agent.totals["short_drains"] == 0
        client.iordestroy(ring)
        client.iovdestroy(iov)

    def test_negative_n_serves_a_short_batch_after_the_wait(self, cluster):
        agent, client, iov, ring, fd = self._ring(cluster, 32, -8)
        self._prep(client, ring, iov, fd, 0, 3)
        done = client.wait_for_ios(ring, 3, timeout=5)
        assert sorted(ud for _, ud in done) == [0, 1, 2]
        assert agent.totals["batches"] == 1
        # twenty at once: no drain holds more than eight
        self._prep(client, ring, iov, fd, 3, 23)
        done = client.wait_for_ios(ring, 20, timeout=5)
        assert sorted(ud for _, ud in done) == list(range(3, 23))
        assert agent.totals["batches"] >= 1 + 3
        assert agent.totals["short_drains"] == 0   # counted for N > 0 only
        client.iordestroy(ring)
        client.iovdestroy(iov)

    def test_a_depth_the_ring_can_never_fill_is_refused(self, cluster):
        fab, agent, client = cluster
        iov = client.iovcreate(4096)
        with pytest.raises(FsError) as ei:
            client.iorcreate(8, [iov], io_depth=9)
        assert ei.value.code == Code.INVALID_ARG
        client.iovdestroy(iov)

    def test_one_semaphore_post_a_batch(self, cluster):
        import time

        agent, client, iov, ring, fd = self._ring(cluster, 16, 8)
        self._prep(client, ring, iov, fd, 0, 8)
        deadline = time.time() + 5
        while ring._counters()[3] < 8 and time.time() < deadline:
            time.sleep(0.01)
        assert ring._counters()[3] == 8
        posts = 0
        while ring.complete_sem.wait(timeout=0.05):
            posts += 1
        assert posts == 1
        assert len(ring.reap()) == 8
        client.iordestroy(ring)
        client.iovdestroy(iov)

    def test_depth_reaches_the_agent_through_the_3fs_virt_target(self):
        from tpu3fs.fuse.ops import VIRT_DIR, FuseOps

        fab = Fabric()
        fio = fab.file_client()
        agent = UsrbioAgent(fab.meta, fio)
        ops = FuseOps(fab.meta, fio, agent)
        iov = Iov(1 << 12, create=True)
        rings = [IoRing(16, create=True) for _ in range(2)]
        try:
            ops.symlink(iov.name, f"/{VIRT_DIR}/iovs/v0")
            ops.symlink(f"{rings[0].name}?entries=16&rw=r&prio=1&depth=4"
                        f"&iov=v0", f"/{VIRT_DIR}/iors/deep")
            ops.symlink(f"{rings[1].name}?entries=16&rw=r&prio=1&iov=v0",
                        f"/{VIRT_DIR}/iors/old")
            assert agent._rings[rings[0].name].io_depth == 4
            assert agent._rings[rings[1].name].io_depth == 0
        finally:
            ops.destroy()
            for r in rings:
                r.close(unlink=True)
            iov.close(unlink=True)


class TestWriteRingsUnchanged:
    def test_each_write_gets_its_cqe_in_ring_order(self, cluster):
        fab, agent, client = cluster
        iov = client.iovcreate(1 << 16)
        ring = client.iorcreate(16, [iov], for_read=False)
        fd = client.reg_fd("/w", write=True)
        for i in range(6):
            iov.write(i * 1000, bytes([65 + i]) * 1000)
            client.prep_io(ring, iov, i * 1000, 1000, fd, i * 1000,
                           read=False, userdata=i)
        client.submit_ios(ring)
        done = client.wait_for_ios(ring, 6, timeout=10)
        assert [ud for _, ud in done] == list(range(6))
        assert all(res == 1000 for res, _ in done)
        client.dereg_fd(fd, length_hint=6000)
        data = bytes(fab.file_client().read(fab.meta.stat("/w"), 0, 6000))
        assert data == b"".join(bytes([65 + i]) * 1000 for i in range(6))
        client.iordestroy(ring)
        client.iovdestroy(iov)


class TestPushCqes:
    def test_many_cqes_one_header_write_one_post(self):
        ring = IoRing(8, create=True)
        try:
            for i in range(5):
                ring.prep_io(0, 1, 0, 1, read=True, userdata=i)
            sqes = ring.drain_sqes(limit=3)
            assert [q.userdata for q in sqes] == [0, 1, 2]
            assert ring.pending_sqes() == 2
            ring.push_cqes([(7, q.userdata) for q in sqes])
            ring.push_cqes([])                      # posts nothing
            assert ring.complete_sem.wait(timeout=0.05)
            assert not ring.complete_sem.wait(timeout=0.05)
            assert ring.reap() == [(7, 0), (7, 1), (7, 2)]
            ring.push_cqe(9, 3, stamps=5)
            assert ring.reap(with_stamps=True) == [(9, 3, 5)]
        finally:
            ring.close(unlink=True)


# -- ring ABI v2 --------------------------------------------------------------


class TestRingAbiV2:
    def test_counter_wraparound(self):
        """Counters are monotonic; slots wrap at entries. Several times
        around the ring, nothing aliases."""
        ring = IoRing(4, create=True)
        try:
            for round_no in range(5):
                for k in range(4):
                    assert ring.prep_io(0, 1, 0, 1, read=True,
                                        userdata=round_no * 10 + k) >= 0
                sqes = ring.drain_sqes()
                assert [s.userdata for s in sqes] == [
                    round_no * 10 + k for k in range(4)]
                for s in sqes:
                    ring.push_cqe(1, s.userdata)
                got = sorted(ud for _, ud in ring.reap())
                assert got == [round_no * 10 + k for k in range(4)]
        finally:
            ring.close()

    def test_token_and_class_flags_roundtrip(self):
        from tpu3fs.qos.core import TrafficClass, class_from_flags, \
            class_to_flags

        ring = IoRing(8, create=True)
        try:
            tok = "t1.0123456789abcdef.fedcba9876543210.1.d1.abc123." \
                  "u1.alice"
            ring.prep_io(0, 100, 0, 5, read=True, token=tok,
                         class_flags=class_to_flags(TrafficClass.KVCACHE))
            sqe = ring.drain_sqes()[0]
            assert sqe.token == tok
            assert class_from_flags(sqe.flags) == TrafficClass.KVCACHE
            from tpu3fs.rpc.deadline import decode_deadline
            from tpu3fs.tenant.identity import decode_tenant

            assert decode_tenant(sqe.token) == "alice"
            assert decode_deadline(sqe.token) is not None
        finally:
            ring.close()

    def test_oversized_token_refused(self):
        ring = IoRing(8, create=True)
        try:
            with pytest.raises(FsError) as ei:
                ring.prep_io(0, 1, 0, 1, read=True, token="u1." + "x" * 200)
            assert ei.value.code == Code.USRBIO_BAD_IOV
        finally:
            ring.close()

    def test_rpc_sqe_roundtrip(self):
        ring = IoRing(8, create=True)
        try:
            slot = ring.prep_rpc(3, 11, 256, 1024, 2048, 8192,
                                 userdata=7, token="u1.bob", bulk=True)
            assert slot == 0
            sqe = ring.drain_sqes()[0]
            assert sqe.is_rpc and sqe.has_bulk and not sqe.is_read
            assert (sqe.service_id, sqe.method_id) == (3, 11)
            assert sqe.iov_offset == 256 and sqe.length == 1024
            assert sqe.rsp_offset == 2048 and sqe.rsp_capacity == 8192
            assert sqe.token == "u1.bob"
        finally:
            ring.close()

    def test_torn_header_detected(self):
        import struct as _struct

        ring = IoRing(8, create=True)
        try:
            ring.buf[0:4] = _struct.pack("<I", 0xDEAD)  # tear the magic
            with pytest.raises(FsError) as ei:
                ring.drain_sqes()
            assert ei.value.code == Code.USRBIO_TORN_RING
        finally:
            ring.buf[0:4] = _struct.pack("<I", 0x3F5B10)
            ring.close()

    def test_open_refuses_wrong_version(self):
        import struct as _struct

        ring = IoRing(8, create=True)
        try:
            ring.buf[40:44] = _struct.pack("<I", 1)  # claim ABI v1
            with pytest.raises(FsError) as ei:
                IoRing(8, name=ring.name, create=False)
            assert ei.value.code == Code.USRBIO_TORN_RING
        finally:
            ring.close()

    def test_owner_pid_stamped_and_reaped(self):
        import os
        import struct as _struct

        from tpu3fs.usrbio.ring import reap_stale_shm

        ring = IoRing(8, create=True)
        name = ring.name
        assert ring.owner_pid == os.getpid()
        # a LIVE owner is never reaped
        assert name not in reap_stale_shm()
        # forge a dead owner (a child that already exited)
        pid = os.fork()
        if pid == 0:
            os._exit(0)
        os.waitpid(pid, 0)
        ring.buf[44:48] = _struct.pack("<I", pid)
        removed = reap_stale_shm()
        assert name in removed
        assert not os.path.exists(ring.path)
        # keep= protects a registration even with a dead owner
        ring.close()

    def test_orphan_iov_age_reap(self):
        import os
        import time as _time

        from tpu3fs.usrbio.ring import reap_stale_shm

        iov = Iov(4096, create=True)
        old = _time.time() - 7200
        os.utime(iov.path, (old, old))
        # protected while registered
        assert iov.name not in reap_stale_shm(keep={iov.name})
        assert os.path.exists(iov.path)
        removed = reap_stale_shm()
        assert iov.name in removed
        iov.close()

    def test_unlink_on_close_default(self):
        import os

        iov = Iov(4096, create=True)
        ring = IoRing(8, create=True)
        ipath, rpath = iov.path, ring.path
        # a mapper (create=False) closing must NOT unlink
        mapped = Iov(4096, name=iov.name, create=False)
        mapped.close()
        assert os.path.exists(ipath)
        iov.close()
        ring.close()
        assert not os.path.exists(ipath)
        assert not os.path.exists(rpath)


class TestShmHardening:
    """The register() path maps client-named segments inside the storage
    process: names must stay path components, symlinks must not be
    followed, and claimed sizes must match the file on disk."""

    def test_traversal_and_bad_prefix_names_rejected(self):
        for bad in ("../../../etc/passwd", "tpu3fs-iov-../x",
                    "tpu3fs-iov-a/b", "not-ours-abc"):
            with pytest.raises(FsError) as ei:
                Iov(4096, name=bad, create=False)
            assert ei.value.code == Code.USRBIO_BAD_IOV
        with pytest.raises(FsError):
            IoRing(8, name="tpu3fs-ior-..", create=False)

    def test_register_rejects_traversal_names(self):
        from tpu3fs.usrbio.server import UsrbioRpcHost
        from tpu3fs.usrbio.transport import UsrbioRegisterReq

        host = UsrbioRpcHost(server=None)
        try:
            nonce = host._nonce
            rsp = host.register(UsrbioRegisterReq(
                ring_name="tpu3fs-ior-../../etc/cron.d/x",
                iov_name="tpu3fs-iov-ok1", entries=8, iov_size=4096,
                nonce=nonce))
            assert not rsp.ok and "bad shm segment name" in rsp.message
            rsp = host.register(UsrbioRegisterReq(
                ring_name="tpu3fs-ior-ok1",
                iov_name="../../etc/shadow", entries=8, iov_size=4096,
                nonce=nonce))
            assert not rsp.ok and "bad shm segment name" in rsp.message
        finally:
            host.stop()

    def test_symlinked_segment_refused(self, tmp_path):
        import os
        import uuid as _uuid

        from tpu3fs.usrbio.ring import SHM_DIR

        target = tmp_path / "victim"
        target.write_bytes(b"\0" * 8192)
        name = f"tpu3fs-iov-{_uuid.uuid4().hex[:12]}"
        link = os.path.join(SHM_DIR, name)
        os.symlink(target, link)
        try:
            with pytest.raises(OSError):
                Iov(4096, name=name, create=False)
        finally:
            os.unlink(link)

    def test_undersized_segment_refused(self):
        iov = Iov(4096, create=True)
        try:
            # claiming more than the file holds must fail up front, not
            # SIGBUS the mapping process on first touch past EOF
            with pytest.raises(FsError) as ei:
                Iov(1 << 20, name=iov.name, create=False)
            assert ei.value.code == Code.USRBIO_BAD_IOV
            with pytest.raises(FsError):
                IoRing(8, name=iov.name, create=False)  # way undersized
        finally:
            iov.close(unlink=True)

    def test_live_v2_ring_never_age_reaped(self):
        import os
        import time as _time

        from tpu3fs.usrbio.ring import reap_stale_shm

        ring = IoRing(8, create=True)
        try:
            old = _time.time() - 7200
            os.utime(ring.path, (old, old))
            # owner (this process) is alive: age alone must not reap a
            # v2 ring — mmap writes never update tmpfs mtime, so a busy
            # ring can look arbitrarily old
            assert ring.name not in reap_stale_shm(iov_max_age_s=3600)
            assert os.path.exists(ring.path)
        finally:
            ring.close(unlink=True)


# -- the RPC ring transport against a live socket cluster ---------------------


@pytest.fixture
def ring_cluster():
    """mgmtd + 2 storage nodes over real TCP, each hosting the USRBIO
    control service + ring agent, with the full storage-internal QoS +
    tenant admission stack installed (storage_main shape)."""
    from tpu3fs.kv import MemKVEngine
    from tpu3fs.mgmtd.service import Mgmtd
    from tpu3fs.mgmtd.types import LocalTargetState, NodeType
    from tpu3fs.qos.core import QosConfig
    from tpu3fs.qos.manager import QosManager
    from tpu3fs.rpc.net import RpcClient, RpcServer
    from tpu3fs.rpc.services import (
        MgmtdRpcClient,
        RpcMessenger,
        bind_mgmtd_service,
        bind_storage_service,
    )
    from tpu3fs.storage.craq import StorageService
    from tpu3fs.storage.target import StorageTarget
    from tpu3fs.usrbio.server import UsrbioRpcHost, bind_usrbio_service

    kv = MemKVEngine()
    mgmtd = Mgmtd(1, kv)
    mgmtd.extend_lease()
    mgmtd_server = RpcServer()
    bind_mgmtd_service(mgmtd_server, mgmtd)
    mgmtd_server.start()
    servers = [mgmtd_server]
    hosts = []
    services = {}
    chain_id = 910_001
    shared = RpcClient()
    for node_id, target_id in zip([10, 11], [1000, 1001]):
        mcli = MgmtdRpcClient(mgmtd_server.address, shared)
        svc = StorageService(node_id, mcli.refresh_routing)
        svc.set_messenger(RpcMessenger(mcli.refresh_routing, shared))
        svc.add_target(StorageTarget(target_id, chain_id, chunk_size=4096))
        svc.set_qos(QosManager(QosConfig(),
                               tags={"node": str(node_id)}))
        server = RpcServer()
        bind_storage_service(server, svc)
        host = UsrbioRpcHost(server)
        bind_usrbio_service(server, host)
        server.start()
        hosts.append(host)
        mgmtd.register_node(node_id, NodeType.STORAGE,
                            host=server.host, port=server.port)
        mgmtd.create_target(target_id, node_id=node_id)
        services[node_id] = svc
        servers.append(server)
    mgmtd.upload_chain(chain_id, [1000, 1001])
    mgmtd.upload_chain_table(1, [chain_id])
    mgmtd.heartbeat(10, 1, {1000: LocalTargetState.UPTODATE})
    mgmtd.heartbeat(11, 1, {1001: LocalTargetState.UPTODATE})
    from tpu3fs.tenant.quota import registry as treg

    treg().clear()
    yield {
        "mgmtd": mgmtd,
        "mgmtd_addr": mgmtd_server.address,
        "chain_id": chain_id,
        "client": shared,
        "services": services,
        "hosts": hosts,
    }
    treg().clear()
    for h in hosts:
        h.stop()
    for svc in services.values():
        # chain-forward messengers grew rings of their own: unlink now,
        # not at interpreter exit (tier-1 runs hundreds of tests)
        close = getattr(getattr(svc, "_messenger", None), "close_rings",
                        None)
        if close is not None:
            close()
    for s in servers:
        s.stop()


def _mk_client(cluster, cid="rc"):
    from tpu3fs.client.storage_client import RetryOptions, StorageClient
    from tpu3fs.rpc.services import MgmtdRpcClient, RpcMessenger

    mcli = MgmtdRpcClient(cluster["mgmtd_addr"], cluster["client"])
    messenger = RpcMessenger(mcli.refresh_routing, cluster["client"])
    sc = StorageClient(cid, mcli.refresh_routing, messenger,
                       retry=RetryOptions(max_retries=0,
                                          backoff_base_s=0.001))
    return sc, messenger


class TestRingTransport:
    def test_ring_selected_and_io_equivalence(self, ring_cluster):
        from tpu3fs.client.storage_client import ReadReq
        from tpu3fs.storage.types import ChunkId

        sc, messenger = _mk_client(ring_cluster)
        chain = ring_cluster["chain_id"]
        writes = [(chain, ChunkId(1, i), 0, bytes([i + 1]) * 700)
                  for i in range(8)]
        assert all(r.ok for r in sc.batch_write(writes, chunk_size=4096))
        # a ring was established to the head node (same host by proof)
        rings = {k: v for k, v in messenger._usrbio_rings.items()
                 if v is not None}
        assert rings, "no USRBIO ring established on a same-host cluster"
        got = sc.batch_read([ReadReq(chain, ChunkId(1, i), 0, -1)
                             for i in range(8)])
        assert [bytes(r.data) for r in got] == [
            bytes([i + 1]) * 700 for i in range(8)]
        # equivalence against a sockets-only client: every node already
        # in the "handshake tried and failed" state, as a cross-host or
        # pre-USRBIO node would be
        sc2, m2 = _mk_client(ring_cluster, "rc-sock")
        for node_id in m2._routing().nodes:
            m2._usrbio_rings[node_id] = None
        got2 = sc2.batch_read([ReadReq(chain, ChunkId(1, i), 0, -1)
                               for i in range(8)])
        assert [bytes(r.data) for r in got2] == \
            [bytes(r.data) for r in got]
        assert not any(m2._usrbio_rings.values())
        sc2.close()
        sc.close()

    def test_a_node_batch_of_256_small_reads_rides_the_ring(self,
                                                            ring_cluster):
        """What a drain of 1024 4-KiB reads sends a node: 256 sub-chunk
        reads in ONE ring call whose reply fits the reply region — never
        silently by socket — each range exact."""
        from tpu3fs.client.storage_client import ReadReq
        from tpu3fs.storage.types import ChunkId

        sc, messenger = _mk_client(ring_cluster, "rc-256")
        chain = ring_cluster["chain_id"]
        rng = np.random.default_rng(3)
        chunks = [rng.integers(0, 256, 4096, dtype=np.uint8).tobytes()
                  for _ in range(16)]
        assert all(r.ok for r in sc.batch_write(
            [(chain, ChunkId(9, i), 0, c) for i, c in enumerate(chunks)],
            chunk_size=4096))
        rings = {k: v for k, v in messenger._usrbio_rings.items()
                 if v is not None}
        assert rings
        before = {k: v._next_ud for k, v in rings.items()}
        reqs = [ReadReq(chain, ChunkId(9, i % 16), (i * 37) % 3072, 1024)
                for i in range(512)]
        got = sc.batch_read(reqs)
        for req, r in zip(reqs, got):
            assert r.ok and bytes(r.data) == chunks[req.chunk_id.index][
                req.offset:req.offset + 1024]
        # one ring call a node group that got reads, and the same rings
        # still stand: nothing fell back to the sockets
        after = {k: messenger._usrbio_rings.get(k) for k in rings}
        assert all(after[k] is rings[k] for k in rings)
        calls = sum(rings[k]._next_ud - before[k] for k in rings)
        assert 1 <= calls <= len(rings)
        sc.close()

    def test_a_traced_ring_hop_carries_the_server_s_stamps(self,
                                                           ring_cluster):
        """The CQE's third word brings the serving side's wait and run
        (what a socket reply's Timestamps bring): a traced ring hop has
        server_wait and server_run beside issue and collect."""
        from tpu3fs.analytics import spans
        from tpu3fs.client.storage_client import ReadReq
        from tpu3fs.storage.types import ChunkId

        sc, messenger = _mk_client(ring_cluster, "rc-span")
        chain = ring_cluster["chain_id"]
        assert all(r.ok for r in sc.batch_write(
            [(chain, ChunkId(7, 0), 0, b"s" * 900)], chunk_size=4096))
        assert any(v is not None for v in messenger._usrbio_rings.values())
        ctx = spans.TraceContext("t" * 16, "s" * 16)
        with spans.trace_scope(ctx):
            got = sc.batch_read([ReadReq(chain, ChunkId(7, 0), 0, -1)])
        assert bytes(got[0].data) == b"s" * 900
        (hop,) = [e for e in ctx.events if e.op == "rpc.client.ring"]
        stages = {e.stage: e for e in ctx.events
                  if e.parent_id == hop.span_id}
        assert {"issue", "collect", "server_wait", "server_run"} <= \
            set(stages)
        assert 0 < stages["server_run"].dur_us < hop.dur_us
        assert stages["server_wait"].t_perf >= stages["issue"].t_perf
        sc.close()

    def test_large_payload_and_single_ops(self, ring_cluster):
        from tpu3fs.storage.types import ChunkId

        sc, messenger = _mk_client(ring_cluster)
        chain = ring_cluster["chain_id"]
        blob = bytes(range(256)) * 16  # one chunk exactly
        assert sc.write_chunk(chain, ChunkId(3, 0), 0, blob,
                              chunk_size=4096).ok
        r = sc.read_chunk(chain, ChunkId(3, 0))
        assert r.ok and bytes(r.data) == blob
        sc.close()

    def test_tenant_flood_sheds_through_ring(self, ring_cluster):
        from tpu3fs.client.storage_client import ReadReq
        from tpu3fs.storage.types import ChunkId
        from tpu3fs.tenant.quota import registry as treg

        sc, messenger = _mk_client(ring_cluster)
        chain = ring_cluster["chain_id"]
        assert sc.write_chunk(chain, ChunkId(4, 0), 0, b"q" * 2000,
                              chunk_size=4096).ok
        treg().configure("tenant=flood,iops=2,burst_s=1")
        try:
            reqs = [ReadReq(chain, ChunkId(4, 0), 0, -1)]
            with tenant_scope("flood"):
                replies = [sc.batch_read(reqs)[0] for _ in range(12)]
            shed = [r for r in replies if r.code == Code.TENANT_THROTTLED]
            assert shed, [r.code for r in replies]
            # the retry-after hint survives the ring (honored by ladders)
            assert all(r.retry_after_ms > 0 for r in shed)
            # the ring really was the transport (still established)
            assert any(v is not None
                       for v in messenger._usrbio_rings.values())
            # other tenants keep reading
            assert sc.batch_read(reqs)[0].ok
        finally:
            treg().clear()
        sc.close()

    def test_qos_class_shed_through_ring(self, ring_cluster):
        from tpu3fs.client.storage_client import ReadReq
        from tpu3fs.qos.core import QosConfig, TrafficClass, tagged
        from tpu3fs.storage.types import ChunkId

        sc, messenger = _mk_client(ring_cluster)
        chain = ring_cluster["chain_id"]
        assert sc.write_chunk(chain, ChunkId(5, 0), 0, b"c" * 512,
                              chunk_size=4096).ok
        # choke the RESYNC class on every node's shared admission
        for svc in ring_cluster["services"].values():
            svc.qos.config.resync.rate = 0.001
            svc.qos.config.resync.burst = 1.0
            svc.qos.admission.reload()
        reqs = [ReadReq(chain, ChunkId(5, 0), 0, -1)]
        with tagged(TrafficClass.RESYNC):
            replies = [sc.batch_read(reqs)[0] for _ in range(8)]
        assert any(r.code == Code.OVERLOADED for r in replies), \
            "class bits never reached admission through the ring SQE"
        # foreground unaffected
        assert sc.batch_read(reqs)[0].ok
        sc.close()

    def test_deadline_shed_at_ring_dequeue(self, ring_cluster):
        import time as _time

        from tpu3fs.client.storage_client import ReadReq
        from tpu3fs.storage.types import ChunkId

        sc, messenger = _mk_client(ring_cluster)
        chain = ring_cluster["chain_id"]
        assert sc.write_chunk(chain, ChunkId(6, 0), 0, b"d" * 128,
                              chunk_size=4096).ok
        # establish the ring first
        assert sc.read_chunk(chain, ChunkId(6, 0)).ok
        node_id = next(k for k, v in messenger._usrbio_rings.items()
                       if v is not None)
        with dl.deadline_scope(_time.time() - 0.5):
            with pytest.raises(FsError) as ei:
                messenger(node_id, "batch_read",
                          [ReadReq(chain, ChunkId(6, 0), 0, -1)])
        assert ei.value.code == Code.DEADLINE_EXCEEDED
        sc.close()

    def test_fallback_when_host_stops(self, ring_cluster):
        from tpu3fs.client.storage_client import ReadReq
        from tpu3fs.storage.types import ChunkId

        sc, messenger = _mk_client(ring_cluster)
        chain = ring_cluster["chain_id"]
        assert sc.write_chunk(chain, ChunkId(7, 0), 0, b"f" * 900,
                              chunk_size=4096).ok
        assert sc.read_chunk(chain, ChunkId(7, 0)).ok
        assert any(v is not None
                   for v in messenger._usrbio_rings.values())
        # kill the agents under the client: reads must keep succeeding
        # (socket fallback), never surface a USRBIO error
        for h in ring_cluster["hosts"]:
            h.stop()
        for _ in range(3):
            got = sc.batch_read([ReadReq(chain, ChunkId(7, 0), 0, -1)])
            assert got[0].ok and bytes(got[0].data) == b"f" * 900
        sc.close()


# -- cross-process rings over real fork ---------------------------------------


def _fork_child_io(addr, chain, q):
    """Runs in a forked child: establish a ring of its own and do IO."""
    try:
        from tpu3fs.client.storage_client import RetryOptions, StorageClient
        from tpu3fs.rpc.net import RpcClient
        from tpu3fs.rpc.services import MgmtdRpcClient, RpcMessenger
        from tpu3fs.storage.types import ChunkId

        mcli = MgmtdRpcClient(addr, RpcClient())
        m = RpcMessenger(mcli.refresh_routing)
        sc = StorageClient("forked", mcli.refresh_routing, m,
                           retry=RetryOptions(max_retries=0,
                                              backoff_base_s=0.001))
        ok = sc.write_chunk(chain, ChunkId(9, 0), 0, b"forked-bytes" * 50,
                            chunk_size=4096).ok
        used_ring = any(v is not None for v in m._usrbio_rings.values())
        r = sc.read_chunk(chain, ChunkId(9, 0))
        q.put((bool(ok), bool(used_ring), bytes(r.data)))
        sc.close()
    except Exception as e:  # surface the child's failure to the parent
        q.put(("err", repr(e), b""))


def _fork_child_crash(addr, chain, q):
    """Establish a ring, report its shm names, then die WITHOUT cleanup
    (os._exit skips atexit) — the leak the agent reaper must collect."""
    import os

    from tpu3fs.client.storage_client import RetryOptions, StorageClient
    from tpu3fs.rpc.net import RpcClient
    from tpu3fs.rpc.services import MgmtdRpcClient, RpcMessenger
    from tpu3fs.storage.types import ChunkId

    mcli = MgmtdRpcClient(addr, RpcClient())
    m = RpcMessenger(mcli.refresh_routing)
    sc = StorageClient("crasher", mcli.refresh_routing, m,
                       retry=RetryOptions(max_retries=0,
                                          backoff_base_s=0.001))
    sc.read_chunk(chain, ChunkId(9, 0))
    names = []
    for ring in m._usrbio_rings.values():
        if ring is not None:
            names.append(ring.ring.name)
            names.append(ring.iov.name)
    q.put(names)
    # flush the queue's feeder thread BEFORE the un-clean exit: os._exit
    # must kill the atexit cleanup, not the message to the parent
    q.close()
    q.join_thread()
    os._exit(1)


class TestRingCrossProcessFork:
    def test_forked_client_rides_its_own_ring(self, ring_cluster):
        import multiprocessing as mp

        ctx = mp.get_context("fork")
        q = ctx.Queue()
        p = ctx.Process(target=_fork_child_io,
                        args=(ring_cluster["mgmtd_addr"],
                              ring_cluster["chain_id"], q))
        p.start()
        ok, used_ring, data = q.get(timeout=60)
        p.join(30)
        assert ok is True, (ok, used_ring, data)
        assert used_ring, "forked client never established a ring"
        assert data == b"forked-bytes" * 50
        # the parent sees the child's bytes through its own transport
        from tpu3fs.storage.types import ChunkId

        sc, _m = _mk_client(ring_cluster, "parent")
        got = sc.read_chunk(ring_cluster["chain_id"], ChunkId(9, 0))
        assert bytes(got.data) == b"forked-bytes" * 50
        sc.close()

    def test_reaper_collects_crashed_client(self, ring_cluster):
        import multiprocessing as mp
        import os

        from tpu3fs.usrbio.ring import SHM_DIR

        ctx = mp.get_context("fork")
        q = ctx.Queue()
        p = ctx.Process(target=_fork_child_crash,
                        args=(ring_cluster["mgmtd_addr"],
                              ring_cluster["chain_id"], q))
        p.start()
        names = q.get(timeout=60)
        p.join(30)
        assert p.exitcode == 1
        assert names, "child never established a ring"
        leaked = [n for n in names
                  if os.path.exists(os.path.join(SHM_DIR, n))]
        assert leaked, "crash did not leak (atexit ran?) — test is moot"
        for host in ring_cluster["hosts"]:
            host.reap_pass(iov_max_age_s=3600.0)
        for n in names:
            assert not os.path.exists(os.path.join(SHM_DIR, n)), \
                f"reaper left {n}"


class TestDeadAgentDetected:
    def test_waiter_stops_when_the_agent_process_is_gone(self):
        """A SIGKILLed storage process completes nothing: the waiter must
        notice the dead pid on its first wake-up, not sit out the 30 s
        call timeout (chip_smoke's kill-and-restart leg stalled 30 s per
        stale ring, client side and chain-forward side, before this)."""
        import subprocess
        import sys
        import time

        from tpu3fs.rpc.services import (
            STORAGE_SERVICE_ID,
            BatchReadReq,
            BatchReadRsp,
        )
        from tpu3fs.usrbio.transport import RingClient

        gone = subprocess.Popen([sys.executable, "-c", "pass"])
        gone.wait()
        ring = RingClient(entries=8, iov_bytes=1 << 20,
                          agent_pid=gone.pid)
        try:
            t0 = time.monotonic()
            with pytest.raises(FsError) as ei:
                ring.call(STORAGE_SERVICE_ID, 11, BatchReadReq([]),
                          BatchReadRsp, bulk_iovs=())
            assert ei.value.code == Code.USRBIO_AGENT_GONE
            assert time.monotonic() - t0 < 5.0
        finally:
            ring.close()
