"""Batched IO end-to-end: one request per node carrying many ops.

Mirrors the reference's BatchReadReq/batchWrite paths
(src/client/storage/StorageClientImpl.cc:1030 groupOpsByNodeId, :1303
sendBatchRequest, :1771 batchWriteWithRetry; server
src/storage/service/StorageOperator.cc:82-231).
"""

import numpy as np
import pytest

from tpu3fs.client.storage_client import ReadReq, StorageClient
from tpu3fs.fabric.fabric import Fabric, SystemSetupConfig
from tpu3fs.mgmtd.types import PublicTargetState
from tpu3fs.storage.types import ChunkId
from tpu3fs.utils.result import Code


class TestFabricBatchedIo:
    def test_batch_write_then_batch_read(self):
        fab = Fabric(SystemSetupConfig(num_chains=4, chunk_size=4096))
        client = fab.storage_client()
        writes = [
            (fab.chain_ids[i % 4], ChunkId(50, i), 0, bytes([i]) * 1000)
            for i in range(16)
        ]
        replies = client.batch_write(writes, chunk_size=4096)
        assert all(r.ok for r in replies)
        # every replica converged (the batch still ran full CRAQ forwarding)
        routing = fab.routing()
        for chain_id, cid, _, data in writes:
            for t in routing.chains[chain_id].targets:
                node = routing.node_of_target(t.target_id)
                eng = fab.nodes[node.node_id].service.target(t.target_id).engine
                assert eng.read(cid) == data
        reads = [ReadReq(c, cid, 0, -1) for c, cid, _, _ in writes]
        got = client.batch_read(reads)
        for r, (_, _, _, data) in zip(got, writes):
            assert r.ok and r.data == data

    def test_batch_larger_than_the_channel_pool(self):
        """One exactly-once channel per op, 1024 in the pool: a batch past
        that (a 1 GiB checkpoint save on the chip, chip_smoke's ckpt leg)
        died CLIENT_NO_CHANNEL before batch_write ran it as rounds."""
        from tpu3fs.client.storage_client import CHANNEL_POOL

        fab = Fabric(SystemSetupConfig(num_chains=4, chunk_size=4096))
        client = fab.storage_client()
        n = CHANNEL_POOL + 77
        writes = [(fab.chain_ids[i % 4], ChunkId(60, i), 0,
                   i.to_bytes(4, "little") * 8) for i in range(n)]
        replies = client.batch_write(writes, chunk_size=4096)
        assert len(replies) == n and all(r.ok for r in replies)
        got = client.batch_read(
            [ReadReq(c, cid, 0, -1) for c, cid, _, _ in writes])
        assert [bytes(r.data) for r in got] == [w[3] for w in writes]
        # every channel came back
        assert len(client._channels._free) == CHANNEL_POOL

    def test_batch_write_falls_back_per_op_on_errors(self):
        fab = Fabric(SystemSetupConfig(num_chains=2, chunk_size=4096))
        client = fab.storage_client()
        bogus = 999_999
        writes = [
            (fab.chain_ids[0], ChunkId(51, 0), 0, b"x" * 100),
            (bogus, ChunkId(51, 1), 0, b"y" * 100),
        ]
        replies = client.batch_write(writes, chunk_size=4096)
        assert replies[0].ok
        assert not replies[1].ok and replies[1].code in (
            Code.CHAIN_NOT_FOUND, Code.TARGET_OFFLINE)

    def test_messenger_count_drops_with_batching(self):
        """The whole point: N ops -> 1 request per node, not N."""
        fab = Fabric(SystemSetupConfig(num_chains=4, chunk_size=4096))
        client = fab.storage_client()
        writes = [
            (fab.chain_ids[i % 4], ChunkId(52, i), 0, b"z" * 64)
            for i in range(32)
        ]
        assert all(r.ok for r in client.batch_write(writes, chunk_size=4096))
        calls = []
        orig = fab.send

        def counting(node_id, method, payload):
            calls.append(method)
            return orig(node_id, method, payload)

        counted = StorageClient("probe", fab.routing, counting)
        reads = [ReadReq(c, cid, 0, -1) for c, cid, _, _ in writes]
        got = counted.batch_read(reads)
        assert all(r.ok for r in got)
        batch_calls = [m for m in calls if m == "batch_read"]
        single_calls = [m for m in calls if m == "read"]
        assert len(batch_calls) <= len(fab.nodes)
        assert not single_calls


class TestEcBatchedStripes:
    def test_write_stripes_batched_encode_and_install(self):
        fab = Fabric(SystemSetupConfig(
            num_storage_nodes=4, num_chains=1, chunk_size=1 << 14,
            ec_k=3, ec_m=1))
        client = fab.storage_client()
        chunk = 1 << 14
        rng = np.random.default_rng(0)
        items = [
            (ChunkId(60, i),
             rng.integers(0, 256, chunk - i * 11, dtype=np.uint8).tobytes())
            for i in range(8)
        ]
        replies = client.write_stripes(
            fab.chain_ids[0], items, chunk_size=chunk)
        assert all(r.ok for r in replies)
        for cid, data in items:
            got = client.read_stripe(
                fab.chain_ids[0], cid, 0, len(data), chunk_size=chunk)
            assert got.ok and got.data == data

    def test_write_stripes_conflict_falls_back(self):
        fab = Fabric(SystemSetupConfig(
            num_storage_nodes=4, num_chains=1, chunk_size=1 << 14,
            ec_k=3, ec_m=1))
        client = fab.storage_client()
        chunk = 1 << 14
        cid = ChunkId(61, 0)
        assert client.write_stripe(
            fab.chain_ids[0], cid, b"old" * 100, chunk_size=chunk).ok
        replies = client.write_stripes(
            fab.chain_ids[0], [(cid, b"new" * 100)], chunk_size=chunk)
        assert replies[0].ok and replies[0].update_ver >= 2
        got = client.read_stripe(
            fab.chain_ids[0], cid, 0, 300, chunk_size=chunk)
        assert got.data == b"new" * 100


def _file_with_data(fab, path, data, *, chunk_size=None, stripe=None):
    from tpu3fs.meta.store import OpenFlags

    res = fab.meta.create(path, flags=OpenFlags.WRITE | OpenFlags.CREATE,
                          chunk_size=chunk_size, stripe=stripe,
                          client_id="t")
    fio = fab.file_client()
    n = fio.write(res.inode, 0, data)
    inode = fab.meta.close(res.inode.id, res.session_id, length_hint=n,
                           wrote=True)
    return inode


class TestReadIntoBoundaries:
    """Satellite: exact byte-range reads at stripe/EC-parity boundaries —
    the primitives the ckpt resharding loader leans on."""

    CS = 4096

    def _fab(self, **kw):
        defaults = dict(num_storage_nodes=4, num_chains=4,
                        chunk_size=self.CS)
        defaults.update(kw)
        return Fabric(SystemSetupConfig(**defaults))

    def _roundtrip_ranges(self, fab, data, ranges):
        inode = _file_with_data(fab, "/rt", data)
        fio = fab.file_client()
        for off, size in ranges:
            want = data[off:off + size]
            if off < len(data):
                want = want.ljust(min(size, len(data) - off), b"\x00")
            dest = memoryview(bytearray(size))
            got_n = fio.read_into(inode, off, size, dest)
            assert bytes(dest[:got_n]) == want, (off, size)
        # and the same ranges as ONE batch
        blobs = fio.batch_read_files(
            [(inode, off, size) for off, size in ranges])
        for (off, size), blob in zip(ranges, blobs):
            want = data[off:off + size]
            assert blob == want, (off, size)

    def test_cr_ranges_straddling_chunk_edges_and_short_tail(self):
        rng = np.random.default_rng(21)
        # 3.5 chunks: a short tail chunk
        data = rng.integers(0, 256, self.CS * 3 + self.CS // 2,
                            dtype=np.uint8).tobytes()
        fab = self._fab()
        cs = self.CS
        self._roundtrip_ranges(fab, data, [
            (0, cs),                      # exactly one chunk
            (cs - 7, 14),                 # straddles chunk 0/1 edge
            (cs - 1, 1),                  # last byte of a chunk
            (cs, 1),                      # first byte of a chunk
            (cs * 2 - 100, cs + 200),     # spans three chunks
            (cs * 3, cs // 2),            # exactly the short tail
            (cs * 3 + 100, cs),           # clamped at EOF (short read)
            (0, len(data)),               # whole file
        ])

    def test_ec_ranges_straddling_stripe_and_parity_boundaries(self):
        """EC(3,1): chunk_size-sized stripes split into 3 data shards +
        parity; ranges crossing shard and stripe edges must assemble
        exactly (read_stripe underneath)."""
        rng = np.random.default_rng(22)
        fab = self._fab(ec_k=3, ec_m=1, num_chains=1)
        cs = self.CS
        shard = -(-cs // 3)  # shard_size_of(cs, 3)
        data = rng.integers(0, 256, cs * 2 + cs // 3,
                            dtype=np.uint8).tobytes()
        self._roundtrip_ranges(fab, data, [
            (0, cs),                      # whole stripe
            (shard - 5, 10),              # straddles data-shard 0/1 edge
            (2 * shard - 5, 10),          # straddles shard 1/2 (parity-
            #                               adjacent) edge
            (cs - 9, 18),                 # straddles stripe 0/1 edge
            (cs * 2 - 1, 2),              # stripe edge into the tail
            (cs * 2, cs // 3),            # exactly the short tail stripe
            (cs * 2 + 10, cs),            # clamped at EOF
            (0, len(data)),               # whole file
        ])

    def test_batch_read_files_mixed_cr_and_ec_files(self):
        """One batch spanning a CR-striped file and an EC file: replies
        keep file order and exact contents."""
        rng = np.random.default_rng(23)
        cs = self.CS
        fab_cr = self._fab(num_chains=2)
        a = rng.integers(0, 256, cs + 17, dtype=np.uint8).tobytes()
        b = rng.integers(0, 256, 3 * cs, dtype=np.uint8).tobytes()
        ia = _file_with_data(fab_cr, "/a", a)
        ib = _file_with_data(fab_cr, "/b", b)
        fio = fab_cr.file_client()
        got = fio.batch_read_files([
            (ia, 0, len(a)), (ib, cs - 3, 7), (ia, cs, 17), (ib, 0, len(b)),
        ])
        assert got == [a, b[cs - 3:cs + 4], a[cs:], b]

    def test_write_boundaries_cr_spanning_chunks_and_tails(self):
        """Write-side twin of the range tests: batched writes landing at
        chunk edges, offsets and short tails must read back byte-exact
        through ranged reads (write-then-ranged-read equivalence)."""
        rng = np.random.default_rng(31)
        fab = self._fab()
        fio = fab.file_client()
        cs = self.CS
        from tpu3fs.meta.store import OpenFlags

        cases = [
            (0, cs),                  # exactly one chunk
            (cs - 7, 14),             # straddles chunk 0/1 edge
            (cs * 2 - 100, cs + 200),  # spans three chunks
            (cs * 3, cs // 2),        # short tail chunk
            (5, 3 * cs + 11),         # offset start spanning everything
        ]
        base = rng.integers(0, 256, cs * 4, dtype=np.uint8).tobytes()
        inode = _file_with_data(fab, "/wb", base)
        shadow = bytearray(base)
        for off, size in cases:
            patch = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
            assert fio.write(inode, off, patch) == size
            shadow[off:off + size] = patch
            # ranged read-back across the patch's boundaries
            lo = max(0, off - 3)
            n = min(len(shadow) - lo, size + 6)
            assert fio.read(inode, lo, n) == bytes(shadow[lo:lo + n]), \
                (off, size)
        fab.close()

    def test_write_boundaries_ec_stripes_and_partial_tails(self):
        """EC(3,1) writes: full stripes ride write_stripes, partials the
        read-modify-write ladder; both must read back exactly across
        stripe and shard boundaries."""
        rng = np.random.default_rng(32)
        fab = self._fab(ec_k=3, ec_m=1, num_chains=1)
        fio = fab.file_client()
        cs = self.CS
        shard = -(-cs // 3)
        base = rng.integers(0, 256, cs * 3, dtype=np.uint8).tobytes()
        inode = _file_with_data(fab, "/wbe", base)
        shadow = bytearray(base)
        cases = [
            (0, cs),                  # whole stripe (write_stripes path)
            (cs, 2 * cs),             # two whole stripes in one batch
            (shard - 5, 10),          # partial: straddles shard 0/1 edge
            (cs - 9, 18),             # partial: straddles stripe 0/1 edge
            (cs * 2 + 7, cs // 3),    # partial inside the last stripe
        ]
        for off, size in cases:
            patch = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
            assert fio.write(inode, off, patch) == size
            shadow[off:off + size] = patch
            lo = max(0, off - 3)
            n = min(len(shadow) - lo, size + 6)
            assert fio.read(inode, lo, n) == bytes(shadow[lo:lo + n]), \
                (off, size)
        assert fio.read(inode, 0, len(shadow)) == bytes(shadow)
        fab.close()

    def test_batch_write_files_mixed_cr_and_ec_write_read_equivalence(self):
        """ONE batch_write_files spanning a CR file and an EC file: every
        op gathers into the batched fan-out, and ranged reads reproduce
        each file exactly (including a partial EC tail stripe)."""
        from tpu3fs.meta.store import OpenFlags

        rng = np.random.default_rng(33)
        cs = self.CS
        fab = self._fab(ec_k=3, ec_m=1, num_chains=2)
        fio = fab.file_client()
        a = rng.integers(0, 256, 2 * cs + 123, dtype=np.uint8).tobytes()
        b = rng.integers(0, 256, cs + cs // 2, dtype=np.uint8).tobytes()
        ra = fab.meta.create("/bwa", flags=OpenFlags.WRITE, client_id="t")
        rb = fab.meta.create("/bwb", flags=OpenFlags.WRITE, client_id="t",
                             stripe=1)
        counts = fio.batch_write_files(
            [(ra.inode, 0, a), (rb.inode, 0, b)])
        assert counts == [len(a), len(b)]
        ia = fab.meta.close(ra.inode.id, ra.session_id, length_hint=len(a),
                            wrote=True)
        ib = fab.meta.close(rb.inode.id, rb.session_id, length_hint=len(b),
                            wrote=True)
        assert fio.read(ia, 0, len(a)) == a
        assert fio.read(ib, 0, len(b)) == b
        # ranged equivalence across chunk/stripe edges
        assert fio.read(ia, cs - 3, 7) == a[cs - 3:cs + 4]
        assert fio.read(ib, cs - 3, 7) == b[cs - 3:cs + 4]
        fab.close()

    def test_read_into_zero_and_hole_semantics(self):
        fab = self._fab()
        from tpu3fs.meta.store import OpenFlags

        res = fab.meta.create("/holes", flags=OpenFlags.WRITE,
                              client_id="t")
        fio = fab.file_client()
        # write only chunk 2: chunks 0-1 are holes
        cs = self.CS
        fio.write(res.inode, 2 * cs, b"\x5a" * 100)
        inode = fab.meta.close(res.inode.id, res.session_id,
                               length_hint=2 * cs + 100, wrote=True)
        dest = memoryview(bytearray(cs * 3))
        n = fio.read_into(inode, 0, cs * 3, dest)
        assert n == 2 * cs + 100  # clamped to length
        assert bytes(dest[:2 * cs]) == b"\x00" * (2 * cs)  # holes zero-fill
        assert bytes(dest[2 * cs:2 * cs + 100]) == b"\x5a" * 100


class TestBatchReadInto:
    """batch_read_into: many (inode, offset, size, dest) as ONE
    StorageClient.batch_read; it equals read() range by range, on
    replication and on an EC chain, and a range that fails fails alone."""

    CS = 4096

    def _fab(self, kind):
        if kind == "ec":
            return Fabric(SystemSetupConfig(
                num_storage_nodes=4, num_chains=1, chunk_size=12 << 10,
                ec_k=3, ec_m=1)), 12 << 10
        return Fabric(SystemSetupConfig(
            num_storage_nodes=4, num_chains=4, chunk_size=self.CS)), self.CS

    @pytest.mark.parametrize("kind", ["cr", "ec"])
    def test_equals_read_range_by_range(self, kind):
        fab, cs = self._fab(kind)
        rng = np.random.default_rng(5)
        a = rng.integers(0, 256, 3 * cs + cs // 2, dtype=np.uint8).tobytes()
        b = rng.integers(0, 256, cs + 17, dtype=np.uint8).tobytes()
        ia = _file_with_data(fab, "/a", a)
        ib = _file_with_data(fab, "/b", b)
        fio = fab.file_client()
        ranges = [(ia, 0, 64), (ib, 0, 64), (ia, cs - 3, 7),
                  (ia, 2 * cs - 100, cs + 200), (ib, cs, 4096),
                  (ia, len(a) - 10, 4096), (ia, len(a), 16), (ib, 5, 0),
                  (ia, 100, 3 * cs)]
        calls = []
        inner = fio.storage.batch_read
        fio.storage.batch_read = lambda reqs: (calls.append(len(reqs)),
                                               inner(reqs))[1]
        dests = [memoryview(bytearray(b"\xEE" * size))
                 for _, _, size in ranges]
        got = fio.batch_read_into(
            [(inode, off, size, dest)
             for (inode, off, size), dest in zip(ranges, dests)])
        fio.storage.batch_read = inner
        assert len(calls) == 1          # ONE batch for all of them
        for (inode, off, size), dest, n in zip(ranges, dests, got):
            want = bytes(fio.read(inode, off, size))
            assert n == len(want), (off, size)
            assert bytes(dest[:n]) == want, (off, size)
            assert bytes(dest[n:]) == b"\xEE" * (size - n)   # untouched
        fab.close()

    def test_holes_zero_fill_and_an_untracked_empty_file_is_eof(self):
        from tpu3fs.meta.store import OpenFlags

        fab, cs = self._fab("cr")
        fio = fab.file_client()
        res = fab.meta.create("/holes", flags=OpenFlags.WRITE,
                              client_id="t")
        fio.write(res.inode, 2 * cs, b"\x5a" * 100)
        holes = fab.meta.close(res.inode.id, res.session_id,
                               length_hint=2 * cs + 100, wrote=True)
        empty = fab.meta.create("/empty", flags=OpenFlags.WRITE,
                                client_id="t").inode
        d1, d2 = (memoryview(bytearray(b"\xEE" * 3 * cs)) for _ in range(2))
        got = fio.batch_read_into([(holes, 0, 3 * cs, d1),
                                   (empty, 0, 3 * cs, d2)])
        assert got == [2 * cs + 100, 0]
        assert bytes(d1[:2 * cs]) == b"\x00" * (2 * cs)
        assert bytes(d1[2 * cs:2 * cs + 100]) == b"\x5a" * 100
        fab.close()

    def test_a_range_that_fails_fails_alone(self):
        from dataclasses import replace

        from tpu3fs.utils.result import FsError

        fab, cs = self._fab("cr")
        data = bytes(range(256)) * 64
        inode = _file_with_data(fab, "/ok", data)
        # a layout that names a chain routing does not know
        lost = replace(inode, layout=replace(
            inode.layout, chains=[99999] * len(inode.layout.chains)))
        fio = fab.file_client()
        d = [memoryview(bytearray(64)) for _ in range(3)]
        got = fio.batch_read_into([(inode, 0, 64, d[0]), (lost, 0, 64, d[1]),
                                   (inode, cs + 1, 64, d[2])])
        assert got[0] == 64 and got[2] == 64
        assert isinstance(got[1], FsError)
        assert bytes(d[0]) == data[:64]
        assert bytes(d[2]) == data[cs + 1:cs + 65]
        with pytest.raises(FsError):   # read_into is its one-element case
            fio.read_into(lost, 0, 64, d[1])
        fab.close()


class TestEcFirstClassWrites:
    """EC as a first-class layout through the normal write path: delta-
    parity RMW for sub-stripe writes, inline degraded decode in batched
    reads, rebuild under concurrent writes, trusted-CRC installs."""

    CS = 4096

    def _ec_fab(self, k=3, m=1, nodes=6):
        return Fabric(SystemSetupConfig(
            num_storage_nodes=nodes, num_chains=1, chunk_size=self.CS,
            ec_k=k, ec_m=m))

    def test_partial_stripe_rmw_matches_full_reencode(self):
        """A sub-stripe write through the delta-parity RMW must leave
        EXACTLY the parity bytes a full re-encode of the merged stripe
        produces — and actually take the fast path."""
        from tpu3fs.ops.stripe import get_codec, shard_size_of

        rng = np.random.default_rng(60)
        fab = self._ec_fab(k=3, m=2, nodes=5)
        client = fab.storage_client()
        cs = self.CS
        k, m = 3, 2
        S = shard_size_of(cs, k)
        cid = ChunkId(90, 0)
        base = rng.integers(0, 256, cs, dtype=np.uint8).tobytes()
        assert client.write_stripe(fab.chain_ids[0], cid, base,
                                   chunk_size=cs).ok
        shadow = bytearray(base)
        for off, n in [(7, 100), (S - 9, 30), (cs - 64, 64)]:
            patch = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
            reply = client.write_stripe_rmw(
                fab.chain_ids[0], cid, off, patch, chunk_size=cs)
            assert reply is not None and reply.ok, (off, n)
            shadow[off:off + n] = patch
        assert client._ec_parity_rmw._value == 3
        assert client._ec_rmw_fallback._value == 0
        # parity on disk == full re-encode of the merged stripe
        codec = get_codec(k, m, S)
        want_shards, _ = codec.encode_stripe(bytes(shadow))
        routing = fab.routing()
        chain = routing.chains[fab.chain_ids[0]]
        for j in range(k + m):
            t = chain.target_of_shard(j)
            node = routing.node_of_target(t.target_id)
            eng = fab.nodes[node.node_id].service.target(t.target_id).engine
            stored = eng.read(cid)
            assert stored.ljust(S, b"\x00") == \
                want_shards[j].tobytes(), f"shard {j}"
        # and the stripe-version invariant held: one committed version
        vers = set()
        for j in range(k + m):
            t = chain.target_of_shard(j)
            node = routing.node_of_target(t.target_id)
            eng = fab.nodes[node.node_id].service.target(t.target_id).engine
            vers.add(eng.get_meta(cid).committed_ver)
        assert len(vers) == 1
        fab.close()

    def test_rmw_moves_fewer_shard_bytes_than_reencode(self):
        """The point of delta parity: a one-shard write ships touched +
        parity payloads, NOT the whole stripe."""
        rng = np.random.default_rng(61)
        fab = self._ec_fab(k=4, m=1, nodes=5)
        client = fab.storage_client()
        cs = self.CS
        cid = ChunkId(91, 0)
        base = rng.integers(0, 256, cs, dtype=np.uint8).tobytes()
        assert client.write_stripe(fab.chain_ids[0], cid, base,
                                   chunk_size=cs).ok
        sent = []
        orig = fab.send

        def counting(node_id, method, payload):
            if method in ("write_shard", "batch_write_shard"):
                ops = payload if isinstance(payload, list) else [payload]
                sent.extend(len(op.data) for op in ops)
            return orig(node_id, method, payload)

        probe = StorageClient("probe-rmw", fab.routing, counting)
        reply = probe.write_stripe_rmw(
            fab.chain_ids[0], cid, 16, b"\xaa" * 32, chunk_size=cs)
        assert reply is not None and reply.ok
        payload_bytes = sum(sent)
        S = -(-cs // 4)
        # touched data shard + 1 parity shard, NOT 4+1 shards
        assert payload_bytes <= 2 * 1024 + 2 * S, payload_bytes
        fab.close()

    def test_ranged_reads_over_degraded_files_byte_exact(self):
        """batch_read_files over an EC file with a DEAD shard node:
        every ranged read decodes inline and stays byte-exact."""
        rng = np.random.default_rng(62)
        fab = self._ec_fab(k=3, m=1, nodes=4)
        fio = fab.file_client()
        cs = self.CS
        shard = -(-cs // 3)
        data = rng.integers(0, 256, 3 * cs - 117, dtype=np.uint8).tobytes()
        inode = _file_with_data(fab, "/deg", data)
        routing = fab.routing()
        chain = routing.chains[fab.chain_ids[0]]
        victim = chain.target_of_shard(1)
        fab.fail_node(routing.node_of_target(victim.target_id).node_id)
        client = fio.storage
        before = client._ec_degraded._value
        ranges = [
            (0, cs),                   # whole stripe
            (shard - 5, 10),           # straddles the dead shard's edge
            (cs - 9, 18),              # straddles stripe boundary
            (cs + shard, shard),       # inside the dead shard, stripe 1
            (2 * cs, cs),              # the short tail stripe
        ]
        blobs = fio.batch_read_files(
            [(inode, off, size) for off, size in ranges])
        for (off, size), blob in zip(ranges, blobs):
            assert blob == data[off:off + size], (off, size)
        assert client._ec_degraded._value > before
        fab.close()

    def test_rebuild_under_concurrent_writes_converges(self):
        """Kill a target, wipe its disk, and keep WRITING (overwrites +
        new stripes, full and sub-stripe) while rebuild rounds run: the
        chain must converge to SERVING with every stripe byte-exact."""
        from tpu3fs.storage.ec_resync import EcResyncWorker

        rng = np.random.default_rng(63)
        fab = self._ec_fab(k=3, m=2, nodes=5)
        client = fab.storage_client()
        cs = self.CS
        cid_of = lambda i: ChunkId(92, i)  # noqa: E731
        shadow = {}
        for i in range(10):
            data = rng.integers(0, 256, cs, dtype=np.uint8).tobytes()
            assert client.write_stripe(fab.chain_ids[0], cid_of(i), data,
                                       chunk_size=cs).ok
            shadow[i] = bytearray(data)
        routing = fab.routing()
        chain = routing.chains[fab.chain_ids[0]]
        victim = chain.target_of_shard(2)
        vnode = routing.node_of_target(victim.target_id)
        fab.fail_node(vnode.node_id)
        svc = fab.nodes[vnode.node_id].service
        eng = svc.target(victim.target_id).engine
        for meta in eng.all_metadata():
            eng.remove(meta.chunk_id)
        fab.restart_node(vnode.node_id)
        fab.tick()
        workers = {nid: EcResyncWorker(node.service, fab.send)
                   for nid, node in fab.nodes.items()}
        for rnd in range(8):
            for nid, w in workers.items():
                if fab.nodes[nid].alive:
                    w.run_once()
            # concurrent mutations between rounds: overwrite one stripe,
            # sub-stripe-write another, add a brand-new one
            i_over = rnd % 10
            data = rng.integers(0, 256, cs, dtype=np.uint8).tobytes()
            assert client.write_stripe(
                fab.chain_ids[0], cid_of(i_over), data, chunk_size=cs).ok
            shadow[i_over] = bytearray(data)
            patch = rng.integers(0, 256, 64, dtype=np.uint8).tobytes()
            r = client.write_stripe_rmw(
                fab.chain_ids[0], cid_of((rnd + 1) % 10), 100, patch,
                chunk_size=cs)
            if r is None:  # mid-rebuild fallback: full RMW ladder
                cur = client.read_stripe(
                    fab.chain_ids[0], cid_of((rnd + 1) % 10), 0, cs,
                    chunk_size=cs)
                merged = bytearray(cur.data.ljust(cs, b"\x00"))
                merged[100:164] = patch
                assert client.write_stripe(
                    fab.chain_ids[0], cid_of((rnd + 1) % 10),
                    bytes(merged[:max(cur.logical_len, 164)]),
                    chunk_size=cs,
                    update_ver=client.next_stripe_ver(cur.commit_ver)).ok
                shadow[(rnd + 1) % 10][:] = merged[:cs]
            else:
                shadow[(rnd + 1) % 10][100:164] = patch
            new_i = 10 + rnd
            data = rng.integers(0, 256, cs - 33, dtype=np.uint8).tobytes()
            assert client.write_stripes(
                fab.chain_ids[0], [(cid_of(new_i), data)],
                chunk_size=cs)[0].ok
            shadow[new_i] = bytearray(data.ljust(cs, b"\x00"))
            fab.tick()
            if all(t.public_state == PublicTargetState.SERVING
                   for t in fab.routing().chains[fab.chain_ids[0]].targets):
                break
        # a couple of quiesced rounds mop up stripes written mid-rebuild
        for _ in range(4):
            for nid, w in workers.items():
                if fab.nodes[nid].alive:
                    w.run_once()
            fab.tick()
        assert all(t.public_state == PublicTargetState.SERVING
                   for t in fab.routing().chains[fab.chain_ids[0]].targets)
        for i, want in shadow.items():
            got = client.read_stripe(fab.chain_ids[0], cid_of(i), 0, cs,
                                     chunk_size=cs)
            assert got.ok and got.data == bytes(want).ljust(cs, b"\x00"), i
        fab.close()

    def test_trusted_crc_validated_installs_on_ec_chains(self):
        """The EC install contract: the client-computed shard CRC is the
        ONE checksum pass — the engine validates against it and adopts it
        as the stored checksum; a wrong CRC is refused before anything
        mutates; a rebase stage re-adopts the committed checksum."""
        from tpu3fs.ops.crc32c import crc32c
        from tpu3fs.storage.craq import ShardWriteReq

        fab = self._ec_fab(k=3, m=1, nodes=4)
        client = fab.storage_client()
        cs = self.CS
        cid = ChunkId(93, 0)
        base = bytes(range(256)) * (cs // 256)
        assert client.write_stripe(fab.chain_ids[0], cid, base,
                                   chunk_size=cs).ok
        routing = fab.routing()
        chain = routing.chains[fab.chain_ids[0]]
        t0 = chain.target_of_shard(0)
        node0 = routing.node_of_target(t0.target_id)
        eng = fab.nodes[node0.node_id].service.target(t0.target_id).engine
        meta = eng.get_meta(cid)
        from tpu3fs.ops.stripe import shard_size_of

        S = shard_size_of(cs, 3)
        want = base[:S]
        # stored checksum IS the client's CRC of the trimmed shard bytes
        assert meta.checksum.value == crc32c(want)
        # a corrupt CRC is refused, committed shard untouched
        bad = ShardWriteReq(
            chain_id=fab.chain_ids[0], chain_ver=chain.chain_version,
            target_id=t0.target_id, chunk_id=cid, data=b"\x11" * S,
            crc=12345, update_ver=client.next_stripe_ver(meta.committed_ver),
            chunk_size=S, logical_len=cs, phase=1)
        reply = fab.send(node0.node_id, "write_shard", bad)
        assert reply.code == Code.CHUNK_CHECKSUM_MISMATCH
        assert eng.read(cid) == want
        # a rebase stage adopts the committed content + checksum
        ver2 = client.next_stripe_ver(meta.committed_ver)
        rebase = ShardWriteReq(
            chain_id=fab.chain_ids[0], chain_ver=chain.chain_version,
            target_id=t0.target_id, chunk_id=cid, data=b"", crc=0,
            update_ver=ver2, chunk_size=S, logical_len=cs, phase=1,
            rebase_of=meta.committed_ver)
        reply = fab.send(node0.node_id, "write_shard", rebase)
        assert reply.ok and reply.checksum.value == crc32c(want)
        # rebase against a superseded base version is refused
        stale = ShardWriteReq(
            chain_id=fab.chain_ids[0], chain_ver=chain.chain_version,
            target_id=t0.target_id, chunk_id=cid, data=b"", crc=0,
            update_ver=client.next_stripe_ver(ver2), chunk_size=S,
            logical_len=cs, phase=1, rebase_of=meta.committed_ver + 7)
        reply = fab.send(node0.node_id, "write_shard", stale)
        assert reply.code == Code.CHUNK_STALE_UPDATE
        fab.close()

    def test_rmw_falls_back_when_chain_degraded(self):
        """A partial write on a degraded chain must still land (full
        re-encode ladder) — the RMW fast path declines, it never wedges."""
        rng = np.random.default_rng(64)
        fab = self._ec_fab(k=3, m=2, nodes=5)
        fio = fab.file_client()
        cs = self.CS
        data = rng.integers(0, 256, cs, dtype=np.uint8).tobytes()
        inode = _file_with_data(fab, "/degw", data)
        routing = fab.routing()
        chain = routing.chains[fab.chain_ids[0]]
        victim = chain.target_of_shard(4)  # a parity shard's node
        fab.fail_node(routing.node_of_target(victim.target_id).node_id)
        patch = rng.integers(0, 256, 50, dtype=np.uint8).tobytes()
        assert fio.write(inode, 123, patch) == 50
        shadow = bytearray(data)
        shadow[123:173] = patch
        assert fio.read(inode, 0, len(data)) == bytes(shadow)
        assert fio.storage._ec_rmw_fallback._value >= 1
        fab.close()


class TestEcPartialWriteErrorPath:
    def test_failed_rmw_read_raises_fserror_with_message(self):
        """A failed stripe read inside the partial-EC RMW ladder must
        surface as FsError(code, message), not AttributeError — failed
        ReadReplies carry no message field (found by the production-day
        soak: an archive write failing inside a fault window crashed the
        client instead of raising the real error)."""
        from tpu3fs.storage.craq import ReadReply
        from tpu3fs.utils.result import FsError

        fab = Fabric(SystemSetupConfig(
            num_storage_nodes=4, num_chains=1, chunk_size=1 << 14,
            ec_k=3, ec_m=1))
        fio = fab.file_client()
        sc = fio.storage
        sc.write_stripe_rmw = lambda *a, **k: None   # force the ladder
        sc.read_stripe = lambda *a, **k: ReadReply(Code.TARGET_OFFLINE)
        inode = fab.meta.create("/ecf").inode
        with pytest.raises(FsError) as ei:
            fio.write(inode, 8, b"x" * 64)
        assert ei.value.code == Code.TARGET_OFFLINE
        assert "stripe RMW read" in ei.value.status.message
