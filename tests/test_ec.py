"""EC chain tables end-to-end: device codec, stripe IO, degraded reads,
failed-target rebuild through the TPU decode path.

The reference has no RS path (CRAQ replication only; "EC" is a chain-table
type in deploy/data_placement/src/model/data_placement.py:30). These tests
cover the added TPU-native capability: client writes erasure-code on device
(RSCode + BatchCrc32c), shards land on chain-position targets, reads verify
and reconstruct, and EcResyncWorker rebuilds a lost target from k survivors
with batched device decodes.
"""

import numpy as np
import pytest

from tpu3fs.client.storage_client import ec_logical_ver
from tpu3fs.fabric.fabric import Fabric, SystemSetupConfig
from tpu3fs.meta.store import OpenFlags
from tpu3fs.ops.stripe import get_codec, shard_size_of, trim_rebuilt_shard
from tpu3fs.storage.types import ChunkId
from tpu3fs.utils.result import Code

K, M = 3, 1
CHUNK = 1 << 16           # stripe logical size
S = shard_size_of(CHUNK, K)


def ec_fabric(**kw) -> Fabric:
    cfg = SystemSetupConfig(
        num_storage_nodes=kw.pop("nodes", K + M),
        num_chains=kw.pop("chains", 2),
        chunk_size=kw.pop("chunk_size", CHUNK),
        ec_k=kw.pop("k", K),
        ec_m=kw.pop("m", M),
        **kw,
    )
    return Fabric(cfg)


class TestStripeCodec:
    def test_encode_matches_numpy_gold(self):
        codec = get_codec(4, 2, 1024)
        rng = np.random.default_rng(0)
        data = rng.integers(0, 256, (3, 4, 1024), dtype=np.uint8)
        shards, crcs = codec.encode_batch(data)
        gold = codec.rs.encode_np(data)
        assert np.array_equal(shards[:, 4:], gold)
        assert np.array_equal(shards[:, :4], data)
        from tpu3fs.ops.crc32c import crc32c

        for b in range(3):
            for j in range(6):
                assert crcs[b, j] == crc32c(shards[b, j].tobytes())

    def test_reconstruct_roundtrip(self):
        codec = get_codec(3, 2, 512)
        rng = np.random.default_rng(1)
        data = rng.integers(0, 256, (2, 3, 512), dtype=np.uint8)
        shards, _ = codec.encode_batch(data)
        # lose shards 0 (data) and 4 (parity); rebuild from 1,2,3
        out = codec.reconstruct_batch((1, 2, 3), (0, 4), shards[:, [1, 2, 3]])
        assert np.array_equal(out[:, 0], shards[:, 0])
        assert np.array_equal(out[:, 1], shards[:, 4])

    def test_trim_rebuilt_shard_cases(self):
        k, s = 3, 100
        full = bytes(range(100))
        # a later data shard has content -> full
        assert trim_rebuilt_shard(full, 0, {1: 40, 2: 0}, k, s) == full
        # an earlier shard is short -> shard must be empty
        assert trim_rebuilt_shard(full, 2, {0: 100, 1: 30}, k, s) == b""
        # ambiguous tail shard -> trailing-zero trim
        pad = b"ab" + b"\x00" * 98
        assert trim_rebuilt_shard(pad, 1, {0: 100, 2: 0}, k, s) == b"ab"
        # parity shards stay untouched
        assert trim_rebuilt_shard(pad, k, {0: 10}, k, s) == pad


class TestEcStripeIo:
    def test_write_read_roundtrip_and_subranges(self):
        fab = ec_fabric()
        client = fab.storage_client()
        rng = np.random.default_rng(2)
        data = rng.integers(0, 256, CHUNK, dtype=np.uint8).tobytes()
        chain = fab.chain_ids[0]
        cid = ChunkId(7, 0)
        assert client.write_stripe(chain, cid, data, chunk_size=CHUNK).ok
        got = client.read_stripe(chain, cid, 0, CHUNK, chunk_size=CHUNK)
        assert got.ok and got.data == data
        # sub-range crossing a shard boundary
        lo, n = S - 100, 300
        sub = client.read_stripe(chain, cid, lo, n, chunk_size=CHUNK)
        assert sub.ok and sub.data == data[lo : lo + n]
        # every shard target holds its trimmed slice with the stripe version
        routing = fab.routing()
        cinfo = routing.chains[chain]
        for j in range(K + M):
            t = cinfo.target_of_shard(j)
            node = routing.node_of_target(t.target_id)
            svc = fab.nodes[node.node_id].service
            meta = svc.target(t.target_id).engine.get_meta(cid)
            assert meta is not None and ec_logical_ver(meta.committed_ver) == 1
            if j < K:
                assert svc.target(t.target_id).engine.read(cid) == \
                    data[j * S : (j + 1) * S]

    def test_short_stripe_lengths_are_precise(self):
        fab = ec_fabric()
        client = fab.storage_client()
        chain = fab.chain_ids[0]
        cid = ChunkId(8, 0)
        payload = b"x" * (S + 123)  # spills 123 bytes into shard 1
        assert client.write_stripe(chain, cid, payload, chunk_size=CHUNK).ok
        idx, length = client.query_last_chunk(chain, 8)
        assert (idx, length) == (0, S + 123)
        got = client.read_stripe(chain, cid, 0, CHUNK, chunk_size=CHUNK)
        assert got.data[: len(payload)] == payload
        assert got.logical_len == len(payload)

    def test_overwrite_bumps_stripe_version(self):
        fab = ec_fabric()
        client = fab.storage_client()
        chain = fab.chain_ids[0]
        cid = ChunkId(9, 0)
        r1 = client.write_stripe(chain, cid, b"v1" * 100, chunk_size=CHUNK)
        assert r1.ok
        r2 = client.write_stripe(chain, cid, b"v2" * 200, chunk_size=CHUNK)
        # the ENCODED version strictly advances (total order); the logical
        # part may stay when the overwrite's nonce wins the tie, so assert
        # order, not an exact logical number
        assert r2.ok and r2.update_ver > r1.update_ver
        got = client.read_stripe(chain, cid, 0, 400, chunk_size=CHUNK)
        assert got.data == b"v2" * 200
        # a stale writer pinned at an old version loses
        r_stale = client.write_stripe(
            chain, cid, b"old" * 10, chunk_size=CHUNK, update_ver=1)
        # the client ladder re-probes above the committed version, so the
        # write LANDS but at a NEWER version (no silent clobber of v2 slot)
        assert r_stale.ok and r_stale.update_ver >= 3

    def test_degraded_read_with_dead_node(self):
        fab = ec_fabric()
        client = fab.storage_client()
        rng = np.random.default_rng(3)
        data = rng.integers(0, 256, CHUNK, dtype=np.uint8).tobytes()
        chain = fab.chain_ids[0]
        cid = ChunkId(10, 0)
        assert client.write_stripe(chain, cid, data, chunk_size=CHUNK).ok
        # kill the node holding data shard 1 (before mgmtd notices)
        routing = fab.routing()
        t1 = routing.chains[chain].target_of_shard(1)
        fab.kill_node(routing.node_of_target(t1.target_id).node_id)
        got = client.read_stripe(chain, cid, 0, CHUNK, chunk_size=CHUNK)
        assert got.ok and got.data == data
        # after mgmtd marks it offline the degraded read still works
        fab.clock.advance(fab.cfg.heartbeat_timeout_s + 1)
        fab.tick()
        got2 = client.read_stripe(chain, cid, 0, CHUNK, chunk_size=CHUNK)
        assert got2.ok and got2.data == data

    def test_write_is_strict_while_failure_unnoticed(self):
        """A shard target that is dead but still marked SERVING must FAIL
        the stripe write (not silently skip): a stale shard on a target
        that never goes through rebuild would serve stale sub-stripe reads
        forever (code-review r2 finding)."""
        from tpu3fs.client.storage_client import RetryOptions

        fab = ec_fabric()
        client = fab.storage_client(retry=RetryOptions(
            max_retries=2, backoff_base_s=0.001, backoff_max_s=0.01))
        chain = fab.chain_ids[0]
        routing = fab.routing()
        t0 = routing.chains[chain].target_of_shard(0)
        fab.kill_node(routing.node_of_target(t0.target_id).node_id)
        # mgmtd has NOT noticed: target still SERVING
        r = client.write_stripe(chain, ChunkId(12, 0), b"x" * 100,
                                chunk_size=CHUNK)
        assert not r.ok

    def test_craq_ops_rejected_on_ec_chains(self):
        fab = ec_fabric()
        client = fab.storage_client()
        chain = fab.chain_ids[0]
        from tpu3fs.utils.result import FsError

        with pytest.raises(FsError) as ei:
            client.write_chunk(chain, ChunkId(13, 0), 0, b"x")
        assert ei.value.code == Code.INVALID_ARG
        replies = client.batch_write([(chain, ChunkId(13, 1), 0, b"y")])
        assert replies[0].code == Code.INVALID_ARG

    def test_multiple_shards_per_node_length_precise(self):
        """Fewer nodes than k+m: one node hosts several shards of a chain;
        query_last_chunk must max over ALL its local shards."""
        fab = ec_fabric(nodes=2)
        client = fab.storage_client()
        chain = fab.chain_ids[0]
        payload = b"p" * (2 * S + 77)   # last data lives in shard 2
        assert client.write_stripe(
            chain, ChunkId(14, 0), payload, chunk_size=CHUNK).ok
        idx, length = client.query_last_chunk(chain, 14)
        assert (idx, length) == (0, 2 * S + 77)

    def test_writes_continue_with_dead_parity_node(self):
        fab = ec_fabric()
        client = fab.storage_client()
        chain = fab.chain_ids[0]
        routing = fab.routing()
        tp = routing.chains[chain].target_of_shard(K)  # parity shard
        fab.fail_node(routing.node_of_target(tp.target_id).node_id)
        cid = ChunkId(11, 0)
        data = b"q" * CHUNK
        r = client.write_stripe(chain, cid, data, chunk_size=CHUNK)
        assert r.ok  # k data shards acked
        got = client.read_stripe(chain, cid, 0, CHUNK, chunk_size=CHUNK)
        assert got.ok and got.data == data


class TestEcRebuild:
    def test_failed_target_rebuilt_through_device_decode(self):
        fab = ec_fabric()
        client = fab.storage_client()
        rng = np.random.default_rng(4)
        chain = fab.chain_ids[0]
        stripes = {}
        for i in range(5):
            payload = rng.integers(0, 256, CHUNK, dtype=np.uint8).tobytes()
            stripes[i] = payload
            assert client.write_stripe(
                chain, ChunkId(20, i), payload, chunk_size=CHUNK).ok
        # short tail stripe exercises trimming through the rebuild
        stripes[5] = b"tail" * 10
        assert client.write_stripe(
            chain, ChunkId(20, 5), stripes[5], chunk_size=CHUNK).ok

        routing = fab.routing()
        t1 = routing.chains[chain].target_of_shard(1)
        victim_node = routing.node_of_target(t1.target_id).node_id
        originals = {}
        svc = fab.nodes[victim_node].service
        for meta in svc.target(t1.target_id).engine.all_metadata():
            originals[meta.chunk_id.to_bytes()] = (
                svc.target(t1.target_id).engine.read(meta.chunk_id),
                meta.checksum.value,
            )
        # fail the node AND lose its disk
        fab.fail_node(victim_node)
        from tpu3fs.storage.engine import MemChunkEngine

        svc.target(t1.target_id).engine = MemChunkEngine()
        fab.restart_node(victim_node)
        # target should be syncing now; rebuild it
        assert fab.routing().targets[t1.target_id].public_state.name in (
            "SYNCING", "WAITING")
        moved = fab.resync_all()
        assert moved >= 6
        # chain fully serving again
        assert all(
            t.public_state.name == "SERVING"
            for t in fab.routing().chains[chain].targets
        )
        # rebuilt shard bytes + checksums identical to the originals
        rebuilt_engine = svc.target(t1.target_id).engine
        for key, (content, crc) in originals.items():
            metas = [m for m in rebuilt_engine.all_metadata()
                     if m.chunk_id.to_bytes() == key]
            assert metas, f"stripe {key!r} not rebuilt"
            assert rebuilt_engine.read(metas[0].chunk_id) == content
            assert metas[0].checksum.value == crc
        # and reads come back byte-exact
        for i, payload in stripes.items():
            got = client.read_stripe(
                chain, ChunkId(20, i), 0, CHUNK, chunk_size=CHUNK)
            assert got.ok and got.data[: len(payload)] == payload

    def test_rebuild_over_mesh_collective(self):
        """The pod-scale rebuild path: same worker, decode inside an
        all-gather collective over a (k+m)-device mesh."""
        import jax

        if len(jax.devices()) < K + M:
            pytest.skip("needs k+m devices")
        from tpu3fs.parallel.mesh import make_storage_mesh

        fab = ec_fabric()
        client = fab.storage_client()
        chain = fab.chain_ids[0]
        data = b"meshmesh" * (CHUNK // 8)
        assert client.write_stripe(
            chain, ChunkId(30, 0), data, chunk_size=CHUNK).ok
        routing = fab.routing()
        t2 = routing.chains[chain].target_of_shard(2)
        victim_node = routing.node_of_target(t2.target_id).node_id
        svc = fab.nodes[victim_node].service
        original = svc.target(t2.target_id).engine.read(ChunkId(30, 0))
        fab.fail_node(victim_node)
        from tpu3fs.storage.engine import MemChunkEngine

        svc.target(t2.target_id).engine = MemChunkEngine()
        fab.restart_node(victim_node)
        mesh = make_storage_mesh(
            K + M, devices=jax.devices()[: K + M])
        assert fab.resync_all(mesh=mesh) >= 1
        assert svc.target(t2.target_id).engine.read(ChunkId(30, 0)) == original


class TestEcFileIo:
    def test_file_write_read_over_ec_chains(self):
        fab = ec_fabric()
        fio = fab.file_client()
        res = fab.meta.create("/ec.bin", flags=OpenFlags.WRITE,
                              client_id="c1")
        rng = np.random.default_rng(5)
        body = rng.integers(0, 256, CHUNK * 2 + 777, dtype=np.uint8).tobytes()
        fio.write(res.inode, 0, body)
        inode = fab.meta.close(res.inode.id, res.session_id)
        assert inode.length == len(body)
        assert fio.read(inode, 0, len(body)) == body
        # cross-stripe partial read
        assert fio.read(inode, CHUNK - 50, 200) == body[CHUNK - 50 : CHUNK + 150]

    def test_partial_writes_read_modify_write(self):
        fab = ec_fabric()
        fio = fab.file_client()
        res = fab.meta.create("/rmw.bin", flags=OpenFlags.WRITE,
                              client_id="c1")
        fio.write(res.inode, 0, b"A" * 1000)
        fio.write(res.inode, 500, b"B" * 1000)      # overlaps tail
        fio.write(res.inode, 3000, b"C" * 100)      # leaves a hole
        inode = fab.meta.close(res.inode.id, res.session_id)
        assert inode.length == 3100
        got = fio.read(inode, 0, 3100)
        assert got[:500] == b"A" * 500
        assert got[500:1500] == b"B" * 1000
        assert got[1500:3000] == b"\x00" * 1500     # hole reads as zeros
        assert got[3000:] == b"C" * 100

    def test_truncate_reencodes_boundary_stripe(self):
        fab = ec_fabric()
        fio = fab.file_client()
        res = fab.meta.create("/trunc.bin", flags=OpenFlags.WRITE,
                              client_id="c1")
        body = b"z" * (CHUNK + 4000)
        fio.write(res.inode, 0, body)
        fab.meta.close(res.inode.id, res.session_id)
        inode = fab.meta.truncate("/trunc.bin", 1234)
        assert inode.length == 1234
        assert fio.read(inode, 0, 5000) == b"z" * 1234
        # second stripe is gone on every target
        routing = fab.routing()
        for chain_id in set(inode.layout.chains):
            cinfo = routing.chains[chain_id]
            for t in cinfo.targets:
                node = routing.node_of_target(t.target_id)
                eng = fab.nodes[node.node_id].service.target(t.target_id).engine
                for meta in eng.all_metadata():
                    if meta.chunk_id.file_id == inode.id:
                        assert meta.chunk_id.index == 0

    def test_remove_and_gc_reclaims_all_shards(self):
        fab = ec_fabric()
        fio = fab.file_client()
        res = fab.meta.create("/gc.bin", flags=OpenFlags.WRITE, client_id="c1")
        fio.write(res.inode, 0, b"g" * CHUNK)
        fab.meta.close(res.inode.id, res.session_id)
        fab.meta.remove("/gc.bin")
        assert fab.run_gc() == 1
        for node in fab.nodes.values():
            for target in node.service.targets():
                assert not [
                    m for m in target.engine.all_metadata()
                    if m.chunk_id.file_id == res.inode.id
                ]

    def test_batched_reads_ride_ec(self):
        fab = ec_fabric()
        fio = fab.file_client()
        bodies = {}
        inodes = []
        for i in range(3):
            res = fab.meta.create(f"/b{i}.bin", flags=OpenFlags.WRITE,
                                  client_id="c1")
            body = bytes([i]) * (CHUNK + i * 100)
            fio.write(res.inode, 0, body)
            inodes.append(fab.meta.close(res.inode.id, res.session_id))
            bodies[i] = body
        got = fio.batch_read_files([
            (ino, 0, len(bodies[i])) for i, ino in enumerate(inodes)
        ])
        for i, b in enumerate(got):
            assert b == bodies[i]


class TestLogicalLengthFidelity:
    """Round-3 fix: ShardWriteReq.logical_len is persisted in the engine's
    aux tag, so zero-tail stripes keep their exact length across
    lose-disk -> rebuild -> stat (round-2 weak #8)."""

    def test_zero_tail_file_exact_length_across_rebuild(self):
        CHUNK = 12 << 10
        fab = Fabric(SystemSetupConfig(
            num_storage_nodes=4, num_chains=1, chunk_size=CHUNK,
            ec_k=3, ec_m=1))
        client = fab.storage_client()
        chain = fab.chain_ids[0]
        # content ends in a run of zeros INSIDE the last shard: the old
        # rstrip inference would undershoot this length after a rebuild
        logical = 10_000
        payload = b"Z" * 9_000 + b"\x00" * 1_000
        assert client.write_stripe(
            chain, ChunkId(30, 0), payload, chunk_size=CHUNK).ok
        assert fab.send(
            fab.routing().node_of_target(
                fab.routing().chains[chain].targets[0].target_id).node_id,
            "query_last_chunk", (chain, 30)) == (0, logical)
        # lose the LAST nonempty data shard's disk (the ambiguous one)
        from tpu3fs.ops.stripe import shard_size_of

        S = shard_size_of(CHUNK, 3)
        last_shard = (logical - 1) // S
        routing = fab.routing()
        t = routing.chains[chain].target_of_shard(last_shard)
        victim_node = routing.node_of_target(t.target_id).node_id
        svc = fab.nodes[victim_node].service
        fab.fail_node(victim_node)
        from tpu3fs.storage.engine import MemChunkEngine

        svc.target(t.target_id).engine = MemChunkEngine()
        fab.restart_node(victim_node)
        assert fab.resync_all() >= 1
        # the rebuilt shard carries the EXACT logical length (engine aux)
        meta = svc.target(t.target_id).engine.get_meta(ChunkId(30, 0))
        assert meta is not None and meta.aux == logical
        got = client.read_stripe(chain, ChunkId(30, 0), 0, CHUNK,
                                 chunk_size=CHUNK)
        assert got.ok and got.logical_len == logical
        assert got.data[:logical] == payload
        # stat through the storage path stays exact after the rebuild
        node = fab.routing().node_of_target(
            fab.routing().chains[chain].targets[0].target_id)
        assert fab.send(node.node_id, "query_last_chunk",
                        (chain, 30)) == (0, logical)

    def test_write_stripes_overwrite_stays_on_batch_path(self):
        """Overwriting existing stripes probes versions in ONE statChunks
        RPC and keeps the batch path (round-2 weak #4)."""
        CHUNK = 12 << 10
        fab = Fabric(SystemSetupConfig(
            num_storage_nodes=4, num_chains=1, chunk_size=CHUNK,
            ec_k=3, ec_m=1))
        client = fab.storage_client()
        chain = fab.chain_ids[0]
        items1 = [(ChunkId(31, i), bytes([i + 1]) * CHUNK) for i in range(6)]
        r1 = client.write_stripes(chain, items1, chunk_size=CHUNK)
        assert all(r.ok and ec_logical_ver(r.commit_ver) == 1 for r in r1)
        # overwrite the same stripes: versions must be probed (2), not
        # collapsed into the per-stripe conflict ladder
        items2 = [(ChunkId(31, i), bytes([i + 101]) * CHUNK)
                  for i in range(6)]
        r2 = client.write_stripes(chain, items2, chunk_size=CHUNK)
        assert all(r.ok and ec_logical_ver(r.commit_ver) == 2 for r in r2), r2
        for cid, data in items2:
            got = client.read_stripe(chain, cid, 0, CHUNK, chunk_size=CHUNK)
            assert got.ok and got.data == data


class TestBatchShardWrite:
    """Server-side batched shard install (round-3 verdict ask #6): one
    engine crossing per target, same semantics as the per-op write_shard."""

    def _reqs(self, fab, chain_id, cids, payload, ver=1):
        from tpu3fs.ops.stripe import get_codec
        from tpu3fs.storage.craq import ShardWriteReq

        chain = fab.routing().chains[chain_id]
        codec = get_codec(chain.ec_k, chain.ec_m, S)
        reqs = []
        for cid in cids:
            shards, crcs = codec.encode_stripe(payload)
            for j in range(chain.ec_k + chain.ec_m):
                t = chain.target_of_shard(j)
                data = (payload[j * S:(j + 1) * S] if j < chain.ec_k
                        else shards[j].tobytes())
                crc = (int(crcs[j]) if len(data) == S
                       else codec.crc_host(data))
                reqs.append(ShardWriteReq(
                    chain_id=chain_id, chain_ver=chain.chain_version,
                    target_id=t.target_id, chunk_id=cid, data=data,
                    crc=crc, update_ver=ver, chunk_size=S,
                    logical_len=len(payload)))
        return reqs

    def test_batch_install_then_duplicate_then_stale(self):
        fab = ec_fabric()
        chain_id = fab.chain_ids[0]
        payload = bytes(range(256)) * (CHUNK // 256)
        cids = [ChunkId(900, i) for i in range(4)]
        reqs = self._reqs(fab, chain_id, cids, payload, ver=1)
        # group per node the way the client does, install via the batch RPC
        by_node = {}
        chain = fab.routing().chains[chain_id]
        for r in reqs:
            node = fab.routing().node_of_target(r.target_id)
            by_node.setdefault(node.node_id, []).append(r)
        for node_id, group in by_node.items():
            outs = fab.send(node_id, "batch_write_shard", group)
            assert all(o.ok for o in outs), [o.message for o in outs]
        # exact duplicate batch: idempotent OK
        for node_id, group in by_node.items():
            outs = fab.send(node_id, "batch_write_shard", group)
            assert all(o.ok for o in outs)
        # stale (lower) version with different content: CHUNK_STALE_UPDATE
        stale = self._reqs(fab, chain_id, cids, b"\xAA" * CHUNK, ver=1)
        node_id = fab.routing().node_of_target(stale[0].target_id).node_id
        outs = fab.send(node_id, "batch_write_shard", [stale[0]])
        assert outs[0].code == Code.CHUNK_STALE_UPDATE

    def test_batch_crc_mismatch_rejected_individually(self):
        fab = ec_fabric()
        chain_id = fab.chain_ids[0]
        payload = b"\x42" * CHUNK
        good = self._reqs(
            fab, chain_id, [ChunkId(901, 0), ChunkId(901, 1)], payload, ver=1)
        bad = good[0].__class__(**{**good[0].__dict__, "crc": 0xDEAD})
        node_of = lambda r: fab.routing().node_of_target(r.target_id).node_id
        # shard 0 of BOTH stripes lands on the same target: one bad op in a
        # batch must not poison its sibling
        sibling = next(r for r in good[1:]
                       if r.target_id == good[0].target_id)
        outs = fab.send(node_of(good[0]), "batch_write_shard", [bad, sibling])
        assert outs[0].code == Code.CHUNK_CHECKSUM_MISMATCH
        assert outs[1].ok

    def test_duplicate_chunk_same_batch_applies_in_order(self):
        fab = ec_fabric()
        chain_id = fab.chain_ids[0]
        r1 = self._reqs(fab, chain_id, [ChunkId(902, 0)], b"\x01" * CHUNK, 1)
        r2 = self._reqs(fab, chain_id, [ChunkId(902, 0)], b"\x02" * CHUNK, 2)
        # same chunk at versions 1 then 2 in ONE request
        node_of = lambda r: fab.routing().node_of_target(r.target_id).node_id
        pair = [r1[0], next(r for r in r2 if r.target_id == r1[0].target_id)]
        outs = fab.send(node_of(r1[0]), "batch_write_shard", pair)
        assert outs[0].ok and outs[1].ok
        assert outs[1].commit_ver == 2


class TestHealthyChainRepair:
    """Round-4 advisor (medium): a client crash between phase-2 commit RPCs
    on a FULLY-HEALTHY chain leaves committed(v_new) on c shards, m < c < k
    — no version holds a committed k-quorum, so the stripe is undecodable,
    and the roll-forward inside _rebuild_target never runs because nothing
    is SYNCING. EcResyncWorker._repair_healthy closes this: the chain's
    first serving target sweeps split stripes and commits the stragglers."""

    def _crash_mid_commit(self, fab, chain_id, cid, data, commits_allowed):
        """Drive write_stripe through a messenger that dies (non-FsError,
        like a process crash) after `commits_allowed` phase-2 commits."""
        client = fab.storage_client()
        committed = []

        real_send = fab.send

        def send(node_id, method, payload):
            if method == "write_shard" and getattr(payload, "phase", 1) == 2:
                if len(committed) >= commits_allowed:
                    raise RuntimeError("client process died mid-commit")
                committed.append(payload.target_id)
            return real_send(node_id, method, payload)

        client._messenger = send
        with pytest.raises(RuntimeError):
            client.write_stripe(chain_id, cid, data, chunk_size=CHUNK)
        return len(committed)

    def test_split_stripe_unreadable_then_repaired(self):
        from tpu3fs.storage.ec_resync import EcResyncWorker

        fab = ec_fabric()
        client = fab.storage_client()
        chain_id = fab.chain_ids[0]
        cid = ChunkId(777, 0)
        v1 = b"\x0a" * CHUNK
        assert client.write_stripe(chain_id, cid, v1, chunk_size=CHUNK).ok
        v2 = b"\x0b" * CHUNK
        # crash after 2 of 4 commits: committed(v2)=2 in (m=1, k=3)
        n = self._crash_mid_commit(fab, chain_id, cid, v2, commits_allowed=2)
        assert n == 2
        got = client.read_stripe(chain_id, cid, 0, CHUNK, chunk_size=CHUNK)
        assert not got.ok, "no version has a committed k-quorum"
        # every target is SERVING: the healthy-chain sweep must repair it
        moved = 0
        for node in fab.nodes.values():
            moved += EcResyncWorker(node.service, fab.send).run_once()
        got = client.read_stripe(chain_id, cid, 0, CHUNK, chunk_size=CHUNK)
        assert got.ok and got.data == v2

    def test_fully_staged_uncommitted_rolls_forward(self):
        """Crash BEFORE any phase-2 commit: every shard staged v_new as
        pending. committed(v_old) still has its k-quorum (reads keep
        working at v_old); the sweep completes the write to v_new."""
        from tpu3fs.storage.ec_resync import EcResyncWorker

        fab = ec_fabric()
        client = fab.storage_client()
        chain_id = fab.chain_ids[0]
        cid = ChunkId(778, 0)
        v1 = b"\x01" * CHUNK
        assert client.write_stripe(chain_id, cid, v1, chunk_size=CHUNK).ok
        v2 = b"\x02" * CHUNK
        assert self._crash_mid_commit(
            fab, chain_id, cid, v2, commits_allowed=0) == 0
        got = client.read_stripe(chain_id, cid, 0, CHUNK, chunk_size=CHUNK)
        assert got.ok and got.data == v1  # old version intact pre-repair
        for node in fab.nodes.values():
            EcResyncWorker(node.service, fab.send).run_once()
        got = client.read_stripe(chain_id, cid, 0, CHUNK, chunk_size=CHUNK)
        assert got.ok and got.data == v2

    def test_healthy_sweep_idle_on_clean_chain(self):
        """No pending / no version split: the sweep must be a no-op (no
        spurious write_shard traffic on clean chains)."""
        from tpu3fs.storage.ec_resync import EcResyncWorker

        fab = ec_fabric()
        client = fab.storage_client()
        chain_id = fab.chain_ids[0]
        assert client.write_stripe(
            chain_id, ChunkId(779, 0), b"x" * CHUNK, chunk_size=CHUNK).ok
        writes = []
        real_send = fab.send

        def spy(node_id, method, payload):
            if method == "write_shard":
                writes.append(payload)
            return real_send(node_id, method, payload)

        for node in fab.nodes.values():
            EcResyncWorker(node.service, spy).run_once()
        assert writes == []

    def test_transient_commit_failure_does_not_freeze_memo(self):
        """A sweep whose phase-2 commit fails transiently must NOT be
        memoized as fruitless — the pending signature is unchanged, so a
        frozen memo would leave the stripe unreadable forever."""
        from tpu3fs.storage.ec_resync import EcResyncWorker

        fab = ec_fabric()
        client = fab.storage_client()
        chain_id = fab.chain_ids[0]
        cid = ChunkId(781, 0)
        assert client.write_stripe(
            chain_id, cid, b"\x01" * CHUNK, chunk_size=CHUNK).ok
        v2 = b"\x02" * CHUNK
        assert self._crash_mid_commit(
            fab, chain_id, cid, v2, commits_allowed=2) == 2

        real_send = fab.send
        drop = [True]

        def flaky(node_id, method, payload):
            if (method == "write_shard" and drop
                    and getattr(payload, "phase", 1) == 2):
                drop.pop()
                from tpu3fs.utils.result import FsError, Status
                raise FsError(Status(Code.RPC_CONNECT_FAILED, "blip"))
            return real_send(node_id, method, payload)

        workers = [EcResyncWorker(node.service, flaky)
                   for node in fab.nodes.values()]
        for w in workers:
            w.run_once()  # first sweep: commit attempt hits the blip
        for w in workers:
            w.run_once()  # second sweep MUST retry (no frozen memo)
        got = client.read_stripe(chain_id, cid, 0, CHUNK, chunk_size=CHUNK)
        assert got.ok and got.data == v2


class TestDeltaParityKernels:
    """Sub-stripe RMW math: the XOR-scheduled encode and the cached
    coefficient-column delta apply must be bit-exact against full
    re-encoding for every shard position and code geometry."""

    def test_xor_scheduled_encode_matches_naive_lut(self):
        from tpu3fs.ops.gf256 import GF
        from tpu3fs.ops.rs import RSCode

        rng = np.random.default_rng(70)
        for k, m in [(3, 1), (4, 2), (6, 3), (12, 4)]:
            rs = RSCode(k, m)
            data = rng.integers(0, 256, (4, k, 256), dtype=np.uint8)
            naive = np.zeros((4, m, 256), dtype=np.uint8)
            for i in range(m):
                for j in range(k):
                    c = int(rs.parity_matrix[i, j])
                    if c == 1:
                        naive[:, i, :] ^= data[:, j, :]
                    elif c:
                        naive[:, i, :] ^= GF.MUL_TABLE[c][data[:, j, :]]
            assert (rs.encode_np(data) == naive).all(), (k, m)
            # the schedule groups at least row 0 (all-ones) into one pass
            sched = rs._encode_schedule()
            assert len(sched[0]) == 1 and sched[0][0][0] == 1

    def test_delta_parity_equals_reencode_every_shard(self):
        from tpu3fs.ops.rs import RSCode

        rng = np.random.default_rng(71)
        for k, m in [(3, 2), (5, 3)]:
            rs = RSCode(k, m)
            data = rng.integers(0, 256, (k, 512), dtype=np.uint8)
            parity = rs.encode_np(data[None])[0]
            for j in range(k):
                new = data.copy()
                new[j, 100:300] = rng.integers(0, 256, 200, dtype=np.uint8)
                delta = data[j] ^ new[j]
                got = parity ^ rs.delta_parity_host(j, delta)
                want = rs.encode_np(new[None])[0]
                assert (got == want).all(), (k, m, j)

    def test_codec_delta_parity_dispatch_and_shapes(self):
        codec = get_codec(K, M, S)
        rng = np.random.default_rng(72)
        delta = rng.integers(0, 256, S, dtype=np.uint8)
        rows = codec.delta_parity(0, delta.tobytes())
        assert rows.shape == (M, S) and rows.dtype == np.uint8
        # bytes input and ndarray input agree
        assert (rows == codec.delta_parity(0, delta)).all()
        with pytest.raises(ValueError):
            codec.rs.parity_delta_matrix(K)  # parity column is not a delta


class TestBatchReadRebuild:
    def test_batched_rebuild_reads_match_singles(self):
        from tpu3fs.storage.craq import ReadReq as RReq

        fab = ec_fabric(chains=1)
        client = fab.storage_client()
        data = [bytes([i]) * (CHUNK - 64 * i) for i in range(1, 4)]
        for i, d in enumerate(data):
            assert client.write_stripe(
                fab.chain_ids[0], ChunkId(7, i), d, chunk_size=CHUNK).ok
        routing = fab.routing()
        chain = routing.chains[fab.chain_ids[0]]
        t0 = chain.target_of_shard(0)
        node = routing.node_of_target(t0.target_id)
        reqs = [RReq(fab.chain_ids[0], ChunkId(7, i), 0, -1, t0.target_id)
                for i in range(3)]
        batched = fab.send(node.node_id, "batch_read_rebuild", reqs)
        singles = [fab.send(node.node_id, "read_rebuild", r) for r in reqs]
        for b, s in zip(batched, singles):
            assert b.ok and s.ok
            assert bytes(b.data) == bytes(s.data)
            assert b.commit_ver == s.commit_ver
            assert b.logical_len == s.logical_len
        fab.close()

    def test_rebuild_recovery_reads_spread_over_peers(self):
        """Source-disjoint scheduling: with more holders than k, the
        rotation must pull recovery reads from EVERY surviving peer."""
        from tpu3fs.storage.ec_resync import EcResyncWorker

        fab = ec_fabric(k=3, m=2, nodes=5, chains=1)
        client = fab.storage_client()
        rng = np.random.default_rng(73)
        for i in range(10):
            d = rng.integers(0, 256, CHUNK, dtype=np.uint8).tobytes()
            assert client.write_stripe(
                fab.chain_ids[0], ChunkId(8, i), d, chunk_size=CHUNK).ok
        routing = fab.routing()
        chain = routing.chains[fab.chain_ids[0]]
        victim = chain.target_of_shard(1)
        vnode = routing.node_of_target(victim.target_id)
        fab.fail_node(vnode.node_id)
        eng = fab.nodes[vnode.node_id].service.target(victim.target_id).engine
        for meta in eng.all_metadata():
            eng.remove(meta.chunk_id)
        fab.restart_node(vnode.node_id)
        fab.tick()
        workers = {nid: EcResyncWorker(node.service, fab.send)
                   for nid, node in fab.nodes.items()}
        for _ in range(6):
            for nid, w in workers.items():
                if fab.nodes[nid].alive:
                    w.run_once()
            fab.tick()
        stats = next(w.last_stats for w in workers.values()
                     if w.last_stats["installed"])
        assert stats["installed"] == 10
        assert stats["bytes"] > 0 and stats["mibps"] > 0
        # 4 surviving holders rotate through 10 stripes x 3 reads: every
        # peer must have served some recovery reads
        assert len(stats["read_sources"]) >= 4, stats["read_sources"]
        fab.close()


# -- fresh partial stripes on the batch path (FileIoClient._write_ec_heads) --

ENTRY = CHUNK * 9 // 16 + 64    # a KVCache entry's share of its chunk
HEAD_LENGTHS = {"one_byte": 1, "S_minus_1": S - 1, "S": S, "S_plus_1": S + 1,
                "kvcache_entry": ENTRY, "chunk_minus_1": CHUNK - 1}


class _Spy:
    """A messenger that counts (method, node) and can fail a method."""

    def __init__(self, fab):
        self.fab = fab
        self.calls = []
        self.fail = {}          # method -> how many calls still fail

    def __call__(self, node_id, method, payload):
        from tpu3fs.utils.result import FsError, Status

        self.calls.append((method, node_id))
        if self.fail.get(method, 0) > 0:
            self.fail[method] -= 1
            raise FsError(Status(Code.RPC_PEER_CLOSED, "injected"))
        return self.fab.send(node_id, method, payload)

    def count(self, method):
        return sum(1 for m, _ in self.calls if m == method)


def _spied(fab, **kw):
    """(FileIoClient, StorageClient, spy) over a counting messenger."""
    from tpu3fs.client.file_io import FileIoClient
    from tpu3fs.client.storage_client import RetryOptions, StorageClient

    spy = _Spy(fab)
    kw.setdefault("retry", RetryOptions(
        max_retries=3, backoff_base_s=0.0005, backoff_max_s=0.005))
    client = StorageClient("spied", fab.routing, spy, **kw)
    return FileIoClient(client), client, spy


def _encode_sizes(monkeypatch):
    """Batch sizes of every StripeCodec.encode_parity call from here on."""
    from tpu3fs.ops.stripe import StripeCodec

    sizes = []
    inner = StripeCodec.encode_parity

    def encode_parity(self, data):
        sizes.append(int(data.shape[0]))
        return inner(self, data)

    monkeypatch.setattr(StripeCodec, "encode_parity", encode_parity)
    return sizes


def _open(fab, path):
    return fab.meta.create(path, flags=OpenFlags.WRITE, client_id="c1").inode


def _shards(fab, chain_id, cid, writable_only=False):
    """[(engine, committed meta)] of a stripe by shard index; nothing may
    be left pending."""
    routing = fab.routing()
    chain = routing.chains[chain_id]
    out = []
    for j in range(chain.ec_k + chain.ec_m):
        t = chain.target_of_shard(j)
        if writable_only and not t.public_state.can_write:
            continue
        engine = fab.nodes[routing.node_of_target(t.target_id).node_id] \
            .service.target(t.target_id).engine
        meta = engine.get_meta(cid)
        assert meta is not None and meta.pending_ver == 0, (j, meta)
        out.append((engine, meta))
    return out


def _stored(fab, chain_id, cid):
    """What every shard target holds of a stripe: [(bytes, crc, logical
    length, stored length)] by shard index."""
    return [(bytes(engine.read(cid)), meta.checksum.value, meta.aux,
             meta.length) for engine, meta in _shards(fab, chain_id, cid)]


def _one_version(fab, chain_id, cid, writable_only=False):
    """The committed version all shards of a stripe agree on."""
    vers = {meta.committed_ver
            for _, meta in _shards(fab, chain_id, cid, writable_only)}
    assert len(vers) == 1, vers
    return vers.pop()


class TestHeadPartialBatch:
    @pytest.mark.parametrize("name", sorted(HEAD_LENGTHS))
    def test_fresh_batch_is_one_probe_one_encode_two_rounds(
            self, name, monkeypatch):
        """N fresh files of one length through batch_write_files: one
        stat_chunks, one encode of B=N, one batch_write_shard a node a
        phase, no single-shard RPC and no read — and every target stores
        what write_stripe stores for the same bytes."""
        n_bytes, N = HEAD_LENGTHS[name], 5
        fab = ec_fabric(chains=1)
        chain = fab.chain_ids[0]
        fio, client, spy = _spied(fab)
        sizes = _encode_sizes(monkeypatch)
        rng = np.random.default_rng(n_bytes)
        bodies = [rng.integers(0, 256, n_bytes, dtype=np.uint8).tobytes()
                  for _ in range(N)]
        inodes = [_open(fab, f"/h{i}") for i in range(N)]
        assert fio.batch_write_files(
            [(ino, 0, body) for ino, body in zip(inodes, bodies)]) \
            == [n_bytes] * N
        assert spy.count("stat_chunks") == 1
        assert sizes == [N]
        nodes = {n for m, n in spy.calls if m == "batch_write_shard"}
        assert spy.count("batch_write_shard") == 2 * len(nodes)
        assert {m for m, _ in spy.calls} == {"stat_chunks",
                                             "batch_write_shard"}
        assert client._ec_head_batched._value == (
            N if n_bytes < CHUNK else 0)
        assert client._ec_head_ladder._value == 0
        # the single-stripe ladder on a twin chunk id is the reference
        for i, (ino, body) in enumerate(zip(inodes, bodies)):
            twin = ChunkId(10_000 + i, 0)
            assert client.write_stripe(chain, twin, body,
                                       chunk_size=CHUNK).ok
            assert _stored(fab, chain, ChunkId(ino.id, 0)) == \
                _stored(fab, chain, twin)
            assert fio.read(ino, 0, n_bytes) == body
        assert client.query_last_chunk(chain, inodes[0].id) == (0, n_bytes)

    @pytest.mark.parametrize("old_len,new_len", [(1000, 300), (300, 1000)],
                             ids=["over_longer", "over_shorter"])
    def test_over_a_committed_stripe_takes_the_ladder(self, old_len,
                                                      new_len):
        """A short write over a longer committed stripe keeps the tail,
        over a shorter one extends it: the probe found it present, the
        ladder was taken and the recorder says so."""
        fab = ec_fabric(chains=1)
        chain = fab.chain_ids[0]
        fio, client, spy = _spied(fab)
        ino = _open(fab, "/over")
        fio.write(ino, 0, b"A" * old_len)
        assert client._ec_head_batched._value == 1
        spy.calls.clear()
        fio.batch_write_files([(ino, 0, b"B" * new_len)])
        assert client._ec_head_ladder._value == 1
        assert client._ec_head_batched._value == 1
        assert spy.count("stat_chunks") == 1
        want = (b"B" * new_len + b"A" * old_len)[:max(old_len, new_len)] \
            if new_len < old_len else b"B" * new_len
        got = client.read_stripe(chain, ChunkId(ino.id, 0), 0, CHUNK,
                                 chunk_size=CHUNK)
        assert got.ok and got.logical_len == max(old_len, new_len)
        assert bytes(got.data[:got.logical_len]) == want
        _one_version(fab, chain, ChunkId(ino.id, 0))

    @pytest.mark.parametrize("how", ["disk_lost", "written_while_down"])
    def test_a_shard0_that_is_not_serving_cannot_say_absent(self, how):
        """Shard 0's target answers (0, 0, 0) for stripes the other 15
        hold — its disk was lost, or it was down while they were written
        — and is SYNCING: it takes writes and answers the probe. Its
        answer is not trusted: the short write takes the ladder, which
        merges from k survivors, and every tail survives (a batched short
        stripe would win the nonce about every second time)."""
        fab = ec_fabric(chains=1)
        chain = fab.chain_ids[0]
        fio, client, spy = _spied(fab)
        routing = fab.routing()
        t0 = routing.chains[chain].target_of_shard(0)
        node0 = routing.node_of_target(t0.target_id).node_id
        inodes = [_open(fab, f"/t{i}") for i in range(6)]
        olds = [bytes([97 + i]) * (1000 + i) for i in range(6)]

        def put_old():
            fio.batch_write_files(
                [(ino, 0, old) for ino, old in zip(inodes, olds)])

        if how == "disk_lost":
            put_old()
            fab.fail_node(node0)
            from tpu3fs.storage.engine import MemChunkEngine

            fab.nodes[node0].service.target(t0.target_id).engine = \
                MemChunkEngine()
        else:
            fab.fail_node(node0)
            put_old()
        fab.restart_node(node0)
        fab.tick()
        state = fab.routing().chains[chain].target_of_shard(0).public_state
        assert state.name == "SYNCING" and state.can_write
        engine0 = fab.nodes[node0].service.target(t0.target_id).engine
        assert engine0.get_meta(ChunkId(inodes[0].id, 0)) is None
        fio2, client2, spy2 = _spied(fab)
        news = [bytes([65 + i]) * (300 + i) for i in range(6)]
        fio2.batch_write_files(
            [(ino, 0, new) for ino, new in zip(inodes, news)])
        assert spy2.count("stat_chunks") == 1
        assert client2._ec_head_ladder._value == 6
        assert client2._ec_head_batched._value == 0
        fab.resync_all()
        for ino, old, new in zip(inodes, olds, news):
            cid = ChunkId(ino.id, 0)
            got = client2.read_stripe(chain, cid, 0, CHUNK, chunk_size=CHUNK)
            assert got.ok and got.logical_len == len(old)
            assert bytes(got.data[:got.logical_len]) == \
                new + old[len(new):]
            _one_version(fab, chain, cid)

    def test_a_large_batch_goes_in_slices_of_one_dispatch(self,
                                                          monkeypatch):
        """40 fresh entries in one call: ONE probe, then slices of 16
        stripes (an encode dispatch), each its own two shard rounds — the
        client never holds more than a slice's shards."""
        fab = ec_fabric(chains=1)
        chain = fab.chain_ids[0]
        fio, client, spy = _spied(fab)
        sizes = _encode_sizes(monkeypatch)
        inodes = [_open(fab, f"/s{i}") for i in range(40)]
        bodies = [bytes([i]) * (ENTRY + i) for i in range(40)]
        fio.batch_write_files(
            [(ino, 0, body) for ino, body in zip(inodes, bodies)])
        assert spy.count("stat_chunks") == 1
        assert sizes == [16, 16, 8]
        nodes = {n for m, n in spy.calls if m == "batch_write_shard"}
        assert spy.count("batch_write_shard") == 3 * 2 * len(nodes)
        assert client._ec_head_batched._value == 40
        for ino, body in zip(inodes, bodies):
            assert fio.read(ino, 0, len(body)) == body
            _one_version(fab, chain, ChunkId(ino.id, 0))

    def test_every_partial_stripe_write_passes_write_ec_chunk(
            self, monkeypatch):
        """FileIoClient._write_ec_chunk is called once for every
        partial-stripe segment before anything of it is sent, the fresh
        short ones of a batch included — the seam on which the
        benchmark's fault kv_ack_without_write stands (it answers OK for
        every second call and writes nothing). What it lets pass still
        rides ONE batch; what it swallows never reaches a target."""
        from tpu3fs.client.file_io import FileIoClient
        from tpu3fs.storage.craq import UpdateReply
        from tpu3fs.utils.result import Code

        fab = ec_fabric(chains=1)
        chain = fab.chain_ids[0]
        fio, client, spy = _spied(fab)
        sizes = _encode_sizes(monkeypatch)
        inner = FileIoClient._write_ec_chunk
        seen = []

        def acks_every_second(self, inode, chain_id, idx, in_off, part, cs):
            seen.append((inode.id, idx, in_off, len(part)))
            if len(seen) % 2 == 0:
                return UpdateReply(Code.OK)
            return inner(self, inode, chain_id, idx, in_off, part, cs)

        monkeypatch.setattr(FileIoClient, "_write_ec_chunk",
                            acks_every_second)
        inodes = [_open(fab, f"/g{i}") for i in range(5)]
        bodies = [bytes([48 + i]) * (ENTRY + i) for i in range(5)]
        # five fresh entries, and a whole stripe with a short tail
        tail = _open(fab, "/gt")
        assert fio.batch_write_files(
            [(ino, 0, body) for ino, body in zip(inodes, bodies)]
            + [(tail, 0, b"t" * (CHUNK + 9))]) == \
            [len(b) for b in bodies] + [CHUNK + 9]
        assert seen == [(ino.id, 0, 0, len(body))
                        for ino, body in zip(inodes, bodies)] \
            + [(tail.id, 1, 0, 9)]
        # calls 2, 4 and 6 were swallowed: files 1, 3 and the tail
        assert sizes == [4] and spy.count("stat_chunks") == 1
        assert client._ec_head_batched._value == 3
        for i, (ino, body) in enumerate(zip(inodes, bodies)):
            got = client.read_stripe(chain, ChunkId(ino.id, 0), 0, CHUNK,
                                     chunk_size=CHUNK)
            if i % 2:
                assert got.code == Code.CHUNK_NOT_FOUND
            else:
                assert bytes(got.data[:got.logical_len]) == body
        assert client.read_stripe(chain, ChunkId(tail.id, 1), 0, CHUNK,
                                  chunk_size=CHUNK).code == \
            Code.CHUNK_NOT_FOUND
        # a write through fio.write passes the same seam
        fio.write(_open(fab, "/gw"), 0, b"w" * 77)
        assert seen[-1][2:] == (0, 77)

    def test_a_segment_inside_its_chunk_never_joins(self, monkeypatch):
        fab = ec_fabric(chains=1)
        fio, client, spy = _spied(fab)
        ino = _open(fab, "/mid")
        batches = []
        inner = client.write_stripe_heads
        monkeypatch.setattr(
            client, "write_stripe_heads",
            lambda *a, **kw: batches.append(a) or inner(*a, **kw))
        fio.write(ino, 100, b"m" * 500)
        fio.batch_write_files([(ino, 700, b"n" * 50)])
        assert batches == [] and spy.count("stat_chunks") == 0
        assert client._ec_head_batched._value == 0
        assert client._ec_head_ladder._value == 0
        assert fio.read(ino, 0, 750) == (b"\x00" * 100 + b"m" * 500
                                         + b"\x00" * 100 + b"n" * 50)

    def test_one_call_full_fresh_and_existing_over_two_files_two_chains(
            self, monkeypatch):
        """Full stripes, fresh and existing head-partials of two files
        striped over two chains: one probe and one encode a chain."""
        fab = ec_fabric(chains=2)
        fio, client, spy = _spied(fab)
        sizes = _encode_sizes(monkeypatch)
        a = fab.meta.create("/a", flags=OpenFlags.WRITE, client_id="c1",
                            stripe=2).inode
        b = fab.meta.create("/b", flags=OpenFlags.WRITE, client_id="c1",
                            stripe=2).inode
        assert len(set(a.layout.chains)) == 2
        # b's tail chunk exists already, longer than what comes
        fio.write(b, CHUNK, b"old" * 400)
        spy.calls.clear()
        del sizes[:]
        rng = np.random.default_rng(7)
        body_a = rng.integers(0, 256, 2 * CHUNK + 777,
                              dtype=np.uint8).tobytes()
        body_b = rng.integers(0, 256, CHUNK + 500, dtype=np.uint8).tobytes()
        before = client._ec_head_batched._value
        fio.batch_write_files([(a, 0, body_a), (b, 0, body_b)])
        assert spy.count("stat_chunks") == 2
        # one encode a chain of what stayed in its batch: a's three chunks
        # and b's first — b's existing tail left for the ladder
        stayed = [a.layout.chain_of_chunk(i) for i in range(3)] \
            + [b.layout.chain_of_chunk(0)]
        assert sorted(sizes[:2]) == sorted(
            stayed.count(c) for c in set(stayed)), sizes
        assert client._ec_head_batched._value - before == 1   # a's tail
        assert client._ec_head_ladder._value == 1             # b's tail
        assert fio.read(a, 0, len(body_a)) == body_a
        assert fio.read(b, 0, CHUNK + 1200) == \
            body_b + (b"old" * 400)[500:]

    def test_write_keeps_file_order_with_a_short_tail(self, monkeypatch):
        """A multi-chunk write that starts inside a chunk and ends with a
        short tail: the partial head run first, then ONE batch of the full
        stripes and the tail; a failure of that batch leaves the clean
        prefix and nothing after it."""
        from tpu3fs.utils.result import FsError, Status

        fab = ec_fabric(chains=1)
        fio, client, spy = _spied(fab)
        sizes = _encode_sizes(monkeypatch)
        ino = _open(fab, "/order")
        body = bytes(range(256)) * ((3 * CHUNK + 900) // 256)
        fio.write(ino, 100, body)
        assert sizes[-1] == 3 and spy.count("stat_chunks") == 1
        assert fio.read(ino, 100, len(body)) == body
        # now the same shape onto a fresh file, the batch failing
        ino2 = _open(fab, "/order2")
        order = []
        inner_chunk = fio._write_ec_chunk

        def boom(chain_id, items, *, chunk_size):
            order.append(("heads", len(items)))
            raise FsError(Status(Code.TARGET_OFFLINE, "injected"))

        monkeypatch.setattr(
            fio, "_write_ec_chunk",
            lambda *a: order.append(("chunk", a[2])) or inner_chunk(*a))
        monkeypatch.setattr(client, "write_stripe_heads", boom)
        with pytest.raises(FsError):
            fio.write(ino2, 100, body)
        # the in-chunk head took the ladder; the short tail was announced
        # (and handed back) before its run's batch, which then failed
        assert order == [("chunk", 0), ("chunk", 3), ("heads", 3)]
        assert fio.read(ino2, 100, CHUNK - 100) == body[:CHUNK - 100]
        assert client.query_last_chunk(fab.chain_ids[0], ino2.id) == \
            (0, CHUNK)

    @pytest.mark.parametrize("fault", ["probe_target_offline",
                                       "stage_batch_fails", "probe_fails"])
    def test_faults_end_on_the_ladder_with_the_strict_rule(self, fault,
                                                           monkeypatch):
        fab = ec_fabric(chains=1)
        chain = fab.chain_ids[0]
        fio, client, spy = _spied(fab)
        routing = fab.routing()
        if fault == "probe_target_offline":
            t0 = routing.chains[chain].target_of_shard(0)
            fab.fail_node(routing.node_of_target(t0.target_id).node_id)
        elif fault == "stage_batch_fails":
            spy.fail["batch_write_shard"] = 1
        else:
            spy.fail["stat_chunks"] = 1
        inodes = [_open(fab, f"/f{i}") for i in range(3)]
        bodies = [bytes([65 + i]) * (ENTRY + i) for i in range(3)]
        fio.batch_write_files(
            [(ino, 0, body) for ino, body in zip(inodes, bodies)])
        if fault == "stage_batch_fails":
            # the batch was tried, a node's stage round was lost, and the
            # single-stripe ladder finished every stripe at its version
            assert client._ec_head_batched._value == 3
            assert spy.count("write_shard") > 0
        else:
            # no answer from the probe: nothing short stays in the batch
            assert client._ec_head_ladder._value == 3
            assert spy.count("batch_write_shard") == 0
        for ino, body in zip(inodes, bodies):
            cid = ChunkId(ino.id, 0)
            # strict: EVERY writable shard committed at one version
            _one_version(fab, chain, cid, writable_only=True)
            got = client.read_stripe(chain, cid, 0, CHUNK, chunk_size=CHUNK)
            assert got.ok and bytes(got.data[:got.logical_len]) == body

    @pytest.mark.parametrize("first_is_longer", [True, False],
                             ids=["loser_shorter", "loser_longer"])
    def test_two_writers_of_a_fresh_stripe_converge(self, first_is_longer):
        """Both probe the stripe absent; B lands whole between A's probe
        and A's stage. Whatever the nonces decide, every shard ends on ONE
        committed version, nothing pending, and the stripe reads as bytes
        that were sent, at an exact length (test_model_ec's E1 and E4)."""
        fab = ec_fabric(chains=1)
        chain = fab.chain_ids[0]
        fio_a, client_a, _ = _spied(fab)
        fio_b = fab.file_client()
        ino = _open(fab, "/race")
        a = b"A" * (700 if first_is_longer else 300)
        b = b"B" * (300 if first_is_longer else 700)
        state = {"raced": False}

        class Racing(_Spy):
            def __call__(self, node_id, method, payload):
                if method == "batch_write_shard" and not state["raced"]:
                    state["raced"] = True
                    fio_b.write(ino, 0, b)
                return super().__call__(node_id, method, payload)

        client_a._messenger = Racing(fab)
        fio_a.write(ino, 0, a)
        assert state["raced"]
        cid = ChunkId(ino.id, 0)
        _one_version(fab, chain, cid)
        got = client_a.read_stripe(chain, cid, 0, CHUNK, chunk_size=CHUNK)
        assert got.ok
        payload = bytes(got.data[:got.logical_len])
        assert payload in (a, a + b[len(a):]), payload[:8]
        assert not bytes(got.data[got.logical_len:]).strip(b"\x00")


class TestLengthSweep:
    """query_last_chunks: a close batch's length sweep asks every distinct
    node that hosts a SERVING target ONCE with all the file ids, and is the
    per-file answer under the per-file policy."""

    K, M, NODES = 12, 4, 4
    CS = 12 * 1024
    SH = shard_size_of(CS, 12)

    def _fab(self):
        return ec_fabric(nodes=self.NODES, chains=1, k=self.K, m=self.M,
                         chunk_size=self.CS)

    def _files(self, fab, client):
        """file id -> the (index, length) the sweep has to answer: an empty
        file, one that ends in shard 0, one whose tail shard (11) sits on
        the last node, a full stripe, and one of three chunks."""
        chain = fab.chain_ids[0]
        want = {40: (-1, 0), 41: (0, 77), 42: (0, 11 * self.SH + 5),
                43: (0, self.CS), 44: (2, 3 * self.SH + 1)}
        rng = np.random.default_rng(44)
        for fid, (idx, n) in want.items():
            for i in range(idx + 1):
                size = n if i == idx else self.CS
                data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
                assert client.write_stripe(chain, ChunkId(fid, i), data,
                                           chunk_size=self.CS).ok
        return want

    def test_the_tail_shard_of_file_42_sits_on_the_last_node(self):
        fab = self._fab()
        routing = fab.routing()
        chain = routing.chains[fab.chain_ids[0]]
        nodes = [routing.node_of_target(chain.target_of_shard(j).target_id)
                 .node_id for j in range(self.K + self.M)]
        assert len(set(nodes)) == self.NODES
        assert nodes[11] == max(nodes)

    def test_batched_answer_equals_the_per_file_answers(self):
        fab = self._fab()
        _, client, _ = _spied(fab)
        want = self._files(fab, client)
        chain = fab.chain_ids[0]
        ids = list(want)
        got = client.query_last_chunks(chain, ids)
        assert got == [want[f] for f in ids]
        assert got == [client.query_last_chunk(chain, f) for f in ids]
        # order and repeats are the caller's
        assert client.query_last_chunks(chain, [44, 40, 44]) == [
            want[44], want[40], want[44]]
        assert client.query_last_chunks(chain, []) == []

    def test_every_node_answers_for_all_its_local_targets(self):
        """The server's batch is its single answer a file."""
        fab = self._fab()
        _, client, _ = _spied(fab)
        want = self._files(fab, client)
        chain = fab.chain_ids[0]
        for node in fab.nodes:
            assert fab.send(node, "query_last_chunks", (chain, list(want))) \
                == [fab.send(node, "query_last_chunk", (chain, f))
                    for f in want]

    def test_one_request_a_distinct_node_a_sweep(self):
        fab = self._fab()
        _, client, spy = _spied(fab)
        want = self._files(fab, client)
        spy.calls.clear()
        client.query_last_chunks(fab.chain_ids[0], list(want))
        asked = [n for m, n in spy.calls if m == "query_last_chunks"]
        assert sorted(asked) == sorted(fab.nodes)       # 4, never 16 x files
        assert spy.count("query_last_chunk") == 0
        assert client._length_rpcs._value >= self.NODES

    def _with_states(self, fab, states):
        """A routing provider whose copy calls shard j `states[j]`."""
        import copy

        def routing():
            ri = copy.deepcopy(fab.routing())
            chain = ri.chains[fab.chain_ids[0]]
            for j, st in states.items():
                chain.target_of_shard(j).public_state = st
            return ri

        return routing

    def test_a_mixed_node_is_still_asked_and_a_lost_one_is_not(self):
        from tpu3fs.client.storage_client import StorageClient
        from tpu3fs.mgmtd.types import PublicTargetState as P

        fab = self._fab()
        _, client, _ = _spied(fab)
        want = self._files(fab, client)
        chain = fab.chain_ids[0]
        # shard 3 SYNCING, its node's other three (7, 11, 15) SERVING
        spy = _Spy(fab)
        mixed = StorageClient("mixed", self._with_states(
            fab, {3: P.SYNCING}), spy)
        assert mixed.query_last_chunks(chain, list(want)) == list(
            want.values())
        assert len([1 for m, _ in spy.calls
                    if m == "query_last_chunks"]) == self.NODES
        # all four of that node out of SERVING: three nodes are the sweep
        spy = _Spy(fab)
        lost = StorageClient("lost", self._with_states(
            fab, {j: P.OFFLINE for j in (3, 7, 11, 15)}), spy)
        lost.query_last_chunks(chain, list(want))
        assert len(spy.calls) == self.NODES - 1

    def test_a_failing_node_fails_the_attempt_for_every_file(self):
        from tpu3fs.utils.result import FsError

        fab = self._fab()
        _, client, spy = _spied(fab)
        want = self._files(fab, client)
        chain = fab.chain_ids[0]
        spy.calls.clear()
        spy.fail["query_last_chunks"] = 1     # one node, once
        assert client.query_last_chunks(chain, list(want)) == list(
            want.values())
        assert spy.count("query_last_chunks") == 2 * self.NODES
        spy.fail["query_last_chunks"] = 10 ** 6
        with pytest.raises(FsError):          # an error, never a short length
            client.query_last_chunks(chain, list(want))

    def test_a_silent_node_counts_for_its_four_targets(self):
        """queried >= k reads as before: three nodes that answered are
        twelve targets, so a sweep whose fourth node does not answer waits
        for routing (no attempt spent) instead of giving up."""
        from tpu3fs.client.storage_client import RetryOptions

        from tpu3fs.client.storage_client import StorageClient
        from tpu3fs.utils.result import FsError, Status

        fab = self._fab()
        _, writer, _ = _spied(fab)
        want = self._files(fab, writer)
        silent, left, asked = max(fab.nodes), [3], []

        def messenger(node_id, method, payload):
            asked.append(node_id)
            if node_id == silent and left[0] > 0:
                left[0] -= 1
                raise FsError(Status(Code.RPC_CONNECT_FAILED, "gone"))
            return fab.send(node_id, method, payload)

        patient = StorageClient("patient", fab.routing, messenger,
                                retry=RetryOptions(
                                    max_retries=0, backoff_max_s=0.005,
                                    routing_wait_s=30.0))
        assert patient.query_last_chunks(
            fab.chain_ids[0], list(want)) == list(want.values())
        assert len(asked) == 4 * self.NODES
        # with no wait for routing and no retry left it is an error at once
        left[0] = 10 ** 6
        hasty = StorageClient("hasty", fab.routing, messenger,
                              retry=RetryOptions(max_retries=0,
                                                 routing_wait_s=0.0))
        with pytest.raises(FsError):
            hasty.query_last_chunks(fab.chain_ids[0], list(want))

    def test_a_close_batch_is_one_sweep(self, monkeypatch):
        """Through the meta store's hook: eight closes in one batch_close
        are four storage requests, and every length is the precise one."""
        from tpu3fs.meta.store import BatchCloseItem

        fab = self._fab()
        fio = fab.file_client()
        items, sizes = [], []
        for i in range(8):
            res = fab.meta.create(f"/ls{i}", flags=OpenFlags.WRITE
                                  | OpenFlags.CREATE | OpenFlags.TRUNC,
                                  client_id="c")
            n = 1 + i * 1500
            fio.write(res.inode, 0, bytes([i]) * n)
            sizes.append(n)
            items.append(BatchCloseItem(res.inode.id, res.session_id,
                                        client_id="c", wrote=1))
        sent = []
        inner = fab.send

        def send(node_id, method, payload):
            sent.append(method)
            return inner(node_id, method, payload)

        monkeypatch.setattr(fab, "send", send)
        out = fab.meta.batch_close(items)
        assert [o.length for o in out] == sizes
        assert sent == ["query_last_chunks"] * self.NODES


class TestCodecBuckets:
    """No encode program is built on a later request's path: the first
    dispatch prepares every bucket (counted where jit keeps its programs,
    so it runs without a TPU)."""

    STEP = 16

    @pytest.fixture(scope="class")
    def codec(self):
        from tpu3fs.ops import stripe

        codec = stripe.StripeCodec(K, M, 64)
        codec._host_mode = False    # the 'device' is the CPU backend
        assert stripe.DEVICE_BATCH_ITEMS == self.STEP
        return codec

    def _encode(self, codec, data, monkeypatch):
        """encode_batch, and the batch size of each dispatch it made."""
        sizes = []
        inner = codec._encode_dev

        def counted(part):
            sizes.append(part.shape[0])
            return inner(part)

        monkeypatch.setattr(codec, "_encode_dev", counted)
        out, _crcs = codec.encode_batch(data)
        monkeypatch.undo()
        return out, sizes

    def test_the_first_dispatch_builds_every_bucket(self, codec,
                                                    monkeypatch):
        assert codec._buckets(K + M) == [1, 2, 4, 8, 16]
        assert codec._encode_dev._cache_size() == 0
        codec.encode_batch(np.ones((3, K, 64), dtype=np.uint8))
        assert codec._encode_dev._cache_size() == 5

    @pytest.mark.parametrize("b", range(1, 2 * STEP + 1, 1))
    def test_a_batch_of_any_size_builds_nothing_new(self, codec, b,
                                                    monkeypatch):
        rng = np.random.default_rng(b)
        data = rng.integers(0, 256, (b, K, 64), dtype=np.uint8)
        codec.encode_batch(data[:1])        # whoever comes first prepares
        built = codec._encode_dev._cache_size()
        assert built == 5
        out, sizes = self._encode(codec, data, monkeypatch)
        assert codec._encode_dev._cache_size() == built
        assert all(s in codec._buckets(K + M) for s in sizes)
        assert sum(sizes) < 2 * b + 1 and len(sizes) == -(-b // self.STEP)
        assert np.array_equal(out[:, K:], codec.rs.encode_host(data))
        assert np.array_equal(out[:, :K], data)

    def test_only_the_encode_is_held_to_sixteen(self, codec):
        """The CRC keeps the bound by bytes: its dispatches return a
        fraction of what an encode's does (the reconstruct is held to
        sixteen too: TestDecodeBuckets)."""
        assert codec._device_step(K + 1) > self.STEP
        assert codec._device_step(1) > self.STEP
        big = np.random.default_rng(7).integers(
            0, 256, (3 * self.STEP, 64), dtype=np.uint8)
        sizes = []
        inner = codec._crc_dev
        codec._crc_dev = lambda part: (sizes.append(part.shape[0]),
                                       inner(part))[1]
        try:
            crcs = codec.crc_batch(big)
        finally:
            codec._crc_dev = inner
        assert crcs.shape == (3 * self.STEP,)
        assert sizes == [64]        # one dispatch: 48 padded to a bucket

    def test_the_serving_encode_prepares(self):
        """encode_parity on the device branch is the call that prepares
        (here the 'device' is the CPU backend)."""
        from tpu3fs.ops import stripe

        codec = stripe.StripeCodec(K, M, 64)
        codec._host_mode = False
        data = np.random.default_rng(1).integers(
            0, 256, (3, K, 64), dtype=np.uint8)
        parity, crcs = codec.encode_parity(data)
        assert np.array_equal(parity, codec.rs.encode_host(data))
        assert crcs.shape == (3, K + M)
        assert codec._prepared
        assert codec._encode_dev._cache_size() == \
            len(codec._buckets(K + M))


class TestEncodeFetch:
    """A device encode brings back the parity and the CRCs, never the data
    shards its caller sent and still holds; the serving encode still
    enters through StripeCodec.encode_batch, where the benchmark's watcher
    counts the calls its encode roofline divides by and its parity
    controls plant themselves."""

    @pytest.mark.parametrize("k,m,chunk", [(3, 1, 4 << 20), (12, 4, 1 << 20)],
                             ids=["rs3p1_4m", "rs12p4_1m"])
    @pytest.mark.parametrize("bp", [1, 16])
    def test_the_device_program_returns_no_data_shard(self, k, m, chunk, bp):
        import jax

        from tpu3fs.ops.stripe import StripeCodec

        s = shard_size_of(chunk, k)
        codec = StripeCodec(k, m, s)
        parity, crcs = jax.eval_shape(
            codec._encode_dev, jax.ShapeDtypeStruct((bp, k, s), np.uint8))
        assert (parity.shape, parity.dtype) == ((bp, m, s), np.uint8)
        assert (crcs.shape, crcs.dtype) == ((bp, k + m), np.uint32)

    def test_the_serving_encode_passes_both_hooks(self, monkeypatch):
        from perfbench.faults import kv_parity_zeroed
        from tpu3fs.ops.stripe import StripeCodec

        chunk, b = 1 << 14, 5
        s = shard_size_of(chunk, K)
        fab = ec_fabric(chains=1, chunk_size=chunk)
        chain = fab.chain_ids[0]
        client = fab.storage_client()
        codec = get_codec(K, M, s)
        monkeypatch.setattr(codec, "_host_mode", False)  # the CPU backend
        calls = []
        inner = StripeCodec.encode_batch

        def encode_batch(self, data):
            calls.append(int(data.shape[0]))
            return inner(self, data)

        monkeypatch.setattr(StripeCodec, "encode_batch", encode_batch)
        rng = np.random.default_rng(43)

        def put(ino):
            items = [(ChunkId(ino, i),
                      rng.integers(0, 256, chunk, dtype=np.uint8).tobytes())
                     for i in range(b)]
            assert all(r.ok for r in client.write_stripes(
                chain, items, chunk_size=chunk))
            return [(_stored(fab, chain, cid), body) for cid, body in items]

        for stored, body in put(70):
            rows = np.zeros((1, K, s), dtype=np.uint8)
            rows.reshape(-1)[:chunk] = np.frombuffer(body, dtype=np.uint8)
            want = codec.rs.encode_host(rows)[0]
            assert b"".join(stored[j][0] for j in range(K))[:chunk] == body
            assert [stored[K + j][0] for j in range(M)] == \
                [want[j].tobytes() for j in range(M)]
            assert any(want.reshape(-1))
        assert calls == [b]

        kv_parity_zeroed.plant(None)   # wraps the method the watcher wraps
        for stored, body in put(71):
            assert b"".join(stored[j][0] for j in range(K))[:chunk] == body
            assert [stored[K + j][0] for j in range(M)] == [bytes(s)] * M
        assert calls == [b, b]


class TestDecodeBuckets:
    """A device decode goes out in buckets of at most sixteen stripes, and
    the first device decode of a lost count builds every one of them, so
    none is built on a later request's path. The CPU backend stands in for
    the device (apply_operand takes its einsum form)."""

    STEP = 16
    KD, MD, SD = 4, 2, 64

    @pytest.fixture(scope="class")
    def codec(self):
        from tpu3fs.ops import stripe

        codec = stripe.StripeCodec(self.KD, self.MD, self.SD)
        codec._use_host = lambda: False
        return codec

    def _stripes(self, b, seed):
        """(B, k+m, S) shards, encoded by the host kernels."""
        data = np.random.default_rng(seed).integers(
            0, 256, (b, self.KD, self.SD), dtype=np.uint8)
        parity = get_codec(self.KD, self.MD, self.SD).rs.encode_host(data)
        return np.concatenate([data, parity], axis=1)

    def test_the_first_decode_of_a_lost_count_builds_every_bucket(
            self, codec):
        assert codec._buckets(self.KD + 1) == [1, 2, 4, 8, 16]
        assert codec._decode_dev._cache_size() == 0
        shards = self._stripes(1, 0)
        present, lost = (0, 2, 3, 4), (1,)
        codec.reconstruct_batch(present, lost, shards[:, list(present)])
        assert codec._decode_dev._cache_size() == 5
        # a second lost count is a program set of its own, built once
        present, lost = (2, 3, 4, 5), (0, 1)
        for _ in range(2):
            codec.reconstruct_batch(present, lost, shards[:, list(present)])
        assert codec._decode_dev._cache_size() == 10

    @pytest.mark.parametrize("b", range(1, 41))
    def test_a_decode_of_any_size_builds_nothing_new(self, codec, b,
                                                     monkeypatch):
        present, lost = (0, 1, 3, 5), (2, 4)
        shards = self._stripes(b, b)
        surv = shards[:, list(present)]
        codec.reconstruct_batch(present, lost, surv[:1])   # prepares
        built = codec._decode_dev._cache_size()
        sizes = []
        inner = codec._decode_dev

        def counted(matrix, part):
            sizes.append(part.shape[0])
            return inner(matrix, part)

        monkeypatch.setattr(codec, "_decode_dev", counted)
        got = codec.reconstruct_batch(present, lost, surv)
        monkeypatch.undo()
        assert codec._decode_dev._cache_size() == built
        assert all(s in codec._buckets(self.KD + len(lost)) for s in sizes)
        assert len(sizes) == -(-b // self.STEP)
        host = get_codec(self.KD, self.MD, self.SD)
        assert host._use_host()
        assert np.array_equal(got, host.reconstruct_batch(present, lost,
                                                          surv))
        assert np.array_equal(got, shards[:, list(lost)])

    def test_a_host_codec_builds_nothing(self):
        from tpu3fs.ops import stripe

        codec = stripe.StripeCodec(self.KD, self.MD, self.SD)
        shards = self._stripes(20, 3)
        present, lost = (0, 2, 3, 4), (1,)
        got = codec.reconstruct_batch(present, lost,
                                      shards[:, list(present)])
        assert np.array_equal(got, shards[:, [1]])
        assert codec._decode_dev._cache_size() == 0
        assert not codec._prepared
