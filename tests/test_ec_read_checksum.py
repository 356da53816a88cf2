"""An erasure-coded read that carries its range's CRC32C, the device program
that checks rows landed in HBM against it, and injected faults treated as
transient by the stripe ladders — on a socket cluster of four storage
services holding one RS(3,1) chain (tests/rpc_cluster.py)."""

from dataclasses import replace

import numpy as np
import pytest

from rpc_cluster import RpcCluster
from tpu3fs.client.storage_client import RetryOptions, StorageClient
from tpu3fs.ops.crc32c import CrcVerifier, crc32c, crc32c_batch_host
from tpu3fs.storage.craq import ReadReq
from tpu3fs.storage.types import Checksum, ChunkId
from tpu3fs.utils.fault_injection import plane
from tpu3fs.utils.result import Code

K, M = 3, 1
CHUNK = 96 * 1024        # shard S = 32 KiB
S = CHUNK // K
FAST = RetryOptions(backoff_base_s=0.001, backoff_max_s=0.01)


@pytest.fixture(scope="module")
def cluster():
    c = RpcCluster(replicas=0, chains=1, size=CHUNK, ec=(K, M), nodes=4)
    yield c
    plane().clear()
    c.close()


@pytest.fixture
def client(cluster):
    cl = cluster.storage_client(retry=FAST)
    yield cl
    plane().clear()
    cl.close()


def _chunks(seed: int, lengths) -> list:
    rng = np.random.default_rng(seed)
    return [(ChunkId(seed, i), rng.integers(0, 256, n, dtype=np.uint8)
             .tobytes()) for i, n in enumerate(lengths)]


def _put(cluster, client, items) -> None:
    replies = client.write_stripes(cluster.chain_ids[0], items,
                                   chunk_size=CHUNK)
    assert all(r.ok for r in replies), replies


def _read(cluster, client, items, offset=0, length=-1, **kw):
    return client.batch_read(
        [ReadReq(cluster.chain_ids[0], cid, offset, length,
                 chunk_size=CHUNK) for cid, _ in items], **kw)


def _node_of_shard(cluster, j: int) -> int:
    routing = cluster.mgmtd.get_routing_info()
    chain = routing.chains[cluster.chain_ids[0]]
    return routing.node_of_target(
        chain.target_of_shard(j).target_id).node_id


@pytest.mark.parametrize("case", [
    "whole_chunks", "short_last_shard", "several_stripes", "degraded",
    "partial_range", "option_off"])
def test_an_ec_read_carries_the_crc32c_of_its_bytes(cluster, client,
                                                    monkeypatch, case):
    lengths = {"whole_chunks": [CHUNK], "short_last_shard": [CHUNK - 1000],
               "several_stripes": [CHUNK, 1000, 2 * S, S + 7],
               "degraded": [CHUNK, CHUNK - 5, 100],
               "partial_range": [CHUNK], "option_off": [CHUNK, 1000]}[case]
    items = _chunks(100 + len(case), lengths)
    _put(cluster, client, items)
    if case == "partial_range":
        # a range that does not start on a shard boundary, and one that
        # ends inside a shard's stored bytes: none carried
        for off, n in ((100, 5000), (0, S + 10)):
            r = _read(cluster, client, items, off, n, with_checksum=True)[0]
            assert r.ok and bytes(r.data) == items[0][1][off:off + n]
            assert r.checksum == Checksum()
        # a range of whole shards from a shard boundary carries its own
        r = _read(cluster, client, items, S, S, with_checksum=True)[0]
        assert r.checksum == Checksum(crc32c(r.data), S)
        return
    if case == "option_off":
        # off: the reply is today's — no checksum, and none computed
        on = _read(cluster, client, items, with_checksum=True)

        def boom(*a, **kw):
            raise AssertionError("a checksum computed with the option off")

        monkeypatch.setattr(StorageClient, "_range_checksum", boom)
        off = _read(cluster, client, items)
        assert [replace(r, checksum=Checksum()) for r in on] == off
        assert all(r.checksum == Checksum() for r in off)
        return
    degraded_before = client._ec_degraded._value
    if case == "degraded":
        # shard 1's node refuses every read: each stripe is decoded from
        # the other three, the rebuilt shard's CRC from its rebuilt bytes
        plane().configure(f"point=storage.read,kind=error,"
                          f"node={_node_of_shard(cluster, 1)}")
        client = cluster.storage_client(retry=replace(FAST, max_retries=2))
    replies = _read(cluster, client, items, with_checksum=True)
    for r, (_, want) in zip(replies, items):
        assert r.ok
        assert bytes(r.data) == want + bytes(CHUNK - len(want))
        assert r.checksum == Checksum(crc32c(r.data), CHUNK)
    decoded = client._ec_degraded._value - (
        0 if case == "degraded" else degraded_before)
    assert decoded == (len(items) if case == "degraded" else 0)


def test_the_device_verify_flags_exactly_the_rows_that_changed():
    """CrcVerifier's program, compiled by the CPU backend: it agrees with
    crc32c_batch_host on seeded rows and flags exactly the rows with a
    flipped byte; `land` puts the rows on the device as they were."""
    import jax

    rows = np.random.default_rng(7).integers(0, 256, (6, 4096),
                                             dtype=np.uint8)
    crcs = crc32c_batch_host(rows)
    verifier = CrcVerifier(4096)
    landed, ok = verifier.land(rows, crcs, jax.devices()[0])
    assert ok.dtype == bool and ok.all()
    assert np.array_equal(np.asarray(landed), rows)
    bad = rows.copy()
    bad[1, 17] ^= 0x01
    bad[4, 4095] ^= 0x80
    flags = verifier.check(jax.numpy.asarray(bad), crcs)
    assert flags.tolist() == [True, False, True, True, False, True]
    assert not verifier.check(landed, crcs ^ 1).any()


@pytest.mark.parametrize("point", ["storage.write_shard", "storage.read"])
def test_an_injected_fault_is_retried_to_success(cluster, client,
                                                 monkeypatch, point):
    from tpu3fs.ops.stripe import StripeCodec

    items = _chunks(300, [CHUNK, CHUNK, 1000])
    if point == "storage.read":
        _put(cluster, client, items)
    before = client._injected_retried._value
    degraded = client._ec_degraded._value

    def boom(*a, **kw):
        raise AssertionError("a stripe decoded around an injected fault")

    monkeypatch.setattr(StripeCodec, "reconstruct_batch", boom)
    plane().configure(f"point={point},kind=error,times=2")
    if point == "storage.write_shard":
        _put(cluster, client, items)
        replies = _read(cluster, client, items)
    else:
        replies = _read(cluster, client, items, with_checksum=True)
    assert [r["fired"] for r in plane().snapshot()] == [2]
    assert all(r.ok for r in replies)
    for r, (_, want) in zip(replies, items):
        assert bytes(r.data) == want + bytes(CHUNK - len(want))
        if point == "storage.read":
            assert r.checksum == Checksum(crc32c(r.data), CHUNK)
    # the stripes the faults met went again, none was decoded around them:
    # a refused stripe write through the single-stripe ladder, a refused
    # shard read in the degraded round's second read of it
    retried = client._injected_retried._value - before
    assert retried > 0
    assert client._ec_degraded._value - degraded == (
        retried if point == "storage.read" else 0)


def test_the_single_stripe_ladder_retries_an_injected_fault(cluster,
                                                            client):
    (cid, data), = _chunks(400, [CHUNK])
    before = client._injected_retried._value
    plane().configure("point=storage.write_shard,kind=error,times=1")
    reply = client.write_stripe(cluster.chain_ids[0], cid, data,
                                chunk_size=CHUNK)
    assert reply.ok and reply.code == Code.OK
    assert client._injected_retried._value == before + 1
    plane().clear()
    got = _read(cluster, client, [(cid, data)])[0]
    assert bytes(got.data) == data
