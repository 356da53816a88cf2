"""A storage node of an RS(12,4) chain dies and an empty replacement takes
its id, over sockets (tests/rpc_cluster.py): shard j on node j % 4, so the
node holds four of the sixteen shards, exactly m.

Held to perfbench's plain references, which import nothing of the program:
lib/reference.py's independent encode (what every target must hold) and
lib/reference_decode.py's Gauss-Jordan decode (what any 12 shards give
back)."""

import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench.lib import reference as ref
from perfbench.lib import reference_decode as refdec
from tests.rpc_cluster import RpcCluster
from tpu3fs.client.storage_client import RetryOptions, StorageClient
from tpu3fs.mgmtd.types import PublicTargetState
from tpu3fs.ops.stripe import get_codec
from tpu3fs.storage.craq import ReadReply, ReadReq, ShardWriteReq
from tpu3fs.storage.types import ChunkId
from tpu3fs.utils.result import Code

K, M = 12, 4
CHUNK = 12 * 1024
S = 1024
BLOCK = 7 * S - 200          # a block covers shards 0..6, as a KVCache block
VICTIM = 13                  # node ids are 10..13: shards 3, 7, 11, 15
LOST = [3, 7, 11, 15]
FAST = dict(max_retries=4, backoff_base_s=0.005, backoff_max_s=0.05)


def payload(i: int, n: int = BLOCK) -> bytes:
    return np.random.default_rng([77, i]).integers(
        0, 256, n, dtype=np.uint8).tobytes()


@pytest.fixture
def cluster():
    c = RpcCluster(replicas=0, chains=1, size=CHUNK, ec=(K, M), nodes=4)
    yield c
    c.close()


def put_blocks(c, client, n: int, file_id: int = 500) -> dict:
    chain = c.chain_ids[0]
    out = {}
    for i in range(n):
        data = payload(file_id * 1000 + i)
        r = client.write_stripe(chain, ChunkId(file_id, i), data,
                                chunk_size=CHUNK)
        assert r.ok, r
        out[(file_id, i)] = data
    return out


def shards_on_targets(c, cid: ChunkId) -> list:
    """Every shard as its own target holds it (None = no good copy)."""
    routing = c.mgmtd.get_routing_info()
    chain = routing.chains[c.chain_ids[0]]
    out = []
    for j in range(K + M):
        t = chain.target_of_shard(j)
        svc = c.svc_by_node[routing.node_of_target(t.target_id).node_id]
        got = svc.read_rebuild(ReadReq(chain.chain_id, cid, 0, -1,
                                       t.target_id))
        out.append(bytes(got.data) if got.ok else None)
    return out


def assert_stored_as_the_reference_encodes(c, stripes: dict) -> None:
    for (fid, idx), data in stripes.items():
        gold = ref.stripe_shards(data, CHUNK, K, M)
        have = shards_on_targets(c, ChunkId(fid, idx))
        for j in range(K + M):
            assert (have[j] or b"") == gold[j], (fid, idx, j)


def test_the_victim_holds_exactly_m_shards(cluster):
    routing = cluster.mgmtd.get_routing_info()
    chain = routing.chains[cluster.chain_ids[0]]
    mine = [j for j in range(K + M) if routing.node_of_target(
        chain.target_of_shard(j).target_id).node_id == VICTIM]
    assert mine == LOST


def test_every_read_is_exact_by_the_degraded_path(cluster):
    client = cluster.storage_client(retry=RetryOptions(**FAST))
    stripes = put_blocks(cluster, client, 6)
    cluster.stop_node(VICTIM)     # mgmtd has not noticed
    chain = cluster.chain_ids[0]
    for (fid, idx), data in stripes.items():
        got = client.read_stripe(chain, ChunkId(fid, idx), 0, len(data),
                                 chunk_size=CHUNK)
        assert got.ok and bytes(got.data) == data
    assert client._ec_degraded._value == len(stripes)
    # and batched, after mgmtd's verdict
    cluster.declare_dead(VICTIM)
    reqs = [ReadReq(chain, ChunkId(fid, idx), 0, len(d), chunk_size=CHUNK)
            for (fid, idx), d in stripes.items()]
    for rep, data in zip(client.batch_read(reqs), stripes.values()):
        assert rep.ok and bytes(rep.data) == data
    assert client._ec_degraded._value == 2 * len(stripes)
    # what the survivors hold decodes to the same bytes by the reference
    (fid, idx), data = next(iter(stripes.items()))
    have = shards_on_targets(cluster, ChunkId(fid, idx))
    present = [j for j in range(K + M) if j not in LOST]
    surv = np.stack([np.frombuffer(have[j].ljust(S, b"\0"), np.uint8)
                     for j in present])
    back = refdec.decode(present, surv, K, M, list(range(7)))
    assert back.tobytes()[:len(data)] == data


def test_a_put_started_before_mgmtd_knows_waits_for_the_verdict(cluster):
    client = cluster.storage_client(retry=RetryOptions(
        routing_wait_s=30.0, **FAST))
    chain = cluster.chain_ids[0]
    cluster.stop_node(VICTIM)
    done = {}

    def put():
        t0 = time.monotonic()
        done["reply"] = client.write_stripe(
            chain, ChunkId(600, 0), payload(1), chunk_size=CHUNK)
        done["s"] = time.monotonic() - t0

    th = threading.Thread(target=put)
    th.start()
    time.sleep(0.6)               # longer than FAST's whole ladder
    assert th.is_alive(), "the put gave up or acked before the verdict"
    cluster.declare_dead(VICTIM)
    th.join(20)
    assert not th.is_alive() and done["reply"].ok, done
    assert done["s"] >= 0.6
    # on every shard routing calls writable now, and on no fewer than k
    have = shards_on_targets(cluster, ChunkId(600, 0))
    gold = ref.stripe_shards(payload(1), CHUNK, K, M)
    assert [j for j in range(K + M) if have[j] is None] == LOST
    assert all((have[j] or b"") == gold[j]
               for j in range(K + M) if j not in LOST)
    assert client._ec_degraded_write._value == 1
    assert client._routing_wait_ms._count >= 1


def test_without_the_wait_the_put_fails_and_never_acks_short(cluster):
    client = cluster.storage_client(retry=RetryOptions(
        routing_wait_s=0.0, **FAST))
    cluster.stop_node(VICTIM)
    r = client.write_stripe(cluster.chain_ids[0], ChunkId(601, 0),
                            payload(2), chunk_size=CHUNK)
    assert not r.ok
    assert client._ec_degraded_write._value == 0


def test_a_batched_put_rides_out_the_detection_too(cluster):
    client = cluster.storage_client(retry=RetryOptions(
        routing_wait_s=30.0, **FAST))
    chain = cluster.chain_ids[0]
    cluster.stop_node(VICTIM)
    items = [(ChunkId(602, i), payload(10 + i)) for i in range(3)]
    out = {}
    th = threading.Thread(target=lambda: out.update(
        replies=client.write_stripe_heads(chain, items, chunk_size=CHUNK)))
    th.start()
    time.sleep(0.4)
    assert th.is_alive()
    cluster.declare_dead(VICTIM)
    th.join(20)
    assert all(r is not None and r.ok for r in out["replies"]), out
    for cid, data in items:
        got = client.read_stripe(chain, cid, 0, len(data), chunk_size=CHUNK)
        assert got.ok and bytes(got.data) == data


def test_a_stage_against_another_chain_version_is_refused(cluster):
    client = cluster.storage_client(retry=RetryOptions(**FAST))
    put_blocks(cluster, client, 1)
    routing = cluster.mgmtd.get_routing_info()
    chain = routing.chains[cluster.chain_ids[0]]
    t = chain.target_of_shard(0)
    svc = cluster.svc_by_node[routing.node_of_target(t.target_id).node_id]
    req = ShardWriteReq(
        chain_id=chain.chain_id, chain_ver=chain.chain_version + 1,
        target_id=t.target_id, chunk_id=ChunkId(603, 0), data=b"x" * 8,
        crc=get_codec(K, M, S).crc_host(b"x" * 8), update_ver=1 << 32,
        chunk_size=S, logical_len=8, phase=1)
    assert svc.write_shard(req).code == Code.CHAIN_VERSION_MISMATCH
    assert svc.batch_write_shard([req])[0].code == \
        Code.CHAIN_VERSION_MISMATCH
    # a rebuild install (phase 0) of proven content lands whatever version
    req.phase = 0
    assert svc.write_shard(req).ok


def test_a_client_with_an_old_snapshot_puts_on_the_syncing_target(cluster):
    """The held snapshot still calls the victim's shards unwritable when
    they are SYNCING again: the stage is refused by version, the ladder
    re-resolves, and the put lands on the returning targets too."""
    client = cluster.storage_client(retry=RetryOptions(**FAST))
    chain = cluster.chain_ids[0]
    put_blocks(cluster, client, 2)
    cluster.stop_node(VICTIM)
    cluster.declare_dead(VICTIM)
    assert client.write_stripe(chain, ChunkId(604, 0), payload(3),
                               chunk_size=CHUNK).ok      # snapshot: 12
    cluster.restart_empty(VICTIM)
    cluster.beat()                # one of the four is SYNCING now
    syncing = [t for t in cluster.mgmtd.get_routing_info()
               .chains[chain].targets
               if t.public_state == PublicTargetState.SYNCING]
    assert len(syncing) == 1
    assert client.write_stripe(chain, ChunkId(604, 1), payload(4),
                               chunk_size=CHUNK).ok
    j = cluster.mgmtd.get_routing_info().chains[chain].shard_index(
        syncing[0].target_id)
    have = shards_on_targets(cluster, ChunkId(604, 1))
    assert (have[j] or b"") == ref.stripe_shards(
        payload(4), CHUNK, K, M)[j] and have[j] is not None


def test_the_node_returns_empty_and_is_rebuilt_under_puts(cluster):
    client = cluster.storage_client(retry=RetryOptions(
        routing_wait_s=30.0, **FAST))
    chain_id = cluster.chain_ids[0]
    stripes = put_blocks(cluster, client, 24)
    cluster.stop_node(VICTIM)
    cluster.declare_dead(VICTIM)
    stripes.update(put_blocks(cluster, client, 6, file_id=501))  # degraded
    assert client._ec_degraded_write._value == 6
    cluster.restart_empty(VICTIM)
    stop = threading.Event()
    late: dict = {}
    failed: list = []

    def writer():
        i = 0
        while not stop.is_set():
            data = payload(900000 + i)
            r = client.write_stripe(chain_id, ChunkId(502, i), data,
                                    chunk_size=CHUNK)
            if not r.ok:      # no put fails while the chain rebuilds
                failed.append((i, r))
                return
            late[(502, i)] = data
            i += 1
            time.sleep(0.002)

    def promoted_targets_are_whole(routing):
        """sync_done not before every stripe is there: a target of the
        victim that routing calls SERVING holds every stripe that was
        acknowledged before this look."""
        chain = routing.chains[chain_id]
        known = dict(stripes)
        for j in LOST:
            t = chain.target_of_shard(j)
            if t.public_state != PublicTargetState.SERVING:
                continue
            svc = cluster.svc_by_node[VICTIM]
            for (fid, idx), data in known.items():
                gold = ref.stripe_shards(data, CHUNK, K, M)[j]
                got = svc.read_rebuild(ReadReq(
                    chain_id, ChunkId(fid, idx), 0, -1, t.target_id))
                assert (bytes(got.data) if got.ok else b"") == gold, \
                    (j, fid, idx)

    th = threading.Thread(target=writer)
    th.start()
    try:
        rounds = cluster.recover(each_round=promoted_targets_are_whole)
    finally:
        stop.set()
        th.join(20)
    assert not failed, failed
    assert rounds >= 4            # one recovery at a time: four targets
    assert late, "no put landed while the chain rebuilt"
    stripes.update(late)
    # a last look after the writer stopped: what landed in the last round
    cluster.recover()
    assert_stored_as_the_reference_encodes(cluster, stripes)
    fresh = cluster.storage_client(retry=RetryOptions(**FAST))
    for (fid, idx), data in stripes.items():
        got = fresh.read_stripe(chain_id, ChunkId(fid, idx), 0, len(data),
                                chunk_size=CHUNK)
        assert got.ok and bytes(got.data) == data
    assert fresh._ec_degraded._value == 0


def test_a_stripe_that_lands_after_the_inventory_blocks_promotion(cluster):
    """The closing inventory: a put that resolved while the target was
    OFFLINE and committed after the pass's opening inventory is not on
    the recovering target; the pass must not end in sync_done."""
    from tpu3fs.storage.ec_resync import EcResyncWorker

    client = cluster.storage_client(retry=RetryOptions(**FAST))
    chain_id = cluster.chain_ids[0]
    put_blocks(cluster, client, 3)
    cluster.stop_node(VICTIM)
    cluster.declare_dead(VICTIM)
    cluster.restart_empty(VICTIM)
    cluster.beat()
    routing = cluster.mgmtd.get_routing_info()
    chain = routing.chains[chain_id]
    syncing = next(t for t in chain.targets
                   if t.public_state == PublicTargetState.SYNCING)
    coordinator = cluster.svc_by_node[routing.node_of_target(
        chain.serving_targets()[0].target_id).node_id]
    worker = EcResyncWorker(coordinator, coordinator._messenger)
    inner = worker._rebuild_batch

    def rebuild_batch(routing, chain, *a, **kw):
        out = inner(routing, chain, *a, **kw)
        # between the opening inventory and the pass's end, a put lands
        # on the twelve shards its (older) routing called writable
        codec = get_codec(K, M, S)
        data = payload(5)
        shards, crcs = codec.encode_stripe(data)
        for phase in (1, 2):
            for t in chain.serving_targets():
                j = chain.shard_index(t.target_id)
                body = data[j * S:(j + 1) * S] if j < K \
                    else shards[j].tobytes()
                svc = cluster.svc_by_node[
                    routing.node_of_target(t.target_id).node_id]
                r = svc.write_shard(ShardWriteReq(
                    chain_id=chain_id, chain_ver=chain.chain_version,
                    target_id=t.target_id, chunk_id=ChunkId(605, 0),
                    data=body if phase == 1 else b"",
                    crc=codec.crc_host(body) if phase == 1 else 0,
                    update_ver=1 << 32, chunk_size=S,
                    logical_len=len(data), phase=phase))
                assert r.ok, r
        return out

    worker._rebuild_batch = rebuild_batch
    worker.run_once()
    victim = cluster.svc_by_node[VICTIM]
    from tpu3fs.mgmtd.types import LocalTargetState

    assert victim.target(syncing.target_id).local_state == \
        LocalTargetState.ONLINE, "promoted with a stripe missing"
    assert worker.finished_passes[-1]["done"] is False
    worker._rebuild_batch = inner
    worker.run_once()             # the next round finds it
    assert victim.target(syncing.target_id).local_state == \
        LocalTargetState.UPTODATE
    j = chain.shard_index(syncing.target_id)
    got = victim.read_rebuild(ReadReq(chain_id, ChunkId(605, 0), 0, -1,
                                      syncing.target_id))
    assert (bytes(got.data) if got.ok else b"") == ref.stripe_shards(
        payload(5), CHUNK, K, M)[j]


def test_the_pass_line_counts_bytes_read_beside_bytes_installed(cluster):
    from tpu3fs.storage.ec_resync import pass_line

    client = cluster.storage_client(retry=RetryOptions(**FAST))
    stripes = put_blocks(cluster, client, 5)
    cluster.stop_node(VICTIM)
    cluster.declare_dead(VICTIM)
    cluster.restart_empty(VICTIM)
    cluster.recover()
    chain = cluster.mgmtd.get_routing_info().chains[cluster.chain_ids[0]]
    passes = {chain.shard_index(st["target"]): st
              for w in cluster.resync_workers.values()
              for st in w.finished_passes}
    assert sorted(passes) == LOST          # one pass a target, four passes
    sizes = [len(s) for d in stripes.values()
             for s in ref.stripe_shards(d, CHUNK, K, M)]
    for j, stats in passes.items():
        assert stats["stripes"] == stats["installed"] == len(stripes)
        assert stats["done"] is True and stats["seconds"] >= 0
        if j in (7, 11):
            # past the end of every block: proven empty by the quorum's
            # logical length, installed empty, no survivor read
            assert stats["bytes"] == 0 and stats["read_bytes"] == 0
        else:
            # twelve survivors (the blocks' seven shards hold bytes) were
            # read for every stripe to make one shard
            assert stats["bytes"] == len(stripes) * S
            assert 6 * stats["bytes"] <= stats["read_bytes"] <= sum(sizes)
        line = pass_line(stats)
        assert line.startswith(f"ec.rebuild target={stats['target']} ")
        assert f"installed_bytes={stats['bytes']} " in line
        assert f"read_bytes={stats['read_bytes']} " in line
        assert line.endswith("done=1")
    assert_stored_as_the_reference_encodes(cluster, stripes)


def test_ec_status_prints_installed_over_known_for_syncing_shards(cluster):
    from tpu3fs.cli import AdminCli, RpcFabricView

    client = cluster.storage_client(retry=RetryOptions(**FAST))
    put_blocks(cluster, client, 4)
    cluster.stop_node(VICTIM)
    cluster.declare_dead(VICTIM)
    cluster.restart_empty(VICTIM)
    cluster.beat()
    out = AdminCli(RpcFabricView(cluster.mgmtd_addr)).run(
        "ec-status --counts")
    assert "DEGRADED (4 shard(s) not serving, 1 rebuilding)" in out
    assert "0/4 stripes installed" in out
    cluster.recover()
    out = AdminCli(RpcFabricView(cluster.mgmtd_addr)).run(
        "ec-status --counts")
    assert "healthy" in out and "rebuild:" not in out


def test_a_restart_inside_the_heartbeat_timeout_is_a_loss_too(cluster):
    """The process dies and an empty one registers under its id before
    mgmtd ever declared it dead: its targets go OFFLINE at the
    registration and come back through SYNCING, rebuilt."""
    client = cluster.storage_client(retry=RetryOptions(**FAST))
    stripes = put_blocks(cluster, client, 5)
    before = cluster.mgmtd.get_routing_info().chains[
        cluster.chain_ids[0]].chain_version
    cluster.stop_node(VICTIM)
    cluster.restart_empty(VICTIM)       # no declare_dead in between
    chain = cluster.mgmtd.get_routing_info().chains[cluster.chain_ids[0]]
    assert chain.chain_version > before
    assert sorted(chain.shard_index(t.target_id) for t in chain.targets
                  if t.public_state != PublicTargetState.SERVING) == LOST
    cluster.recover()
    assert_stored_as_the_reference_encodes(cluster, stripes)


def test_the_kvcache_loads_exact_blocks_around_the_dead_node(cluster):
    """A load never answers a block that is only degraded with a miss or
    a hole: the file client's batched read decodes it."""
    from tpu3fs.client.file_io import FileIoClient
    from tpu3fs.meta.types import Acl, Inode, Layout

    sc = cluster.storage_client(retry=RetryOptions(**FAST))
    fio = FileIoClient(sc)
    inodes = []
    for i in range(4):
        ino = Inode.new_file(700 + i, Acl(0, 0, 0o644), Layout(
            table_id=1, chains=[cluster.chain_ids[0]], chunk_size=CHUNK))
        ino.length = BLOCK
        fio.write(ino, 0, payload(700 + i))
        inodes.append(ino)
    cluster.stop_node(VICTIM)
    got = fio.batch_read_files([(ino, 0, BLOCK) for ino in inodes])
    assert [bytes(b) for b in got] == [payload(700 + i) for i in range(4)]
    assert sc._ec_degraded._value == 4


PATTERNS = [[j for j in range(K + M) if j % 4 == n] for n in range(4)] + [
    sorted(np.random.default_rng([5, i]).choice(
        K + M, size=int(np.random.default_rng([6, i]).integers(1, M + 1)),
        replace=False).tolist()) for i in range(16)]


@pytest.mark.parametrize("lost", PATTERNS,
                         ids=["-".join(map(str, p)) for p in PATTERNS])
def test_reconstruct_batch_against_the_reference_decode(lost):
    """Every pattern a node can cause and sixteen random ones, on the
    host kernels and on the device program."""
    rng = np.random.default_rng([9] + lost)
    data = rng.integers(0, 256, (3, K, 512), dtype=np.uint8)
    codec = get_codec(K, M, 512)
    shards = np.concatenate([data, codec.rs.encode_np(data)], axis=1)
    present = [j for j in range(K + M) if j not in lost][:K]
    want = np.stack([refdec.decode(present, shards[b, present], K, M, lost)
                     for b in range(3)])
    assert (want == shards[:, lost]).all()      # the reference round trip
    saved = codec._host_mode
    try:
        for host in (True, False):
            codec._host_mode = host
            got = codec.reconstruct_batch(present, lost, shards[:, present])
            assert got.dtype == np.uint8 and (got == want).all(), host
    finally:
        codec._host_mode = saved


def test_the_length_sweep_of_a_close_rides_out_the_detection(cluster):
    """query_last_chunk (what a close settles a file's length by) asks
    every SERVING shard target; with a node gone and mgmtd not yet saying
    so it waits for the verdict, it does not fail the close."""
    client = cluster.storage_client(retry=RetryOptions(
        routing_wait_s=30.0, **FAST))
    put_blocks(cluster, client, 3, file_id=800)
    cluster.stop_node(VICTIM)
    out = {}
    th = threading.Thread(target=lambda: out.update(
        got=client.query_last_chunk(cluster.chain_ids[0], 800)))
    th.start()
    time.sleep(0.6)
    assert th.is_alive(), "the sweep failed or answered short"
    cluster.declare_dead(VICTIM)
    th.join(20)
    assert out["got"] == (2, BLOCK)
    # without the wait it is an error, never a short answer
    impatient = cluster.storage_client(retry=RetryOptions(
        routing_wait_s=0.0, **FAST))
    cluster.restart_empty(VICTIM)
    cluster.recover()
    cluster.stop_node(VICTIM)
    from tpu3fs.utils.result import FsError

    with pytest.raises(FsError):
        impatient.query_last_chunk(cluster.chain_ids[0], 800)


def _sweep_files(cluster, client) -> dict:
    """file id -> its (last index, length): three blocks, one block, one
    whose tail shard (3) sits on the victim, a full stripe, none at all."""
    chain = cluster.chain_ids[0]
    want = {810: (2, BLOCK), 811: (0, BLOCK), 812: (0, 3 * S + 17),
            813: (0, CHUNK), 814: (-1, 0)}
    for fid, (idx, n) in want.items():
        for i in range(idx + 1):
            assert client.write_stripe(chain, ChunkId(fid, i),
                                       payload(fid + i, n),
                                       chunk_size=CHUNK).ok
    return want


def test_a_batched_sweep_asks_each_node_once_over_sockets(cluster):
    client = cluster.storage_client(retry=RetryOptions(**FAST))
    want = _sweep_files(cluster, client)
    chain = cluster.chain_ids[0]
    sent = []
    inner = client._messenger

    def counting(node_id, method, payload):
        sent.append((method, node_id))
        return inner(node_id, method, payload)

    counting.parallel_fanout = True         # side by side, as served
    client._messenger = counting
    assert client.query_last_chunks(chain, list(want)) == list(want.values())
    assert sorted(sent) == [("query_last_chunks", n) for n in (10, 11, 12, 13)]
    assert [client.query_last_chunk(chain, f) for f in want] == list(
        want.values())


def test_a_batched_sweep_rides_out_the_detection(cluster):
    """One node down with routing still SERVING: the whole sweep waits for
    mgmtd's verdict and then settles every file precisely — the file whose
    tail shard the victim held too; without the wait it raises. Never a
    short length."""
    from tpu3fs.utils.result import FsError

    client = cluster.storage_client(retry=RetryOptions(
        routing_wait_s=30.0, **FAST))
    want = _sweep_files(cluster, client)
    chain = cluster.chain_ids[0]
    cluster.stop_node(VICTIM)
    out = {}
    th = threading.Thread(target=lambda: out.update(
        got=client.query_last_chunks(chain, list(want))))
    th.start()
    time.sleep(0.6)
    assert th.is_alive(), "the sweep failed or answered short"
    cluster.declare_dead(VICTIM)
    th.join(20)
    assert out["got"] == list(want.values())
    impatient = cluster.storage_client(retry=RetryOptions(
        routing_wait_s=0.0, **FAST))
    cluster.restart_empty(VICTIM)
    cluster.recover()
    cluster.stop_node(VICTIM)
    with pytest.raises(FsError):
        impatient.query_last_chunks(chain, list(want))


# -- a batch's degraded stripes decode together, one call a loss pattern --

def _degraded_stripe(k, m, S, seed, *, L=None, offset=0, length=None,
                     lost=(1,), tag=True, mixed=False, missing=False):
    """(spec, shard replies, the stripe's bytes) for one stripe that went
    degraded: its shards stored trimmed as a put leaves them (views, as a
    transport hands them over), the ``lost`` ones not answering. ``tag``:
    replies carry the stored logical length; ``mixed``: half the shards
    are at a newer version, so no version holds k; ``missing``: the
    stripe is absent on every shard."""
    L = k * S if L is None else L
    length = L - offset if length is None else length
    data = np.random.default_rng([31, seed]).integers(
        1, 256, L, dtype=np.uint8)
    padded = np.zeros((1, k, S), dtype=np.uint8)
    padded.reshape(-1)[:L] = data
    parity = get_codec(k, m, S).rs.encode_host(padded)[0]
    replies = {}
    for j in range(k + m):
        stored = (data[j * S:min((j + 1) * S, L)] if j < k
                  else parity[j - k]).tobytes()
        if missing:
            replies[j] = ReadReply(Code.CHUNK_NOT_FOUND)
        elif j in lost:
            replies[j] = ReadReply(Code.TARGET_OFFLINE)
        else:
            ver = 7 + (mixed and j >= (k + m) // 2)
            replies[j] = ReadReply(Code.OK, data=memoryview(stored),
                                   commit_ver=ver,
                                   logical_len=L if tag else 0)
    j0 = offset // S
    j1 = (offset + length - 1) // S + 1
    spec = {"chain": SimpleNamespace(target_of_shard=lambda j: None),
            "k": k, "m": m, "S": S, "j0": j0, "j1": j1, "offset": offset,
            "length": length, "wire": {}}
    return spec, replies, data.tobytes()


def _cases(k, m, S):
    """Each case: keyword sets for _degraded_stripe, one a stripe."""
    full = [dict(lost=(1,)), dict(lost=(1,))]
    return {
        "shared_pattern": [dict(lost=(1,)) for _ in range(5)],
        "two_patterns": full + [dict(lost=(0,)), dict(lost=(0,))],
        "no_lost_in_range": full + [dict(lost=(k - 1,), length=S)],
        "partial_ranges": full + [
            dict(offset=S // 2 + 3, length=2 * S),
            dict(offset=S + 1, length=S - 2),
            dict(offset=3, length=k * S - 5, lost=(0,))],
        "short_last_shard": [
            dict(L=(k - 1) * S + 17, lost=(k - 1,), tag=False),
            dict(L=(k - 1) * S + 17, lost=(0,), tag=False),
            dict(L=S + 5, lost=(0,), tag=False)],
        "mixed_versions": full + [dict(mixed=True)],
        "every_shard_missing": full + [dict(missing=True)],
    }


CODECS = [(K, M, 512), (3, 1, 64)]
LADDER = ReadReply(Code.CHUNK_NOT_COMMIT)


def _through_the_batch(stripes, monkeypatch):
    """What _degraded_round answers for these stripes, nothing left to
    fetch; a stripe with no decodable version goes down the ladder."""
    client = StorageClient("t", lambda: None, lambda *a: None)
    ladder = []
    monkeypatch.setattr(client, "_issue_wire_reads", lambda wire: [])
    monkeypatch.setattr(client, "read_stripe",
                        lambda *a, **kw: ladder.append(a) or LADDER)
    specs = {i: spec for i, (spec, _, _) in enumerate(stripes)}
    have = {i: dict(rs) for i, (_, rs, _) in enumerate(stripes)}
    reqs = [ReadReq(1, ChunkId(90, i), spec["offset"], spec["length"],
                    chunk_size=spec["k"] * spec["S"])
            for i, spec in specs.items()]
    replies = [None] * len(stripes)
    client._degraded_round(reqs, replies, specs, have, None,
                           list(specs))
    return client, replies, ladder


@pytest.mark.parametrize("case", list(_cases(1, 1, 1)))
@pytest.mark.parametrize("k,m,S", CODECS, ids=lambda v: str(v))
def test_a_batch_decodes_as_the_stripes_one_by_one(k, m, S, case,
                                                   monkeypatch):
    """Same bytes, commit_ver and logical_len as the single-stripe path
    (and as the stripe holds), on the host kernels."""
    stripes = [_degraded_stripe(k, m, S, n, **kw)
               for n, kw in enumerate(_cases(k, m, S)[case])]
    client, batch, ladder = _through_the_batch(stripes, monkeypatch)
    for (spec, rs, data), got in zip(stripes, batch):
        one = client._stripe_degraded(spec, rs) or LADDER
        assert (got.code, bytes(got.data), got.commit_ver,
                got.logical_len) == (one.code, bytes(one.data),
                                     one.commit_ver, one.logical_len)
        if got is LADDER or not got.ok:
            continue
        lo = spec["offset"]
        assert bytes(got.data) == data[lo:lo + spec["length"]]
        assert got.commit_ver == 7
        if (spec["j0"], spec["j1"]) == (0, k) or rs[k].logical_len:
            assert got.logical_len == len(data)
    assert len(ladder) == (case == "mixed_versions")
    ok = sum(r is not LADDER and r.ok for r in batch)
    assert client._ec_degraded._value == ok


def test_a_batch_makes_one_decode_a_loss_pattern(monkeypatch):
    """Three stripes lose shard 1, two lose shard 0, one loses nothing in
    its range: two reconstruct_batch calls, of three and of two."""
    from tpu3fs.ops.stripe import StripeCodec

    calls = []
    inner = StripeCodec.reconstruct_batch

    def counted(self, present_idx, lost_idx, present):
        calls.append((tuple(lost_idx), present.shape[0]))
        return inner(self, present_idx, lost_idx, present)

    monkeypatch.setattr(StripeCodec, "reconstruct_batch", counted)
    stripes = [_degraded_stripe(K, M, 512, n, lost=lost, length=length)
               for n, (lost, length) in enumerate(
                   [((1,), None)] * 3 + [((0,), None)] * 2
                   + [((K - 1,), 512)])]
    _client, batch, _ = _through_the_batch(stripes, monkeypatch)
    assert all(r.ok for r in batch)
    assert sorted(calls) == [((0,), 2), ((1,), 3)]
