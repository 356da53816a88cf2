"""A KVCache that is full (the deployment of perfbench's host-tier cell, at
small sizes on the CPU with the in-process fabric and an RS chain): the
capacity collector against a plain cache model step by step, readers
through one shared tiered store racing the collector, write-back producers
at the dirty bound, and the spans that deployment added."""

import threading
import time as _time

import numpy as np
import pytest

from tpu3fs.fabric import Fabric, SystemSetupConfig
from tpu3fs.kvcache import (
    KVCacheClient,
    KVCacheGC,
    PrefixBlockStore,
    TieredKVCache,
)
from tpu3fs.kvcache.layout import encode_array, shard_path
from tpu3fs.ops.stripe import get_codec, shard_size_of
from tpu3fs.storage.types import ChunkId

CHUNK = 12 * 1024


def ec_fabric(k=3, m=1, nodes=None, chunk_size=CHUNK):
    return Fabric(SystemSetupConfig(
        num_storage_nodes=nodes or k + m, num_chains=1,
        chunk_size=chunk_size, ec_k=k, ec_m=m))


# -- the plain cache the system is held to (the test's own copy of
# perfbench/lib/reference_cache.py: tier-1 does not import perfbench) -------
class PlainCache:
    def __init__(self):
        self.entries = {}   # key -> [value, touched]

    def put(self, key, value, now):
        self.entries[key] = [bytes(value), now]

    def get(self, key, now):
        entry = self.entries.get(key)
        if entry is None:
            return None
        entry[1] = now
        return entry[0]

    def capacity_pass(self, budget):
        total = sum(len(v) for v, _ in self.entries.values())
        gone = []
        for key in sorted(self.entries,
                          key=lambda k: (self.entries[k][1], k)):
            if total <= budget:
                break
            total -= len(self.entries[key][0])
            del self.entries[key]
            gone.append(key)
        return gone


class SteppedClock:
    """Stands in for the `time` module where the cache client and the meta
    store read the wall clock: every op of the drive has its own second."""

    def __init__(self):
        self.now = 1_000_000.0

    def time(self):
        return self.now

    def __getattr__(self, name):
        return getattr(_time, name)


@pytest.fixture
def clock(monkeypatch):
    import tpu3fs.kvcache.cache as cache_mod
    import tpu3fs.meta.store as store_mod

    c = SteppedClock()
    monkeypatch.setattr(cache_mod, "time", c)
    monkeypatch.setattr(store_mod, "time", c)
    return c


class TestAgainstThePlainCache:
    @pytest.mark.parametrize("seed", [1, 2, 3, 4])
    def test_a_seeded_drive_matches_the_model_after_every_step(self, seed,
                                                               clock):
        """(a) puts, gets (each a touch) and capacity passes in a seeded
        order: every get is the model's bytes or a miss exactly where the
        model misses, the surviving set is the model's after every pass,
        and the audit callback names what the model removed, in order."""
        fab = ec_fabric()
        cache = KVCacheClient(fab.meta, fab.file_client())
        gc = KVCacheGC(fab.meta, capacity_bytes=1)
        trail = []
        gc.on_remove = lambda path, mtime, length: trail.append(path)
        model = PlainCache()
        rng = np.random.default_rng(seed)
        keys = [f"blk/{i}" for i in range(24)]
        passes = 0
        for step in range(160):
            clock.now += 1.0
            op = rng.choice(["put", "get", "get", "pass"],
                            p=[0.35, 0.3, 0.25, 0.1])
            key = keys[int(rng.integers(len(keys)))]
            if op == "put" and key not in model.entries:
                value = rng.integers(0, 256, int(rng.integers(100, 9000)),
                                     dtype=np.uint8).tobytes()
                cache.put(key, value)
                model.put(key, value, clock.now)
            elif op == "get":
                assert cache.get(key) == model.get(key, clock.now), step
            elif op == "pass":
                passes += 1
                budget = int(rng.integers(8_000, 60_000))
                del trail[:]
                removed = gc.capacity_pass(now=clock.now,
                                           capacity_bytes=budget)
                gone = model.capacity_pass(budget)
                assert removed == len(gone)
                assert trail == [shard_path(cache.root, k) for k in gone]
                fab.run_gc()   # the meta server's chunk reclaim
                alive = [k for k in keys if cache.contains(k)]
                assert alive == [k for k in keys if k in model.entries]
                left = sum(len(v) for v, _ in model.entries.values())
                assert gc.last_pass["resident"] == left
                assert gc.last_pass["entries"] == len(model.entries)
        assert passes >= 5


def _doc(d, nblocks, block_tokens=4):
    tokens = list(range(1000 * d, 1000 * d + nblocks * block_tokens))
    rng = np.random.default_rng(100 + d)
    rows = [rng.integers(1, 1 << 16, (6, 64), dtype=np.uint16)
            for _ in range(nblocks)]
    return tokens, rows


def _shared_store(fab, tier_bytes, **tier_kw):
    cache = KVCacheClient(fab.meta, fab.file_client(), root="/kv",
                          inode_cache=256)
    tier = TieredKVCache(cache, capacity_bytes=tier_bytes, **tier_kw)
    return tier, PrefixBlockStore(tier, block_tokens=4)


class TestReadersRaceTheCollector:
    def test_every_block_is_exact_or_a_miss_never_zeros(self):
        """(b) three readers on ONE shared tiered store load whole
        documents and put again what did not come back, while a collector
        thread removes oldest-touched entries and the meta server's chunk
        reclaim runs: every block exact or None, no exception."""
        fab = ec_fabric()
        block = len(encode_array(_doc(0, 1)[1][0]))
        tier, store = _shared_store(fab, tier_bytes=5 * block)
        docs = [_doc(d, 2 + d % 4) for d in range(8)]
        for tokens, rows in docs:
            store.append_blocks(tokens, rows, write_through=True)
        total = sum(len(rows) for _, rows in docs)
        gc = KVCacheGC(fab.meta, root="/kv",
                       capacity_bytes=(total * 2 // 3) * block)
        stop = threading.Event()
        errors, seen = [], {"exact": 0, "miss": 0, "removed": 0}
        lock = threading.Lock()

        def collect():
            try:
                while not stop.is_set():
                    n = gc.capacity_pass()
                    fab.run_gc()
                    with lock:
                        seen["removed"] += n
                    _time.sleep(0.002)
            except Exception as e:   # pragma: no cover - the failure path
                errors.append(e)

        def reader(seed):
            rng = np.random.default_rng(seed)
            try:
                for _ in range(120):
                    tokens, rows = docs[int(rng.integers(len(docs)))]
                    match = store.match_prefix(tokens)
                    got = store.get_blocks(tokens, count=match.blocks) \
                        if match.blocks else []
                    missing = []
                    for i, want in enumerate(rows):
                        have = got[i] if i < len(got) else None
                        if have is None:
                            missing.append(i)
                            continue
                        assert have.any(), "zeros served as a block"
                        assert np.array_equal(have, want)
                    with lock:
                        seen["exact"] += len(rows) - len(missing)
                        seen["miss"] += len(missing)
                    for i in missing:
                        store.append_blocks(tokens, [rows[i]],
                                            start_block=i)
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=reader, args=(s,))
                   for s in range(3)]
        collector = threading.Thread(target=collect)
        collector.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        collector.join()
        assert tier.flush(30.0)
        tier.close()
        assert not errors, errors
        assert seen["exact"] > 300 and seen["removed"] > 0, seen

    def test_a_removed_entry_behind_a_held_inode_is_a_miss(self):
        """(b) the stale path: the put left the inodes held; the collector
        takes the entries and their chunks are reclaimed; nothing in the
        host tier. The get comes back None for each, counted in
        `kvcache.stale_reads`; a match after it finds nothing."""
        fab = ec_fabric()
        tier, store = _shared_store(fab, tier_bytes=1 << 20)
        tokens, rows = _doc(1, 3)
        assert store.append_blocks(tokens, rows, write_through=True) == 3
        tier.tier.clear()
        gc = KVCacheGC(fab.meta, root="/kv")
        assert gc.capacity_pass(capacity_bytes=1) == 3
        assert fab.run_gc() == 3
        before = store._stale_reads._value
        assert store.get_blocks(tokens) == [None, None, None]
        assert store._stale_reads._value - before == 3
        assert store.match_prefix(tokens).blocks == 0
        # and put again, they are whole again
        assert store.append_blocks(tokens, rows, write_through=True) == 3
        tier.tier.clear()
        got = store.get_blocks(tokens)
        assert all(np.array_equal(g, w) for g, w in zip(got, rows))
        tier.close()


class TestWriteBackAtTheBound:
    def test_four_producers_one_flusher_then_any_12_of_16_shards(self):
        """(c) four producers outrun the one flusher and stand at
        dirty_max_bytes (the buffer never holds more than the bound and
        one value); flush() is the barrier; every key then decodes from
        any 12 of its 16 RS(12,4) shards."""
        k, m = 12, 4
        fab = ec_fabric(k=k, m=m, chunk_size=CHUNK)
        S = shard_size_of(CHUNK, k)
        cache = KVCacheClient(fab.meta, fab.file_client(), root="/kv")
        rng = np.random.default_rng(7)
        values = {f"w{p}/{i}": rng.integers(
            0, 256, int(rng.integers(CHUNK // 2, CHUNK)),
            dtype=np.uint8).tobytes() for p in range(4) for i in range(10)}
        bound = 3 * CHUNK
        tier = TieredKVCache(cache, capacity_bytes=1 << 20,
                             dirty_max_bytes=bound, flush_batch=4)
        high = []

        def produce(p):
            for i in range(10):
                tier.put(f"w{p}/{i}", values[f"w{p}/{i}"])
                high.append(tier.dirty_bytes())

        threads = [threading.Thread(target=produce, args=(p,))
                   for p in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert tier.flush(60.0) is True and tier.dirty_bytes() == 0
        assert max(high) <= bound + CHUNK
        assert max(high) > bound - CHUNK, "the producers never met the bound"
        tier.close()
        routing = fab.routing()
        chain = routing.chains[fab.chain_ids[0]]
        codec = get_codec(k, m, S)
        for key, value in values.items():
            inode = fab.meta.stat(shard_path("/kv", key))
            assert inode.length == len(value)
            shards = []
            for j in range(k + m):
                t = chain.target_of_shard(j)
                node = routing.node_of_target(t.target_id)
                engine = fab.nodes[node.node_id].service.target(
                    t.target_id).engine
                raw = engine.read(ChunkId(inode.id, 0)) or b""
                shards.append(np.frombuffer(
                    bytes(raw).ljust(S, b"\0"), dtype=np.uint8))
            for lost in ((12, 13, 14, 15), (0, 1, 2, 3), tuple(sorted(
                    rng.choice(k + m, m, replace=False).tolist()))):
                present = [j for j in range(k + m) if j not in lost][:k]
                need = [j for j in lost if j < k]
                data = {j: shards[j] for j in present if j < k}
                if need:
                    rebuilt = codec.reconstruct_batch(
                        present, need,
                        np.stack([shards[j] for j in present])[None])[0]
                    data.update(zip(need, rebuilt))
                whole = b"".join(data[j].tobytes() for j in range(k))
                assert whole[:len(value)] == value, (key, lost)


@pytest.fixture(scope="module")
def churn_trees(tmp_path_factory):
    """The deployment's ops under ONE profiler session ->
    {root op name: [trees]} with each tree's rows by name."""
    import jax

    from tpu3fs.analytics import assemble, spans

    fab = ec_fabric()
    block = len(encode_array(_doc(0, 1)[1][0]))
    tier, store = _shared_store(fab, tier_bytes=4 * block,
                                dirty_max_bytes=2 * block, flush_batch=4)
    warm_tokens, warm_rows = _doc(9, 2)
    store.append_blocks(warm_tokens, warm_rows)
    assert tier.flush(30.0)
    store.get_blocks(warm_tokens)
    gc = KVCacheGC(fab.meta, root="/kv")
    tracer = spans.tracer()
    tracer.reset_captured()
    jax.profiler.start_trace(str(tmp_path_factory.mktemp("xplane")))
    try:
        tokens, rows = _doc(2, 6)
        # six blocks against a bound of two: the producer stands at it
        assert store.append_blocks(tokens, rows) == 6
        assert tier.flush(30.0)
        # the tier holds four of the six: hits and a fill
        got = store.get_blocks(tokens)
        assert all(g is not None for g in got)
        # the collector takes all, the chunks go, the held inodes stay
        tier.tier.clear()
        assert gc.capacity_pass(capacity_bytes=1) >= 6
        fab.run_gc()
        assert store.get_blocks(tokens) == [None] * 6
    finally:
        jax.profiler.stop_trace()
    tier.close()
    rows_ = assemble.rows_of_captured(tracer.captured())
    tracer.reset_captured()
    out = {}
    for tree in assemble.assemble_traces(rows_).values():
        if tree.root is not None:
            out.setdefault(tree.root["op"], []).append(tree)
    return out


def _beneath(tree, row):
    """{name: [rows]} of every span beneath `row`."""
    out, todo = {}, list(tree.children.get(row["span_id"], []))
    while todo:
        r = todo.pop()
        name = f"{r['op']}.{r['stage']}" if r["stage"] else r["op"]
        out.setdefault(name, []).append(r)
        todo.extend(tree.children.get(r["span_id"], []))
    return out


class TestTheDeploymentsSpans:
    """(d) beside tests/test_trace.py's cases: the spans the full-cache
    deployment added, under the parents docs/observability.md names."""

    def test_a_load_splits_into_host_tier_and_fill(self, churn_trees):
        block = len(encode_array(_doc(0, 1)[1][0]))
        tree = churn_trees["kvcache.get_blocks"][0]
        names = _beneath(tree, tree.root)
        (hit,) = names["kvcache.get_blocks.host_tier"]
        (fill,) = names["kvcache.get_blocks.fill"]
        assert hit["nbytes"] + fill["nbytes"] == 6 * block
        assert hit["nbytes"] >= 2 * block and fill["nbytes"] >= block
        # the miss path's reads parent under the fill
        assert "fio.batch_read_files" in _beneath(tree, fill)

    def test_a_stale_read_is_a_reprobe_stage(self, churn_trees):
        tree = churn_trees["kvcache.get_blocks"][-1]
        names = _beneath(tree, tree.root)
        assert len(names["kvcache.get_blocks.reprobe"]) == 6
        assert all(r["parent_id"] != tree.root["span_id"]   # under decode
                   for r in names["kvcache.get_blocks.reprobe"])

    def test_a_producer_s_stand_at_the_bound_is_dirty_wait(self,
                                                           churn_trees):
        (tree,) = churn_trees["kvcache.append_blocks"]
        waits = _beneath(tree, tree.root)["kvcache.append_blocks.dirty_wait"]
        assert len(waits) == 6   # one a block put, most of them ~0
        assert max(w["dur_us"] for w in waits) > 200
        assert all(w["parent_id"] == tree.root["span_id"] for w in waits)

    def test_a_drain_is_a_root_op_with_the_put_ladder_beneath(self,
                                                              churn_trees):
        block = len(encode_array(_doc(0, 1)[1][0]))
        drains = churn_trees["kvcache.flush"]
        assert sum(t.root["nbytes"] for t in drains) == 6 * block
        entries = 0
        for tree in drains:
            names = _beneath(tree, tree.root)
            stage = (names.get("kvcache.flush.batch_put")
                     or names["kvcache.flush.put_each"])[0]
            entries += stage["nbytes"]   # a count: entries of the drain
            for want in ("client.write_stripes", "codec.encode",
                         "fio.write_ec_chunk.rmw_probe",
                         "client.write_stripe.stage_shards",
                         "client.write_stripe.commit_shards"):
                assert want in names, (want, sorted(names))
        assert entries == 6

    def test_a_capacity_pass_is_a_root_op_with_scan_and_remove(self,
                                                               churn_trees):
        (tree,) = churn_trees["kvcache.gc.pass"]
        names = _beneath(tree, tree.root)
        assert set(names) >= {"kvcache.gc.pass.scan",
                              "kvcache.gc.pass.remove"}
        assert all(r["parent_id"] == tree.root["span_id"]
                   for n in ("kvcache.gc.pass.scan", "kvcache.gc.pass.remove")
                   for r in names[n])
