"""KVCache serving tier (ref README.md:17,45-51 — KV tensors of previous
tokens cached in files; GC remove-ops reclaim expired entries): the fs
tier, the host-RAM hot tier + write-back, the content-addressed
prefix-block store, pin leases, and the TTL/capacity GC."""

import threading
import time

import numpy as np
import pytest

from tpu3fs.fabric import Fabric, SystemSetupConfig
from tpu3fs.kvcache import (
    HostTier,
    KVCacheClient,
    KVCacheGC,
    LeaseManager,
    PrefixBlockStore,
    TieredKVCache,
)
from tpu3fs.kvcache.layout import decode_array, encode_array
from tpu3fs.utils.result import Code, FsError


@pytest.fixture
def cache():
    fab = Fabric(SystemSetupConfig(num_storage_nodes=2, num_chains=4,
                                   num_replicas=2, chunk_size=4096))
    c = KVCacheClient(fab.meta, fab.file_client())
    return fab, c


class TestKVCacheClient:
    def test_put_get_roundtrip(self, cache):
        _, c = cache
        c.put("req42/layer0", b"kv-bytes" * 1000)
        assert c.get("req42/layer0") == b"kv-bytes" * 1000
        assert c.get("req42/layer1") is None
        assert c.contains("req42/layer0")
        assert not c.contains("nope")

    def test_overwrite_truncates(self, cache):
        _, c = cache
        c.put("k", b"x" * 10_000)
        c.put("k", b"y" * 100)
        assert c.get("k") == b"y" * 100

    def test_batch_get_mixed_hits(self, cache):
        _, c = cache
        blobs = {f"p/{i}": bytes([i]) * (128 << 10) for i in range(4)}
        for k, v in blobs.items():
            c.put(k, v)
        keys = list(blobs) + ["missing/1", "missing/2"]
        out = c.batch_get(keys)
        assert [out[i] == blobs[k] for i, k in enumerate(blobs)] == [True] * 4
        assert out[4] is None and out[5] is None

    def test_batch_put_batches_dir_creates(self, cache):
        """The drain's directory fan-in: batch_put issues ONE batch_mkdirs
        round trip for all uncached parents (fanned per meta partition by
        a routed client) and ZERO per-item mkdirs — round-trip accounting
        for the meta-bound half of the write-back flush."""
        fab, c = cache
        meta = fab.meta
        mk_calls, bm_calls = [], []
        real_mkdirs, real_bm = meta.mkdirs, meta.batch_mkdirs

        def spy_mkdirs(*a, **kw):
            mk_calls.append(a)
            return real_mkdirs(*a, **kw)

        def spy_bm(paths, *a, **kw):
            bm_calls.append(len(list(paths)))
            return real_bm(paths, *a, **kw)

        meta.mkdirs, meta.batch_mkdirs = spy_mkdirs, spy_bm
        try:
            items = [(f"bm{i}/l{j}", bytes([i]) * 256)
                     for i in range(8) for j in range(2)]
            c.batch_put(items)
        finally:
            meta.mkdirs, meta.batch_mkdirs = real_mkdirs, real_bm
        from tpu3fs.kvcache.layout import shard_path
        nparents = len({shard_path(c.root, k).rsplit("/", 1)[0]
                        for k, _ in items})
        assert bm_calls == [nparents]
        assert mk_calls == []          # no per-item round trips
        # a second drain over the SAME keys skips the RPC entirely
        meta.batch_mkdirs = spy_bm
        try:
            c.batch_put([(k, b"z" * 64) for k, _ in items[:8]])
        finally:
            meta.batch_mkdirs = real_bm
        assert bm_calls == [nparents]  # parents cached: no new call
        for k, v in items[8:]:
            assert c.get(k) == v
        for k, _ in items[:8]:
            assert c.get(k) == b"z" * 64

    def test_array_roundtrip_bf16_like(self, cache):
        _, c = cache
        # decoder-layer KV block: [2(kv), heads, tokens, head_dim] f16
        arr = np.arange(2 * 4 * 32 * 16, dtype=np.float16).reshape(2, 4, 32, 16)
        c.put_array("req/kv/0", arr)
        back = c.get_array("req/kv/0")
        assert back.dtype == arr.dtype and back.shape == arr.shape
        assert np.array_equal(back, arr)
        assert c.get_array("req/kv/1") is None

    def test_remove(self, cache):
        _, c = cache
        c.put("gone", b"z")
        assert c.remove("gone")
        assert c.get("gone") is None
        assert not c.remove("gone")


class TestKVCacheGC:
    def test_expired_entries_removed_fresh_kept(self, cache):
        fab, c = cache
        gc = KVCacheGC(fab.meta, ttl_s=100.0, max_shards=1024)
        now = time.time()
        for i in range(6):
            c.put(f"e/{i}", b"v" * 512)
        # age half of them past the TTL
        for i in range(3):
            from tpu3fs.kvcache.cache import _shard_path

            fab.meta.set_attr(_shard_path(c.root, f"e/{i}"),
                              mtime=now - 1000)
        assert gc.run_once(now=now) == 3
        assert [c.get(f"e/{i}") is None for i in range(6)] == \
            [True] * 3 + [False] * 3

    def test_touch_on_get_is_lru(self, cache):
        fab, c = cache
        from tpu3fs.kvcache.cache import _shard_path

        gc = KVCacheGC(fab.meta, ttl_s=100.0, max_shards=1024)
        now = time.time()
        c.put("hot", b"h")
        c.put("cold", b"c")
        for k in ("hot", "cold"):
            fab.meta.set_attr(_shard_path(c.root, k), mtime=now - 1000)
        # a get() refreshes mtime, rescuing the entry from this GC pass
        assert c.get("hot") == b"h"
        assert gc.run_once(now=now) == 1
        assert c.get("hot") == b"h"
        assert c.get("cold") is None

    def test_batch_get_refreshes_mtime_like_get(self, cache):
        fab, c = cache
        from tpu3fs.kvcache.cache import _shard_path

        gc = KVCacheGC(fab.meta, ttl_s=100.0, max_shards=1024)
        now = time.time()
        c.put("bk", b"b")
        fab.meta.set_attr(_shard_path(c.root, "bk"), mtime=now - 1000)
        assert c.batch_get(["bk"]) == [b"b"]
        assert gc.run_once(now=now) == 0  # batch_get rescued it

    def test_gc_shard_budget_partial_pass(self, cache):
        fab, c = cache
        gc = KVCacheGC(fab.meta, ttl_s=0.0, max_shards=1)
        for i in range(8):
            c.put(f"b/{i}", b"x")
        total = 0
        # each pass visits one shard; repeated passes drain all of them
        for _ in range(600):
            total += gc.run_once(now=time.time() + 10)
            if total == 8:
                break
        assert total == 8


class TestArrayCodec:
    def test_roundtrip_is_view(self):
        arr = np.arange(64, dtype=np.float16).reshape(4, 16)
        raw = encode_array(arr)
        back = decode_array(raw)
        assert back.dtype == arr.dtype and np.array_equal(back, arr)
        assert back.base is not None  # frombuffer view, no payload copy

    def test_zero_hole_read_is_stale_not_zeros(self):
        # a GC'd entry under a cached inode reads back as all zeros —
        # the magic turns that into a typed error, never zeros-as-KV
        raw = encode_array(np.ones(8, np.float32))
        with pytest.raises(FsError) as ei:
            decode_array(b"\x00" * len(raw))
        assert ei.value.code == Code.KVCACHE_STALE

    def test_bad_magic_and_truncation_are_corrupt(self):
        raw = bytearray(encode_array(np.ones(8, np.float32)))
        raw[12] ^= 0xFF  # flip a magic byte
        with pytest.raises(FsError) as ei:
            decode_array(bytes(raw))
        assert ei.value.code == Code.KVCACHE_CORRUPT
        with pytest.raises(FsError) as ei:
            decode_array(b"\x01\x02")
        assert ei.value.code == Code.KVCACHE_CORRUPT


class TestHostTier:
    def test_lru_eviction_order_and_bounded_bytes(self):
        t = HostTier(capacity_bytes=300)
        t.put("a", b"x" * 100)
        t.put("b", b"y" * 100)
        t.put("c", b"z" * 100)
        assert t.get("a") == b"x" * 100  # refresh a: b is now LRU
        t.put("d", b"w" * 100)           # evicts b
        assert t.get("b") is None
        assert t.get("a") is not None and t.get("c") is not None
        assert t.bytes <= 300

    def test_oversized_value_not_cached(self):
        t = HostTier(capacity_bytes=100)
        t.put("small", b"s" * 50)
        assert t.put("huge", b"h" * 500) == 0
        assert t.get("huge") is None
        assert t.get("small") is not None  # hot set not thrashed

    def test_overwrite_adjusts_bytes(self):
        t = HostTier(capacity_bytes=1000)
        t.put("k", b"a" * 400)
        t.put("k", b"b" * 100)
        assert t.bytes == 100
        assert t.remove("k") and t.bytes == 0 and not t.remove("k")


class TestTieredKVCache:
    def _tiered(self, fab, **kw):
        base = KVCacheClient(fab.meta, fab.file_client())
        return base, TieredKVCache(base, **kw)

    def test_host_hit_serves_without_any_storage_or_meta_op(self, cache):
        fab, base = cache
        tc = TieredKVCache(base, write_through=True)
        try:
            tc.put("hot", b"v" * 4096)
            fio, meta = base._fio, base._meta
            calls = {"n": 0}

            def trip(*a, **kw):
                calls["n"] += 1
                raise AssertionError("host hit touched the cluster")

            for obj, names in ((fio, ("read", "batch_read_files")),
                               (meta, ("stat", "batch_stat_by_path"))):
                for name in names:
                    setattr(obj, name, trip)
            assert tc.get("hot") == b"v" * 4096
            assert tc.batch_get(["hot"]) == [b"v" * 4096]
            assert calls["n"] == 0
        finally:
            tc.close(flush=False)
            fab.close()

    def test_miss_fills_as_one_batch_and_lands_in_tier(self, cache):
        fab, base = cache
        blobs = {f"m/{i}": bytes([i + 1]) * 2048 for i in range(6)}
        for k, v in blobs.items():
            base.put(k, v)
        tc = TieredKVCache(base)
        try:
            fio = base._fio
            batches = []
            real = fio.batch_read_files

            def spy(files):
                batches.append(len(files))
                return real(files)

            fio.batch_read_files = spy
            out = tc.batch_get(list(blobs))
            assert out == list(blobs.values())
            assert batches == [6]  # every miss in ONE striped batch
            out = tc.batch_get(list(blobs))  # now resident
            assert out == list(blobs.values())
            assert batches == [6]
        finally:
            tc.close(flush=False)
            fab.close()

    def test_write_back_visible_immediately_durable_after_flush(self, cache):
        fab, base = cache
        tc = TieredKVCache(base)
        try:
            tc.put("wb", b"payload" * 100)
            assert tc.get("wb") == b"payload" * 100  # read-your-writes
            assert tc.flush(10.0)
            # durable: a FRESH client (no tier) sees it
            fresh = KVCacheClient(fab.meta, fab.file_client())
            assert fresh.get("wb") == b"payload" * 100
        finally:
            tc.close()
            fab.close()

    def test_write_through_is_synchronous(self, cache):
        fab, base = cache
        tc = TieredKVCache(base, write_through=True)
        try:
            tc.put("wt", b"d" * 512)
            assert tc.dirty_bytes() == 0
            fresh = KVCacheClient(fab.meta, fab.file_client())
            assert fresh.get("wt") == b"d" * 512
        finally:
            tc.close()
            fab.close()

    def test_read_your_writes_survives_tier_eviction(self, cache):
        fab, base = cache
        # tier far smaller than the dirty buffer: entries evict from the
        # hot tier while still dirty — reads must hit the dirty buffer,
        # not fall through to fs (where the value is not yet durable)
        stall = threading.Event()
        real_put = base.put

        def stalled_put(key, value):
            stall.wait(10.0)
            return real_put(key, value)

        base.put = stalled_put
        tc = TieredKVCache(base, capacity_bytes=1024,
                           dirty_max_bytes=1 << 20)
        try:
            for i in range(8):
                tc.put(f"e/{i}", bytes([i]) * 900)
            assert len(tc.tier) <= 1  # evicted from the hot tier
            for i in range(8):
                assert tc.get(f"e/{i}") == bytes([i]) * 900
        finally:
            stall.set()
            tc.close()
            fab.close()

    def test_dirty_buffer_bounded_under_stalled_storage(self, cache):
        fab, base = cache
        stall = threading.Event()
        real_put = base.put

        def stalled_put(key, value):
            stall.wait(30.0)
            return real_put(key, value)

        real_batch_put = base.batch_put

        def stalled_batch_put(items):
            stall.wait(30.0)
            return real_batch_put(items)

        base.put = stalled_put
        base.batch_put = stalled_batch_put  # the flusher's batched drain
        tc = TieredKVCache(base, dirty_max_bytes=4096)
        try:
            for i in range(4):  # 4 x 1KiB fill the bound
                tc.put(f"s/{i}", bytes([i]) * 1024)
            blocked = threading.Event()
            done = threading.Event()

            def producer():
                blocked.set()
                tc.put("s/overflow", b"x" * 1024)  # must BLOCK at bound
                done.set()

            t = threading.Thread(target=producer, daemon=True)
            t.start()
            assert blocked.wait(5.0)
            assert not done.wait(0.3)          # still blocked
            assert tc.dirty_bytes() <= 4096 + 1024
            # the memory-observability gauges see the same bound (what
            # admin_cli top reports: kvcache.dirty_bytes/host_bytes)
            assert tc._dirty_gauge._value <= 4096 + 1024
            assert tc._host_gauge._value is not None
            assert tc._host_gauge._value <= tc.tier.capacity_bytes
            stall.set()                        # storage recovers
            assert done.wait(10.0)             # producer unblocks
            assert tc.flush(10.0)
            t.join(5.0)
        finally:
            stall.set()
            tc.close()
            fab.close()

    def test_flush_error_budget_poisons_put(self, cache):
        """Carried follow-up from PR 5: after N consecutive failed flush
        cycles the write-back buffer POISONS — put() raises
        KVCACHE_FLUSH_POISONED to the producer instead of buffering
        silently forever; a successful flush clears the poison."""
        from tpu3fs.utils.result import Code, FsError, Status

        fab, base = cache
        dead = threading.Event()
        dead.set()
        real_put, real_batch_put = base.put, base.batch_put

        def failing_put(key, value):
            if dead.is_set():
                raise FsError(Status(Code.TARGET_OFFLINE, "storage down"))
            return real_put(key, value)

        def failing_batch_put(items):
            if dead.is_set():
                raise FsError(Status(Code.TARGET_OFFLINE, "storage down"))
            return real_batch_put(items)

        base.put = failing_put
        base.batch_put = failing_batch_put
        tc = TieredKVCache(base, flush_error_budget=3)
        try:
            tc.put("p/0", b"a" * 100)  # buffered; flusher starts failing
            deadline = time.monotonic() + 10.0
            while not tc.flush_poisoned and time.monotonic() < deadline:
                time.sleep(0.02)
            assert tc.flush_poisoned
            with pytest.raises(FsError) as ei:
                tc.put("p/1", b"b" * 100)
            assert ei.value.code == Code.KVCACHE_FLUSH_POISONED
            # reads of the buffered value still work (read-your-writes)
            assert tc.get("p/0") == b"a" * 100
            # storage recovers: the flusher drains and the poison clears
            dead.clear()
            assert tc.flush(10.0)
            assert not tc.flush_poisoned
            tc.put("p/2", b"c" * 100)  # accepted again
            assert tc.flush(10.0)
            assert base.get("p/2") == b"c" * 100
        finally:
            dead.clear()
            tc.close()
            fab.close()

    def test_flusher_drains_via_batch_put(self, cache):
        """The write-back flusher drains the dirty buffer as ONE batched
        striped write (batch_put -> batch_write_files), not per-key
        puts."""
        fab, base = cache
        batches = []
        real_batch_put = base.batch_put

        def spy_batch_put(items):
            batches.append(len(list(items)))
            return real_batch_put(items)

        base.batch_put = spy_batch_put
        tc = TieredKVCache(base, flush_batch=8)
        try:
            gate = threading.Event()
            real_put = base.put

            def gated_put(key, value):  # hold the loop so puts pile up
                gate.wait(5.0)
                return real_put(key, value)

            base.put = gated_put
            for i in range(6):
                tc.put(f"bf/{i}", bytes([i]) * 500)
            gate.set()
            assert tc.flush(10.0)
            assert any(n > 1 for n in batches), batches
            for i in range(6):
                assert base.get(f"bf/{i}") == bytes([i]) * 500
        finally:
            tc.close()
            fab.close()

    def test_remove_drops_tier_and_dirty(self, cache):
        fab, base = cache
        stall = threading.Event()
        real_put = base.put
        base.put = lambda k, v: (stall.wait(10.0), real_put(k, v))[1]
        tc = TieredKVCache(base)
        try:
            tc.put("gone", b"g" * 256)
            tc.remove("gone")
            assert tc.get("gone") is None
            stall.set()
            assert tc.flush(10.0)
        finally:
            stall.set()
            tc.close()
            fab.close()


class TestPrefixBlocks:
    BT = 4

    def _pages(self, n, fill=0):
        return [np.full((2, 2, self.BT, 8), fill * 100 + i,
                        dtype=np.float16) for i in range(n)]

    def test_chain_keys_commit_to_the_whole_prefix(self):
        from tpu3fs.kvcache import chain_keys

        a = chain_keys([1, 2, 3, 4, 5, 6, 7, 8], 4)
        b = chain_keys([9, 2, 3, 4, 5, 6, 7, 8], 4)
        assert len(a) == len(b) == 2
        # same second-block TOKENS, different prefix -> different key
        assert a[1] != b[1] and a[0] != b[0]
        # partial trailing block has no key
        assert len(chain_keys([1, 2, 3, 4, 5], 4)) == 1
        assert chain_keys([1, 2, 3], 4) == []

    def test_match_prefix_longest_and_hole_ends_match(self, cache):
        fab, base = cache
        store = PrefixBlockStore(base, block_tokens=self.BT)
        toks = list(range(5 * self.BT))
        store.append_blocks(toks, self._pages(5))
        m = store.match_prefix(toks)
        assert (m.blocks, m.tokens) == (5, 20)
        # mid-chain hole: removing block 2 ends the match at 2 blocks
        keys = store.block_keys(toks)
        base.remove(keys[2])
        m = store.match_prefix(toks)
        assert (m.blocks, m.tokens) == (2, 8)
        assert m.keys == keys[:2]
        # diverging suffix matches only the shared prefix
        m = store.match_prefix(toks[:self.BT] + [99] * self.BT)
        assert m.blocks == 1
        fab.close()

    def test_shared_prefix_blocks_stored_exactly_once(self, cache):
        """ACCEPTANCE: two sessions sharing a prompt prefix store each
        shared block exactly once (counted at the fs put layer)."""
        fab, base = cache
        puts = []
        real_put = base.put
        real_batch_put = base.batch_put

        def spy(key, value):
            puts.append(key)
            return real_put(key, value)

        def batch_spy(items):
            items = list(items)
            puts.extend(key for key, _ in items)
            return real_batch_put(items)

        base.put = spy
        base.batch_put = batch_spy  # the drain path (append_blocks >1)
        store = PrefixBlockStore(base, block_tokens=self.BT)
        toks_a = list(range(4 * self.BT))
        assert store.append_blocks(toks_a, self._pages(4)) == 4
        # session B shares the first 2 blocks, diverges after
        toks_b = toks_a[:2 * self.BT] + [77] * (2 * self.BT)
        m = store.match_prefix(toks_b)
        assert m.blocks == 2
        stored = store.append_blocks(
            toks_b, self._pages(2, fill=7), start_block=m.blocks)
        assert stored == 2  # only the divergent tail
        keys_a = set(store.block_keys(toks_a))
        keys_b = set(store.block_keys(toks_b))
        assert len(puts) == len(set(puts)) == len(keys_a | keys_b) == 6
        # a FULL re-append of A's sequence writes nothing new
        assert store.append_blocks(toks_a, self._pages(4)) == 0
        assert len(puts) == 6
        fab.close()

    def test_get_blocks_roundtrip_and_device_put(self, cache):
        import jax

        fab, base = cache
        store = PrefixBlockStore(base, block_tokens=self.BT)
        toks = list(range(3 * self.BT))
        pages = self._pages(3)
        store.append_blocks(toks, pages)
        out = store.get_blocks(toks)
        assert all(np.array_equal(a, p) for a, p in zip(out, pages))
        dev = jax.devices("cpu")[0]
        on_dev = store.get_blocks(toks, count=2, device=dev)
        assert len(on_dev) == 2
        assert all(isinstance(a, jax.Array) for a in on_dev)
        assert np.array_equal(np.asarray(on_dev[1]), pages[1])
        fab.close()

    def test_stale_cached_inode_reads_as_miss_not_zeros(self, cache):
        fab, _ = cache
        serving = KVCacheClient(fab.meta, fab.file_client(),
                                inode_cache=64)
        store = PrefixBlockStore(serving, block_tokens=self.BT)
        toks = list(range(2 * self.BT))
        store.append_blocks(toks, self._pages(2))
        assert all(a is not None for a in store.get_blocks(toks))
        # GC removes the entries AND reclaims chunks behind the client's
        # cached inodes
        gc = KVCacheGC(fab.meta, ttl_s=0.0, max_shards=1 << 20)
        assert gc.run_once(now=time.time() + 10) == 2
        fab.run_gc()
        out = store.get_blocks(toks)
        assert out == [None, None]  # plain misses — never zeros-as-KV
        fab.close()


class TestLeases:
    def test_leased_blocks_survive_ttl_and_capacity_gc(self, cache):
        """ACCEPTANCE: GC never removes a leased block — under both TTL
        and capacity-target eviction."""
        fab, c = cache
        leases = LeaseManager(fab.meta, default_ttl_s=300.0)
        store = PrefixBlockStore(c, block_tokens=4, leases=leases)
        toks = list(range(16))
        store.append_blocks(toks, [np.full((4, 8), i, np.float16)
                                   for i in range(4)])
        m = store.match_prefix(toks[:8])
        lease = store.pin_prefix(m)
        assert len(lease.keys) == 2 and leases.active == 2
        gc = KVCacheGC(fab.meta, ttl_s=0.0, max_shards=1 << 20,
                       capacity_bytes=0)
        now = time.time() + 10
        assert gc.run_once(now=now) == 2          # the 2 unleased
        assert gc.capacity_pass(now=now) == 0     # leased = floor
        assert store.match_prefix(toks).blocks == 2  # leased still there
        leases.unpin(lease)
        assert gc.capacity_pass(now=now) == 2
        fab.close()

    def test_expired_lease_is_collectable(self, cache):
        fab, c = cache
        leases = LeaseManager(fab.meta, default_ttl_s=0.001)
        c.put("brief", b"b" * 128)
        leases.pin(["brief"])
        gc = KVCacheGC(fab.meta, ttl_s=0.0, max_shards=1 << 20)
        time.sleep(0.01)  # lease expires
        assert gc.run_once(now=time.time() + 10) == 1
        fab.close()

    def test_unpin_keeps_longer_foreign_lease(self, cache):
        fab, c = cache
        c.put("shared", b"s" * 64)
        long_mgr = LeaseManager(fab.meta, default_ttl_s=600.0)
        short_mgr = LeaseManager(fab.meta, default_ttl_s=60.0)
        long_lease = long_mgr.pin(["shared"])
        short = short_mgr.pin(["shared"])   # longer lease already there
        short_mgr.unpin(short)              # must NOT strip the long pin
        gc = KVCacheGC(fab.meta, ttl_s=0.0, max_shards=1 << 20)
        assert gc.run_once(now=time.time() + 10) == 0
        long_mgr.unpin(long_lease)
        assert gc.run_once(now=time.time() + 10) == 1
        fab.close()

    def test_renew_extends_protection(self, cache):
        fab, c = cache
        c.put("renewed", b"r")
        mgr = LeaseManager(fab.meta, default_ttl_s=0.05)
        lease = mgr.pin(["renewed"])
        mgr.renew(lease, ttl_s=600.0)
        time.sleep(0.06)  # original ttl long gone
        gc = KVCacheGC(fab.meta, ttl_s=0.0, max_shards=1 << 20)
        assert gc.run_once(now=time.time() + 10) == 0
        fab.close()


class TestGCEdgeCases:
    def test_cursor_wraps_mid_pass_without_looping(self, cache):
        fab, c = cache
        for i in range(6):
            c.put(f"w/{i}", b"x")
        # budget far above the leaf count: one pass must wrap the whole
        # shard tree EXACTLY once (seen-leaf cycle detection) and stop
        gc = KVCacheGC(fab.meta, ttl_s=0.0, max_shards=1 << 20)
        t0 = time.monotonic()
        assert gc.run_once(now=time.time() + 10) == 6
        assert time.monotonic() - t0 < 30
        assert gc.run_once(now=time.time() + 10) == 0  # idempotent
        fab.close()

    def test_cursor_resumes_across_budgeted_passes(self, cache):
        fab, c = cache
        for i in range(8):
            c.put(f"b/{i}", b"x")
        gc = KVCacheGC(fab.meta, ttl_s=0.0, max_shards=1)
        total, passes = 0, 0
        while total < 8 and passes < 600:
            total += gc.run_once(now=time.time() + 10)
            passes += 1
        assert total == 8
        assert passes > 1  # the budget actually split the work

    def test_capacity_pass_evicts_oldest_first_to_budget(self, cache):
        fab, c = cache
        from tpu3fs.kvcache import shard_path

        now = time.time()
        for i in range(4):
            c.put(f"cap/{i}", bytes([i]) * 1000)
            fab.meta.set_attr(shard_path(c.root, f"cap/{i}"),
                              mtime=now - 100 + i)  # 0 oldest .. 3 newest
        gc = KVCacheGC(fab.meta, ttl_s=1e9, capacity_bytes=2000)
        removed = gc.capacity_pass(now=now)
        assert removed == 2
        assert c.get("cap/0") is None and c.get("cap/1") is None
        assert c.get("cap/2") is not None and c.get("cap/3") is not None
        # under budget: a second pass is a no-op
        assert gc.capacity_pass(now=now) == 0
        fab.close()

    def test_concurrent_touch_vs_remove_race_is_safe(self, cache):
        fab, c = cache
        n = 24
        for i in range(n):
            c.put(f"race/{i}", bytes([i]) * 256)
        gc = KVCacheGC(fab.meta, ttl_s=0.5, max_shards=1 << 20)
        stop = threading.Event()
        errors = []

        def toucher():
            try:
                while not stop.is_set():
                    c.batch_get([f"race/{i}" for i in range(n)])
            except BaseException as e:  # any crash fails the test
                errors.append(e)

        t = threading.Thread(target=toucher, daemon=True)
        t.start()
        try:
            removed = 0
            deadline = time.time() + 10
            while time.time() < deadline:
                removed += gc.run_once(now=time.time() + 0.25)
        finally:
            stop.set()
            t.join(10)
        assert not errors
        # every entry is either fully present or fully gone
        out = c.batch_get([f"race/{i}" for i in range(n)])
        for i, blob in enumerate(out):
            assert blob is None or blob == bytes([i]) * 256
        fab.close()


class TestBatchedTouch:
    def test_batch_get_touches_in_one_metadata_call(self, cache):
        """Satellite: the N-set_attr-per-batch hot path is gone — one
        batch_set_attr per batch_get, zero per-key set_attr calls."""
        fab, c = cache
        for i in range(8):
            c.put(f"t/{i}", b"v")
        calls = {"batch": 0, "single": 0}
        real_batch = fab.meta.batch_set_attr
        real_single = fab.meta.set_attr

        def spy_batch(*a, **kw):
            calls["batch"] += 1
            return real_batch(*a, **kw)

        def spy_single(*a, **kw):
            calls["single"] += 1
            return real_single(*a, **kw)

        fab.meta.batch_set_attr = spy_batch
        fab.meta.set_attr = spy_single
        assert all(b is not None
                   for b in c.batch_get([f"t/{i}" for i in range(8)]))
        assert calls == {"batch": 1, "single": 0}
        c.get("t/0")
        assert calls == {"batch": 2, "single": 0}
        fab.close()

    def test_coalesced_touch_drains_once_per_interval(self, cache):
        fab, _ = cache
        c = KVCacheClient(fab.meta, fab.file_client(),
                          touch_coalesce_s=30.0)
        c.put("cz", b"z")
        calls = {"n": 0}
        real = fab.meta.batch_set_attr

        def spy(*a, **kw):
            calls["n"] += 1
            return real(*a, **kw)

        fab.meta.batch_set_attr = spy
        for _ in range(10):
            assert c.get("cz") == b"z"
        assert calls["n"] == 0          # nothing on the read path
        c.flush_touches()
        assert calls["n"] == 1          # one drain for all 10 touches
        mtime = fab.meta.stat(
            __import__("tpu3fs.kvcache.layout",
                       fromlist=["shard_path"]).shard_path(
                           c.root, "cz")).mtime
        assert time.time() - mtime < 5.0
        fab.close()


class TestKvcacheCli:
    def test_stats_and_gc_commands(self, cache):
        from tpu3fs.cli import AdminCli

        fab, c = cache
        leases = LeaseManager(fab.meta)
        for i in range(5):
            c.put(f"cli/{i}", bytes(400))
        leases.pin([f"cli/{0}", f"cli/{1}"])
        cli = AdminCli(fab)
        out = cli.run("kvcache-stats")
        assert "entries=5" in out and "bytes=2000" in out
        assert "leased=2" in out
        out = cli.run("kvcache-gc --ttl 0 --max-shards 100000")
        assert "removed 3" in out  # leased pair survives
        out = cli.run("kvcache-gc --ttl 1e9 --capacity-bytes 0 "
                      "--max-shards 100000")
        assert "capacity pass removed 0" in out  # all remaining leased
        fab.close()


class TestBatchPutCreateFanIn:
    def test_batch_put_uses_one_batch_create(self, cache):
        """The create half of the write-back drain fans IN: one
        batch_create call for the whole batch, zero per-key meta.create
        round trips (the PR 6 follow-up that left the flush meta-bound)."""
        fab, c = cache
        calls = {"create": 0, "batch_create": 0}
        real_create = fab.meta.create
        real_batch_create = fab.meta.batch_create

        def spy_create(*a, **kw):
            calls["create"] += 1
            return real_create(*a, **kw)

        def spy_batch_create(items, *a, **kw):
            calls["batch_create"] += 1
            return real_batch_create(items, *a, **kw)

        fab.meta.create = spy_create
        fab.meta.batch_create = spy_batch_create
        try:
            c.batch_put([(f"bk{i}", bytes([i]) * 500) for i in range(12)])
        finally:
            fab.meta.create = real_create
            fab.meta.batch_create = real_batch_create
        assert calls["batch_create"] == 1
        assert calls["create"] == 0
        for i in range(12):
            assert c.get(f"bk{i}") == bytes([i]) * 500

    def test_append_blocks_drain_is_one_meta_batch(self, cache):
        """PR 16 carried follow-up: a PrefixBlockStore.append_blocks
        drain routes through KVCacheClient.batch_put — exactly ONE
        batch_create for the whole drain and zero per-block serial
        meta.create round trips (the last serial-create path)."""
        fab, c = cache
        calls = {"create": 0, "batch_create": 0}
        real_create = fab.meta.create
        real_batch_create = fab.meta.batch_create

        def spy_create(*a, **kw):
            calls["create"] += 1
            return real_create(*a, **kw)

        def spy_batch_create(items, *a, **kw):
            calls["batch_create"] += 1
            return real_batch_create(items, *a, **kw)

        store = PrefixBlockStore(c, block_tokens=4)
        tokens = list(range(16))  # 4 full blocks
        blocks = [np.full((2, 2, 4, 8), i, dtype=np.float16)
                  for i in range(4)]
        fab.meta.create = spy_create
        fab.meta.batch_create = spy_batch_create
        try:
            wrote = store.append_blocks(tokens, blocks)
        finally:
            fab.meta.create = real_create
            fab.meta.batch_create = real_batch_create
        assert wrote == 4
        assert calls["batch_create"] == 1
        assert calls["create"] == 0
        out = store.get_blocks(tokens)
        assert len(out) == 4
        for i, arr in enumerate(out):
            np.testing.assert_array_equal(arr, blocks[i])

    def test_batch_put_failed_create_raises_and_closes(self, cache):
        fab, c = cache
        real_batch_create = fab.meta.batch_create

        def failing(items, *a, **kw):
            res = real_batch_create(items, *a, **kw)
            res[-1] = FsError.__new__(FsError)
            FsError.__init__(res[-1], __import__(
                "tpu3fs.utils.result", fromlist=["Status"]).Status(
                    Code.META_NO_PERMISSION, "nope"))
            return res

        fab.meta.batch_create = failing
        try:
            with pytest.raises(FsError):
                c.batch_put([("ok", b"x"), ("bad", b"y")])
        finally:
            fab.meta.batch_create = real_batch_create
        # no leaked write sessions: a fresh put on the same key succeeds
        c.put("ok", b"z")
        assert c.get("ok") == b"z"


# -- over RPC the batched stat is ONE round trip (MetaSerde 30) -------------

META_SID, STAT, BATCH_STAT_BY_PATH = 4, 2, 30


def _meta_calls(mc, record=lambda addr, method_id, req: method_id):
    """What ``record`` makes (the method id) of every MetaSerde RPC the
    client sends from now on."""
    seen = []
    real = mc._client.call

    def spy(addr, service_id, method_id, req, rsp_type, *a, **kw):
        if service_id == META_SID:
            seen.append(record(addr, method_id, req))
        return real(addr, service_id, method_id, req, rsp_type, *a, **kw)

    mc._client.call = spy
    return seen


@pytest.fixture
def rpc_cache():
    """A KVCacheClient whose meta is a MetaRpcClient over a real
    RpcServer bound to the fabric's store (the storage side stays
    in-fabric: the count is of meta round trips)."""
    from tpu3fs.rpc.net import RpcServer
    from tpu3fs.rpc.services import MetaRpcClient, bind_meta_service

    fab = Fabric(SystemSetupConfig(num_storage_nodes=2, num_chains=4,
                                   num_replicas=2, chunk_size=4096))
    server = RpcServer()
    bind_meta_service(server, fab.meta)
    server.start()
    mc = MetaRpcClient([server.address], client_id="kv-rpc")
    yield mc, KVCacheClient(mc, fab.file_client())
    server.stop()
    fab.close()


class TestBatchedStatOverRpc:
    BT = 4

    def _pages(self, n):
        return [np.full((2, 2, self.BT, 8), i, dtype=np.float16)
                for i in range(n)]

    @pytest.mark.parametrize("nblocks", [1, 5, 22])
    def test_load_is_two_batched_stats_and_no_stat(self, rpc_cache, nblocks):
        mc, base = rpc_cache
        store = PrefixBlockStore(base, block_tokens=self.BT)
        toks = list(range(nblocks * self.BT))
        pages = self._pages(nblocks)
        assert store.append_blocks(toks, pages) == nblocks
        seen = _meta_calls(mc)
        m = store.match_prefix(toks)
        out = store.get_blocks(toks, count=m.blocks)
        assert m.blocks == nblocks
        for got, want in zip(out, pages):
            np.testing.assert_array_equal(got, want)
        assert seen.count(BATCH_STAT_BY_PATH) == 2
        assert seen.count(STAT) == 0

    def test_append_probe_is_one_batched_stat(self, rpc_cache):
        mc, base = rpc_cache
        store = PrefixBlockStore(base, block_tokens=self.BT)
        toks = list(range(6 * self.BT))
        store.append_blocks(toks[:3 * self.BT], self._pages(3))
        seen = _meta_calls(mc)
        # three blocks are there already: the probe finds them in one call
        assert store.append_blocks(toks, self._pages(6)) == 3
        assert seen.count(BATCH_STAT_BY_PATH) == 1
        assert seen.count(STAT) == 0

    def test_hole_and_miss_read_as_before(self, rpc_cache):
        mc, base = rpc_cache
        store = PrefixBlockStore(base, block_tokens=self.BT)
        toks = list(range(5 * self.BT))
        store.append_blocks(toks, self._pages(5))
        base.remove(store.block_keys(toks)[2])
        seen = _meta_calls(mc)
        assert store.match_prefix(toks).blocks == 2
        out = store.get_blocks(toks)
        assert [o is None for o in out] == [False, False, True, False, False]
        assert base.batch_contains(["never/put"]) == [False]
        assert seen.count(BATCH_STAT_BY_PATH) == 3
        assert seen.count(STAT) == 0

    def test_unreachable_meta_raises_instead_of_missing(self, rpc_cache):
        from tpu3fs.rpc.services import MetaRpcClient

        _, base = rpc_cache
        dead = KVCacheClient(MetaRpcClient([("127.0.0.1", 1)]), base._fio)
        with pytest.raises(FsError) as ei:
            dead.batch_contains(["a", "b"])
        assert ei.value.code == Code.RPC_CONNECT_FAILED

    def test_routed_client_makes_one_call_a_partition(self):
        """MetaRpcClient(mgmtd=...) over two ShardedMetaStore servers that
        own half the partitions each: the kvcache shard directories of one
        probe lie in several partitions; one batchStatByPath goes to each
        partition's owner, and the answers merge in request order."""
        from types import SimpleNamespace

        from tpu3fs.kv import MemKVEngine
        from tpu3fs.kvcache.layout import shard_path
        from tpu3fs.meta.store import ChainAllocator
        from tpu3fs.metashard import ShardedMetaStore, partition_of_path
        from tpu3fs.rpc.net import RpcServer
        from tpu3fs.rpc.services import MetaRpcClient, bind_meta_service

        nparts = 4
        eng = MemKVEngine()
        servers, owner = [], {}
        for half in (0, 1):
            owned = {p for p in range(nparts) if p % 2 == half}
            st = ShardedMetaStore(eng, ChainAllocator(1, [901, 902]),
                                  nparts=nparts,
                                  owner_view=lambda v=owned: v)
            srv = RpcServer()
            bind_meta_service(srv, st)
            srv.start()
            servers.append(srv)
            owner.update({p: srv.address for p in owned})
        try:
            table = SimpleNamespace(
                meta_owner=lambda pid: SimpleNamespace(
                    host=owner[pid][0], port=owner[pid][1]))
            mgmtd = SimpleNamespace(routing=lambda: table,
                                    invalidate_routing=lambda: None,
                                    refresh_routing=lambda: None)
            mc = MetaRpcClient([s.address for s in servers], mgmtd=mgmtd,
                               nparts=nparts)
            keys = [f"blk{i}" for i in range(24)]
            paths = [shard_path("/kvcache", k) for k in keys]
            pids = [partition_of_path(p, nparts) for p in paths]
            assert len(set(pids)) >= 2
            mc.batch_mkdirs(sorted({p.rsplit("/", 1)[0] for p in paths}))
            made = {}
            for i, p in enumerate(paths):
                if i % 3:       # every third key is never put
                    made[p] = mc.create(p).inode.id
            sent = _meta_calls(
                mc, lambda addr, mid, req: (addr, mid, list(req.paths)))
            got = mc.batch_stat_by_path(paths)
            assert [None if g is None else g.id for g in got] == \
                [made.get(p) for p in paths]
            # one call a partition, each to its owner, each carrying
            # exactly that partition's paths in request order
            assert sorted(m for _, m, _ in sent) == \
                [BATCH_STAT_BY_PATH] * len(set(pids))
            for addr, _, sub in sent:
                (pid,) = {partition_of_path(p, nparts) for p in sub}
                assert addr == owner[pid]
                assert sub == [p for p, q in zip(paths, pids) if q == pid]
        finally:
            for srv in servers:
                srv.stop()
