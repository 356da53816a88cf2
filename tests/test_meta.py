"""Per-op metadata tests on MemKV (mirrors tests/meta/store/ops of the ref)."""

import threading

import pytest

from tpu3fs.kv import MemKVEngine
from tpu3fs.meta import MetaStore, OpenFlags
from tpu3fs.meta.store import ChainAllocator, User
from tpu3fs.meta.types import InodeType
from tpu3fs.utils.result import Code, FsError


@pytest.fixture(params=["mem", "remote"])
def store(request):
    """The whole per-op suite runs against BOTH the in-memory engine and the
    network KV service — the reference runs its meta suite against MemKV and
    real FDB the same way (tests/common/kv/mem vs tests/common/kv/fdb)."""
    if request.param == "mem":
        yield MetaStore(MemKVEngine(), ChainAllocator(1, [101, 102, 103, 104]))
        return
    from tpu3fs.kv.remote import RemoteKVEngine
    from tpu3fs.kv.service import KvService, bind_kv_service
    from tpu3fs.rpc.net import RpcServer

    server = RpcServer()
    bind_kv_service(server, KvService())
    server.start()
    try:
        yield MetaStore(RemoteKVEngine(server.address),
                        ChainAllocator(1, [101, 102, 103, 104]))
    finally:
        server.stop()


ALICE = User(uid=1000, gid=100)
BOB = User(uid=2000, gid=200)


def code_of(exc_info):
    return exc_info.value.code


class TestCreateStat:
    def test_create_and_stat(self, store):
        res = store.create("/f1", stripe=2)
        assert res.inode.is_file()
        assert len(res.inode.layout.chains) == 2
        got = store.stat("/f1")
        assert got.id == res.inode.id

    def test_create_missing_parent(self, store):
        with pytest.raises(FsError) as ei:
            store.create("/nodir/f")
        assert code_of(ei) == Code.META_NOT_FOUND

    def test_create_excl_conflict(self, store):
        store.create("/f")
        with pytest.raises(FsError) as ei:
            store.create("/f", flags=OpenFlags.EXCL)
        assert code_of(ei) == Code.META_EXISTS

    def test_create_open_existing(self, store):
        a = store.create("/f")
        b = store.create("/f")  # no EXCL: opens
        assert a.inode.id == b.inode.id

    def test_stat_missing(self, store):
        with pytest.raises(FsError) as ei:
            store.stat("/ghost")
        assert code_of(ei) == Code.META_NOT_FOUND

    def test_relative_path_rejected(self, store):
        with pytest.raises(FsError) as ei:
            store.stat("oops")
        assert code_of(ei) == Code.META_INVALID_PATH

    def test_chains_round_robin(self, store):
        c1 = store.create("/a", stripe=2).inode.layout.chains
        c2 = store.create("/b", stripe=2).inode.layout.chains
        assert c1 != c2  # cursor advanced

    def test_batch_stat(self, store):
        a = store.create("/a").inode
        got = store.batch_stat([a.id, 99999])
        assert got[0].id == a.id and got[1] is None

    def test_batch_stat_by_path(self, store):
        store.create("/a")
        got = store.batch_stat_by_path(["/a", "/nope"])
        assert got[0] is not None and got[1] is None


class TestMkdirsList:
    def test_mkdirs_recursive(self, store):
        d = store.mkdirs("/a/b/c", recursive=True)
        assert d.is_dir()
        assert store.stat("/a/b").is_dir()

    def test_mkdirs_nonrecursive_missing(self, store):
        with pytest.raises(FsError) as ei:
            store.mkdirs("/x/y")
        assert code_of(ei) == Code.META_NOT_FOUND

    def test_mkdirs_exists(self, store):
        store.mkdirs("/d")
        with pytest.raises(FsError) as ei:
            store.mkdirs("/d")
        assert code_of(ei) == Code.META_EXISTS

    def test_list(self, store):
        store.mkdirs("/d")
        store.create("/d/f1")
        store.create("/d/f2")
        store.mkdirs("/d/sub")
        names = [e.name for e in store.list_dir("/d")]
        assert names == ["f1", "f2", "sub"]

    def test_list_prefix_and_limit(self, store):
        store.mkdirs("/d")
        for n in ("aa", "ab", "ba"):
            store.create(f"/d/{n}")
        assert [e.name for e in store.list_dir("/d", prefix="a")] == ["aa", "ab"]
        assert len(store.list_dir("/d", limit=2)) == 2

    def test_list_file_fails(self, store):
        store.create("/f")
        with pytest.raises(FsError) as ei:
            store.list_dir("/f")
        assert code_of(ei) == Code.META_NOT_DIRECTORY


class TestOpenCloseSessions:
    def test_write_open_creates_session(self, store):
        res = store.create("/f", flags=OpenFlags.WRITE, client_id="c1")
        assert res.session_id
        sessions = store.list_sessions(res.inode.id)
        assert len(sessions) == 1 and sessions[0].client_id == "c1"

    def test_close_settles_length_and_drops_session(self, store):
        res = store.create("/f", flags=OpenFlags.WRITE, client_id="c1")
        inode = store.close(res.inode.id, res.session_id, length_hint=12345)
        assert inode.length == 12345
        assert store.list_sessions(res.inode.id) == []

    def test_close_idempotent_via_request_id(self, store):
        res = store.create("/f", flags=OpenFlags.WRITE, client_id="c1")
        store.close(res.inode.id, res.session_id, length_hint=10,
                    client_id="c1", request_id="r1")
        # retry with the same request id succeeds despite the session being gone
        inode = store.close(res.inode.id, res.session_id, length_hint=10,
                            client_id="c1", request_id="r1")
        assert inode.length == 10

    def test_close_unknown_session(self, store):
        res = store.create("/f")
        with pytest.raises(FsError) as ei:
            store.close(res.inode.id, "nope")
        assert code_of(ei) == Code.META_NO_SESSION

    def test_trunc_resets_length(self, store):
        res = store.create("/f", flags=OpenFlags.WRITE, client_id="c")
        store.close(res.inode.id, res.session_id, length_hint=100)
        r2 = store.open("/f", flags=OpenFlags.WRITE | OpenFlags.TRUNC, client_id="c")
        assert store.stat("/f").length == 0
        assert r2.session_id

    def test_prune_session(self, store):
        store.create("/f1", flags=OpenFlags.WRITE, client_id="dead")
        store.create("/f2", flags=OpenFlags.WRITE, client_id="dead")
        store.create("/f3", flags=OpenFlags.WRITE, client_id="alive")
        assert store.prune_session("dead") == 2
        assert len(store.list_sessions()) == 1

    def test_sync_monotonic_hint(self, store):
        res = store.create("/f")
        store.sync(res.inode.id, length_hint=100)
        store.sync(res.inode.id, length_hint=50)  # stale hint ignored
        assert store.stat("/f").length == 100

    def test_file_length_hook_wins(self):
        store = MetaStore(
            MemKVEngine(), ChainAllocator(1, [1]),
            file_length_hook=lambda inodes: [777] * len(inodes),
        )
        res = store.create("/f", flags=OpenFlags.WRITE, client_id="c")
        inode = store.close(res.inode.id, res.session_id, length_hint=5)
        assert inode.length == 777
        assert store.sync(res.inode.id, length_hint=9).length == 777


class TestRemoveGc:
    def test_remove_file_goes_to_gc(self, store):
        res = store.create("/f")
        store.remove("/f")
        with pytest.raises(FsError):
            store.stat("/f")
        gc = store.gc_scan()
        assert [i.id for i in gc] == [res.inode.id]
        store.gc_finish(res.inode.id)
        assert store.gc_scan() == []

    def test_remove_nonempty_dir(self, store):
        store.mkdirs("/d")
        store.create("/d/f")
        with pytest.raises(FsError) as ei:
            store.remove("/d")
        assert code_of(ei) == Code.META_NOT_EMPTY

    def test_remove_recursive(self, store):
        store.mkdirs("/d/sub", recursive=True)
        store.create("/d/sub/f")
        store.remove("/d", recursive=True)
        with pytest.raises(FsError):
            store.stat("/d")
        assert len(store.gc_scan()) == 1  # the file under /d/sub

    def test_remove_idempotent(self, store):
        store.create("/f")
        store.remove("/f", client_id="c", request_id="rq")
        store.remove("/f", client_id="c", request_id="rq")  # retry: ok
        with pytest.raises(FsError):
            store.remove("/f", client_id="c", request_id="rq2")

    def test_hardlink_remove_keeps_inode(self, store):
        store.create("/f")
        store.hard_link("/f", "/g")
        store.remove("/f")
        assert store.stat("/g").nlink == 1
        assert store.gc_scan() == []  # still linked
        store.remove("/g")
        assert len(store.gc_scan()) == 1


class TestRename:
    def test_rename_file(self, store):
        a = store.create("/a").inode
        store.rename("/a", "/b")
        assert store.stat("/b").id == a.id
        with pytest.raises(FsError):
            store.stat("/a")

    def test_rename_replaces_existing_file(self, store):
        store.create("/a")
        old = store.create("/b").inode
        store.rename("/a", "/b")
        assert [i.id for i in store.gc_scan()] == [old.id]

    def test_rename_dir_updates_parent(self, store):
        store.mkdirs("/d1/sub", recursive=True)
        store.mkdirs("/d2")
        store.rename("/d1/sub", "/d2/sub")
        assert store.stat("/d2/sub").is_dir()
        assert store.get_real_path("/d2/sub") == "/d2/sub"

    def test_rename_loop_detected(self, store):
        store.mkdirs("/a/b", recursive=True)
        with pytest.raises(FsError) as ei:
            store.rename("/a", "/a/b/c")
        assert code_of(ei) == Code.META_LOOP

    def test_rename_to_self_noop(self, store):
        store.create("/a")
        store.rename("/a", "/a")
        assert store.stat("/a")


class TestSymlinks:
    def test_symlink_resolution(self, store):
        store.mkdirs("/real")
        store.create("/real/f")
        store.symlink("/link", "/real")
        assert store.stat("/link/f").is_file()

    def test_symlink_nofollow(self, store):
        store.create("/t")
        store.symlink("/l", "/t")
        assert store.stat("/l", follow=False).is_symlink()
        assert store.stat("/l").is_file()

    def test_relative_symlink(self, store):
        store.mkdirs("/d")
        store.create("/d/f")
        store.symlink("/d/l", "f")
        assert store.stat("/d/l").is_file()

    def test_symlink_loop(self, store):
        store.symlink("/l1", "/l2")
        store.symlink("/l2", "/l1")
        with pytest.raises(FsError) as ei:
            store.stat("/l1")
        assert code_of(ei) == Code.META_TOO_MANY_SYMLINKS


class TestPermissions:
    def test_non_owner_cannot_write_dir(self, store):
        store.mkdirs("/home", perm=0o755)  # owned by root
        with pytest.raises(FsError) as ei:
            store.create("/home/f", user=ALICE)
        assert code_of(ei) == Code.META_NO_PERMISSION

    def test_owner_can_write(self, store):
        store.mkdirs("/home", perm=0o777)
        store.mkdirs("/home/alice", user=ALICE, perm=0o700)
        store.create("/home/alice/f", user=ALICE)
        with pytest.raises(FsError):
            store.stat("/home/alice/f", user=BOB)  # no X on alice's dir

    def test_chmod_chown(self, store):
        store.create("/f")
        store.set_attr("/f", perm=0o600, uid=1000, gid=100)
        inode = store.stat("/f")
        assert inode.acl.perm == 0o600 and inode.acl.uid == 1000
        with pytest.raises(FsError):
            store.set_attr("/f", user=BOB, perm=0o777)

    def test_lock_directory(self, store):
        store.mkdirs("/d", perm=0o777)
        store.lock_directory("/d", "holder1")
        with pytest.raises(FsError) as ei:
            store.create("/d/f", user=ALICE)
        assert code_of(ei) == Code.META_NO_PERMISSION
        store.lock_directory("/d", "")  # unlock
        store.create("/d/f", user=ALICE)


class TestMisc:
    def test_truncate(self, store):
        store.create("/f")
        store.truncate("/f", 4096)
        assert store.stat("/f").length == 4096

    def test_get_real_path(self, store):
        store.mkdirs("/a/b", recursive=True)
        store.create("/a/b/f")
        assert store.get_real_path("/a/b/f") == "/a/b/f"
        store.symlink("/l", "/a/b")
        assert store.get_real_path("/l/f") == "/a/b/f"

    def test_stat_fs(self, store):
        r = store.create("/f", flags=OpenFlags.WRITE, client_id="c")
        store.close(r.inode.id, r.session_id, length_hint=1000)
        fs = store.stat_fs()
        assert fs.files == 1 and fs.used == 1000

    def test_concurrent_creates_unique_ids(self, store):
        ids = []
        lock = threading.Lock()

        def make(i):
            inode = store.create(f"/f{i}").inode
            with lock:
                ids.append(inode.id)

        threads = [threading.Thread(target=make, args=(i,)) for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(set(ids)) == 16


class TestBatchClose:
    """Batched length settles: one KV transaction per 64 closes instead of
    one per file (round-3 verdict ask #10; ref BatchOperation.cc:750)."""

    def _mk(self):
        from tpu3fs.kv.mem import MemKVEngine
        from tpu3fs.meta.store import BatchCloseItem, MetaStore, OpenFlags

        eng = MemKVEngine()
        store = MetaStore(eng)
        return eng, store, BatchCloseItem, OpenFlags

    def test_close_heavy_workload_txn_count(self):
        eng, store, Item, OpenFlags = self._mk()
        items = []
        for i in range(256):
            res = store.create(f"/bf{i}", flags=OpenFlags.WRITE,
                               client_id="c1")
            items.append(Item(inode_id=res.inode.id,
                              session_id=res.session_id,
                              length_hint=100 + i, wrote=1))
        calls = {"n": 0}
        orig = eng.transaction

        def counting():
            calls["n"] += 1
            return orig()

        eng.transaction = counting
        results = store.batch_close(items)
        assert calls["n"] <= 256 // 64 + 1   # O(n/64), not O(n)
        assert all(not isinstance(r, Exception) for r in results)
        for i in range(0, 256, 37):
            assert store.stat(f"/bf{i}").length == 100 + i

    def test_per_item_failures_dont_poison_batchmates(self):
        eng, store, Item, OpenFlags = self._mk()
        good = store.create("/ok", flags=OpenFlags.WRITE, client_id="c1")
        items = [
            Item(inode_id=good.inode.id, session_id=good.session_id,
                 length_hint=7, wrote=1),
            Item(inode_id=999999, session_id="nope", length_hint=1),
        ]
        res = store.batch_close(items)
        from tpu3fs.utils.result import Code, FsError

        assert not isinstance(res[0], FsError)
        assert isinstance(res[1], FsError)
        assert res[1].code in (Code.META_NOT_FOUND, Code.META_NO_SESSION)
        assert store.stat("/ok").length == 7

    def test_batch_close_over_rpc(self):
        from tpu3fs.fabric.fabric import Fabric, SystemSetupConfig
        from tpu3fs.meta.store import BatchCloseItem, OpenFlags

        fab = Fabric(SystemSetupConfig(num_storage_nodes=2, num_chains=1,
                                       chunk_size=4096))
        items = []
        for i in range(8):
            res = fab.meta.create(f"/r{i}", flags=OpenFlags.WRITE,
                                  client_id="rc")
            items.append(BatchCloseItem(inode_id=res.inode.id,
                                        session_id=res.session_id,
                                        length_hint=10 * i, wrote=1))
        outs = fab.meta.batch_close(items)
        assert all(not isinstance(o, Exception) for o in outs)
        # the fabric meta settles lengths from STORAGE (queryLastChunk
        # hook), so the hint is rightly ignored; the sessions must be gone
        from tpu3fs.utils.result import FsError

        import pytest as _pytest
        with _pytest.raises(FsError):
            fab.meta.close(items[5].inode_id, items[5].session_id)


class TestLengthHookAndTruncate:
    """What a batch pays storage for: ONE length-hook call a transaction
    chunk for the files that passed, and no truncate round for an inode
    the create itself made."""

    def _mk(self, fail=()):
        from tpu3fs.kv.mem import MemKVEngine
        from tpu3fs.utils.result import Status

        calls, truncs, fail = [], [], set(fail)

        def lengths(inodes):
            calls.append([ino.id for ino in inodes])
            return [FsError(Status(Code.TARGET_OFFLINE, "no shard"))
                    if ino.id in fail else 1000 + ino.id for ino in inodes]

        store = MetaStore(
            MemKVEngine(), ChainAllocator(1, [101]),
            file_length_hook=lengths,
            truncate_hook=lambda ino, ln: truncs.append((ino.id, ln)))
        return store, calls, truncs, fail

    def _open(self, store, n, prefix="/h"):
        from tpu3fs.meta.store import BatchCloseItem

        items = []
        for i in range(n):
            res = store.create(f"{prefix}{i}", flags=OpenFlags.WRITE,
                               client_id="c")
            items.append(BatchCloseItem(res.inode.id, res.session_id,
                                        client_id="c", wrote=1))
        return items

    def test_one_hook_call_a_chunk_with_all_passing_files(self):
        from tpu3fs.meta.store import BatchCloseItem

        store, calls, _, _ = self._mk()
        items = self._open(store, 70)
        items.insert(3, BatchCloseItem(999999, "nope"))       # fails a check
        out = store.batch_close(items)
        assert [len(c) for c in calls] == [63, 7]     # 64 + 7 less the bad
        assert calls[0] + calls[1] == [
            it.inode_id for it in items if it.inode_id != 999999]
        assert isinstance(out[3], FsError)
        assert all(o.length == 1000 + o.id for o in out
                   if not isinstance(o, FsError))
        assert store._closed_files._value == 70
        assert store.list_sessions() == []

    def test_a_failed_length_keeps_session_and_old_length(self):
        store, calls, _, fail = self._mk()
        items = self._open(store, 5)
        bad = items[2]
        store.sync(bad.inode_id)                 # old length: 1000 + id
        fail.add(bad.inode_id)
        out = store.batch_close(items)
        assert isinstance(out[2], FsError) \
            and out[2].code == Code.TARGET_OFFLINE
        assert [s.session_id for s in store.list_sessions()] == [
            bad.session_id]
        assert store.stat("/h2").length == 1000 + bad.inode_id
        for i in (0, 1, 3, 4):
            assert out[i].length == 1000 + items[i].inode_id
        # the item is whole: it closes once storage answers
        fail.clear()
        assert not isinstance(store.batch_close([bad])[0], FsError)
        assert store.list_sessions() == []

    def test_close_and_sync_raise_the_length_error(self):
        store, _, _, fail = self._mk()
        (it,) = self._open(store, 1)
        fail.add(it.inode_id)
        with pytest.raises(FsError) as ei:
            store.close(it.inode_id, it.session_id)
        assert code_of(ei) == Code.TARGET_OFFLINE
        with pytest.raises(FsError):
            store.sync(it.inode_id)
        assert len(store.list_sessions()) == 1
        fail.clear()
        assert store.close(it.inode_id, it.session_id).length \
            == 1000 + it.inode_id

    def test_a_replayed_request_makes_no_storage_call(self):
        store, calls, _, _ = self._mk()
        items = self._open(store, 3)
        for k, it in enumerate(items):
            it.request_id = f"r{k}"
        first = store.batch_close(items)
        assert len(calls) == 1
        again = store.batch_close(items)
        assert len(calls) == 1                       # answered from the cache
        assert [a.length for a in again] == [f.length for f in first]
        it = items[0]
        assert store.close(it.inode_id, it.session_id, client_id="c",
                           request_id="r0").length == first[0].length
        assert len(calls) == 1
        # a replay beside a fresh close: the hook sees the fresh file alone
        (fresh,) = self._open(store, 1, prefix="/g")
        store.batch_close([items[1], fresh])
        assert calls[-1] == [fresh.inode_id]

    def test_two_closes_of_one_file_share_the_inode(self):
        from tpu3fs.kv.mem import MemKVEngine
        from tpu3fs.meta.store import BatchCloseItem

        store = MetaStore(MemKVEngine())             # hints, no hook
        a = store.create("/two", flags=OpenFlags.WRITE, client_id="a")
        b = store.open("/two", flags=OpenFlags.WRITE, client_id="b")
        out = store.batch_close([
            BatchCloseItem(a.inode.id, a.session_id, 100, wrote=1),
            BatchCloseItem(a.inode.id, b.session_id, 50, wrote=1)])
        assert [o.length for o in out] == [100, 100]
        assert store.stat("/two").length == 100
        assert store.list_sessions() == []

    @pytest.mark.parametrize("batched", [False, True])
    def test_trunc_truncates_what_existed_not_what_it_made(self, batched):
        from tpu3fs.meta.store import BatchCreateItem

        store, _, truncs, _ = self._mk()
        flags = OpenFlags.WRITE | OpenFlags.CREATE | OpenFlags.TRUNC
        old = store.create("/old").inode
        if batched:
            out = store.batch_create([
                BatchCreateItem("/old", flags=flags, client_id="c"),
                BatchCreateItem("/new", flags=flags, client_id="c"),
                BatchCreateItem("/nodir/x", flags=flags)])
            assert isinstance(out[2], FsError)
            assert out[0].inode.id == old.id and out[1].session_id
        else:
            assert store.create("/old", flags=flags).inode.id == old.id
            store.create("/new", flags=flags)
        assert truncs == [(old.id, 0)]
        assert store._create_truncated._value == 1
        assert store._create_truncate_skipped._value == 1
        # a re-create over the path just made and open(TRUNC) keep theirs
        new = store.stat("/new")
        store.create("/new", flags=flags)
        store.open("/new", flags=OpenFlags.WRITE | OpenFlags.TRUNC)
        assert truncs[1:] == [(new.id, 0), (new.id, 0)]
        # without TRUNC nothing is sent or counted either way
        store.create("/plain", flags=OpenFlags.WRITE)
        assert len(truncs) == 3
        assert store._create_truncate_skipped._value == 1

    def test_a_retried_create_transaction_still_knows_what_it_made(self):
        """A KV conflict reruns the create; the attempt that commits says
        whether the inode is new."""
        from tpu3fs.meta.store import BatchCreateItem
        from tpu3fs.utils.result import Status

        store, _, truncs, _ = self._mk()
        flags = OpenFlags.WRITE | OpenFlags.CREATE | OpenFlags.TRUNC
        inner_create = store._create_in_txn
        seen = []

        def noting(txn, path, *a):
            res = inner_create(txn, path, *a)
            seen.append(res[0].inode.id)
            return res

        store._create_in_txn = noting
        eng, inner_txn, commits = store.engine, store.engine.transaction, []

        def transaction():
            txn = inner_txn()
            commit = txn.commit

            def flaky_commit():
                if len(seen) == 1 and not commits:     # the create's own
                    commits.append(1)
                    raise FsError(Status(Code.KV_CONFLICT, "injected"))
                return commit()

            txn.commit = flaky_commit
            return txn

        eng.transaction = transaction
        out = store.batch_create([BatchCreateItem("/r", flags=flags)])
        assert len(seen) == 2 and seen[0] != seen[1]
        assert out[0].inode.id == seen[1] and truncs == []
        assert store.stat("/r").id == seen[1]


class TestBatchSetAttr:
    """Batched time touch (the kvcache touch-on-get satellite): one
    transaction per chunk, by path or walk-free by inode id."""

    def test_touch_many_paths(self, store):
        ids = []
        for i in range(5):
            res = store.create(f"/t{i}")
            store.close(res.inode.id, res.session_id)
            ids.append(res.inode.id)
        out = store.batch_set_attr([f"/t{i}" for i in range(5)],
                                   mtime=1234.5, atime=77.0)
        assert [o.id for o in out] == ids
        for i in range(5):
            ino = store.stat(f"/t{i}")
            assert ino.mtime == 1234.5 and ino.atime == 77.0

    def test_touch_by_inode_id_skips_walks(self, store):
        res = store.create("/byid")
        store.close(res.inode.id, res.session_id)
        out = store.batch_set_attr(inode_ids=[res.inode.id, 999_999],
                                   mtime=42.0)
        assert out[0].id == res.inode.id
        assert isinstance(out[1], FsError)
        assert out[1].code == Code.META_NOT_FOUND
        assert store.stat("/byid").mtime == 42.0

    def test_per_item_failures_do_not_poison_batchmates(self, store):
        res = store.create("/ok")
        store.close(res.inode.id, res.session_id)
        out = store.batch_set_attr(["/missing", "/ok"], mtime=5.0)
        assert isinstance(out[0], FsError)
        assert out[0].code == Code.META_NOT_FOUND
        assert out[1].id == res.inode.id
        assert store.stat("/ok").mtime == 5.0

    def test_permission_enforced_per_item(self, store):
        store.mkdirs("/home", perm=0o777)
        store.create("/home/mine", ALICE)
        store.create("/home/theirs", BOB)
        out = store.batch_set_attr(["/home/mine", "/home/theirs"],
                                   ALICE, mtime=9.0)
        assert out[0].acl.uid == ALICE.uid
        assert isinstance(out[1], FsError)
        assert out[1].code == Code.META_NO_PERMISSION

    def test_paths_xor_inode_ids(self, store):
        with pytest.raises(FsError) as ei:
            store.batch_set_attr(["/x"], inode_ids=[1])
        assert code_of(ei) == Code.INVALID_ARG
        with pytest.raises(FsError) as ei:
            store.batch_set_attr()
        assert code_of(ei) == Code.INVALID_ARG

    def test_many_items_chunk_transactions(self, store):
        paths = []
        for i in range(70):  # crosses the txn_batch=64 boundary
            res = store.create(f"/m{i}")
            store.close(res.inode.id, res.session_id)
            paths.append(f"/m{i}")
        out = store.batch_set_attr(paths, mtime=7.0)
        assert all(not isinstance(o, FsError) for o in out)
        assert store.stat("/m69").mtime == 7.0


class TestBatchCreate:
    """Batched file creates: one KV transaction per 64 creates — the
    create fan-in behind kvcache batch_put and the ckpt archiver."""

    def _mk(self):
        from tpu3fs.kv.mem import MemKVEngine
        from tpu3fs.meta.store import BatchCreateItem, MetaStore

        eng = MemKVEngine()
        return eng, MetaStore(eng, ChainAllocator(1, [101, 102])), \
            BatchCreateItem

    def test_batch_create_txn_count_and_results(self):
        eng, store, Item = self._mk()
        n0 = getattr(eng, "txn_count", None)
        items = [Item(path=f"/f{i}", flags=OpenFlags.WRITE, client_id="c1")
                 for i in range(130)]
        results = store.batch_create(items)
        assert len(results) == 130
        for i, res in enumerate(results):
            assert not isinstance(res, FsError)
            assert res.session_id  # WRITE flag opened a session
            assert store.stat(f"/f{i}").id == res.inode.id
        if n0 is not None:
            assert eng.txn_count - n0 <= 4  # ceil(130/64) + slack

    def test_per_item_failures_do_not_poison_batch(self):
        _, store, Item = self._mk()
        store.create("/taken")
        results = store.batch_create([
            Item(path="/ok1", flags=OpenFlags.WRITE),
            Item(path="/nodir/x", flags=OpenFlags.WRITE),
            Item(path="/taken", flags=OpenFlags.EXCL),
            Item(path="/ok2", flags=OpenFlags.WRITE),
        ])
        assert not isinstance(results[0], FsError)
        assert isinstance(results[1], FsError) \
            and results[1].code == Code.META_NOT_FOUND
        assert isinstance(results[2], FsError) \
            and results[2].code == Code.META_EXISTS
        assert not isinstance(results[3], FsError)

    def test_explicit_layout_pins_chains(self):
        from tpu3fs.meta.types import Layout

        _, store, Item = self._mk()
        lay = Layout(table_id=1, chains=[999], chunk_size=4096, seed=3)
        res = store.batch_create([Item(path="/pinned", layout=lay)])[0]
        assert res.inode.layout.chains == [999]
        assert res.inode.layout.chunk_size == 4096
        # empty layout is a per-item error, not a raise
        bad = store.batch_create([Item(
            path="/bad", layout=Layout(table_id=1, chains=[],
                                       chunk_size=4096, seed=0))])[0]
        assert isinstance(bad, FsError) and bad.code == Code.META_BAD_LAYOUT

    def test_allocator_striping_matches_singletons(self):
        """Chain allocation order through batch_create is identical to N
        singleton creates (same allocator walk)."""
        _, a, Item = self._mk()
        _, b, _ = self._mk()
        batch = a.batch_create([Item(path=f"/s{i}") for i in range(6)])
        singles = [b.create(f"/s{i}") for i in range(6)]
        for x, y in zip(batch, singles):
            assert x.inode.layout.chains == y.inode.layout.chains
