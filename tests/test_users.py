"""UserStore / AclCache / token-authenticated meta RPC tests
(ref src/core/user/UserStore.cc, UserToken.cc, src/meta/components/
AclCache.h, and the MetaSerde authenticate method)."""

import pytest

from tpu3fs.core.user import AclCache, UserStore
from tpu3fs.fabric.fabric import Fabric, FabricClock
from tpu3fs.kv import MemKVEngine
from tpu3fs.meta.store import ChainAllocator, MetaStore
from tpu3fs.rpc.net import RpcClient, RpcServer
from tpu3fs.rpc.services import MetaRpcClient, bind_meta_service
from tpu3fs.utils.result import Code, FsError


class TestUserStore:
    @pytest.fixture
    def store(self):
        return UserStore(MemKVEngine())

    def test_add_get_list_remove(self, store):
        a = store.add_user(1000, "alice", gid=100)
        b = store.add_user(2000, "bob", admin=True)
        assert store.get_user(1000).name == "alice"
        assert {u.uid for u in store.list_users()} == {1000, 2000}
        assert a.token != b.token and len(a.token) == 32
        assert store.remove_user(1000)
        assert store.get_user(1000) is None
        assert not store.remove_user(1000)

    def test_duplicate_uid_rejected(self, store):
        store.add_user(1, "x")
        with pytest.raises(FsError) as ei:
            store.add_user(1, "y")
        assert ei.value.code == Code.META_EXISTS

    def test_authenticate(self, store):
        rec = store.add_user(1000, "alice", gid=100, groups=[5, 6])
        got = store.authenticate(rec.token)
        assert (got.uid, got.gid, got.groups) == (1000, 100, [5, 6])
        user = got.as_user()
        assert user.uid == 1000 and user.groups == (5, 6)
        with pytest.raises(FsError) as ei:
            store.authenticate("bogus")
        assert ei.value.code == Code.META_NO_PERMISSION
        with pytest.raises(FsError):
            store.authenticate("")

    def test_rotate_token(self, store):
        rec = store.add_user(1000, "alice")
        old = rec.token
        new = store.rotate_token(1000)
        assert new != old
        assert store.authenticate(new).uid == 1000
        with pytest.raises(FsError):
            store.authenticate(old)

    def test_acl_cache_ttl_and_rotation(self, store):
        clock = FabricClock(100.0)
        cache = AclCache(store, ttl_s=5.0, clock=clock)
        rec = store.add_user(1000, "alice")
        assert cache.authenticate(rec.token).uid == 1000
        new = store.rotate_token(1000)
        # old token still cached inside the TTL window
        assert cache.authenticate(rec.token).uid == 1000
        clock.advance(6.0)
        with pytest.raises(FsError):
            cache.authenticate(rec.token)  # expired -> store says invalid
        assert cache.authenticate(new).uid == 1000

    def test_groups_grant_group_perm(self, store):
        from tpu3fs.meta.store import User
        from tpu3fs.meta.types import Acl, PERM_W

        acl = Acl(uid=1, gid=55, perm=0o670)
        member = User(uid=2, gid=9, groups=(55,))
        outsider = User(uid=2, gid=9)
        assert acl.check_user(member, PERM_W)
        assert not acl.check_user(outsider, PERM_W)
        assert acl.check_user(User(uid=3, gid=3, root=True), PERM_W)


class TestAuthenticatedMetaRpc:
    @pytest.fixture
    def cluster(self):
        engine = MemKVEngine()
        users = UserStore(engine)
        meta = MetaStore(engine, ChainAllocator(1, [101, 102]))
        server = RpcServer()
        bind_meta_service(server, meta, user_store=users, acl_ttl_s=0.0)
        server.start()
        yield server, users, meta
        server.stop()

    def test_token_identity_enforced(self, cluster):
        server, users, meta = cluster
        alice = users.add_user(1000, "alice", gid=100)
        meta.mkdirs("/home", perm=0o777)
        mc = MetaRpcClient([server.address], token=alice.token)
        rsp = mc.create("/home/af")
        # identity comes from the token, not anything the client claims
        assert rsp.inode.acl.uid == 1000 and rsp.inode.acl.gid == 100
        assert mc.authenticate().uid == 1000

    def test_bad_or_missing_token_rejected(self, cluster):
        server, users, _ = cluster
        no_token = MetaRpcClient([server.address])
        with pytest.raises(FsError) as ei:
            no_token.stat("/")
        assert ei.value.code == Code.META_NO_PERMISSION
        bad = MetaRpcClient([server.address], token="ffff" * 8)
        with pytest.raises(FsError) as ei:
            bad.stat("/")
        assert ei.value.code == Code.META_NO_PERMISSION

    def test_permissions_apply_to_token_user(self, cluster):
        server, users, meta = cluster
        alice = users.add_user(1000, "alice")
        meta.mkdirs("/private", perm=0o700)  # root-owned, no group/other
        mc = MetaRpcClient([server.address], token=alice.token)
        with pytest.raises(FsError) as ei:
            mc.create("/private/forbidden")
        assert ei.value.code == Code.META_NO_PERMISSION
        # a root-flagged user bypasses
        boss = users.add_user(9999, "boss", root=True)
        mb = MetaRpcClient([server.address], token=boss.token)
        assert mb.create("/private/ok").inode.is_file()

    def test_unauthenticated_mode_still_trusts_requests(self):
        meta = MetaStore(MemKVEngine(), ChainAllocator(1, [101]))
        server = RpcServer()
        bind_meta_service(server, meta)  # no user store: dev mode
        server.start()
        try:
            mc = MetaRpcClient([server.address])
            assert mc.mkdirs("/x").is_dir()
        finally:
            server.stop()


class TestCliUserCommands:
    def test_user_lifecycle_via_cli(self):
        from tpu3fs.cli import AdminCli

        fab = Fabric()
        cli = AdminCli(fab)
        out = cli.run("user-add 1000 alice --gid 100")
        assert "token=" in out
        token = out.split("token=")[1].strip()
        assert "alice" in cli.run("user-list")
        out2 = cli.run("user-rotate-token 1000")
        assert token not in out2 and "new token:" in out2
        assert cli.run("user-remove 1000") == "removed"
        assert cli.run("user-list") == "(no users)"


class TestAuthGateRegressions:
    @pytest.fixture
    def cluster(self):
        engine = MemKVEngine()
        users = UserStore(engine)
        meta = MetaStore(engine, ChainAllocator(1, [101, 102]))
        server = RpcServer()
        bind_meta_service(server, meta, user_store=users, acl_ttl_s=0.0)
        server.start()
        yield server, users, meta
        server.stop()

    def test_session_ops_require_token(self, cluster):
        """statFs/sync/close/pruneSession/batchStat must not bypass auth."""
        server, users, meta = cluster
        from tpu3fs.meta.store import OpenFlags

        res = meta.create("/victim", flags=OpenFlags.WRITE,
                          client_id="victim-client")
        anon = MetaRpcClient([server.address])
        for call in (
            lambda: anon.stat_fs(),
            lambda: anon.sync(res.inode.id),
            lambda: anon.close(res.inode.id, res.session_id),
            lambda: anon.prune_session("victim-client"),
            lambda: anon.batch_stat([res.inode.id]),
        ):
            with pytest.raises(FsError) as ei:
                call()
            assert ei.value.code == Code.META_NO_PERMISSION
        # the victim's session is intact
        assert meta.list_sessions(res.inode.id)
        # with a token the same ops work
        rec = users.add_user(7, "svc", root=True)
        mc = MetaRpcClient([server.address], token=rec.token)
        assert mc.stat_fs() is not None
        assert mc.batch_stat([res.inode.id])[0].id == res.inode.id

    def test_session_ops_authorize_not_just_authenticate(self, cluster):
        """A VALID non-root token must still be denied on other users' state:
        prune_session needs admin, close/sync need PERM_W on the inode,
        batch_stat masks unreadable inodes (a round-1 review's high finding)."""
        server, users, meta = cluster
        from tpu3fs.meta.store import OpenFlags, User

        victim = users.add_user(1000, "victim")
        res = meta.create("/secret", User(1000, 1000), perm=0o600,
                          flags=OpenFlags.WRITE, client_id="victim-client")
        mallory = users.add_user(2000, "mallory")
        mc = MetaRpcClient([server.address], token=mallory.token)
        # cannot prune another client's write sessions
        with pytest.raises(FsError) as ei:
            mc.prune_session("victim-client")
        assert ei.value.code == Code.META_NO_PERMISSION
        assert meta.list_sessions(res.inode.id)
        # cannot settle length/mtime on a file it cannot write (even with
        # the empty-session-id shortcut)
        with pytest.raises(FsError) as ei:
            mc.close(res.inode.id, "", length_hint=12345)
        assert ei.value.code == Code.META_NO_PERMISSION
        with pytest.raises(FsError) as ei:
            mc.sync(res.inode.id, length_hint=12345)
        assert ei.value.code == Code.META_NO_PERMISSION
        assert meta.stat("/secret").length == 0
        # batch_stat masks inodes without read permission
        assert mc.batch_stat([res.inode.id]) == [None]
        # an admin (non-root) token may prune; the owner may close
        admin = users.add_user(3000, "ops", admin=True)
        ma = MetaRpcClient([server.address], token=admin.token)
        assert ma.prune_session("victim-client") == 1
        mv = MetaRpcClient([server.address], token=victim.token)
        assert mv.batch_stat([res.inode.id])[0].id == res.inode.id

    def test_close_idempotency_cache_is_identity_scoped(self, cluster):
        """Replaying another client's (client_id, request_id) with a
        different token must NOT return the cached inode (code-review r2)."""
        server, users, meta = cluster
        from tpu3fs.meta.store import OpenFlags, User

        victim = users.add_user(1000, "victim")
        res = meta.create("/secret2", User(1000, 1000), perm=0o600,
                          flags=OpenFlags.WRITE, client_id="vc")
        mv = MetaRpcClient([server.address], token=victim.token,
                           client_id="vc")
        closed = mv.close(res.inode.id, res.session_id, request_id="rq-9",
                          length_hint=77)
        assert closed.length == 77
        # victim's own retry hits the cache (idempotent)
        again = mv.close(res.inode.id, res.session_id, request_id="rq-9")
        assert again.length == 77
        # mallory replays the exact same identifiers with her own token
        mallory = users.add_user(2000, "mallory")
        mm = MetaRpcClient([server.address], token=mallory.token,
                           client_id="vc")
        with pytest.raises(FsError) as ei:
            mm.close(res.inode.id, "", request_id="rq-9", length_hint=1)
        assert ei.value.code == Code.META_NO_PERMISSION

    def test_chmod_between_open_and_close_does_not_wedge_session(self, cluster):
        """close/sync authorize by session ownership, not the live ACL:
        a chmod 0o400 after open must not leak the write session."""
        server, users, meta = cluster
        alice = users.add_user(1000, "alice")
        meta.mkdirs("/w", perm=0o777)
        mc = MetaRpcClient([server.address], token=alice.token, client_id="ac")
        from tpu3fs.meta.store import OpenFlags

        rsp = mc.create("/w/f", flags=OpenFlags.WRITE)
        # root chmods the file read-only underneath the open session
        meta.set_attr("/w/f", perm=0o400)
        # alice's fsync and close still settle the length
        assert mc.sync(rsp.inode.id, length_hint=5).length == 5
        closed = mc.close(rsp.inode.id, rsp.session_id, length_hint=9)
        assert closed.length == 9
        assert not meta.list_sessions(rsp.inode.id)
        # but another non-owner still cannot close someone else's session
        bob = users.add_user(3000, "bob")
        mb = MetaRpcClient([server.address], token=bob.token)
        rsp2 = mc.create("/w/g", flags=OpenFlags.WRITE)
        with pytest.raises(FsError) as ei:
            mb.close(rsp2.inode.id, rsp2.session_id)
        assert ei.value.code == Code.META_NO_PERMISSION

    def test_root_flag_grants_setattr_and_chown(self, cluster):
        server, users, meta = cluster
        meta.mkdirs("/private", perm=0o700)
        boss = users.add_user(9999, "boss", root=True)
        mb = MetaRpcClient([server.address], token=boss.token)
        mb.create("/private/f")
        got = mb.set_attr("/private/f", perm=0o640, uid=1234, gid=55)
        assert (got.acl.perm, got.acl.uid, got.acl.gid) == (0o640, 1234, 55)

    def test_cli_user_add_flag_not_taken_as_name(self):
        from tpu3fs.cli import AdminCli

        cli = AdminCli(Fabric())
        out = cli.run("user-add 1000 --admin")
        assert "user1000" in out and "--admin" not in out.split("token=")[0].split("(")[1]
        rec = [u for u in __import__("tpu3fs.core.user", fromlist=["UserStore"]).UserStore(cli.fab.kv).list_users()][0]
        assert rec.name == "user1000" and rec.admin
