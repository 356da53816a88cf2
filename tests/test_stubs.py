"""Stub factory + in-memory client double (tpu3fs/client/{stubs,inmem}.py
— the reference's src/stubs DI layer and StorageClientInMem.h test
double). The same consumer code must run unchanged against the inmem
double and a live socket cluster built by the factory."""

import pytest

from tpu3fs.client.inmem import StorageClientInMem
from tpu3fs.client.stubs import StubFactory
from tpu3fs.meta.types import Inode, InodeType, Layout
from tpu3fs.storage.types import ChunkId
from tpu3fs.utils.result import Code, FsError


class TestStorageClientInMem:
    def test_chunk_roundtrip_and_versions(self):
        c = StorageClientInMem()
        r = c.write_chunk(7, ChunkId(1, 0), 0, b"hello", chunk_size=4096)
        assert r.ok and r.commit_ver == 1
        r2 = c.write_chunk(7, ChunkId(1, 0), 5, b" world", chunk_size=4096)
        assert r2.commit_ver == 2
        got = c.read_chunk(7, ChunkId(1, 0))
        assert got.ok and got.data == b"hello world"
        assert c.read_chunk(7, ChunkId(9, 9)).code == Code.CHUNK_NOT_FOUND
        assert c.write_chunk(7, ChunkId(1, 1), 4090, b"xxxxxxxx",
                             chunk_size=4096).code == Code.INVALID_ARG

    def test_file_surface(self):
        c = StorageClientInMem()
        for i in range(3):
            c.write_chunk(5, ChunkId(42, i), 0, bytes([i]) * 100,
                          chunk_size=4096)
        assert c.query_last_chunk(5, 42) == (2, 100)
        assert c.truncate_file_chunks(5, 42, 1, 40) == 1
        assert c.query_last_chunk(5, 42) == (1, 40)
        assert c.remove_file_chunks(5, 42) == 2
        assert c.query_last_chunk(5, 42) == (-1, 0)
        assert c.space_info().chunk_count == 0

    def test_file_io_client_runs_on_the_double(self):
        """FileIoClient — a real consumer — moves bytes through the double
        exactly as it does through the fabric client (multi-chunk writes,
        ordered flush, length query)."""
        from tpu3fs.client.file_io import FileIoClient

        fio = FileIoClient(StorageClientInMem())
        layout = Layout(table_id=1, chains=[11, 12], chunk_size=1024)
        from tpu3fs.meta.types import Acl
        inode = Inode(id=77, type=InodeType.FILE, acl=Acl(), layout=layout)
        payload = bytes(range(256)) * 10  # 2560 bytes -> 3 chunks
        wrote = fio.write(inode, 0, payload)
        assert wrote == len(payload)
        assert fio.read(inode, 0, len(payload)) == payload
        assert fio.file_length(inode) >= len(payload)


@pytest.fixture
def socket_cluster():
    """Small live cluster; the factory must build working stubs for it."""
    from rpc_cluster import RpcCluster

    cluster = RpcCluster(replicas=2, chains=2, size=4096)
    yield cluster
    cluster.close()


class TestStubFactory:
    def test_inmem_stubs(self):
        stubs = StubFactory(transport="inmem")
        sc = stubs.storage_client()
        assert isinstance(sc, StorageClientInMem)
        meta = stubs.meta_client()
        res = meta.create("/f", client_id="t")
        assert meta.stat("/f").id == res.inode.id
        with pytest.raises(FsError):
            stubs.rpc_client()

    def test_unknown_transport_rejected(self):
        with pytest.raises(FsError):
            StubFactory(transport="quic")

    def test_tcp_stubs_against_live_cluster(self, socket_cluster):
        stubs = StubFactory(transport="tcp",
                            mgmtd_addr=socket_cluster.mgmtd_addr)
        try:
            sc = stubs.storage_client("stub-live")
            chain = socket_cluster.chain_ids[0]
            r = sc.write_chunk(chain, ChunkId(1, 0), 0, b"via-stub",
                               chunk_size=4096)
            assert r.ok
            assert sc.read_chunk(chain, ChunkId(1, 0)).data == b"via-stub"
            admin = stubs.mgmtd_admin()
            assert admin.routing().chains  # admin stub shares the client
        finally:
            sc.close()
            stubs.close()

    def test_native_transport_stubs(self, socket_cluster):
        """Same factory, native transport — stubs interoperate with the
        python-transport cluster because the wire format is shared."""
        stubs = StubFactory(transport="native",
                            mgmtd_addr=socket_cluster.mgmtd_addr)
        try:
            sc = stubs.storage_client("stub-native")
            chain = socket_cluster.chain_ids[1]
            r = sc.write_chunk(chain, ChunkId(2, 0), 0, b"native-stub",
                               chunk_size=4096)
            assert r.ok
            assert sc.read_chunk(chain, ChunkId(2, 0)).data == b"native-stub"
        finally:
            sc.close()
            stubs.close()

    def test_storage_stub_resolves_against_a_held_snapshot(
            self, socket_cluster):
        """The factory's storage client polls mgmtd for routing once, not
        once an op, and its retry ladders can still expire the snapshot."""
        stubs = StubFactory(transport="tcp",
                            mgmtd_addr=socket_cluster.mgmtd_addr)
        sc = stubs.storage_client("stub-snap")
        try:
            mcli = stubs.mgmtd_client()
            assert sc._routing == mcli.cached_routing
            assert sc._routing_invalidate == mcli.invalidate_routing
            chain = socket_cluster.chain_ids[0]
            for i in range(16):
                assert sc.write_chunk(chain, ChunkId(2, i), 0, b"s%d" % i,
                                      chunk_size=4096).ok
            for i in range(16):
                assert sc.read_chunk(chain, ChunkId(2, i)).data == b"s%d" % i
            assert mcli.routing_polls._value == 1
            assert mcli.routing_cached._value >= 32
            # a direct refresh on the same client still asks every time
            before = mcli._routing_ts
            mcli.refresh_routing()
            assert mcli._routing_ts > before
        finally:
            sc.close()
            stubs.close()
