"""QoS subsystem: admission control, weighted-fair scheduling, shedding.

Covers the tpu3fs/qos package end to end: primitives (token bucket,
stride scheduler), the admission controller and its hot updates, RPC
dispatch enforcement (Python transport), the storage service's read/write
gates and weighted-fair update queues, client retry-after honoring,
background-worker self-throttling, the monitor recorders, and the
synthetic-overload acceptance criteria (bounded queue depth, OVERLOADED
sheds, everything retried to success). The `slow`-marked soak drives a
storage service at several times its configured capacity while a
resync-class flood runs and captures foreground read latency.
"""

from __future__ import annotations

import threading
import time

import pytest

from tpu3fs.fabric import Fabric, SystemSetupConfig
from tpu3fs.qos.core import (
    AdmissionController,
    QosConfig,
    TokenBucket,
    TrafficClass,
    class_from_flags,
    class_to_flags,
    current_class,
    format_retry_after,
    infer_write_class,
    retry_after_ms_of,
    tagged,
)
from tpu3fs.qos.manager import QosManager
from tpu3fs.qos.scheduler import WeightedFairQueue, WfqPolicy
from tpu3fs.storage.craq import ReadReq, WriteReq
from tpu3fs.storage.types import ChunkId
from tpu3fs.utils.result import Code, FsError, Status


class TestPrimitives:
    def test_token_bucket_admits_until_burst_then_hints(self):
        b = TokenBucket(rate=10.0, burst=3)
        assert b.try_acquire() == 0.0
        assert b.try_acquire() == 0.0
        assert b.try_acquire() == 0.0
        wait = b.try_acquire()
        assert 0.0 < wait <= 0.11  # one token at 10/s is 100ms away

    def test_token_bucket_refills(self):
        b = TokenBucket(rate=1000.0, burst=1)
        assert b.try_acquire() == 0.0
        assert b.try_acquire() > 0.0
        time.sleep(0.01)
        assert b.try_acquire() == 0.0

    def test_token_bucket_unlimited(self):
        b = TokenBucket(rate=0.0, burst=1)
        for _ in range(1000):
            assert b.try_acquire() == 0.0

    def test_token_bucket_reconfigure_live(self):
        b = TokenBucket(rate=0.0, burst=1)
        assert b.try_acquire() == 0.0
        b.configure(rate=1.0, burst=1)
        b.try_acquire()
        assert b.try_acquire() > 0.0

    def test_retry_after_roundtrip(self):
        msg = format_retry_after(75, "queue full")
        assert retry_after_ms_of(msg) == 75
        assert retry_after_ms_of("no hint here") == 0
        assert retry_after_ms_of("") == 0

    def test_class_flag_bits_roundtrip(self):
        for tc in TrafficClass:
            assert class_from_flags(class_to_flags(tc) | 1) == tc
        assert class_from_flags(1) is None  # untagged legacy frame

    def test_thread_local_tagging(self):
        assert current_class() is None
        with tagged(TrafficClass.RESYNC):
            assert current_class() == TrafficClass.RESYNC
            with tagged(TrafficClass.GC):
                assert current_class() == TrafficClass.GC
            assert current_class() == TrafficClass.RESYNC
        assert current_class() is None
        # FG_READ is value 0 and must survive the default fallthrough
        with tagged(TrafficClass.FG_READ):
            assert current_class(TrafficClass.FG_WRITE) == TrafficClass.FG_READ

    def test_infer_write_class(self):
        resync = WriteReq(chain_id=1, chain_ver=1, chunk_id=ChunkId(1, 0),
                          offset=0, data=b"", chunk_size=64,
                          full_replace=True, from_target=9)
        assert infer_write_class(resync) == TrafficClass.RESYNC
        mig = WriteReq(chain_id=1, chain_ver=1, chunk_id=ChunkId(1, 0),
                      offset=0, data=b"", chunk_size=64,
                      client_id="migration-3")
        assert infer_write_class(mig) == TrafficClass.MIGRATION
        fg = WriteReq(chain_id=1, chain_ver=1, chunk_id=ChunkId(1, 0),
                      offset=0, data=b"", chunk_size=64, client_id="c1")
        assert infer_write_class(fg) == TrafficClass.FG_WRITE

    def test_overloaded_is_retryable(self):
        assert Status(Code.OVERLOADED).retryable()


class _Item:
    def __init__(self, tag, cost=1):
        self.tag = tag
        self.cost = cost


class TestWeightedFairQueue:
    def test_weighted_shares(self):
        cfg = QosConfig()
        q = WeightedFairQueue(WfqPolicy(cfg), cap=512)
        for i in range(80):
            assert q.try_push(_Item(("fg", i)), TrafficClass.FG_WRITE) is None
        for i in range(80):
            assert q.try_push(_Item(("gc", i)), TrafficClass.GC) is None
        # fg weight 8 vs gc weight 1: the first 27 pops should be ~8:1 fg
        first = [q.pop()[1] for _ in range(27)]
        fg = sum(1 for tc in first if tc == TrafficClass.FG_WRITE)
        gc = sum(1 for tc in first if tc == TrafficClass.GC)
        assert fg >= 7 * gc, (fg, gc)

    def test_fifo_within_class(self):
        q = WeightedFairQueue(WfqPolicy(QosConfig()), cap=64)
        for i in range(10):
            q.try_push(_Item(i), TrafficClass.FG_WRITE)
        seen = [q.pop()[0].tag for _ in range(10)]
        assert seen == list(range(10))

    def test_background_share_shed(self):
        cfg = QosConfig()
        cfg.set("migration.queue_share", 0.25)
        q = WeightedFairQueue(WfqPolicy(cfg), cap=16)
        shed = None
        accepted = 0
        for i in range(16):
            shed = q.try_push(_Item(i), TrafficClass.MIGRATION)
            if shed is None:
                accepted += 1
        # migration may occupy at most 25% of the 16-slot queue
        assert accepted == 4
        assert shed is not None and shed > 0
        # foreground still gets the remaining capacity
        for i in range(12):
            assert q.try_push(_Item(i), TrafficClass.FG_WRITE) is None
        assert q.try_push(_Item(99), TrafficClass.FG_WRITE) is not None

    def test_work_conserving_when_foreground_idle(self):
        q = WeightedFairQueue(WfqPolicy(QosConfig()), cap=64)
        for i in range(8):
            q.try_push(_Item(i), TrafficClass.RESYNC)
        assert [q.pop()[0].tag for _ in range(8)] == list(range(8))
        assert q.pop() is None


class TestAdmissionController:
    def test_class_bucket_sheds_and_recovers(self):
        cfg = QosConfig()
        cfg.set("fg_write.rate", 5.0)
        cfg.set("fg_write.burst", 2.0)
        adm = AdmissionController(cfg)
        leases = []
        shed_ms = None
        for _ in range(5):
            lease, ms = adm.try_admit("StorageSerde", "write",
                                      TrafficClass.FG_WRITE)
            if lease is not None:
                leases.append(lease)
            else:
                shed_ms = ms
        assert len(leases) == 2
        assert shed_ms is not None and shed_ms >= 1
        for lease in leases:
            lease.release()

    def test_concurrency_gate(self):
        cfg = QosConfig()
        cfg.set("resync.max_inflight", 2)
        adm = AdmissionController(cfg)
        l1, _ = adm.try_admit("StorageSerde", "update", TrafficClass.RESYNC)
        l2, _ = adm.try_admit("StorageSerde", "update", TrafficClass.RESYNC)
        l3, ms = adm.try_admit("StorageSerde", "update", TrafficClass.RESYNC)
        assert l1 is not None and l2 is not None
        assert l3 is None and ms >= 1
        l1.release()
        l4, _ = adm.try_admit("StorageSerde", "update", TrafficClass.RESYNC)
        assert l4 is not None
        l2.release()
        l4.release()

    def test_hot_update_retunes_live(self):
        cfg = QosConfig()
        adm = AdmissionController(cfg)
        lease, _ = adm.try_admit("S", "write", TrafficClass.FG_WRITE)
        assert lease is not None  # unlimited by default
        lease.release()
        cfg.hot_update({"fg_write.rate": 1.0, "fg_write.burst": 1.0})
        l1, _ = adm.try_admit("S", "write", TrafficClass.FG_WRITE)
        l2, ms = adm.try_admit("S", "write", TrafficClass.FG_WRITE)
        assert l1 is not None and l2 is None and ms >= 1
        l1.release()
        # and back off again
        cfg.hot_update({"fg_write.rate": 0.0})
        for _ in range(10):
            lease, _ = adm.try_admit("S", "write", TrafficClass.FG_WRITE)
            assert lease is not None
            lease.release()

    def test_method_overrides(self):
        cfg = QosConfig()
        cfg.set("method_overrides", "Mgmtd.heartbeat=1/1")
        adm = AdmissionController(cfg)
        l1, _ = adm.try_admit("Mgmtd", "heartbeat", TrafficClass.CONTROL)
        l2, ms = adm.try_admit("Mgmtd", "heartbeat", TrafficClass.CONTROL)
        assert l1 is not None and l2 is None and ms >= 1
        # other methods of the same class stay unlimited
        l3, _ = adm.try_admit("Mgmtd", "getRoutingInfo", TrafficClass.CONTROL)
        assert l3 is not None
        l1.release()
        l3.release()

    def test_disabled_admits_everything(self):
        cfg = QosConfig()
        cfg.set("fg_write.rate", 0.001)
        cfg.set("enabled", False)
        adm = AdmissionController(cfg)
        for _ in range(20):
            lease, _ = adm.try_admit("S", "write", TrafficClass.FG_WRITE)
            assert lease is not None
            lease.release()


class TestRpcDispatchAdmission:
    """Admission enforced in the Python RPC server's dispatch, keyed by
    the envelope's traffic-class flag bits."""

    def _echo_server(self, cfg):
        from tpu3fs.rpc.net import RpcClient, RpcServer, ServiceDef
        from tpu3fs.rpc.services import EchoReq, EchoRsp

        server = RpcServer()
        svc = ServiceDef(42, "Echo")
        seen = []

        def handler(req):
            seen.append(current_class())
            return EchoRsp(req.text)

        svc.method(1, "echo", EchoReq, EchoRsp, handler)
        server.add_service(svc)
        server.set_admission(AdmissionController(cfg))
        server.start()
        return server, RpcClient(), seen

    def test_shed_carries_retry_after_and_recovers(self):
        from tpu3fs.rpc.services import EchoReq, EchoRsp

        cfg = QosConfig()
        cfg.set("control.rate", 2.0)
        cfg.set("control.burst", 1.0)
        server, client, _ = self._echo_server(cfg)
        try:
            rsp = client.call(server.address, 42, 1, EchoReq("hi"), EchoRsp)
            assert rsp.text == "hi"
            with pytest.raises(FsError) as ei:
                client.call(server.address, 42, 1, EchoReq("again"), EchoRsp)
            assert ei.value.code == Code.OVERLOADED
            hint = retry_after_ms_of(ei.value.status.message)
            assert hint >= 1
            time.sleep(hint / 1000.0 + 0.2)
            rsp = client.call(server.address, 42, 1, EchoReq("ok"), EchoRsp)
            assert rsp.text == "ok"
        finally:
            client.close()
            server.stop()

    def test_envelope_class_reaches_handler(self):
        from tpu3fs.rpc.services import EchoReq, EchoRsp

        server, client, seen = self._echo_server(QosConfig())
        try:
            with tagged(TrafficClass.MIGRATION):
                client.call(server.address, 42, 1, EchoReq("x"), EchoRsp)
            client.call(server.address, 42, 1, EchoReq("y"), EchoRsp)
        finally:
            client.close()
            server.stop()
        assert seen[0] == TrafficClass.MIGRATION
        # untagged frames classify by method name inside try_admit, but
        # the handler sees no tag
        assert seen[1] is None

    def test_per_class_isolation(self):
        """A drained background class must not shed foreground."""
        from tpu3fs.rpc.services import EchoReq, EchoRsp

        cfg = QosConfig()
        cfg.set("migration.rate", 1.0)
        cfg.set("migration.burst", 1.0)
        server, client, _ = self._echo_server(cfg)
        try:
            with tagged(TrafficClass.MIGRATION):
                client.call(server.address, 42, 1, EchoReq("a"), EchoRsp)
                with pytest.raises(FsError) as ei:
                    client.call(server.address, 42, 1, EchoReq("b"), EchoRsp)
                assert ei.value.code == Code.OVERLOADED
            # foreground-tagged calls sail through
            with tagged(TrafficClass.FG_WRITE):
                for _ in range(5):
                    client.call(server.address, 42, 1, EchoReq("c"), EchoRsp)
        finally:
            client.close()
            server.stop()


class TestNativeTransportQos:
    """The cheap C-side admission ceiling mirrored in native/rpc_net.cpp's
    dispatch: frames shed in the worker thread with OVERLOADED + a
    retry-after hint before anything crosses into Python."""

    def test_native_ceiling_sheds_before_python(self):
        pytest.importorskip("ctypes")
        from tpu3fs.rpc.native_net import NativeRpcServer
        from tpu3fs.rpc.net import RpcClient
        from tpu3fs.rpc.services import (
            CORE_SERVICE_ID,
            EchoReq,
            EchoRsp,
            bind_core_service,
        )

        cfg = QosConfig()
        cfg.set("native_ceiling_rate", 2.0)
        cfg.set("native_ceiling_burst", 2.0)
        server = NativeRpcServer()
        bind_core_service(server)
        server.set_admission(AdmissionController(cfg))
        server.start()
        if server.qos_shed_count() == 0 and not hasattr(
                server._lib, "tpu3fs_rpc_qos_set"):
            server.stop()
            pytest.skip("stale libtpu3fs_rpc.so without the qos ceiling")
        client = RpcClient()
        shed_hints = []
        try:
            ok = 0
            for _ in range(10):
                try:
                    rsp = client.call(server.address, CORE_SERVICE_ID, 1,
                                      EchoReq("x"), EchoRsp)
                    assert rsp.text == "x"
                    ok += 1
                except FsError as e:
                    assert e.code == Code.OVERLOADED
                    hint = retry_after_ms_of(e.status.message)
                    assert hint >= 1
                    shed_hints.append(hint)
            assert ok >= 2          # the burst was admitted
            assert shed_hints      # the flood was ceilinged in C
            assert server.qos_shed_count() == len(shed_hints)
            # hot update lifts the ceiling live (reload hook resyncs C)
            cfg.hot_update({"native_ceiling_rate": 0.0})
            for _ in range(5):
                client.call(server.address, CORE_SERVICE_ID, 1,
                            EchoReq("y"), EchoRsp)
        finally:
            client.close()
            server.stop()


def _qos_fabric(qcfg, **kw):
    defaults = dict(num_storage_nodes=2, num_chains=1, num_replicas=2,
                    chunk_size=4096, qos=qcfg)
    defaults.update(kw)
    return Fabric(SystemSetupConfig(**defaults))


class TestStorageServiceQos:
    def test_write_admission_sheds_and_client_recovers(self):
        qcfg = QosConfig()
        qcfg.set("fg_write.rate", 30.0)
        qcfg.set("fg_write.burst", 2.0)
        fab = _qos_fabric(qcfg)
        sc = fab.storage_client()
        chain = fab.chain_ids[0]
        # burst exhausted after 2 writes; the 8-deep ladder with the
        # server's retry-after hint must still land every write
        for i in range(6):
            r = sc.write_chunk(chain, ChunkId(100, i), 0, b"x" * 128,
                               chunk_size=4096)
            assert r.ok, (i, r)
        snap = fab.nodes[min(fab.nodes)].service.qos_snapshot()
        assert snap["enabled"]

    def test_read_admission_sheds_with_hint(self):
        qcfg = QosConfig()
        qcfg.set("fg_read.rate", 1.0)
        qcfg.set("fg_read.burst", 1.0)
        fab = _qos_fabric(qcfg)
        sc = fab.storage_client()
        chain = fab.chain_ids[0]
        qcfg.set("fg_read.rate", 0.0)  # let the write path through
        assert sc.write_chunk(chain, ChunkId(200, 0), 0, b"y" * 64,
                              chunk_size=4096).ok
        qcfg.hot_update({"fg_read.rate": 1.0, "fg_read.burst": 1.0})
        # direct service read: first admitted, second shed with a hint
        svc = fab.nodes[min(fab.nodes)].service
        tid = [t.target_id for t in fab.routing().chains[chain].targets
               if t.target_id in {t2.target_id for t2 in svc.targets()}][0]
        r1 = svc.read(ReadReq(chain, ChunkId(200, 0), target_id=tid))
        r2 = svc.read(ReadReq(chain, ChunkId(200, 0), target_id=tid))
        codes = {r1.code, r2.code}
        assert Code.OVERLOADED in codes
        shed = r1 if r1.code == Code.OVERLOADED else r2
        assert shed.retry_after_ms >= 1

    def test_background_write_classified_without_tag(self):
        """An untagged recovery full-replace lands in the RESYNC queue
        (request-shape inference), not the foreground one."""
        from tpu3fs.qos.manager import QosManager
        from tpu3fs.storage.craq import StorageService

        captured = []

        class _SpyWorker:
            def submit(self, reqs, make_reply, tclass=None):
                captured.append(tclass)
                return [make_reply(Code.OK, "")]

        fab = _qos_fabric(QosConfig())
        node = fab.nodes[min(fab.nodes)]
        svc = node.service
        target = svc.targets()[0]
        svc._update_workers[target.target_id] = _SpyWorker()
        req = WriteReq(chain_id=target.chain_id, chain_ver=1,
                       chunk_id=ChunkId(9, 0), offset=0, data=b"z" * 16,
                       chunk_size=4096, update_ver=3, full_replace=True,
                       from_target=777)
        svc._submit_batch_update(target, [req])
        assert captured == [TrafficClass.RESYNC]

    def test_queue_depth_bounded_and_sheds_under_overload(self):
        """The acceptance-criteria core: drive a single target at several
        times its queue capacity (24 concurrent submitters against a
        4-deep queue over a slowed engine), assert bounded queue depth,
        OVERLOADED sheds carrying hints, and zero lost writes after
        client retries."""
        qcfg = QosConfig()
        qcfg.set("update_queue_cap", 4)
        fab = _qos_fabric(qcfg, num_storage_nodes=1, num_replicas=1)
        chain = fab.chain_ids[0]
        node_id = min(fab.nodes)
        svc = fab.nodes[node_id].service
        target = svc.targets()[0]

        # slow the engine's batch_update to create real queueing
        real = target.engine.batch_update

        def slow_batch_update(ops, chain_ver):
            time.sleep(0.002)
            return real(ops, chain_ver)

        target.engine.batch_update = slow_batch_update
        sheds = []
        depths = []
        oks = []
        lock = threading.Lock()

        def writer(tid):
            # the retry-laddered client path: every write must land
            sc = fab.storage_client()
            for i in range(6):
                out = sc.batch_write(
                    [(chain, ChunkId(1000 + tid, i), 0, b"d" * 256)],
                    chunk_size=4096)
                with lock:
                    oks.append(out[0].ok)

        def flooder(tid):
            # raw unladdered batch sends: observe the sheds directly
            ver = fab.routing().chains[chain].chain_version
            for i in range(10):
                req = WriteReq(chain_id=chain, chain_ver=ver,
                               chunk_id=ChunkId(7000 + tid, i), offset=0,
                               data=b"f" * 256, chunk_size=4096,
                               update_ver=1, full_replace=True,
                               from_target=target.target_id)
                reply = fab.send(node_id, "batch_update", [req])[0]
                if reply.code == Code.OVERLOADED:
                    with lock:
                        sheds.append(reply.retry_after_ms
                                     or retry_after_ms_of(reply.message))

        def sampler():
            for _ in range(150):
                snap = svc.qos_snapshot()
                depths.append(sum(snap["queue_depths"].values()))
                time.sleep(0.001)

        threads = ([threading.Thread(target=writer, args=(t,))
                    for t in range(8)]
                   + [threading.Thread(target=flooder, args=(t,))
                      for t in range(16)])
        smp = threading.Thread(target=sampler)
        smp.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        smp.join()
        assert all(oks) and len(oks) == 48
        assert max(depths) <= 4, max(depths)  # bounded by update_queue_cap
        assert sheds, "24 submitters vs a 4-deep queue must shed"
        assert all(ms >= 1 for ms in sheds)

    def test_shed_metrics_reach_monitor(self):
        from tpu3fs.monitor.recorder import MemorySink, Monitor

        qcfg = QosConfig()
        qcfg.set("fg_write.rate", 1.0)
        qcfg.set("fg_write.burst", 1.0)
        fab = _qos_fabric(qcfg, num_storage_nodes=1, num_replicas=1)
        svc = fab.nodes[min(fab.nodes)].service
        chain = fab.chain_ids[0]
        for i in range(4):
            fab.send(min(fab.nodes), "write",
                     WriteReq(chain_id=chain, chain_ver=1,
                              chunk_id=ChunkId(50, i), offset=0,
                              data=b"m" * 32, chunk_size=4096))
        samples = Monitor.default().collect()
        names = {(s.name, s.tags.get("class")) for s in samples
                 if s.name.startswith("qos.")}
        assert ("qos.admitted", "fg_write") in names
        assert ("qos.shed", "fg_write") in names


class TestBackgroundSelfThrottle:
    def test_resync_honors_retry_after(self):
        from tpu3fs.storage.craq import UpdateReply
        from tpu3fs.storage.resync import ResyncWorker

        calls = []

        class _Svc:
            pass

        def messenger(node_id, method, payload):
            assert method == "update"
            calls.append(time.monotonic())
            if len(calls) < 3:
                return UpdateReply(Code.OVERLOADED, retry_after_ms=20)
            return UpdateReply(Code.OK)

        w = ResyncWorker(_Svc(), messenger)
        req = WriteReq(chain_id=1, chain_ver=1, chunk_id=ChunkId(1, 0),
                       offset=0, data=b"", chunk_size=64)
        reply = w._send_throttled(5, req)
        assert reply.ok
        assert len(calls) == 3
        # honored the 20ms hints between attempts
        assert calls[-1] - calls[0] >= 0.03

    def test_migration_pauses_not_fails_on_overload(self):
        from tpu3fs.client.storage_client import StorageClient
        from tpu3fs.migration.service import JobState, MigrationService
        from tpu3fs.storage.craq import UpdateReply

        fab = Fabric(SystemSetupConfig(num_storage_nodes=2, num_chains=2,
                                       num_replicas=1, chunk_size=4096))
        sc = fab.storage_client()
        src, dst = fab.chain_ids[0], fab.chain_ids[1]
        assert sc.write_chunk(src, ChunkId(1, 0), 0, b"mig" * 10,
                              chunk_size=4096).ok
        overloads = {"n": 2}
        real_send = fab.send

        def flaky_send(node_id, method, payload):
            # shed the first write attempts on BOTH the batched path and
            # the client ladder's single-op fallback
            if method in ("batch_write", "write") and overloads["n"] > 0:
                overloads["n"] -= 1
                reply = UpdateReply(Code.OVERLOADED, retry_after_ms=10)
                return [reply] * len(payload) \
                    if method == "batch_write" else reply
            return real_send(node_id, method, payload)

        svc = MigrationService(
            StorageClient("mig-test", fab.routing, flaky_send))
        job_id = svc.start_job(src, dst)
        job = svc.run_job(job_id, batch=8, max_steps=20)
        assert job.state == JobState.DONE
        assert job.copied == 1
        assert overloads["n"] == 0  # both sheds were absorbed, not fatal


class TestConfigPushHotUpdate:
    def test_qos_limits_hot_update_via_core_service(self):
        """The mgmtd-config-push path: hotUpdateConfig over RPC retunes a
        live AdmissionController without restart."""
        from tpu3fs.rpc.net import RpcClient, RpcServer
        from tpu3fs.rpc.services import (
            CORE_SERVICE_ID,
            Empty,
            StrReply,
            bind_core_service,
        )
        from tpu3fs.utils.config import Config

        class AppCfg(Config):
            qos = QosConfig

        cfg = AppCfg()
        adm = AdmissionController(cfg.qos)
        server = RpcServer()
        bind_core_service(server, config=cfg)
        server.start()
        client = RpcClient()
        try:
            client.call(server.address, CORE_SERVICE_ID, 3,
                        StrReply('[qos.fg_write]\nrate = 2.0\nburst = 1.0\n'),
                        Empty)
        finally:
            client.close()
            server.stop()
        l1, _ = adm.try_admit("S", "write", TrafficClass.FG_WRITE)
        l2, ms = adm.try_admit("S", "write", TrafficClass.FG_WRITE)
        assert l1 is not None and l2 is None and ms >= 1
        l1.release()


class TestCliQosView:
    def test_cmd_qos_lists_classes_and_depths(self):
        from tpu3fs.cli import AdminCli

        fab = _qos_fabric(QosConfig())
        out = AdminCli(fab).run("qos")
        assert "fg_read" in out and "resync" in out and "enabled" in out

    def test_cmd_qos_without_manager(self):
        from tpu3fs.cli import AdminCli

        fab = Fabric(SystemSetupConfig(num_storage_nodes=1, num_chains=1,
                                       num_replicas=1, chunk_size=4096))
        out = AdminCli(fab).run("qos")
        assert "disabled" in out


@pytest.mark.slow
class TestOverloadSoak:
    def test_foreground_read_p99_under_resync_flood(self):
        """Soak: a resync-class write flood at >4x the foreground rate
        runs against foreground reads for a few seconds, with QoS
        scheduling ON vs OFF. Asserts the scheduled run keeps queue depth
        bounded and sheds background instead of foreground; records both
        p99s."""

        def drive(qos_on: bool) -> dict:
            qcfg = None
            if qos_on:
                qcfg = QosConfig()
                qcfg.set("update_queue_cap", 8)
                qcfg.set("resync.queue_share", 0.25)
            fab = Fabric(SystemSetupConfig(
                num_storage_nodes=1, num_chains=1, num_replicas=1,
                chunk_size=4096, qos=qcfg))
            chain = fab.chain_ids[0]
            svc = fab.nodes[min(fab.nodes)].service
            target = svc.targets()[0]
            sc = fab.storage_client()
            for i in range(16):
                assert sc.write_chunk(chain, ChunkId(1, i), 0, b"r" * 512,
                                      chunk_size=4096).ok
            real = target.engine.batch_update

            def slow(ops, chain_ver):
                time.sleep(0.001)
                return real(ops, chain_ver)

            target.engine.batch_update = slow
            stop = threading.Event()
            sheds = [0]

            def bg_flood(fid: int):
                i = 0
                ver = fab.routing().chains[chain].chain_version
                with tagged(TrafficClass.RESYNC):
                    while not stop.is_set():
                        i += 1
                        req = WriteReq(chain_id=chain, chain_ver=ver,
                                       chunk_id=ChunkId(6000 + fid, i),
                                       offset=0, data=b"b" * 512,
                                       chunk_size=4096, update_ver=1,
                                       full_replace=True,
                                       from_target=target.target_id)
                        r = fab.send(min(fab.nodes), "batch_update",
                                     [req])[0]
                        if r.code == Code.OVERLOADED:
                            sheds[0] += 1
                            time.sleep((r.retry_after_ms or 10) / 1000.0)

            flooders = [threading.Thread(target=bg_flood, args=(n,))
                        for n in range(12)]
            for f in flooders:
                f.start()
            lat = []
            depth_max = 0
            t_end = time.monotonic() + 3.0
            while time.monotonic() < t_end:
                t0 = time.perf_counter()
                r = sc.read_chunk(chain, ChunkId(1, len(lat) % 16))
                lat.append(time.perf_counter() - t0)
                assert r.ok
                depth_max = max(depth_max, sum(
                    svc.qos_snapshot()["queue_depths"].values()))
            stop.set()
            for f in flooders:
                f.join()
            lat.sort()
            fab.close()
            return {"p99_ms": lat[int(len(lat) * 0.99)] * 1000,
                    "reads": len(lat), "sheds": sheds[0],
                    "depth": depth_max}

        scheduled = drive(qos_on=True)
        unscheduled = drive(qos_on=False)
        # the scheduled run must shed background (bounded bg share) and
        # keep its queue depth within the configured cap
        assert scheduled["sheds"] > 0
        assert scheduled["depth"] <= 8
        # loose comparative bound: scheduling must not make foreground
        # reads worse than the unscheduled chaos by more than 2x (it is
        # typically much better)
        assert scheduled["p99_ms"] <= max(unscheduled["p99_ms"] * 2.0, 50.0), (
            scheduled, unscheduled)


class TestCkptTrafficClass:
    """Satellite: the ckpt class registered end-to-end — enum, config
    section, envelope bits, WFQ share bound, admin_cli row — so a
    checkpoint flood demonstrably cannot starve foreground IO."""

    def test_registered_in_enum_config_and_flags(self):
        from tpu3fs.qos.core import BACKGROUND_CLASSES, CLASS_ATTRS

        assert TrafficClass.CKPT in BACKGROUND_CLASSES
        assert CLASS_ATTRS[TrafficClass.CKPT] == "ckpt"
        cfg = QosConfig()
        assert cfg.ckpt.weight == 2 and cfg.ckpt.queue_share == 0.5
        # envelope flag bits round-trip (4-bit field holds class 7)
        assert class_from_flags(
            class_to_flags(TrafficClass.CKPT)) == TrafficClass.CKPT
        adm = AdmissionController(cfg)
        assert "ckpt" in adm.snapshot()

    def test_wfq_fg_outweighs_ckpt_and_share_bounds_it(self):
        cfg = QosConfig()
        q = WeightedFairQueue(WfqPolicy(cfg), cap=8)

        class _Item:
            def __init__(self, tag):
                self.tag, self.cost = tag, 1

        # ckpt is share-bounded at 0.5 * cap = 4: the 5th queued ckpt
        # item sheds while foreground still gets in
        for i in range(4):
            assert q.try_push(_Item("ckpt"), TrafficClass.CKPT) is None
        assert q.try_push(_Item("ckpt"), TrafficClass.CKPT) is not None
        for i in range(4):
            assert q.try_push(_Item("fg"), TrafficClass.FG_WRITE) is None
        # stride pop: fg (weight 8) drains 4x faster than ckpt (weight 2)
        order = [q.pop()[0].tag for _ in range(8)]
        assert order[:3].count("fg") >= 2
        assert sorted(order) == ["ckpt"] * 4 + ["fg"] * 4

    def test_cli_qos_view_has_ckpt_row(self):
        from tpu3fs.cli import AdminCli

        fab = _qos_fabric(QosConfig())
        out = AdminCli(fab).run("qos")
        assert "ckpt" in out

    def test_ckpt_flood_cannot_starve_foreground_writes(self):
        """Integration: a tagged ckpt-class flood saturating a 4-deep
        queue over a slowed engine sheds at its share bound while every
        foreground write still lands (client ladder absorbs any shed)."""
        qcfg = QosConfig()
        qcfg.set("update_queue_cap", 4)
        qcfg.set("ckpt.queue_share", 0.25)
        fab = _qos_fabric(qcfg, num_storage_nodes=1, num_replicas=1)
        chain = fab.chain_ids[0]
        node_id = min(fab.nodes)
        svc = fab.nodes[node_id].service
        target = svc.targets()[0]
        real = target.engine.batch_update

        def slow(ops, chain_ver):
            time.sleep(0.002)
            return real(ops, chain_ver)

        target.engine.batch_update = slow
        stop = threading.Event()
        ckpt_sheds = [0]

        def flood(fid: int):
            ver = fab.routing().chains[chain].chain_version
            i = 0
            with tagged(TrafficClass.CKPT):
                while not stop.is_set():
                    i += 1
                    req = WriteReq(chain_id=chain, chain_ver=ver,
                                   chunk_id=ChunkId(7000 + fid, i),
                                   offset=0, data=b"c" * 256,
                                   chunk_size=4096, update_ver=1,
                                   full_replace=True,
                                   from_target=target.target_id)
                    r = fab.send(node_id, "batch_update", [req])[0]
                    if r.code == Code.OVERLOADED:
                        ckpt_sheds[0] += 1
                        time.sleep((r.retry_after_ms or 5) / 1000.0)

        flooders = [threading.Thread(target=flood, args=(n,))
                    for n in range(8)]
        for f in flooders:
            f.start()
        try:
            sc = fab.storage_client()
            for i in range(20):
                r = sc.write_chunk(chain, ChunkId(7100, i), 0, b"f" * 256,
                                   chunk_size=4096)
                assert r.ok, (i, r)
            depths = svc.qos_snapshot()["queue_depths"]
            assert sum(depths.values()) <= 4
        finally:
            stop.set()
            for f in flooders:
                f.join()
            fab.close()
        assert ckpt_sheds[0] > 0  # the share bound actually engaged


class TestQueueCapHotShrink:
    """Satellite: hot-updated update_queue_cap resizes LIVE queues —
    shrink caps new admits without dropping queued work."""

    def test_worker_shrink_keeps_queued_work(self):
        from tpu3fs.storage.update_worker import UpdateWorker

        cfg = QosConfig()
        gate = threading.Event()
        done = []

        def runner(reqs):
            gate.wait(5.0)
            done.extend(r.chunk_id for r in reqs)
            return ["ok"] * len(reqs)

        class _Req:
            def __init__(self, i):
                self.chain_id = 1
                self.chunk_id = ChunkId(1, i)

        from tpu3fs.qos.scheduler import WfqPolicy as _P

        w = UpdateWorker(runner, queue_cap=8, policy=_P(cfg))
        results = []

        def submit(i):
            results.append(w.submit(
                [_Req(i)], lambda code, msg, ra=0: Status(code, msg)))

        threads = [threading.Thread(target=submit, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for _ in range(100):  # wait until the queue holds blocked jobs
            if len(w) >= 4:
                break
            time.sleep(0.01)
        assert len(w) >= 4
        cfg.hot_update({"update_queue_cap": 2})
        w.set_queue_cap(int(cfg.update_queue_cap))
        assert w.queue_cap == 2
        # new admits shed at the shrunken cap while the old ones stay
        shed = w.submit([_Req(99)],
                        lambda code, msg, ra=0: Status(code, msg))
        assert shed[0].code == Code.OVERLOADED
        assert len(w) >= 4  # nothing queued was dropped
        gate.set()
        for t in threads:
            t.join()
        # every pre-shrink job completed
        assert all(r[0] == "ok" for r in results)
        assert len(done) == 6
        w.stop()

    def test_config_push_resizes_live_service_queues(self):
        """End-to-end: hot_update on the fabric's QosConfig reaches every
        live per-target worker through the craq config callback."""
        qcfg = QosConfig()
        qcfg.set("update_queue_cap", 64)
        fab = _qos_fabric(qcfg, num_storage_nodes=1, num_replicas=1)
        chain = fab.chain_ids[0]
        sc = fab.storage_client()
        # force worker creation (batched writes go through the queue)
        replies = sc.batch_write(
            [(chain, ChunkId(8000, i), 0, b"w" * 64) for i in range(4)],
            chunk_size=4096)
        assert all(r.ok for r in replies)
        svc = fab.nodes[min(fab.nodes)].service
        workers = list(svc._update_workers.values())
        assert workers and all(w.queue_cap == 64 for w in workers)
        qcfg.hot_update({"update_queue_cap": 3})
        assert all(w.queue_cap == 3 for w in workers)
        # growth works live too
        qcfg.hot_update({"update_queue_cap": 128})
        assert all(w.queue_cap == 128 for w in workers)
        fab.close()


class TestKvcacheTrafficClass:
    """The kvcache class registered end-to-end — enum, config section,
    envelope bits, WFQ share bound, admin_cli row — so an inference
    cache-fill flood demonstrably cannot starve foreground IO, while
    decode-loop reads schedule at foreground weight."""

    def test_registered_in_enum_config_flags_and_share_bound(self):
        from tpu3fs.qos.core import (
            BACKGROUND_CLASSES,
            CLASS_ATTRS,
            SHARE_BOUNDED_CLASSES,
        )

        assert CLASS_ATTRS[TrafficClass.KVCACHE] == "kvcache"
        # foreground-weighted, share-bounded, NOT background-weighted
        # (like dataload: latency-coupled to a serving loop)
        assert TrafficClass.KVCACHE in SHARE_BOUNDED_CLASSES
        assert TrafficClass.KVCACHE not in BACKGROUND_CLASSES
        cfg = QosConfig()
        assert cfg.kvcache.weight == 8
        assert cfg.kvcache.queue_share == 0.5
        assert class_from_flags(class_to_flags(
            TrafficClass.KVCACHE)) == TrafficClass.KVCACHE
        adm = AdmissionController(cfg)
        assert "kvcache" in adm.snapshot()

    def test_wfq_share_bounds_kvcache_but_not_fg(self):
        q = WeightedFairQueue(WfqPolicy(QosConfig()), cap=8)

        class _Item:
            cost = 1

        for _ in range(4):  # share 0.5 * cap 8 = 4
            assert q.try_push(_Item(), TrafficClass.KVCACHE) is None
        assert q.try_push(_Item(), TrafficClass.KVCACHE) is not None
        for _ in range(4):  # foreground fills the rest, unbounded
            assert q.try_push(_Item(), TrafficClass.FG_WRITE) is None

    def test_cli_qos_view_has_kvcache_row(self):
        from tpu3fs.cli import AdminCli

        fab = _qos_fabric(QosConfig())
        out = AdminCli(fab).run("qos")
        assert "kvcache" in out

    def test_client_ops_ride_the_kvcache_class(self):
        from tpu3fs.kvcache import KVCacheClient

        fab = Fabric(SystemSetupConfig(num_storage_nodes=2, num_chains=2,
                                       num_replicas=2, chunk_size=4096))
        try:
            fio = fab.file_client()
            c = KVCacheClient(fab.meta, fio)
            seen = []
            for name in ("read", "batch_read_files", "write"):
                real = getattr(fio, name)

                def spy(*a, _real=real, **kw):
                    seen.append(current_class())
                    return _real(*a, **kw)

                setattr(fio, name, spy)
            c.put("q/1", b"v" * 256)
            c.get("q/1")
            c.batch_get(["q/1"])
            assert seen and all(tc == TrafficClass.KVCACHE for tc in seen)
        finally:
            fab.close()

    def test_kvcache_flood_cannot_starve_foreground_writes(self):
        """Integration: a tagged kvcache-class write-back flood
        saturating a 4-deep queue over a slowed engine sheds at its
        share bound while every foreground write still lands."""
        qcfg = QosConfig()
        qcfg.set("update_queue_cap", 4)
        qcfg.set("kvcache.queue_share", 0.25)
        fab = _qos_fabric(qcfg, num_storage_nodes=1, num_replicas=1)
        chain = fab.chain_ids[0]
        node_id = min(fab.nodes)
        svc = fab.nodes[node_id].service
        target = svc.targets()[0]
        real = target.engine.batch_update

        def slow(ops, chain_ver):
            time.sleep(0.002)
            return real(ops, chain_ver)

        target.engine.batch_update = slow
        stop = threading.Event()
        kv_sheds = [0]

        def flood(fid: int):
            ver = fab.routing().chains[chain].chain_version
            i = 0
            with tagged(TrafficClass.KVCACHE):
                while not stop.is_set():
                    i += 1
                    req = WriteReq(chain_id=chain, chain_ver=ver,
                                   chunk_id=ChunkId(7700 + fid, i),
                                   offset=0, data=b"k" * 256,
                                   chunk_size=4096, update_ver=1,
                                   full_replace=True,
                                   from_target=target.target_id)
                    r = fab.send(node_id, "batch_update", [req])[0]
                    if r.code == Code.OVERLOADED:
                        kv_sheds[0] += 1
                        time.sleep((r.retry_after_ms or 5) / 1000.0)

        flooders = [threading.Thread(target=flood, args=(n,))
                    for n in range(8)]
        for f in flooders:
            f.start()
        try:
            sc = fab.storage_client()
            for i in range(20):
                r = sc.write_chunk(chain, ChunkId(7800, i), 0, b"f" * 256,
                                   chunk_size=4096)
                assert r.ok, (i, r)
            depths = svc.qos_snapshot()["queue_depths"]
            assert sum(depths.values()) <= 4
        finally:
            stop.set()
            for f in flooders:
                f.join()
            fab.close()
        assert kv_sheds[0] > 0  # the share bound actually engaged
