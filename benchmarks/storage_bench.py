"""storage_bench: chain-replicated chunk IO throughput harness.

Port of the reference's benchmarks/storage_bench (StorageBench.h:28-50):
configurable chunk count/size, batch size, worker concurrency, read/write
phases, optional checksum verification of every read, and optional random
error injection to exercise the retry ladders while measuring. Runs against
the in-process fabric (the reference reuses its UnitTestFabric the same way),
so the numbers measure the CRAQ write path + engine, not socket overhead —
pair with benchmarks/usrbio_bench.py for the client-API path.

Usage:
  python -m benchmarks.storage_bench [--chunks 256] [--size 262144]
      [--batch 16] [--threads 4] [--replicas 2] [--chains 4]
      [--engine mem|native] [--verify] [--inject 0.05]
      [--rpc] [--transport python|native]

Prints one JSON line per phase: write / read (+ IOPS, GiB/s).

--rpc stands the cluster up over real TCP sockets (mgmtd + storage
servers + RpcMessenger clients) instead of the in-process fabric, so the
numbers include the transport: serde envelopes, bulk-section framing
(FLAG_BULK scatter/gather — the RDMA-batch analogue), connection pooling.
--transport picks the Python or the native (epoll/writev) transport for
both servers and clients.
"""

from __future__ import annotations

import argparse
import json
import threading
import time

from tpu3fs.client.storage_client import RetryOptions
from tpu3fs.fabric.fabric import Fabric, SystemSetupConfig
from tpu3fs.ops.crc32c import crc32c
from tpu3fs.storage.types import ChunkId
from tpu3fs.utils.fault_injection import fault_injection

FILE_ID = 4242


def run_bench(
    *,
    chunks: int = 256,
    size: int = 256 << 10,
    batch: int = 16,
    threads: int = 4,
    replicas: int = 2,
    chains: int = 4,
    engine: str = "mem",
    verify: bool = False,
    inject: float = 0.0,
) -> list:
    import os

    engine_dir = None
    if engine != "mem" and os.path.isdir("/dev/shm"):
        # tmpfs keeps the measurement on the framework, not the host
        # disk's writeback throttle (real deployments pair the engine
        # with NVMe; this harness has none)
        engine_dir = "/dev/shm"
    fab = Fabric(SystemSetupConfig(
        num_storage_nodes=max(3, replicas),
        num_chains=chains,
        num_replicas=replicas,
        chunk_size=size,
        engine=engine,
        engine_dir=engine_dir,
    ))
    fast = RetryOptions(backoff_base_s=0.001, backoff_max_s=0.05)
    payloads = [bytes([i & 0xFF]) * size for i in range(min(chunks, 64))]
    crcs = [crc32c(p) for p in payloads]
    results = []

    def phase(name: str, fn) -> None:
        errors = []
        done = [0] * threads

        def worker(wid: int) -> None:
            client = fab.storage_client(retry=fast)
            try:
                for i in range(wid, chunks, threads):
                    if inject > 0:
                        # injected faults are non-retryable at the client
                        # (deterministic in tests); the bench absorbs them
                        # with one bare retry, like the reference's
                        # error-injecting StorageBench counts-and-continues
                        with fault_injection(inject, times=1):
                            try:
                                fn(client, i)
                            except AssertionError:
                                fn(client, i)
                    else:
                        fn(client, i)
                    done[wid] += 1
            except BaseException as e:
                errors.append(e)

        ts = [threading.Thread(target=worker, args=(w,))
              for w in range(threads)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        if errors:
            raise errors[0]
        n = sum(done)
        row = {
            "metric": f"storage_bench_{name}",
            "value": round(n * size / dt / (1 << 30), 3),
            "unit": "GiB/s",
            "iops": round(n / dt, 1),
            "ops": n,
            "chunk_size": size,
            "replicas": replicas,
            "threads": threads,
            "engine": engine,
        }
        results.append(row)
        print(json.dumps(row), flush=True)

    def do_write(client, i: int) -> None:
        chain = fab.chain_ids[i % len(fab.chain_ids)]
        reply = client.write_chunk(
            chain, ChunkId(FILE_ID, i), 0, payloads[i % len(payloads)],
            chunk_size=size)
        assert reply.ok, reply

    def do_read(client, i: int) -> None:
        chain = fab.chain_ids[i % len(fab.chain_ids)]
        reply = client.read_chunk(chain, ChunkId(FILE_ID, i))
        assert reply.ok, reply
        if verify:
            assert crc32c(reply.data) == crcs[i % len(crcs)], (
                f"checksum mismatch on chunk {i}")

    phase("write", do_write)
    phase("read", do_read)
    # batched read phase: all chunks in node-grouped batches of `batch`
    client = fab.storage_client(retry=fast)
    from tpu3fs.client.storage_client import ReadReq

    t0 = time.perf_counter()
    got = 0
    for base in range(0, chunks, batch):
        idxs = list(range(base, min(base + batch, chunks)))
        reqs = [
            ReadReq(fab.chain_ids[i % len(fab.chain_ids)],
                    ChunkId(FILE_ID, i), 0, -1)
            for i in idxs
        ]
        if inject > 0:
            with fault_injection(inject, times=1):
                replies = client.batch_read(reqs)
        else:
            replies = client.batch_read(reqs)
        assert all(r.ok for r in replies)
        if verify:
            for i, r in zip(idxs, replies):
                assert crc32c(r.data) == crcs[i % len(crcs)], (
                    f"batch-read checksum mismatch on chunk {i}")
        got += len(replies)
    dt = time.perf_counter() - t0
    row = {
        "metric": "storage_bench_batch_read",
        "value": round(got * size / dt / (1 << 30), 3),
        "unit": "GiB/s",
        "iops": round(got / dt, 1),
        "batch": batch,
        "engine": engine,
    }
    results.append(row)
    print(json.dumps(row), flush=True)

    # batched write phase: node-grouped BatchWrite requests (a second file
    # id so the write path runs fresh, not as overwrites)
    for node in fab.nodes.values():
        node.service.write_path_stats(reset=True)
    t0 = time.perf_counter()
    wrote = 0
    for base in range(0, chunks, batch):
        idxs = list(range(base, min(base + batch, chunks)))
        ops = [
            (fab.chain_ids[i % len(fab.chain_ids)],
             ChunkId(FILE_ID + 1, i), 0, payloads[i % len(payloads)])
            for i in idxs
        ]
        replies = client.batch_write(ops, chunk_size=size)
        assert all(r.ok for r in replies)
        wrote += len(replies)
    dt = time.perf_counter() - t0
    row = {
        "metric": "storage_bench_batch_write",
        "value": round(wrote * size / dt / (1 << 30), 3),
        "unit": "GiB/s",
        "iops": round(wrote / dt, 1),
        "batch": batch,
        "engine": engine,
    }
    results.append(row)
    print(json.dumps(row), flush=True)

    # write-path decomposition: where the batched-write seconds went,
    # split by chain role — "head" (entered from a client), "mid"
    # (entered from a predecessor, forwarded on; replicas >= 3), "tail"
    # (ended the chain). A forwarder's forward_s CONTAINS its successor's
    # whole pipeline, so at ANY chain depth the pure messaging/serde cost
    # of all hops together is
    #   forward_msg = (head.forward + mid.forward) - (mid.wall + tail.wall)
    # and head.wall decomposes as
    #   head_stage + head_commit + head_other + forward_msg
    #     + downstream stage/commit/other.
    agg = {}
    for role in ("head", "mid", "tail"):
        agg[role] = {"stage_s": 0.0, "forward_s": 0.0, "commit_s": 0.0,
                     "wall_s": 0.0, "ops": 0, "bytes": 0}
    for node in fab.nodes.values():
        st = node.service.write_path_stats()
        for role, vals in agg.items():
            for k in vals:
                vals[k] += st[role][k]
    head, mid, tail = agg["head"], agg["mid"], agg["tail"]
    row = {
        "metric": "storage_bench_write_decomp",
        "unit": "s",
        "head_stage_s": round(head["stage_s"], 4),
        "mid_stage_s": round(mid["stage_s"], 4),
        "tail_stage_s": round(tail["stage_s"], 4),
        "forward_msg_s": round(
            max(head["forward_s"] + mid["forward_s"]
                - mid["wall_s"] - tail["wall_s"], 0.0), 4),
        "head_commit_s": round(head["commit_s"], 4),
        "mid_commit_s": round(mid["commit_s"], 4),
        "tail_commit_s": round(tail["commit_s"], 4),
        "head_other_s": round(
            max(head["wall_s"] - head["stage_s"] - head["forward_s"]
                - head["commit_s"], 0.0), 4),
        "downstream_other_s": round(
            max(mid["wall_s"] - mid["stage_s"] - mid["forward_s"]
                - mid["commit_s"], 0.0)
            + max(tail["wall_s"] - tail["stage_s"] - tail["commit_s"],
                  0.0), 4),
        "head_wall_s": round(head["wall_s"], 4),
        "ops": head["ops"],
        "bytes": head["bytes"],
        "engine": engine,
    }
    results.append(row)
    print(json.dumps(row), flush=True)
    fab.close()
    return results


class _RpcCluster:
    """mgmtd + N storage nodes over real sockets (the socket-mode twin of
    the fabric; same shape as the reference running its UnitTestFabric
    against live transports)."""

    def __init__(self, *, replicas: int, chains: int, size: int,
                 transport: str = "python", engine: str = "mem"):
        from tpu3fs.kv.mem import MemKVEngine
        from tpu3fs.mgmtd.service import Mgmtd
        from tpu3fs.mgmtd.types import LocalTargetState, NodeType
        from tpu3fs.rpc.services import (
            MgmtdRpcClient,
            RpcMessenger,
            bind_mgmtd_service,
            bind_storage_service,
        )
        from tpu3fs.storage.craq import StorageService
        from tpu3fs.storage.target import StorageTarget

        if transport == "native":
            from tpu3fs.rpc.native_net import (
                NativeRpcClient as ClientCls,
                NativeRpcServer as ServerCls,
            )
        else:
            from tpu3fs.rpc.net import (
                RpcClient as ClientCls,
                RpcServer as ServerCls,
            )

        self.mgmtd = Mgmtd(1, MemKVEngine())
        self.mgmtd.extend_lease()
        self.servers = []
        mgmtd_server = ServerCls()
        bind_mgmtd_service(mgmtd_server, self.mgmtd)
        mgmtd_server.start()
        self.servers.append(mgmtd_server)
        self.mgmtd_addr = mgmtd_server.address
        self.shared_client = ClientCls()
        self._client_cls = ClientCls
        self._messenger_cls = RpcMessenger
        self._mgmtd_cli_cls = MgmtdRpcClient

        num_nodes = max(3, replicas)
        node_ids = [10 + i for i in range(num_nodes)]
        self.chain_ids = [900_001 + i for i in range(chains)]
        node_states: dict = {n: {} for n in node_ids}
        services = []
        svc_by_node = {}
        for node_id in node_ids:
            # the held snapshot: per-op getRoutingInfo round trips were a
            # measured double-digit share of served-read time; the bench
            # cluster's routing is static, retries invalidate anyway
            mcli = MgmtdRpcClient(self.mgmtd_addr, self.shared_client)
            svc = StorageService(node_id, mcli.cached_routing)
            svc.set_messenger(RpcMessenger(mcli.cached_routing,
                                           self.shared_client))
            server = ServerCls()
            bind_storage_service(server, svc)
            server.start()
            self.mgmtd.register_node(node_id, NodeType.STORAGE,
                                     host=server.host, port=server.port)
            self.servers.append(server)
            services.append(svc)
            svc_by_node[node_id] = svc
        import os
        import tempfile

        self._tmp = None
        if engine == "native":
            base = "/dev/shm" if os.path.isdir("/dev/shm") else None
            self._tmp = tempfile.TemporaryDirectory(
                prefix="tpu3fs-rpcbench-", dir=base)
        for ci, chain_id in enumerate(self.chain_ids):
            targets = []
            for r in range(replicas):
                node_id = node_ids[(ci + r) % num_nodes]
                target_id = 1000 + ci * 16 + r
                path = (os.path.join(self._tmp.name, str(target_id))
                        if self._tmp else None)
                svc_by_node[node_id].add_target(
                    StorageTarget(target_id, chain_id, chunk_size=size,
                                  engine=engine, path=path))
                self.mgmtd.create_target(target_id, node_id=node_id)
                node_states[node_id][target_id] = LocalTargetState.UPTODATE
                targets.append(target_id)
            self.mgmtd.upload_chain(chain_id, targets)
        self.mgmtd.upload_chain_table(1, self.chain_ids)
        for node_id in node_ids:
            self.mgmtd.heartbeat(node_id, 1, node_states[node_id])
        # native transport + native engine: serve batchRead in C++
        self.services = services
        if transport == "native":
            from tpu3fs.storage.native_fastpath import sync_read_fastpath

            for server, svc in zip(self.servers[1:], services):
                sync_read_fastpath(server, svc)
        self._client_seq = 0

    def storage_client(self, **kw):
        from tpu3fs.client.storage_client import StorageClient

        self._client_seq += 1
        mcli = self._mgmtd_cli_cls(self.mgmtd_addr, self.shared_client)
        messenger = self._messenger_cls(mcli.cached_routing,
                                        self.shared_client)
        return StorageClient(f"bench-rpc-{self._client_seq}",
                             mcli.cached_routing, messenger, **kw)

    def close(self) -> None:
        self.shared_client.close()
        for s in self.servers:
            s.stop()
        if self._tmp is not None:
            self._tmp.cleanup()


def run_rpc_bench(
    *,
    chunks: int = 256,
    size: int = 256 << 10,
    batch: int = 16,
    threads: int = 4,
    replicas: int = 2,
    chains: int = 4,
    transport: str = "python",
    engine: str = "mem",
    verify: bool = False,
) -> list:
    cluster = _RpcCluster(replicas=replicas, chains=chains, size=size,
                          transport=transport, engine=engine)
    fast = RetryOptions(backoff_base_s=0.001, backoff_max_s=0.05)
    payloads = [bytes([i & 0xFF]) * size for i in range(min(chunks, 64))]
    crcs = [crc32c(p) for p in payloads]
    results = []
    chain_ids = cluster.chain_ids

    def emit(name: str, n: int, dt: float, **extra) -> None:
        row = {
            "metric": f"storage_bench_rpc_{name}",
            "value": round(n * size / dt / (1 << 30), 3),
            "unit": "GiB/s",
            "iops": round(n / dt, 1),
            "chunk_size": size,
            "replicas": replicas,
            "transport": transport,
            "engine": engine,
            **extra,
        }
        results.append(row)
        print(json.dumps(row), flush=True)

    def threaded(fn) -> float:
        errors: list = []
        ts = []

        def worker(wid: int) -> None:
            client = cluster.storage_client(retry=fast)
            try:
                for i in range(wid, chunks, threads):
                    fn(client, i)
            except BaseException as e:
                errors.append(e)

        ts = [threading.Thread(target=worker, args=(w,))
              for w in range(threads)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        if errors:
            raise errors[0]
        return dt

    def do_write(client, i: int) -> None:
        reply = client.write_chunk(
            chain_ids[i % len(chain_ids)], ChunkId(FILE_ID, i), 0,
            payloads[i % len(payloads)], chunk_size=size)
        assert reply.ok, reply

    def do_read(client, i: int) -> None:
        reply = client.read_chunk(chain_ids[i % len(chain_ids)],
                                  ChunkId(FILE_ID, i))
        assert reply.ok, reply
        if verify:
            assert crc32c(reply.data) == crcs[i % len(crcs)]

    emit("write", chunks, threaded(do_write), threads=threads)
    emit("read", chunks, threaded(do_read), threads=threads)

    client = cluster.storage_client(retry=fast)
    from tpu3fs.client.storage_client import ReadReq

    t0 = time.perf_counter()
    got = 0
    for base in range(0, chunks, batch):
        idxs = list(range(base, min(base + batch, chunks)))
        reqs = [ReadReq(chain_ids[i % len(chain_ids)], ChunkId(FILE_ID, i),
                        0, -1) for i in idxs]
        replies = client.batch_read(reqs)
        assert all(r.ok for r in replies)
        if verify:
            for i, r in zip(idxs, replies):
                assert crc32c(r.data) == crcs[i % len(crcs)]
        got += len(replies)
    emit("batch_read", got, time.perf_counter() - t0, batch=batch)

    t0 = time.perf_counter()
    wrote = 0
    for base in range(0, chunks, batch):
        idxs = list(range(base, min(base + batch, chunks)))
        ops = [(chain_ids[i % len(chain_ids)], ChunkId(FILE_ID + 1, i), 0,
                payloads[i % len(payloads)]) for i in idxs]
        replies = client.batch_write(ops, chunk_size=size)
        assert all(r.ok for r in replies)
        wrote += len(replies)
    emit("batch_write", wrote, time.perf_counter() - t0, batch=batch)
    cluster.close()
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunks", type=int, default=256)
    ap.add_argument("--size", type=int, default=256 << 10)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--threads", type=int, default=4)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--chains", type=int, default=4)
    ap.add_argument("--engine", default="mem", choices=["mem", "native"])
    ap.add_argument("--verify", action="store_true")
    ap.add_argument("--inject", type=float, default=0.0)
    ap.add_argument("--rpc", action="store_true",
                    help="run over real sockets instead of the fabric")
    ap.add_argument("--transport", default="python",
                    choices=["python", "native"])
    args = ap.parse_args()
    if args.rpc:
        run_rpc_bench(chunks=args.chunks, size=args.size, batch=args.batch,
                      threads=args.threads, replicas=args.replicas,
                      chains=args.chains, transport=args.transport,
                      engine=args.engine, verify=args.verify)
    else:
        run_bench(chunks=args.chunks, size=args.size, batch=args.batch,
                  threads=args.threads, replicas=args.replicas,
                  chains=args.chains, engine=args.engine,
                  verify=args.verify, inject=args.inject)


if __name__ == "__main__":
    main()
