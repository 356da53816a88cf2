"""metashard_bench: the partitioned metadata plane over REAL processes.

Boots kvd (the shared transactional KV — the FoundationDB role) + mgmtd
+ M meta servers as separate OS processes with a table of exactly M
metadata partitions (``--config.meta_partitions=M``), then storms
create/stat/list from W client worker processes (the dataload-pack /
kvcache-churn shape: many files into many directories, each directory
hashing to one partition owner). The headline is SCALING: aggregate
metadata ops/s at M=4 over M=1.

Honesty notes, because this bench is designed to be rerun anywhere:

- The M axis spreads HANDLER CPU across meta processes. On a
  multi-core host that is real parallelism; on a single-core host
  (``host_cpus`` is recorded in the row) every process time-shares one
  core and aggregate ops/s is core-bound at any M — the row still
  records the measured ratio, it just cannot exceed ~1.0 there.
- ``kv_raw_txns_s`` probes the shared kvd's single-writer txn ceiling
  in the same run: the storm's kvd traffic (~6 KV RPCs per create)
  sits well under it, i.e. the meta tier — not the KV — is the first
  bottleneck the partitioning relieves.

Also re-captures the kvcache write-back drain as a same-run A/B: the
pre-PR serial drain (per-key puts, ``flush_batch=1`` — the shape that
recorded 0.078 GiB/s in BENCH_KVCACHE before the batched drain landed)
against the batched drain (ONE batch_create + ONE striped batch write +
ONE batch_close per flush cycle) over a ShardedMetaStore plane. Both
legs run on the same machine minutes apart, so ``drain_speedup`` is
drift-free even when the absolute GiB/s moved with the host (the
recorded baselines are reproduced in the row for reference).

Prints one JSON object (bench.py conventions) and writes it to
--json-out (BENCH_METASHARD.json).

Usage: python -m benchmarks.metashard_bench [--ops 300] [--workers 4]
           [--json-out BENCH_METASHARD.json]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
# BENCH_KVCACHE writeback_flush_gibps: pre-batched-drain / as recorded
DRAIN_BASELINE_GIBPS = 0.078
DRAIN_RECORDED_GIBPS = 0.083


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def wait_port(port: int, deadline_s: float = 60.0) -> None:
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=0.5).close()
            return
        except OSError:
            time.sleep(0.2)
    raise RuntimeError(f"port {port} never came up")


class Cluster:
    """kvd + mgmtd + M meta servers (M partitions), real subprocesses."""

    def __init__(self, m: int):
        self.m = m
        self.procs: list = []
        self.kv_port = free_port()
        self.mport = free_port()
        self._spawn("tpu3fs.bin.kv_main", "--node-id", "5",
                    "--port", str(self.kv_port))
        wait_port(self.kv_port)
        self._spawn("tpu3fs.bin.mgmtd_main", "--node-id", "1",
                    "--port", str(self.mport),
                    "--kv", f"127.0.0.1:{self.kv_port}",
                    "--config.tick_interval_s=0.5",
                    f"--config.meta_partitions={m}")
        wait_port(self.mport)
        for i in range(m):
            # partition width is a deployment constant: the meta flag and
            # the mgmtd config must agree (the first server boots before
            # the lazily-created table exists, so it cannot infer it)
            self._spawn("tpu3fs.bin.meta_main", "--node-id", str(201 + i),
                        "--mgmtd", f"127.0.0.1:{self.mport}",
                        "--kv", f"127.0.0.1:{self.kv_port}",
                        "--meta-partitions", str(m),
                        "--heartbeat_interval", "1.0")
        self._wait_table()

    def _spawn(self, mod: str, *args: str) -> None:
        self.procs.append(subprocess.Popen(
            [sys.executable, "-m", mod, *args], env=ENV, cwd="/tmp",
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))

    def _wait_table(self) -> None:
        """Every partition owned by one of the M live meta nodes."""
        from tpu3fs.rpc.services import MgmtdAdminRpcClient

        admin = MgmtdAdminRpcClient(("127.0.0.1", self.mport))
        want = {201 + i for i in range(self.m)}
        deadline = time.time() + 90
        while time.time() < deadline:
            try:
                ri = admin.refresh_routing()
            except Exception:
                time.sleep(0.3)
                continue
            live = {n.node_id for n in ri.nodes.values()
                    if n.node_id in want and n.port}
            table = ri.meta_partitions
            if (live == want and len(table) == self.m
                    and all(r.node_id in want for r in table.values())
                    and len({r.node_id for r in table.values()}) == self.m):
                self.nparts = len(table)
                return
            time.sleep(0.3)
        raise RuntimeError(f"partition table never settled for M={self.m}")

    def stop(self) -> None:
        for p in self.procs:
            try:
                p.send_signal(signal.SIGTERM)
            except OSError:
                pass
        time.sleep(0.5)
        for p in self.procs:
            try:
                p.kill()
                p.wait(timeout=5)
            except OSError:
                pass


def storm(cluster: Cluster, *, workers: int, ops: int) -> float:
    """W worker PROCESSES storm create/stat/list; returns aggregate
    metadata ops/s (each API call counts as one op)."""
    from tpu3fs.rpc.services import MetaRpcClient, MgmtdRpcClient

    mg = MgmtdRpcClient(("127.0.0.1", cluster.mport))
    ri = mg.refresh_routing()
    meta_addrs = [(n.host, n.port) for n in ri.nodes.values()
                  if n.node_id >= 201 and n.host]
    mc = MetaRpcClient(meta_addrs, mgmtd=mg, nparts=cluster.nparts)
    # a directory per (worker, slot): parents spread over every
    # partition by hash, so the storm exercises the whole table
    dirs = [f"/storm/w{w}/d{i}" for w in range(workers) for i in range(8)]
    mc.batch_mkdirs(["/storm"] + sorted({d.rsplit("/", 1)[0] for d in dirs}))
    mc.batch_mkdirs(dirs)
    procs = [subprocess.Popen(
        [sys.executable, "-m", "benchmarks.metashard_bench", "--worker",
         "--mgmtd-port", str(cluster.mport), "--worker-id", str(w),
         "--nparts", str(cluster.nparts), "--ops", str(ops)],
        env=ENV, cwd=REPO, stdout=subprocess.PIPE)
        for w in range(workers)]
    total_ops = 0
    slowest = 0.0
    for p in procs:
        out, _ = p.communicate(timeout=600)
        if p.returncode != 0:
            raise RuntimeError(f"storm worker failed rc={p.returncode}")
        row = json.loads(out)
        total_ops += row["ops"]
        slowest = max(slowest, row["elapsed_s"])
    return total_ops / max(slowest, 1e-9)


def worker_main(args) -> int:
    """One storm worker process: create + stat + periodic list into its
    own directory set, routed per-op through the partition table."""
    from tpu3fs.rpc.services import MetaRpcClient, MgmtdRpcClient

    mg = MgmtdRpcClient(("127.0.0.1", args.mgmtd_port))
    ri = mg.refresh_routing()
    meta_addrs = [(n.host, n.port) for n in ri.nodes.values()
                  if n.node_id >= 201 and n.host]
    mc = MetaRpcClient(meta_addrs, client_id=f"storm-{args.worker_id}",
                       mgmtd=mg, nparts=args.nparts)
    dirs = [f"/storm/w{args.worker_id}/d{i}" for i in range(8)]
    done = 0
    t0 = time.perf_counter()
    for i in range(args.ops):
        d = dirs[i % len(dirs)]
        path = f"{d}/f{i:05d}"
        mc.create(path)
        done += 1
        mc.stat(path)
        done += 1
        if i % 8 == 7:
            mc.list_dir(d, limit=16)
            done += 1
    elapsed = time.perf_counter() - t0
    print(json.dumps({"ops": done, "elapsed_s": elapsed}))
    return 0


def kv_raw_txns_s(kv_port: int, n: int = 400) -> float:
    """Single-writer txn/s against the live kvd: the shared-KV ceiling
    the storm's per-create KV traffic must stay under."""
    from tpu3fs.kv.kv import with_transaction
    from tpu3fs.kv.remote import RemoteKVEngine

    eng = RemoteKVEngine(("127.0.0.1", kv_port))

    def bump(txn):
        raw = txn.get(b"BENCHC")
        txn.set(b"BENCHC", str(int(raw or 0) + 1).encode())

    with_transaction(eng, bump)  # warm
    t0 = time.perf_counter()
    for _ in range(n):
        with_transaction(eng, bump)
    return n / (time.perf_counter() - t0)


def drain_ab(*, blocks: int = 64, block_kb: int = 128,
             trials: int = 2) -> dict:
    """Same-run A/B of the kvcache write-back drain over a
    ShardedMetaStore plane: serial per-key drain (flush_batch=1, the
    pre-batching shape) vs the batched drain (ONE batch_create + ONE
    striped batch write + ONE batch_close per cycle)."""
    import numpy as np

    from benchmarks.storage_bench import _RpcCluster
    from tpu3fs.client.file_io import FileIoClient
    from tpu3fs.client.storage_client import RetryOptions
    from tpu3fs.kv.mem import MemKVEngine
    from tpu3fs.kvcache import KVCacheClient, TieredKVCache
    from tpu3fs.meta.store import ChainAllocator
    from tpu3fs.metashard.store import ShardedMetaStore

    chunk = 256 << 10
    cluster = _RpcCluster(replicas=2, chains=4, size=chunk,
                          transport="python")
    fio = FileIoClient(cluster.storage_client(
        retry=RetryOptions(backoff_base_s=0.001, backoff_max_s=0.05)))
    try:
        meta = ShardedMetaStore(
            MemKVEngine(), ChainAllocator(1, list(cluster.chain_ids)),
            file_length_hook=fio.file_length,
            truncate_hook=fio.truncate_chunks,
            default_chunk_size=chunk)
        cache = KVCacheClient(meta, fio, inode_cache=65536,
                              touch_coalesce_s=0.25)
        nbytes = blocks * block_kb << 10
        pages = [np.full((block_kb << 10,), i % 251, np.uint8)
                 for i in range(blocks)]

        def one_drain(tag: str, flush_batch: int) -> float:
            wb = TieredKVCache(cache, capacity_bytes=2 * nbytes + (1 << 20),
                               dirty_max_bytes=nbytes + (1 << 20),
                               flush_batch=flush_batch)
            try:
                t0 = time.perf_counter()
                for i, p in enumerate(pages):
                    wb.put(f"{tag}/{i}", p.tobytes())
                assert wb.flush(timeout=120.0)
                return nbytes / (time.perf_counter() - t0) / (1 << 30)
            finally:
                wb.close(flush=False)

        one_drain("warm", blocks)  # warm the chains + allocator
        serial, batched = 0.0, 0.0
        for t in range(trials):  # interleaved: drift hits both legs
            serial = max(serial, one_drain(f"s{t}", 1))
            batched = max(batched, one_drain(f"b{t}", blocks))
        return {
            "kvcache_drain_serial_gibps": round(serial, 3),
            "kvcache_drain_batched_gibps": round(batched, 3),
            "drain_speedup": round(batched / max(serial, 1e-9), 2),
            "drain_baseline_recorded_gibps": DRAIN_BASELINE_GIBPS,
        }
    finally:
        fio.close()
        cluster.close()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", type=int, default=300,
                    help="create/stat/list iterations per worker")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--json-out", default="")
    # internal: storm worker mode
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--mgmtd-port", type=int, default=0)
    ap.add_argument("--worker-id", type=int, default=0)
    ap.add_argument("--nparts", type=int, default=8)
    args = ap.parse_args()
    if args.worker:
        return worker_main(args)

    row = {"metric": "metashard", "workers": args.workers,
           "ops_per_worker": args.ops,
           "host_cpus": os.cpu_count() or 1}
    for m in (1, 4):
        cluster = Cluster(m)
        try:
            ops_s = storm(cluster, workers=args.workers, ops=args.ops)
            if m == 4:
                row["kv_raw_txns_s"] = round(
                    kv_raw_txns_s(cluster.kv_port), 1)
        finally:
            cluster.stop()
        row[f"meta_storm_m{m}_ops_s"] = round(ops_s, 1)
        print(f"# M={m}: {ops_s:.1f} ops/s", file=sys.stderr)
    row["scaling_m1_to_m4"] = round(
        row["meta_storm_m4_ops_s"] / max(row["meta_storm_m1_ops_s"], 1e-9),
        2)
    if row["host_cpus"] == 1:
        row["scaling_note"] = (
            "single-core host: all processes time-share one CPU, so "
            "aggregate ops/s is core-bound at any M; rerun on a "
            "multi-core host to see the partition scaling")

    row.update(drain_ab())

    row["value"] = row["scaling_m1_to_m4"]
    out = json.dumps(row, indent=1)
    print(out)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(out + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
