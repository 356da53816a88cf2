"""North-star workloads from BASELINE.md, as measurable bench phases.

Three workloads the reference publishes headline numbers for
(`/root/reference/README.md:30,40,48`), each scaled to the bench budget by
env knobs and reporting GiB/s next to its BASELINE.md row:

1. GraySort-style shuffle (BASELINE.md "GraySort ... 3.66 TiB/min"):
   records are range-partitioned by key on the accelerator (the sort's
   shuffle step — device argsort + gather), partition files are laid out
   over chains via a placement-solver table, written back through the
   batched CR path, then read and spot-verified. The device all-to-all
   form of the same exchange is tpu3fs.parallel.shuffle.shuffle_partitions
   (exercised by the multi-chip dryrun; one process has one mesh axis).

2. KVCache random read with concurrent GC (BASELINE.md "KVCache read
   ~40 GiB/s" + GC remove-op IOPS chart): 128 KiB values on an RS(12,4)
   EC layout, random batched gets racing a TTL GC that is concurrently
   draining an expired pool; reports read GiB/s and GC remove IOPS.

3. Sized failed-target rebuild (BASELINE.json "1 TiB failed-target
   rebuild from RS(12,4)"): write a sized file over RS(12,4), fail a
   node, resync through the device decode path, report rebuilt GiB/s.

Env knobs (defaults fit the CPU bench budget; raise on real hardware):
  TPU3FS_NS_SHUFFLE_MB   (512)   total record bytes shuffled
  TPU3FS_NS_KV_READS     (1024)  random gets measured
  TPU3FS_NS_REBUILD_MB   (1024)  file bytes written before the failure
"""

from __future__ import annotations

import json
import os
import threading
import time

import numpy as np


def _gibps(nbytes: float, dt: float) -> float:
    return nbytes / max(dt, 1e-9) / (1 << 30)


# ---------------------------------------------------------------------------
# 1. GraySort-style shuffle
# ---------------------------------------------------------------------------

def graysort_shuffle(*, total_mb: int = 512, partitions: int = 64,
                     record: int = 4096, nodes: int = 4,
                     chains: int = 8) -> dict:
    from tpu3fs.fabric.fabric import Fabric, SystemSetupConfig
    from tpu3fs.meta.store import OpenFlags
    from tpu3fs.placement.solver import (
        PlacementProblem,
        check_solution,
        solve_placement,
    )

    import jax
    import jax.numpy as jnp

    replicas = 2
    fab = Fabric(SystemSetupConfig(
        num_storage_nodes=nodes, num_chains=chains,
        num_replicas=replicas, chunk_size=1 << 20))
    try:
        # placement validation: the reference's GraySort runs sit on chain
        # tables produced by the offline placement solver
        # (deploy/data_placement) — mirror that by (a) solving the same
        # (v, k, r) instance and checking it, and (b) extracting the
        # DEPLOYED incidence from routing and holding it to the solver's
        # structural bar, so the shuffle below runs on a provably balanced
        # layout
        prob = PlacementProblem(num_nodes=nodes, group_size=replicas,
                                targets_per_node=chains * replicas // nodes)
        table = solve_placement(prob, steps=60, proposals_per_step=32)
        assert check_solution(table, prob), "solver table invalid"
        routing = fab.routing()
        node_ids = sorted(fab.nodes)
        deployed = np.zeros((chains, nodes), dtype=np.int8)
        for ci, chain_id in enumerate(fab.chain_ids):
            for t in routing.chains[chain_id].targets:
                node = routing.node_of_target(t.target_id)
                deployed[ci, node_ids.index(node.node_id)] = 1
        assert check_solution(deployed, prob), (
            "deployed chain layout fails the placement solver's bar")

        n_rec = (total_mb << 20) // record
        rng = np.random.default_rng(11)
        # 31-bit keys stored in the record's 8-byte key field: device
        # argsort is exact in int32 (jax downcasts int64 without x64 mode,
        # which would silently corrupt the sort)
        keys = rng.integers(0, 1 << 31, n_rec, dtype=np.int64)
        payload = rng.integers(0, 256, (n_rec, record - 8), dtype=np.uint8)

        t0 = time.perf_counter()
        # device partitioning: the shuffle's compute step (sort by key,
        # then range-split) runs on the accelerator
        dkeys = jnp.asarray(keys.astype(np.int32))
        perm = np.asarray(jax.device_get(jnp.argsort(dkeys)))
        sorted_keys = keys[perm]
        edges = np.linspace(0, 1 << 31, partitions + 1).astype(np.int64)
        bounds = np.searchsorted(sorted_keys, edges[1:-1])
        part_slices = np.split(perm, bounds)
        t_part = time.perf_counter() - t0

        fio = fab.file_client()
        fab.meta.mkdirs("/shuffle")
        t0 = time.perf_counter()
        written = 0
        inodes = []
        for p, rows in enumerate(part_slices):
            res = fab.meta.create(f"/shuffle/p{p:04d}", flags=OpenFlags.WRITE,
                                  client_id="bench")
            blob = np.concatenate(
                [keys[rows].view(np.uint8).reshape(-1, 8),
                 payload[rows]], axis=1).tobytes()
            fio.write(res.inode, 0, blob)
            written += len(blob)
            inodes.append((res.inode, int(edges[p]) if p else None,
                           len(blob)))
        t_write = time.perf_counter() - t0

        t0 = time.perf_counter()
        read = 0
        for p, (inode, lo, size) in enumerate(inodes):
            back = fio.read(inode, 0, size)
            read += len(back)
            got = np.frombuffer(back, dtype=np.uint8).reshape(-1, record)
            got_keys = got[:, :8].copy().view(np.int64).ravel()
            # spot-verify the partition invariant: every key in range
            if lo is not None and len(got_keys):
                assert got_keys.min() >= lo, f"partition {p} range broken"
        t_read = time.perf_counter() - t0
        return {
            "e2e_graysort_shuffle_gibps": round(
                _gibps(written, t_part + t_write), 3),
            "e2e_graysort_readback_gibps": round(_gibps(read, t_read), 3),
            "graysort_bytes": written,
            "graysort_partitions": partitions,
            "graysort_placement_checked": True,
        }
    finally:
        fab.close()


# ---------------------------------------------------------------------------
# 2. KVCache random read with concurrent GC
# ---------------------------------------------------------------------------

def kvcache_random_read(*, hot_entries: int = 128, expired_entries: int = 128,
                        value_kb: int = 128, reads: int = 1024,
                        batch: int = 16) -> dict:
    from tpu3fs.fabric.fabric import Fabric, SystemSetupConfig
    from tpu3fs.kvcache import KVCacheClient, KVCacheGC

    value = value_kb << 10
    fab = Fabric(SystemSetupConfig(
        num_storage_nodes=4, num_chains=2, chunk_size=value,
        ec_k=12, ec_m=4))
    try:
        cache = KVCacheClient(fab.meta, fab.file_client(),
                              touch_on_get=False)
        rng = np.random.default_rng(5)
        blob = rng.integers(0, 256, value, dtype=np.uint8).tobytes()
        for i in range(expired_entries):
            cache.put(f"old/{i}", blob)
        time.sleep(0.005)    # > ttl: every old mtime is beyond the cutoff
        t_mid = time.time()  # entries before t_mid are the expired pool
        hot_keys = [f"hot/{i}" for i in range(hot_entries)]
        for k in hot_keys:
            cache.put(k, blob)

        # GC drains the expired pool CONCURRENTLY with the measured reads
        # (ttl tiny + fixed `now` between the pools: exactly the old pool
        # expires, mirroring a TTL cache under live read traffic)
        gc = KVCacheGC(fab.meta, ttl_s=0.001, max_shards=32)
        removed = [0]
        stop = threading.Event()

        def _gc_loop():
            while not stop.is_set():
                n = gc.run_once(now=t_mid)
                removed[0] += n
                if n == 0:
                    time.sleep(0.001)

        gct = threading.Thread(target=_gc_loop, daemon=True)
        t0 = time.perf_counter()
        gct.start()
        got_bytes = 0
        hits = 0
        idx = rng.integers(0, hot_entries, reads)
        for base in range(0, reads, batch):
            ks = [hot_keys[i] for i in idx[base:base + batch]]
            vals = cache.batch_get(ks)
            for v in vals:
                if v is not None:
                    got_bytes += len(v)
                    hits += 1
        dt = time.perf_counter() - t0
        stop.set()
        gct.join(timeout=10)
        assert hits == reads, f"hot entries must survive GC: {hits}/{reads}"
        # drain whatever GC has left so the IOPS figure covers the pool
        t0 = time.perf_counter()
        while True:
            n = gc.run_once(now=t_mid)
            if n == 0:
                break
            removed[0] += n
        gc_extra = time.perf_counter() - t0
        return {
            "e2e_kvcache_read_gibps": round(_gibps(got_bytes, dt), 3),
            "e2e_kvcache_gc_remove_iops": round(
                removed[0] / max(dt + gc_extra, 1e-9), 1),
            "kvcache_reads": reads,
            "kvcache_gc_removed": removed[0],
        }
    finally:
        fab.close()


# ---------------------------------------------------------------------------
# 3. Sized failed-target EC rebuild
# ---------------------------------------------------------------------------

def failed_target_rebuild(*, file_mb: int = 1024, k: int = 12, m: int = 4,
                          chunk_mb: int = 1, engine: str = "mem") -> dict:
    from tpu3fs.fabric.fabric import Fabric, SystemSetupConfig
    from tpu3fs.meta.store import OpenFlags
    from tpu3fs.mgmtd.types import PublicTargetState

    chunk = chunk_mb << 20
    engine_dir = "/dev/shm" if engine != "mem" else None
    fab = Fabric(SystemSetupConfig(
        num_storage_nodes=4, num_chains=2, chunk_size=chunk,
        ec_k=k, ec_m=m, engine=engine, engine_dir=engine_dir))
    try:
        fio = fab.file_client()
        res = fab.meta.create("/big", flags=OpenFlags.WRITE,
                              client_id="bench")
        rng = np.random.default_rng(3)
        stripe_payload = rng.integers(0, 256, chunk, dtype=np.uint8).tobytes()
        written = 0
        t0 = time.perf_counter()
        for i in range(file_mb // chunk_mb):
            fio.write(res.inode, i * chunk, stripe_payload)
            written += chunk
        t_write = time.perf_counter() - t0

        victim = sorted(fab.nodes)[0]
        lost = sum(t.engine.used_size()
                   for t in fab.nodes[victim].service.targets())
        fab.fail_node(victim)
        t0 = time.perf_counter()
        fab.restart_node(victim)
        fab.resync_all(rounds=8)
        dt = time.perf_counter() - t0
        assert all(
            t.public_state == PublicTargetState.SERVING
            for chain in fab.routing().chains.values()
            for t in chain.targets), "rebuild must restore full health"
        # verify a sample of the file post-rebuild
        back = fio.read(res.inode, 0, chunk)
        assert back == stripe_payload, "post-rebuild read mismatch"
        return {
            "e2e_rebuild_gibps": round(_gibps(lost, dt), 3),
            "e2e_rebuild_bytes": lost,
            "e2e_rebuild_write_gibps": round(_gibps(written, t_write), 3),
            "rebuild_file_bytes": written,
            "rebuild_engine": engine,
        }
    finally:
        fab.close()


def run_all() -> dict:
    out = {}
    shuffle_mb = int(os.environ.get("TPU3FS_NS_SHUFFLE_MB", "512"))
    kv_reads = int(os.environ.get("TPU3FS_NS_KV_READS", "1024"))
    rebuild_mb = int(os.environ.get("TPU3FS_NS_REBUILD_MB", "1024"))
    # a workload that fails raises: the caller's run fails with it
    out.update(graysort_shuffle(total_mb=shuffle_mb))
    out.update(kvcache_random_read(reads=kv_reads))
    out.update(failed_target_rebuild(file_mb=rebuild_mb))
    return out


if __name__ == "__main__":
    print(json.dumps(run_all()))
