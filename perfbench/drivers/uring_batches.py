"""Traffic on the USRBIO API itself: matched batches of small random reads
through `UsrbioClient` and `UsrbioAgent` (file-mode SQEs, one agent in the
client's process) on chain replication, every batch landed in HBM and
consumed there by a jitted step.

A request is one matched batch of one job: `batch` prep_io (block numbers
from the job's own stream of the seed, Iov slot i for SQE i), one
submit_ios, wait_for_ios for all of them, device_put of the Iov and one
step over it. Block size, batch and ring depth are the configuration's
`io`; the mix names only the jobs and what the comparison samples. Every
seed reads the same count of blocks a batch, at offsets of that seed.
"""

from __future__ import annotations

import inspect
import os
import threading
import time

import numpy as np

from ..lib import reference as ref
from ..lib import reference_blocks as refb
from ..lib.cluster import SHM_DIR, read_target
from ..lib.harness import Check

GOLDEN = np.uint32(ref.GOLDEN)
PIECE = 4 << 20      # set-up writes a file in pieces of this size
KEEP_EVERY = 16      # one batch of each 16 of a job may keep its bytes


class Job:
    """One fio job: a file, an Iov, a read ring, and what its batches of
    the window left behind for the comparison."""

    def __init__(self, index: int):
        self.index = index
        self.path = ""
        self.fd = self.iov = self.ring = self.host = None
        self.draws = None
        self.batches: list = []   # (sums, maxes, kept array or None)
        self.cqe_errors = self.cqes_lost = 0


class Driver:
    def __init__(self, ctx):
        from tpu3fs.client.file_io import FileIoClient
        from tpu3fs.usrbio import UsrbioAgent

        self.ctx = ctx
        io, p = ctx.config["io"], ctx.params
        self.bs = int(io["bs"])
        self.batch = int(p.get("batch", io["iodepth"]))
        self.io_depth = int(p.get("io_depth", io["ior_depth"]))
        self.file_bytes = int(p.get("file_bytes", io["file_bytes"]))
        self.blocks_in_file = self.file_bytes // self.bs
        self.jobs = [Job(j) for j in range(int(p["jobs"]))]
        self.timeout = float(p["wait_timeout_s"])
        if ("io_depth" not in inspect.signature(
                UsrbioAgent.register_ring).parameters
                or not hasattr(FileIoClient, "batch_read_into")):
            raise SystemExit("perfbench: this program's USRBIO agent does "
                             "not take a ring's io_depth or serve a drain "
                             "as one batch; it cannot run this deployment")
        self.agent = self.client = None
        self.storages: list = []
        self.before: dict = {}
        self.shm_left = 0
        self._lock = threading.Lock()
        self._next_id = 0

    # -- set-up -------------------------------------------------------------
    def setup(self) -> None:
        from tpu3fs.meta.store import OpenFlags
        from tpu3fs.usrbio import UsrbioAgent, UsrbioClient

        ctx, jax = self.ctx, self.ctx.jax
        import jax.numpy as jnp

        t0 = time.time()
        fio_w = ctx.view.file_client(retry=ctx.retry)
        self.storages.append(fio_w.storage)
        root = f"/fio/seed{ctx.seed}"
        ctx.view.meta.mkdirs(root, recursive=True)
        for job in self.jobs:
            job.path = f"{root}/job{job.index}.dat"
            data = refb.file_bytes(ctx.seed, job.index, self.file_bytes)
            res = ctx.view.meta.create(
                job.path, flags=OpenFlags.WRITE | OpenFlags.CREATE
                | OpenFlags.TRUNC)
            for off in range(0, self.file_bytes, PIECE):
                fio_w.write(res.inode, off, data[off:off + PIECE])
            ctx.view.meta.close(res.inode.id, res.session_id,
                                length_hint=self.file_bytes, wrote=True)
        ctx.say(f"[files] {len(self.jobs)} files of "
                f"{self.file_bytes >> 20} MiB written in "
                f"{time.time() - t0:.1f}s")
        view = ctx.new_view("ur")
        fio = view.file_client(retry=ctx.retry)   # prefetch off
        self.storages.append(fio.storage)
        self.agent = UsrbioAgent(ctx.wrap(view.meta, "meta"),
                                 ctx.wrap(fio, "fio"))
        self.client = UsrbioClient(self.agent)
        for job in self.jobs:
            job.iov = self.client.iovcreate(self.batch * self.bs)
            job.ring = self.client.iorcreate(
                self.batch, [job.iov], for_read=True,
                io_depth=self.io_depth)
            job.fd = self.client.reg_fd(job.path)
            job.host = np.frombuffer(job.iov.buf, dtype=np.uint8).reshape(
                self.batch, self.bs)
            job.draws = refb.block_draws(ctx.seed, job.index,
                                         self.blocks_in_file, self.batch)
        words = self.bs // 4

        @jax.jit
        def step(x):
            b = x.reshape(x.shape[0], words, 4).astype(jnp.uint32)
            u = (b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16)
                 | (b[..., 3] << 24))
            return u.sum(axis=1), (u * jnp.uint32(GOLDEN)).max(axis=1)

        self.step = step

    def warm(self) -> None:
        """One batch a job at offsets of a stream of its own (the window's
        stream starts at its first draw), and the step's one shape."""
        for job in self.jobs:
            rng = np.random.default_rng([self.ctx.seed, 12, job.index])
            rec = self.run_batch(job, -1, rng.integers(
                0, self.blocks_in_file, self.batch, dtype=np.int64))
            if not rec["ok"]:
                raise RuntimeError(f"warm-up batch failed: {rec['error']}")
        self.before = dict(self.agent.totals)

    # -- the timed path -----------------------------------------------------
    def run_batch(self, job: Job, b: int, blocks, keep: bool = False):
        """One matched batch of `job`, its b-th of the window (-1: a
        warm-up, nothing kept) -> the request's record."""
        ctx, jax, client = self.ctx, self.ctx.jax, self.client
        bs, n = self.bs, self.batch
        with self._lock:
            rid = self._next_id
            self._next_id += 1
        rec = {"id": rid, "job": job.index, "ok": False, "load_bytes": 0,
               "store_bytes": 0, "phases": {}}
        ctx.spans.set_request(rid)
        base = max(b, 0) * n
        t0 = time.perf_counter()
        try:
            for i, blk in enumerate(blocks.tolist()):
                if client.prep_io(job.ring, job.iov, i * bs, bs, job.fd,
                                  blk * bs, read=True,
                                  userdata=base + i) < 0:
                    raise RuntimeError(f"ring full at SQE {i}")
            t_prep = time.perf_counter()
            client.submit_ios(job.ring)
            with jax.profiler.TraceAnnotation("pb:batch.wait"):
                done = client.wait_for_ios(job.ring, n, self.timeout)
            t_wait = time.perf_counter()
            res = np.fromiter((r for r, _ in done), dtype=np.int64,
                              count=len(done))
            uds = np.fromiter((u for _, u in done), dtype=np.int64,
                              count=len(done))
            errors = int((res != bs).sum())
            lost = int(len(done) != n or not np.array_equal(
                np.sort(uds), np.arange(base, base + n)))
            with jax.profiler.TraceAnnotation("pb:batch.land"):
                # on the cpu backend device_put may alias the host buffer
                host = job.host.copy() if ctx.rehearse else job.host
                x = jax.device_put(host, ctx.chip)
                jax.block_until_ready(x)
            t_land = time.perf_counter()
            sums, maxes = jax.block_until_ready(self.step(x))
            rec["phases"] = {"prep": t_prep - t0, "wait": t_wait - t_prep,
                             "land": t_land - t_wait}
            rec["load_bytes"] = int(res[res > 0].sum())
            rec["ok"] = len(done) == n
            if not rec["ok"]:
                rec["error"] = f"{len(done)} of {n} CQEs in {self.timeout}s"
            if b >= 0:
                job.cqe_errors += errors
                job.cqes_lost += lost
                job.batches.append((sums, maxes, x if keep else None))
        except Exception as e:  # a failed batch is a failed request
            rec["error"] = repr(e)
        if not rec["ok"]:
            ctx.say(f"job {job.index} batch {b} FAILED: {rec['error']}")
        rec["t0"], rec["t1"] = t0, time.perf_counter()
        return rec

    def window(self, seconds: float) -> None:
        ctx = self.ctx
        quota = -(-int(ctx.params["verify_batches"]) // len(self.jobs))
        t_start = time.perf_counter()

        def work(job: Job) -> None:
            rng = np.random.default_rng([ctx.seed, 9, job.index])
            keep, kept, b = set(), 0, 0
            while time.perf_counter() - t_start < seconds:
                if b % KEEP_EVERY == 0:
                    keep.add(b + int(rng.integers(KEEP_EVERY)))
                mine = b in keep and kept < quota
                rec = self.run_batch(job, b, next(job.draws), keep=mine)
                kept += mine and rec["ok"]
                with self._lock:
                    ctx.requests.append(rec)
                b += 1
                if not rec["ok"]:
                    break   # SQEs of a batch cut short may still be queued

        threads = [threading.Thread(target=work, args=(j,),
                                    name=f"uring-j{j.index}")
                   for j in self.jobs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for key in ("batches", "sqes", "short_drains"):
            ctx.counters[f"uring_{key}"] = (self.agent.totals[key]
                                            - self.before.get(key, 0))

    # -- the comparison -----------------------------------------------------
    def verify(self):
        from tpu3fs.storage.types import ChunkId

        ctx = self.ctx
        bs = self.bs
        rng = np.random.default_rng([ctx.seed, 10])
        want_replicas = int(ctx.config["cluster"]["tables"][0]["chains"][0][
            "targets"])
        routing = ctx.cluster.admin.refresh_routing()
        rows_wrong = bytes_wrong = replicas_wrong = 0
        rows_seen = bytes_seen = replicas_seen = 0
        for job in self.jobs:
            data = refb.file_bytes(ctx.seed, job.index, self.file_bytes)
            ref_sum, ref_max = refb.fingerprints(data, bs)
            draws = refb.block_draws(ctx.seed, job.index,
                                     self.blocks_in_file, self.batch)
            for sums, maxes, kept in job.batches:
                blocks = next(draws)   # the reference's own draw, in order
                got_s, got_m = np.asarray(sums), np.asarray(maxes)
                rows_seen += len(blocks)
                if got_s.shape != (self.batch,):
                    rows_wrong += self.batch
                    continue
                rows_wrong += int(((got_s != ref_sum[blocks])
                                   | (got_m != ref_max[blocks])).sum())
                if kept is not None:
                    bytes_seen += 1
                    got = np.asarray(kept)
                    bytes_wrong += int(
                        got.dtype != np.uint8
                        or got.tobytes() != refb.blocks_of(
                            data, blocks, bs).tobytes())
            # the stored form: sampled chunks, each replica read on its own
            inode = ctx.view.meta.stat(job.path)
            cs = inode.layout.chunk_size
            if inode.length != self.file_bytes:
                replicas_wrong += want_replicas
            chunks = rng.permutation(-(-self.file_bytes // cs))[
                :int(ctx.params["verify_chunks"])]
            for idx in chunks.tolist():
                chain_id = inode.layout.chain_of_chunk(idx)
                want = data[idx * cs:(idx + 1) * cs].tobytes()
                chain = routing.chains[chain_id]
                if len(chain.targets) != want_replicas:
                    replicas_wrong += want_replicas
                for t in chain.targets:
                    got = read_target(ctx.view, routing, chain_id,
                                      ChunkId(inode.id, idx), t.target_id)
                    replicas_seen += 1
                    replicas_wrong += got != want
            job.batches = []
            del data
        ctx.say(f"[verify] {rows_seen} rows against the reference's sums "
                f"and maxima, {bytes_seen} batches byte for byte in HBM, "
                f"{replicas_seen} replica reads of sampled chunks")
        self.teardown()
        return [
            Check("cqe_errors", sum(j.cqe_errors for j in self.jobs), 0),
            Check("cqes_lost_or_doubled",
                  sum(j.cqes_lost for j in self.jobs), 0),
            Check("rows_wrong_in_hbm", rows_wrong, 0),
            Check("blocks_wrong_bytes", bytes_wrong, 0),
            Check("replicas_wrong", replicas_wrong, 0),
            Check("short_drains", ctx.counters.get("uring_short_drains", 0),
                  0),
            Check("shm_left", self.shm_left, 0),
        ]

    def teardown(self) -> None:
        """Rings and Iovs destroyed through the API, the agent stopped, and
        what of their names is still in /dev/shm counted."""
        if self.client is None:
            return
        names = []
        for job in self.jobs:
            job.host = None
            for obj, destroy in ((job.ring, self.client.iordestroy),
                                 (job.iov, self.client.iovdestroy)):
                if obj is not None:
                    names.append(obj.name)
                    destroy(obj)
            if job.fd is not None:
                self.client.dereg_fd(job.fd)
            job.ring = job.iov = job.fd = None
        self.agent.stop()
        self.client = None
        try:
            entries = os.listdir(SHM_DIR)
        except OSError:
            entries = []
        self.shm_left = sum(1 for e in entries
                            if any(name in e for name in names))

    def close(self) -> None:
        self.teardown()
        for s in self.storages:
            s.close()
