"""Traffic on the training data path: random fixed-size records on CR-3
through PackedDataset and DataLoader (a mesh of one chip) into a jitted step
that consumes every batch, flat out, epoch after epoch.

A request is one batch: from asking the loader for it to the step's outputs
being ready. Every seed reads the same data set sizes in the loader's own
shuffle of that seed.
"""

from __future__ import annotations

import time

import numpy as np

from ..lib import reference as ref
from ..lib.cluster import read_target
from ..lib.harness import Check

GOLDEN = np.uint32(ref.GOLDEN)


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        p, cfg = ctx.params, ctx.config
        self.n_records = int(p.get("dataset_records", cfg["dataset_records"]))
        self.record_tokens = int(cfg["record"]["tokens"])
        self.batch = int(p["global_batch"])
        self.path = f"/data/seed{ctx.seed}/train.rec"
        self.loader = None
        self.kept: dict = {}      # batch number -> (ids, sums, maxes, data?)
        self.storages: list = []

    def setup(self) -> None:
        from jax.sharding import Mesh

        from tpu3fs.dataload import (DataLoader, LoaderConfig, PackedDataset,
                                     pack_records)

        ctx, jax = self.ctx, self.ctx.jax
        import jax.numpy as jnp

        t0 = time.time()
        self.tokens = np.random.default_rng([ctx.seed, 5]).integers(
            0, 1 << 31, (self.n_records, self.record_tokens), dtype=np.int32)
        fio_w = ctx.view.file_client(retry=ctx.retry)
        self.storages.append(fio_w.storage)
        ctx.view.meta.mkdirs(self.path.rsplit("/", 1)[0], recursive=True)
        pack_records(ctx.view.meta, fio_w, self.path,
                     (row.tobytes() for row in self.tokens),
                     num_records=self.n_records)
        ctx.say(f"[dataset] {self.n_records} records of "
                f"{self.record_tokens * 4 >> 10} KiB packed in "
                f"{time.time() - t0:.1f}s")
        view = ctx.new_view("dl")
        fio = view.file_client(retry=ctx.retry)
        self.storages.append(fio.storage)
        ds = PackedDataset(ctx.wrap(view.meta, "meta"), ctx.wrap(fio, "fio"),
                           [self.path])
        if len(ds) != self.n_records:
            raise RuntimeError(f"the data set holds {len(ds)} records, "
                               f"not {self.n_records}")
        self.mesh = Mesh(np.array([ctx.chip]).reshape(1, 1), ("dp", "chain"))
        self.cfg = LoaderConfig(
            global_batch=self.batch, seed=ctx.seed % (1 << 31),
            depth=int(ctx.params["prefetch_depth"]), epochs=None,
            dtype="int32", sample_shape=(self.record_tokens,))
        self.make_loader = lambda: DataLoader(ds, self.cfg, mesh=self.mesh)

        @jax.jit
        def step(x):
            u = x.astype(jnp.uint32)
            return u.sum(axis=1), (u * jnp.uint32(GOLDEN)).max(axis=1)

        self.step = step

    def warm(self) -> None:
        """The step's one shape and the loader's path, on a loader of its
        own: the window's loader starts at epoch 0, step 0."""
        with self.make_loader() as loader:
            for _ in range(3):
                batch = next(loader)
                self.ctx.jax.block_until_ready(self.step(batch.data))

    def window(self, seconds: float) -> None:
        ctx, jax = self.ctx, self.ctx.jax
        rng = np.random.default_rng([ctx.seed, 9])
        every = 64
        keep_data = set()
        self.loader = self.make_loader()
        t_start = time.perf_counter()
        n = n_kept = 0
        while time.perf_counter() - t_start < seconds:
            if n % every == 0:   # one batch of each 64 keeps its bytes
                keep_data.add(n + int(rng.integers(every)))
            rec = {"id": n, "ok": False, "load_bytes": 0, "store_bytes": 0,
                   "phases": {}}
            ctx.spans.set_request(n)
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("pb:batch.wait"):
                    batch = next(self.loader)
                t_got = time.perf_counter()
                with jax.profiler.TraceAnnotation("pb:batch.land"):
                    jax.block_until_ready(batch.data)
                t_land = time.perf_counter()
                sums, maxes = jax.block_until_ready(self.step(batch.data))
                rec["phases"] = {"wait": t_got - t0, "land": t_land - t_got}
                rec["load_bytes"] = int(batch.data.size) * 4
                rec["ok"] = True
                kept_data = None
                if n in keep_data and n_kept < int(
                        ctx.params["verify_batches"]):
                    kept_data, n_kept = batch.data, n_kept + 1
                self.kept[n] = (list(batch.ids), sums, maxes, kept_data,
                                batch.epoch)
            except Exception as e:  # a failed batch is a failed request
                rec["error"] = repr(e)
                ctx.say(f"batch {n} FAILED: {e!r}")
            rec["t0"], rec["t1"] = t0, time.perf_counter()
            ctx.requests.append(rec)
            n += 1
            if not rec["ok"]:
                break   # the loader's error is sticky: no second answer

    def verify(self):
        from tpu3fs.storage.types import ChunkId

        ctx = self.ctx
        self.loader.close()
        ref_u = self.tokens.view(np.uint32)
        ref_sum = ref_u.sum(axis=1, dtype=np.uint32)
        ref_max = (ref_u * GOLDEN).max(axis=1)
        step_wrong = rows_wrong = short = bytes_wrong = bytes_seen = 0
        seen_in_epoch: dict = {}
        repeats = 0
        for n in sorted(self.kept):
            ids, sums, maxes, data, epoch = self.kept[n]
            idx = np.asarray(ids, dtype=np.int64)
            if len(ids) != self.batch:
                short += 1
            if idx.min() < 0 or idx.max() >= self.n_records:
                rows_wrong += len(ids)
                continue
            got_s, got_m = np.asarray(sums), np.asarray(maxes)
            bad = (got_s != ref_sum[idx][:len(got_s)]) | (
                got_m != ref_max[idx][:len(got_m)])
            if got_s.shape[0] != len(ids):
                step_wrong += 1
            rows_wrong += int(bad.sum())
            seen = seen_in_epoch.setdefault(epoch, set())
            repeats += sum(1 for i in ids if i in seen)
            seen.update(ids)
            if data is not None:
                bytes_seen += 1
                if np.asarray(data).tobytes() != self.tokens[idx].tobytes():
                    bytes_wrong += 1
        epochs = sorted(seen_in_epoch)
        missing = sum(self.n_records // self.batch * self.batch
                      - len(seen_in_epoch[e]) for e in epochs[:-1])
        ctx.say(f"[verify] {len(self.kept)} batches against the reference's "
                f"sums and maxima, {bytes_seen} byte for byte in HBM, "
                f"{len(epochs)} epoch(s)")
        checks = [Check("batch_rows_wrong", rows_wrong + step_wrong, 0),
                  Check("batches_short", short, 0),
                  Check("records_repeated_or_missing", repeats + missing, 0),
                  Check("batches_wrong_in_hbm", bytes_wrong, 0)]
        # the stored form: the record file's first chunk (header, index,
        # every record's CRC) and sampled others, each replica of its chain
        # read on its own, against the file the reference packs itself
        rng = np.random.default_rng([ctx.seed, 10])
        head = ref.record_file_head(self.tokens)
        length = len(head) + self.tokens.nbytes
        inode = ctx.view.meta.stat(self.path)
        cs = inode.layout.chunk_size
        routing = ctx.cluster.admin.refresh_routing()
        want_replicas = int(ctx.config["cluster"]["tables"][0]["chains"][0][
            "targets"])
        replicas_wrong = replicas_seen = 0
        if inode.length != length:
            replicas_wrong += want_replicas
        others = 1 + rng.permutation(-(-length // cs) - 1)[
            :int(ctx.params["verify_chunks"]) - 1]
        for idx in [0] + others.tolist():
            chain_id = inode.layout.chain_of_chunk(idx)
            want = ref.record_file_bytes(head, self.tokens, idx * cs,
                                         min(length, (idx + 1) * cs))
            chain = routing.chains[chain_id]
            if len(chain.targets) != want_replicas:
                replicas_wrong += want_replicas
            for t in chain.targets:
                got = read_target(ctx.view, routing, chain_id,
                                  ChunkId(inode.id, idx), t.target_id)
                replicas_seen += 1
                replicas_wrong += got != want
        ctx.say(f"[verify] {replicas_seen} replica reads of sampled chunks")
        checks.append(Check("replicas_wrong", replicas_wrong, 0))
        return checks

    def close(self) -> None:
        if self.loader is not None:
            self.loader.close()
        for s in self.storages:
            s.close()
