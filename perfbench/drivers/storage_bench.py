"""Traffic of upstream's storage bench on an erasure-coded table: closed
workers that each keep one batch of whole chunks in flight, straight through
`StorageClient` — `write_stripes` and `batch_read` on the chain table, no
meta and no file layer on the served path (3FS
benchmarks/storage_bench/StorageBench.h: chunks, batch size, coroutines,
read checksums verified, random client/server errors injected).

- The working set: `chunks` chunks over the table's chains (chunk c on chain
  c mod chains); the latest generation of every chunk is resident in HBM,
  and it is the source of every rewrite.
- A batch is a read or a write with equal odds, in rounds that hold as
  many of each, in the seed's order (`plan`). Worker w
  rewrites only the chunks it owns (c mod workers == w): on the chip, each
  chunk's next generation is made from its resident copy (reference_sb), then
  `write_stripes` puts the batch, encode and CRCs on the chip. A read batch
  draws from every chunk, reads them whole with their checksums, lands them
  in HBM as one (batch, chunk) array and checks every row there against the
  checksum it was read with (`CrcVerifier`); a row that fails fails the op.
  The chip also takes each landed row's fingerprint, which the comparison
  holds against the reference's generation for every read.
- Before the window the fault rules go to the storage processes through
  mgmtd's config push: an error at `storage.read` and at
  `storage.write_shard` with the mix's probability, seeded from --seed; the
  client has to retry through them.
"""

from __future__ import annotations

import functools
import inspect
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..lib import reference as ref
from ..lib import reference_sb as refsb
from ..lib.harness import Check

FILE_ID = 0x5B0001      # the file id of every chunk: ChunkId(FILE_ID, c)


@functools.lru_cache(maxsize=None)
def _programs(words: int):
    """The chip's two programs at one chunk size (both one compile a batch
    size): generation 0 of a batch of chunks from their keys, and the next
    generation of a batch from its resident rows. Each returns one array a
    chunk, so that a chunk's resident copy is a buffer of its own."""
    import jax
    import jax.numpy as jnp

    def mix(x):
        x = x ^ (x >> 16)
        x = x * jnp.uint32(refsb.C1)
        x = x ^ (x >> 13)
        x = x * jnp.uint32(refsb.C2)
        return x ^ (x >> 16)

    @jax.jit
    def first(keys):
        i = jnp.arange(words, dtype=jnp.uint32) * jnp.uint32(ref.GOLDEN)
        return tuple(mix(i ^ keys[b]) for b in range(keys.shape[0]))

    @jax.jit
    def following(rows, steps):
        return tuple(r + steps[b] for b, r in enumerate(rows))

    return first, following


@functools.lru_cache(maxsize=None)
def _prints():
    """(B, n) uint8 rows on the chip -> (B, 2) uint32: lib.device's
    fingerprint of each row's bytes (the reference's twin is
    reference.fingerprint_np). One compile a batch shape."""
    import jax

    from ..lib.device import fingerprint

    return jax.jit(jax.vmap(fingerprint))


class Driver:
    def __init__(self, ctx):
        from tpu3fs.client.storage_client import StorageClient

        if "with_checksum" not in inspect.signature(
                StorageClient.batch_read).parameters:
            raise SystemExit("perfbench: this program's StorageClient does "
                             "not carry an erasure-coded range's checksum "
                             "(batch_read with_checksum); it cannot run this "
                             "deployment")
        self.ctx = ctx
        cfg, p = ctx.config, ctx.params
        self.chunk = int(p.get("chunk_size", cfg["chunk_size"]))
        self.n = int(p.get("chunks", cfg["chunks"]))
        self.batch = int(p["batch"])
        self.workers = int(p["workers"])
        self.read_share = float(p["read_share"])
        self.round_ops = int(p["round_ops"])
        self.error_prob = float(p["error_prob"])
        self.chains = [c for t in cfg["cluster"]["tables"]
                       for c in t["chains"] if c.get("ec_k")]
        self.k, self.m = self.chains[0]["ec_k"], self.chains[0]["ec_m"]
        if self.chunk % 512 or self.n % (self.workers * self.batch):
            raise ValueError("chunk size a multiple of 512 and every worker "
                             "owning whole batches, please")
        self.gen = [0] * self.n          # latest acknowledged generation
        self.ver = [0] * self.n          # ... and its stripe version
        self.acked: dict = {}            # (chunk, version) -> generation
        self.pool: list = [None] * self.n   # resident copy of each chunk
        self.reads: list = []            # (chunk, version) of every read
        self.printed: list = []          # (chunks, versions, fingerprints)
        self.kept: list = []             # read batches kept in HBM whole
        self.storages: list = []
        self.clients: list = []
        self.before = 0
        self._lock = threading.Lock()

    # -- what the seed fixes ------------------------------------------------
    def chain_of(self, c: int) -> int:
        return int(self.chains[c % len(self.chains)]["chain_id"])

    def plan(self, w: int, op: int) -> tuple:
        """-> ("read" | "write", chunks) of worker w's op number `op`. A
        worker's ops come in rounds of `round_ops`, each holding the mix's
        share of reads in the seed's order: every seed reads and writes as
        many batches, since a read batch takes half a write's time and a
        seed's draw of the mix would move the rates by more than the runs
        spread."""
        r, at = divmod(op, self.round_ops)
        order = np.random.default_rng([self.ctx.seed, 20, w, r]).permutation(
            self.round_ops)
        rng = np.random.default_rng([self.ctx.seed, 21, w, op])
        if order[at] < round(self.round_ops * self.read_share):
            return "read", rng.choice(self.n, self.batch,
                                      replace=False).tolist()
        owned = np.arange(w, self.n, self.workers)
        return "write", rng.choice(owned, self.batch, replace=False).tolist()

    # -- set-up -------------------------------------------------------------
    def make_client(self, tag: str):
        storage = self.ctx.new_view(tag).storage_client(retry=self.ctx.retry)
        self.storages.append(storage)
        return storage

    def put(self, storage, chunks: list, rows: tuple) -> list:
        """write_stripes of a batch (one call a chain) from its rows on the
        chip -> the replies in the batch's order."""
        from tpu3fs.storage.types import ChunkId

        host = self.ctx.jax.device_get(rows)
        by_chain: dict = {}
        for b, c in enumerate(chunks):
            by_chain.setdefault(self.chain_of(c), []).append(b)
        out = [None] * len(chunks)
        for chain_id, idx in by_chain.items():
            items = [(ChunkId(FILE_ID, chunks[b]),
                      memoryview(host[b].view(np.uint8))) for b in idx]
            for b, reply in zip(idx, storage.write_stripes(
                    chain_id, items, chunk_size=self.chunk)):
                out[b] = reply
        return out

    def setup(self) -> None:
        from tpu3fs.ops import stripe

        ctx = self.ctx
        t0 = time.time()
        if ctx.rehearse:   # the harness sends the configuration's codec
            # to the device on the cpu; a rehearsal's own chunk size too
            stripe.get_codec(self.k, self.m, stripe.shard_size_of(
                self.chunk, self.k))._host_mode = False
        first, _ = _programs(self.chunk // 4)
        errors: list = []

        def fill(w: int) -> None:
            try:
                storage = self.make_client(f"s{w}")
                owned = list(range(w, self.n, self.workers))
                for lo in range(0, len(owned), self.batch):
                    chunks = owned[lo:lo + self.batch]
                    keys = np.array([refsb.chunk_key(ctx.seed, c)
                                     for c in chunks], dtype=np.uint32)
                    rows = first(keys)
                    replies = self.put(storage, chunks, rows)
                    for c, r, row in zip(chunks, replies, rows):
                        if not r.ok:
                            raise RuntimeError(f"set-up write of chunk {c}: "
                                               f"{r.code!r}")
                        self.pool[c], self.ver[c] = row, r.commit_ver
                        self.acked[(c, r.commit_ver)] = 0
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=fill, args=(w,))
                   for w in range(self.workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        ctx.say(f"[chunks] {self.n} chunks of {self.chunk >> 10} KiB "
                f"({self.n * self.chunk >> 20} MiB) over "
                f"{len(self.chains)} RS({self.k},{self.m}) chains in "
                f"{time.time() - t0:.1f}s")
        from tpu3fs.ops.crc32c import CrcVerifier

        self.verifier = CrcVerifier(self.chunk)
        self.clients = [ctx.wrap(self.make_client(f"w{w}"), "client")
                        for w in range(self.workers)]
        if not hasattr(ctx, "crc_calls"):
            ctx.crc_calls = []
        if ctx.trace:
            from ..lib import codecwatch

            codecwatch.watch(ctx)

    def warm(self) -> None:
        """Every shape of the window: a write and a read batch a worker
        (the next-generation program, the encode's buckets, the landing and
        the verify program), and the decode of one lost data shard — a
        read that meets a rewrite of its stripe between two shards' commits
        assembles the newest version whole from any k of them; then the
        fault rules go out."""
        from tpu3fs.ops import stripe

        for w, client in enumerate(self.clients):
            chunks = list(range(w, self.n, self.workers))[:self.batch]
            self.write(client, chunks)
            self.read(client, chunks, keep=False)
        S = stripe.shard_size_of(self.chunk, self.k)
        stripe.get_codec(self.k, self.m, S).reconstruct_batch(
            list(range(self.k - 1)) + [self.k], [self.k - 1],
            np.zeros((1, self.k, S), dtype=np.uint8))
        self.push_faults(
            f"point=storage.read,kind=error,prob={self.error_prob};"
            f"point=storage.write_shard,kind=error,prob={self.error_prob}")
        self.before = self.injected()

    def push_faults(self, spec: str) -> None:
        """The rules to every storage process through mgmtd's config push
        (admin_cli fault set), seeded from --seed; heartbeats deliver
        them within an interval."""
        from tpu3fs.cli import AdminCli
        from tpu3fs.mgmtd.types import NodeType

        admin = self.ctx.cluster.admin
        blob = admin.get_config(NodeType.STORAGE)
        admin.set_config(NodeType.STORAGE, AdminCli._merge_faults_toml(
            blob.content, spec, self.ctx.seed % (1 << 62)))
        time.sleep(float(self.ctx.params["fault_settle_s"]))

    def injected(self) -> int:
        return sum(int(s._injected_retried._value) for s in self.storages)

    # -- one batch ----------------------------------------------------------
    def run(self, client, w: int, op: int) -> dict:
        """Worker w's op number `op`, recorded as a request."""
        ctx = self.ctx
        kind, chunks = self.plan(w, op)
        rec = {"id": (w, op), "kind": kind, "ok": False, "load_bytes": 0,
               "store_bytes": 0}
        t0 = time.perf_counter()
        try:
            if kind == "write":
                self.write(client, chunks)
                rec["store_bytes"] = len(chunks) * self.chunk
            else:
                self.read(client, chunks,
                          keep=op % int(ctx.params["keep_every"]) == 0)
                rec["load_bytes"] = len(chunks) * self.chunk
            rec["ok"] = True
        except Exception as e:   # a failed batch is a failed request
            rec["error"] = repr(e)
            ctx.say(f"{kind} batch {w}/{op} FAILED: {e!r}")
        rec["t0"], rec["t1"] = t0, time.perf_counter()
        ctx.requests.append(rec)
        return rec

    def write(self, client, chunks: list) -> None:
        _, following = _programs(self.chunk // 4)
        seed = self.ctx.seed
        steps = np.array([refsb.step(seed, c, self.gen[c] + 1)
                          for c in chunks], dtype=np.uint32)
        rows = following(tuple(self.pool[c] for c in chunks), steps)
        replies = self.put(client, chunks, rows)
        bad = [(c, r.code) for c, r in zip(chunks, replies) if not r.ok]
        for c, r, row in zip(chunks, replies, rows):
            if r.ok:
                self.gen[c] += 1
                self.pool[c], self.ver[c] = row, r.commit_ver
                self.acked[(c, r.commit_ver)] = self.gen[c]
        if bad:
            raise RuntimeError(f"write_stripes refused {bad}")

    def read(self, client, chunks: list, keep: bool) -> None:
        from tpu3fs.storage.craq import ReadReq
        from tpu3fs.storage.types import ChunkId

        ctx = self.ctx
        replies = client.batch_read(
            [ReadReq(self.chain_of(c), ChunkId(FILE_ID, c), 0, self.chunk,
                     chunk_size=self.chunk) for c in chunks],
            with_checksum=True)
        host = np.empty((len(chunks), self.chunk), dtype=np.uint8)
        for b, (c, r) in enumerate(zip(chunks, replies)):
            if (not r.ok or len(r.data) != self.chunk
                    or r.checksum.length != self.chunk):
                raise RuntimeError(f"chunk {c}: {r.code!r}, {len(r.data)} "
                                   f"bytes, checksum of "
                                   f"{r.checksum.length}")
            host[b] = np.frombuffer(r.data, dtype=np.uint8)
        crcs = np.array([r.checksum.value for r in replies], dtype=np.uint32)
        t0 = time.perf_counter()
        landed, ok = self.verifier.land(host, crcs, ctx.chip)
        ctx.crc_calls.append((t0, time.perf_counter(), len(chunks),
                              self.chunk))
        vers = [r.commit_ver for r in replies]
        self.reads.extend(zip(chunks, vers))
        self.printed.append((chunks, vers, _prints()(landed)))
        if not ok.all():
            raise RuntimeError(f"rows {np.flatnonzero(~ok).tolist()} landed "
                               f"unlike the checksum they were read with")
        with self._lock:
            if keep and len(self.kept) < int(
                    ctx.params["verify_read_batches"]):
                self.kept.append((chunks, vers, crcs, landed))

    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()

        def work(w: int, client) -> None:
            op = 0
            while time.perf_counter() - t0 < seconds:
                self.run(client, w, op)   # one in flight at the deadline
                op += 1                   # is finished and counted

        threads = [threading.Thread(target=work, args=(w, c),
                                    name=f"sb-w{w}")
                   for w, c in enumerate(self.clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        self.ctx.counters["injected_retried"] = self.injected() - self.before

    # -- the comparison -----------------------------------------------------
    def verify(self):
        ctx = self.ctx
        self.push_faults("")   # the comparison reads without them
        checks = []
        # 1. every read of the window: a version some write acknowledged
        unknown = sum(1 for key in self.reads if key not in self.acked)
        checks.append(Check("reads_of_unacked_version", unknown, 0))
        # 2. reads kept in HBM: the bytes of the generation their version
        # names, and the checksum they carried is that generation's CRC32C
        bytes_wrong = crc_wrong = rows = 0
        for chunks, vers, crcs, landed in self.kept:
            got = np.asarray(landed)
            for b, (c, v) in enumerate(zip(chunks, vers)):
                rows += 1
                g = self.acked.get((c, v))
                if g is None:
                    bytes_wrong += 1
                    crc_wrong += 1
                    continue
                want = refsb.generation(ctx.seed, c, g, self.chunk)
                bytes_wrong += not np.array_equal(got[b], want)
                crc_wrong += int(crcs[b]) != ref.crc32c(want)
        checks += [Check("read_bytes_wrong", bytes_wrong, 0),
                   Check("read_checksums_wrong", crc_wrong, 0)]
        # 3. every read, by the fingerprint the chip took of its row as it
        # landed: the generation its version names. The device verify alone
        # cannot say so of a read decoded around a lost shard, whose rebuilt
        # shard's checksum comes from the bytes it answers with
        checks.append(Check("read_fingerprints_wrong", self.prints_wrong(),
                            0))
        # 4. the device verify sees: the kept rows pass against the
        # checksums they carried and fail against any other
        blind = 0
        for chunks, vers, crcs, landed in self.kept[:4]:
            blind += int((~self.verifier.check(landed, crcs)).sum())
            blind += int(self.verifier.check(landed, crcs ^ 1).sum())
        checks.append(Check("verify_blind", blind, 0))
        for r in self.kept:   # free HBM before the shard reads
            r[3].delete()
        self.kept.clear()
        ctx.say(f"[verify] {len(self.reads)} reads by version and by "
                f"fingerprint, {rows} rows byte for byte and by checksum")
        # 5. every stripe, target by target: the shards of its latest
        # acknowledged generation, at its version, with their CRCs
        checks.append(Check("stored_shards_wrong", self.shards_wrong(), 0))
        from tpu3fs.ops import stripe

        codec = stripe.get_codec(self.k, self.m, stripe.shard_size_of(
            self.chunk, self.k))
        checks.append(Check("codecs_on_host", int(codec._use_host()), 0))
        checks.append(Check("injected_faults_absent",
                            int(ctx.counters.get("injected_retried", 0) == 0),
                            0))
        ctx.counters["degraded_reads"] = sum(
            int(s._ec_degraded._value) for s in self.storages)
        ctx.counters["read_ladder_ops"] = sum(
            int(s._read_ladder_ops._value) for s in self.storages)
        self.say_plan()
        return checks

    def say_plan(self) -> None:
        """Under --trace 1: how the window's batches went out — median
        `rpc.client` hops beneath a write batch and a read batch, and the
        stripes the write batches left to the single-stripe ladder."""
        from ..readers import span_ms

        ctx = self.ctx
        index = span_ms.index_of(ctx) if ctx.trace else None
        if index is None:
            return
        hops = {root: span_ms.read(ctx, {"root": root, "mode": "count",
                                         "pick": ["rpc.client.*"]})
                for root in ("client.write_stripes", "client.batch_read")}
        t_lo, t_hi = (t * 1e6 for t in ctx.window)
        ladder = sum(1 for row in index.by_op.get("client.write_stripe", [])
                     if t_lo <= row[4] <= t_hi)
        ctx.say(f"[plan] hops a write batch {hops['client.write_stripes']}, "
                f"a read batch {hops['client.batch_read']}; stripes on the "
                f"write ladder {ladder}; degraded reads "
                f"{ctx.counters['degraded_reads']}, read ladder ops "
                f"{ctx.counters['read_ladder_ops']}")

    def prints_wrong(self) -> int:
        """Reads whose row's fingerprint is not that of the generation its
        version names (a version no write acknowledged counts too)."""
        seed = self.ctx.seed
        want: dict = {}   # chunk -> the generations its reads name
        got = []
        for chunks, vers, fps in self.printed:
            for (c, v), fp in zip(zip(chunks, vers), np.asarray(fps)):
                g = self.acked.get((c, v))
                got.append((c, g, (int(fp[0]), int(fp[1]))))
                if g is not None:
                    want.setdefault(c, set()).add(g)
        with ThreadPoolExecutor(8) as pool:   # numpy lets go of the GIL
            ref_fp = dict(zip(want, pool.map(
                lambda c: refsb.fingerprints(seed, c, want[c], self.chunk),
                want)))
        return sum(1 for c, g, fp in got
                   if g is None or fp != ref_fp[c][g])

    def shards_wrong(self) -> int:
        from tpu3fs.storage.craq import ReadReq
        from tpu3fs.storage.types import ChunkId

        ctx = self.ctx
        routing = ctx.cluster.admin.refresh_routing()
        wrong = [0] * len(self.chains)
        errors: list = []

        def check(ci: int) -> None:
            try:
                check_chain(ci)
            except Exception as e:   # a comparison that could not run
                errors.append(e)

        def check_chain(ci: int) -> None:
            chain = routing.chains[self.chain_of(ci)]
            for c in range(ci, self.n, len(self.chains)):
                want = refsb.stored_shards(
                    refsb.generation(ctx.seed, c, self.gen[c],
                                     self.chunk).tobytes(),
                    self.chunk, self.k, self.m)
                for j, (data, crc) in enumerate(want):
                    t = chain.target_of_shard(j).target_id
                    got = ctx.view.send(
                        routing.node_of_target(t).node_id, "read_rebuild",
                        ReadReq(chain.chain_id, ChunkId(FILE_ID, c), 0, -1,
                                t))
                    wrong[ci] += not (
                        got.ok and got.commit_ver == self.ver[c]
                        and got.checksum.value == crc
                        and bytes(got.data) == data)

        threads = [threading.Thread(target=check, args=(ci,))
                   for ci in range(len(self.chains))]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        ctx.say(f"[verify] {self.n} stripes, {self.n * (self.k + self.m)} "
                f"shards target by target in {time.time() - t0:.1f}s")
        return sum(wrong)

    def close(self) -> None:
        for s in self.storages:
            s.close()
