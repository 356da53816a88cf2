"""Traffic on the KVCache: closed-loop workers that each load a shared
document's whole prefix into HBM and, where the mix says so, store a fresh
suffix whose rows were made on the chip.

Every seed gets the same work: the documents' lengths are fixed quantiles of
the mix's lognormal, bound to their popularity ranks by the mix's own
`layout_seed`; the turns come in rounds of `round_turns`, each round holding
the same multiset of (document, suffix length) pairs, Zipf by apportionment.
The seed makes the bytes, the token ids and the order inside each round.
"""

from __future__ import annotations

import collections
import threading
import time

import numpy as np

from ..lib import reference as ref
from ..lib.cluster import read_target
from ..lib.harness import Check


WARM = 1_000_000_000   # turn numbers of the warm-up, outside any window


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        p, cfg = ctx.params, ctx.config
        self.shape = tuple(cfg["block"]["shape"])
        self.block_tokens = int(cfg["block"]["tokens"])
        self.block_bytes = int(np.prod(self.shape)) * 2
        self.root = f"/kv/seed{ctx.seed}"
        self.workers = int(p["workers"])
        self.store_suffix = bool(p["store_suffix"])
        self.pool_cap = int(p.get("resident_pool_bytes",
                                  cfg["resident_pool_bytes"]))
        n = int(p["docs"])
        lengths = ref.quantiles_lognormal(
            n, p["doc_tokens_median"], p["doc_tokens_sigma"],
            p["doc_tokens_min"], p["doc_tokens_max"], self.block_tokens)
        layout = np.random.default_rng(int(p["layout_seed"]))
        order = layout.permutation(n)
        # document d has popularity rank d and this many blocks
        self.doc_blocks = [lengths[order[d]] // self.block_tokens
                           for d in range(n)]
        weights = [1.0 / (d + 1) ** float(p["zipf_alpha"])
                   for d in range(n)]
        rt = int(p["round_turns"])
        docs = [d for d, c in enumerate(ref.apportion(weights, rt))
                for _ in range(c)]
        if self.store_suffix:
            suffix = ref.quantiles_lognormal(
                rt, p["suffix_tokens_median"], p["suffix_tokens_sigma"],
                p["suffix_tokens_min"], p["suffix_tokens_max"],
                self.block_tokens)
            suffix = [suffix[i] // self.block_tokens
                      for i in layout.permutation(rt)]
        else:
            suffix = [0] * rt
        self.round = list(zip(docs, suffix))
        self.round_orders: dict = {}
        self.doc_tokens: list = []
        self._next_turn = 0
        self._lock = threading.Lock()
        self.pool: collections.deque = collections.deque()
        self.pool_bytes = 0
        self.turns: dict = {}       # turn -> record kept for verify()
        self.storages: list = []    # unproxied StorageClients (counters)

    # -- what the seed fixes ------------------------------------------------
    def plan(self, turn: int) -> tuple:
        """-> (document, suffix blocks) of turn number `turn`."""
        rt = len(self.round)
        r = turn // rt
        with self._lock:
            order = self.round_orders.get(r)
            if order is None:
                order = np.random.default_rng(
                    [self.ctx.seed, 3, r]).permutation(rt)
                self.round_orders[r] = order
        return self.round[int(order[turn % rt])]

    def make_doc(self, d: int) -> tuple:
        nb = self.doc_blocks[d]
        tokens = np.random.default_rng([self.ctx.seed, 1, d]).integers(
            0, 1 << 40, nb * self.block_tokens).tolist()
        rows = np.random.default_rng([self.ctx.seed, 2, d]).integers(
            0, 1 << 16, (nb,) + self.shape, dtype=np.uint16)
        return tokens, rows

    def suffix_tokens(self, turn: int, nb: int) -> list:
        return np.random.default_rng([self.ctx.seed, 4, turn]).integers(
            0, 1 << 40, nb * self.block_tokens).tolist()

    def suffix_rows(self, turn: int, nb: int) -> list:
        """Rows of a fresh suffix, made on the chip from the seed."""
        from ..lib.device import random_block

        seed = np.uint32(self.ctx.seed % (1 << 31))
        return [random_block(seed, np.uint32(turn), np.uint32(j), self.shape)
                for j in range(nb)]

    # -- the program's clients ----------------------------------------------
    def make_store(self, traced: bool):
        from tpu3fs.kvcache import KVCacheClient, PrefixBlockStore

        ctx = self.ctx
        view = ctx.new_view(f"w{len(self.storages)}")
        fio = view.file_client(retry=ctx.retry)
        self.storages.append(fio.storage)
        meta = view.meta
        if traced:
            meta, fio = ctx.wrap(meta, "meta"), ctx.wrap(fio, "fio")
        cache = KVCacheClient(meta, fio, root=self.root,
                              client_id=f"pb-{len(self.storages)}")
        return PrefixBlockStore(cache, block_tokens=self.block_tokens)

    def setup(self) -> None:
        ctx = self.ctx
        t0 = time.time()
        for d in range(len(self.doc_blocks)):
            self.doc_tokens.append(self.make_doc(d)[0])
        # the corpus goes in through as many clients as the window has
        # workers, longest documents first: set-up is paid by every run
        order = sorted(range(len(self.doc_blocks)),
                       key=lambda d: -self.doc_blocks[d])
        errors: list = []

        def put(docs) -> None:
            store = self.make_store(traced=False)
            try:
                for d in docs:
                    tokens, rows = self.make_doc(d)
                    wrote = store.append_blocks(tokens, list(rows))
                    if wrote != len(rows):
                        raise RuntimeError(f"corpus document {d}: stored "
                                           f"{wrote} of {len(rows)} blocks")
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=put,
                                    args=(order[w::self.workers],))
                   for w in range(self.workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        nb = sum(self.doc_blocks)
        ctx.say(f"[corpus] {len(self.doc_blocks)} documents, {nb} blocks, "
                f"{nb * self.block_bytes >> 20} MiB put in "
                f"{time.time() - t0:.1f}s")
        inode = ctx.view.meta.stat(ref.entry_path(
            self.root, ref.chain_keys(self.doc_tokens[0], self.block_tokens)[0]))
        want = [c["chain_id"] for t in ctx.config["cluster"]["tables"]
                for c in t["chains"]]
        if list(inode.layout.chains) != want:
            raise RuntimeError(f"a kvcache file landed on chains "
                               f"{inode.layout.chains}, not {want}")
        self.stores = [self.make_store(traced=True)
                       for _ in range(self.workers)]
        if ctx.trace and self.store_suffix:
            from ..lib import codecwatch

            codecwatch.watch(ctx)

    def warm(self) -> None:
        """Every shape the window uses: the fingerprint, the row maker, the
        encode program, and one load of each worker's client."""
        for w, store in enumerate(self.stores):
            self.turn(store, WARM + w, record=False)

    # -- one turn -----------------------------------------------------------
    def turn(self, store, turn: int, record: bool = True) -> None:
        from ..lib.device import fingerprint

        ctx, jax = self.ctx, self.ctx.jax
        doc, nsuf = self.plan(turn) if turn < WARM else (
            (turn - WARM) % len(self.doc_blocks),
            1 if self.store_suffix else 0)
        tokens = self.doc_tokens[doc]
        nb = self.doc_blocks[doc]
        rec = {"id": turn, "doc": doc, "suffix_blocks": nsuf, "ok": False,
               "load_bytes": 0, "store_bytes": 0, "phases": {}}
        ctx.spans.set_request(turn)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("pb:turn.load"):
                match = store.match_prefix(tokens)
                blocks = store.get_blocks(tokens, device=ctx.chip)
            t_got = time.perf_counter()
            if match.blocks != nb or any(b is None for b in blocks):
                raise RuntimeError(f"turn {turn}: document {doc} matched "
                                   f"{match.blocks} of {nb} blocks")
            with jax.profiler.TraceAnnotation("pb:turn.land"):
                jax.block_until_ready(blocks)
            t_land = time.perf_counter()
            fps = [fingerprint(b) for b in blocks]
            rec["load_bytes"] = nb * self.block_bytes
            rec["phases"]["land"] = t_land - t_got
            if nsuf:
                with jax.profiler.TraceAnnotation("pb:turn.store"):
                    rows = self.suffix_rows(turn, nsuf)
                    seq = tokens + self.suffix_tokens(turn, nsuf)
                    wrote = store.append_blocks(seq, rows, start_block=nb)
                if wrote != nsuf:
                    raise RuntimeError(f"turn {turn}: stored {wrote} of "
                                       f"{nsuf} suffix blocks")
                rec["store_bytes"] = nsuf * self.block_bytes
            jax.block_until_ready(fps)
            rec["ok"] = True
        except Exception as e:  # a failed turn is a failed request
            rec["error"] = repr(e)
            ctx.say(f"turn {turn} FAILED: {e!r}")
            blocks, fps = [], []
        rec["t0"], rec["t1"] = t0, time.perf_counter()
        if not record:
            if not rec["ok"]:
                raise RuntimeError(f"warm-up turn failed: {rec['error']}")
            return
        with self._lock:
            ctx.requests.append(rec)
            self.turns[turn] = {"doc": doc, "fps": fps, "blocks": blocks,
                                "suffix_blocks": nsuf, "ok": rec["ok"]}
            self.pool.append(turn)
            self.pool_bytes += rec["load_bytes"]
            while self.pool_bytes > self.pool_cap and len(self.pool) > 1:
                old = self.turns[self.pool.popleft()]
                self.pool_bytes -= len(old["blocks"]) * self.block_bytes
                old["blocks"] = None   # leaves HBM; its fingerprints stay

    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()

        def work(store) -> None:
            while time.perf_counter() - t0 < seconds:
                with self._lock:
                    turn = self._next_turn
                    self._next_turn += 1
                self.turn(store, turn)   # one in flight at the deadline
                                         # is finished and counted

        threads = [threading.Thread(target=work, args=(s,), name=f"kv-w{i}")
                   for i, s in enumerate(self.stores)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    # -- the comparison -----------------------------------------------------
    def verify(self):
        ctx, jax = self.ctx, self.ctx.jax
        p = ctx.params
        rng = np.random.default_rng([ctx.seed, 9])
        done = sorted(t for t, r in self.turns.items() if r["ok"])
        # 1. every loaded block of every turn: its fingerprint, taken on
        # the chip when it landed, against the reference document's
        doc_fp = {}
        fp_wrong = fp_seen = 0
        for t in done:
            r = self.turns[t]
            d = r["doc"]
            if d not in doc_fp:
                rows = self.make_doc(d)[1]
                doc_fp[d] = [ref.fingerprint_np(b) for b in rows]
            got = [tuple(int(x) for x in np.asarray(f)) for f in r["fps"]]
            fp_seen += len(got)
            fp_wrong += sum(1 for g, w in zip(got, doc_fp[d]) if g != w)
            fp_wrong += abs(len(got) - len(doc_fp[d]))
        # 2. bytes as they lie in HBM: a sample of the turns still
        # resident, the longest among them, byte for byte
        resident = [t for t in done if self.turns[t]["blocks"]]
        sample = set(rng.permutation(resident)[:int(p["verify_turns"])]
                     .tolist())
        if resident:
            sample.add(max(resident,
                           key=lambda t: len(self.turns[t]["blocks"])))
        hbm_wrong = hbm_seen = 0
        for t in sorted(sample):
            r = self.turns[t]
            rows = self.make_doc(r["doc"])[1]
            for b, want in zip(r["blocks"], rows):
                hbm_seen += 1
                got = np.asarray(b)
                if (got.dtype != np.uint16 or got.shape != self.shape
                        or got.tobytes() != want.tobytes()):
                    hbm_wrong += 1
        for r in self.turns.values():   # free HBM before the read-backs
            r["blocks"] = None
        self.pool.clear()
        checks = [Check("loaded_fingerprints_wrong", fp_wrong, 0),
                  Check("loaded_blocks_wrong_in_hbm", hbm_wrong, 0)]
        ctx.say(f"[verify] {fp_seen} loaded blocks by fingerprint, "
                f"{hbm_seen} byte for byte in HBM ({len(sample)} turns)")
        # 3. what the window stored and the store acknowledged: a sample
        # of turns read back through a fresh client; 4. their stored form,
        # all 16 shards against an independent encode
        stored = [t for t in done if self.turns[t]["suffix_blocks"]]
        entries = []   # (path, entry bytes) of sampled blocks
        if stored:
            pick = set(rng.permutation(stored)[:int(p["verify_store_turns"])]
                       .tolist())
            pick.add(max(stored,
                         key=lambda t: self.turns[t]["suffix_blocks"]))
            for t in sorted(pick):
                r = self.turns[t]
                nsuf, d = r["suffix_blocks"], r["doc"]
                seq = self.doc_tokens[d] + self.suffix_tokens(t, nsuf)
                keys = ref.chain_keys(seq, self.block_tokens)[
                    self.doc_blocks[d]:]
                rows = [np.asarray(x) for x in self.suffix_rows(t, nsuf)]
                entries += [(ref.entry_path(self.root, k),
                             ref.encode_entry(x)) for k, x in zip(keys, rows)]
        else:   # a mix that stores nothing: the corpus set-up stored
            for d in rng.permutation(len(self.doc_blocks))[:2].tolist():
                tokens, rows = self.make_doc(d)
                keys = ref.chain_keys(tokens, self.block_tokens)
                entries += [(ref.entry_path(self.root, k),
                             ref.encode_entry(x))
                            for k, x in list(zip(keys, rows))[:4]]
        checks += self.read_back(entries, rng)
        # 5. the counters: the device codec ran, no read was degraded
        from tpu3fs.ops import stripe

        codecs = list(stripe._codecs.values())
        host = sum(1 for c in codecs if c._use_host())
        checks.append(Check("codecs_on_host", host + (0 if codecs else 1), 0))
        degraded = sum(int(s._ec_degraded._value) for s in self.storages)
        checks.append(Check("degraded_reads", degraded, 0))
        ctx.counters.update(degraded_reads=degraded, codecs=len(codecs))
        return checks

    def read_back(self, entries: list, rng) -> list:
        from tpu3fs.storage.types import ChunkId

        ctx = self.ctx
        fio = ctx.view.file_client(retry=ctx.retry)
        self.storages.append(fio.storage)
        meta = ctx.view.meta
        inodes = meta.batch_stat_by_path([p for p, _ in entries])
        wrong = 0
        for (path, want), ino in zip(entries, inodes):
            if ino is None or bytes(fio.read(ino, 0, ino.length)) != want:
                wrong += 1
        checks = [Check("stored_blocks_wrong", wrong, 0)]
        chain_spec = next(c for t in ctx.config["cluster"]["tables"]
                          for c in t["chains"] if c.get("ec_k"))
        k, m = chain_spec["ec_k"], chain_spec["ec_m"]
        routing = ctx.cluster.admin.refresh_routing()
        chain = routing.chains[chain_spec["chain_id"]]
        n_shard = int(ctx.params["verify_shard_blocks"])
        pick = rng.permutation(len(entries))[:n_shard].tolist()
        shards_wrong = shards_seen = 0
        for i in pick:
            (path, want), ino = entries[i], inodes[i]
            if ino is None:
                shards_wrong += k + m
                continue
            gold = ref.stripe_shards(want, ctx.config["chunk_size"], k, m)
            for j in range(k + m):
                got = read_target(
                    ctx.view, routing, chain.chain_id, ChunkId(ino.id, 0),
                    chain.target_of_shard(j).target_id)
                shards_seen += 1
                if gold[j]:
                    shards_wrong += got != gold[j]
                elif got:
                    shards_wrong += 1   # past the block's end: empty
        ctx.say(f"[verify] {len(entries)} stored blocks read back, "
                f"{shards_seen} shards against an independent RS({k},{m})")
        checks.append(Check("stored_shards_wrong", shards_wrong, 0))
        return checks

    def close(self) -> None:
        for s in self.storages:
            s.close()
