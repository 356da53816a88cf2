"""Traffic on a KVCache that is full: the kv_turns corpus, draw and suffixes
on ONE tiered store shared by the workers (a host-RAM tier with write-back
puts over one cache client that keeps inodes), while the capacity collector
(the program's own daemon, one more CPU-pinned child of the cluster)
removes oldest-touched entries beside them.

A turn matches its document's prefix, loads what matched, puts again every
block of the document that did not come back (its rows made as set-up made
them: the prefill a real fleet would run), then puts a fresh suffix. A
short match is a miss, not a failure. What the store answered is held to the
plain cache of lib/reference_cache.py: every block exact or a miss, and an
acknowledged block there and exact or named in the collector's audit trail.
"""

from __future__ import annotations

import importlib
import os
import re
import signal
import subprocess
import time
from statistics import median

import numpy as np

from ..lib import reference as ref
from ..lib import reference_cache as refcache
from ..lib.cluster import read_target
from ..lib.harness import Check
from ..readers.rate import window_seconds
from . import kv_turns

WARM = kv_turns.WARM
GC_NAME = "kvgc"
TICK = re.compile(r"^kvcache-gc: root=\S+ (.*)$")
REMOVED = re.compile(r"^kvcache-gc: removed (\S+) mtime=\S+ bytes=(\d+)$")


def runs_of(indices: list) -> list:
    """Sorted indices -> [[i, i+1, ...], ...], each run contiguous."""
    out: list = []
    for i in indices:
        if out and out[-1][-1] + 1 == i:
            out[-1].append(i)
        else:
            out.append([i])
    return out


class Driver(kv_turns.Driver):
    def __init__(self, ctx):
        super().__init__(ctx)
        p, cfg = ctx.params, ctx.config
        self.tier_bytes = int(p.get("host_tier_bytes",
                                    cfg["host_tier_bytes"]))
        gc = cfg["gc"]
        self.gc_budget = int(p.get("gc_capacity_bytes",
                                   gc["capacity_bytes"]))
        self.gc_interval = float(p.get("gc_interval", gc["interval"]))
        self.gc_args = [
            "--connect", f"127.0.0.1:{ctx.cluster.mport}",
            "--root", self.root,
            "--capacity-bytes", str(self.gc_budget),
            "--ttl", str(gc["ttl"]), "--max-shards", str(gc["max_shards"]),
            "--interval", str(self.gc_interval), "--verbose"]
        self.gc_module = gc["module"]
        # a program whose collector cannot keep the audit trail cannot run
        # this deployment: say so before any set-up is paid for
        try:
            importlib.import_module(self.gc_module).parse_args(self.gc_args)
        except SystemExit:
            raise SystemExit("perfbench: this program's collector daemon "
                             "does not take the deployment's arguments; "
                             "nothing ran, no result")
        self.gc_log = os.path.join(ctx.cluster.logs, f"{GC_NAME}.log")
        self.shared = None
        self.tier = None
        self.log_marks = [0, 0]     # the log's size at the window's ends
        self.chunks_per_entry = 0.0  # what an entry took at set-up
        self.versions: dict = {}

    # -- the program's clients ----------------------------------------------
    def make_store(self, traced: bool):
        """Set-up's corpus put goes in as in kv_turns (own plain clients);
        the window's workers all get the one tiered store."""
        if not traced:
            return super().make_store(False)
        if self.shared is None:
            from tpu3fs.kvcache import (KVCacheClient, PrefixBlockStore,
                                        TieredKVCache)

            ctx = self.ctx
            view = ctx.new_view("tier")
            fio = view.file_client(retry=ctx.retry)
            self.storages.append(fio.storage)
            meta, fio = ctx.wrap(view.meta, "meta"), ctx.wrap(fio, "fio")
            cache = KVCacheClient(
                meta, fio, root=self.root, client_id="pb-tier",
                inode_cache=int(ctx.config["inode_cache"]))
            # the tier's own defaults, but for a rehearsal's smaller
            # dirty bound (drains small enough to land inside 2 s)
            sized = {k: int(ctx.params[k]) for k in ("dirty_max_bytes",)
                     if k in ctx.params}
            self.tier = TieredKVCache(cache, capacity_bytes=self.tier_bytes,
                                      **sized)
            self.shared = PrefixBlockStore(
                self.tier, block_tokens=self.block_tokens)
        return self.shared

    def routing_version(self) -> int:
        return int(self.ctx.cluster.admin.refresh_routing().version)

    def setup(self) -> None:
        super().setup()
        ctx = self.ctx
        self.chunks_per_entry = (
            int(self.storages[0].space_info().chunk_count)
            / max(1, sum(self.doc_blocks)))
        ctx.cluster.spawn(GC_NAME, self.gc_module, *self.gc_args)
        ctx.say(f"[collector] {self.gc_module} started: budget "
                f"{self.gc_budget} B, tick {self.gc_interval} s; host tier "
                f"{self.tier_bytes} B")

    def read_log(self, lo: int = 0, hi: int = -1) -> str:
        try:
            with open(self.gc_log, "rb") as f:
                f.seek(lo)
                raw = f.read() if hi < 0 else f.read(max(0, hi - lo))
        except OSError:
            return ""
        return raw.decode("utf-8", "replace")

    def warm(self) -> None:
        super().warm()
        if not self.tier.flush(float(self.ctx.params["flush_timeout_s"])):
            raise RuntimeError("the warm-up's puts never drained")
        # the window opens on a collector that has made a whole pass
        proc = self.ctx.cluster.procs[GC_NAME]
        deadline = time.time() + 90
        while not any(TICK.match(ln) for ln in self.read_log().splitlines()):
            if proc.poll() is not None:
                raise RuntimeError(
                    f"the collector exited with {proc.returncode}:\n"
                    + self.read_log()[-2000:])
            if time.time() > deadline:
                raise RuntimeError("the collector never finished a pass")
            time.sleep(0.1)
        self.versions["setup"] = self.routing_version()

    # -- one turn -----------------------------------------------------------
    def turn(self, store, turn: int, record: bool = True) -> None:
        from ..lib.device import fingerprint

        ctx, jax = self.ctx, self.ctx.jax
        doc, nsuf = self.plan(turn) if turn < WARM else (
            (turn - WARM) % len(self.doc_blocks), 1)
        tokens = self.doc_tokens[doc]
        nb = self.doc_blocks[doc]
        rec = {"id": turn, "doc": doc, "suffix_blocks": nsuf, "ok": False,
               "load_bytes": 0, "store_bytes": 0, "refill_bytes": 0,
               "doc_blocks": nb, "missed_blocks": nb, "phases": {}}
        ctx.spans.set_request(turn)
        got: dict = {}      # block number -> array in HBM
        fps: dict = {}
        refilled: list = []
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("pb:turn.load"):
                match = store.match_prefix(tokens)
                blocks = store.get_blocks(
                    tokens, count=match.blocks, device=ctx.chip) \
                    if match.blocks else []
            t_got = time.perf_counter()
            got = {i: b for i, b in enumerate(blocks) if b is not None}
            with jax.profiler.TraceAnnotation("pb:turn.land"):
                jax.block_until_ready(list(got.values()))
            t_land = time.perf_counter()
            fps = {i: fingerprint(b) for i, b in got.items()}
            rec["load_bytes"] = len(got) * self.block_bytes
            rec["missed_blocks"] = nb - len(got)
            rec["phases"]["land"] = t_land - t_got
            missing = [i for i in range(nb) if i not in got]
            if missing:
                # prefilled again: the rows as set-up made them
                with jax.profiler.TraceAnnotation("pb:turn.refill"):
                    rows = self.make_doc(doc)[1]
                    for run in runs_of(missing):
                        wrote = store.append_blocks(
                            tokens, [rows[i] for i in run],
                            start_block=run[0])
                        rec["refill_bytes"] += wrote * self.block_bytes
                    refilled = missing
            with jax.profiler.TraceAnnotation("pb:turn.store"):
                rows = self.suffix_rows(turn, nsuf)
                seq = tokens + self.suffix_tokens(turn, nsuf)
                wrote = store.append_blocks(seq, rows, start_block=nb)
            if wrote != nsuf:
                raise RuntimeError(f"turn {turn}: stored {wrote} of "
                                   f"{nsuf} suffix blocks")
            rec["store_bytes"] = (nsuf * self.block_bytes
                                  + rec["refill_bytes"])
            jax.block_until_ready(list(fps.values()))
            rec["ok"] = True
        except Exception as e:  # a failed turn is a failed request
            rec["error"] = repr(e)
            ctx.say(f"turn {turn} FAILED: {e!r}")
            got, fps = {}, {}
        rec["t0"], rec["t1"] = t0, time.perf_counter()
        if not record:
            if not rec["ok"]:
                raise RuntimeError(f"warm-up turn failed: {rec['error']}")
            return
        with self._lock:
            ctx.requests.append(rec)
            self.turns[turn] = {"doc": doc, "fps": fps, "blocks": got,
                                "suffix_blocks": nsuf, "ok": rec["ok"],
                                "refilled": refilled}
            self.pool.append(turn)
            self.pool_bytes += rec["load_bytes"]
            while self.pool_bytes > self.pool_cap and len(self.pool) > 1:
                old = self.turns[self.pool.popleft()]
                self.pool_bytes -= len(old["blocks"]) * self.block_bytes
                old["blocks"] = None   # leaves HBM; its fingerprints stay

    def window(self, seconds: float) -> None:
        self.log_marks[0] = os.path.getsize(self.gc_log)
        super().window(seconds)
        self.log_marks[1] = os.path.getsize(self.gc_log)
        self.versions["window_end"] = self.routing_version()

    # -- the collector's own account ----------------------------------------
    def stop_collector(self) -> int:
        """SIGTERM: the daemon stops between two removals, so its trail is
        whole. -> 1 if it had to be killed (the trail may then lack one)."""
        proc = self.ctx.cluster.procs[GC_NAME]
        if proc.poll() is not None:
            return 1    # it died on its own: the trail proves nothing
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=20)
            return 0
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)
            return 1

    @staticmethod
    def ticks_of(text: str) -> list:
        out = []
        for line in text.splitlines():
            m = TICK.match(line)
            if m:
                out.append({k: float(v) for k, v in
                            (kv.split("=", 1) for kv in m.group(1).split())})
        return out

    # -- the comparison -----------------------------------------------------
    def verify(self):
        ctx = self.ctx
        p = ctx.params
        rng = np.random.default_rng([ctx.seed, 9])
        done = sorted(t for t, r in self.turns.items() if r["ok"])
        # 1. exact or a miss: every block any load returned, by the
        # fingerprint taken on the chip as it landed
        doc_fp: dict = {}
        fp_wrong = fp_seen = 0
        for t in done:
            r = self.turns[t]
            d = r["doc"]
            if d not in doc_fp:
                doc_fp[d] = [ref.fingerprint_np(b)
                             for b in self.make_doc(d)[1]]
            for i, f in r["fps"].items():
                fp_seen += 1
                fp_wrong += tuple(int(x) for x in np.asarray(f)) \
                    != doc_fp[d][i]
        # 2. bytes as they lie in HBM: sampled resident turns, the longest
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
            for i, b in r["blocks"].items():
                hbm_seen += 1
                have = np.asarray(b)
                if (have.dtype != np.uint16 or have.shape != self.shape
                        or have.tobytes() != rows[i].tobytes()):
                    hbm_wrong += 1
        for r in self.turns.values():   # free HBM before the read-backs
            r["blocks"] = None
        self.pool.clear()
        checks = [Check("loaded_fingerprints_wrong", fp_wrong, 0),
                  Check("loaded_blocks_wrong_in_hbm", hbm_wrong, 0)]
        ctx.say(f"[verify] {fp_seen} loaded blocks by fingerprint, "
                f"{hbm_seen} byte for byte in HBM ({len(sample)} turns)")
        # 3. the collector stops, then the barrier: flush() returns true
        killed = self.stop_collector()
        drained = self.tier.flush(float(p["flush_timeout_s"]))
        checks.append(Check("flush_incomplete",
                            int(not drained) + int(self.tier.flush_poisoned),
                            0))
        log = self.read_log()
        named = {m.group(1) for m in map(REMOVED.match, log.splitlines())
                 if m}
        in_window = self.read_log(*self.log_marks)
        inside = self.ticks_of(in_window)
        ticks = self.ticks_of(log)
        # 4. acknowledged blocks of sampled turns through a fresh client:
        # there and exact, or named in the trail
        entries = self.acknowledged(done, rng)
        checks += self.read_back_or_named(entries, named)
        # 5. the collector did its work inside the window, and held the
        # budget: what its last whole pass left against the budget plus
        # one tick's puts
        removed_in = sum(1 for ln in in_window.splitlines()
                         if REMOVED.match(ln))
        checks.append(Check("gc_idle", int(removed_in == 0), 0))
        window_s = max(1e-9, window_seconds(ctx))
        stored = sum(r["store_bytes"] for r in ctx.requests if r["ok"])
        period = window_s / max(1, len(inside))
        allowed = self.gc_budget + stored / window_s * period
        # the pass SIGTERM cut prints no tick: the last line is a whole one
        last = ticks[-1]["resident"] if ticks else float("inf")
        checks.append(Check("capacity_overrun",
                            int(last > allowed) + killed, 0))
        # 6. the counters: the device codec ran, no read was degraded
        from tpu3fs.ops import stripe

        codecs = list(stripe._codecs.values())
        host = sum(1 for c in codecs if c._use_host())
        checks.append(Check("codecs_on_host", host + (0 if codecs else 1), 0))
        degraded = sum(int(s._ec_degraded._value) for s in self.storages)
        checks.append(Check("degraded_reads", degraded, 0))
        # reported, not compared
        blocks = sum(r["doc_blocks"] for r in ctx.requests if r["ok"])
        missed = sum(r["missed_blocks"] for r in ctx.requests if r["ok"])
        held = self.chunks_held()
        ctx.counters.update(
            degraded_reads=degraded, codecs=len(codecs),
            gc_ticks_in_window=len(inside), gc_removed_in_window=removed_in,
            gc_removes_per_s=removed_in / window_s,
            miss_share=missed / max(1, blocks),
            routing_versions=dict(self.versions), **held)
        if inside:
            ctx.counters["gc_scan_s"] = median(t["scan_s"] for t in inside)
            ctx.counters["gc_remove_s"] = median(t["remove_s"]
                                                 for t in inside)
        ctx.say("[collector] ticks inside the window, removed/left/scan_s: "
                + " ".join(f"{int(t['ttl_removed'] + t['capacity_removed'])}"
                           f"/{int(t['entries'])}/{t['scan_s']:.2f}"
                           for t in inside))
        ctx.say(f"[collector] {len(inside)} ticks inside the window removed "
                f"{removed_in} entries ({len(named)} named in all); last "
                f"pass left {last:.0f} B (allowed {allowed:.0f}); scan "
                f"{ctx.counters.get('gc_scan_s', 0):.2f} s a pass")
        ctx.say(f"[reported] {missed} of {blocks} blocks missed "
                f"({100 * missed / max(1, blocks):.1f}%); targets hold "
                f"{held['chunks_held']} chunks, "
                f"{held['chunks_per_entry']:.2f} an entry at set-up: "
                f"{held['entries_worth_held']:.0f} entries' worth against "
                f"{ticks[-1]['entries'] if ticks else 0:.0f} resident "
                f"(budget {self.gc_budget // self.block_bytes} blocks); "
                f"routing version {self.versions}")
        return checks

    def acknowledged(self, done: list, rng) -> list:
        """(turn, path, entry bytes) of every block the store acknowledged
        in the sampled turns: the suffix, and what the turn put again."""
        p = self.ctx.params
        pick = set(rng.permutation(done)[:int(p["verify_store_turns"])]
                   .tolist())
        if done:
            pick.add(max(done, key=lambda t: self.turns[t]["suffix_blocks"]
                         + len(self.turns[t]["refilled"])))
        out = []
        for t in sorted(pick):
            r = self.turns[t]
            nsuf, d = r["suffix_blocks"], r["doc"]
            seq = self.doc_tokens[d] + self.suffix_tokens(t, nsuf)
            keys = ref.chain_keys(seq, self.block_tokens)
            rows = [np.asarray(x) for x in self.suffix_rows(t, nsuf)]
            out += [(t, ref.entry_path(self.root, k), ref.encode_entry(x))
                    for k, x in zip(keys[self.doc_blocks[d]:], rows)]
            if r["refilled"]:
                doc_rows = self.make_doc(d)[1]
                out += [(t, ref.entry_path(self.root, keys[i]),
                         ref.encode_entry(doc_rows[i]))
                        for i in r["refilled"]]
        return out

    def read_back_or_named(self, entries: list, named: set) -> list:
        from tpu3fs.storage.types import ChunkId
        from tpu3fs.utils.result import FsError

        ctx = self.ctx
        fio = ctx.view.file_client(retry=ctx.retry)
        self.storages.append(fio.storage)
        inodes = ctx.view.meta.batch_stat_by_path([e[1] for e in entries])
        wrong = gone = 0
        present = []
        for (turn, path, want), ino in zip(entries, inodes):
            have = refcache.MISS
            if ino is not None:
                try:   # an error is a wrong block, counted, not raised
                    have = bytes(fio.read(ino, 0, ino.length))
                except FsError as e:
                    ctx.say(f"[verify] read-back of {path}: {e!r}")
                    have = b""
            ok = refcache.verdict(have, want, path in named)
            wrong += not ok
            gone += have is refcache.MISS
            if ok and have is not refcache.MISS:
                present.append((turn, path, want, ino))
        checks = [Check("stored_blocks_wrong", wrong, 0)]
        chain_spec = next(c for t in ctx.config["cluster"]["tables"]
                          for c in t["chains"] if c.get("ec_k"))
        k, m = chain_spec["ec_k"], chain_spec["ec_m"]
        routing = ctx.cluster.admin.refresh_routing()
        chain = routing.chains[chain_spec["chain_id"]]
        # the stored form of the newest blocks that are there
        present.sort(key=lambda e: -e[0])
        shards_wrong = shards_seen = 0
        for turn, path, want, ino in present[
                :int(ctx.params["verify_shard_blocks"])]:
            gold = ref.stripe_shards(want, ctx.config["chunk_size"], k, m)
            for j in range(k + m):
                have = read_target(
                    ctx.view, routing, chain.chain_id, ChunkId(ino.id, 0),
                    chain.target_of_shard(j).target_id)
                shards_seen += 1
                if gold[j]:
                    shards_wrong += have != gold[j]
                elif have:
                    shards_wrong += 1   # past the block's end: empty
        if entries and not shards_seen:
            shards_wrong += 1   # nothing left to look at proves nothing
        ctx.say(f"[verify] {len(entries)} acknowledged blocks read back "
                f"({gone} gone, all named: {wrong == 0}), {shards_seen} "
                f"shards against an independent RS({k},{m})")
        checks.append(Check("stored_shards_wrong", shards_wrong, 0))
        return checks

    def chunks_held(self) -> dict:
        """What the targets hold now, in chunks, against what an entry
        took at set-up: whether removed entries' chunks are reclaimed."""
        try:
            now = int(self.storages[0].space_info().chunk_count)
        except Exception as e:   # a report, not a comparison
            self.ctx.say(f"[reported] space_info: {e!r}")
            now = 0
        per_entry = self.chunks_per_entry
        return {"chunks_held": now, "chunks_per_entry": per_entry,
                "entries_worth_held": now / per_entry if per_entry else 0.0}

    def close(self) -> None:
        if self.tier is not None:
            self.tier.close(flush=False)
        super().close()
