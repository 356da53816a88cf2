"""Traffic on a KVCache whose chain loses a storage node and gets an empty
replacement: the kv_turns corpus, draw, suffixes and workers, plus one
conductor thread that, by the window's own clock, kills the configuration's
node (SIGKILL to its process group), later removes its directory and starts
a new process under the same node id, and stamps what routing says
meanwhile: the kill, the node's targets out of SERVING, the first of them
SYNCING, all targets SERVING and up to date again.

The window closes at `seconds` whatever the chain's state; the comparison
waits for recovery first (outside every metric) and then holds the system
to the configuration's guarantees: nothing failed, every loaded block exact
whether read whole or decoded, every acknowledged put exact during the
outage and after it, all 16 shards of every sampled stripe on their targets
and equal to the independent encode, the rebuilt targets' shards among
them, and no degraded decode once recovery is done.
"""

from __future__ import annotations

import os
import re
import shutil
import signal
import threading
import time

import numpy as np

from ..lib import cluster as cl
from ..lib import reference as ref
from ..lib.harness import Check
from . import kv_turns

PASS = re.compile(r"^ec\.rebuild target=(\d+) (.*)$")
PHASES = ("before", "outage", "rebuild")


def parse_passes(text: str) -> list:
    """The storage service's one line a finished target pass ->
    [{"target": id, key: number, ...}]."""
    out = []
    for line in text.splitlines():
        m = PASS.match(line.strip())
        if m:
            row = {k: float(v) for k, v in
                   (kv.split("=", 1) for kv in m.group(2).split())}
            out.append({"target": int(m.group(1)), **row})
    return out


def phase_of(t_ack: float, t_kill, t_syncing) -> str:
    """Which part of the timeline a put was acknowledged in."""
    if t_kill is None or t_ack < t_kill:
        return "before"
    if t_syncing is None or t_ack < t_syncing:
        return "outage"
    return "rebuild"


def sample_by_phase(turns: dict, per_phase: int, rng) -> list:
    """{phase: [turn, ...]} -> at most per_phase turns of each phase, in
    turn order; a phase that holds fewer gives what it has."""
    out = []
    for phase in PHASES:
        have = sorted(turns.get(phase, []))
        out += sorted(rng.permutation(have)[:per_phase].tolist())
    return out


class Timeline:
    """What the conductor saw, on the window's clock (seconds from its
    opening); a stamp is set once."""

    def __init__(self):
        self.at: dict = {}

    def stamp(self, name: str, t: float) -> bool:
        if name in self.at:
            return False
        self.at[name] = t
        return True

    def get(self, name: str):
        return self.at.get(name)

    def between(self, a: str, b: str):
        if a in self.at and b in self.at:
            return self.at[b] - self.at[a]
        return None

    def observe(self, t: float, mine: list, everyone: list) -> None:
        """One look at routing: `mine` the (public, local) state names of
        the lost node's targets, `everyone` those of all targets."""
        if "t_kill" not in self.at:
            return
        if "t_offline" not in self.at:
            if mine and all(pub != "SERVING" for pub, _ in mine):
                self.stamp("t_offline", t)
            return
        if any(pub == "SYNCING" for pub, _ in mine):
            self.stamp("t_syncing", t)
        if everyone and all(pub == "SERVING" and loc == "UPTODATE"
                            for pub, loc in everyone):
            self.stamp("t_recovered", t)


class Driver(kv_turns.Driver):
    def __init__(self, ctx):
        super().__init__(ctx)
        from tpu3fs.client.storage_client import RetryOptions

        # a program whose put gives up before mgmtd has spoken on a dead
        # node cannot keep this deployment's first guarantee: say so
        # before any set-up is paid for
        if "routing_wait_s" not in getattr(RetryOptions,
                                           "__dataclass_fields__", {}):
            raise SystemExit("perfbench: this program's client does not "
                             "wait for mgmtd's verdict on a node that "
                             "stopped answering; it cannot run this "
                             "deployment; nothing ran, no result")
        p, failure = ctx.params, ctx.config["failure"]
        self.node = int(failure["node"])
        self.kill_sig = getattr(signal, failure["signal"])
        self.kill_at = float(p.get("kill_at_s", failure["kill_at_s"]))
        self.restart_at = float(p.get("restart_at_s",
                                      failure["restart_at_s"]))
        self.recover_timeout = float(p["recover_timeout_s"])
        self.timeline = Timeline()
        self.t_open = 0.0           # perf_counter at the window's opening
        self.conductor = None
        self.conductor_error = ""
        self.window_closed = threading.Event()
        self.give_up = threading.Event()
        self.conducted = threading.Event()   # recovered, given up or failed
        self.dead_pids: list = []
        self.degraded_wrong = 0     # the conductor's read-back in the outage
        self.degraded_seen = 0
        self.degraded_decodes = 0
        self.lost_targets: list = []
        spec = next(c for t in ctx.config["cluster"]["tables"]
                    for c in t["chains"] if c.get("ec_k"))
        self.chain_id = int(spec["chain_id"])
        self.k, self.m = int(spec["ec_k"]), int(spec["ec_m"])

    # -- set-up ---------------------------------------------------------------
    def setup(self) -> None:
        super().setup()
        ctx = self.ctx
        routing = ctx.cluster.admin.refresh_routing()
        chain = routing.chains[self.chain_id]
        self.lost_targets = [
            (j, chain.target_of_shard(j).target_id)
            for j in range(self.k + self.m)
            if routing.node_of_target(
                chain.target_of_shard(j).target_id).node_id == self.node]
        if not 0 < len(self.lost_targets) <= self.m:
            raise RuntimeError(
                f"node {self.node} holds shards "
                f"{[j for j, _ in self.lost_targets]} of RS({self.k},"
                f"{self.m}): the chain cannot bear its loss")
        ctx.say(f"[failure] node {self.node} holds shards "
                f"{[j for j, _ in self.lost_targets]}; kill at "
                f"{self.kill_at} s, empty restart at {self.restart_at} s")
        if ctx.trace:
            from ..lib import decodewatch

            decodewatch.watch(ctx)

    def warm(self) -> None:
        """kv_turns' shapes, and the decode the outage will use: one
        stripe a dispatch, the block's one lost shard out of 12 survivors
        (every loss pattern that loses as many shares the program)."""
        super().warm()
        from tpu3fs.ops import stripe

        ctx = self.ctx
        S = stripe.shard_size_of(ctx.config["chunk_size"], self.k)
        codec = stripe.get_codec(self.k, self.m, S)
        covered = -(-self.block_entry_bytes() // S)
        lost = [j for j, _ in self.lost_targets if j < covered]
        if lost:
            dead = {j for j, _ in self.lost_targets}
            present = [j for j in range(self.k + self.m)
                       if j not in dead][:self.k]
            codec.reconstruct_batch(
                present, lost, np.zeros((1, self.k, S), dtype=np.uint8))

    def block_entry_bytes(self) -> int:
        """A stored block as the store frames it (the reference's own
        framing of an all-zero block)."""
        return len(ref.encode_entry(np.zeros(self.shape, dtype=np.uint16)))

    # -- the window -----------------------------------------------------------
    def clock(self) -> float:
        return time.perf_counter() - self.t_open

    def window(self, seconds: float) -> None:
        self.t_open = time.perf_counter()
        self.conductor = threading.Thread(target=self.conduct,
                                          name="kv-conductor", daemon=True)
        self.conductor.start()
        super().window(seconds)
        self.window_closed.set()
        self.ctx.counters["rb_recovered_in_window"] = int(
            self.timeline.get("t_recovered") is not None)

    def states(self, routing) -> tuple:
        mine, everyone = [], []
        lost = {tid for _, tid in self.lost_targets}
        for chain in routing.chains.values():
            for t in chain.targets:
                pair = (t.public_state.name, t.local_state.name)
                everyone.append(pair)
                if t.target_id in lost:
                    mine.append(pair)
        return mine, everyone

    def conduct(self) -> None:
        """The failure, by the window's clock; goes on past the window's
        end until the chain has recovered or the comparison gives up.
        The thread then stays, parked: the replacement process is ITS
        child, and the cluster's children ask the kernel to kill them
        when the thread that started them ends (PR_SET_PDEATHSIG goes by
        thread, not by process)."""
        try:
            self.run_timeline()
        finally:
            self.conducted.set()
            threading.Event().wait()

    def run_timeline(self) -> None:
        from tpu3fs.utils.result import FsError

        ctx = self.ctx
        cluster = ctx.cluster
        name = f"storage{self.node}"
        try:
            view = ctx.new_view("cond")
            while self.clock() < self.kill_at:
                time.sleep(min(0.05, max(0.0, self.kill_at - self.clock())))
            proc = cluster.procs.pop(name)
            os.killpg(proc.pid, self.kill_sig)
            proc.wait()
            self.dead_pids.append(proc.pid)
            self.timeline.stamp("t_kill", self.clock())
            ctx.say(f"[failure] {self.clock():.2f}s: {name} (pid "
                    f"{proc.pid}) killed")
            restarted = read_back = False
            while not self.give_up.is_set():
                now = self.clock()
                if not restarted and now >= self.restart_at:
                    if self.timeline.get("t_offline") and not read_back:
                        self.read_back_degraded(view)
                        read_back = True
                    shutil.rmtree(os.path.join(
                        cluster.run_dir, f"storage_{self.node}"),
                        ignore_errors=True)
                    cluster.spawn_storage(self.node)
                    restarted = True
                    self.timeline.stamp("t_restart", self.clock())
                    ctx.say(f"[failure] {self.clock():.2f}s: {name} "
                            f"started again on an empty directory")
                try:
                    mine, everyone = self.states(view.routing())
                except FsError as e:   # mgmtd busy: look again
                    ctx.say(f"[failure] routing: {e!r}")
                    time.sleep(0.1)
                    continue
                before = dict(self.timeline.at)
                self.timeline.observe(self.clock(), mine, everyone)
                for key in self.timeline.at.keys() - before.keys():
                    ctx.say(f"[failure] {self.timeline.at[key]:.2f}s: "
                            f"{key}")
                # while the node is gone: as soon as enough turns were put
                # in the outage; at the latest when the window has closed or
                # the first target is SYNCING
                if (self.timeline.get("t_offline") and not read_back
                        and (self.outage_turns(
                            int(ctx.params["verify_degraded_turns"]))
                            or self.window_closed.is_set()
                            or self.timeline.get("t_syncing"))):
                    self.read_back_degraded(view)
                    read_back = True
                if self.timeline.get("t_recovered") is not None:
                    return
                time.sleep(0.1)
        except Exception as e:   # the comparison reports it, as a failure
            self.conductor_error = repr(e)
            ctx.say(f"[failure] the conductor failed: {e!r}")

    def stored_by_phase(self) -> dict:
        """{phase: [turn, ...]} of the turns whose put was acknowledged."""
        at = self.timeline.at
        t_kill = at.get("t_kill")
        t_syncing = at.get("t_syncing")
        out: dict = {}
        with self._lock:
            acked = [(r["id"], r["t1"] - self.t_open)
                     for r in self.ctx.requests
                     if r["ok"] and r["store_bytes"]]
        for turn, t_ack in acked:
            out.setdefault(phase_of(t_ack, t_kill, t_syncing),
                           []).append(turn)
        return out

    def outage_turns(self, need: int) -> bool:
        return len(self.stored_by_phase().get("outage", [])) >= need

    def entries_of(self, turns: list) -> list:
        """(path, entry bytes) of every block the sampled turns put, the
        rows made again on the chip."""
        out = []
        for t in turns:
            r = self.turns[t]
            nsuf, d = r["suffix_blocks"], r["doc"]
            seq = self.doc_tokens[d] + self.suffix_tokens(t, nsuf)
            keys = ref.chain_keys(seq, self.block_tokens)[
                self.doc_blocks[d]:]
            rows = [np.asarray(x) for x in self.suffix_rows(t, nsuf)]
            out += [(ref.entry_path(self.root, k), ref.encode_entry(x))
                    for k, x in zip(keys, rows)]
        return out

    def read_entries(self, view, entries: list) -> tuple:
        """Entries read back through a fresh client of `view` -> (wrong,
        degraded decodes it counted). An error counts, it does not
        raise."""
        from tpu3fs.utils.result import FsError

        ctx = self.ctx
        fio = view.file_client(retry=ctx.retry)
        wrong = 0
        try:
            inodes = view.meta.batch_stat_by_path([p for p, _ in entries])
            for (path, want), ino in zip(entries, inodes):
                try:
                    have = (None if ino is None
                            else bytes(fio.read(ino, 0, ino.length)))
                except FsError as e:
                    ctx.say(f"[verify] read-back of {path}: {e!r}")
                    have = None
                wrong += have != want
        except FsError as e:
            ctx.say(f"[verify] read-back: {e!r}")
            wrong += len(entries)
        degraded = int(fio.storage._ec_degraded._value)
        fio.storage.close()
        return wrong, degraded

    def read_back_degraded(self, view) -> None:
        """While the node is gone: turns put during the outage, read back
        through a client of the conductor's own (no request, no metric)."""
        rng = np.random.default_rng([self.ctx.seed, 11])
        turns = self.stored_by_phase().get("outage", [])
        pick = sorted(rng.permutation(sorted(turns))[
            :int(self.ctx.params["verify_degraded_turns"])].tolist())
        entries = self.entries_of(pick)
        self.degraded_seen = len(entries)
        self.degraded_wrong, self.degraded_decodes = self.read_entries(
            view, entries)
        self.ctx.say(f"[failure] {self.clock():.2f}s: {len(entries)} blocks "
                     f"of {len(pick)} turns put during the outage read "
                     f"back degraded, {self.degraded_wrong} wrong")

    # -- after the window -----------------------------------------------------
    def wait_recovered(self) -> bool:
        """Up to recover_timeout_s for all targets SERVING and up to date
        again; outside the window and outside every metric. The control's
        hook calls it too."""
        if self.conductor is not None:
            self.conducted.wait(self.recover_timeout)
            self.give_up.set()
            self.conducted.wait(10)
        return self.timeline.get("t_recovered") is not None

    def passes(self) -> list:
        """The finished rebuild passes of the lost node's targets, from
        the storage processes' logs (the coordinator writes them)."""
        lost = {tid for _, tid in self.lost_targets}
        out = []
        logs = self.ctx.cluster.logs
        for name in sorted(os.listdir(logs)):
            if not name.startswith("storage"):
                continue
            with open(os.path.join(logs, name), "rb") as f:
                text = f.read().decode("utf-8", "replace")
            out += [row for row in parse_passes(text)
                    if row["target"] in lost]
        return out

    def verify(self):
        ctx, p = self.ctx, self.ctx.params
        recovered = self.wait_recovered()
        if not recovered:   # what the services say of it, for the reader
            ctx.say(f"[failure] no recovery inside {self.recover_timeout} "
                    f"s after the window:\n" + ctx.cluster.log_tails(30))
        tl = self.timeline
        rng = np.random.default_rng([ctx.seed, 9])
        done = sorted(t for t, r in self.turns.items() if r["ok"])
        # 1. every loaded block of every turn, read whole or decoded: its
        # fingerprint, taken on the chip when it landed; 2. a sample of
        # the resident turns byte for byte out of HBM
        doc_fp: dict = {}
        fp_wrong = fp_seen = 0
        for t in done:
            r = self.turns[t]
            d = r["doc"]
            if d not in doc_fp:
                doc_fp[d] = [ref.fingerprint_np(b)
                             for b in self.make_doc(d)[1]]
            got = [tuple(int(x) for x in np.asarray(f)) for f in r["fps"]]
            fp_seen += len(got)
            fp_wrong += sum(1 for g, w in zip(got, doc_fp[d]) if g != w)
            fp_wrong += abs(len(got) - len(doc_fp[d]))
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
        # 3. what the window stored and the store acknowledged, a third
        # from each part of the timeline, through a fresh client now that
        # recovery is over (or was given up)
        by_phase = self.stored_by_phase()
        third = max(1, int(p["verify_store_turns"]) // len(PHASES))
        entries = self.entries_of(sample_by_phase(by_phase, third, rng))
        view = ctx.new_view("verify")
        stored_wrong, _ = self.read_entries(view, entries)
        checks.append(Check("stored_blocks_wrong", stored_wrong, 0))
        checks.append(Check("stored_blocks_wrong_degraded",
                            self.degraded_wrong, 0))
        # 4. the stored form: sampled blocks of each phase and of the
        # corpus, all 16 shards target by target against the independent
        # encode; 5. for more stripes, the rebuilt targets' shards alone
        per = max(1, int(p["verify_shard_blocks"]) // len(PHASES))
        window_blocks = []
        for phase in PHASES:
            got = self.entries_of(sample_by_phase(
                {phase: by_phase.get(phase, [])}, per, rng))
            window_blocks += [got[i] for i in
                              rng.permutation(len(got))[:per].tolist()]
        corpus = self.corpus_entries(
            int(p["verify_corpus_shard_blocks"])
            + int(p["verify_rebuilt_stripes"]), rng)
        n_all = int(p["verify_corpus_shard_blocks"])
        shards_wrong, seen = self.shards_wrong(
            view, window_blocks + corpus[:n_all], range(self.k + self.m))
        checks.append(Check("stored_shards_wrong", shards_wrong, 0))
        more = corpus[n_all:] + [e for e in entries
                                 if e not in window_blocks]
        more = [more[i] for i in rng.permutation(len(more))[
            :int(p["verify_rebuilt_stripes"])].tolist()]
        rebuilt_wrong, seen_rebuilt = self.shards_wrong(
            view, more, [j for j, _ in self.lost_targets])
        checks.append(Check("rebuilt_shards_wrong", rebuilt_wrong, 0))
        ctx.say(f"[verify] {len(entries)} stored blocks read back "
                f"({ {ph: len(by_phase.get(ph, [])) for ph in PHASES} } "
                f"stored turns by phase), {seen} shards of "
                f"{len(window_blocks) + n_all} blocks and {seen_rebuilt} "
                f"shards of the rebuilt targets against an independent "
                f"RS({self.k},{self.m})")
        # 6. recovery: finished, by itself, and every target serves
        routing = ctx.cluster.admin.refresh_routing()
        _, everyone = self.states(routing)
        not_serving = sum(1 for pub, loc in everyone
                          if pub != "SERVING" or loc != "UPTODATE")
        checks.append(Check(
            "rebuild_incomplete",
            int(not recovered) + not_serving + int(bool(
                self.conductor_error)), 0))
        checks.append(Check("node_never_lost",
                            int(tl.get("t_offline") is None), 0))
        # 7. degraded decodes: some in the outage, none once recovered
        window_degraded = self.degraded_decodes + sum(
            int(s._ec_degraded._value) for s in self.storages)
        after_wrong, after_degraded = self.read_entries(
            view, self.corpus_entries(int(p["verify_after_blocks"]), rng))
        checks.append(Check("degraded_after_recovery",
                            after_degraded + after_wrong, 0))
        checks.append(Check("undegraded_outage",
                            int(window_degraded == 0), 0))
        from tpu3fs.ops import stripe

        codecs = list(stripe._codecs.values())
        host = sum(1 for c in codecs if c._use_host())
        checks.append(Check("codecs_on_host", host + (0 if codecs else 1), 0))
        self.report(window_degraded)
        return checks

    def corpus_entries(self, n: int, rng) -> list:
        """(path, entry bytes) of n blocks of the corpus, spread over its
        documents."""
        out = []
        docs = rng.permutation(len(self.doc_blocks)).tolist()
        while len(out) < n and docs:
            d = docs.pop()
            tokens, rows = self.make_doc(d)
            keys = ref.chain_keys(tokens, self.block_tokens)
            take = rng.permutation(len(keys))[:max(1, n // 8)].tolist()
            out += [(ref.entry_path(self.root, keys[i]),
                     ref.encode_entry(rows[i])) for i in take]
        return out[:n]

    def shards_wrong(self, view, entries: list, shard_ids) -> tuple:
        """The stored shards `shard_ids` of each entry's stripe, read
        target by target, against the independent encode -> (wrong,
        seen). A missing inode or an unreadable target counts."""
        from tpu3fs.storage.types import ChunkId
        from tpu3fs.utils.result import FsError

        ctx = self.ctx
        shard_ids = list(shard_ids)
        if not entries:
            return 0, 0
        routing = ctx.cluster.admin.refresh_routing()
        chain = routing.chains[self.chain_id]
        inodes = view.meta.batch_stat_by_path([p for p, _ in entries])
        wrong = seen = 0
        for (path, want), ino in zip(entries, inodes):
            if ino is None:
                wrong += len(shard_ids)
                continue
            gold = ref.stripe_shards(want, ctx.config["chunk_size"],
                                     self.k, self.m)
            for j in shard_ids:
                try:
                    got = cl.read_target(
                        view, routing, chain.chain_id, ChunkId(ino.id, 0),
                        chain.target_of_shard(j).target_id)
                except FsError as e:
                    ctx.say(f"[verify] shard {j} of {path}: {e!r}")
                    got = None
                seen += 1
                if gold[j]:
                    wrong += got != gold[j]
                elif got:
                    wrong += 1   # past the block's end: empty
        return wrong, seen

    def report(self, window_degraded: int) -> None:
        ctx, tl = self.ctx, self.timeline
        loaded = sum(r["load_bytes"] for r in ctx.requests
                     if r["ok"]) // self.block_bytes
        counters = {"rb_degraded_stripes": window_degraded,
                    "rb_loaded_stripes": loaded,
                    "rb_timeline": {k: round(v, 3)
                                    for k, v in tl.at.items()},
                    "rb_degraded_read_back_blocks": self.degraded_seen}
        for key, (a, b) in {"rb_detect_s": ("t_kill", "t_offline"),
                            "rb_recover_s": ("t_kill", "t_recovered"),
                            "rb_rebuild_s": ("t_syncing", "t_recovered"),
                            }.items():
            value = tl.between(a, b)
            if value is not None:
                counters[key] = value
        passes = self.passes()
        rebuilt = sum(r.get("installed_bytes", 0) for r in passes)
        read = sum(r.get("read_bytes", 0) for r in passes)
        if passes:
            counters.update(rb_rebuilt_bytes=rebuilt, rb_read_bytes=read,
                            rb_passes=len(passes))
            if rebuilt:
                counters["rb_read_per_rebuilt"] = read / rebuilt
            if counters.get("rb_rebuild_s"):
                counters["rb_rebuild_mibps"] = (
                    rebuilt / counters["rb_rebuild_s"] / (1 << 20))
        ctx.counters.update(counters)
        ctx.say(f"[failure] timeline {counters['rb_timeline']}; "
                f"{len(passes)} rebuild passes installed {rebuilt} B from "
                f"{read} B read; {window_degraded} degraded decodes for "
                f"{loaded} blocks loaded in the window")

    def close(self) -> None:
        self.give_up.set()
        if self.conductor is not None:
            self.conducted.wait(10)
        super().close()
        # what the killed process left in /dev/shm: it is no child of the
        # cluster any more, so Cluster.stop does not look for its pid
        for name in cl.shm_entries():
            if cl.shm_owner(name) in self.dead_pids:
                try:
                    os.unlink(os.path.join(cl.SHM_DIR, name))
                except OSError:
                    pass
