"""Randomized model check of the EC stripe plane — the EC twin of
tests/test_model_craq.py. The EC design (shard-addressed writes with
stripe versioning, degraded reads, device-decode rebuild) is ORIGINAL to
this framework (the reference has no RS data plane), so it gets the same
treatment as the chain protocol: a seeded explorer drives the REAL fabric
through writes, overwrites, injected faults, node kills, DISK LOSSES and
rebuilds, then asserts the stripe invariants.

Invariants:
  E1 (no fabrication): any successful full-stripe read returns bytes that
     some client actually sent for that chunk.
  E2 (acked durability): after healing + rebuild, every acknowledged
     stripe is readable and equals an acknowledged payload for that chunk
     at least as new as the oldest surviving ack.
  E3 (degraded serving): with the FULL erasure budget of m nodes down
     simultaneously, every acked stripe still reads back correctly.
  E4 (length precision): short stripes read back at their exact logical
     length, through rebuilds.

Mutation-tested: re-introducing single-phase installs is caught at seed
0 (wedged chain), and constant writer nonces at seed 9 (mixed-stripe
fabrication). Disabling the rebuilder's max_safe_ver rollback guard is
NOT caught by these schedules — by design it protects a beyond-budget
corner (an acked version losing its entire k-quorum to >m concurrent
losses) that the explorer's kill policy deliberately excludes; the guard
is defense-in-depth past the modeled envelope.
"""

import random
from types import SimpleNamespace

import numpy as np
import pytest

from tpu3fs.client.storage_client import RetryOptions
from tpu3fs.fabric.fabric import Fabric, SystemSetupConfig
from tpu3fs.mgmtd.types import PublicTargetState
from tpu3fs.storage.types import ChunkId
from tpu3fs.utils.fault_injection import fault_injection
from tpu3fs.utils.result import Code

K, M = 3, 1
CHUNK = 12 << 10
NUM_CHUNKS = 6
FILE_ID = 31


class EcExplorer:
    CHUNKS = NUM_CHUNKS

    def __init__(self, seed: int, *, nodes: int = 4, k: int = K, m: int = M):
        self.rng = random.Random(seed)
        self.np_rng = np.random.default_rng(seed)
        self.k = k
        self.m = m
        self.fab = Fabric(SystemSetupConfig(
            num_storage_nodes=nodes, num_chains=2, chunk_size=CHUNK,
            ec_k=k, ec_m=m))
        fast = RetryOptions(max_retries=3, backoff_base_s=0.0005,
                            backoff_max_s=0.01)
        self.client = self.fab.storage_client(retry=fast)
        self.chain = self.fab.chain_ids[0]
        # model state per chunk
        self.sent = {i: set() for i in range(self.CHUNKS)}
        self.acked = {i: {} for i in range(self.CHUNKS)}   # ver -> payload

    # -- actions -------------------------------------------------------------
    def _payload(self, idx: int) -> bytes:
        if self.rng.random() < 0.25:  # short stripe (tail-trim paths)
            n = self.rng.randrange(1, CHUNK)
        else:
            n = CHUNK
        return self.np_rng.integers(0, 256, n, dtype=np.uint8).tobytes()

    def act_write(self, faulty: bool = False) -> None:
        idx = self.rng.randrange(self.CHUNKS)
        payload = self._payload(idx)
        self.sent[idx].add(payload)
        try:
            if faulty:
                with fault_injection(0.4, times=1):
                    r = self.client.write_stripe(
                        self.chain, ChunkId(FILE_ID, idx), payload,
                        chunk_size=CHUNK)
            else:
                r = self.client.write_stripe(
                    self.chain, ChunkId(FILE_ID, idx), payload,
                    chunk_size=CHUNK)
        except Exception:
            return
        if r.ok:
            self.acked[idx][r.commit_ver or r.update_ver] = payload

    def act_read(self) -> None:
        idx = self.rng.randrange(self.CHUNKS)
        try:
            got = self.client.read_stripe(
                self.chain, ChunkId(FILE_ID, idx), 0, CHUNK,
                chunk_size=CHUNK)
        except Exception:
            return
        if got.ok and (self.sent[idx] or got.data):
            # E1: no fabricated bytes (empty = never-written chunk).
            # Stripe reads return the ZERO-PADDED stripe + logical_len
            # (the read contract; file_io clamps) — clamp before comparing
            payload = self._clamp(got)
            assert payload == b"" or payload in self.sent[idx], (
                f"chunk {idx}: read returned bytes nobody sent")

    def act_kill(self) -> None:
        live = [n for n in self.fab.nodes.values() if n.alive]
        if len(live) <= self.k:  # keep at least k nodes up
            return
        victim = self.rng.choice(live)
        if self.rng.random() < 0.4:
            self.fab.fail_node(victim.node_id)  # disk loss
        else:
            self.fab.kill_node(victim.node_id)

    def act_recover(self) -> None:
        dead = [n for n in self.fab.nodes.values() if not n.alive]
        if dead:
            self.fab.restart_node(self.rng.choice(dead).node_id)
            self.fab.resync_all(rounds=2)

    def act_tick(self) -> None:
        self.fab.clock.advance(self.fab.cfg.heartbeat_timeout_s + 1)
        self.fab.tick()

    # -- schedule ------------------------------------------------------------
    def run(self, steps: int = 60) -> None:
        actions = [
            (self.act_write, 28),
            (lambda: self.act_write(faulty=True), 14),
            (self.act_read, 26),
            (self.act_kill, 9),
            (self.act_recover, 14),
            (self.act_tick, 9),
        ]
        fns = [fn for fn, w in actions for _ in range(w)]
        for _ in range(steps):
            self.rng.choice(fns)()
        self.heal_and_check()

    def heal_and_check(self) -> None:
        for node in self.fab.nodes.values():
            if not node.alive:
                self.fab.restart_node(node.node_id)
        self.fab.resync_all(rounds=10)
        routing = self.fab.routing()
        chain = routing.chains[self.chain]
        for t in chain.targets:
            assert t.public_state == PublicTargetState.SERVING, (
                f"shard target {t.target_id} stuck {t.public_state.name}")
        self._check_reads("healed")
        # E3: m-node-down degraded serving for every acked stripe — the
        # full erasure budget, not just one loss (RS(4,2) must survive
        # TWO simultaneous erasures)
        victims = self.rng.sample(
            [n for n in self.fab.nodes.values() if n.alive],
            k=min(self.m, len(self.fab.nodes) - self.k))
        for v in victims:
            self.fab.kill_node(v.node_id)
        names = ",".join(str(v.node_id) for v in victims)
        self._check_reads(f"degraded(nodes {names} down)")
        for v in victims:
            self.fab.restart_node(v.node_id)
        self.fab.resync_all(rounds=4)

    @staticmethod
    def _clamp(got) -> bytes:
        if got.logical_len:
            return bytes(got.data[:got.logical_len])
        return bytes(got.data)

    def _check_reads(self, phase: str) -> None:
        for idx in range(self.CHUNKS):
            if not self.acked[idx]:
                continue
            got = self.client.read_stripe(
                self.chain, ChunkId(FILE_ID, idx), 0, CHUNK,
                chunk_size=CHUNK)
            assert got.ok, f"[{phase}] chunk {idx} unreadable: {got.code}"
            payload = self._clamp(got)
            # E2: an acked (or at least sent) payload, never garbage
            assert payload in self.sent[idx], (
                f"[{phase}] chunk {idx}: not a sent payload")
            newest = self.acked[idx][max(self.acked[idx])]
            if payload != newest:
                # an even newer sent-but-unacked write may have won the
                # version race; anything OLDER than every ack is a loss
                assert payload not in (
                    set(self.acked[idx].values()) - {newest}), (
                    f"[{phase}] chunk {idx}: rollback to a stale ack")
            # E4: exact logical length + zero padding beyond it
            assert len(payload) in {len(p) for p in self.sent[idx]}, idx
            assert not bytes(
                got.data[len(payload):]).strip(b"\x00"), (
                f"[{phase}] chunk {idx}: non-zero bytes past logical_len")


@pytest.mark.parametrize("seed", range(12))
def test_random_ec_schedules(seed):
    EcExplorer(seed).run(steps=60)


@pytest.mark.parametrize("seed", range(6))
def test_random_ec_schedules_more_nodes(seed):
    EcExplorer(500 + seed, nodes=5).run(steps=80)


@pytest.mark.parametrize("seed", range(6))
def test_random_ec_schedules_double_parity(seed):
    """RS(4,2): multi-loss rebuilds — the degraded-serving check (E3)
    kills m=2 nodes simultaneously after healing."""
    EcExplorer(900 + seed, nodes=6, k=4, m=2).run(steps=80)


class HeadBatchExplorer(EcExplorer):
    """The same schedules with the file client's stripe batch among the
    writers: write_stripe_heads of one to three chunks at once, half of
    them short. A short item is a write AT OFFSET 0, not a replacement:
    fresh it rides the batch, over a stripe it comes back None and the
    file client's ladder merges it — so what the model expects to read
    is the new bytes followed by the TAIL of what was there (read just
    before; the explorer is one thread). A short stripe installed over a
    longer acknowledged one reads as bytes nobody was promised, and E1 /
    E2 fail. Nodes also come back WITHOUT a rebuild round, so batches
    meet SYNCING targets that hold nothing yet, and the chunks are many,
    so that most stripes are at their first version when that happens
    (a batch at version 1 is refused by a stripe that is further on)."""

    CHUNKS = 24

    def __init__(self, seed: int, **kw):
        super().__init__(seed, **kw)
        from tpu3fs.client.file_io import FileIoClient

        self.fio = FileIoClient(self.client)

    def _current(self, idx: int):
        """The stripe's bytes now, b"" if absent, None if unreadable."""
        try:
            got = self.client.read_stripe(
                self.chain, ChunkId(FILE_ID, idx), 0, CHUNK,
                chunk_size=CHUNK)
        except Exception:
            return None
        if got.ok:
            return self._clamp(got)
        return b"" if got.code == Code.CHUNK_NOT_FOUND else None

    def act_write_batch(self, faulty: bool = False) -> None:
        idxs = self.rng.sample(range(self.CHUNKS), self.rng.randrange(1, 4))
        items, expect = [], []
        for idx in idxs:
            n = (self.rng.randrange(1, CHUNK) if self.rng.random() < 0.5
                 else CHUNK)
            payload = self.np_rng.integers(
                0, 256, n, dtype=np.uint8).tobytes()
            old = self._current(idx)
            if old is None:
                # unreadable now: merged with whichever sent payload lies
                # there, or with nothing
                olds = list(self.sent[idx]) + [b""]
            else:
                olds = [old]
                if old not in self.acked[idx].values():
                    # a torn, never acknowledged stripe may be replaced
                    olds.append(b"")
            merged = [payload + o[len(payload):] for o in olds]
            self.sent[idx].update(merged)
            expect.append(merged[0] if old is not None else None)
            items.append((ChunkId(FILE_ID, idx), payload))
        inode = SimpleNamespace(id=FILE_ID)

        def put():
            out = []
            for idx, (cid, payload), r in zip(
                    idxs, items, self.client.write_stripe_heads(
                        self.chain, items, chunk_size=CHUNK)):
                if r is None:
                    assert len(payload) < CHUNK  # only a short one leaves
                    try:
                        r = self.fio._write_ec_ladder(
                            inode, self.chain, idx, 0, payload, CHUNK)
                    except Exception:
                        r = None
                out.append(r)
            return out

        try:
            if faulty:
                with fault_injection(0.4, times=1):
                    replies = put()
            else:
                replies = put()
        except Exception:
            return
        for idx, want, r in zip(idxs, expect, replies):
            if r is not None and r.ok and want is not None:
                self.acked[idx][r.commit_ver or r.update_ver] = want

    def act_kill(self) -> None:
        """One node at a time, and only off a fully rebuilt chain — a
        node that came back unsynced still counts against the erasure
        budget. Half the victims lose their disk with it."""
        chain = self.fab.routing().chains[self.chain]
        if not all(n.alive for n in self.fab.nodes.values()) or any(
                t.public_state != PublicTargetState.SERVING
                for t in chain.targets):
            return
        victim = self.rng.choice(list(self.fab.nodes.values()))
        self.fab.fail_node(victim.node_id)
        if self.rng.random() < 0.5:
            from tpu3fs.storage.engine import MemChunkEngine

            for target in victim.service.targets():
                target.engine = MemChunkEngine()

    def act_recover_unsynced(self) -> None:
        dead = [n for n in self.fab.nodes.values() if not n.alive]
        if dead:
            self.fab.restart_node(self.rng.choice(dead).node_id)
            self.fab.tick()

    def run(self, steps: int = 60) -> None:
        actions = [
            (self.act_write, 8),
            (self.act_write_batch, 24),
            (lambda: self.act_write_batch(faulty=True), 10),
            (self.act_read, 20),
            (self.act_kill, 10),
            (self.act_recover, 8),
            (self.act_recover_unsynced, 8),
            (self.act_tick, 8),
        ]
        fns = [fn for fn, w in actions for _ in range(w)]
        for _ in range(steps):
            self.rng.choice(fns)()
        self.heal_and_check()


@pytest.mark.parametrize("seed", range(12))
def test_random_ec_schedules_with_head_batches(seed):
    """Mutation-tested: trusting a SYNCING shard 0's "absent" (a short
    stripe batched over a longer one the other shards hold) is caught at
    seeds 4 and 9."""
    HeadBatchExplorer(2000 + seed).run(steps=70)
