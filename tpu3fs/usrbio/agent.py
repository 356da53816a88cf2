"""The USRBIO agent: serves registered rings against the storage cluster.

The FUSE-daemon half of the reference (src/fuse/IovTable.h:10-39 iov
registration; src/fuse/FuseClients.cc:150,218 — watch threads poll submit
semaphores, ioRingWorkers run IoRing::process; src/fuse/PioV.cc splits ring
entries into chunk IOs and sends them as batches). Here the agent owns
Meta/Storage clients and worker threads: each ring gets a dedicated worker
(the reference multiplexes rings over 3 priority-lane semaphores,
IoRing.h:259-264; with a worker per ring the ring's priority is recorded
but does not schedule). A worker serves its ring one DRAIN at a time, and
how many SQEs make a drain is the ring's ``io_depth`` (hf3fs_iorcreate):
0 whatever is queued, N > 0 exactly N, N < 0 up to -N after a short wait.
The reads of a drain are ONE batch — one ``batch_stat`` of the distinct
inodes, one node-grouped ``FileIoClient.batch_read_into`` whose replies
land in the SQEs' Iov windows, all CQEs pushed with one wake-up — and an
SQE that is wrong fails alone with its negative code; writes run one by
one in ring order, a write between two reads splitting the batch so that
a read never overtakes it. Across rings the agent bounds the DRAINS being
served at once (``max_concurrent_batches``), not the I/Os inside them.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, Optional

from tpu3fs.analytics import spans as _spans
from tpu3fs.client.file_io import FileIoClient
from tpu3fs.meta.store import MetaStore, OpenFlags
from tpu3fs.usrbio.ring import SQE_FLAG_READ, Iov, IoRing, reap_stale_shm
from tpu3fs.utils.result import Code, FsError, Status

OP = "usrbio.ring_batch"

#: how long a ring of ``io_depth`` < 0 waits for its batch to fill before
#: it serves what is there
BATCH_WAIT_S = 0.002


def _scope_key(sqe):
    """What of an SQE decides the scopes its I/O runs under: the reads of
    a drain that share it ride one batch."""
    return sqe.flags & ~SQE_FLAG_READ, sqe.token


def _sqe_scopes(sqe):
    """The SQE-borne request context, scoped like an inbound RPC envelope:
    QoS class from the flag bits (same positions as the wire envelope),
    trace/deadline/tenant from the token field's ``t1.*``/``d1.*``/``u1.*``
    string — so IO the agent issues on a client's behalf is admitted,
    attributed and shed exactly as if the client had spoken sockets."""
    import contextlib

    from tpu3fs.qos.core import class_from_flags, tagged
    from tpu3fs.rpc import deadline as _deadline
    from tpu3fs.tenant import identity as _tenant_id

    stack = contextlib.ExitStack()
    tclass = class_from_flags(sqe.flags)
    if tclass is not None:
        stack.enter_context(tagged(tclass))
    tok = sqe.token
    if tok:
        dl = _deadline.decode_deadline(tok)
        if dl is not None:
            stack.enter_context(_deadline.deadline_scope(dl))
        tenant = _tenant_id.decode_tenant(tok)
        if tenant is not None:
            stack.enter_context(_tenant_id.tenant_scope(tenant))
        if _spans.tracer().enabled:
            in_ctx = _spans.decode_wire(tok)
            if in_ctx is not None:
                stack.enter_context(_spans.trace_scope(in_ctx.child()))
    return stack


class _RingState:
    def __init__(self, ring: IoRing, iovs: List[Iov], io_depth: int = 0):
        self.ring = ring
        self.iovs = iovs
        self.io_depth = io_depth
        self.worker: Optional[threading.Thread] = None
        self.running = True
        # set when deregister gives up joining a busy worker: the worker
        # then owns the mapping and closes it on exit
        self.close_on_exit = False


class UsrbioAgent:
    """One agent per host, shared by all local USRBIO clients."""

    def __init__(self, meta: MetaStore, file_client: FileIoClient,
                 client_id: str = "usrbio-agent", *,
                 max_concurrent_batches: int = 64):
        self._meta = meta
        self._fio = file_client
        self._client_id = client_id
        # fd table (ref hf3fs_reg_fd): small int -> [inode, session, wrote]
        self._fds: Dict[int, List] = {}
        self._next_fd = 100
        self._rings: Dict[str, _RingState] = {}
        self._lock = threading.Lock()
        # host-wide throttle across ALL rings (the reference bounds
        # in-flight usrbio IO with semaphores per priority lane,
        # IoRing.h:259-264). ONE rule: it bounds the DRAINS being served
        # at once, whatever they hold — a ring has one drain in flight, a
        # drain is one batch at the storage client, and how much a batch
        # may ask of the backend is the storage client's own striping
        from tpu3fs.monitor.recorder import CounterRecorder
        from tpu3fs.utils.executor import ConcurrencyLimiter

        self._batch_limiter = ConcurrencyLimiter("usrbio-batch",
                                                 max_concurrent_batches)
        # file-mode rings (docs/observability.md): SQEs served, drains,
        # drains of an io_depth > 0 ring that held fewer than its depth,
        # CQEs that carried a negative code. The recorders reset a
        # collection window; `totals` keeps the lifetime counts
        self._rec = {
            "sqes": CounterRecorder("usrbio.sqes"),
            "batches": CounterRecorder("usrbio.batches"),
            "short_drains": CounterRecorder("usrbio.short_drains"),
            "sqe_errors": CounterRecorder("usrbio.sqe_errors"),
        }
        self.totals = dict.fromkeys(self._rec, 0)

    def _count(self, **counts: int) -> None:
        with self._lock:
            for key, n in counts.items():
                if n:
                    self._rec[key].add(n)
                    self.totals[key] += n

    # -- control plane (the reference's ClientAgent service, fbs/lib) --------
    def open(self, path: str, *, write: bool = False) -> int:
        """Open + register a file; returns the fd for prep_io."""
        flags = OpenFlags.READ | (OpenFlags.WRITE if write else 0)
        try:
            res = self._meta.open(path, flags=flags, client_id=self._client_id)
        except FsError as e:
            if e.code == Code.META_NOT_FOUND and write:
                res = self._meta.create(
                    path, flags=flags, client_id=self._client_id
                )
            else:
                raise
        with self._lock:
            fd = self._next_fd
            self._next_fd += 1
            self._fds[fd] = [res.inode, res.session_id, False]
        return fd

    def close_fd(self, fd: int, length_hint: Optional[int] = None) -> None:
        with self._lock:
            entry = self._fds.pop(fd, None)
        if entry is None:
            raise FsError(Status(Code.INVALID_ARG, f"unknown fd {fd}"))
        inode, session, wrote = entry
        if session:
            self._meta.close(inode.id, session, length_hint=length_hint,
                             wrote=wrote)

    def register_iov(self, name: str, size: int) -> Iov:
        """Map a client's shm buffer into the agent (ref IovTable.addIov —
        where the reference also registers it for RDMA)."""
        return Iov(size, name=name, create=False)

    def register_ring(self, name: str, entries: int, iovs: List[Iov],
                      *, for_read: bool = True, priority: int = 1,
                      io_depth: int = 0) -> None:
        """``io_depth`` as hf3fs_iorcreate gives it: 0 serve what is queued
        as soon as it is there; N > 0 serve the ring in batches of exactly
        N (the caller owes the N: fewer stay queued); N < 0 up to -N a
        batch, served after BATCH_WAIT_S whatever is there."""
        if io_depth > entries:
            raise FsError(Status(
                Code.INVALID_ARG,
                f"io_depth {io_depth} can never fill a ring of {entries}"))
        ring = IoRing(entries, name=name, create=False, for_read=for_read,
                      io_depth=io_depth, priority=priority)
        state = _RingState(ring, iovs, io_depth)
        t = threading.Thread(
            target=self._ring_worker, args=(state,), daemon=True,
            name=f"usrbio-{name}",
        )
        state.worker = t
        with self._lock:
            self._rings[name] = state
        t.start()

    def deregister_ring(self, name: str) -> None:
        with self._lock:
            state = self._rings.pop(name, None)
        if state is not None:
            state.running = False
            state.ring.submit_sem.post()  # wake the worker so it exits
            if state.worker:
                state.worker.join(timeout=5)
                if state.worker.is_alive():
                    # worker is mid-IO (slow storage op); closing the mmap
                    # under it would crash the thread and drop the in-flight
                    # completion — hand it the mapping to close on exit
                    state.close_on_exit = True
                    return
            state.ring.close()

    # -- data plane ----------------------------------------------------------
    def _ring_worker(self, state: _RingState) -> None:
        ring = state.ring
        try:
            while state.running:
                if not ring.submit_sem.wait(timeout=0.5):
                    continue
                while state.running:
                    t0 = time.perf_counter()
                    limit = self._due(state)
                    if not limit:
                        break
                    with self._batch_limiter:
                        self._serve_drain(state, limit, t0)
        except (ValueError, FsError):
            # ring mmap closed under us during deregistration (ValueError)
            # or the header tore (USRBIO_TORN_RING): exit quietly — the
            # reaper owns cleanup of torn/abandoned segments
            return
        finally:
            if state.close_on_exit:
                state.ring.close()

    def _due(self, state: _RingState) -> int:
        """How many SQEs the ring's next drain takes by its ``io_depth``,
        or 0 when none is due yet (the worker goes back to the submit
        semaphore)."""
        ring, depth = state.ring, state.io_depth
        pending = ring.pending_sqes()
        if depth == 0:
            return pending
        if depth > 0:
            return depth if pending >= depth else 0
        if not pending:
            return 0
        deadline = time.perf_counter() + BATCH_WAIT_S
        while state.running and ring.pending_sqes() < -depth:
            left = deadline - time.perf_counter()
            if left <= 0:
                break
            ring.submit_sem.wait(timeout=left)
        return -depth

    def _serve_drain(self, state: _RingState, limit: int,
                     t0: float) -> None:
        """One drain as one root op ``usrbio.ring_batch`` (``nbytes`` =
        bytes moved; stages ``drain``, ``stat``, ``read``, ``complete``):
        runs of reads as one batch each, writes one by one where the ring
        has them. The op starts at ``t0``, where the worker began to look
        for a drain; it is opened before the SQEs are unpacked, so its
        ``cpu_us`` holds the unpack."""
        ctx = _spans.open_op(OP, live=False)
        sqes = state.ring.drain_sqes(limit=limit)
        t1 = time.perf_counter()
        if ctx is not None:
            ctx.ts = _spans.wall_of_perf(t0)
            _spans.add_span_at(ctx, OP, "drain", t0, t1 - t0,
                               nbytes=len(sqes))
        # counted before the CQEs go out: whoever reaps a batch finds it
        # in the totals
        self._count(batches=1,
                    short_drains=int(len(sqes) < state.io_depth))
        moved = 0
        try:
            with _spans.trace_scope(ctx):
                for done in self._serve_steps(state, sqes):
                    self._count(sqes=len(done),
                                sqe_errors=sum(r < 0 for r, _ in done))
                    # CPU-only (pack_into, one header write, one post): its
                    # wall less its CPU is the queue for the interpreter
                    with _spans.span(OP, "complete", nbytes=len(done),
                                     cpu=True):
                        state.ring.push_cqes(done)
                    moved += sum(r for r, _ in done if r > 0)
        finally:
            _spans.close_op(ctx, OP, t0, time.perf_counter() - t0,
                            nbytes=moved)

    def _serve_steps(self, state: _RingState, sqes: list):
        """The drain in ring order -> the (result, userdata) completions of
        each step: a run of consecutive reads is one step, a write is a
        step of its own (its CQE goes out when it is done, as ever)."""
        for is_read, run in itertools.groupby(sqes, key=lambda q: q.is_read):
            if is_read:
                yield self._serve_reads(state, list(run))
                continue
            for sqe in run:
                with _sqe_scopes(sqe):
                    result = self._process_write(state, sqe)
                yield [(result, sqe.userdata)]

    def _check_sqe(self, state: _RingState, sqe):
        """-> (fd entry, iov) of a well-formed SQE, or its negative code."""
        entry = self._fds.get(sqe.fd)
        if entry is None:
            return -int(Code.META_NOT_FOUND)
        if sqe.iov_id >= len(state.iovs):
            return -int(Code.INVALID_ARG)
        iov = state.iovs[sqe.iov_id]
        if sqe.iov_offset + sqe.length > iov.size:
            return -int(Code.INVALID_ARG)
        return entry, iov

    def _serve_reads(self, state: _RingState, run: list) -> list:
        """A run of read SQEs as ONE batch (one a distinct request scope:
        class bits and token, in practice one): the lengths of the run's
        distinct inodes refreshed by one ``batch_stat`` so that EOF
        clamping sees recent writes, then one
        ``FileIoClient.batch_read_into`` whose replies land directly in
        the registered shm windows — no assembly buffer, no iov copy. An
        SQE that is wrong (unknown fd, window outside its Iov, a storage
        error on its range) gets its own negative code and its neighbours
        are served."""
        results: List[Optional[int]] = [None] * len(run)
        groups: Dict[tuple, list] = {}
        for i, sqe in enumerate(run):
            ok = self._check_sqe(state, sqe)
            if isinstance(ok, int):
                results[i] = ok
            else:
                groups.setdefault(_scope_key(sqe), []).append((i, *ok))
        for members in groups.values():
            with _sqe_scopes(run[members[0][0]]):
                try:
                    got = self._read_batch(run, members)
                except FsError as e:
                    got = [-int(e.code)] * len(members)
                except Exception:
                    # transport/storage faults must surface as CQE errors,
                    # never kill the ring worker (clients would block
                    # forever)
                    got = [-int(Code.INTERNAL)] * len(members)
            for (i, _, _), res in zip(members, got):
                results[i] = res
        return [(res, sqe.userdata) for res, sqe in zip(results, run)]

    def _read_batch(self, run: list, members: list) -> List[int]:
        """members: [(index into run, fd entry, iov)] -> bytes moved or a
        negative code, a member."""
        inodes = {entry[0].id: entry[0] for _, entry, _ in members}
        with _spans.span(OP, "stat", nbytes=len(inodes)):
            ids = list(inodes)
            for ino, fresh in zip(ids, self._meta.batch_stat(ids)):
                if fresh is not None:
                    inodes[ino] = fresh
        files = []
        for i, entry, iov in members:
            sqe = run[i]
            files.append((inodes[entry[0].id], sqe.file_offset, sqe.length,
                          iov.view(sqe.iov_offset, sqe.length)))
        with _spans.span(OP, "read", nbytes=sum(f[2] for f in files)):
            got = self._fio.batch_read_into(files)
        return [g if isinstance(g, int) else -int(g.code) for g in got]

    def _process_write(self, state: _RingState, sqe) -> int:
        """-> bytes written, or negative Code on failure."""
        ok = self._check_sqe(state, sqe)
        if isinstance(ok, int):
            return ok
        entry, iov = ok
        inode = entry[0]
        try:
            data = iov.read(sqe.iov_offset, sqe.length)
            # flag before issuing so a close_fd racing this write still
            # sees the session as written
            entry[2] = True
            written = self._fio.write(inode, sqe.file_offset, data)
            self._meta.sync(inode.id, length_hint=sqe.file_offset + written)
            return written
        except FsError as e:
            return -int(e.code)
        except Exception:
            return -int(Code.INTERNAL)

    def reap_stale(self, *, iov_max_age_s: float = 3600.0) -> list:
        """Reaper pass over /dev/shm: unlink rings whose stamped owner pid
        is dead and orphan iov buffers nothing live references — the crash
        half of the shm lifecycle (the creating side unlinks on orderly
        close). Live registrations served by this agent are protected."""
        with self._lock:
            keep = set(self._rings)
            for state in self._rings.values():
                keep.update(v.name for v in state.iovs)
        return reap_stale_shm(keep=keep, iov_max_age_s=iov_max_age_s)

    def stop(self) -> None:
        for name in list(self._rings):
            self.deregister_ring(name)
