"""USRBIO ring transport: serde RPCs over shared-memory rings.

The client half of the tentpole wiring (ROADMAP item: kill the single-host
wire ceiling): a co-located client speaks whole storage RPCs through an
``IoRing`` — one RPC-mode SQE per (possibly batched) call, the serialized
request staged in a registered ``Iov`` region, the reply (control + bulk
data) landing in a client-designated region of the SAME shm, gathered there
straight from engine buffer views by the storage process's ring agent
(tpu3fs/usrbio/server.py). Zero sockets, zero kernel copies, no per-op
syscall beyond the semaphore doorbell — the analogue of the reference's
USRBIO data path (hf3fs_usrbio.h) where RDMA moves bytes directly between
storage and user-registered buffers.

``RpcMessenger`` (tpu3fs/rpc/services.py) selects this transport
transparently for same-host storage nodes (shm-nonce handshake) and falls
back to the pipelined sockets on any USRBIO-class failure, so FileIoClient,
FUSE, dataload and kvcache inherit the fast path with no API change.

QoS class, tenant id, deadline and trace context ride the SQE itself — the
class bits at their envelope flag positions and the ``t1.*``/``d1.*``/
``u1.*`` token string in the SQE token field — and admission happens at
ring dequeue through the SAME ``dispatch_packet`` entry the socket
transports use, so the shm path is structurally unable to bypass
enforcement (tools/check_rpc_registry.py check 7).
"""

from __future__ import annotations

import os
import struct
import threading
import time
import weakref
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from tpu3fs.rpc.net import pack_bulk_header, split_bulk
from tpu3fs.rpc.serde import deserialize, serialize
from tpu3fs.usrbio.ring import (
    RSP_HDR,
    TOKEN_CAP,
    Iov,
    IoRing,
    _pid_alive,
    unpack_stamps,
)
from tpu3fs.utils.result import Code, FsError, Status

#: control-plane service the storage binary binds for ring registration
#: (tpu3fs/usrbio/server.py bind_usrbio_service)
USRBIO_SERVICE_ID = 6

#: shm prefix of the handshake nonce files the serving process creates;
#: clients refuse to read any other path the server might name
from tpu3fs.usrbio.ring import HS_PREFIX as HANDSHAKE_PREFIX

#: (service_id, method_id) -> (service name, method name): the ONLY RPCs
#: an RPC-mode SQE may carry. The ring agent refuses everything else with
#: USRBIO_UNSUPPORTED, and check_rpc_registry check 7 statically verifies
#: every row is bound by the storage binary and fully classified
#: (QoS + idempotency + tenant), so the shm path can never grow a
#: dispatch surface the admission stack does not know.
RING_METHODS: Dict[Tuple[int, int], Tuple[str, str]] = {
    (3, 1): ("StorageSerde", "write"),
    (3, 2): ("StorageSerde", "update"),
    (3, 3): ("StorageSerde", "read"),
    (3, 11): ("StorageSerde", "batchRead"),
    (3, 12): ("StorageSerde", "batchWrite"),
    (3, 13): ("StorageSerde", "writeShard"),
    (3, 14): ("StorageSerde", "batchWriteShard"),
    (3, 15): ("StorageSerde", "batchUpdate"),
    (3, 21): ("StorageSerde", "batchReadRebuild"),
    (3, 22): ("StorageSerde", "chainEncodeWrite"),
    # fleet serving data plane: co-located peer fills skip the loopback
    # stack (tpu3fs/serving — the serving binary binds Usrbio too, so
    # its agent dispatches peerRead into its own Serving table)
    (7, 1): ("Serving", "peerRead"),
}

_U32 = struct.Struct("<I")

#: USRBIO failure codes: the messenger treats every one as "use sockets
#: for this call", never as an op failure surfaced to ladders
TRANSPORT_CODES = frozenset({
    Code.USRBIO_RING_FULL, Code.USRBIO_BAD_IOV, Code.USRBIO_AGENT_GONE,
    Code.USRBIO_TORN_RING, Code.USRBIO_REPLY_OVERFLOW,
    Code.USRBIO_UNSUPPORTED,
})

#: codes after which the ring itself is unusable (re-handshake needed)
FATAL_CODES = frozenset({Code.USRBIO_AGENT_GONE, Code.USRBIO_TORN_RING})


# -- control-plane wire types (bound by bind_usrbio_service) -----------------

@dataclass
class UsrbioHandshakeRsp:
    supported: bool = False
    nonce_name: str = ""     # /dev/shm file holding the same-host proof
    pid: int = 0             # serving process (diagnostics)


@dataclass
class UsrbioRegisterReq:
    ring_name: str
    iov_name: str = ""
    entries: int = 0
    iov_size: int = 0
    owner_pid: int = 0
    nonce: str = ""          # hex of the nonce file's bytes: proves the
    #                          client reads the server's /dev/shm


@dataclass
class UsrbioRegisterRsp:
    ok: bool = False
    message: str = ""


@dataclass
class UsrbioDeregisterReq:
    ring_name: str


# -- observability (single declaration site for the usrbio.* family) ---------

_RECORDERS = None
_REC_LOCK = threading.Lock()


def recorders():
    """usrbio.* metric family (docs/observability.md): submitted/completed
    SQEs and bytes moved on the agent side, ring_full refusals on the
    client side, live agent dispatch depth."""
    global _RECORDERS
    if _RECORDERS is None:
        with _REC_LOCK:
            if _RECORDERS is None:
                from tpu3fs.monitor.recorder import (
                    CounterRecorder,
                    ValueRecorder,
                )

                _RECORDERS = {
                    "submitted": CounterRecorder("usrbio.submitted"),
                    "completed": CounterRecorder("usrbio.completed"),
                    "ring_full": CounterRecorder("usrbio.ring_full"),
                    "bytes": CounterRecorder("usrbio.bytes"),
                    "agent_depth": ValueRecorder("usrbio.agent_depth"),
                }
    return _RECORDERS


# -- request / reply region framing (both halves) ----------------------------

def request_size(payload: bytes, bulk_iovs) -> int:
    n = _U32.size + len(payload)
    if bulk_iovs is not None:
        n += len(pack_bulk_header(bulk_iovs)) + sum(
            len(b) for b in bulk_iovs)
    return n


def stage_request(iov: Iov, offset: int, payload: bytes, bulk_iovs) -> int:
    """Write [u32 payload_len][payload][bulk header + segments] at
    ``offset``; -> total bytes staged. The bulk copy here is the ring
    write path's ONE client-side copy (the socket path pays the same copy
    into the kernel)."""
    buf = iov.buf
    pos = offset
    buf[pos:pos + 4] = _U32.pack(len(payload))
    pos += 4
    buf[pos:pos + len(payload)] = payload
    pos += len(payload)
    if bulk_iovs is not None:
        hdr = pack_bulk_header(bulk_iovs)
        buf[pos:pos + len(hdr)] = hdr
        pos += len(hdr)
        for seg in bulk_iovs:
            n = len(seg)
            if n:
                buf[pos:pos + n] = seg
            pos += n
    return pos - offset


def parse_request(region: memoryview, has_bulk: bool):
    """Agent side: -> (payload bytes, bulk segment views | None). Views
    alias the client's shm — valid for the synchronous dispatch only."""
    if len(region) < 4:
        raise FsError(Status(Code.USRBIO_BAD_IOV, "request region short"))
    (plen,) = _U32.unpack(bytes(region[:4]))
    if 4 + plen > len(region):
        raise FsError(Status(Code.USRBIO_BAD_IOV,
                             "request payload overruns region"))
    payload = bytes(region[4:4 + plen])
    bulk = None
    if has_bulk:
        try:
            bulk = split_bulk(region[4 + plen:])
        except ConnectionError as e:
            raise FsError(Status(Code.USRBIO_BAD_IOV, str(e)))
    return payload, bulk


def write_reply(iov: Iov, offset: int, capacity: int, status: int,
                message: str, payload: bytes, reply_iovs) -> int:
    """Agent side: write [RSP_HDR][msg][payload][bulk] into the client's
    reply region — the engine-view -> registered-shm gather that replaces
    the socket's writev + recv copies. -> total bytes, or -1 when the
    reply does not fit ``capacity`` (client sees USRBIO_REPLY_OVERFLOW
    and retries over sockets)."""
    msg_b = message.encode("utf-8")
    bulk_hdr = b""
    bulk_len = 0
    if reply_iovs is not None:
        bulk_hdr = pack_bulk_header(reply_iovs)
        bulk_len = len(bulk_hdr) + sum(len(s) for s in reply_iovs)
    total = RSP_HDR.size + len(msg_b) + len(payload) + bulk_len
    if total > capacity:
        return -1
    buf = iov.buf
    pos = offset
    buf[pos:pos + RSP_HDR.size] = RSP_HDR.pack(
        status & 0xFFFFFFFF, len(msg_b), len(payload), bulk_len)
    pos += RSP_HDR.size
    buf[pos:pos + len(msg_b)] = msg_b
    pos += len(msg_b)
    buf[pos:pos + len(payload)] = payload
    pos += len(payload)
    if reply_iovs is not None:
        buf[pos:pos + len(bulk_hdr)] = bulk_hdr
        pos += len(bulk_hdr)
        for seg in reply_iovs:
            n = len(seg)
            if n:
                buf[pos:pos + n] = seg
            pos += n
    return total


def parse_reply(region: memoryview, total: int):
    """Client side: validate the reply framing against the CQE-reported
    ``total`` (torn/short replies surface as typed USRBIO errors, never
    as silently-wrong bytes) -> (status, message, payload bytes,
    bulk segment views | None)."""
    if total < RSP_HDR.size or total > len(region):
        raise FsError(Status(Code.USRBIO_TORN_RING,
                             f"reply length {total} escapes region"))
    status, msg_len, payload_len, bulk_len = RSP_HDR.unpack(
        bytes(region[:RSP_HDR.size]))
    if RSP_HDR.size + msg_len + payload_len + bulk_len != total:
        raise FsError(Status(Code.USRBIO_TORN_RING,
                             "reply header inconsistent with CQE length"))
    pos = RSP_HDR.size
    message = bytes(region[pos:pos + msg_len]).decode("utf-8", "replace")
    pos += msg_len
    payload = bytes(region[pos:pos + payload_len])
    pos += payload_len
    bulk = None
    if bulk_len:
        try:
            bulk = split_bulk(region[pos:pos + bulk_len])
        except ConnectionError as e:
            raise FsError(Status(Code.USRBIO_TORN_RING, str(e)))
    return status, message, payload, bulk


# -- shm arena ----------------------------------------------------------------

_ALIGN = 64
#: what RingClient.start puts on a reply region besides the caller's data
#: estimate: the header and room for the status message and control payload
RSP_CTRL_BYTES = RSP_HDR.size + 4096


class _ShmArena:
    """First-fit free-list allocator over one registered Iov. Reply
    regions are exported as numpy-backed memoryviews with a finalizer:
    the region returns to the free list when the LAST view over it dies —
    the shm analogue of the socket path's detached receive buffers
    (consumers that retain replies past the request must copy)."""

    def __init__(self, iov: Iov):
        import numpy as np

        self._iov = iov
        self._np = np.frombuffer(iov.buf, dtype=np.uint8)
        self._lock = threading.Lock()
        self._free: List[Tuple[int, int]] = [(0, iov.size)]
        # prefault every page ONCE at setup: a fresh tmpfs mapping would
        # otherwise pay an allocating page fault per 4 KiB on the first
        # pass through the buffer — measured ~2x on the first big batch
        # (the server side then pays only cheap minor faults)
        self._np[::4096] = 0

    def alloc(self, n: int) -> Optional[int]:
        n = (n + _ALIGN - 1) & ~(_ALIGN - 1)
        with self._lock:
            for i, (off, size) in enumerate(self._free):
                if size >= n:
                    if size == n:
                        del self._free[i]
                    else:
                        self._free[i] = (off + n, size - n)
                    return off
        return None

    def free(self, off: int, n: int) -> None:
        n = (n + _ALIGN - 1) & ~(_ALIGN - 1)
        with self._lock:
            self._free.append((off, n))
            self._free.sort()
            merged: List[Tuple[int, int]] = []
            for o, s in self._free:
                if merged and merged[-1][0] + merged[-1][1] == o:
                    merged[-1] = (merged[-1][0], merged[-1][1] + s)
                else:
                    merged.append((o, s))
            self._free = merged

    def tracked_view(self, off: int, n: int) -> memoryview:
        """A memoryview over [off, off+n) whose region self-frees when all
        views over it are garbage (the exporting ndarray slice is weakref-
        finalized; every sub-slice of the returned view keeps it alive)."""
        sub = self._np[off:off + n]
        weakref.finalize(sub, self.free, off, n)
        return memoryview(sub)


# -- the ring transport client -----------------------------------------------

def _cleanup_shm(ring: IoRing, iov: Iov) -> None:
    """GC/exit finalizer for a RingClient's shm pair: the orderly half of
    the lifecycle for clients never closed explicitly — runs both when a
    client is garbage-collected mid-process AND at interpreter exit
    (weakref.finalize registers atexit). The crash half is the agent
    reaper's dead-owner-pid pass."""
    try:
        ring.close()
    except Exception:
        pass
    try:
        iov.close()
    except Exception:
        pass


class _Pending:
    __slots__ = ("userdata", "rsp_type", "req_off", "req_size",
                 "rsp_off", "rsp_cap", "hop", "nbytes")

    def __init__(self, userdata, rsp_type, req_off, req_size, rsp_off,
                 rsp_cap, hop, nbytes):
        self.userdata = userdata
        self.rsp_type = rsp_type
        self.req_off = req_off
        self.req_size = req_size
        self.rsp_off = rsp_off
        self.rsp_cap = rsp_cap
        self.hop = hop   # spans.Hop of a traced call, else None
        self.nbytes = nbytes


class RingClient:
    """One ring + iov pair against one co-located storage process,
    multiplexing whole serde RPCs from many threads: ``start`` preps an
    RPC-mode SQE (pipelined — many starts before any finish), ``finish``
    waits for its CQE and parses the reply out of shared memory. Raises
    FsError with a 12xx USRBIO code on transport-level trouble (the
    messenger's cue to use sockets) and the remote status code on
    application errors, exactly like RpcClient."""

    def __init__(self, entries: int = 128, iov_bytes: int = 64 << 20,
                 call_timeout: float = 30.0, agent_pid: int = 0):
        """``agent_pid`` is the serving process from the handshake: a
        waiter that finds it dead stops at once (USRBIO_AGENT_GONE ->
        sockets) instead of sitting out the call timeout on a ring
        nobody will ever complete — the mirror of the server reaping the
        rings of dead owners."""
        self._agent_pid = agent_pid
        self.iov = Iov(iov_bytes)
        self.ring = IoRing(entries, for_read=True)
        self._arena = _ShmArena(self.iov)
        self._sq_lock = threading.Lock()
        self._cv = threading.Condition()
        self._done: Dict[int, tuple] = {}   # userdata -> (result, stamps)
        #: ops whose caller gave up at a per-call deadline while the op
        #: was still in flight: userdata -> ((req_off, req_size),
        #: (rsp_off, rsp_cap)). The agent may yet read the request and
        #: WILL write the reply region, so both regions stay allocated
        #: until the late CQE is reaped (freed at publish, reply dropped).
        self._abandoned: Dict[int, tuple] = {}
        self._reaping = False
        self._next_ud = 0
        self._call_timeout = call_timeout
        self.closed = False
        self._finalizer = weakref.finalize(
            self, _cleanup_shm, self.ring, self.iov)

    # -- issue ---------------------------------------------------------------
    def start(self, service_id: int, method_id: int, req, rsp_type, *,
              req_type=None, bulk_iovs=None, rsp_data_est: int = 0):
        """Serialize + stage + prep + doorbell. ``rsp_data_est`` sizes the
        reply region's data share (reads pass the requested byte total);
        control slack is added on top."""
        from tpu3fs.analytics import spans as _spans
        from tpu3fs.qos.core import class_to_flags, current_class
        from tpu3fs.rpc.net import encode_envelope_message

        if self.closed:
            raise FsError(Status(Code.USRBIO_AGENT_GONE, "ring closed"))
        hop = _spans.Hop.start()
        token = encode_envelope_message(hop.ctx if hop is not None else None)
        if len(token.encode("utf-8")) > TOKEN_CAP:
            raise FsError(Status(
                Code.USRBIO_BAD_IOV,
                f"envelope token exceeds SQE field ({len(token)} chars)"))
        payload = serialize(req, req_type or type(req))
        req_size = request_size(payload, bulk_iovs)
        rsp_cap = RSP_CTRL_BYTES + int(rsp_data_est)
        # the reply region first: a caller that turns regions of one size
        # over (RpcMessenger.batch_read_pipelined) finds the one it just
        # gave back whole, not a request's length short
        rsp_off = self._arena.alloc(rsp_cap)
        if rsp_off is None:
            raise FsError(Status(Code.USRBIO_RING_FULL,
                                 f"iov arena exhausted ({rsp_cap}B rsp)"))
        req_off = self._arena.alloc(req_size)
        if req_off is None:
            self._arena.free(rsp_off, rsp_cap)
            raise FsError(Status(Code.USRBIO_RING_FULL,
                                 f"iov arena exhausted ({req_size}B req)"))
        try:
            stage_request(self.iov, req_off, payload, bulk_iovs)
            with self._sq_lock:
                self._next_ud += 1
                ud = self._next_ud
                slot = self.ring.prep_rpc(
                    service_id, method_id, req_off, req_size, rsp_off,
                    rsp_cap, userdata=ud,
                    token=token,
                    class_flags=class_to_flags(current_class()),
                    bulk=bulk_iovs is not None)
            if slot < 0:
                recorders()["ring_full"].add()
                raise FsError(Status(Code.USRBIO_RING_FULL,
                                     f"{self.ring.entries} ops in flight"))
            self.ring.submit()
        except BaseException:
            self._arena.free(req_off, req_size)
            self._arena.free(rsp_off, rsp_cap)
            raise
        nbytes = (sum(len(b) for b in bulk_iovs)
                  if bulk_iovs else len(payload))
        if hop is not None:
            hop.issued(nbytes)
        return _Pending(ud, rsp_type, req_off, req_size, rsp_off, rsp_cap,
                        hop, nbytes)

    # -- collect -------------------------------------------------------------
    def finish(self, pending: _Pending, *,
               deadline_s: Optional[float] = None):
        """-> (rsp, reply bulk segment views | None). Reply segments alias
        this client's registered shm; their region recycles when the last
        view dies (retainers must copy, same contract as sockets).

        ``deadline_s`` bounds the wait: past it the call raises
        RPC_TIMEOUT and the op is ABANDONED — its arena regions move to
        ``_abandoned`` and are reclaimed when the late CQE lands, never
        freed under an agent that may still be reading/writing them."""
        hop = pending.hop
        if hop is not None:
            hop.waiting()
        try:
            result, stamps = self._await(pending.userdata,
                                         deadline_s=deadline_s)
        except FsError as e:
            self._give_up(pending, e)
            raise
        self._arena.free(pending.req_off, pending.req_size)
        # what follows is this side's: the reply out of shm into objects
        if hop is not None:
            hop.decoding()
        if result < 0:
            self._arena.free(pending.rsp_off, pending.rsp_cap)
            try:
                code = Code(-result)
            except ValueError:
                code = Code.INTERNAL
            raise FsError(Status(code, "usrbio agent error"))
        # the region's lifetime now belongs to the views parse_reply hands
        # out; when the reply carries no bulk, nothing retains it and the
        # tracked view frees the region as soon as parsing ends
        region = self._arena.tracked_view(pending.rsp_off, pending.rsp_cap)
        try:
            status, message, payload, bulk = parse_reply(region, result)
        finally:
            del region
        # the serving side's two durations ride the CQE's third word
        # (ring.pack_stamps), as a socket reply's Timestamps do
        server = unpack_stamps(stamps) if hop is not None else None
        if status != int(Code.OK):
            if hop is not None:
                hop.collected("rpc.client.ring", code=status, server=server)
            try:
                code = Code(status)
            except ValueError:
                # version skew: a newer server's code outside our enum
                # must still surface as an FsError, not a ValueError that
                # escapes the messenger's error handling
                code = Code.INTERNAL
            raise FsError(Status(code, message))
        rsp = deserialize(payload, pending.rsp_type)
        if hop is not None:
            hop.collected("rpc.client.ring", server=server)
        return rsp, bulk

    def call(self, service_id: int, method_id: int, req, rsp_type, *,
             req_type=None, bulk_iovs=None, rsp_data_est: int = 0,
             deadline_s: Optional[float] = None):
        return self.finish(self.start(
            service_id, method_id, req, rsp_type, req_type=req_type,
            bulk_iovs=bulk_iovs, rsp_data_est=rsp_data_est),
            deadline_s=deadline_s)

    def _give_up(self, pending: _Pending, e: FsError) -> None:
        """Arena bookkeeping for a finish() that raised out of _await. A
        per-call deadline expiry (RPC_TIMEOUT) abandons the in-flight op:
        region ownership moves to the publish path. Any other failure
        keeps the old contract (free the request; the ring is dying)."""
        if e.code != Code.RPC_TIMEOUT:
            self._arena.free(pending.req_off, pending.req_size)
            return
        with self._cv:
            if pending.userdata in self._done:
                # completed inside the give-up window: drop the late
                # reply and reclaim both regions immediately
                self._done.pop(pending.userdata)
                self._arena.free(pending.req_off, pending.req_size)
                self._arena.free(pending.rsp_off, pending.rsp_cap)
            else:
                self._abandoned[pending.userdata] = (
                    (pending.req_off, pending.req_size),
                    (pending.rsp_off, pending.rsp_cap))

    def _await(self, ud: int, *, deadline_s: Optional[float] = None):
        """Wait for `ud`'s CQE -> (result, stamps word). Many threads may wait concurrently: one of
        them at a time plays reaper (semaphore wait + reap + publish),
        the rest sleep on the condition. A caller ``deadline_s`` raises
        RPC_TIMEOUT (the op stays in flight — finish() abandons it);
        the default call timeout raises USRBIO_AGENT_GONE as before."""
        timeout = self._call_timeout if deadline_s is None else deadline_s
        code = (Code.USRBIO_AGENT_GONE if deadline_s is None
                else Code.RPC_TIMEOUT)
        deadline = time.monotonic() + timeout
        while True:
            with self._cv:
                while True:
                    if ud in self._done:
                        return self._done.pop(ud)
                    if self.closed:
                        raise FsError(Status(Code.USRBIO_AGENT_GONE,
                                             "ring closed while waiting"))
                    if not self._reaping:
                        self._reaping = True
                        break
                    left = deadline - time.monotonic()
                    if not self._cv.wait(
                            timeout=min(0.2, max(0.001, left))) \
                            and time.monotonic() > deadline:
                        raise FsError(Status(
                            code, f"no completion in {timeout}s"))
            try:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise FsError(Status(
                        code, f"no completion in {timeout}s"))
                self.ring.complete_sem.wait(timeout=min(0.2, remaining))
                cqes = self.ring.reap(with_stamps=True)
                if not cqes and self._agent_pid \
                        and not _pid_alive(self._agent_pid):
                    raise FsError(Status(
                        Code.USRBIO_AGENT_GONE,
                        f"agent process {self._agent_pid} is gone"))
            except (FsError, ValueError, OSError) as e:
                # _reaping MUST clear on ANY reaper failure — a ValueError
                # from the mmap closing under us (close() racing in-flight
                # calls) would otherwise leave every other waiter spinning
                # to its full call timeout with nobody reaping
                with self._cv:
                    self._reaping = False
                    self._cv.notify_all()
                if isinstance(e, FsError):
                    raise
                raise FsError(Status(
                    Code.USRBIO_AGENT_GONE,
                    f"ring torn down while waiting: {e}"))
            with self._cv:
                self._reaping = False
                if cqes:
                    for result, u, stamps in cqes:
                        regions = self._abandoned.pop(u, None)
                        if regions is not None:
                            # the caller left at its deadline: reclaim
                            for off, size in regions:
                                self._arena.free(off, size)
                        else:
                            self._done[u] = (result, stamps)
                self._cv.notify_all()

    def close(self) -> None:
        """Tear the client half down (creator side: unlinks the shm)."""
        self.closed = True
        with self._cv:
            self._cv.notify_all()
        self._finalizer()  # idempotent: close + unlink ring and iov
