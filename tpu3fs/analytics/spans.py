"""Distributed request tracing: span-stamped RPCs + stage-level timings.

Re-expresses the reference's three-way instrumentation (monitor latency
families on every op, a StructuredTraceLog plugged into the storage write
path, per-request identity threaded through the stack) as ONE substrate:
a ``TraceContext`` (trace id, current span id, sampled + slow bits) rides
the RPC envelope's ``message`` field on requests — a field every decoder,
old or new, python or native, already parses and ignores on requests, so
the encoding is version-tolerant in both directions — and propagates
in-process through a ``contextvars.ContextVar`` (the same machinery that
carries the QoS traffic class through WorkerPool fan-outs, chain-forward
helper threads and the fabric's direct dispatch).

Each layer emits typed ``SpanEvent`` rows — op spans (an RPC dispatch, a
client batch op) and stage spans (admission wait, update-queue wait,
engine stage, chain forward, commit, meta txn, client issue/collect) —
into the context's process-local accumulator. At op end ONE decision
flushes or drops the whole accumulation:

- HEAD SAMPLING: the root creator samples deterministically from the
  trace id (``sampled_of``), downstream hops honor the bit — a trace is
  captured everywhere or nowhere;
- SLOW-OP CAPTURE: an op whose wall time exceeds ``slow_op_ms`` flushes
  UNCONDITIONALLY, sampling rate 0 included — the ops an operator most
  needs are never the ones sampling dropped;
- FORCED capture: the wire slow bit (set via ``start_trace(force=True)``)
  makes every hop flush, for targeted debugging;
- PROFILED capture: while a ``jax.profiler`` session is active in this
  process every root op is captured, ``trace.dir`` or not, into a
  bounded in-memory sink (``tracer().captured()``), and every live span
  is also a profiler annotation ``t3:<op>[.<stage>]`` — whoever profiles
  the chip gets the host's spans with the profile, on one clock (the
  ``t3:anchor`` mark; ``assemble.spans_to_trace_clock``); nobody else
  pays more than one ``TraceMe.is_enabled()`` call a root op.

Flushed spans stream through ``analytics.trace.StructuredTraceLog`` —
the same columnar sink the storage event trace uses — one file set per
process; ``analytics.assemble`` joins the files of N processes back into
per-trace trees. Overhead discipline: with no tracer configured and no
profiler session the only cost on any hot path is one ContextVar read
returning None (nested sites) or that plus one ``is_enabled()`` call (root
op sites).

Three clocks, each read only where a span starts or ends and only when the
op is traced: ``time.time()`` gives a row its ``ts`` (the one clock other
processes share), ``time.perf_counter()`` its ``dur_us`` and ``t_perf``
(this process's, the clock the profiler's trace is tied to), and
``time.thread_time_ns()`` its ``cpu_us`` — how long the EMITTING THREAD
was on a CPU between the span's start and end. A thread that waits — for
the GIL, a lock, a socket, a semaphore, the device — is off the CPU alike,
so ``dur_us - cpu_us`` of a span that makes no blocking call is what it
queued for the interpreter. The clock is per thread: a row whose start and
end were not read by one thread, or that was measured after the fact, or
that is derived from another process's stamps, carries ``cpu_us`` -1.
It is also a real system call where the other two are not, and on the chip
host a dear one (6 us a read in a loop, about 20 inside a busy client, in
10-ms ticks: docs/observability.md "Overhead"), so it is read only where a
reading adds something: by a span that no span above it on the same
thread already covers (``TraceContext.cover``) — the outermost op span of
a thread (``root_span``, ``open_op``/``close_op``) and a hop on a pool
worker's thread — and by a stage whose site asks (``span(..., cpu=True)``:
a stage a metric names). What runs beneath a reading on its thread is
inside it; every other row reads -1.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import operator
import os
import random
import threading
import time
from dataclasses import dataclass, fields
from typing import List, Optional, Sequence, Tuple

from tpu3fs.utils.config import Config, ConfigItem

# -- the wire + file schema ---------------------------------------------------

WIRE_VERSION = "t1"

# wire flag bits (TraceContext.flags on the envelope)
FLAG_SAMPLED = 1
FLAG_SLOW = 2      # forced capture: every hop flushes


@dataclass
class SpanEvent:
    """One span row (columnar via analytics.trace; schema in
    docs/observability.md). Op spans have stage == ""; stage spans carry
    the stage name and parent to their op span."""

    trace_id: str = ""
    span_id: str = ""
    parent_id: str = ""
    service: str = ""      # emitting process role (storage/meta/client/...)
    node: int = 0          # emitting node id (0 = client-side)
    op: str = ""           # operation name (client.batch_write, rpc.server...)
    stage: str = ""        # "" for op spans; stage name for stage spans
    ts: float = 0.0        # wall-clock start (time.time; cross-process join)
    dur_us: float = 0.0
    code: int = 0          # status code (0 = OK)
    nbytes: int = 0
    tclass: str = ""       # QoS traffic class, when tagged
    tenant: str = ""       # owning tenant (op spans; tpu3fs/tenant)
    sampled: bool = False
    slow: bool = False     # flushed by the slow-op/forced path
    # the emitting thread's CPU time over the span (time.thread_time_ns at
    # both ends: a thread's outermost op span, a pool worker's hop, a stage
    # that asks); -1 = not measured
    cpu_us: float = -1.0
    # in-memory only (the profiled sink; never written to the span files):
    t_perf: float = 0.0    # start on time.perf_counter (this process's)
    tid: int = 0           # emitting thread's ident


# the two fields the span files do not carry (a per-process clock and a
# per-process thread id mean nothing to another process's reader; cpu_us is
# a duration like dur_us and is a column of the files)
_MEMORY_ONLY = ("t_perf", "tid")
# a captured row is a plain tuple in this field order: a window of a
# traced cell holds some hundred thousand rows, and a tuple of shared
# strings and small numbers costs about a third of a dataclass instance
CAPTURED_FIELDS = tuple(f.name for f in fields(SpanEvent))
_row_of = operator.attrgetter(*CAPTURED_FIELDS)
# most rows the in-memory sink holds; the oldest are dropped and counted.
# Reckoned from the largest window measured (kvcache_sessions: about
# 300 000 rows, 110 MB): three windows' worth, under 400 MB
CAPTURE_MAX_ROWS = 1 << 20

ANNOTATION_PREFIX = "t3:"
ANCHOR_NAME = ANNOTATION_PREFIX + "anchor"


def _resolve_profiler():
    """-> (is_enabled, TraceMe) of this process's jaxlib, looked up once.
    No jaxlib, or a jaxlib that moved the symbol, means "never profiled"
    (tests/test_trace.py pins the import so that an upgrade fails a test
    instead of silently ending all capture)."""
    try:
        from jaxlib._profiler import TraceMe

        return TraceMe.is_enabled, TraceMe
    except (ImportError, AttributeError):
        return (lambda: False), None


_is_profiling = None   # resolved at the first root op
_TraceMe = None


def profiler_active() -> bool:
    """Whether a jax.profiler session is active in this process."""
    global _is_profiling, _TraceMe
    if _is_profiling is None:
        _is_profiling, _TraceMe = _resolve_profiler()
    return _is_profiling()


class TraceContext:
    """Per-request trace identity + the process-local span accumulator.

    ``span_id`` is the CURRENT span: events emitted under this context
    parent to it. ``child()`` derives a nested context (new span id, same
    trace, same accumulator) for a sub-operation whose own events should
    parent to the sub-op span — the RPC client span does this so server
    spans nest under the wire hop.
    """

    __slots__ = ("trace_id", "span_id", "parent_id", "sampled", "slow",
                 "events", "profiled", "root", "nbytes", "ts", "mark",
                 "cpu0", "cover")

    def __init__(self, trace_id: str, span_id: str, parent_id: str = "",
                 sampled: bool = False, slow: bool = False,
                 events: Optional[list] = None, profiled: bool = False,
                 cover: int = 0):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.sampled = sampled
        self.slow = slow
        # captured because a profiler session is active: rows go to the
        # tracer's in-memory sink, live spans are profiler annotations
        self.profiled = profiled
        # open_op()'s bookkeeping for the op this context is the span of
        self.root = False      # owns the accumulator: flushes at close
        self.nbytes = 0        # payload bytes, where known only at the end
        self.ts = 0.0          # wall-clock start
        self.mark = None       # the open profiler annotation
        self.cpu0 = None       # (thread ident, thread_time_ns) at the start
        # ident of the thread whose CPU a span above this one is reading
        # (0 = none): what runs on that thread beneath it is inside that
        # reading and need not read the clock again
        self.cover = cover
        # list.append is GIL-atomic: overlap-forward helper threads and
        # worker threads may append concurrently with the op thread
        self.events: List[SpanEvent] = events if events is not None else []

    def child(self) -> "TraceContext":
        """Nested context for a sub-op in THIS process (shared
        accumulator: one flush decision covers the whole op)."""
        return TraceContext(self.trace_id, _new_id(), self.span_id,
                            self.sampled, self.slow, self.events,
                            self.profiled, self.cover)

    # -- envelope carriage -------------------------------------------------
    def to_wire(self) -> str:
        flags = (FLAG_SAMPLED if self.sampled else 0) \
            | (FLAG_SLOW if self.slow else 0)
        return f"{WIRE_VERSION}.{self.trace_id}.{self.span_id}.{flags:x}"


def decode_wire(message: str) -> Optional[TraceContext]:
    """Parse a TraceContext off a request envelope; None for absent,
    malformed or future-versioned encodings (old servers that never call
    this simply ignore the field — interop is free in both directions).
    Fields beyond the fourth are ignored: a newer peer may append."""
    if not message or not message.startswith(WIRE_VERSION + "."):
        return None
    parts = message.split(".")
    if len(parts) < 4:
        return None
    trace_id, span_id = parts[1], parts[2]
    if not trace_id or not span_id:
        return None
    try:
        flags = int(parts[3], 16)
    except ValueError:
        return None
    # fresh accumulator: this process flushes its own spans
    return TraceContext(trace_id, span_id,
                        sampled=bool(flags & FLAG_SAMPLED),
                        slow=bool(flags & FLAG_SLOW))


# Span and trace ids: 64 random bits from a generator of this process's own
# (seeded from the OS, again in a forked child), not os.urandom per id — a
# traced RPC hop makes six ids, and where getrandom() is a slow system call
# (the chip host: about 20 us) that alone took a quarter of a traced cell's
# requests (PERF.md, PR 25).
_id_source = random.Random()
os.register_at_fork(after_in_child=_id_source.seed)


def _new_id() -> str:
    return "%016x" % _id_source.getrandbits(64)


def sampled_of(trace_id: str, rate: float) -> bool:
    """Deterministic head-sampling decision: a pure function of
    (trace id, rate), so any process given the same id and rate agrees —
    the property the sampling-determinism test pins."""
    if rate <= 0.0:
        return False
    if rate >= 1.0:
        return True
    try:
        v = int(trace_id[:8], 16)
    except ValueError:
        return False
    return (v / float(0xFFFFFFFF)) < rate


# -- config -------------------------------------------------------------------


class TraceConfig(Config):
    """Hot-updatable tracing knobs, one section per service binary
    (config pushes through mgmtd retune sampling live — no restart)."""

    enabled = ConfigItem(True, hot=True)
    # head-sampling probability for ops with no inbound context
    sample_rate = ConfigItem(0.0, hot=True,
                             checker=lambda v: 0.0 <= v <= 1.0)
    # ops slower than this flush unconditionally (sampling=0 included);
    # <= 0 disables slow-op capture
    slow_op_ms = ConfigItem(200.0, hot=True)
    # span sink directory; "" = tracing off for this process
    dir = ConfigItem("")
    flush_rows = ConfigItem(512, hot=True, checker=lambda v: v >= 1)


# -- the per-process tracer ---------------------------------------------------


class Tracer:
    """Process-global tracing state: identity tags, sampling knobs, the
    columnar sink. ``configure()`` is idempotent and hot-callable."""

    def __init__(self):
        self.enabled = False
        self.service = "proc"
        self.node = 0
        self.sample_rate = 0.0
        self.slow_op_us = 200_000.0
        self._log = None
        self._log_dir = None
        self._lock = threading.Lock()
        # slow-op hooks (the flight recorder's black-box feed): called
        # with the op's accumulated events whenever an op crosses the
        # slow threshold, independent of the sampling decision
        self._slow_hooks: List = []
        # the profiled sink: rows of ops that finished under a profiler
        # session (module doc); bounded, oldest dropped and counted
        self._captured: collections.deque = collections.deque(
            maxlen=CAPTURE_MAX_ROWS)
        self._captured_total = 0
        self._cap_lock = threading.Lock()
        # the clock tie of the current profiler session: perf_counter_ns
        # and time_ns read inside its one t3:anchor annotation
        self._anchor: Optional[Tuple[int, int]] = None
        self._in_session = False

    def configure(self, *, service: Optional[str] = None,
                  node: Optional[int] = None,
                  directory: Optional[str] = None,
                  sample_rate: Optional[float] = None,
                  slow_op_ms: Optional[float] = None,
                  enabled: Optional[bool] = None,
                  flush_rows: int = 512) -> "Tracer":
        with self._lock:
            if service is not None:
                self.service = service
            if node is not None:
                self.node = node
            if sample_rate is not None:
                self.sample_rate = float(sample_rate)
            if slow_op_ms is not None:
                self.slow_op_us = (float(slow_op_ms) * 1e3
                                   if slow_op_ms and slow_op_ms > 0
                                   else float("inf"))
            if directory is not None and directory != self._log_dir:
                from tpu3fs.analytics.trace import StructuredTraceLog

                self._log = StructuredTraceLog("spans", directory,
                                               flush_rows=flush_rows)
                self._log_dir = directory
            if enabled is not None:
                self.enabled = bool(enabled) and self._log is not None
            elif self._log is not None:
                self.enabled = True
        return self

    def apply_config(self, cfg: TraceConfig, *, service: str,
                     node: int) -> None:
        """Bind a TraceConfig section (and follow its hot updates)."""
        def _apply(_node=None):
            self.configure(
                service=service, node=node,
                directory=(cfg.dir or None),
                sample_rate=cfg.sample_rate, slow_op_ms=cfg.slow_op_ms,
                enabled=bool(cfg.enabled) and bool(cfg.dir),
                flush_rows=int(cfg.flush_rows))

        _apply()
        cfg.add_callback(_apply)

    def add_slow_hook(self, fn) -> None:
        """Register fn(events) to run on every slow-op flush (idempotent
        for the same callable — N apps in one process hook once)."""
        if fn not in self._slow_hooks:
            self._slow_hooks.append(fn)

    def flush(self) -> None:
        log = self._log
        if log is not None:
            log.flush()

    @property
    def span_paths(self) -> List[str]:
        log = self._log
        if log is None:
            return []
        return list(log.paths)

    # -- the profiled sink ---------------------------------------------------
    def captured(self) -> List[tuple]:
        """Rows (CAPTURED_FIELDS order) of the ops that finished under a
        profiler session since the last reset, oldest first."""
        with self._cap_lock:
            return list(self._captured)

    def captured_dropped(self) -> int:
        """Rows the bounded sink dropped since the last reset."""
        with self._cap_lock:
            return self._captured_total - len(self._captured)

    def reset_captured(self) -> None:
        with self._cap_lock:
            self._captured.clear()
            self._captured_total = 0

    def anchor(self) -> Optional[Tuple[int, int]]:
        """(perf_counter_ns, time_ns) read inside the newest session's
        t3:anchor annotation; None before the first profiled op."""
        return self._anchor

    def _profiled(self) -> bool:
        """Whether a profiler session is active; its first root op drops
        the anchor that ties this process's clock to the trace's."""
        if not profiler_active():
            self._in_session = False
            return False
        if not self._in_session:
            with self._cap_lock:
                if not self._in_session:
                    with _TraceMe(ANCHOR_NAME):
                        self._anchor = (time.perf_counter_ns(),
                                        time.time_ns())
                    self._in_session = True
        return True

    # -- emission ----------------------------------------------------------
    def start_trace(self, force: bool = False) -> Optional[TraceContext]:
        """Head decision for an op with no inbound context. Returns None
        when tracing is off for this process and no profiler session is
        active (the zero-overhead path: one is_enabled() call)."""
        profiled = self._profiled()
        if not (self.enabled or profiled):
            return None
        tid = _new_id()
        return TraceContext(tid, _new_id(),
                            sampled=(self.enabled
                                     and sampled_of(tid, self.sample_rate)),
                            slow=force, profiled=profiled)

    def _flush_events(self, events: Sequence[SpanEvent],
                      slow: bool) -> None:
        log = self._log
        if log is None:
            return
        for ev in events:
            if slow:
                ev.slow = True
            # SpanEvent is flat: its __dict__ IS the columnar row (skips
            # the per-event reflection walk on the flush path)
            row = dict(ev.__dict__)
            for name in _MEMORY_ONLY:
                del row[name]
            log.append_row(row)

    def _capture(self, events: Sequence[SpanEvent]) -> None:
        rows = [_row_of(ev) for ev in events]
        with self._cap_lock:
            self._captured_total += len(rows)
            self._captured.extend(rows)

    def end_op(self, ctx: TraceContext, op: str, ts: float, dur_s: float,
               *, code: int = 0, nbytes: int = 0,
               tclass: str = "", tenant: str = "",
               t_perf: Optional[float] = None,
               cpu_us: float = -1.0) -> None:
        """Append the op span for a NESTED op (the flush decision belongs
        to whichever op owns the accumulator — the process root). An
        empty tenant resolves from the ambient scope, so every op span
        carries its owner without each call site threading it."""
        if not tenant:
            tenant = _ambient_tenant()
        ctx.events.append(SpanEvent(
            trace_id=ctx.trace_id, span_id=ctx.span_id,
            parent_id=ctx.parent_id, service=self.service, node=self.node,
            op=op, stage="", ts=ts, dur_us=dur_s * 1e6, code=code,
            nbytes=nbytes, tclass=tclass, tenant=tenant,
            sampled=ctx.sampled, cpu_us=cpu_us,
            t_perf=perf_of_wall(ts) if t_perf is None else t_perf,
            tid=threading.get_ident()))

    def finish_op(self, ctx: TraceContext, op: str, ts: float,
                  dur_s: float, *, code: int = 0, nbytes: int = 0,
                  tclass: str = "", tenant: str = "",
                  t_perf: Optional[float] = None,
                  cpu_us: float = -1.0) -> None:
        """Emit the op span and make the flush-or-drop decision for every
        event the op accumulated in this process."""
        self.end_op(ctx, op, ts, dur_s, code=code, nbytes=nbytes,
                    tclass=tclass, tenant=tenant, t_perf=t_perf,
                    cpu_us=cpu_us)
        is_slow = ctx.slow or dur_s * 1e6 >= self.slow_op_us
        if is_slow and self._slow_hooks:
            for hook in self._slow_hooks:
                try:
                    hook(list(ctx.events))
                except Exception:
                    pass  # a black-box feed must never fail the op
        if ctx.profiled:
            self._capture(ctx.events)
        if ctx.sampled or is_slow:
            self._flush_events(ctx.events, is_slow and not ctx.sampled)
        ctx.events.clear()


_TRACER = Tracer()

_current_tenant = None   # tpu3fs.tenant.identity's, resolved once


def _ambient_tenant() -> str:
    global _current_tenant
    if _current_tenant is None:
        from tpu3fs.tenant.identity import current_tenant

        _current_tenant = current_tenant
    return _current_tenant() or ""


# A row's start on perf_counter, for emitters that took only a wall-clock
# start (server-side stages, older call sites): the two clocks' distance
# when this module was loaded. Live spans and the RPC hop read both clocks
# at the start instead, which is what the anchor conversion wants.
_WALL_MINUS_PERF = time.time() - time.perf_counter()


def perf_of_wall(ts: float) -> float:
    return ts - _WALL_MINUS_PERF


def wall_of_perf(t_perf: float) -> float:
    return t_perf + _WALL_MINUS_PERF


def tracer() -> Tracer:
    return _TRACER


# -- context propagation ------------------------------------------------------

_trace_var: contextvars.ContextVar[Optional[TraceContext]] = \
    contextvars.ContextVar("tpu3fs_trace_ctx", default=None)

# the update worker's coalesced round may serve SEVERAL traces in one
# engine crossing; stage spans fan out to all of them (each op genuinely
# experienced the full round's stage wall time)
_round_var: contextvars.ContextVar[Optional[Tuple[TraceContext, ...]]] = \
    contextvars.ContextVar("tpu3fs_trace_round", default=None)


def current_trace() -> Optional[TraceContext]:
    return _trace_var.get()


@contextlib.contextmanager
def trace_scope(ctx: Optional[TraceContext]):
    token = _trace_var.set(ctx)
    try:
        yield ctx
    finally:
        _trace_var.reset(token)


@contextlib.contextmanager
def round_scope(ctxs: Sequence[TraceContext]):
    """Scope of one coalesced update round: stage spans address every
    member trace; downstream RPCs (chain forward) propagate the first."""
    ctxs = tuple(ctxs)
    tok_r = _round_var.set(ctxs if ctxs else None)
    tok_t = _trace_var.set(ctxs[0] if ctxs else None)
    try:
        yield
    finally:
        _round_var.reset(tok_r)
        _trace_var.reset(tok_t)


def round_traces() -> Tuple[TraceContext, ...]:
    """Traces the current update round serves: the round scope's set, or
    the single current context, or ()."""
    ctxs = _round_var.get()
    if ctxs is not None:
        return ctxs
    ctx = _trace_var.get()
    return (ctx,) if ctx is not None else ()


# -- emission helpers ---------------------------------------------------------


def add_span(ctx: Optional[TraceContext], op: str, stage: str, ts: float,
             dur_s: float, *, code: int = 0, nbytes: int = 0,
             t_perf: Optional[float] = None,
             span_id: Optional[str] = None, cpu_us: float = -1.0) -> None:
    """Append one already-measured stage span to a context (no-op on
    None): the storage pipeline measures its stage/forward/commit walls
    anyway — tracing reuses those numbers instead of re-clocking. Stages
    measured after the fact are rows only, never profiler annotations,
    and carry no CPU time (``cpu_us`` -1) unless the emitter read the
    thread's CPU clock at both ends itself (``span``, ``Hop``)."""
    if ctx is None:
        return
    t = _TRACER
    ctx.events.append(SpanEvent(
        trace_id=ctx.trace_id, span_id=span_id or _new_id(),
        parent_id=ctx.span_id, service=t.service, node=t.node, op=op,
        stage=stage, ts=ts, dur_us=dur_s * 1e6, code=code, nbytes=nbytes,
        sampled=ctx.sampled, cpu_us=cpu_us,
        t_perf=perf_of_wall(ts) if t_perf is None else t_perf,
        tid=threading.get_ident()))


def add_span_at(ctx: Optional[TraceContext], op: str, stage: str,
                t_perf: float, dur_s: float, *, nbytes: int = 0) -> None:
    """add_span for a stage the caller clocked on perf_counter (a monitor
    recorder's two reads): the wall-clock start is derived."""
    if ctx is not None:
        add_span(ctx, op, stage, wall_of_perf(t_perf), dur_s, nbytes=nbytes,
                 t_perf=t_perf)


def add_span_multi(ctxs: Sequence[TraceContext], op: str, stage: str,
                   ts: float, dur_s: float, *, code: int = 0,
                   nbytes: int = 0) -> None:
    for ctx in ctxs:
        add_span(ctx, op, stage, ts, dur_s, code=code, nbytes=nbytes)


class Hop:
    """The client side of one traced wire hop, whatever the transport
    (sockets, native sockets, USRBIO ring): a child context that rides the
    envelope, and the "rpc.client" stages under it — ``issue`` (serialize
    and put on the wire), ``collect`` (the wait for the reply; a
    container), and, where the reply carries the server's stamps,
    ``server_wait`` (receive to handler start: queue, admission, decode),
    ``server_run`` (the handler), ``wire`` (the collect wait minus the
    server's window: frames in flight, reply serialize and parse) and
    ``decode`` (the reply's payload back into objects, this side). Each
    start is read from the clocks when the stage starts, never
    reconstructed as "now minus duration": the anchor conversion needs
    starts that are exact. The server's two stages tile from the end of
    ``issue``, where the request left this side; ``wire`` ends where the
    reply is in.

    Clocks: ``ts`` is ``time.time()`` at the start; every stage's start
    and duration come from ``time.perf_counter()``. The hop's op span
    carries ``cpu_us`` (``time.thread_time_ns()`` in ``__init__`` and
    ``collected``) only where no span above the hop reads this thread's
    CPU (``TraceContext.cover``: a pool worker's hop); beneath such a
    span the reading is there already, and the hops one thread pipelines
    overlap, so their own readings would count one another's work. The
    stages carry -1."""

    __slots__ = ("ctx", "ts", "t0", "t_issued", "t_wait", "t_decode",
                 "tid", "c0")

    def __init__(self, parent: TraceContext):
        self.ctx = parent.child()
        self.tid = threading.get_ident()
        self.ts = time.time()
        self.t0 = self.t_issued = self.t_wait = time.perf_counter()
        self.c0 = (time.thread_time_ns() if parent.cover != self.tid
                   else None)
        self.t_decode: Optional[float] = None

    @classmethod
    def start(cls) -> Optional["Hop"]:
        """A hop under the calling context's trace; None when untraced
        (one ContextVar read and nothing else)."""
        parent = _trace_var.get()
        return cls(parent) if parent is not None else None

    def _add(self, stage: str, t_perf: float, dur_s: float,
             nbytes: int = 0) -> None:
        add_span(self.ctx, "rpc.client", stage,
                 self.ts + (t_perf - self.t0), dur_s, nbytes=nbytes,
                 t_perf=t_perf)

    def issued(self, nbytes: int = 0) -> None:
        """The request is on the wire; the wait for its reply begins here
        unless ``waiting`` says later (a caller that issues several hops
        before it collects the first)."""
        self.t_issued = self.t_wait = time.perf_counter()
        self._add("issue", self.t0, self.t_issued - self.t0, nbytes)

    def waiting(self) -> None:
        """The wait for the reply begins (``collect``'s start)."""
        self.t_wait = time.perf_counter()

    def decoding(self) -> None:
        """The reply is in and its decode begins (``collect``'s end)."""
        self.t_decode = time.perf_counter()

    def collected(self, op: str, *, code: int = 0,
                  server: Optional[Tuple[float, float]] = None) -> None:
        """The reply is in and, where ``decoding`` said when that began,
        decoded: ``server`` is the (wait, run) seconds of the server's own
        stamps where the reply carried them. Closes the hop."""
        cpu_us = -1.0
        if self.c0 is not None and threading.get_ident() == self.tid:
            cpu_us = (time.thread_time_ns() - self.c0) / 1e3
        now = time.perf_counter()
        t_wait, t_decode = self.t_wait, self.t_decode
        got = now if t_decode is None else t_decode
        self._add("collect", t_wait, got - t_wait)
        if server is not None:
            wait_s, run_s = server
            self._add("server_wait", self.t_issued, wait_s)
            self._add("server_run", self.t_issued + wait_s, run_s)
            wire = (got - t_wait) - (wait_s + run_s)
            if wire > 0:
                self._add("wire", got - wire, wire)
        if t_decode is not None:
            self._add("decode", t_decode, now - t_decode)
        _TRACER.end_op(self.ctx, op, self.ts, now - self.t0, code=code,
                       t_perf=self.t0, cpu_us=cpu_us)


def _mark(ctx: TraceContext, op: str, stage: str = ""):
    """Open the profiler annotation of a live span (None unless the trace
    is a profiled one): the span then lies on the profiler's own timeline
    beside the device's operations."""
    if not ctx.profiled:
        return None
    mark = _TraceMe(f"{ANNOTATION_PREFIX}{op}.{stage}" if stage
                    else ANNOTATION_PREFIX + op)
    mark.__enter__()
    return mark


@contextlib.contextmanager
def span(op: str, stage: str, *, nbytes: int = 0, cpu: bool = False):
    """Clock a block as a stage span under the current context (no-op —
    not even a clock read — when untraced). What the block calls parents
    to the stage, so a tree's leaves are what is attributed and a stage
    that holds other spans is seen to (``TraceTree.coverage``). A block
    that learns its payload only at the end sets ``nbytes`` on
    ``current_trace()``, which inside the block is the stage's own.
    Clocks: ``ts`` from ``time.time()``, ``t_perf`` and ``dur_us`` from
    ``time.perf_counter()``; with ``cpu`` (a stage whose CPU a metric
    reads, covered or not: the clock is a system call) ``cpu_us`` from
    ``time.thread_time_ns()`` — the calling thread's CPU time inside the
    block, whatever other threads did for it — and -1 without."""
    ctx = _trace_var.get()
    if ctx is None:
        yield None
        return
    inner = ctx.child()
    mark = _mark(ctx, op, stage)
    ts = time.time()
    t0 = time.perf_counter()
    c0 = None
    if cpu:
        c0 = time.thread_time_ns()
        inner.cover = threading.get_ident()
    token = _trace_var.set(inner)
    try:
        yield ctx
    finally:
        cpu_us = (time.thread_time_ns() - c0) / 1e3 if cpu else -1.0
        dur = time.perf_counter() - t0
        _trace_var.reset(token)
        if mark is not None:
            mark.__exit__(None, None, None)
        add_span(ctx, op, stage, ts, dur, nbytes=nbytes or inner.nbytes,
                 t_perf=t0, span_id=inner.span_id, cpu_us=cpu_us)


def _join_or_start(force: bool) -> Optional[TraceContext]:
    """The context of a new op span: a child of the current trace, or the
    root of a trace of its own (None when nothing captures)."""
    outer = _trace_var.get()
    if outer is not None:
        return outer.child()
    ctx = _TRACER.start_trace(force=force)
    if ctx is not None:
        ctx.root = True
    return ctx


def open_op(op: str, *, force: bool = False,
            live: bool = True) -> Optional[TraceContext]:
    """Open an op span whose body the caller scopes (``trace_scope``) and
    whose clock the caller reads — a monitor recorder's two reads serve
    the span too (``close_op``). Joins the current trace as a child op
    when one is active, otherwise head-starts a trace (sampling or a
    profiler session decide; None when neither captures). ``live`` False
    is for an op whose start the caller back-dates: it gets no profiler
    annotation. The context keeps the wall-clock start (``ts``) and,
    unless a span above it already reads this thread's CPU (``cover``),
    the thread's CPU clock (``cpu0``): the op's ``cpu_us`` counts from
    here, wherever the caller puts its start."""
    ctx = _join_or_start(force)
    if ctx is None:
        return None
    if live:
        ctx.mark = _mark(ctx, op)
    ctx.ts = time.time()
    tid = threading.get_ident()
    if ctx.cover != tid:
        ctx.cover = tid
        ctx.cpu0 = (tid, time.thread_time_ns())
    return ctx


def close_op(ctx: Optional[TraceContext], op: str, t_perf: float,
             dur_s: float, *, code: int = 0, nbytes: int = 0) -> None:
    """Emit the op span of an ``open_op`` context from the caller's clock
    reads (start on perf_counter, seconds); the root op flushes or drops
    everything the op accumulated (incl. slow-op capture). ``cpu_us`` is
    the calling thread's CPU time since ``open_op`` where that read the
    clock, and -1 where it did not or another thread opened the op."""
    if ctx is None:
        return
    cpu_us = -1.0
    if ctx.cpu0 is not None and ctx.cpu0[0] == threading.get_ident():
        cpu_us = (time.thread_time_ns() - ctx.cpu0[1]) / 1e3
    if ctx.mark is not None:
        ctx.mark.__exit__(None, None, None)
        ctx.mark = None
    emit = _TRACER.finish_op if ctx.root else _TRACER.end_op
    emit(ctx, op, ctx.ts, dur_s, code=code, nbytes=nbytes or ctx.nbytes,
         t_perf=t_perf, cpu_us=cpu_us)


def add_op(op: str, t_perf: float, dur_s: float, *, nbytes: int = 0) -> None:
    """An already-measured op with nothing beneath it (a consumer's wait a
    recorder clocked): one row under the current trace, or a trace of its
    own. No annotation and no CPU time: it is over when it is known."""
    ctx = _join_or_start(False)
    if ctx is not None:
        ctx.ts = wall_of_perf(t_perf)
        close_op(ctx, op, t_perf, dur_s, nbytes=nbytes)


@contextlib.contextmanager
def root_span(op: str, *, nbytes: int = 0, force: bool = False,
              code: int = 0):
    """Client-side op boundary: joins the current trace when one is
    active (a nested client op is a child op span, and what it calls
    parents to it), otherwise head-starts a trace — sampling decision,
    envelope stamping downstream, flush-or-drop at exit (incl. slow-op
    capture). Yields the op's context or None; a caller that learns the
    payload size only at the end sets ``ctx.nbytes`` before leaving.
    ``code`` is what a block that does not raise records (-1 if it does).
    Clocks: ``open_op``'s (``ts``; the thread's CPU where this is the
    outermost op span of its thread) and perf_counter here."""
    ctx = open_op(op, force=force)
    if ctx is None:
        yield None
        return
    t0 = time.perf_counter()
    token = _trace_var.set(ctx)
    try:
        yield ctx
    except BaseException:
        code = -1
        raise
    finally:
        _trace_var.reset(token)
        close_op(ctx, op, t0, time.perf_counter() - t0, code=code,
                 nbytes=nbytes)
