"""CPU time from the program's own span tree: the `cpu_us` column of the
rows span_ms reads (tpu3fs/analytics/spans.py: the emitting thread's
`time.thread_time_ns` over a live span; -1 = not measured), with each row's
thread (`tid`). The program reads that clock only where no span above reads
the same thread already (a thread's outermost op span, a pool worker's hop)
and in the stages that ask for it; what runs beneath a reading is in it. A
thread that waits for the interpreter lock, a lock, a socket or the device
is off the CPU alike, so a span's wall time splits into its thread's CPU,
what the server's stamps explain, and a queue.

Takes the ops named `args["root"]` that started inside the window, as span_ms
does, computes one value an op and returns the MEAN over those ops — not
span_ms's median: a thread CPU clock may tick coarsely (on the chip host in
10-ms steps, sampled: an op of 30 ms reads 20, 30 or 40), and of such
readings the mean is right where the median is one of the steps:

  mode "cpu"     the op's CPU in ALL threads. Walks down from the root
                 carrying the thread whose reading already covers the rows
                 it meets: a measured row of ANOTHER thread adds its
                 `cpu_us` and covers its own subtree; a measured row of the
                 covering thread adds nothing (it ran inside the reading
                 above it); rows that read -1 are walked through. A pool's
                 fan-out counts once a thread, a nested span never twice.
  mode "resume"  the op's wall time less its own thread's CPU (the root's
                 row) less the time the servers' stamps explain (the union
                 of the `server_wait` and `server_run` stages of the hops
                 beneath it), not below 0: how long the op's thread stood
                 off the CPU for something no server was doing — flight,
                 wake-up, the queue for the interpreter lock, a wait for
                 the device. A hop without stamps explains nothing. Ops
                 whose root reads -1 are left out.
  mode "offcpu"  over the picked spans beneath the root (`pick`, `stages`,
                 `under` as in span_ms) that are measured, the sum of
                 duration - CPU. Pointed at a stage that makes no blocking
                 call it reads preemption and the lock's queue inside
                 CPU-only code.

  mode "cores"   no root: over ALL rows that start inside the window, the
                 "cpu" rule's sum (a trace's topmost measured rows, and rows
                 of another thread than the reading above them) over the
                 window's length: how many cores the program's own spans
                 kept busy in this process. Native code that drops the lock
                 counts, so it can pass 1.

Durations come out in `args["unit"]` ("us", "ms" or "s"); "cores" is a
ratio. Finds nothing (returns None, never 0) where span_ms finds nothing —
no sink, rows dropped, no root in the window — and on a program whose rows
have no `cpu_us`, and where every row looked at reads -1.
"""

from statistics import fmean

from .span_ms import UNIT_US, beneath, union_us
from .span_ms import index_of as span_index_of

T_US, DUR, CPU, TID = 4, 5, 7, 8
SERVED = ["rpc.client.server_wait", "rpc.client.server_run"]


class Index:
    """The captured rows as span_ms.Index has them (so that its `beneath`
    walks this tree too), each with `cpu_us` and `tid` behind."""

    def __init__(self, rows: list, fields: tuple):
        f = {name: i for i, name in enumerate(fields)}
        self.rows = [(r[f["span_id"]], r[f["parent_id"]], r[f["op"]],
                      r[f["stage"]], r[f["t_perf"]] * 1e6, r[f["dur_us"]],
                      r[f["nbytes"]], r[f["cpu_us"]], r[f["tid"]])
                     for r in rows]
        self.children: dict = {}
        self.by_op: dict = {}
        for row in self.rows:
            self.children.setdefault(row[1], []).append(row)
            if not row[3]:
                self.by_op.setdefault(row[2], []).append(row)
        ids = {row[0] for row in self.rows}
        # rows whose parent was not captured here: a trace's root, or the
        # child of a span of another process
        self.tops = [row for row in self.rows if row[1] not in ids]
        self.measured = sum(1 for row in self.rows if row[CPU] >= 0)


def index_from(rows: list, fields: tuple):
    """-> Index, or None for rows of a program without the column."""
    if "cpu_us" not in fields or "tid" not in fields:
        return None
    return Index(rows, fields)


def index_of(run):
    """The run's index with the CPU column, built once; None where span_ms
    finds no rows to read (no sink, rows dropped: it counts them) or the
    program's rows have no `cpu_us`."""
    if hasattr(run, "span_cpu_index"):
        return run.span_cpu_index
    run.span_cpu_index = None
    if span_index_of(run) is None:
        return None
    from tpu3fs.analytics import spans

    index = index_from(spans.tracer().captured(), spans.CAPTURED_FIELDS)
    if index is not None:
        run.counters["spans_with_cpu"] = index.measured
        say = getattr(run, "say", None)
        if say is not None:
            say(f"[spans] {len(index.rows)} rows captured in the profiled "
                f"session, none dropped, {index.measured} with cpu_us")
    run.span_cpu_index = index
    return index


def threads_cpu_us(index: Index, tops: list, t_lo: float = float("-inf"),
                   t_hi: float = float("inf")):
    """The "cpu" rule over the trees below `tops`, adding only rows that
    start in [t_lo, t_hi]; None where no measured row was added."""
    total, added = 0.0, 0
    todo = [(row, None) for row in tops]
    while todo:
        row, cover = todo.pop()
        if row[CPU] >= 0 and row[TID] != cover:
            cover = row[TID]
            if t_lo <= row[T_US] <= t_hi:
                total += row[CPU]
                added += 1
        todo.extend((kid, cover) for kid in index.children.get(row[0], ()))
    return total if added else None


def resume_us(index: Index, root):
    if root[CPU] < 0:
        return None
    served = union_us(beneath(index, root, [], SERVED, None), root[T_US],
                      root[T_US] + root[DUR])
    return max(0.0, root[DUR] - root[CPU] - served)


def offcpu_us(index: Index, root, args):
    found = [r for r in beneath(index, root, args.get("pick", []),
                                args.get("stages", []), args.get("under"))
             if r[CPU] >= 0]
    return sum(r[DUR] - r[CPU] for r in found) if found else None


def value_of(index: Index, root, args):
    mode = args["mode"]
    if mode == "cpu":
        return threads_cpu_us(index, [root])
    if mode == "resume":
        return resume_us(index, root)
    if mode == "offcpu":
        return offcpu_us(index, root, args)
    raise ValueError(f"span_cpu: no mode {mode!r}")


def read(run, args):
    index = index_of(run)
    if index is None:
        return None
    t_lo, t_hi = (t * 1e6 for t in run.window)
    if args["mode"] == "cores":
        total = threads_cpu_us(index, index.tops, t_lo, t_hi)
        if total is None or t_hi <= t_lo:
            return None
        return total / (t_hi - t_lo)
    values = [v for v in (value_of(index, root, args)
                          for root in index.by_op.get(args["root"], [])
                          if t_lo <= root[T_US] <= t_hi) if v is not None]
    if not values:
        return None
    return fmean(values) / UNIT_US[args["unit"]]
