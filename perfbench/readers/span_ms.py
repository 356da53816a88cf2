"""A number from the program's own span tree: the rows the program's tracer
captured while the profiler ran (tpu3fs/analytics/spans.py: every root op
under a profiler session lands in `tracer().captured()`).

Takes the ops named `args["root"]`, outermost or nested, that started inside
the window, computes one value an op from the spans beneath it, and returns
the median over those ops. What is beneath is picked by fnmatch patterns:
`pick` against the names of op spans, `stages` against `op.stage` of stage
spans; `under` keeps only what lies below a span whose name matches it.

  mode "sum"          durations of the picked spans, added
  mode "union"        the time at least one picked span was open (fan-outs
                      run side by side; their sum exceeds the wall)
  mode "self"         the op's own duration less the union of the picked
                      spans beneath it (less its direct children where
                      nothing is picked); with `of`, that of every op
                      beneath the root whose name matches `of`, added
  mode "count"        how many picked spans; with `per_bytes`, over the
                      root's payload in units of that many bytes
  mode "mean_nbytes"  the picked spans' `nbytes` (a count, where the
                      program's doc says so) over how many they are

Durations come out in `args["unit"]` ("us", "ms" or "s"). Finds nothing
(returns None, never 0) where the program has no such sink (an older
program), where no root or no picked span lies in the window, and for every
metric where the sink dropped rows: the count goes to `run.counters`.
"""

from fnmatch import fnmatchcase
from statistics import median

UNIT_US = {"us": 1.0, "ms": 1e3, "s": 1e6}


class Index:
    """The captured rows as a tree: by op name, and children by parent."""

    def __init__(self, rows: list, fields: tuple):
        f = {name: i for i, name in enumerate(fields)}
        self.rows = [(r[f["span_id"]], r[f["parent_id"]], r[f["op"]],
                      r[f["stage"]], r[f["t_perf"]] * 1e6, r[f["dur_us"]],
                      r[f["nbytes"]]) for r in rows]
        self.children: dict = {}
        self.by_op: dict = {}
        for row in self.rows:
            self.children.setdefault(row[1], []).append(row)
            if not row[3]:
                self.by_op.setdefault(row[2], []).append(row)


def index_of(run):
    """The run's span index, built once; None where the program keeps no
    captured spans. Sets run.counters["spans_dropped"] where rows went."""
    if hasattr(run, "span_index"):
        return run.span_index
    run.span_index = None
    try:
        from tpu3fs.analytics import spans
    except ImportError:
        return None
    tracer = spans.tracer()
    if not hasattr(tracer, "captured"):
        return None
    rows, dropped = tracer.captured(), tracer.captured_dropped()
    run.counters["spans_captured"] = len(rows)
    if dropped:
        run.counters["spans_dropped"] = dropped
        return None
    run.span_index = Index(rows, spans.CAPTURED_FIELDS)
    return run.span_index


def name_of(row) -> str:
    return f"{row[2]}.{row[3]}" if row[3] else row[2]


def matches(row, pick, stages) -> bool:
    if row[3]:
        return any(fnmatchcase(name_of(row), p) for p in stages)
    return any(fnmatchcase(row[2], p) for p in pick)


def beneath(index: Index, root, pick, stages, under):
    """Picked spans below `root`; with `under`, only those below a span
    (itself below the root) whose name matches it. A picked span's own
    descendants are not looked at: it stands for them."""
    out = []
    todo = [(kid, under is None) for kid in index.children.get(root[0], [])]
    while todo:
        row, inside = todo.pop()
        if inside and matches(row, pick, stages):
            out.append(row)
            continue
        inside = inside or fnmatchcase(name_of(row), under)
        todo.extend((kid, inside) for kid in index.children.get(row[0], []))
    return out


def union_us(spans: list, lo: float, hi: float) -> float:
    """Microseconds of [lo, hi) that at least one span covers."""
    total, end = 0.0, lo
    for a, b in sorted((max(lo, r[4]), min(hi, r[4] + r[5])) for r in spans):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def self_us(index: Index, op, pick, stages, under) -> float:
    below = (beneath(index, op, pick, stages, under) if pick or stages
             else index.children.get(op[0], []))
    return op[5] - union_us(below, op[4], op[4] + op[5])


def value_of(index: Index, root, args):
    """One root's value, or None where nothing picked lies beneath it."""
    pick, stages = args.get("pick", []), args.get("stages", [])
    under, mode = args.get("under"), args["mode"]
    if mode == "self":
        if "of" not in args:
            return self_us(index, root, pick, stages, under)
        ops = beneath(index, root, [args["of"]], [], None)
        if not ops:
            return None
        return sum(self_us(index, op, pick, stages, under) for op in ops)
    found = beneath(index, root, pick, stages, under)
    if not found:
        return None
    if mode == "sum":
        return sum(r[5] for r in found)
    if mode == "union":
        return union_us(found, root[4], root[4] + root[5])
    if mode == "mean_nbytes":
        return sum(r[6] for r in found) / len(found)
    if mode == "count":
        if "per_bytes" not in args:
            return float(len(found))
        units = root[6] / float(args["per_bytes"])
        return len(found) / units if units > 0 else None
    raise ValueError(f"span_ms: no mode {mode!r}")


def read(run, args):
    index = index_of(run)
    if index is None:
        return None
    t_lo, t_hi = (t * 1e6 for t in run.window)
    values = [v for v in (value_of(index, root, args)
                          for root in index.by_op.get(args["root"], [])
                          if t_lo <= root[4] <= t_hi) if v is not None]
    if not values:
        return None
    scale = (UNIT_US[args["unit"]] if args["mode"] in ("sum", "union", "self")
             else 1.0)
    return median(values) / scale
