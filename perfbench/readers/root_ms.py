"""The wall time of the program's own root op: over the ops named
`args["root"]` that started inside the window (span_ms's rows), the median
of their durations, in `args["unit"]`. Finds nothing where span_ms finds
nothing: no sink, rows dropped, no such op in the window."""

from statistics import median

from .span_ms import UNIT_US, index_of


def read(run, args):
    index = index_of(run)
    if index is None:
        return None
    t_lo, t_hi = (t * 1e6 for t in run.window)
    durs = [row[5] for row in index.by_op.get(args["root"], [])
            if t_lo <= row[4] <= t_hi]
    if not durs:
        return None
    return median(durs) / UNIT_US[args["unit"]]
