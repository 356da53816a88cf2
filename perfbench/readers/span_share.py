"""A share of bytes in the program's own span tree: over the window's ops
named `args["root"]`, the `nbytes` of the stage spans matching `part` as a
percentage of the `nbytes` of those matching `whole` (fnmatch patterns
against `op.stage`). One share over all the ops, not a median of shares: an
op that moved nothing weighs nothing. Finds nothing where span_ms finds
nothing, and where the whole is 0 bytes."""

from .span_ms import beneath, index_of


def read(run, args):
    index = index_of(run)
    if index is None:
        return None
    t_lo, t_hi = (t * 1e6 for t in run.window)
    part = whole = 0
    for root in index.by_op.get(args["root"], []):
        if not t_lo <= root[4] <= t_hi:
            continue
        part += sum(r[6] for r in beneath(index, root, [], args["part"],
                                          None))
        whole += sum(r[6] for r in beneath(index, root, [], args["whole"],
                                           None))
    if whole <= 0:
        return None
    return 100.0 * part / whole
