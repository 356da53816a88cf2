"""Median, over the window's requests, of the milliseconds a request spent
inside one layer's calls as the timing proxies saw them. `ops` narrows the
layer to some of its methods. Spans of threads that serve no one request
(a prefetching producer) carry no request id: with "per": "request_count"
the layer's whole time in the window is divided by the number of requests
instead."""

from statistics import median


def read(run, args):
    ops = set(args["ops"]) if args.get("ops") else None
    t_lo, t_hi = run.window
    picked = [(rid, t1 - t0) for layer, op, rid, t0, t1 in run.spans.spans
              if layer == args["layer"] and (ops is None or op in ops)
              and t_lo <= t0 <= t_hi]
    ok = [r for r in run.requests if r["ok"]]
    if not picked or not ok:
        return None
    if args.get("per") == "request_count":
        return sum(dt for _, dt in picked) * 1e3 / len(ok)
    per: dict = {}
    for rid, dt in picked:
        per[rid] = per.get(rid, 0.0) + dt
    values = [per[r["id"]] * 1e3 for r in ok if r["id"] in per]
    return median(values) if values else None
