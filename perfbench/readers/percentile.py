"""A percentile over ALL requests of the window, in milliseconds. A failed
request is missing: it is counted at the whole window's length, the worst a
request could read."""

import math

from .rate import window_seconds


def read(run, args):
    if not run.requests:
        return None
    worst = window_seconds(run) * 1e3
    lat = sorted((r["t1"] - r["t0"]) * 1e3 if r["ok"] else worst
                 for r in run.requests)
    q = float(args["q"]) / 100.0
    # nearest rank: the smallest value with at least q of the sample at or
    # below it
    rank = max(1, math.ceil(q * len(lat) - 1e-9))
    return lat[rank - 1]
