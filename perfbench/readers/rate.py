"""All of a field's units over all of the window's time: the window runs
from the first request's start to the last one's end, so the requests in
flight at the deadline count with the time they took."""


def window_seconds(run) -> float:
    reqs = run.requests
    if not reqs:
        return 0.0
    return max(r["t1"] for r in reqs) - min(r["t0"] for r in reqs)


def read(run, args):
    seconds = window_seconds(run)
    if seconds <= 0:
        return None
    total = sum(r[args["field"]] for r in run.requests if r["ok"])
    return total / float(args.get("scale", 1)) / seconds
