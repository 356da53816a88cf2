"""Median over the window's requests of one phase the driver timed on the
host clock around a call that ends in block_until_ready."""

from statistics import median


def read(run, args):
    values = [r["phases"][args["phase"]] for r in run.requests
              if r["ok"] and args["phase"] in r.get("phases", {})]
    if not values:
        return None
    return median(values) * float(args.get("scale", 1))
