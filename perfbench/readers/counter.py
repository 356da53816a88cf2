"""A number the driver itself counted or read from a child's log and left
in `run.counters` under `args["key"]` (the collector's tick lines). Finds
nothing where the driver left none."""


def read(run, args):
    return run.counters.get(args["key"])
