"""The whole window over its requests (cycles): first start to last end,
divided by how many were started, each of them finished."""

from .rate import window_seconds


def read(run, args):
    done = [r for r in run.requests if r["ok"]]
    if not done:
        return None
    return window_seconds(run) / len(done)
