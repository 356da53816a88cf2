"""Faults planted under the timed path, one module a fault, found by name:
perfbench/faults/<name>.py with `plant(ctx)`. Each patches the PROGRAM in
this process for the length of the run; the comparison that decides
`correct` has to catch every one. The controls (each breaks one guarantee
the configuration states) and the faults the tests drive a run with."""

from __future__ import annotations


def patch_next(fn) -> None:
    """Every batch the loader hands out goes through fn(batch, state)."""
    from tpu3fs.dataload import DataLoader

    inner = DataLoader.__next__
    state = {"n": 0, "prev": None}

    def __next__(self):
        batch = inner(self)
        state["n"] += 1
        out = fn(batch, state)
        state["prev"] = batch
        return out

    DataLoader.__next__ = __next__
