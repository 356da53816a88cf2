"""The seam the small-I/O cell's faults share: every ring drain's one batched
read (`FileIoClient.batch_read_into`) patched in this process for the
length of the run."""

from __future__ import annotations


def patch_batch_read_into(before=None, after=None) -> None:
    """`before(files)` returns the (index, range) pairs really read, the
    rest are acknowledged at their full size with no byte moved;
    `after(files, state)` sees the filled windows (`state["n"]` counts the
    drains)."""
    from tpu3fs.client.file_io import FileIoClient

    inner = FileIoClient.batch_read_into
    state = {"n": 0}

    def batch_read_into(self, files):
        out = [size for _, _, size, _ in files]
        picked = before(files) if before else list(enumerate(files))
        for (i, _), got in zip(picked, inner(self, [f for _, f in picked])):
            out[i] = got
        state["n"] += 1
        if after:
            after(files, state)
        return out

    FileIoClient.batch_read_into = batch_read_into
