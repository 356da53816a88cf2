"""A read served from the wrong place: every 64th SQE of a drain gets the
block beside its own (the one before it at the file's end)."""

from ..lib.uring_faults import patch_batch_read_into


def plant(ctx) -> None:
    def shift(files):
        out = []
        for i, (inode, offset, size, dest) in enumerate(files):
            if i % 64 == 0:
                beside = offset + size
                if inode.length and beside + size > inode.length:
                    beside = offset - size
                offset = max(0, beside)
            out.append((i, (inode, offset, size, dest)))
        return out

    patch_batch_read_into(before=shift)
