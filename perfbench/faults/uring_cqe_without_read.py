"""The control of the small-I/O cell: a completion that moved no byte. Every
second SQE of a drain is completed with its full length and never read: its
Iov slot keeps what the batch before left there."""

from ..lib.uring_faults import patch_batch_read_into


def plant(ctx) -> None:
    patch_batch_read_into(
        before=lambda files: list(enumerate(files))[::2])
