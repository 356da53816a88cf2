"""An answer altered where it lands: after the read, one byte of one slot of
every drain is another byte."""

from ..lib.uring_faults import patch_batch_read_into


def plant(ctx) -> None:
    def alter(files, state):
        dest = files[(7 * state["n"]) % len(files)][3]
        dest[len(dest) // 2] ^= 0x40

    patch_batch_read_into(after=alter)
